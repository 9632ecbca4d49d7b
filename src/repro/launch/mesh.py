"""Mesh construction.

Defined as functions (not module-level constants) so importing this module
never touches jax device state. :func:`make_mesh` is the one constructor:
every mesh in the repo is built through it."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, *, devices=None):
    """A mesh of ``shape`` over ``axes`` with every axis ``AxisType.Auto``,
    so shardings propagate through ``jnp`` ops as the models' ``constrain``
    hints expect (``jax.make_mesh`` alone defaults to Explicit axes, under
    which a sharded gather must name its output sharding). ``devices``
    defaults to ``jax.devices()``."""
    axes = tuple(axes)
    return jax.make_mesh(
        tuple(shape), axes, axis_types=(AxisType.Auto,) * len(axes), devices=devices
    )


def make_production_mesh(*, multi_pod: bool = False, pipeline: bool = False):
    """The production meshes the dry-run lowers against.

    Default: one pod as 16 data x 16 model; ``multi_pod`` stacks a leading
    2-pod axis. ``pipeline`` carves a 4-way ``pipe`` axis out of the pod
    (4 stages x 8 data x 8 model — same 256 chips): the axis
    ``dist.pipeline.pipeline_forward`` schedules over and
    ``dist.sharding`` resolves the ``"pipe"`` role onto. Combined with
    ``multi_pod`` this is the 512-chip 2 x 4 x 8 x 8 mesh."""
    if pipeline:
        shape = (2, 4, 8, 8) if multi_pod else (4, 8, 8)
        axes = (("pod",) if multi_pod else ()) + ("pipe", "data", "model")
    else:
        shape = (2, 16, 16) if multi_pod else (16, 16)
        axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def mesh_tag(*, multi_pod: bool = False, pipeline: bool = False) -> str:
    """Short mesh label used in dry-run artifact names/metadata."""
    if pipeline:
        return "2x4x8x8pp" if multi_pod else "4x8x8pp"
    return "2x16x16" if multi_pod else "16x16"
