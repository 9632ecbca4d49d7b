from functools import partial

import jax

from repro.kernels import largest_divisor_block
from repro.kernels.rmsnorm.kernel import rmsnorm_pallas
from repro.kernels.rmsnorm.ref import rmsnorm_ref


def grid_shape(R: int, d: int, *, block_rows: int = 256) -> tuple:
    """Static ``pallas_call`` grid of :func:`rmsnorm` over ``R`` flattened
    rows: ``(R/block,)`` after largest-divisor clamping (never ragged)."""
    return (R // largest_divisor_block(R, block_rows),)


def vmem_footprint(R: int, d: int, *, block_rows: int = 256, dtype_bytes: int = 2) -> int:
    """Peak VMEM bytes one grid step of :func:`rmsnorm` holds resident:
    double-buffered ``x (rows, d)`` / ``w (d,)`` / ``out (rows, d)``
    blocks (no scratch)."""
    rows = largest_divisor_block(R, block_rows)
    return 2 * (rows * d + d + rows * d) * dtype_bytes


@partial(jax.jit, static_argnames=("eps", "block_rows", "interpret", "use_pallas"))
def rmsnorm(x, w, *, eps=1e-6, block_rows=256, interpret=None, use_pallas=True):
    if not use_pallas:
        return rmsnorm_ref(x, w, eps)
    return rmsnorm_pallas(x, w, eps=eps, block_rows=block_rows, interpret=interpret)
