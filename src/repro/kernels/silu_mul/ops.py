from functools import partial

import jax

from repro.kernels import largest_divisor_block
from repro.kernels.silu_mul.kernel import silu_mul_pallas
from repro.kernels.silu_mul.ref import silu_mul_ref


def grid_shape(R: int, d: int, *, block_rows: int = 128) -> tuple:
    """Static ``pallas_call`` grid of :func:`act_mul` over ``R`` flattened
    rows: ``(R/block,)`` after largest-divisor clamping (never ragged)."""
    return (R // largest_divisor_block(R, block_rows),)


def vmem_footprint(R: int, d: int, *, block_rows: int = 128, dtype_bytes: int = 2) -> int:
    """Peak VMEM bytes one grid step of :func:`act_mul` holds resident:
    double-buffered ``g``/``u``/``out`` blocks of ``(rows, d)`` each (no
    scratch)."""
    rows = largest_divisor_block(R, block_rows)
    return 2 * (3 * rows * d) * dtype_bytes


@partial(jax.jit, static_argnames=("act", "block_rows", "interpret", "use_pallas"))
def act_mul(g, u, *, act="silu", block_rows=128, interpret=None, use_pallas=True):
    if not use_pallas:
        return silu_mul_ref(g, u, act=act)
    return silu_mul_pallas(g, u, act=act, block_rows=block_rows, interpret=interpret)
