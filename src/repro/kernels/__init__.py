"""Pallas TPU kernels: one package per kernel family, each with the
``pallas_call`` (``kernel.py``), its jit'd entry point with static
grid/VMEM helpers (``ops.py``) and a pure-jnp oracle (``ref.py``)."""
from __future__ import annotations

from typing import Optional

#: Scoped-VMEM limit every kernel requests from the Mosaic compiler
#: (``vmem_limit_bytes``). Without it the compiler enforces its own default
#: (16 MiB on v5e), which refuses dbrx-width ``fused_moe`` and
#: d_ff=22016 ``silu_mul``. 64 MiB is the smallest VMEM in the hardware
#: registry and half of a v5e's 128 MiB. The static SP201 lint
#: (``repro.analysis.kernels``) budgets exactly this limit.
VMEM_LIMIT_BYTES = 64 * 2**20


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """The ``interpret`` mode a kernel runs in: compiled on a TPU backend,
    interpreted everywhere else; an explicit bool always wins."""
    if interpret is not None:
        return interpret
    import jax

    return jax.default_backend() != "tpu"


def largest_divisor_block(total: int, block: int) -> int:
    """Largest divisor of ``total`` that is ``<= block`` (and >= 1).

    The block-clamping rule shared by the scaled_mm / rmsnorm / silu_mul
    kernels and their static ``grid_shape``/``vmem_footprint`` helpers:
    these kernels never launch a ragged grid — they shrink the block until
    it divides the dimension. (flash_attention and fused_moe instead
    *assert* divisibility after a plain ``min`` clamp; their helpers raise
    ``ValueError`` where the kernel would assert.)"""
    block = min(block, total)
    return next(b for b in range(block, 0, -1) if total % b == 0)
