"""jit'd wrapper: model-layout (B, S, H, D) GQA attention dispatching to the
Pallas kernel (TPU) or the jnp reference (CPU / dry-run tracing)."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.flash_attention.ref import flash_attention_ref


def grid_shape(
    B: int, S: int, Skv: int, Hq: int, Hkv: int, D: int,
    *, block_q: int = 128, block_k: int = 128,
) -> tuple:
    """Static ``pallas_call`` grid of :func:`attention`: ``(BKG, n_q, n_k)``
    where ``BKG = B * Hkv * (Hq // Hkv)``. Raises ``ValueError`` exactly
    where the kernel would fail its divisibility assert (after the
    ``min(block, dim)`` clamp) — the contract ``repro.analysis`` lints
    before any compile."""
    bq, bk = min(block_q, S), min(block_k, Skv)
    if S % bq or Skv % bk:
        raise ValueError(
            f"flash_attention: S={S} %% block_q={bq} or Skv={Skv} %% "
            f"block_k={bk} != 0 (non-divisible tiling)"
        )
    return (B * Hkv * (Hq // Hkv), S // bq, Skv // bk)


def vmem_footprint(
    B: int, S: int, Skv: int, Hq: int, Hkv: int, D: int,
    *, block_q: int = 128, block_k: int = 128, dtype_bytes: int = 2,
) -> int:
    """Peak VMEM bytes one grid step of :func:`attention` holds resident:
    the double-buffered in/out BlockSpec blocks (Mosaic pipelines the next
    tile's DMA while computing, so every block is resident twice) plus the
    f32 scratch accumulators ``(block_q, 1) x2 + (block_q, D)``. Mirrors
    the kernel's BlockSpecs exactly; pinned by ``tests/test_analysis.py``."""
    bq, bk = min(block_q, S), min(block_k, Skv)
    blocks = (bq * D + 2 * bk * D + bq * D) * dtype_bytes  # q, k, v, out
    scratch = (bq * 1 + bq * 1 + bq * D) * 4
    return 2 * blocks + scratch


@partial(
    jax.jit,
    static_argnames=(
        "causal", "window", "softcap", "block_q", "block_k", "interpret", "use_pallas",
    ),
)
def attention(
    q,  # (B, S, Hq, D)
    k,  # (B, Skv, Hkv, D)
    v,
    *,
    causal: bool = True,
    window: int | None = None,
    softcap: float | None = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool | None = None,
    use_pallas: bool = True,
):
    B, S, Hq, D = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    qf = (
        q.reshape(B, S, Hkv, G, D)
        .transpose(0, 2, 3, 1, 4)
        .reshape(B * Hkv * G, S, D)
    )
    kf = k.transpose(0, 2, 1, 3).reshape(B * Hkv, -1, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * Hkv, -1, D)
    fn = flash_attention_pallas if use_pallas else flash_attention_ref
    kw = dict(group=G, causal=causal, window=window, softcap=softcap)
    if use_pallas:
        kw.update(block_q=block_q, block_k=block_k, interpret=interpret)
    of = fn(qf, kf, vf, **kw)
    return (
        of.reshape(B, Hkv, G, S, D).transpose(0, 3, 1, 2, 4).reshape(B, S, Hq, D)
    )
