"""FlashAttention-2-style Pallas TPU kernel.

TPU adaptation of the paper's Attention kernel family (Table V): online
softmax over KV blocks with VMEM accumulators. The grid's last dimension
(KV blocks) is sequential on a TensorCore, so the running (m, l, acc) state
lives in VMEM scratch across grid steps — the TPU analogue of FA2's
per-CTA streaming loop. Causal and sliding-window masking skip fully-masked
KV blocks via pl.when (the tile-level workload variance the paper's
Scheduling Simulator models).

Layouts: q is passed as (BKG, S, D) where BKG = batch * kv_heads * group
(GQA flattened); k/v as (BK, Skv, D). Block sizes (block_q, block_k) are the
kernel's autotuning knobs.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import VMEM_LIMIT_BYTES, resolve_interpret

NEG_INF = -1.0e30


def _fa_kernel(
    q_ref,  # (1, block_q, D)
    k_ref,  # (1, block_k, D)
    v_ref,  # (1, block_k, D)
    o_ref,  # (1, block_q, D)
    m_scr,  # (block_q, 1) f32
    l_scr,  # (block_q, 1) f32
    acc_scr,  # (block_q, D) f32
    *,
    scale: float,
    causal: bool,
    window: int | None,
    softcap: float | None,
    block_q: int,
    block_k: int,
    n_k: int,
):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = iq * block_q
    k_start = ik * block_k

    # skip KV blocks that are entirely masked out (causal upper triangle /
    # outside the sliding window) — tile-level work skipping, FA2-style
    run = True
    if causal:
        run = k_start <= q_start + block_q - 1
    if window is not None:
        run = jnp.logical_and(run, k_start + block_k - 1 > q_start - window)

    @pl.when(run)
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        s = s * scale
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = jnp.ones((block_q, block_k), jnp.bool_)
        if causal:
            mask = jnp.logical_and(mask, kpos <= qpos)
        if window is not None:
            mask = jnp.logical_and(mask, kpos > qpos - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = corr * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0].astype(jnp.float32)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        acc_scr[...] = corr * acc_scr[...] + pv
        m_scr[...] = m_new

    @pl.when(ik == n_k - 1)
    def _emit():
        l = l_scr[...]
        o = acc_scr[...] / jnp.maximum(l, 1e-30)
        o_ref[0] = o.astype(o_ref.dtype)


def flash_attention_pallas(
    q,  # (BKG, S, D)
    k,  # (BK, Skv, D)
    v,
    *,
    group: int,  # q rows per kv head (BKG = BK * group)
    causal: bool = True,
    window: int | None = None,
    softcap: float | None = None,
    scale: float | None = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool | None = None,
):
    BKG, S, D = q.shape
    BK, Skv, _ = k.shape
    assert BKG == BK * group
    block_q = min(block_q, S)
    block_k = min(block_k, Skv)
    assert S % block_q == 0 and Skv % block_k == 0
    n_q, n_k = S // block_q, Skv // block_k
    scale = scale if scale is not None else 1.0 / math.sqrt(D)

    kernel = functools.partial(
        _fa_kernel,
        scale=scale,
        causal=causal,
        window=window,
        softcap=softcap,
        block_q=block_q,
        block_k=block_k,
        n_k=n_k,
    )
    return pl.pallas_call(
        kernel,
        grid=(BKG, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, iq, ik: (b, iq, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, iq, ik, g=group: (b // g, ik, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, iq, ik, g=group: (b // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda b, iq, ik: (b, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((BKG, S, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=resolve_interpret(interpret),
    )(q, k, v)
