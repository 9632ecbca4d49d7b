"""Scaled Matrix Multiplication (W8A8) Pallas TPU kernel — the paper's
Scaled MM family (Table V): int8 activations x int8 weights with int32 MXU
accumulation and a per-row/per-column fp32 scale dequant epilogue.

Grid (M/bm, N/bn, K/bk) with the K dimension sequential and an int32 VMEM
accumulator; (block_m, block_n, block_k) are the tuning knobs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import VMEM_LIMIT_BYTES, largest_divisor_block, resolve_interpret


def _scaled_mm_kernel(
    x_ref,  # (bm, bk) int8
    w_ref,  # (bk, bn) int8
    sx_ref,  # (bm, 1) f32 per-row activation scale
    sw_ref,  # (1, bn) f32 per-col weight scale
    o_ref,  # (bm, bn) out dtype
    acc_scr,  # (bm, bn) int32
    *,
    n_k: int,
):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # int8 operands straight into the MXU with int32 accumulation (Mosaic
    # has no int32 x int32 matmul); the products are exact either way
    acc_scr[...] += jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )

    @pl.when(ik == n_k - 1)
    def _emit():
        deq = acc_scr[...].astype(jnp.float32) * sx_ref[...] * sw_ref[...]
        o_ref[...] = deq.astype(o_ref.dtype)


def scaled_mm_pallas(
    x,  # (M, K) int8
    w,  # (K, N) int8
    sx,  # (M,) f32
    sw,  # (N,) f32
    *,
    out_dtype=jnp.bfloat16,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 256,
    interpret: bool | None = None,
):
    M, K = x.shape
    N = w.shape[1]
    block_m = largest_divisor_block(M, block_m)
    block_n = largest_divisor_block(N, block_n)
    block_k = largest_divisor_block(K, block_k)
    n_k = K // block_k
    return pl.pallas_call(
        functools.partial(_scaled_mm_kernel, n_k=n_k),
        grid=(M // block_m, N // block_n, n_k),
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((block_k, block_n), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((block_m, 1), lambda i, j, kk: (i, 0)),
            pl.BlockSpec((1, block_n), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=resolve_interpret(interpret),
    )(x, w, sx[:, None].astype(jnp.float32), sw[None, :].astype(jnp.float32))
