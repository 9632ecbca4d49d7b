"""Fused MoE grouped-GEMM Pallas TPU kernel — the paper's §VII case study.

Computes, for every expert e over its gathered token block x_e (capacity C):

    y_e = (silu(x_e @ w_gate[e]) * (x_e @ w_up[e])) @ w_down[e]

in one kernel: grid (E, C/block_m, F/block_f) with the down-projection
accumulated across the (sequential) F dimension in a VMEM scratch — the TPU
analogue of the SGLang Triton fused-MoE kernel whose BLOCK_SIZE / num_warps /
num_stages the paper autotunes. Here the tunable knobs are (block_m,
block_f); the ``repro.tune`` autotuner derives exactly this space from the
ops signature, pre-filters it through the static SP2xx lint, and measures
the predictor-ranked top-k (benchmarks/bench_perf_gap.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import VMEM_LIMIT_BYTES, resolve_interpret


def _moe_kernel(
    x_ref,  # (1, block_m, D)
    wg_ref,  # (1, D, block_f)
    wu_ref,  # (1, D, block_f)
    wd_ref,  # (1, block_f, D)
    o_ref,  # (1, block_m, D)
    acc_scr,  # (block_m, D) f32
    *,
    n_f: int,
):
    jf = pl.program_id(2)

    @pl.when(jf == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    x = x_ref[0].astype(jnp.float32)
    g = jax.lax.dot_general(
        x, wg_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    u = jax.lax.dot_general(
        x, wu_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    h = jax.nn.silu(g) * u  # (block_m, block_f)
    acc_scr[...] += jax.lax.dot_general(
        h, wd_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(jf == n_f - 1)
    def _emit():
        o_ref[0] = acc_scr[...].astype(o_ref.dtype)


def fused_moe_pallas(
    x,  # (E, C, D) gathered per-expert token blocks
    w_gate,  # (E, D, F)
    w_up,  # (E, D, F)
    w_down,  # (E, F, D)
    *,
    block_m: int = 128,
    block_f: int = 256,
    interpret: bool | None = None,
):
    E, C, D = x.shape
    F = w_gate.shape[2]
    block_m = min(block_m, C)
    block_f = min(block_f, F)
    assert C % block_m == 0 and F % block_f == 0
    n_m, n_f = C // block_m, F // block_f

    kernel = functools.partial(_moe_kernel, n_f=n_f)
    return pl.pallas_call(
        kernel,
        grid=(E, n_m, n_f),
        in_specs=[
            pl.BlockSpec((1, block_m, D), lambda e, im, jf: (e, im, 0)),
            pl.BlockSpec((1, D, block_f), lambda e, im, jf: (e, 0, jf)),
            pl.BlockSpec((1, D, block_f), lambda e, im, jf: (e, 0, jf)),
            pl.BlockSpec((1, block_f, D), lambda e, im, jf: (e, jf, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_m, D), lambda e, im, jf: (e, im, 0)),
        out_shape=jax.ShapeDtypeStruct((E, C, D), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_m, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=resolve_interpret(interpret),
    )(x, w_gate, w_up, w_down)
