"""Fused SiLU&Mul (SwiGLU gate) Pallas TPU kernel — elementwise VPU + EX2."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import VMEM_LIMIT_BYTES, largest_divisor_block, resolve_interpret


def _silu_mul_kernel(g_ref, u_ref, o_ref, *, act: str):
    g = g_ref[...].astype(jnp.float32)
    u = u_ref[...].astype(jnp.float32)
    if act == "geglu":
        h = jax.nn.gelu(g, approximate=True)
    else:
        h = jax.nn.silu(g)
    o_ref[...] = (h * u).astype(o_ref.dtype)


def silu_mul_pallas(
    g, u, *, act: str = "silu", block_rows: int = 128, interpret: bool | None = None
):
    orig_shape = g.shape
    d = g.shape[-1]
    gf, uf = g.reshape(-1, d), u.reshape(-1, d)
    R = gf.shape[0]
    block_rows = largest_divisor_block(R, block_rows)
    out = pl.pallas_call(
        functools.partial(_silu_mul_kernel, act=act),
        grid=(R // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, d), g.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",), vmem_limit_bytes=VMEM_LIMIT_BYTES
        ),
        interpret=resolve_interpret(interpret),
    )(gf, uf)
    return out.reshape(orig_shape)
