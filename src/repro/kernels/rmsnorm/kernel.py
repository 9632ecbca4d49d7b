"""Fused RMSNorm Pallas TPU kernel (VPU + rsqrt transcendental)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import VMEM_LIMIT_BYTES, largest_divisor_block, resolve_interpret


def _rmsnorm_kernel(x_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    o = x * jax.lax.rsqrt(var + eps) * (1.0 + w_ref[...].astype(jnp.float32))
    o_ref[...] = o.astype(o_ref.dtype)


def rmsnorm_pallas(
    x, w, *, eps: float = 1e-6, block_rows: int = 256, interpret: bool | None = None
):
    orig_shape = x.shape
    d = x.shape[-1]
    xf = x.reshape(-1, d)
    R = xf.shape[0]
    block_rows = largest_divisor_block(R, block_rows)
    out = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=(R // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",), vmem_limit_bytes=VMEM_LIMIT_BYTES
        ),
        interpret=resolve_interpret(interpret),
    )(xf, w)
    return out.reshape(orig_shape)
