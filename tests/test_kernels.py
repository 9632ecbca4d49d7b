"""Per-kernel validation: Pallas (interpret=True, the CPU-executable path of
the TPU kernels) vs pure-jnp oracles, swept over shapes/dtypes/block sizes."""
import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import resolve_interpret
from repro.kernels.flash_attention import kernel as fa_kernel
from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.fused_moe import kernel as moe_kernel
from repro.kernels.fused_moe import ops as moe_ops
from repro.kernels.fused_moe.ref import fused_moe_ref
from repro.kernels.rmsnorm import kernel as rms_kernel
from repro.kernels.rmsnorm import ops as rms_ops
from repro.kernels.rmsnorm.ref import rmsnorm_ref
from repro.kernels.scaled_mm import kernel as mm_kernel
from repro.kernels.scaled_mm import ops as mm_ops
from repro.kernels.silu_mul import kernel as silu_kernel
from repro.kernels.silu_mul import ops as silu_ops
from repro.kernels.silu_mul.ref import silu_mul_ref


def test_resolve_interpret_follows_the_backend():
    assert resolve_interpret(None) is (jax.default_backend() != "tpu")
    assert resolve_interpret(True) is True and resolve_interpret(False) is False


@pytest.mark.parametrize("fn", [
    fa_ops.attention, moe_ops.fused_moe, rms_ops.rmsnorm, mm_ops.scaled_mm, silu_ops.act_mul,
    fa_kernel.flash_attention_pallas, moe_kernel.fused_moe_pallas, rms_kernel.rmsnorm_pallas,
    mm_kernel.scaled_mm_pallas, silu_kernel.silu_mul_pallas,
], ids=lambda f: f.__name__)
def test_entry_points_default_to_the_backend_mode(fn):
    """No kernel entry point pins interpret mode: ``None`` resolves to
    compiled on a TPU and interpreted elsewhere."""
    assert inspect.signature(fn).parameters["interpret"].default is None


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(rtol=2e-5, atol=2e-5)


# ----------------------------------------------------------------------
# flash attention
# ----------------------------------------------------------------------

FA_CASES = [
    # (B, S, Skv, Hq, Hkv, D, causal, window, softcap)
    (1, 64, 64, 2, 2, 16, True, None, None),
    (2, 128, 128, 4, 2, 32, True, None, None),
    (1, 64, 64, 2, 1, 16, True, 32, None),  # sliding window
    (1, 64, 64, 2, 2, 16, True, None, 30.0),  # softcap (gemma2)
    (2, 64, 64, 4, 4, 16, False, None, None),  # bidirectional (whisper enc)
    (1, 32, 128, 2, 2, 16, False, None, None),  # cross-attn shape
]


@pytest.mark.parametrize("case", FA_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_ref(case, dtype):
    B, S, Skv, Hq, Hkv, D, causal, window, softcap = case
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, Hq, D)).astype(dtype)
    k = jax.random.normal(ks[1], (B, Skv, Hkv, D)).astype(dtype)
    v = jax.random.normal(ks[2], (B, Skv, Hkv, D)).astype(dtype)
    out_k = fa_ops.attention(
        q, k, v, causal=causal, window=window, softcap=softcap,
        block_q=32, block_k=32, interpret=True, use_pallas=True,
    )
    out_r = fa_ops.attention(
        q, k, v, causal=causal, window=window, softcap=softcap, use_pallas=False
    )
    np.testing.assert_allclose(
        np.asarray(out_k, np.float32), np.asarray(out_r, np.float32), **_tol(dtype)
    )


@pytest.mark.parametrize("block", [(16, 16), (32, 64), (64, 32)])
def test_flash_attention_block_size_sweep(block):
    bq, bk = block
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (2, 64, 4, 16))
    k = jax.random.normal(ks[1], (2, 64, 2, 16))
    v = jax.random.normal(ks[2], (2, 64, 2, 16))
    out_k = fa_ops.attention(q, k, v, causal=True, block_q=bq, block_k=bk)
    out_r = fa_ops.attention(q, k, v, causal=True, use_pallas=False)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r), rtol=2e-5, atol=2e-5)


def test_flash_attention_matches_model_attention():
    """The kernel agrees with the model stack's chunked_attention."""
    from repro.models.layers import chunked_attention

    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    B, S, Hq, Hkv, D = 2, 64, 4, 2, 16
    q = jax.random.normal(ks[0], (B, S, Hq, D))
    k = jax.random.normal(ks[1], (B, S, Hkv, D))
    v = jax.random.normal(ks[2], (B, S, Hkv, D))
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    out_model = chunked_attention(q, k, v, pos, pos, causal=True, q_block=16)
    out_kernel = fa_ops.attention(q, k, v, causal=True, block_q=16, block_k=16)
    np.testing.assert_allclose(
        np.asarray(out_model), np.asarray(out_kernel), rtol=1e-4, atol=1e-4
    )


# ----------------------------------------------------------------------
# fused MoE
# ----------------------------------------------------------------------

MOE_CASES = [
    # (E, C, D, F, block_m, block_f)
    (4, 32, 64, 128, 16, 64),
    (2, 64, 32, 64, 32, 32),
    (8, 16, 48, 96, 16, 96),
]


@pytest.mark.parametrize("case", MOE_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_moe_matches_ref(case, dtype):
    E, C, D, F, bm, bf = case
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    x = (0.5 * jax.random.normal(ks[0], (E, C, D))).astype(dtype)
    wg = (0.1 * jax.random.normal(ks[1], (E, D, F))).astype(dtype)
    wu = (0.1 * jax.random.normal(ks[2], (E, D, F))).astype(dtype)
    wd = (0.1 * jax.random.normal(ks[3], (E, F, D))).astype(dtype)
    out_k = moe_ops.fused_moe(x, wg, wu, wd, block_m=bm, block_f=bf)
    out_r = fused_moe_ref(x, wg, wu, wd)
    np.testing.assert_allclose(
        np.asarray(out_k, np.float32), np.asarray(out_r, np.float32), **_tol(dtype)
    )


# ----------------------------------------------------------------------
# rmsnorm / silu&mul
# ----------------------------------------------------------------------


@pytest.mark.parametrize("shape", [(4, 32, 64), (2, 7, 48), (128, 16)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_matches_ref(shape, dtype):
    k1, k2 = jax.random.split(jax.random.PRNGKey(4))
    x = jax.random.normal(k1, shape).astype(dtype)
    w = (0.1 * jax.random.normal(k2, shape[-1:])).astype(dtype)
    out_k = rms_ops.rmsnorm(x, w, block_rows=8)
    out_r = rmsnorm_ref(x, w)
    np.testing.assert_allclose(
        np.asarray(out_k, np.float32), np.asarray(out_r, np.float32), **_tol(dtype)
    )


@pytest.mark.parametrize("act", ["silu", "geglu"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_silu_mul_matches_ref(act, dtype):
    k1, k2 = jax.random.split(jax.random.PRNGKey(5))
    g = jax.random.normal(k1, (4, 32, 64)).astype(dtype)
    u = jax.random.normal(k2, (4, 32, 64)).astype(dtype)
    out_k = silu_ops.act_mul(g, u, act=act, block_rows=16)
    out_r = silu_mul_ref(g, u, act=act)
    np.testing.assert_allclose(
        np.asarray(out_k, np.float32), np.asarray(out_r, np.float32), **_tol(dtype)
    )


# ----------------------------------------------------------------------
# property-based: flash attention invariants
# ----------------------------------------------------------------------

from hypothesis import given, settings, strategies as st


@settings(deadline=None, max_examples=10)
@given(
    s=st.sampled_from([32, 64]),
    h=st.sampled_from([1, 2]),
    d=st.sampled_from([8, 16]),
    seed=st.integers(0, 2**31 - 1),
)
def test_flash_attention_convex_combination(s, h, d, seed):
    """Attention output rows are convex combinations of V rows: the output
    must lie inside [min(V), max(V)] per feature."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (1, s, h, d))
    k = jax.random.normal(ks[1], (1, s, h, d))
    v = jax.random.normal(ks[2], (1, s, h, d))
    out = fa_ops.attention(q, k, v, causal=True, block_q=16, block_k=16)
    vmin = np.asarray(v.min())
    vmax = np.asarray(v.max())
    o = np.asarray(out)
    assert o.min() >= vmin - 1e-3 and o.max() <= vmax + 1e-3


# ----------------------------------------------------------------------
# scaled_mm (W8A8)
# ----------------------------------------------------------------------

from repro.kernels.scaled_mm import ops as smm_ops
from repro.kernels.scaled_mm.ref import quantize_rowwise, scaled_mm_ref


@pytest.mark.parametrize("shape", [(64, 128, 96), (128, 64, 128)])
@pytest.mark.parametrize("blocks", [(32, 32, 64), (64, 64, 32)])
def test_scaled_mm_matches_ref(shape, blocks):
    M, K, N = shape
    bm, bn, bk = blocks
    k1, k2 = jax.random.split(jax.random.PRNGKey(7))
    x, sx = quantize_rowwise(jax.random.normal(k1, (M, K)))
    wq, sw = quantize_rowwise(jax.random.normal(k2, (N, K)))
    w = wq.T  # (K, N) with per-col scales sw
    out_k = smm_ops.scaled_mm(x, w, sx, sw, block_m=bm, block_n=bn, block_k=bk)
    out_r = scaled_mm_ref(x, w, sx, sw)
    np.testing.assert_allclose(
        np.asarray(out_k, np.float32), np.asarray(out_r, np.float32),
        rtol=1e-2, atol=1e-2,
    )


def test_scaled_mm_quantized_approximates_fp():
    """End-to-end W8A8 ~ fp32 matmul within quantization error."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(8))
    a = jax.random.normal(k1, (64, 128))
    b = jax.random.normal(k2, (96, 128))
    x, sx = quantize_rowwise(a)
    wq, sw = quantize_rowwise(b)
    out = smm_ops.scaled_mm(x, wq.T, sx, sw, block_m=32, block_n=32, block_k=64)
    ref = a @ b.T
    rel = np.abs(np.asarray(out, np.float32) - np.asarray(ref)) / (np.abs(np.asarray(ref)) + 1e-2)
    assert np.median(rel) < 0.05
