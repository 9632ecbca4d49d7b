"""Direct unit tests pinning each kernel's static ``grid_shape`` /
``vmem_footprint`` helpers to the ``pallas_call`` BlockSpecs they mirror
(satellite of the static-auditor PR): footprints are recomputed here from
the BlockSpec block shapes by hand, so a kernel BlockSpec edit that
forgets the helper fails loudly."""
import pytest

from repro.kernels import largest_divisor_block
from repro.kernels.flash_attention import ops as flash_ops
from repro.kernels.fused_moe import ops as moe_ops
from repro.kernels.rmsnorm import ops as rms_ops
from repro.kernels.scaled_mm import ops as mm_ops
from repro.kernels.silu_mul import ops as silu_ops


def test_largest_divisor_block():
    assert largest_divisor_block(1024, 256) == 256
    assert largest_divisor_block(100, 256) == 100  # clamp to total
    assert largest_divisor_block(100, 64) == 50  # largest divisor <= 64
    assert largest_divisor_block(7, 4) == 1  # prime: falls to 1


# ---------------------------------------------------------------------------
# flash_attention: BlockSpecs (1,bq,D) q/out, (1,bk,D) k/v;
# scratch (bq,1) f32 x2 + (bq,D) f32


@pytest.mark.parametrize("S,Skv,bq,bk", [(512, 512, 128, 128), (64, 512, 128, 128), (1, 384, 128, 128)])
def test_flash_static_helpers(S, Skv, bq, bk):
    B, Hq, Hkv, D = 2, 8, 2, 64
    ebq, ebk = min(bq, S), min(bk, Skv)
    grid = flash_ops.grid_shape(B, S, Skv, Hq, Hkv, D, block_q=bq, block_k=bk)
    assert grid == (B * Hkv * (Hq // Hkv), S // ebq, Skv // ebk)
    fp = flash_ops.vmem_footprint(B, S, Skv, Hq, Hkv, D, block_q=bq, block_k=bk, dtype_bytes=2)
    blocks = (ebq * D + ebk * D + ebk * D + ebq * D) * 2  # q + k + v + out
    scratch = (ebq * 1 + ebq * 1 + ebq * D) * 4  # m, l, acc (f32)
    assert fp == 2 * blocks + scratch


def test_flash_grid_raises_where_kernel_asserts():
    with pytest.raises(ValueError):
        flash_ops.grid_shape(1, 192, 192, 4, 4, 64)  # 192 % min(128,192) != 0
    # the clamp path: S < block never raises on its own
    assert flash_ops.grid_shape(1, 64, 64, 4, 4, 64)[1:] == (1, 1)


# ---------------------------------------------------------------------------
# fused_moe: BlockSpecs x (1,bm,D), w_gate/w_up (1,D,bf), w_down (1,bf,D),
# out (1,bm,D); scratch (bm,D) f32


@pytest.mark.parametrize("C,F,bm,bf", [(256, 1024, 128, 256), (64, 128, 128, 256)])
def test_moe_static_helpers(C, F, bm, bf):
    E, D = 8, 512
    ebm, ebf = min(bm, C), min(bf, F)
    assert moe_ops.grid_shape(E, C, D, F, block_m=bm, block_f=bf) == (E, C // ebm, F // ebf)
    fp = moe_ops.vmem_footprint(E, C, D, F, block_m=bm, block_f=bf, dtype_bytes=2)
    blocks = (ebm * D + D * ebf + D * ebf + ebf * D + ebm * D) * 2
    assert fp == 2 * blocks + ebm * D * 4


def test_moe_grid_raises_on_ragged_capacity():
    with pytest.raises(ValueError):
        moe_ops.grid_shape(8, 192, 512, 1024)  # C=192 % 128 != 0


# ---------------------------------------------------------------------------
# scaled_mm: int8 x (bm,bk) / w (bk,bn), f32 scales (bm,1)/(1,bn),
# out (bm,bn); scratch (bm,bn) int32 — largest-divisor clamp, never raises


@pytest.mark.parametrize("M,K,N", [(1024, 512, 2048), (100, 96, 60)])
def test_scaled_mm_static_helpers(M, K, N):
    bm = largest_divisor_block(M, 128)
    bn = largest_divisor_block(N, 128)
    bk = largest_divisor_block(K, 256)
    assert mm_ops.grid_shape(M, K, N) == (M // bm, N // bn, K // bk)
    fp = mm_ops.vmem_footprint(M, K, N, out_dtype_bytes=2)
    blocks = bm * bk + bk * bn + (bm * 1 + 1 * bn) * 4 + bm * bn * 2
    assert fp == 2 * blocks + bm * bn * 4


# ---------------------------------------------------------------------------
# rmsnorm / silu_mul: full-width row blocks


def test_rmsnorm_static_helpers():
    R, d = 1024, 2048
    rows = largest_divisor_block(R, 256)
    assert rms_ops.grid_shape(R, d) == (R // rows,)
    assert rms_ops.vmem_footprint(R, d, dtype_bytes=2) == 2 * (rows * d + d + rows * d) * 2


def test_silu_mul_static_helpers():
    R, d = 1024, 2048
    rows = largest_divisor_block(R, 128)  # default block_rows is 128
    assert silu_ops.grid_shape(R, d) == (R // rows,)
    assert silu_ops.vmem_footprint(R, d, dtype_bytes=2) == 2 * (3 * rows * d) * 2


def test_silu_mul_default_fits_smallest_vmem_for_largest_dff():
    """The auditor-motivated default: deepseek's d_ff=22016 must fit the
    smallest scoped-VMEM budget in the registry (the original 256-row
    default was 64.5 MiB)."""
    from repro.analysis.kernels import vmem_budget
    from repro.core.hardware import REGISTRY

    min_vmem = min(vmem_budget(hw) for hw in REGISTRY.values())
    assert silu_ops.vmem_footprint(1024, 22016, dtype_bytes=2) <= min_vmem
    assert silu_ops.vmem_footprint(1024, 22016, block_rows=256, dtype_bytes=2) > min_vmem


# ---------------------------------------------------------------------------
# helpers agree with a real launch (grid arithmetic exercised end-to-end)


def test_helpers_match_executed_kernel_shapes():
    import jax
    import numpy as np

    B, S, Hq, Hkv, D = 1, 128, 4, 2, 64
    q = jax.random.normal(jax.random.PRNGKey(0), (B, S, Hq, D), "bfloat16")
    k = jax.random.normal(jax.random.PRNGKey(1), (B, S, Hkv, D), "bfloat16")
    v = jax.random.normal(jax.random.PRNGKey(2), (B, S, Hkv, D), "bfloat16")
    out = flash_ops.attention(q, k, v)
    assert out.shape == (B, S, Hq, D)
    grid = flash_ops.grid_shape(B, S, S, Hq, Hkv, D)
    assert grid == (B * Hkv * (Hq // Hkv), 1, 1)
    assert np.isfinite(np.asarray(out, dtype=np.float32)).all()
