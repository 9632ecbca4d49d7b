"""Every Pallas kernel compiles for a TPU v5e at real model widths, and the
serving decode step updates its stacked KV cache in place there.

The TPU compiler is installed with JAX and compiles for a chip that is
described, not attached, so these tests need no accelerator. The topology is
described inside a module fixture (never at import), and everything built
from it is built in fixtures or tests, so every pytest worker collects the
same tests and only the worker that runs this file loads the TPU library.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.analysis.kernels import check_blocks
from repro.configs import get_arch
from repro.core.hardware import get_hw
from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.fused_moe import ops as moe_ops
from repro.kernels.rmsnorm import ops as rms_ops
from repro.kernels.scaled_mm import ops as mm_ops
from repro.kernels.silu_mul import ops as silu_ops
from repro.models.registry import build_model

QWEN = get_arch("qwen3-0.6b")
DBRX = get_arch("dbrx-132b")
DEEPSEEK = get_arch("deepseek-67b")
ROWS = 4096  # flattened tokens of a prefill step


@pytest.fixture(scope="module")
def one_chip():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else the compiler logs under /tmp
        from jax.experimental import topologies

        try:
            topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure means no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])


def _compile(fn, shapes, sharding, **kw):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    return jax.jit(lambda *a: fn(*a, interpret=False, **kw)).lower(*args).compile()


def _cases():
    bf16, i8, f32 = jnp.bfloat16, jnp.int8, jnp.float32
    S, hd = 2048, QWEN.resolved_head_dim
    D, F = DBRX.d_model, DBRX.moe_hidden
    return {
        "flash_attention-qwen3": (fa_ops.attention, [
            ((1, S, QWEN.n_heads, hd), bf16),
            ((1, S, QWEN.n_kv_heads, hd), bf16),
            ((1, S, QWEN.n_kv_heads, hd), bf16),
        ]),
        "rmsnorm-qwen3": (rms_ops.rmsnorm, [((ROWS, QWEN.d_model), bf16),
                                            ((QWEN.d_model,), bf16)]),
        "silu_mul-qwen3": (silu_ops.act_mul, [((ROWS, QWEN.d_ff), bf16)] * 2),
        "silu_mul-deepseek": (silu_ops.act_mul, [((ROWS, DEEPSEEK.d_ff), bf16)] * 2),
        "fused_moe-dbrx": (moe_ops.fused_moe, [
            ((DBRX.n_experts, 256, D), bf16),
            ((DBRX.n_experts, D, F), bf16),
            ((DBRX.n_experts, D, F), bf16),
            ((DBRX.n_experts, F, D), bf16),
        ]),
        "scaled_mm-qwen3": (mm_ops.scaled_mm, [
            ((ROWS, QWEN.d_model), i8), ((QWEN.d_model, QWEN.d_ff), i8),
            ((ROWS,), f32), ((QWEN.d_ff,), f32),
        ]),
    }


@pytest.mark.parametrize("case", sorted(_cases()))
def test_kernel_compiles_for_v5e(case, one_chip):
    fn, shapes = _cases()[case]
    compiled = _compile(fn, shapes, one_chip)
    assert "tpu_custom_call" in compiled.as_text()


def test_sp201_agrees_with_the_compiler(one_chip):
    """At d_ff=22016 a 256-row silu_mul block double-buffers 64.5 MiB: the
    static lint flags it and the compiler refuses it; the 128-row default
    passes both."""
    kw = {"R": ROWS, "d": DEEPSEEK.d_ff}
    shapes = [((ROWS, DEEPSEEK.d_ff), jnp.bfloat16)] * 2
    v5e = [get_hw("tpu-v5e")]
    assert [d.code for d in check_blocks("silu_mul", kw, {"block_rows": 256}, hws=v5e)] \
        == ["SP201"]
    with pytest.raises(Exception, match="vmem"):
        _compile(silu_ops.act_mul, shapes, one_chip, block_rows=256)
    assert check_blocks("silu_mul", kw, {"block_rows": 128}, hws=v5e) == []
    _compile(silu_ops.act_mul, shapes, one_chip, block_rows=128)



@pytest.mark.parametrize("arch,slots,max_len", [
    ("qwen3-0.6b", 12, 4096),  # head dim of whole lanes: one scatter of the rows
    ("stablelm-3b", 3, 3200),  # head dim 80: the sequence axis is minor on the chip
])
def test_decode_updates_the_stacked_cache_in_place_on_v5e(arch, slots, max_len, one_chip):
    """The decode step as the serving engine jits it, bf16 at full width:
    the donated caches are updated in place, so the program needs no
    scratch of the cache's size and never copies the stacked cache."""
    api = build_model(dataclasses.replace(get_arch(arch), param_dtype="bfloat16"))

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
                            tree)

    params = on_chip(jax.eval_shape(api.init, jax.random.PRNGKey(0)))
    caches = on_chip(jax.eval_shape(lambda: api.init_cache(slots, max_len)))
    pos = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one_chip)
    compiled = jax.jit(api.decode, donate_argnums=(1,)).lower(params, caches, pos, pos).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20
    dims = ",".join(map(str, jax.tree.leaves(caches)[0].shape))
    assert not re.search(rf"= bf16\[{dims}\]\S* copy\(", compiled.as_text())
