"""Validate the loop-aware HLO cost walker against known workloads."""
import jax
import jax.numpy as jnp
from jax import lax

from repro.launch.mesh import make_mesh
from repro.roofline.hlo_cost import analyze_hlo


def _cost(fn, *specs, **jit_kw):
    compiled = jax.jit(fn, **jit_kw).lower(*specs).compile()
    return analyze_hlo(compiled.as_text()), compiled


def test_single_matmul_flops():
    x = jax.ShapeDtypeStruct((128, 256), jnp.float32)
    w = jax.ShapeDtypeStruct((256, 512), jnp.float32)
    s, _ = _cost(lambda a, b: a @ b, x, w)
    expect = 2 * 128 * 256 * 512
    assert abs(s.dot_flops - expect) / expect < 0.01


def test_scan_multiplies_by_trip_count():
    """THE fix over XLA cost_analysis: a scanned matmul counts trip times."""
    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    w = jax.ShapeDtypeStruct((64, 64), jnp.float32)

    def f(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        y, _ = lax.scan(body, x, None, length=13)
        return y

    s, compiled = _cost(f, x, w)
    one = 2 * 64 * 64 * 64
    assert abs(s.dot_flops - 13 * one) / (13 * one) < 0.01, s.dot_flops
    # XLA's own counter misses the loop:
    xla_flops = compiled.cost_analysis().get("flops", 0)
    assert xla_flops < 2 * one
    # transcendentals: 13 tanh of 64*64
    assert s.transcendentals >= 13 * 64 * 64


def test_nested_scan():
    x = jax.ShapeDtypeStruct((32, 32), jnp.float32)

    def f(x):
        def outer(c, _):
            def inner(c2, _):
                return c2 @ c2, None
            c2, _ = lax.scan(inner, c, None, length=3)
            return c2, None
        y, _ = lax.scan(outer, x, None, length=5)
        return y

    s, _ = _cost(f, x)
    one = 2 * 32 * 32 * 32
    assert abs(s.dot_flops - 15 * one) / (15 * one) < 0.01, s.dot_flops


def test_batched_dot_contracting_dims():
    a = jax.ShapeDtypeStruct((4, 64, 32), jnp.float32)
    b = jax.ShapeDtypeStruct((4, 32, 16), jnp.float32)
    s, _ = _cost(lambda a, b: jnp.einsum("bij,bjk->bik", a, b), a, b)
    expect = 2 * 4 * 64 * 32 * 16
    assert abs(s.dot_flops - expect) / expect < 0.01, s.dot_flops


def test_hbm_bytes_reasonable():
    """Bytes of a simple matmul ~ inputs + output (within fusion slack)."""
    x = jax.ShapeDtypeStruct((512, 512), jnp.float32)
    w = jax.ShapeDtypeStruct((512, 512), jnp.float32)
    s, _ = _cost(lambda a, b: a @ b, x, w)
    expect = 3 * 512 * 512 * 4
    assert expect * 0.5 <= s.hbm_bytes <= expect * 3, s.hbm_bytes


def test_collectives_counted_under_sharding():
    mesh = make_mesh((1,), ("d",))
    from jax.sharding import NamedSharding, PartitionSpec as P

    def f(x):
        return x.sum()

    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    s, _ = _cost(f, x, in_shardings=NamedSharding(mesh, P("d", None)))
    # single-device mesh: no collectives expected — just exercise the path
    assert s.collective_bytes >= 0


def test_no_unknown_heavy_ops_on_model_step():
    """The walker recognizes every op the real models emit (no silent
    undercount): compile a tiny model train step and check unknowns."""
    from repro.configs import get_arch
    from repro.models.registry import build_model

    cfg = get_arch("qwen3-0.6b").smoke()
    api = build_model(cfg)
    params = jax.eval_shape(api.init, jax.random.PRNGKey(0))
    batch = {"tokens": jax.ShapeDtypeStruct((2, 32), jnp.int32)}
    compiled = jax.jit(lambda p, b: api.loss(p, b)[0]).lower(params, batch).compile()
    s = analyze_hlo(compiled.as_text())
    assert s.dot_flops > 0
    assert not s.unknown_ops, s.unknown_ops
