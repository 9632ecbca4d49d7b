"""Fast single-device unit tests for the distribution substrate — the cheap
complement to test_dist.py's multi-device subprocess integration suite."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax.sharding import NamedSharding, PartitionSpec as P

from repro.dist.collectives import (
    DEFAULT_BUCKET_BYTES,
    bucket_leaves,
    ef_compress_grads,
    ef_compress_grads_bucketed,
    int8_dequantize,
    int8_quantize,
)
from repro.dist.pipeline import pipeline_bubble_fraction
from repro.dist.sharding import (
    active_mesh,
    batch_pspecs,
    cache_pspecs,
    constrain,
    param_pspecs,
    resolve_pspec,
    to_named,
    use_mesh,
)
from repro.launch.mesh import make_mesh


# ----------------------------------------------------------------------
# resolve_pspec edge cases
# ----------------------------------------------------------------------


def _mesh(sizes, names):
    from jax.sharding import AbstractMesh

    return AbstractMesh(sizes, names)


def test_resolve_pspec_odd_head_counts_replicate():
    mesh = _mesh((16, 16), ("data", "model"))
    # hymba-style odd head counts on a 16-way model axis
    for heads in (25, 7, 17, 31):
        assert resolve_pspec((heads, 64), ("tp", None), mesh) == P(None, None)
    # even-but-non-divisible also replicates
    assert resolve_pspec((24, 64), ("tp", None), mesh) == P(None, None)
    # divisible shards
    assert resolve_pspec((32, 64), ("tp", None), mesh) == P("model", None)


def test_resolve_pspec_multipod_greedy_batch_factoring():
    mesh = _mesh((2, 16, 16), ("pod", "data", "model"))
    # divisible by pod*data -> joint sharding
    assert resolve_pspec((256, 8), ("batch", None), mesh) == P(("pod", "data"), None)
    # divisible by pod only -> greedy keeps the prefix
    assert resolve_pspec((2, 8), ("batch", None), mesh) in (P("pod", None), P(("pod",), None))
    assert resolve_pspec((6, 8), ("batch", None), mesh) in (P("pod", None), P(("pod",), None))
    # not even divisible by pod -> replicate
    assert resolve_pspec((3, 8), ("batch", None), mesh) == P(None, None)
    # odd batch of 1 (long-context decode) -> replicate
    assert resolve_pspec((1, 8), ("batch", None), mesh) == P(None, None)


def test_resolve_pspec_no_axis_reuse():
    mesh = _mesh((2, 2), ("data", "model"))
    # experts claims the model axis first; a later tp dim must not reuse it
    spec = resolve_pspec((4, 64, 96), ("experts", "fsdp", "tp"), mesh)
    assert spec == P("model", "data", None)


def test_resolve_pspec_missing_axes_replicate():
    mesh = _mesh((4,), ("pipe",))
    assert resolve_pspec((8, 8), ("batch", "tp"), mesh) == P(None, None)


def test_resolve_pspec_rank_mismatch_raises():
    mesh = _mesh((2, 2), ("data", "model"))
    with pytest.raises(ValueError):
        resolve_pspec((4, 4), ("batch",), mesh)


# ----------------------------------------------------------------------
# tree mappers + mesh context
# ----------------------------------------------------------------------


def test_param_pspecs_moe_expert_dim_on_model_axis():
    from repro.configs import get_arch
    from repro.models.registry import build_model

    cfg = get_arch("dbrx-132b").smoke()
    api = build_model(cfg)
    shapes = jax.eval_shape(api.init, jax.random.PRNGKey(0))
    mesh = _mesh((2, 2), ("data", "model"))
    specs = param_pspecs(shapes, mesh)
    moe_spec = specs["segments"][0]["moe"]["w_gate"]
    # stacked (L, E, d, f): expert dim sharded on the model axis
    assert moe_spec[1] == "model"


def test_batch_pspecs_structure_and_batch_dim():
    mesh = _mesh((2, 2), ("data", "model"))
    batch = {
        "tokens": jax.ShapeDtypeStruct((4, 32), jnp.int32),
        "frames": jax.ShapeDtypeStruct((4, 24, 64), jnp.float32),
        "odd": jax.ShapeDtypeStruct((3, 5), jnp.float32),
    }
    specs = batch_pspecs(batch, mesh)
    assert specs["tokens"] == P("data", None)
    assert specs["frames"] == P("data", None, None)
    assert specs["odd"] == P(None, None)  # 3 doesn't divide the data axis


def test_cache_pspecs_kv_heads_on_model_axis():
    mesh = _mesh((2, 2), ("data", "model"))
    cache = {"k": jax.ShapeDtypeStruct((2, 4, 32, 2, 16), jnp.float32)}
    assert cache_pspecs(cache, mesh)["k"] == P(None, "data", None, "model", None)


def test_use_mesh_nesting_and_constrain_noop():
    assert active_mesh() is None
    x = jnp.ones((4, 8))
    assert constrain(x, ("batch", None)) is x  # no mesh -> identity
    m1 = make_mesh((1,), ("data",))
    with use_mesh(m1) as m:
        assert active_mesh() is m1 and m is m1
        with use_mesh(m1):
            assert active_mesh() is m1
        assert active_mesh() is m1
    assert active_mesh() is None


def test_to_named_wraps_specs_and_passes_none_through():
    mesh = make_mesh((1, 1), ("data", "model"))
    tree = {"a": P("data", None), "b": None, "c": {"d": P()}}
    out = to_named(tree, mesh)
    assert isinstance(out["a"], NamedSharding) and out["a"].spec == P("data", None)
    assert out["b"] is None
    assert isinstance(out["c"]["d"], NamedSharding)
    assert isinstance(to_named(P(), mesh), NamedSharding)  # bare spec


# ----------------------------------------------------------------------
# int8 error-feedback compression
# ----------------------------------------------------------------------


def test_ef_compress_deterministic():
    rng = np.random.default_rng(3)
    g = {"w": jnp.asarray(rng.standard_normal((32, 16)), jnp.float32)}
    d1, e1 = ef_compress_grads(g, None)
    d2, e2 = ef_compress_grads(g, None)
    np.testing.assert_array_equal(np.asarray(d1["w"]), np.asarray(d2["w"]))
    np.testing.assert_array_equal(np.asarray(e1["w"]), np.asarray(e2["w"]))


def test_ef_compress_int8_levels_and_scale():
    g = jnp.asarray(np.linspace(-2.0, 2.0, 1000), jnp.float32)
    q, scale = int8_quantize(g)
    assert q.dtype == jnp.int8
    assert float(scale) == pytest.approx(2.0 / 127.0)
    levels = np.unique(np.asarray(q))
    assert levels.min() >= -127 and levels.max() <= 127
    # dequantization error bounded by half a quantization step
    err = np.abs(np.asarray(int8_dequantize(q, scale)) - np.asarray(g))
    assert err.max() <= float(scale) / 2 + 1e-7


def test_ef_compress_zero_grads_exact():
    g = {"w": jnp.zeros((8, 8), jnp.float32)}
    deq, err = ef_compress_grads(g, None)
    np.testing.assert_array_equal(np.asarray(deq["w"]), 0.0)
    np.testing.assert_array_equal(np.asarray(err["w"]), 0.0)


def test_ef_compress_residual_carries_between_steps():
    g = {"w": jnp.full((4,), 0.501 * (1.0 / 127.0), jnp.float32)}
    deq1, err1 = ef_compress_grads(g, None)
    # residual is what quantization dropped
    np.testing.assert_allclose(
        np.asarray(err1["w"]),
        np.asarray(g["w"]) - np.asarray(deq1["w"]),
        rtol=1e-6,
    )
    # feeding the residual back changes the next quantization target
    deq2, _ = ef_compress_grads(g, err1)
    total = np.asarray(deq1["w"]) + np.asarray(deq2["w"])
    np.testing.assert_allclose(total, 2 * np.asarray(g["w"]), atol=float(1 / 127.0))


def test_ef_compress_jit_compatible():
    g = {"w": jnp.ones((8,), jnp.float32)}
    e = {"w": jnp.zeros((8,), jnp.float32)}
    deq, err = jax.jit(ef_compress_grads)(g, e)
    np.testing.assert_allclose(np.asarray(deq["w"]), 1.0, rtol=1e-6)


# ----------------------------------------------------------------------
# bucketed, overlapped error-feedback (ISSUE 10)
# ----------------------------------------------------------------------


def _grad_tree(seed: int = 0) -> dict:
    """A small nested tree with uneven leaf sizes, so mid-range bucket caps
    produce a genuinely mixed ledger (multi-leaf and singleton buckets)."""
    rng = np.random.default_rng(seed)
    arr = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)
    return {
        "emb": arr(64, 16),
        "blocks": [{"w": arr(16, 16), "b": arr(16)} for _ in range(3)],
        "head": arr(16, 7),
    }


def test_bucket_leaves_partition_invariants():
    leaves = jax.tree.leaves(_grad_tree())
    for bucket_bytes in (1, 64, 300, 1 << 20):
        ledger = bucket_leaves(leaves, bucket_bytes)
        covered = [i for b in ledger for i in b.leaf_indices]
        # exact partition, walked in reverse tree order (the order backward
        # makes gradients available, hence the order buckets can launch)
        assert covered == list(reversed(range(len(leaves))))
        for b in ledger:
            assert b.nbytes == sum(int(leaves[i].size) + 4 for i in b.leaf_indices)
            # a bucket only exceeds the cap when a single leaf does
            assert b.nbytes <= bucket_bytes or len(b.leaf_indices) == 1
    # a cap larger than the whole tree yields one launch
    assert len(bucket_leaves(leaves, 1 << 30)) == 1
    # every-leaf-alone at the minimum cap
    assert all(len(b.leaf_indices) == 1 for b in bucket_leaves(leaves, 1))
    with pytest.raises(ValueError):
        bucket_leaves(leaves, 0)


def test_bucketed_ef_bit_identical_to_sync_across_bucket_sizes():
    """Partitioning the leaves into launch buckets changes the launch
    schedule, not one arithmetic op: dequantized grads AND carried
    residuals match the synchronous path bit for bit, for any cap."""
    grads = _grad_tree(1)
    err = jax.tree.map(lambda g: 1e-3 * g, _grad_tree(2))
    deq_s, err_s = ef_compress_grads(grads, err)
    for bucket_bytes in (1, 64, 300, 1500, DEFAULT_BUCKET_BYTES):
        deq_b, err_b, ledger = ef_compress_grads_bucketed(
            grads, err, bucket_bytes=bucket_bytes
        )
        assert jax.tree_util.tree_structure(deq_b) == jax.tree_util.tree_structure(grads)
        for a, b in zip(jax.tree.leaves(deq_b), jax.tree.leaves(deq_s)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(err_b), jax.tree.leaves(err_s)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert ledger == bucket_leaves(jax.tree.leaves(grads), bucket_bytes)
    # first-step (err=None) path agrees too
    d0_s, e0_s = ef_compress_grads(grads, None)
    d0_b, e0_b, _ = ef_compress_grads_bucketed(grads, None, bucket_bytes=300)
    for a, b in zip(jax.tree.leaves(d0_b), jax.tree.leaves(d0_s)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(e0_b), jax.tree.leaves(e0_s)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_bucketed_ef_invariants_hold_per_bucket():
    """The EF invariants survive bucketing: per-leaf conservation
    (deq + new_err == grads + err), residual bounded by half a
    quantization step, float32 structure stability."""
    grads = _grad_tree(3)
    err = jax.tree.map(lambda g: 1e-2 * g, _grad_tree(4))
    deq, new_err, ledger = ef_compress_grads_bucketed(grads, err, bucket_bytes=300)
    assert len(ledger) > 1  # the cap actually split the tree
    g_l, e_l = jax.tree.leaves(grads), jax.tree.leaves(err)
    d_l, n_l = jax.tree.leaves(deq), jax.tree.leaves(new_err)
    for g, e, d, n in zip(g_l, e_l, d_l, n_l):
        assert d.dtype == jnp.float32 and n.dtype == jnp.float32
        target = np.asarray(g, np.float32) + np.asarray(e, np.float32)
        np.testing.assert_allclose(
            np.asarray(d) + np.asarray(n), target, rtol=1e-6, atol=1e-7
        )
        scale = np.abs(target).max() / 127.0
        assert np.abs(np.asarray(n)).max() <= scale / 2 + 1e-7


def test_bucketed_ef_per_bucket_transport_applies():
    """The optional per-bucket ``all_reduce`` callable sees each bucket's
    dequantized leaves and its result lands in the output tree — a 2x
    stand-in transport checks wiring without needing devices."""
    grads = _grad_tree(5)
    calls = []

    def fake_reduce(bucket):
        calls.append(len(bucket))
        return [2.0 * x for x in bucket]

    deq, _, ledger = ef_compress_grads_bucketed(
        grads, None, bucket_bytes=300, all_reduce=fake_reduce
    )
    assert calls == [len(b.leaf_indices) for b in ledger]
    ref, _ = ef_compress_grads(grads, None)
    for a, b in zip(jax.tree.leaves(deq), jax.tree.leaves(ref)):
        np.testing.assert_array_equal(np.asarray(a), 2.0 * np.asarray(b))


def test_train_step_overlap_grads_bit_identical_to_sync():
    """TrainConfig(overlap_grads=True) reproduces the synchronous
    compressed step exactly — losses and updated params bit for bit over
    several steps, with a cap small enough to force many buckets."""
    from repro.configs import get_arch
    from repro.models.registry import build_model, materialize_batch
    from repro.train.step import (
        TrainConfig,
        init_train_state,
        make_optimizer,
        make_train_step,
    )

    cfg = get_arch("qwen3-0.6b").smoke()
    api = build_model(cfg)
    batch = materialize_batch(cfg, 4, 32)
    runs = {}
    for overlap in (False, True):
        tc = TrainConfig(
            compress_grads=True,
            overlap_grads=overlap,
            bucket_bytes=32 << 10,
            total_steps=8,
            warmup=1,
        )
        opt = make_optimizer(tc)
        state = init_train_state(api, opt, jax.random.PRNGKey(0), compress_grads=True)
        step = jax.jit(make_train_step(api, opt, tc))
        losses = []
        for _ in range(3):
            state, metrics = step(state, batch)
            losses.append(float(metrics["loss"]))
        runs[overlap] = (losses, state)
    assert runs[True][0] == runs[False][0]
    for key in ("params", "err"):
        for a, b in zip(
            jax.tree.leaves(runs[True][1][key]), jax.tree.leaves(runs[False][1][key])
        ):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ----------------------------------------------------------------------
# pipeline accounting
# ----------------------------------------------------------------------


def test_pipeline_bubble_fraction():
    assert pipeline_bubble_fraction(4, 4) == pytest.approx(3 / 7)
    assert pipeline_bubble_fraction(1, 8) == 0.0
