"""Distribution tests. Multi-device cases run in subprocesses so the host
test process keeps a single CPU device (device count locks at first jax
init; the dry-run spec forbids a global XLA_FLAGS override)."""
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np

from jax.sharding import PartitionSpec as P

from repro.launch.mesh import make_mesh


def _run_sub(script: str, devices: int = 8, timeout: int = 480):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = "src"
    r = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(script)],
        capture_output=True,
        text=True,
        timeout=timeout,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env=env,
    )
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


# ----------------------------------------------------------------------
# sharding rule unit tests (no devices needed beyond 1)
# ----------------------------------------------------------------------


def test_resolve_pspec_divisibility_fallback():
    from jax.sharding import AbstractMesh

    from repro.dist.sharding import resolve_pspec

    # rule logic only reads mesh.shape — test on the production geometry
    mesh = AbstractMesh((16, 16), ("data", "model"))
    # batch=1 cannot shard -> None; vocab-sized dim shards on model
    assert resolve_pspec((1, 128), ("batch", "tp"), mesh) == P(None, "model")
    # odd head count (hymba's 25) cannot shard on a 16-way model axis
    assert resolve_pspec((25, 64), ("tp", None), mesh) == P(None, None)
    # fsdp falls back to replication when the dim doesn't divide
    assert resolve_pspec((24, 48), ("fsdp", "tp"), mesh) == P(None, "model")
    # multi-pod batch uses (pod, data) jointly when divisible
    mesh3 = AbstractMesh((2, 16, 16), ("pod", "data", "model"))
    assert resolve_pspec((64, 10), ("batch", None), mesh3) == P(("pod", "data"), None)
    # batch divisible by pod but not pod*data -> greedy keeps pod only
    assert resolve_pspec((8, 10), ("batch", None), mesh3) == P(("pod",), None) or \
        resolve_pspec((8, 10), ("batch", None), mesh3) == P("pod", None)


def test_param_rules_cover_all_archs():
    """Every parameter leaf of every arch resolves to a valid PartitionSpec
    on the production mesh geometry (checked symbolically on a 1x1 mesh with
    divisibility against 16/16 sizes via a fake mesh shape) — and every leaf
    *name* is in the audited rule set, so a new model family cannot silently
    ride the generic matrix fallback (ISSUE 3 sharding-rule audit)."""
    from repro.configs import get_arch, list_archs
    from repro.dist.sharding import AUDITED_PARAM_LEAVES, _path_names, param_pspecs
    from repro.models.registry import build_model

    def leaf_names(shapes):
        names = set()

        def one(path, leaf):
            # same path parsing param_pspecs itself uses, so the audit sees
            # exactly the names the rules resolve
            parts = _path_names(path)
            names.add(parts[-1] if parts else "")
            return leaf

        jax.tree_util.tree_map_with_path(one, shapes)
        return names

    mesh = make_mesh((1, 1), ("data", "model"))
    for name in list_archs():
        cfg = get_arch(name).smoke()
        api = build_model(cfg)
        shapes = jax.eval_shape(api.init, jax.random.PRNGKey(0))
        specs = param_pspecs(shapes, mesh)
        leaves = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
        assert leaves, name
        unaudited = leaf_names(shapes) - AUDITED_PARAM_LEAVES
        assert not unaudited, (
            f"{name}: param leaves {sorted(unaudited)} have no audited "
            "sharding rule — add them to dist.sharding._PARAM_RULES"
        )


# ----------------------------------------------------------------------
# multi-device integration (subprocess)
# ----------------------------------------------------------------------


def test_sharded_train_step_matches_single_device():
    """One fsdp+tp train step on a 2x2 mesh reproduces the single-device
    loss (numerical equivalence of the distribution strategy)."""
    _run_sub(
        """
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.configs import get_arch
        from repro.models.registry import build_model, materialize_batch
        from repro.dist.sharding import param_pspecs, batch_pspecs, to_named, use_mesh
        cfg = get_arch("qwen3-0.6b").smoke()
        api = build_model(cfg)
        params = api.init(jax.random.PRNGKey(0))
        batch = materialize_batch(cfg, 4, 32)
        loss_single, _ = jax.jit(api.loss)(params, batch)

        mesh = make_mesh((2, 2), ("data", "model"))
        with use_mesh(mesh):
            p_sh = to_named(param_pspecs(params, mesh), mesh)
            b_sh = to_named(batch_pspecs(batch, mesh), mesh)
            params_s = jax.device_put(params, p_sh)
            batch_s = jax.device_put(batch, b_sh)
            loss_dist, _ = jax.jit(api.loss, in_shardings=(p_sh, b_sh))(params_s, batch_s)
        np.testing.assert_allclose(float(loss_single), float(loss_dist), rtol=2e-3)
        print("OK", float(loss_single), float(loss_dist))
        """,
        devices=4,
    )


def test_moe_expert_parallel_matches_single_device():
    _run_sub(
        """
        import jax, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.configs import get_arch
        from repro.models.registry import build_model, materialize_batch
        from repro.dist.sharding import param_pspecs, batch_pspecs, to_named, use_mesh
        import dataclasses
        cfg = dataclasses.replace(get_arch("dbrx-132b").smoke(), capacity_factor=8.0)
        api = build_model(cfg)
        params = api.init(jax.random.PRNGKey(0))
        batch = materialize_batch(cfg, 4, 32)
        loss_single, _ = jax.jit(api.loss)(params, batch)
        mesh = make_mesh((2, 2), ("data", "model"))
        with use_mesh(mesh):
            p_sh = to_named(param_pspecs(params, mesh), mesh)
            b_sh = to_named(batch_pspecs(batch, mesh), mesh)
            loss_dist, _ = jax.jit(api.loss, in_shardings=(p_sh, b_sh))(
                jax.device_put(params, p_sh), jax.device_put(batch, b_sh))
        np.testing.assert_allclose(float(loss_single), float(loss_dist), rtol=2e-3)
        print("OK")
        """,
        devices=4,
    )


def test_pipeline_parallel_matches_sequential():
    """GPipe shard_map pipeline == sequential layer stack."""
    _run_sub(
        """
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from jax import lax
        from repro.dist.pipeline import pipeline_forward
        mesh = make_mesh((4,), ("pipe",))
        n_layers, micro, mb, d = 8, 4, 2, 16
        ks = jax.random.split(jax.random.PRNGKey(0), n_layers)
        params = {"w": jax.vmap(lambda k: 0.3*jax.random.normal(k, (d, d)))(ks)}
        x = jax.random.normal(jax.random.PRNGKey(1), (micro, mb, d))
        layer_fn = lambda lp, h: jnp.tanh(h @ lp["w"])
        out_pp = pipeline_forward(layer_fn, params, x, mesh)
        def seq(x):
            def body(c, lp):
                return layer_fn(lp, c), None
            y, _ = lax.scan(body, x, params)
            return y
        out_ref = jax.vmap(seq)(x)
        np.testing.assert_allclose(np.asarray(out_pp), np.asarray(out_ref), rtol=2e-5, atol=2e-5)
        print("OK bubble", (4-1)/(4+4-1))
        """,
        devices=4,
    )


def test_pipeline_1f1b_matches_sequential_at_exact_tick_count():
    """Interleaved 1F1B == sequential layer stack, and the analytical
    ``schedule_ticks`` is *minimal*: the executed shard_map schedule run
    one tick short must fail to complete the last microbatch. Covers a
    non-divisible microbatch count (M=6 on S=4) and the divisible case."""
    _run_sub(
        """
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from jax import lax
        from repro.dist.pipeline import pipeline_forward, schedule_ticks
        mesh = make_mesh((4,), ("pipe",))
        layer_fn = lambda lp, h: jnp.tanh(h @ lp["w"])
        def seq(params, x):
            def body(c, lp):
                return layer_fn(lp, c), None
            return jax.vmap(lambda xx: lax.scan(body, xx, params)[0])(x)
        for n_layers, micro, V in ((16, 8, 2), (16, 6, 2), (8, 1, 2)):
            ks = jax.random.split(jax.random.PRNGKey(0), n_layers)
            params = {"w": jax.vmap(lambda k: 0.3*jax.random.normal(k, (16, 16)))(ks)}
            x = jax.random.normal(jax.random.PRNGKey(1), (micro, 2, 16))
            out = pipeline_forward(layer_fn, params, x, mesh,
                                   schedule="1f1b", interleave=V)
            ref = seq(params, x)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       rtol=2e-5, atol=2e-5)
            t = schedule_ticks(4, micro, "1f1b", V)
            short = pipeline_forward(layer_fn, params, x, mesh,
                                     schedule="1f1b", interleave=V, ticks=t - 1)
            assert not np.allclose(np.asarray(short), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5), (micro, V)
        # same minimality statement for GPipe's M + S - 1
        params = {"w": jax.vmap(lambda k: 0.3*jax.random.normal(k, (16, 16)))(
            jax.random.split(jax.random.PRNGKey(0), 8))}
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 2, 16))
        ref = seq(params, x)
        out = pipeline_forward(layer_fn, params, x, mesh)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)
        short = pipeline_forward(layer_fn, params, x, mesh,
                                 ticks=schedule_ticks(4, 4, "gpipe") - 1)
        assert not np.allclose(np.asarray(short), np.asarray(ref), rtol=2e-5, atol=2e-5)
        print("OK 1f1b ticks exact")
        """,
        devices=4,
    )


def test_pipeline_zb_h1_matches_sequential_at_exact_tick_count():
    """Executed ZB-H1 == sequential layer stack at exactly
    ``schedule_ticks`` ring ticks, and one tick short fails — the
    three-phase (F/B/W) slot lifecycle really occupies the ring for the
    ticks the closed form counts. Covers divisible and straggler
    microbatch counts, the degenerate M=1 fill/drain, and V=1."""
    _run_sub(
        """
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from jax import lax
        from repro.dist.pipeline import pipeline_forward, schedule_ticks
        mesh = make_mesh((4,), ("pipe",))
        layer_fn = lambda lp, h: jnp.tanh(h @ lp["w"])
        def seq(params, x):
            def body(c, lp):
                return layer_fn(lp, c), None
            return jax.vmap(lambda xx: lax.scan(body, xx, params)[0])(x)
        for n_layers, micro, V in ((16, 8, 2), (16, 6, 2), (8, 1, 2), (8, 4, 1)):
            ks = jax.random.split(jax.random.PRNGKey(0), n_layers)
            params = {"w": jax.vmap(lambda k: 0.3*jax.random.normal(k, (16, 16)))(ks)}
            x = jax.random.normal(jax.random.PRNGKey(1), (micro, 2, 16))
            out = pipeline_forward(layer_fn, params, x, mesh,
                                   schedule="zb-h1", interleave=V)
            ref = seq(params, x)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       rtol=2e-5, atol=2e-5)
            t = schedule_ticks(4, micro, "zb-h1", V)
            short = pipeline_forward(layer_fn, params, x, mesh,
                                     schedule="zb-h1", interleave=V, ticks=t - 1)
            assert not np.allclose(np.asarray(short), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5), (micro, V)
        print("OK zb-h1 ticks exact")
        """,
        devices=4,
    )


def test_bucketed_ef_allreduce_transport_matches_sync():
    """Bucketed EF with a per-bucket psum transport inside shard_map ==
    synchronous compress-then-tree-psum, bit for bit, on 8 forced host
    devices — the overlapped launch schedule changes nothing numerically
    even with the collective on the wire."""
    _run_sub(
        """
        import functools
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from repro.dist.collectives import ef_compress_grads, ef_compress_grads_bucketed
        mesh = make_mesh((8,), ("data",))
        rng = np.random.default_rng(0)
        grads = {
            "w1": jnp.asarray(rng.standard_normal((8, 64, 16)), jnp.float32),
            "w2": jnp.asarray(rng.standard_normal((8, 33)), jnp.float32),
            "w3": jnp.asarray(rng.standard_normal((8, 5, 3)), jnp.float32),
        }
        err = jax.tree.map(lambda g: jnp.zeros(g.shape, jnp.float32), grads)
        psum = lambda ls: [jax.lax.psum(x, "data") for x in ls]

        @functools.partial(shard_map, mesh=mesh, in_specs=(P("data"), P("data")),
                           out_specs=(P("data"), P("data")))
        def bucketed(g, e):
            deq, new_err, _ = ef_compress_grads_bucketed(
                g, e, bucket_bytes=600, all_reduce=psum)
            return deq, new_err

        @functools.partial(shard_map, mesh=mesh, in_specs=(P("data"), P("data")),
                           out_specs=(P("data"), P("data")))
        def sync(g, e):
            deq, new_err = ef_compress_grads(g, e)
            deq = jax.tree.map(lambda x: jax.lax.psum(x, "data"), deq)
            return deq, new_err

        db, eb = jax.jit(bucketed)(grads, err)
        ds, es = jax.jit(sync)(grads, err)
        for a, b in zip(jax.tree.leaves((db, eb)), jax.tree.leaves((ds, es))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # the reduced grads really aggregated across devices: every
        # device's slice of the psum'd output is the same
        blocks = np.asarray(db["w2"])
        for i in range(1, 8):
            np.testing.assert_array_equal(blocks[i], blocks[0])
        print("OK bucketed transport")
        """,
        devices=8,
    )


def test_elastic_restart_across_device_counts():
    """Checkpoint written under a 4-device mesh restores into a 2-device
    mesh (elastic scaling)."""
    _run_sub(
        """
        import jax, numpy as np, tempfile, os
        from repro.launch.mesh import make_mesh
        from repro.configs import get_arch
        from repro.data.pipeline import DataConfig
        from repro.train.step import TrainConfig
        from repro.train.trainer import Trainer, TrainerConfig
        d = tempfile.mkdtemp()
        cfg = get_arch("qwen3-0.6b").smoke()
        def mk(total):
            return Trainer(cfg, DataConfig(batch=4, seq_len=32),
                           TrainConfig(total_steps=total, warmup=1),
                           TrainerConfig(total_steps=total, ckpt_every=2, ckpt_dir=d, log_every=100))
        t = mk(2); t.run(seed=0)
        # "restart" with a different sharded mesh
        from repro.dist.sharding import param_pspecs, to_named, use_mesh
        mesh = make_mesh((2, 2), ("data", "model"))
        from repro.train.step import init_train_state, make_optimizer
        from repro.optim.adamw import AdamWState
        from jax.sharding import PartitionSpec as P
        with use_mesh(mesh):
            api = t.api
            opt = t.optimizer
            state = init_train_state(api, opt, jax.random.PRNGKey(0))
            sh = {
              "params": to_named(param_pspecs(state["params"], mesh), mesh),
              "opt": AdamWState(step=to_named(P(), mesh),
                                mu=to_named(param_pspecs(state["opt"].mu, mesh), mesh),
                                nu=to_named(param_pspecs(state["opt"].nu, mesh), mesh)),
              "step": to_named(P(), mesh),
              "err": None,
            }
            restored = t.ckpt.restore_latest(state, sh)
            assert restored is not None
            step, new_state, _ = restored
            assert step == 2
            # leaves actually live on the new mesh
            leaf = jax.tree.leaves(new_state["params"])[0]
            assert len(leaf.sharding.device_set) >= 1
        print("OK elastic")
        """,
        devices=4,
    )
