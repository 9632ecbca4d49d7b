"""Mesh-native serving tests (ISSUE 6): engines that actually execute
sharded, recorder mesh inheritance, and the engine-level satellite fixes
(admission priced at the engine's tp, per-batch PRNG keys, deque queues,
real per-request residency).

Multi-device numerics run in subprocesses (device count locks at first jax
init in the host test process); in-process variants are additionally
gated on ``jax.device_count() >= 8`` so the CI multi-device leg
(``XLA_FLAGS=--xla_force_host_platform_device_count=8``) exercises the
sharded path without a subprocess hop.
"""
import dataclasses
import os
import subprocess
import sys
import textwrap
from collections import deque

import jax
import numpy as np
import pytest

from repro.configs import get_arch
from repro.launch.mesh import make_mesh
from repro.serve.engine import ContinuousBatchingEngine, Request, ServeEngine
from repro.serve.trace import TraceRecorder


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


def _f32_smoke(name="qwen3-0.6b"):
    # float32 compute so sharded-vs-unsharded argmax comparisons are not
    # at the mercy of bf16 reaccumulation ties
    return dataclasses.replace(get_arch(name).smoke(), compute_dtype="float32")


# ----------------------------------------------------------------------
# mesh-native numerics: same tokens sharded vs single-device
# ----------------------------------------------------------------------

_SHARDED_SERVE = """
    import dataclasses
    import numpy as np, jax
    from repro.configs import get_arch
    from repro.serve.engine import ServeEngine, ContinuousBatchingEngine, Request
    from repro.launch.mesh import make_mesh
    from repro.serve.trace import TraceRecorder

    assert jax.device_count() == 8
    cfg = dataclasses.replace(get_arch("qwen3-0.6b").smoke(), compute_dtype="float32")
    mesh = make_mesh((2, 4), ("data", "model"))
    prompts = [np.arange(1, 9 + i, dtype=np.int32) for i in range(4)]

    eng1 = ServeEngine(cfg, seed=0, max_batch=4)
    for i, p in enumerate(prompts):
        eng1.submit(Request(i, p, max_new=8))
    ref = {r.rid: r.tokens for r in eng1.step_batch()}

    rec = TraceRecorder()
    eng2 = ServeEngine(cfg, params=eng1.params, seed=0, max_batch=4,
                       mesh=mesh, recorder=rec)
    for i, p in enumerate(prompts):
        eng2.submit(Request(i, p, max_new=8))
    got = {r.rid: r.tokens for r in eng2.step_batch()}
    assert got == ref, (got, ref)
    # the engine reports the mesh's degrees and the recorder inherits them
    # without the caller declaring tp=/pp=
    assert eng2.tp == 4 and eng2.pp == 1
    assert rec.meta and all(m.tp == 4 and m.pp == 1 for m in rec.meta)
    # params are genuinely placed sharded, not replicated wholesale
    shardings = {str(l.sharding.spec) for l in jax.tree.leaves(eng2.params)
                 if hasattr(l.sharding, "spec")}
    assert any("model" in s for s in shardings), shardings

    c1 = ContinuousBatchingEngine(cfg, slots=2, max_len=48, seed=0)
    for i, p in enumerate(prompts):
        c1.submit(Request(10 + i, p, max_new=6))
    ref2 = {r.rid: r.tokens for r in c1.run_to_completion()}

    rec2 = TraceRecorder()
    c2 = ContinuousBatchingEngine(cfg, slots=2, max_len=48, params=c1.params,
                                  seed=0, mesh=mesh, recorder=rec2)
    for i, p in enumerate(prompts):
        c2.submit(Request(10 + i, p, max_new=6))
    got2 = {r.rid: r.tokens for r in c2.run_to_completion()}
    assert got2 == ref2, (got2, ref2)
    assert all(m.tp == 4 for m in rec2.meta)
    print("OK")
"""


def test_sharded_engines_match_single_process_subprocess():
    """Both engines produce identical tokens on an 8-device (2 data x 4
    model) mesh vs unsharded, and an attached recorder inherits the
    mesh's degrees — the ISSUE 6 acceptance numerics."""
    assert "OK" in _run_sub(_SHARDED_SERVE)


@pytest.mark.skipif(jax.device_count() < 8, reason="needs 8 devices (CI multi-device leg)")
def test_sharded_serve_engine_matches_in_process():
    cfg = _f32_smoke()
    mesh = make_mesh((2, 4), ("data", "model"))
    prompts = [np.arange(1, 7 + i, dtype=np.int32) for i in range(3)]
    eng1 = ServeEngine(cfg, seed=0, max_batch=4)
    for i, p in enumerate(prompts):
        eng1.submit(Request(i, p, max_new=6))
    ref = {r.rid: r.tokens for r in eng1.step_batch()}

    rec = TraceRecorder()
    eng2 = ServeEngine(cfg, params=eng1.params, seed=0, max_batch=4,
                       mesh=mesh, recorder=rec)
    for i, p in enumerate(prompts):
        eng2.submit(Request(i, p, max_new=6))
    assert {r.rid: r.tokens for r in eng2.step_batch()} == ref
    assert eng2.tp == 4 and all(m.tp == 4 for m in rec.meta)


# ----------------------------------------------------------------------
# recorder mesh inheritance (unit — no devices needed)
# ----------------------------------------------------------------------


def test_recorder_inherits_bound_mesh_degrees():
    cfg = get_arch("qwen3-0.6b").smoke()
    rec = TraceRecorder()
    rec.bind_mesh(4, 2)
    assert rec.resolved_tp == 4 and rec.resolved_pp == 2
    rec.record_step("tick", cfg, 2, 1, 16, phase="decode")
    assert rec.meta[0].tp == 4 and rec.meta[0].pp == 2
    # bound pp > 1 carries the stage-boundary traffic like declared pp did
    assert rec.steps[0][2][-1][0] == "pp_boundary"


def test_recorder_declared_mode_still_works():
    """The pre-ISSUE-6 declared path (deprecation shim): no engine mesh
    bound, declared degrees price the trace, no warning."""
    import warnings as w

    cfg = get_arch("qwen3-0.6b").smoke()
    with w.catch_warnings():
        w.simplefilter("error")
        rec = TraceRecorder(tp=2, pp=2)
        rec.record_step("tick", cfg, 2, 1, 16, phase="decode")
    assert rec.meta[0].tp == 2 and rec.meta[0].pp == 2


def test_recorder_mesh_wins_over_declared_with_deprecation():
    cfg = get_arch("qwen3-0.6b").smoke()
    rec = TraceRecorder(tp=2)
    with pytest.warns(DeprecationWarning, match="mesh wins"):
        rec.bind_mesh(4, 1)
    rec.record_step("tick", cfg, 2, 1, 16, phase="decode")
    assert rec.meta[0].tp == 4


def test_meshless_engine_leaves_declared_degrees_alone():
    """A recorder with declared degrees attached to a meshless engine
    keeps pricing at the declared mesh (the PR 5 hypothetical-mesh use),
    with no warning."""
    import warnings as w

    cfg = _f32_smoke()
    with w.catch_warnings():
        w.simplefilter("error")
        rec = TraceRecorder(tp=2)
        eng = ServeEngine(cfg, seed=0, max_batch=2, recorder=rec)
        eng.submit(Request(0, np.arange(1, 6, dtype=np.int32), max_new=2))
        eng.step_batch()
    assert all(m.tp == 2 for m in rec.meta)


# ----------------------------------------------------------------------
# satellite: admission prices the engine's actual tp
# ----------------------------------------------------------------------


def test_predicted_admission_prices_engine_tp():
    """_predicted_tick_s must price at the engine's tp, not a hard-coded
    tp=1: with a tp-sensitive predictor, the logged predicted_s matches
    the tp=engine.tp lowering and differs from the tp=1 one."""
    from repro.core.e2e import model_calls
    from repro.core.hardware import get_hw
    from repro.predict import get_predictor

    cfg = _f32_smoke()
    pred = get_predictor("oracle", get_hw("tpu-v5e"))
    eng = ContinuousBatchingEngine(
        cfg, slots=2, max_len=64, seed=0,
        admission="predicted", predictor=pred, decode_slo_s=10.0,
    )
    # simulate a mesh-native engine without needing devices: the runner's
    # degrees are plain attributes resolved from the mesh at construction
    eng._runner.tp = 2
    eng.submit(Request(0, np.arange(1, 9, dtype=np.int32), max_new=4))
    eng.step()
    assert eng.admission_log, "admission decision was not logged"
    entry = eng.admission_log[0]
    at_tp2 = pred.predict(model_calls(cfg, 2, 1, entry["kv"], tp=2)).total_s
    at_tp1 = pred.predict(model_calls(cfg, 2, 1, entry["kv"], tp=1)).total_s
    assert entry["predicted_s"] == pytest.approx(at_tp2, rel=1e-12)
    assert entry["predicted_s"] != pytest.approx(at_tp1, rel=1e-6)


# ----------------------------------------------------------------------
# satellite: per-batch PRNG keys
# ----------------------------------------------------------------------


def test_batches_sample_independently_but_reproducibly():
    cfg = _f32_smoke()
    prompt = np.arange(1, 9, dtype=np.int32)

    def two_batches(seed):
        eng = ServeEngine(cfg, seed=seed, max_batch=1)
        out = []
        for rid in range(2):
            eng.submit(Request(rid, prompt, max_new=8, temperature=1.0))
        out.append(eng.step_batch()[0].tokens)
        out.append(eng.step_batch()[0].tokens)
        return out

    a = two_batches(seed=0)
    # identical request in consecutive batches must not sample identically
    # (the old fixed PRNGKey(17) made every batch an exact replay)
    assert a[0] != a[1]
    # but the engine stays reproducible under its seed
    assert two_batches(seed=0) == a
    assert two_batches(seed=1) != a


# ----------------------------------------------------------------------
# satellites: deque queues + real residency metrics
# ----------------------------------------------------------------------


def test_queues_are_deques_and_fifo():
    cfg = _f32_smoke()
    eng = ServeEngine(cfg, seed=0, max_batch=2)
    cont = ContinuousBatchingEngine(cfg, slots=2, max_len=48, seed=0)
    assert isinstance(eng.queue, deque) and isinstance(cont.queue, deque)
    for rid in range(3):
        eng.submit(Request(rid, np.arange(1, 5, dtype=np.int32), max_new=2))
    first = eng.step_batch()
    assert [r.rid for r in first] == [0, 1] and [r.rid for r in eng.queue] == [2]


def test_continuous_results_carry_residency():
    cfg = _f32_smoke()
    cont = ContinuousBatchingEngine(cfg, slots=2, max_len=48, seed=0)
    for rid in range(3):
        cont.submit(Request(rid, np.arange(1, 6, dtype=np.int32), max_new=4))
    results = cont.run_to_completion()
    assert len(results) == 3
    for r in results:
        # one admission prefill + one tick per decode token
        assert r.ticks == len(r.tokens)
        assert r.prefill_s > 0.0
        assert r.decode_s >= 0.0
        assert r.latency_s >= r.prefill_s + r.decode_s - 1e-9


def test_serve_engine_results_carry_residency():
    cfg = _f32_smoke()
    eng = ServeEngine(cfg, seed=0, max_batch=2)
    eng.submit(Request(0, np.arange(1, 6, dtype=np.int32), max_new=4))
    eng.submit(Request(1, np.arange(1, 4, dtype=np.int32), max_new=2))
    results = eng.step_batch()
    by_rid = {r.rid: r for r in results}
    assert by_rid[0].ticks == 4 and by_rid[1].ticks == 2
    for r in results:
        assert r.latency_s == pytest.approx(r.prefill_s + r.decode_s)
