"""Trace-residual round trip (ISSUE 9): the wall-clock the engines stamp
onto recorded steps (``StepMeta.measured_s``) plus the recorded call
groups feed the residual monitor, and re-lowering a step's recorded
shapes (``step_predicted_s``) reproduces the live prediction exactly —
for both engines, including mesh-inherited parallel degrees."""
import jax
import numpy as np
import pytest

from repro.configs import get_arch
from repro.core.hardware import get_hw
from repro.launch.mesh import make_mesh
from repro.predict import get_predictor
from repro.serve.engine import ContinuousBatchingEngine, Request, ServeEngine
from repro.serve.monitor import (
    ResidualMonitor,
    step_predicted_s,
    trace_residuals,
)
from repro.serve.trace import TraceRecorder

HW = get_hw("tpu-v5e")


@pytest.fixture(scope="module")
def predictor():
    return get_predictor("oracle", HW)


@pytest.fixture(scope="module")
def cfg():
    return get_arch("qwen3-0.6b").smoke()


@pytest.fixture(scope="module")
def served(cfg):
    """One recorded ServeEngine run: (recorder, results)."""
    rec = TraceRecorder()
    eng = ServeEngine(cfg, max_batch=2, recorder=rec)
    eng.submit(Request(rid=0, prompt=np.arange(1, 13, dtype=np.int32), max_new=3))
    eng.submit(Request(rid=1, prompt=np.arange(1, 9, dtype=np.int32), max_new=3))
    return rec, eng.step_batch()


# ----------------------------------------------------------------------
# engines stamp wall-clock onto every recorded step
# ----------------------------------------------------------------------


def test_serve_engine_stamps_every_step(served):
    rec, results = served
    # 1 prefill + (max_new - 1) decode steps, all measured
    assert rec.n_steps == 3
    assert rec.phases() == ["prefill", "decode", "decode"]
    assert all(m.measured_s > 0 for m in rec.meta)
    # the prefill stamp *is* the Result's prefill_s — same float
    assert rec.meta[0].measured_s == results[0].prefill_s


def test_continuous_engine_stamps_every_step(cfg):
    rec = TraceRecorder()
    eng = ContinuousBatchingEngine(cfg, slots=2, max_len=48, recorder=rec)
    eng.submit(Request(rid=0, prompt=np.arange(1, 11, dtype=np.int32), max_new=3))
    results = eng.run_to_completion()
    assert all(m.measured_s > 0 for m in rec.meta)
    # the admit step's stamp == the slot's (hence the Result's) prefill_s
    admit = next(m for m in rec.meta if m.phase == "prefill")
    assert admit.measured_s == results[0].prefill_s
    assert results[0].latency_s > 0


def test_mark_measured_guards():
    rec = TraceRecorder()
    with pytest.raises(RuntimeError):
        rec.mark_measured(0.1)
    rec.record_step("s", get_arch("qwen3-0.6b").smoke(), 1, 4, 4)
    with pytest.raises(ValueError):
        rec.mark_measured(-1.0)


# ----------------------------------------------------------------------
# StepMeta re-lowering round trip
# ----------------------------------------------------------------------


def test_relowered_meta_predicts_exactly_like_recorded_calls(served, cfg, predictor):
    # step_calls is the single lowering record_step and step_predicted_s
    # share, so the round trip is float-exact, step by step
    rec, _ = served
    for (_, _, calls), meta in zip(rec.steps, rec.meta):
        live = predictor.predict(calls).total_s
        relowered = step_predicted_s(meta, cfg, predictor)
        assert live > 0
        assert relowered == live


def test_round_trip_at_declared_degrees(predictor):
    # tp/pp ride along in StepMeta: a trace recorded at declared degrees
    # re-lowers with its collectives and PP boundary traffic included
    cfg = get_arch("dbrx-132b").smoke()
    rec = TraceRecorder(tp=2, pp=2)
    rec.record_step("prefill", cfg, 2, 16, 16, phase="prefill")
    rec.record_step("decode", cfg, 2, 1, 17, phase="decode")
    for (_, _, calls), meta in zip(rec.steps, rec.meta):
        assert meta.tp == 2 and meta.pp == 2
        assert step_predicted_s(meta, cfg, predictor) == \
            predictor.predict(calls).total_s


def test_continuous_engine_mesh_inherited_degrees(cfg, predictor):
    # a mesh-native engine binds the recorder to its mesh axes; the
    # recorded meta carries those degrees and still round-trips
    mesh = make_mesh((1, 1), ("data", "model"))
    rec = TraceRecorder()
    eng = ContinuousBatchingEngine(cfg, slots=2, max_len=48,
                                   recorder=rec, mesh=mesh)
    eng.submit(Request(rid=0, prompt=np.arange(1, 9, dtype=np.int32), max_new=2))
    eng.run_to_completion()
    assert rec.resolved_tp == eng.tp == 1  # inherited, not declared
    assert all(m.tp == eng.tp and m.pp == eng.pp for m in rec.meta)
    assert all(m.measured_s > 0 for m in rec.meta)
    for (_, _, calls), meta in zip(rec.steps, rec.meta):
        assert step_predicted_s(meta, cfg, predictor) == \
            predictor.predict(calls).total_s


# ----------------------------------------------------------------------
# residual extraction feeds the monitor
# ----------------------------------------------------------------------


def test_trace_residuals_reproduce_live_measurements(served, predictor):
    rec, _ = served
    res = trace_residuals(rec, predictor)
    assert len(res) == rec.n_steps  # every step was measured
    assert [r.label for r in res] == rec.labels()
    assert [r.measured_s for r in res] == [m.measured_s for m in rec.meta]
    for r in res:
        assert r.hw == HW.name  # defaulted from the predictor's hardware
        assert r.predicted_s > 0 and np.isfinite(r.ratio) and r.ratio > 0
    # timestamps are the cumulative measured clock, strictly increasing
    ts = [r.t for r in res]
    assert all(b > a for a, b in zip(ts, ts[1:]))
    assert ts[-1] == pytest.approx(sum(m.measured_s for m in rec.meta))


def test_unmeasured_steps_are_skipped(cfg, predictor):
    rec = TraceRecorder()
    rec.record_step("measured", cfg, 1, 8, 8, phase="prefill")
    rec.mark_measured(0.25)
    rec.record("pre-lowered", [], phase="other")  # never stamped
    rec.record_step("also-unmeasured", cfg, 1, 1, 9, phase="decode")
    res = trace_residuals(rec, predictor)
    assert [r.label for r in res] == ["measured"]
    assert res[0].measured_s == 0.25


def test_monitor_observe_trace(served, predictor):
    rec, _ = served
    mon = ResidualMonitor()
    mon.observe_trace(rec, predictor)
    assert mon.n_observed == rec.n_steps
    assert mon.keys() == [("trace", HW.name)]
    assert mon.ewma("trace", HW.name) > 0


def test_monitor_observe_results(served):
    rec, results = served
    # predicted at 10x the measured request latency: ratio 0.1, deviation
    # 0.9 — an immediate-trip monitor fires on the first result
    mon = ResidualMonitor(window=4, threshold=0.5, sustain=1, min_samples=1)
    events = mon.observe_results(
        results, predicted_s=results[0].latency_s * 10.0,
        cls="chat", hw=HW.name,
    )
    assert len(events) == len(results)
    assert mon.events == events
    # timestamps accumulate the per-result latencies
    assert events[0].t == pytest.approx(results[0].latency_s)
