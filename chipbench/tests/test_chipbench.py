"""The benchmark's own tests, on the CPU at the program's ``.smoke()``
widths: the generator, the statistics, the trace reduction, the counts,
the peaks table, the chip check, whole runs through the serving and training loops, and
faults planted under the timed path that ``correct`` must catch.

    python -m pytest chipbench/tests -q
"""
from __future__ import annotations

import dataclasses
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import count, run, serve, stats, traffic, train
from chipbench.common import Record, load_json
from chipbench.tests import smoke
from chipbench.trace import Event, TraceSummary

MIX = load_json(run.HERE / "mixes" / "conv.json")


# ----------------------------------------------------------------------
# traffic


def test_schedule_is_a_function_of_the_seed():
    a = traffic.schedule(MIX, 2.0, 30, 2**31 + 11, 1000)
    b = traffic.schedule(MIX, 2.0, 30, 2**31 + 11, 1000)
    c = traffic.schedule(MIX, 2.0, 30, 2**31 + 12, 1000)
    key = lambda s: [(r.due_s, r.max_new, r.prompt.tobytes()) for r in s]  # noqa: E731
    assert key(a) == key(b)
    assert key(a) != key(c)


def test_every_seed_gets_the_same_work():
    a = traffic.schedule(MIX, 2.0, 30, 1, 1000)
    b = traffic.schedule(MIX, 2.0, 30, 2, 1000)
    assert len(a) == len(b) == 60
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in b)
    assert sorted(r.max_new for r in a) == sorted(r.max_new for r in b)
    assert all(0 < r.due_s < 30 for r in a)
    assert max(r.due_s for r in a) == pytest.approx(max(r.due_s for r in b))


def test_lengths_fall_in_their_buckets():
    s = traffic.schedule(MIX, 3.0, 40, 7, 1000)
    p, o = MIX["prompt"], MIX["output"]
    for r in s:
        assert len(r.prompt) % p["bucket"] == 0
        assert p["min"] <= len(r.prompt) <= p["max"]
        assert o["min"] <= r.max_new <= o["max"]
        assert r.prompt.min() >= 3 and r.prompt.max() < 1000
    assert set(traffic.prompt_buckets(MIX, 3.0, 40)) == {len(r.prompt) for r in s}
    # the median request sits near the published medians
    assert np.median([len(r.prompt) for r in s]) == pytest.approx(p["median"], rel=0.3)
    assert np.median([r.max_new for r in s]) == pytest.approx(o["median"], rel=0.1)


# ----------------------------------------------------------------------
# statistics on a synthetic schedule


def synthetic_record():
    rec = Record(spec=None, seconds=10.0)
    rec.window = (100.0, 110.0)
    mk = lambda due, times, n: serve.Req(0, due, np.zeros(n, np.int32), len(times),  # noqa: E731
                                         token_times=times)
    rec.requests = {
        0: mk(100.0, [100.5, 100.6, 100.8], 10),
        1: mk(101.0, [101.2, 101.2, 101.5], 20),
        2: mk(109.0, [109.9, 110.4], 30),  # its second token comes after the close
        3: mk(109.5, [], 40),  # never started
    }
    return rec


def test_percentiles_count_a_request_that_never_started_as_inf():
    rec = synthetic_record()
    ttft = sorted(stats.ttfts_ms(rec))
    assert ttft[:3] == pytest.approx([200.0, 500.0, 900.0])
    assert ttft[3] == math.inf
    assert stats.nearest_rank(ttft, 0.5) == pytest.approx(500.0)
    assert stats.nearest_rank(ttft, 0.9) == math.inf
    assert stats.nearest_rank([1, 2, 3, 4, 5, 6, 7, 8, 9, 10], 0.9) == 9
    assert sorted(stats.itl_ms(rec)) == pytest.approx([0.0, 100.0, 200.0, 300.0, 500.0])


def test_window_rate_counts_prompt_and_output_tokens_inside_the_window():
    rec = synthetic_record()
    # outputs inside: 3 + 3 + 1; prompts of the requests whose first token is inside: 10+20+30
    assert stats.window_tokens_per_s(rec) == pytest.approx((7 + 60) / 10.0)


# ----------------------------------------------------------------------
# trace reduction


def synthetic_trace():
    E = Event
    ops = [[E("%fusion.1 = f(x)", 1.0, 1.2), E("%fusion.2 = g(x)", 1.1, 1.3),
            E("%copy.3 = c(x)", 1.6, 1.7), E("%fusion.1 = f(x)", 2.0, 2.4),
            E("%while.9 = w(x)", 2.0, 2.4), E("%fusion.1 = f(x)", 2.9, 3.5)]]
    mods = [[E("jit_decode(123)", 1.0, 1.3), E("jit_scatter(7)", 1.6, 1.7),
             E("jit_decode(123)", 2.0, 2.4), E("jit_prefill(9)", 2.9, 3.5)]]
    spans = [E("chipbench.window", 0.5, 3.0), E("chipbench.step", 0.9, 1.8),
             E("chipbench.idle", 1.8, 1.95), E("chipbench.step", 1.95, 2.5),
             E("other", 0.0, 9.0)]
    return TraceSummary.build(ops, mods, spans)


def test_busy_union_and_idle_gaps():
    t = synthetic_trace()
    assert t.window == (0.5, 3.0)
    assert t.busy() == pytest.approx([(1.0, 1.3), (1.6, 1.7), (2.0, 2.4), (2.9, 3.0)])
    assert t.busy_s == pytest.approx(0.3 + 0.1 + 0.4 + 0.1)
    assert t.busy_within(0.9, 1.8) == pytest.approx(0.4)
    gaps = t.idle_gaps()
    assert [g[1] for g in gaps] == pytest.approx([0.5, 0.5, 0.3, 0.3])
    # each gap names the harness span at its middle and the program before it
    assert sorted(g[0] for g in gaps) == sorted(
        ["none after start", "step after jit_decode", "idle after jit_scatter",
         "none after jit_decode"])


def test_program_time_and_top_ops():
    t = synthetic_trace()
    assert t.program_s("decode") == pytest.approx(0.7)
    assert t.program_count("decode") == 2
    assert t.program_s("prefill") == pytest.approx(0.1)  # clipped at the window's end
    assert t.program_count("train_step") == 0
    # the trace's first run may be cut by the profiler's start
    assert t.program_run_s("decode") == pytest.approx(0.4)
    assert t.program_run_s("prefill") is None  # its one run is cut by the window's end
    top = dict((n, s) for n, s in t.top_ops())
    assert top["jit_decode:%fusion.1"] == pytest.approx(0.6)
    assert top["jit_scatter:%copy.3"] == pytest.approx(0.1)
    assert not any("while" in n for n in top)  # a loop holds its body's ops
    assert [e.name for e in t.spans] == ["step", "idle", "step"]


# ----------------------------------------------------------------------
# counts and peaks


def test_counts_by_hand_at_smoke_sizes():
    hf = smoke.SMOKE_HF["qwen3-0.6b"]
    s = count.Sizes.from_config(hf)
    # d=64, q=4x16=64, kv=2x16=32, f=128, V=256, 2 layers
    per_layer = 64 * (64 + 2 * 32) + 64 * 64 + 3 * 64 * 128
    assert s.layer_matmul_params == per_layer == 36864
    assert s.matmul_params == 2 * per_layer + 64 * 256
    flops, nbytes = s.prefill(10)
    attn = 4 * 2 * 4 * 16 * (10 * 11 // 2)
    assert flops == 2 * 2 * per_layer * 10 + 2 * 64 * 256 + attn
    weights = (2 * per_layer + 64 * 256 + 2 * 2 * 64 + 64) * 2
    kv_tok = 2 * 2 * 2 * 16 * 2
    assert nbytes == weights + 10 * 64 * 2 + 10 * kv_tok + 4 * 256
    flops, nbytes = s.decode([5, 7])
    assert flops == 2 * s.matmul_params * 2 + 4 * 2 * 4 * 16 * 12
    assert nbytes == weights + 2 * 64 * 2 + 12 * kv_tok + 2 * 4 * 256
    assert s.train_flops_per_token(8) == 6 * s.matmul_params + 3 * 4 * 2 * 4 * 16 * 4.5


def test_peaks_table_refuses_an_unknown_device():
    assert count.peaks("TPU v5 lite")["flops_bf16_per_s"] == 197e12
    with pytest.raises(KeyError):
        count.peaks("TPU v99")


def test_run_refuses_to_run_without_a_tpu(capsys):
    assert jax.devices()[0].platform != "tpu"
    assert run.main(["--workload", "qwen3-0.6b.conv", "--seed", "1", "--seconds", "1"]) != 0
    assert capsys.readouterr().out == ""


def test_benchmark_files_are_found_by_name():
    bench = run.benchmark()
    for w in bench["workloads"]:
        spec = run.load_spec(w["name"], bench)
        assert spec.cfg.d_model == spec.hf["hidden_size"]
        assert run.metric_names(spec, bench, False) and run.metric_names(spec, bench, True)
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(run.reader(m["name"]))


# ----------------------------------------------------------------------
# whole runs at smoke size, and planted faults


def serve_run(patch=None, spec=None):
    spec = spec or smoke.serve_spec()
    spec = dataclasses.replace(spec, name="qwen3-0.6b.conv")
    return run.run_cell(spec, 2**31 + 3, 2.0, False, bench=run.benchmark(),
                        t_start=0.0, patch=patch)


def test_serving_run_end_to_end():
    out = serve_run()
    assert out["correct"], out["checks"]
    assert out["attempted"] == 40 and out["failed"] == 0
    assert set(out["metrics"]) == {"ttft_p50_ms", "itl_p99_ms", "tok_s", "setup_s"}
    assert list(out)[-1] == "checks"
    json.dumps(out)


def altered_token(engine):
    orig = engine._sample_one
    engine._sample_one = lambda logits, req, key: (orig(logits, req, key) + 1) % 256


def kv_not_written(engine):
    orig = engine._runner.decode
    engine._runner.decode = lambda caches, toks, pos: (
        orig(jax.tree.map(jnp.copy, caches), toks, pos)[0], caches)


def half_the_slots(engine):
    """The lower half of the slots is served the upper half's logits, as if
    only the upper half were decoded: slots fill from the lowest, so this
    bites however few are busy at once."""
    orig = engine._runner.decode

    def decode(caches, toks, pos):
        logits, caches = orig(caches, toks, pos)
        h = logits.shape[0] // 2
        return logits.at[:h].set(logits[h:2 * h]), caches

    engine._runner.decode = decode


@pytest.mark.parametrize("fault", [altered_token, kv_not_written, half_the_slots])
def test_serving_faults_are_not_correct(fault):
    out = serve_run(patch=fault)
    assert not out["correct"], out["checks"]


def test_control_reads_far_above_the_program():
    """The float8 control's widest gap is many times the program's, and the
    program stays under the conversation cell's limit, here at smoke widths
    (the limit itself is set from full-width readings on the chip)."""
    from chipbench import control

    limit = load_json(run.HERE / "cells" / "qwen3-0.6b.conv.json")["limits"]["logit_gap"]
    got = control.serve_readings(smoke.serve_spec(), 5, 2.0)
    assert got["program_gap"] < limit, got
    assert got["control_gap"] > 3 * got["program_gap"], got


SERVING_CELLS = [w for w in run.benchmark()["workloads"]
                 if load_json(run.HERE / "mixes" / f"{w['traffic']}.json")["loop"] == "serve_open_loop"]


@pytest.mark.parametrize("cell", SERVING_CELLS, ids=lambda w: w["name"])
def test_control_in_the_programs_place_is_not_correct(cell, monkeypatch):
    """A whole run with the float8 reference in the program's place (its
    first token at each position read against the float32 reference's
    best) reads ``correct`` false under the cell's own limit, here at
    smoke widths."""
    limit = load_json(run.HERE / "cells" / f"{cell['name']}.json")["limits"]["logit_gap"]
    check = serve.check_outputs
    monkeypatch.setattr(serve, "check_outputs",
                        lambda spec, seed, reqs: check(spec, seed, reqs, quant="fp8"))
    out = serve_run(spec=smoke.serve_spec(cell["config"], limit=limit))
    assert not out["correct"], out["checks"]
    assert out["checks"]["logit_gap"]["value"] > limit


def train_run(patch=None):
    """A training run at smoke size, under the training cell's limits."""
    limits = load_json(run.HERE / "cells" / "qwen3-0.6b.train.json")["limits"]
    spec = dataclasses.replace(smoke.train_spec(limits), name="qwen3-0.6b.train")
    return run.run_cell(spec, 2**31 + 5, 2.0, False, bench=run.benchmark(), t_start=0.0,
                        patch=patch)


def test_training_run_end_to_end():
    out = train_run()
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"train_tok_s", "setup_s"}
    assert out["attempted"] > 0 and out["failed"] == 0


def unchanged_state(trainer):
    orig = trainer.train_step

    def step(state, batch):
        _, metrics = orig(jax.tree.map(jnp.copy, state), batch)
        return state, metrics

    trainer.train_step = step


def half_batch(trainer):
    orig = trainer.train_step
    trainer.train_step = lambda state, batch: orig(
        state, {k: v[: v.shape[0] // 2] for k, v in batch.items()})


@pytest.mark.parametrize("fault", [unchanged_state, half_batch])
def test_training_faults_are_not_correct(fault):
    out = train_run(patch=fault)
    assert not out["correct"], out["checks"]


def test_train_control_fails_a_limit():
    """The float8 reference in the program's place fails one of the
    training cell's numbers, here at smoke widths."""
    spec = smoke.train_spec()
    spec.cell["limits"] = load_json(run.HERE / "cells" / "qwen3-0.6b.train.json")["limits"]
    ref = train.reference_steps(spec, 9)
    got = train.compare(spec, *train.reference_steps(spec, 9, quant="fp8"), ref)
    assert any(got[k] > spec.cell["limits"][k] for k in got), got
