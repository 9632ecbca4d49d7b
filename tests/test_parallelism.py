"""Parallelism-aware prediction (ISSUE 5): EP all-to-all byte exactness,
GPipe/1F1B schedule analytics, and the comm wiring through predict/serve.

The executed ``shard_map`` schedules are validated in ``tests/test_dist.py``
(multi-device subprocesses); here the closed forms are pinned against the
pure event-driven ring simulation, and the decomposer's EP payload against
the dry-run's model-derived ledger, across the whole grid."""
import warnings

import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs import get_arch, list_archs
from repro.core.decomposer import (
    COMPUTE_DTYPE_BYTES,
    ep_alltoall_bytes,
    moe_dispatch_geometry,
)
from repro.core.e2e import layer_calls, pp_bubble, request_estimate
from repro.core.hardware import get_hw
from repro.dist.pipeline import (
    bubble_fraction,
    pipeline_bubble_fraction,
    schedule_ticks,
    simulate_schedule,
)
from repro.launch.dryrun import count_ep_alltoall_bytes
from repro.predict import CommCall, CommRegressor, SweepPredictor, get_predictor
from repro.serve.trace import TraceRecorder

HW = get_hw("tpu-v5e")

MOE_ARCHS = [a for a in list_archs() if get_arch(a).n_experts]


# ----------------------------------------------------------------------
# schedule analytics: closed form == event simulation, 1F1B <= GPipe
# ----------------------------------------------------------------------


@settings(max_examples=80, deadline=None)
@given(S=st.integers(1, 8), M=st.integers(1, 32), V=st.integers(1, 4))
def test_schedule_ticks_match_ring_simulation(S, M, V):
    """Both analytical tick counts equal the executed ring machine's,
    tick for tick, over the whole (S, M, V) grid."""
    assert simulate_schedule(S, M, "gpipe") == schedule_ticks(S, M, "gpipe") == M + S - 1
    assert simulate_schedule(S, M, "1f1b", V) == schedule_ticks(S, M, "1f1b", V)


@settings(max_examples=80, deadline=None)
@given(S=st.integers(1, 8), M=st.integers(1, 32))
def test_1f1b_bubble_never_worse_than_gpipe(S, M):
    b_1f1b = bubble_fraction(S, M, "1f1b", 2)
    b_gpipe = bubble_fraction(S, M, "gpipe")
    assert b_1f1b <= b_gpipe + 1e-12
    if S > 1 and M % S == 0:
        # the production case (microbatches a multiple of stages): the
        # interleaved schedule is strictly better whenever there is a
        # bubble at all
        assert b_1f1b < b_gpipe


def test_bubble_fraction_edge_cases():
    assert bubble_fraction(1, 8, "gpipe") == 0.0
    assert bubble_fraction(1, 8, "1f1b", 2) == 0.0  # S=1: perfect overlap
    assert pipeline_bubble_fraction(4, 4) == pytest.approx(3 / 7)
    # interleave=1 degenerates to GPipe: same machine, same bubble
    assert bubble_fraction(4, 6, "1f1b", 1) == bubble_fraction(4, 6, "gpipe")
    # S | M: the Megatron closed form (S-1)/(V*M + S - 1)
    assert bubble_fraction(4, 8, "1f1b", 2) == pytest.approx(3 / 19)
    # ZB-H1: three-phase ticks, canonical 3M+S-1 makespan at V=1
    assert schedule_ticks(4, 8, "zb-h1", 1) == 3 * 8 + 4 - 1
    assert bubble_fraction(4, 8, "zb-h1", 2) == pytest.approx(3 / 51)
    with pytest.raises(ValueError, match="schedule"):
        schedule_ticks(4, 4, "zb-h2")


def test_pp_bubble_surcharge():
    # default microbatch count (2*pp) reproduces the pre-ISSUE-5 GPipe
    # heuristic exactly — estimates did not shift under the refactor
    for pp in (2, 3, 4, 8):
        assert pp_bubble(pp) == pytest.approx(1 + 0.5 * (pp - 1) / pp)
        assert pp_bubble(pp, schedule="1f1b") < pp_bubble(pp)
    assert pp_bubble(1) == 1.0
    # surcharge = ticks / ideal work in matching units
    assert pp_bubble(4, 8, "gpipe") == pytest.approx(11 / 8)
    assert pp_bubble(4, 8, "1f1b", 2) == pytest.approx(19 / 16)
    # zb-h1: 3*V*S*ceil(M/S) + (M-1)%S ticks over 3*V*M work units
    assert pp_bubble(4, 8, "zb-h1", 2) == pytest.approx(51 / 48)
    for pp in (2, 3, 4, 8):
        assert pp_bubble(pp, schedule="zb-h1") <= pp_bubble(pp, schedule="1f1b")


def test_request_estimate_1f1b_cheaper_than_gpipe():
    cfg = get_arch("qwen3-0.6b")
    oracle = get_predictor("oracle", HW)
    gp = request_estimate(cfg, 2, 64, 8, tp=1, pp=4, predictor=oracle)
    il = request_estimate(cfg, 2, 64, 8, tp=1, pp=4, pp_schedule="1f1b",
                          predictor=oracle)
    assert il.total_s < gp.total_s
    # the interleaved placement crosses more stage boundaries per token
    assert il.by_comm_op["p2p"] > gp.by_comm_op["p2p"]


# ----------------------------------------------------------------------
# EP all-to-all payloads: decomposer == dry-run model-derived ledger
# ----------------------------------------------------------------------


@pytest.mark.parametrize("arch", MOE_ARCHS)
def test_ep_bytes_exact_against_dryrun_count(arch):
    """The decomposer's workload-dict arithmetic must reproduce the
    dry-run's ledger — counted through the executed model layer's own
    ``dispatch_geometry`` — byte for byte, on every MoE arch and across
    prefill/decode/train shapes."""
    cfg = get_arch(arch)
    for B, qlen, train in ((32, 2048, False), (4, 128, False), (128, 1, False),
                           (1, 1, False), (8, 512, True)):
        led = count_ep_alltoall_bytes(cfg, B, qlen, train=train)
        cf = cfg.capacity_factor if train else max(cfg.capacity_factor, 2.0)
        mine = ep_alltoall_bytes({
            "T": B * qlen, "d": cfg.d_model, "E": cfg.n_experts,
            "topk": cfg.top_k, "capacity_factor": cf,
            "moe_group": cfg.moe_group,
            "dtype_bytes": COMPUTE_DTYPE_BYTES[cfg.compute_dtype],
        })
        assert mine == led["dispatch_bytes"] == led["combine_bytes"], (arch, B, qlen)
        assert led["layer_bytes"] == 2 * mine
        assert led["model_bytes"] == 2 * mine * cfg.n_layers


def test_moe_dispatch_geometry_invariants():
    G, Sg, C = moe_dispatch_geometry(T=1024, E=16, topk=4, capacity_factor=2.0,
                                     moe_group=512)
    assert G * Sg == 1024 and Sg <= 512
    assert C == -(-Sg * 4 // 16) * 2  # ceil(Sg*topk/E) * cf
    # tiny decode step: one group, capacity floored at topk
    G1, Sg1, C1 = moe_dispatch_geometry(T=2, E=128, topk=2, capacity_factor=2.0,
                                        moe_group=512)
    assert (G1, Sg1) == (1, 2) and C1 == 2


def test_layer_calls_emit_ep_alltoalls():
    cfg = get_arch("dbrx-132b")
    calls = layer_calls(cfg, 4, 128, 128, tp=4)
    a2a = [c for c in calls if isinstance(c, CommCall) and c.op == "all_to_all"]
    assert len(a2a) == 2  # dispatch + combine
    want = ep_alltoall_bytes({
        "T": 4 * 128, "d": cfg.d_model, "E": cfg.n_experts, "topk": cfg.top_k,
        "capacity_factor": max(cfg.capacity_factor, 2.0),
        "moe_group": cfg.moe_group,
    })
    assert a2a[0].nbytes == a2a[1].nbytes == want
    assert all(c.n_units == 4 for c in a2a)
    # single-unit: no EP traffic; dense archs: never
    assert not [c for c in layer_calls(cfg, 4, 128, 128, tp=1)
                if isinstance(c, CommCall) and c.op == "all_to_all"]
    dense = layer_calls(get_arch("deepseek-67b"), 4, 128, 128, tp=4)
    assert not [c for c in dense if isinstance(c, CommCall) and c.op == "all_to_all"]


def test_moe_request_estimate_prices_ep_traffic():
    cfg = get_arch("dbrx-132b")
    est = request_estimate(cfg, 2, 64, 8, tp=4, predictor=get_predictor("oracle", HW))
    assert est.by_comm_op.get("all_to_all", 0.0) > 0.0
    assert est.comm_s >= est.by_comm_op["all_to_all"]
    # EP traffic is priced per hardware across a sweep
    res = SweepPredictor(["tpu-v5e", "tpu-v6e"], "roofline").predict(
        [("step", 1.0, layer_calls(cfg, 2, 1, 256, tp=4))]
    )
    t5 = res["tpu-v5e"].by_comm_op["all_to_all"]
    t6 = res["tpu-v6e"].by_comm_op["all_to_all"]
    assert t5 > 0 and t6 > 0 and t5 != t6


# ----------------------------------------------------------------------
# comm oracle: per-op contention branches + skew-dependent all-to-all
# ----------------------------------------------------------------------


def test_simulate_comm_per_op_step_factors():
    """Each collective's alpha-beta step count, exercised directly: at a
    fixed payload/fleet the deterministic part of the latency orders as
    the (n-1)/n step factors say."""
    from repro.core import hwsim

    n, b = 4, 1e8
    t = {op: hwsim.simulate_comm(op, b, n, HW)
         for op in ("all_reduce", "all_gather", "reduce_scatter",
                    "all_to_all", "p2p")}
    assert all(v > 0 for v in t.values())
    # all_reduce ships 2(n-1)/n — clearly above the one-pass collectives
    assert t["all_reduce"] > t["all_gather"]
    assert t["all_reduce"] > t["reduce_scatter"]
    # p2p ships the whole payload: above the (n-1)/n single-pass ops
    assert t["p2p"] > t["all_gather"]
    with pytest.raises(KeyError):
        hwsim.simulate_comm("broadcast", b, n, HW)


def test_simulate_comm_zero_cases():
    from repro.core import hwsim

    assert hwsim.simulate_comm("all_reduce", 1e6, 1, HW) == 0.0
    assert hwsim.simulate_comm("all_reduce", 0.0, 8, HW) == 0.0
    assert hwsim.simulate_comm("all_to_all", -5.0, 8, HW) == 0.0


def test_simulate_comm_contention_flags():
    """The fixed contention line: >8 chips adds 12%, all_reduce 5%,
    all_to_all 8% — visible as ratios once noise (deterministic per
    (op, bytes, n, hw)) is divided out."""
    from repro.core import hwsim

    def deterministic(op, n):
        t = hwsim.simulate_comm(op, 1e9, n, HW)
        return t / hwsim._noise(op, {"b": int(1e9), "n": n}, HW, amp=0.05)

    # the >8-chip surcharge: deterministic latency jumps by more than the
    # step-factor drift between n=8 and n=16
    bw_steps = lambda n: 2.0 * (n - 1) / n
    r = (deterministic("all_reduce", 16) / bw_steps(16)) / (
        deterministic("all_reduce", 8) / bw_steps(8)
    )
    assert r == pytest.approx(1.17 / 1.05, rel=1e-3)


def test_a2a_hot_ratio_properties():
    from repro.core.hwsim import a2a_hot_ratio

    # balanced traffic or a single chip: exactly the legacy model
    assert a2a_hot_ratio(0.0, 8) == 1.0
    assert a2a_hot_ratio(-1.0, 8) == 1.0
    assert a2a_hot_ratio(0.9, 1) == 1.0
    # skew stretches the exchange, monotonically, bounded by n_chips
    prev = 1.0
    for skew in (0.1, 0.3, 0.6, 0.9):
        r = a2a_hot_ratio(skew, 8)
        assert prev < r <= 8.0
        prev = r
    # deterministic (lru_cached over a fixed seed range)
    assert a2a_hot_ratio(0.3, 8) == a2a_hot_ratio(0.3, 8)


def test_simulate_comm_skew_monotone_and_legacy_exact():
    from repro.core import hwsim

    t0 = hwsim.simulate_comm("all_to_all", 1e8, 8, HW)
    assert hwsim.simulate_comm("all_to_all", 1e8, 8, HW, 0.0) == t0  # legacy
    prev = t0
    for skew in (0.2, 0.5, 0.8):
        t = hwsim.simulate_comm("all_to_all", 1e8, 8, HW, skew)
        assert t > prev
        prev = t
    # skew only prices all_to_all — other ops ignore it entirely
    assert hwsim.simulate_comm("all_reduce", 1e8, 8, HW, 0.9) == (
        hwsim.simulate_comm("all_reduce", 1e8, 8, HW)
    )


def test_moe_layer_calls_carry_ep_skew():
    """The EP dispatch/combine CommCalls inherit the fused-MoE workload's
    routing skew (0.3), and the oracle prices skewed traffic above the
    balanced legacy estimate."""
    from repro.core import hwsim

    cfg = get_arch("dbrx-132b")
    a2a = [c for c in layer_calls(cfg, 4, 128, 128, tp=4)
           if isinstance(c, CommCall) and c.op == "all_to_all"]
    assert len(a2a) == 2 and all(c.skew == 0.3 for c in a2a)
    skewed = hwsim.simulate_comm("all_to_all", a2a[0].nbytes, 4, HW, 0.3)
    balanced = hwsim.simulate_comm("all_to_all", a2a[0].nbytes, 4, HW)
    assert skewed > balanced


def test_pp_boundary_hops_across_schedules():
    from repro.core.e2e import pp_boundary_hops

    for pp in (1, 2, 4, 8):
        for V in (1, 2, 4):
            gp = pp_boundary_hops(pp, "gpipe", V)
            il = pp_boundary_hops(pp, "1f1b", V)
            zb = pp_boundary_hops(pp, "zb-h1", V)
            if pp == 1:
                assert gp == il == zb == 0
            else:
                assert gp == pp - 1
                assert il == pp * V - 1
                assert zb == 2 * pp * V - 1  # B wave re-crosses every chunk
                assert zb > il >= gp


# ----------------------------------------------------------------------
# comm regressor: all_to_all coverage + actionable errors
# ----------------------------------------------------------------------


def test_comm_regressor_fits_all_to_all():
    reg = CommRegressor().fit(HW)
    assert "all_to_all" in reg.fitted_ops()
    t = reg.predict("all_to_all", 1e7, 4)
    from repro.core import hwsim

    assert t == pytest.approx(hwsim.simulate_comm("all_to_all", 1e7, 4, HW), rel=0.5)


def test_unfitted_errors_name_fitted_ops():
    with pytest.raises(RuntimeError, match=r"fitted ops: none"):
        CommRegressor().predict("all_to_all", 1e6, 4)
    # a regressor fitted before all_to_all joined OPS names what it has
    stale = CommRegressor().fit(HW)
    stale.theta = {k: v for k, v in stale.theta.items() if k[0] != "all_to_all"}
    with pytest.raises(RuntimeError, match=r"'all_to_all' \(fitted ops: \['all_gather'"):
        stale.predict("all_to_all", 1e6, 4)


def test_router_skips_stale_comm_hw_with_actionable_warning():
    """An EP sweep over a fleet where one entry's regressor predates the
    all_to_all bucket skips that entry with a warning naming the fitted
    ops, instead of aborting the whole placement."""
    from repro.serve.placement import FleetRouter

    stale = CommRegressor().fit(get_hw("tpu-v5e"))
    stale.theta = {k: v for k, v in stale.theta.items() if k[0] != "all_to_all"}
    sweep = SweepPredictor(predictors={
        "tpu-v5e": get_predictor("roofline", get_hw("tpu-v5e"), comm=stale),
        "tpu-v6e": get_predictor("roofline", get_hw("tpu-v6e")),
    })
    trace = [("step", 1.0, layer_calls(get_arch("dbrx-132b"), 2, 1, 256, tp=4))]
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        pl = FleetRouter(sweep=sweep).route(trace)
    assert pl.best == "tpu-v6e"
    assert "tpu-v5e" in pl.skipped and "all_to_all" in pl.skipped["tpu-v5e"]
    assert any("fitted ops" in str(w.message) for w in caught)


# ----------------------------------------------------------------------
# trace capture at declared parallel degrees
# ----------------------------------------------------------------------


def test_trace_recorder_carries_collectives():
    cfg = get_arch("dbrx-132b").smoke()
    rec = TraceRecorder(tp=2, pp=2)
    rec.record_step("prefill", cfg, 2, 16, 16, phase="prefill")
    rec.record_step("decode", cfg, 2, 1, 17, phase="decode")
    assert rec.meta[0].tp == 2 and rec.meta[0].pp == 2
    from repro.predict import flatten_calls

    flat = [c for c, _ in flatten_calls(rec.calls())]
    ops = {c.op for c in flat if isinstance(c, CommCall)}
    assert {"all_to_all", "p2p", "all_reduce"} <= ops
    # the recorded trace prices end to end, collectives included
    est = get_predictor("oracle", HW).predict(rec.calls())
    assert est.by_comm_op["all_to_all"] > 0 and est.by_comm_op["p2p"] > 0
    # tp=1 recorder (the engines' default) stays collective-free
    rec1 = TraceRecorder()
    rec1.record_step("decode", cfg, 2, 1, 17)
    flat1 = [c for c, _ in flatten_calls(rec1.calls())]
    assert not [c for c in flat1 if isinstance(c, CommCall)]
    assert rec1.meta[0].tp == 1 and rec1.meta[0].pp == 1
