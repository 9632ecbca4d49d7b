"""Parallelism-aware prediction smoke (ISSUE 5): EP all-to-all byte
exactness, pipeline bubble-model exactness, and the 1F1B-beats-GPipe
margin.

Three standing criteria (asserted under ``--smoke``, the CI gate):

1. **EP-bytes exactness** — ``core.decomposer.ep_alltoall_bytes`` (the
   workload-dict arithmetic the e2e ``CommCall``s carry) equals
   ``launch.dryrun.count_ep_alltoall_bytes`` (the ledger counted through
   the executed model layer's ``dispatch_geometry``) *exactly*, on every
   MoE arch in the registry across prefill/decode/train shapes.
2. **Bubble-model exactness** — the closed-form ``schedule_ticks`` equals
   the event-driven ring simulation for GPipe and interleaved 1F1B over
   the whole (S, M, V) grid (the executed shard_map schedules are pinned
   to the same counts in tier-1 ``tests/test_dist.py``).
3. **1F1B margin** — at the production point (S=4, M=2S, V=2) the
   interleaved bubble fraction must stay <= ``MAX_BUBBLE_RATIO`` x
   GPipe's (analytically (S-1)/(V*M+S-1) vs (S-1)/(M+S-1) ~ 0.58x).
4. **ZB-H1 margin** (ISSUE 10) — at the same point the zero-bubble
   schedule's bubble must stay <= ``MAX_ZB_RATIO`` x 1F1B's
   (analytically r/(3VM+r... exactly 3/51 vs 3/19 = 19/51 ~ 0.37x),
   with the zb-h1 grid folded into criterion 2's exactness sweep.
5. **Overlap bound** (ISSUE 10) — the overlap-priced estimate of a
   >=12k-call decode trace lands in ``[kernel-only, additive]`` and
   actually engages (strictly below additive when comm exists).

Standalone: ``python -m benchmarks.bench_parallelism [--smoke] [--json
PATH]`` (non-zero exit when a smoke criterion fails — the CI gate).
"""
from __future__ import annotations

import argparse
import sys
import time

from benchmarks.common import Csv, write_bench_json

from repro.configs import get_arch, list_archs
from repro.core.decomposer import COMPUTE_DTYPE_BYTES, ep_alltoall_bytes
from repro.core.e2e import layer_calls, pp_bubble
from repro.core.hardware import get_hw
from repro.dist.pipeline import bubble_fraction, schedule_ticks, simulate_schedule
from repro.launch.dryrun import count_ep_alltoall_bytes
from repro.predict import CommCall, SweepPredictor, get_predictor

#: the artifact's schema (tests/test_bench_schemas.py gates compare.py
#: keys against this)
BENCH_KEYS = (
    "moe_archs", "ep_cells", "ep_max_rel_diff", "ep_commcalls_exact",
    "ep_swept_per_hw", "bubble_grid_points", "bubble_grid_mismatches",
    "bubble_gpipe", "bubble_1f1b", "bubble_ratio",
    "max_bubble_ratio_target",
    "bubble_zb_h1", "zb_ratio", "max_zb_ratio_target",
    "overlap_trace_calls", "overlap_total_ratio", "overlap_bounded",
)

#: 1F1B bubble must be at most this fraction of GPipe's at the gate point
MAX_BUBBLE_RATIO = 0.65
#: ZB-H1 bubble must be at most this fraction of 1F1B's at the same point
#: (analytically (3/51)/(3/19) = 19/51 ~ 0.373)
MAX_ZB_RATIO = 0.4
GATE_S, GATE_V = 4, 2

EP_SHAPES = ((32, 2048, False), (4, 128, False), (128, 1, False), (8, 512, True))


def run(csv: Csv, smoke: bool = False) -> dict:
    # ---- 1. EP byte exactness across the MoE registry -------------------
    moe_archs = [a for a in list_archs() if get_arch(a).n_experts]
    n_cells = 0
    max_rel = 0.0
    t0 = time.perf_counter()
    for arch in moe_archs:
        cfg = get_arch(arch)
        for B, qlen, train in EP_SHAPES:
            led = count_ep_alltoall_bytes(cfg, B, qlen, train=train)
            cf = cfg.capacity_factor if train else max(cfg.capacity_factor, 2.0)
            mine = ep_alltoall_bytes({
                "T": B * qlen, "d": cfg.d_model, "E": cfg.n_experts,
                "topk": cfg.top_k, "capacity_factor": cf,
                "moe_group": cfg.moe_group,
                "dtype_bytes": COMPUTE_DTYPE_BYTES[cfg.compute_dtype],
            })
            rel = abs(mine - led["dispatch_bytes"]) / max(led["dispatch_bytes"], 1.0)
            max_rel = max(max_rel, rel)
            n_cells += 1
    ep_s = time.perf_counter() - t0
    csv.add("parallelism/ep_bytes_cells", ep_s * 1e6 / max(n_cells, 1),
            f"{n_cells} (arch x shape) cells, max rel diff {max_rel:.1e}")
    ep_exact = max_rel == 0.0

    # the modeled calls carry exactly these bytes (spot check on dbrx)
    cfg = get_arch("dbrx-132b")
    a2a = [c for c in layer_calls(cfg, 4, 128, 128, tp=4)
           if isinstance(c, CommCall) and c.op == "all_to_all"]
    led = count_ep_alltoall_bytes(cfg, 4, 128)
    calls_exact = (len(a2a) == 2
                   and all(c.nbytes == led["dispatch_bytes"] for c in a2a))
    nbytes_str = f"{a2a[0].nbytes:.3e}B" if a2a else "none emitted"
    csv.add("parallelism/ep_commcalls", 0.0,
            f"dbrx layer: {len(a2a)} all_to_all x {nbytes_str} "
            f"({'exact' if calls_exact else 'MISMATCH'})")

    # ...and a sweep prices them per hardware
    trace = [("step", 1.0, layer_calls(cfg, 2, 1, 256, tp=4))]
    res = SweepPredictor(["tpu-v5e", "tpu-v6e"], "roofline").predict(trace)
    per_hw_a2a = {n: e.by_comm_op.get("all_to_all", 0.0) for n, e in res.items()}
    swept = all(v > 0 for v in per_hw_a2a.values())
    csv.add("parallelism/ep_swept", 0.0,
            " ".join(f"{n}={v*1e6:.1f}us" for n, v in per_hw_a2a.items()))

    # ---- 2. bubble-model exactness over the schedule grid ----------------
    t0 = time.perf_counter()
    n_grid = 0
    mismatches = 0
    for S in range(1, 9):
        for M in range(1, 25):
            if simulate_schedule(S, M, "gpipe") != schedule_ticks(S, M, "gpipe"):
                mismatches += 1
            n_grid += 1
            for V in (1, 2, 3, 4):
                for sched in ("1f1b", "zb-h1"):
                    if simulate_schedule(S, M, sched, V) != schedule_ticks(S, M, sched, V):
                        mismatches += 1
                    n_grid += 1
    grid_s = time.perf_counter() - t0
    csv.add("parallelism/bubble_grid", grid_s * 1e6 / n_grid,
            f"{n_grid} (S,M,V) schedules, {mismatches} sim-vs-closed-form "
            "mismatches")

    # ---- 3. 1F1B margin at the production point --------------------------
    M = 2 * GATE_S
    b_gp = bubble_fraction(GATE_S, M, "gpipe")
    b_il = bubble_fraction(GATE_S, M, "1f1b", GATE_V)
    ratio = b_il / b_gp
    csv.add("parallelism/bubble_gpipe", 0.0, f"{b_gp:.4f} (S={GATE_S}, M={M})")
    csv.add("parallelism/bubble_1f1b", 0.0,
            f"{b_il:.4f} (V={GATE_V}) = {ratio:.2f}x gpipe "
            f"(target <={MAX_BUBBLE_RATIO}x)")
    csv.add("parallelism/pp_surcharge", 0.0,
            f"gpipe {pp_bubble(GATE_S, M):.4f}x vs 1f1b "
            f"{pp_bubble(GATE_S, M, '1f1b', GATE_V):.4f}x vs zb-h1 "
            f"{pp_bubble(GATE_S, M, 'zb-h1', GATE_V):.4f}x")

    # ---- 4. ZB-H1 margin at the same point -------------------------------
    b_zb = bubble_fraction(GATE_S, M, "zb-h1", GATE_V)
    zb_ratio = b_zb / b_il
    csv.add("parallelism/bubble_zb_h1", 0.0,
            f"{b_zb:.4f} (V={GATE_V}) = {zb_ratio:.2f}x 1f1b "
            f"(target <={MAX_ZB_RATIO}x)")

    # ---- 5. overlap-priced estimate bounded on a long decode trace -------
    step_calls = layer_calls(cfg, 2, 1, 256, tp=4)
    repeats = max(1, -(-12_000 // len(step_calls)))  # >= 12k calls total
    trace_calls = step_calls * repeats
    t0 = time.perf_counter()
    roofline = get_predictor("roofline", get_hw("tpu-v5e"))
    add = roofline.predict(trace_calls)
    ovl = add.overlapped()
    overlap_s = time.perf_counter() - t0
    overlap_ratio = ovl.total_s / add.total_s if add.total_s > 0 else 1.0
    overlap_bounded = (add.kernel_s - 1e-12 <= ovl.total_s <= add.total_s + 1e-12
                       and ovl.total_s < add.total_s)
    csv.add("parallelism/overlap_trace", overlap_s * 1e6 / len(trace_calls),
            f"{len(trace_calls)} calls: overlap {ovl.total_s*1e3:.2f}ms = "
            f"{overlap_ratio:.3f}x additive {add.total_s*1e3:.2f}ms "
            f"({'bounded' if overlap_bounded else 'OUT OF BOUNDS'})")

    results = {
        "moe_archs": moe_archs,
        "ep_cells": n_cells,
        "ep_max_rel_diff": max_rel,
        "ep_commcalls_exact": calls_exact,
        "ep_swept_per_hw": {n: v for n, v in per_hw_a2a.items()},
        "bubble_grid_points": n_grid,
        "bubble_grid_mismatches": mismatches,
        "bubble_gpipe": b_gp,
        "bubble_1f1b": b_il,
        "bubble_ratio": ratio,
        "max_bubble_ratio_target": MAX_BUBBLE_RATIO,
        "bubble_zb_h1": b_zb,
        "zb_ratio": zb_ratio,
        "max_zb_ratio_target": MAX_ZB_RATIO,
        "overlap_trace_calls": len(trace_calls),
        "overlap_total_ratio": overlap_ratio,
        "overlap_bounded": overlap_bounded,
    }
    if smoke:
        assert ep_exact, (
            f"EP all-to-all bytes diverged from the dry-run ledger "
            f"(max rel diff {max_rel:.2e} over {n_cells} cells) — "
            "decomposer.ep_alltoall_bytes vs models.moe.dispatch_geometry drift"
        )
        assert calls_exact, "layer_calls EP CommCalls lost byte exactness"
        assert swept, f"sweep failed to price EP traffic per hw: {per_hw_a2a}"
        assert mismatches == 0, (
            f"{mismatches} schedule grid points where the closed-form tick "
            "count diverged from the ring simulation"
        )
        assert ratio <= MAX_BUBBLE_RATIO, (
            f"1F1B bubble is {ratio:.2f}x GPipe's at S={GATE_S}, M={M} "
            f"(target <={MAX_BUBBLE_RATIO}x) — interleaving regressed"
        )
        assert zb_ratio <= MAX_ZB_RATIO, (
            f"ZB-H1 bubble is {zb_ratio:.2f}x 1F1B's at S={GATE_S}, M={M} "
            f"(target <={MAX_ZB_RATIO}x) — the split backward stopped "
            "filling the warmup/cooldown bubble"
        )
        assert overlap_bounded, (
            f"overlap-priced trace estimate left [kernel, additive]: "
            f"kernel {add.kernel_s:.6f}s, overlap {ovl.total_s:.6f}s, "
            f"additive {add.total_s:.6f}s over {len(trace_calls)} calls"
        )
    return results


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="assert the exactness + margin criteria (CI gate)")
    ap.add_argument("--json", help="write BENCH_parallelism.json-style artifact here")
    args = ap.parse_args(argv)
    csv = Csv()
    print("name,us_per_call,derived")
    try:
        results = run(csv, smoke=args.smoke)
        failed = False
    except AssertionError as e:
        print(f"# SMOKE FAILURE: {e}", file=sys.stderr)
        results = {"error": str(e)}
        failed = True
    if args.json:
        write_bench_json(args.json, csv, declared=BENCH_KEYS, **results, passed=not failed)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
