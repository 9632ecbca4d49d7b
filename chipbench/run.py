"""Run one benchmark cell once and print its result line.

    python chipbench/run.py --workload qwen3-0.6b.conv --seed 7 --seconds 45 --trace 0

Everything about a cell is data, found by name: its entry in
``BENCHMARK.json`` names a configuration (``chipbench/configs/<config>.json``)
and a traffic mix (``chipbench/mixes/<mix>.json``); the cell's own sizes and
limits are in ``chipbench/cells/<cell>.json``, and each metric is read by
``chipbench/metrics/<metric>.py``. A run makes the seed's weights on the
device, warms up every shape the cell's traffic uses, measures for
``--seconds``, checks what the timed path produced against the plain
reference, and prints one JSON line: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer metrics from a profiler trace of part of the window), ``device``,
``breakdown`` with ``--trace 1``, and last ``checks``, each number compared
beside its limit. With no TPU, or fewer chips than the cell asks for, it
exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench.common import HERE, Spec, Tracer, correct, load_json, program_config  # noqa: E402

#: keys of a configuration file that are the benchmark's, not the model's
HARNESS_KEYS = ("bench", "source", "reduced", "assumed", "deployment")


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def load_spec(name: str, bench: dict | None = None) -> Spec:
    """The cell ``name`` from ``BENCHMARK.json`` and its files."""
    from repro.configs import get_arch

    bench = bench or benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json (have {sorted(cells)})")
    entry = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[entry["config"]]
    raw = load_json(ROOT / conf["file"])
    hf = {k: v for k, v in raw.items() if k not in HARNESS_KEYS}
    cell = load_json(HERE / "cells" / f"{name}.json")
    cfg = program_config(get_arch(raw["bench"]["arch"]), cell)
    spec = Spec(name=name, hf=hf, bench=raw["bench"], mix=load_json(HERE / "mixes" / f"{entry['traffic']}.json"),
                cell=cell, cfg=cfg, chips=entry["chips"])
    check_widths(spec)
    return spec


def check_widths(spec: Spec):
    """The program's config must have the published widths."""
    hf, cfg = spec.hf, spec.cfg
    want = {"n_layers": hf["num_hidden_layers"], "d_model": hf["hidden_size"],
            "n_heads": hf["num_attention_heads"],
            "n_kv_heads": hf.get("num_key_value_heads", hf["num_attention_heads"]),
            "resolved_head_dim": hf.get("head_dim") or hf["hidden_size"] // hf["num_attention_heads"],
            "d_ff": hf["intermediate_size"], "vocab_size": hf["vocab_size"]}
    got = {k: getattr(cfg, k) for k in want}
    if got != want:
        raise ValueError(f"program config {cfg.name} has {got}, the published config {want}")


def metric_names(spec: Spec, bench: dict, trace: bool) -> list[tuple[str, str]]:
    """``(name, unit)`` of the metrics this cell reports in this mode."""
    def applies(m):
        return "workloads" not in m or spec.name in m["workloads"]

    e2e = [m for m in bench["end_to_end"] if applies(m)]
    if not trace:
        return [(m["name"], m["unit"]) for m in e2e]
    moved = {m["name"] for m in e2e}
    return [(m["name"], m["unit"]) for m in bench["per_layer"] if applies(m) and m["moves"] in moved]


def reader(name: str):
    path = HERE / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(f"chipbench.metrics.{name}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def run_cell(spec: Spec, seed: int, seconds: float, trace: bool, *, bench: dict,
             t_start: float, patch=None) -> dict:
    """Run the cell and return its result line as a dict (no chip check:
    ``main`` makes it)."""
    import jax

    from chipbench import count, serve, stats, train
    from chipbench import trace as tr

    devices = jax.devices()[: spec.chips]
    loop = {"serve_open_loop": serve, "train_steps": train}[spec.mix["loop"]]
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    try:
        tracer = Tracer(trace, trace_dir, seconds)
        rec = loop.run(spec, seed, seconds, tracer, t_start, devices[0], patch=patch)
        if trace:
            rec.trace = tr.load(trace_dir)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    dev = devices[0]
    rec.peaks = count.peaks(dev.device_kind) if dev.platform == "tpu" else \
        {"flops_bf16_per_s": float("nan"), "hbm_bytes_per_s": float("nan")}
    metrics = {}
    for name, unit in metric_names(spec, bench, trace):
        value = reader(name)(rec)
        if value is not None:
            metrics[name] = {"value": value, "unit": unit}
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices()),
              "memory_peak_bytes": rec.memory_peak_bytes}
    out = {
        "correct": correct(rec.checks),
        "attempted": rec.attempted,
        "failed": rec.failed,
        "metrics": metrics,
        "device": device,
    }
    if trace and rec.trace is not None:
        device["busy_s"] = rec.trace.busy_s
        device["window_s"] = rec.trace.window_s
        out["breakdown"] = {"device_ops": rec.trace.top_ops(), "idle_gaps": rec.trace.idle_gaps()}
    out["info"] = {"compiles_in_window": rec.compiles_in_window,
                   "generator_lag_ms": stats.generator_lag_ms(rec)}
    out["checks"] = rec.checks
    return out


def enable_compile_cache():
    """JAX's persistent compilation cache at a fixed path inside the
    checkout (or where ``JAX_COMPILATION_CACHE_DIR`` says), every program
    kept however fast it compiled."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = benchmark()
    spec = load_spec(args.workload, bench)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < spec.chips:
        print(f"chipbench: {args.workload} needs {spec.chips} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)", file=sys.stderr)
        return 1
    enable_compile_cache()
    out = run_cell(spec, args.seed, args.seconds, bool(args.trace), bench=bench,
                   t_start=T_START)
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})", file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
