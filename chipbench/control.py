"""Readings that set a cell's limits: the program's numbers on many seeds
and the control's, at the cell's own size, on the chip.

    python chipbench/control.py --workload qwen3-0.6b.conv --seeds 1,2,3 --seconds 10

Serving: each seed's weights serve the cell's traffic at its rate for a
short window and its drain; the run's own output check is made twice on
its sample, once on the served tokens and once with the control in the
program's place (the token the float8 reference puts first), and each
gives its widest logit gap and whether the run would be ``correct``.
Training: the first three steps of the program, the float8 reference, and
the reference fed half of each batch, each against the float32 reference
and judged by the cell's limits. One JSON line per seed. The benchmark's
own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parent.parent),
                str(Path(__file__).resolve().parent.parent / "src")]

from chipbench import count, serve, train, traffic  # noqa: E402
from chipbench.common import Record, Tracer, correct  # noqa: E402
from chipbench.run import enable_compile_cache, load_spec  # noqa: E402


def serve_readings(spec, seed: int, seconds: float) -> dict:
    engine = serve.build(spec, seed)
    rate = spec.cell["rate_per_s"]
    serve.warm_up(engine, spec, traffic.prompt_buckets(spec.mix, rate, seconds))
    rec = Record(spec=spec, seconds=seconds, sizes=count.Sizes.from_config(spec.hf))
    serve.window(engine, rec, traffic.schedule(spec.mix, rate, seconds, seed, spec.hf["vocab_size"]),
                 seconds, Tracer(False))
    del engine
    prog = serve.check_outputs(spec, seed, rec.requests)
    ctrl = serve.check_outputs(spec, seed, rec.requests, quant="fp8")
    return {"requests": rec.attempted, "failed": rec.failed,
            "program_gap": prog["logit_gap"]["value"], "program_correct": correct(prog),
            "control_gap": ctrl["logit_gap"]["value"], "control_correct": correct(ctrl),
            "limit": prog["logit_gap"]["limit"]}


def train_readings(spec, seed: int) -> dict:
    ref = train.reference_steps(spec, seed)
    trainer, state = train.build(spec, seed)
    state, readings = train.first_steps(trainer, state, spec, seed)
    del trainer, state
    half = slice(0, spec.cell["batch"] // 2)
    runs = {
        "program": train.compare(spec, *readings, ref),
        "control_fp8": train.compare(spec, *train.reference_steps(spec, seed, quant="fp8"), ref),
        "half_batch": train.compare(spec, *train.reference_steps(spec, seed, batch_rows=half), ref),
    }
    return {name: {**got, "correct": correct(train.with_limits(spec, got))}
            for name, got in runs.items()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    spec = load_spec(args.workload)
    import jax

    if jax.devices()[0].platform != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 1
    enable_compile_cache()
    for seed in [int(s) for s in args.seeds.split(",")]:
        t = time.perf_counter()
        if spec.mix["loop"] == "serve_open_loop":
            out = serve_readings(spec, seed, args.seconds)
        else:
            out = train_readings(spec, seed)
        print(json.dumps({"workload": spec.name, "seed": seed, **out,
                          "seconds": time.perf_counter() - t}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
