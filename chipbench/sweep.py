"""Find a serving cell's knee once: offer its traffic at each of a few
fixed rates to one warmed engine and print one line per rate.

    python chipbench/sweep.py --workload qwen3-0.6b.conv --rates 1,2,3,4 --seconds 20 --seed 5

Each line gives the requests due, the time to first token (median and
90th percentile), the 99th percentile inter-token gap, tokens per second,
and the backlog: requests handed over but unfinished when the window
closed. Past the knee the backlog grows with the rate and the TTFT tail
with the window. The cell's ``rate_per_s`` is set once, by hand, at about
four fifths of the highest rate that holds.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parent.parent),
                str(Path(__file__).resolve().parent.parent / "src")]

from chipbench import count, serve, stats, traffic  # noqa: E402
from chipbench.common import Record, Tracer  # noqa: E402
from chipbench.run import enable_compile_cache, load_spec  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="comma-separated requests per second")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--drain", type=float, default=serve.DRAIN_S,
                    help="seconds the window's requests may run on after it")
    args = ap.parse_args(argv)
    serve.DRAIN_S = args.drain
    spec = load_spec(args.workload)
    import jax

    if jax.devices()[0].platform != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return 1
    enable_compile_cache()
    engine = serve.build(spec, args.seed)
    p = spec.mix["prompt"]
    serve.warm_up(engine, spec, range(p["bucket"] * -(-p["min"] // p["bucket"]), p["max"] + 1,
                                      p["bucket"]))
    for rate in [float(r) for r in args.rates.split(",")]:
        rec = Record(spec=spec, seconds=args.seconds, sizes=count.Sizes.from_config(spec.hf))
        sched = traffic.schedule(spec.mix, rate, args.seconds, args.seed, spec.hf["vocab_size"])
        t = time.perf_counter()
        serve.window(engine, rec, sched, args.seconds, Tracer(False))
        ttft = stats.ttfts_ms(rec)
        print(json.dumps({
            "workload": spec.name, "rate_per_s": rate, "due": rec.attempted,
            "failed": rec.failed, "backlog_at_close": rec.backlog_at_close,
            "ttft_p50_ms": stats.nearest_rank(ttft, 0.5),
            "ttft_p90_ms": stats.nearest_rank(ttft, 0.9),
            "itl_p99_ms": stats.nearest_rank(stats.itl_ms(rec), 0.99),
            "tok_s": stats.window_tokens_per_s(rec),
            "drain_s": time.perf_counter() - t - args.seconds,
            "compiles_in_window": rec.compiles_in_window,
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
