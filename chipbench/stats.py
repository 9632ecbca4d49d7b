"""Arithmetic the metric readers share: percentiles, rates, and the
needed work of the steps inside the traced window."""
from __future__ import annotations

import math


def nearest_rank(values, q: float) -> float:
    """The ``q`` quantile by nearest rank: the smallest value with at least
    ``q`` of the values at or below it. ``inf`` sorts last."""
    xs = sorted(values)
    if not xs:
        raise ValueError("no values")
    return xs[max(0, math.ceil(q * len(xs)) - 1)]


def ttfts_ms(rec) -> list[float]:
    """Due time to first token of every request due in the window, in ms;
    ``inf`` for a request that never produced one."""
    return [(r.token_times[0] - r.due) * 1e3 if r.token_times else math.inf
            for r in rec.requests.values()]


def itl_ms(rec) -> list[float]:
    """Every gap between consecutive output tokens of the window's
    requests, in ms (tokens one step produced together are 0 apart)."""
    out = []
    for r in rec.requests.values():
        t = r.token_times
        out += [(b - a) * 1e3 for a, b in zip(t, t[1:])]
    return out


def generator_lag_ms(rec) -> float:
    """How late the harness handed the latest request over, past its due
    time (ms): a starved generator would show here, not as a fast server."""
    return max(((r.submitted - r.due) * 1e3 for r in rec.requests.values() if r.submitted),
               default=0.0)


def window_tokens_per_s(rec) -> float:
    """Prompt tokens prefilled and output tokens produced inside the
    window, over the window's seconds."""
    t0, t1 = rec.window
    n = 0
    for r in rec.requests.values():
        inside = [t for t in r.token_times if t0 <= t <= t1]
        n += len(inside)
        if r.token_times and t0 <= r.token_times[0] <= t1:
            n += len(r.prompt)
    return n / (t1 - t0)


def traced_steps(rec) -> list:
    """The harness's steps that lie wholly inside the traced window."""
    return [s for s in rec.steps if rec.in_trace(s.start, s.end)]


def idle_pct(rec):
    if rec.trace is None or not rec.trace.n_devices or rec.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - rec.trace.busy_s / rec.trace.window_s)


def bound_s(rec, flops: float, nbytes: float) -> float:
    """The least time the chip could take: the larger of operations over
    peak and bytes over bandwidth."""
    return max(flops / rec.peaks["flops_bf16_per_s"], nbytes / rec.peaks["hbm_bytes_per_s"])


def serve_step_flops(rec, step) -> float:
    f = sum(rec.sizes.prefill(n)[0] for n in step.admitted)
    if step.decode_kv:
        f += rec.sizes.decode(step.decode_kv)[0]
    return f


def serve_mfu_pct(rec):
    """Needed FLOPs of the engine's steps in the traced window over their
    wall time at the chip's peak: the whole step's share of the peak."""
    steps = traced_steps(rec)
    wall = sum(s.end - s.start for s in steps)
    if not steps or wall <= 0:
        return None
    return 100.0 * sum(serve_step_flops(rec, s) for s in steps) / (
        wall * rec.peaks["flops_bf16_per_s"])
