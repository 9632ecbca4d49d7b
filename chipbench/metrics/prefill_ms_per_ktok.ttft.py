"""Device time of the prefill programs per 1000 prompt tokens admitted in
the traced window (device trace, harness counts)."""
from chipbench import stats


def read(rec):
    if rec.trace is None:
        return None
    toks = sum(sum(s.admitted) for s in stats.traced_steps(rec))
    t = rec.trace.program_s("prefill")
    return 1e3 * t / (toks / 1000) if toks and t > 0 else None
