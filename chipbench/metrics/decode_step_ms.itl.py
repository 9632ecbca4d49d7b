"""Device time of the decode program per decode tick: the mean over its runs
that lie wholly inside the traced window (device trace)."""


def read(rec):
    t = rec.trace.program_run_s("decode") if rec.trace else None
    return 1e3 * t if t is not None else None
