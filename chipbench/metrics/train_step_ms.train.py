"""Device time of the train step program per step: the mean over its runs
that lie wholly inside the traced window (device trace)."""


def read(rec):
    t = rec.trace.program_run_s("train_step") if rec.trace else None
    return 1e3 * t if t is not None else None
