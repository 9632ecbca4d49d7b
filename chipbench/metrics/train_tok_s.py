"""Tokens of every train step completed in the window, over the window's
seconds, from its start to the end of its last step (host clock)."""


def read(rec):
    if not rec.steps or not hasattr(rec.steps[0], "tokens"):
        return None
    t0, t1 = rec.window
    return sum(s.tokens for s in rec.steps) / (t1 - t0)
