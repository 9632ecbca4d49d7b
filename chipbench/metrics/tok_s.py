"""Prompt tokens prefilled plus output tokens produced inside the window,
per second of the window (host clock)."""
from chipbench import stats


def read(rec):
    return stats.window_tokens_per_s(rec) if rec.requests else None
