"""Whole-step share of the chip's peak: needed FLOPs of every engine step
in the traced window (prefills and decode ticks; count.py) over those
steps' wall time at the peak (harness clock, peaks.json)."""
from chipbench import stats


def read(rec):
    return stats.serve_mfu_pct(rec)
