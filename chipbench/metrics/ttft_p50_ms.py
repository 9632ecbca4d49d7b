"""Median of due time to first token over every request due in the window
(host clock); a request with no first token counts as inf."""
from chipbench import stats


def read(rec):
    return stats.nearest_rank(stats.ttfts_ms(rec), 0.50) if rec.requests else None
