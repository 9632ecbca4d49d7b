"""99th percentile of the gaps between consecutive output tokens of all
requests due in the window (host clock)."""
from chipbench import stats


def read(rec):
    gaps = stats.itl_ms(rec)
    return stats.nearest_rank(gaps, 0.99) if gaps else None
