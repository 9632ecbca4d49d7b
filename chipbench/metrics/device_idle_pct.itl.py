"""Share of the traced window in which no operation ran on the device:
1 - (union of device op intervals) / window (device trace)."""
from chipbench import stats


def read(rec):
    return stats.idle_pct(rec)
