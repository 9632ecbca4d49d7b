"""Share of its roofline the decode program reaches: the least time the
chip needs for the traced window's decode ticks (weights read once per
tick, the KV of the active sequences at their live lengths; count.py),
over the decode program's device time (device trace)."""
from chipbench import stats


def read(rec):
    if rec.trace is None:
        return None
    t = rec.trace.program_s("decode")
    ticks = [s.decode_kv for s in stats.traced_steps(rec) if s.decode_kv]
    if not ticks or t <= 0:
        return None
    need = sum(stats.bound_s(rec, *rec.sizes.decode(kv)) for kv in ticks)
    return 100.0 * need / t
