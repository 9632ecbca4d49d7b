"""Share of its roofline the prefill program reaches: the least time the
chip needs for the traced window's prefills (matmuls, causal attention
over the lower triangle, weights read once; count.py), over the prefill
programs' device time (device trace)."""
from chipbench import stats


def read(rec):
    if rec.trace is None:
        return None
    t = rec.trace.program_s("prefill")
    lens = [n for s in stats.traced_steps(rec) for n in s.admitted]
    if not lens or t <= 0:
        return None
    return 100.0 * sum(stats.bound_s(rec, *rec.sizes.prefill(n)) for n in lens) / t
