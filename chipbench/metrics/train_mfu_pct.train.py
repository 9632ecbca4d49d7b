"""Model FLOP utilization of training: forward and backward FLOPs per
token (6 per matmul weight plus causal attention; count.py, recomputation
not counted) times the tokens of the traced window's steps, over those
steps' wall time at the chip's peak (harness clock, peaks.json)."""
from chipbench import stats


def read(rec):
    steps = stats.traced_steps(rec)
    wall = sum(s.end - s.start for s in steps)
    if not steps or wall <= 0:
        return None
    per_tok = rec.sizes.train_flops_per_token(rec.spec.mix["seq_len"])
    return 100.0 * per_tok * sum(s.tokens for s in steps) / (wall * rec.peaks["flops_bf16_per_s"])
