"""Seconds from the start of the run to the start of the window: weights,
engine or trainer, warm-up and any compilation (host clock)."""


def read(rec):
    return rec.setup_s
