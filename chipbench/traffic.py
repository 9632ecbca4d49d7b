"""One generator for every serving mix: an open-loop schedule of requests
from a mix's parameters (``mixes/<mix>.json``), the cell's rate and the run's
seed.

Every seed gets the same work in another order. Prompt lengths, output
lengths and inter-arrival gaps are each taken at the ``n`` quantile points
``(i + 0.5) / n`` of their distributions, and the seed permutes each list
independently and draws the prompt tokens. So two seeds offer the same
number of requests, the same multiset of shapes and the same total load,
and differ only in which request comes when. Arrivals are Poisson: the gaps
are the exponential distribution's quantiles, shuffled.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass
class Arrival:
    rid: int
    due_s: float  # offset from the window's start
    prompt: np.ndarray  # (L,) int32
    max_new: int


def quantile_points(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths at the quantile points of ``spec``: a lognormal of
    ``median`` and ``sigma``, clipped to ``[min, max]``, then rounded up to a
    multiple of ``bucket`` (and clipped again, so the top bucket is ``max``)."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    z = np.array([NormalDist().inv_cdf(u) for u in quantile_points(n)])
    x = spec["median"] * np.exp(spec["sigma"] * z)
    x = np.clip(np.round(x), spec["min"], spec["max"]).astype(np.int64)
    bucket = spec.get("bucket", 1)
    x = -(-x // bucket) * bucket
    return np.minimum(x, spec["max"])


def gaps(spec: dict, n: int, rate: float, seconds: float) -> np.ndarray:
    """``n`` inter-arrival gaps at the quantile points of the arrival
    process, scaled so the last request is due just inside the window."""
    if spec["process"] != "poisson":
        raise ValueError(f"unknown arrival process {spec['process']!r}")
    g = -np.log1p(-quantile_points(n)) / rate
    return g * (seconds * (1.0 - 0.5 / n) / g.sum())


def schedule(mix: dict, rate: float, seconds: float, seed: int, vocab: int) -> list[Arrival]:
    """The requests due in a window of ``seconds`` at ``rate`` per second,
    sorted by due time."""
    n = max(1, math.floor(rate * seconds))
    rng = np.random.default_rng(seed)
    prompt_lens = rng.permutation(lengths(mix["prompt"], n))
    out_lens = rng.permutation(lengths(mix["output"], n))
    due = np.cumsum(rng.permutation(gaps(mix["arrivals"], n, rate, seconds)))
    return [
        Arrival(rid=i, due_s=float(due[i]),
                prompt=rng.integers(3, vocab, int(prompt_lens[i]), dtype=np.int32),
                max_new=int(out_lens[i]))
        for i in range(n)
    ]


def prompt_buckets(mix: dict, rate: float, seconds: float) -> list[int]:
    """The distinct prompt lengths a window's schedule uses (the same for
    every seed): the prefill shapes to warm up."""
    n = max(1, math.floor(rate * seconds))
    return sorted({int(x) for x in lengths(mix["prompt"], n)})
