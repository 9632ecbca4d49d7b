"""The ``serve_open_loop`` loop: requests arrive on a seeded Poisson
schedule at the cell's fixed rate and go to ``ContinuousBatchingEngine``
through ``submit``; the harness calls ``step`` whenever the engine has work
and waits for the next arrival when it has none.

Each request is timed from when it was due. The engine hands its tokens
over when ``step`` returns, so every token a step produced is stamped with
that step's end: a request's first token is its time to first token, and
the gaps between consecutive stamps are its inter-token gaps. After the
window closes no request is added, and those in flight run to the end
(at most ``DRAIN_S``) so that every request due in the window counts.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import count, reference, traffic, weights
from chipbench.common import Record, Spec, Tracer, check, compile_counter, memory_peak

#: how long requests due in the window may run on after it closes
DRAIN_S = 60.0
#: the output check's sample: at least this many served tokens and requests
CHECK_TOKENS, CHECK_MIN_REQS, CHECK_MAX_REQS = 300, 4, 32


@dataclasses.dataclass
class Req:
    rid: int
    due: float  # host perf_counter time the request was due
    prompt: np.ndarray
    max_new: int
    submitted: float = 0.0
    token_times: list = dataclasses.field(default_factory=list)
    tokens: list = None  # served tokens, once finished

    @property
    def finished(self) -> bool:
        return self.tokens is not None


@dataclasses.dataclass
class Step:
    start: float
    end: float
    admitted: list  # prompt lengths prefilled in this step
    decode_kv: list  # attended positions of each sequence the tick decoded


def build(spec: Spec, seed: int):
    """The engine with the seed's weights, as served (bf16)."""
    from repro.serve.engine import ContinuousBatchingEngine

    w = weights.make(spec.hf, seed, qk_norm=spec.qk_norm,
                     vocab_rows=spec.cfg.padded_vocab, dtype=spec.cell["weights_dtype"])
    params = weights.to_program(w, spec.hf)
    del w
    return ContinuousBatchingEngine(spec.cfg, slots=spec.cell["slots"],
                                    max_len=spec.cell["max_len"], params=params)


class Tracker:
    """Reads which tokens each ``step`` produced from the engine's slots
    and its list of finished results."""

    def __init__(self, engine, reqs: dict):
        self.engine, self.reqs = engine, reqs
        self.seen: dict[int, int] = {}
        self.done_at = len(engine.done)

    def after_step(self, start: float, end: float) -> Step:
        eng = self.engine
        now = {s.req.rid: len(s.emitted) for s in eng.slots if s.req is not None}
        for r in eng.done[self.done_at:]:
            now[r.rid] = len(r.tokens)
            if r.rid in self.reqs:
                self.reqs[r.rid].tokens = list(r.tokens)
        self.done_at = len(eng.done)
        step = Step(start, end, [], [])
        for rid, n in now.items():
            prev = self.seen.get(rid, 0)
            if n <= prev:
                continue
            self.seen[rid] = n
            req = self.reqs.get(rid)
            if req is None:  # a warm-up request
                continue
            req.token_times += [end] * (n - prev)
            decoded = n - prev - (1 if prev == 0 else 0)
            if prev == 0:
                step.admitted.append(len(req.prompt))
            if decoded > 0:
                step.decode_kv.append(len(req.prompt) + n - 1)
        return step


def warm_up(engine, spec: Spec, prompt_lens):
    """Serve one short request of every prompt length in ``prompt_lens``,
    and enough more to fill every slot: each prefill shape, cache growth,
    slot write and the decode tick compile (or load) here."""
    from repro.serve.engine import Request

    lens = list(prompt_lens)
    lens += [lens[0]] * max(0, spec.cell["slots"] - len(lens))
    rng = np.random.default_rng(0)
    for i, n in enumerate(lens):
        engine.submit(Request(rid=-1 - i, prompt=rng.integers(3, spec.hf["vocab_size"], n,
                                                              dtype=np.int32), max_new=2))
    while engine.step():
        pass
    jax.block_until_ready(engine.caches)


def window(engine, rec: Record, sched, seconds: float, tracer: Tracer):
    """Offer ``sched`` to the engine for ``seconds`` from now, then let the
    requests due in the window finish (at most ``DRAIN_S`` more)."""
    from repro.serve.engine import Request

    t0 = time.perf_counter()
    reqs = {a.rid: Req(a.rid, t0 + a.due_s, a.prompt, a.max_new) for a in sched}
    order = sorted(reqs.values(), key=lambda r: r.due)
    tracker = Tracker(engine, reqs)
    t_end = t0 + seconds
    nxt = 0

    def submit_due(now):
        nonlocal nxt
        while nxt < len(order) and order[nxt].due <= now:
            r = order[nxt]
            r.submitted = now
            engine.submit(Request(rid=r.rid, prompt=r.prompt, max_new=r.max_new))
            nxt += 1

    def step():
        s = time.perf_counter()
        with tracer.span("step"):
            busy = engine.step()
        e = time.perf_counter()
        rec.steps.append(tracker.after_step(s, e))
        return busy

    with compile_counter() as comp:
        while True:
            now = time.perf_counter()
            tracer.maybe_start(now, t0)
            tracer.maybe_stop(now, t0)
            if now >= t_end:
                break
            with tracer.span("submit"):
                submit_due(now)
            if not step():
                wake = min(order[nxt].due if nxt < len(order) else t_end, t_end)
                with tracer.span("idle"):
                    time.sleep(max(0.0, wake - time.perf_counter()))
    rec.window = (t0, t_end)
    tracer.maybe_stop(time.perf_counter(), t0, force=True)
    rec.trace_window = tuple(tracer.host_window) if tracer.host_window else None
    rec.compiles_in_window = comp["compiles"]
    rec.backlog_at_close = sum(not r.finished for r in order[:nxt])
    submit_due(t_end)  # due in the window, not yet handed over
    while any(not r.finished for r in order) and time.perf_counter() < t_end + DRAIN_S:
        step()
    jax.block_until_ready(engine.caches)
    rec.requests = reqs
    rec.attempted = len(order)
    rec.failed = sum(not r.finished for r in order)


def run(spec: Spec, seed: int, seconds: float, tracer: Tracer, t_start: float,
        device, patch=None) -> Record:
    """One run of a serving cell: set-up, the window, the drain, then the
    output check. ``patch(engine)`` lets a test break the timed path."""
    rec = Record(spec=spec, seconds=seconds, sizes=count.Sizes.from_config(spec.hf))
    engine = build(spec, seed)
    if patch is not None:
        patch(engine)
    rate = spec.cell["rate_per_s"]
    warm_up(engine, spec, traffic.prompt_buckets(spec.mix, rate, seconds))
    sched = traffic.schedule(spec.mix, rate, seconds, seed, spec.hf["vocab_size"])
    rec.setup_s = time.perf_counter() - t_start
    window(engine, rec, sched, seconds, tracer)
    rec.memory_peak_bytes = memory_peak(device)
    del engine
    rec.checks = check_outputs(spec, seed, rec.requests)
    return rec


def sample(reqs: dict, seed: int) -> list:
    """The finished requests the output check reads: the one that served
    the most tokens, then others in an order drawn from the seed, until
    ``CHECK_TOKENS`` served tokens and ``CHECK_MIN_REQS`` requests."""
    done = sorted((r for r in reqs.values() if r.finished and r.tokens),
                  key=lambda r: (-len(r.tokens), -len(r.prompt), r.rid))
    if not done:
        return []
    rng = np.random.default_rng([seed, 1])
    rest = [done[i] for i in 1 + rng.permutation(len(done) - 1)]
    out, n = [done[0]], len(done[0].tokens)
    for r in rest:
        if (n >= CHECK_TOKENS and len(out) >= CHECK_MIN_REQS) or len(out) >= CHECK_MAX_REQS:
            break
        out.append(r)
        n += len(r.tokens)
    return out


def teacher_inputs(r: Req, seq_len: int, n_max: int):
    """The prompt followed by the served tokens (the last one never fed
    back), padded to ``seq_len``; the positions whose logits chose each
    served token, padded to ``n_max``."""
    toks = np.zeros(seq_len, np.int32)
    seq = np.concatenate([r.prompt, np.asarray(r.tokens[:-1], np.int32)])
    toks[: len(seq)] = seq
    n = len(r.tokens)
    pos = np.full(n_max, len(r.prompt) - 1, np.int32)
    pos[:n] = len(r.prompt) - 1 + np.arange(n)
    served = np.zeros(n_max, np.int32)
    served[:n] = r.tokens
    return toks, pos, served, n


def served_gaps(spec: Spec, seed: int, reqs: list, quant=None) -> list[np.ndarray]:
    """For each request, at each served token: by how much the reference's
    logit of that token lies below the reference's best. With ``quant``,
    the token read is not the served one but the one the reference at that
    precision puts first (the control)."""
    w = weights.make(spec.hf, seed, qk_norm=spec.qk_norm,
                     vocab_rows=spec.cfg.padded_vocab, dtype=spec.cell["weights_dtype"])
    kw = dict(hf_items=weights.hf_items(spec.hf), qk_norm=spec.qk_norm)
    seq_len, n_max = spec.cell["max_len"], spec.mix["output"]["max"]
    out = []
    with jax.default_matmul_precision("highest"):
        for r in reqs:
            toks, pos, served, n = teacher_inputs(r, seq_len, n_max)
            toks, pos = jnp.asarray(toks), jnp.asarray(pos)
            ref = reference.logits_at(w, toks, pos, **kw)
            pick = jnp.asarray(served) if quant is None else \
                jnp.argmax(reference.logits_at(w, toks, pos, quant=quant, **kw), -1)
            gap = jnp.max(ref, -1) - jnp.take_along_axis(ref, pick[:, None], -1)[:, 0]
            out.append(np.asarray(gap)[:n])
    return out


def check_outputs(spec: Spec, seed: int, reqs: dict, quant=None) -> dict:
    """The numbers compared, each beside its limit: the widest logit gap
    of a served token under the reference's best, and how many finished
    requests served another number of tokens than they asked for. With
    ``quant`` the control stands in the program's place (``served_gaps``)."""
    short = sum(1 for r in reqs.values() if r.finished and len(r.tokens) != r.max_new)
    gaps = served_gaps(spec, seed, sample(reqs, seed), quant=quant)
    widest = float(max((g.max() for g in gaps), default=float("inf")))
    return {
        "logit_gap": check(widest, spec.cell["limits"]["logit_gap"]),
        "wrong_length": check(short, 0),
    }
