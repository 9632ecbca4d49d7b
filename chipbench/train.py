"""The ``train_steps`` loop: the program's ``Trainer`` steps on its own
``SyntheticLM`` stream, batch after batch, for the whole window.

Set-up builds one trainer and one state from the seed's weights and takes
the first three steps through the window's own call and feed; those are
the steps the output check follows. The same trainer and state then run
the window: each step makes its batch on the host and dispatches the
jitted step, as ``Trainer.run`` does, with no checkpoint. Steps are
dispatched about ``AHEAD_S`` seconds ahead of the one whose loss is read,
so that the chip stays fed while the host stalls; when the window's time
is up nothing more is dispatched, every step sent is waited for, and the
window ends at that wait.
"""
from __future__ import annotations

import dataclasses
import math
import shutil
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import count, reference, weights
import collections

from chipbench.common import Record, Spec, Tracer, check, compile_counter, memory_peak

#: steps the output check follows from the reference
CHECKED_STEPS = 3
#: a leaf whose reference gradient is under this share of the median
#: leaf's moves under Adam by round-off alone, and is not compared
STILL_LEAF = 1e-3
#: seconds of steps dispatched ahead of the step whose loss is read
AHEAD_S = 5.0


@dataclasses.dataclass
class TrainStep:
    start: float  # host clock: the previous step's loss read, or the window's start
    end: float  # host clock: this step's loss read
    tokens: int
    loss: float


def optimizer(spec: Spec) -> dict:
    return dict(spec.mix["optimizer"])


def build(spec: Spec, seed: int):
    """The trainer and its fresh state, from the seed's float32 weights."""
    from repro.data.pipeline import DataConfig
    from repro.train.step import TrainConfig
    from repro.train.trainer import Trainer, TrainerConfig

    opt = optimizer(spec)
    tc = TrainConfig(lr=opt["lr"], warmup=opt["warmup"], total_steps=opt["total_steps"],
                     weight_decay=opt["weight_decay"], clip_norm=opt["clip_norm"])
    trainer = Trainer(spec.cfg, DataConfig(batch=spec.cell["batch"], seq_len=spec.mix["seq_len"],
                                           seed=seed),
                      tc, TrainerConfig(total_steps=opt["total_steps"],
                                        ckpt_dir=tempfile.mkdtemp(prefix="chipbench-ckpt-")))
    w = weights.make(spec.hf, seed, qk_norm=spec.qk_norm, vocab_rows=spec.cfg.padded_vocab,
                     dtype="float32")
    params = weights.to_program(w, spec.hf)
    del w
    state = {"params": params, "opt": trainer.optimizer.init(params),
             "step": jnp.zeros((), jnp.int32), "err": None}
    return trainer, state


def leaf_norms(tree) -> dict:
    """``path -> norm`` of every leaf of a program parameter tree."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    norms = jax.jit(lambda xs: [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))) for x in xs])(
        [x for _, x in flat])
    return {jax.tree_util.keystr(p): float(n) for (p, _), n in zip(flat, norms)}


def change_norms(params, spec: Spec, seed: int) -> dict:
    """Norm of each leaf's change from the seed's starting weights."""
    w0 = weights.to_program(weights.make(spec.hf, seed, qk_norm=spec.qk_norm,
                                         vocab_rows=spec.cfg.padded_vocab, dtype="float32"),
                            spec.hf)
    return leaf_norms(jax.tree.map(jnp.subtract, params, w0))


def dispatch(trainer, state, step: int):
    """Dispatch one step; its loss stays on the device."""
    batch = jax.tree.map(jnp.asarray, trainer.data.batch_at(step))
    state, metrics = trainer.train_step(state, batch)
    return state, metrics["loss"]


def one_step(trainer, state, step: int):
    state, loss = dispatch(trainer, state, step)
    return state, float(loss)


def run(spec: Spec, seed: int, seconds: float, tracer: Tracer, t_start: float,
        device, patch=None) -> Record:
    """One run of a training cell. ``patch(trainer)`` lets a test break the
    timed path."""
    rec = Record(spec=spec, seconds=seconds, sizes=count.Sizes.from_config(spec.hf))
    trainer, state = build(spec, seed)
    if patch is not None:
        patch(trainer)
    tokens = spec.cell["batch"] * spec.mix["seq_len"]
    state, readings = first_steps(trainer, state, spec, seed)
    step = CHECKED_STEPS
    pending = collections.deque()

    def read_oldest():
        loss = float(pending.popleft())
        last = rec.steps[-1].end if rec.steps else t0
        rec.steps.append(TrainStep(last, time.perf_counter(), tokens, loss))

    def ahead() -> int:
        """Steps in flight: ``AHEAD_S`` over the mean time a step has taken."""
        if not rec.steps:
            return 1
        return max(1, math.ceil(AHEAD_S * len(rec.steps) / (rec.steps[-1].end - t0)))

    t0 = time.perf_counter()
    rec.setup_s = t0 - t_start
    with compile_counter() as comp:
        while True:
            now = time.perf_counter()
            tracer.maybe_start(now, t0)
            tracer.maybe_stop(now, t0)
            if now >= t0 + seconds:
                break
            with tracer.span("train_step"):
                state, loss = dispatch(trainer, state, step)
            pending.append(loss)
            step += 1
            if len(pending) > ahead():
                read_oldest()
        while pending:
            read_oldest()
    tracer.maybe_stop(time.perf_counter(), t0, force=True)
    rec.trace_window = tuple(tracer.host_window) if tracer.host_window else None
    rec.window = (t0, rec.steps[-1].end if rec.steps else t0 + seconds)
    rec.compiles_in_window = comp["compiles"]
    rec.memory_peak_bytes = memory_peak(device)
    rec.attempted = len(rec.steps)
    rec.failed = sum(not math.isfinite(s.loss) for s in rec.steps)
    shutil.rmtree(trainer.tcfg.ckpt_dir, ignore_errors=True)
    del trainer, state
    rec.checks = check_steps(spec, seed, *readings)
    return rec


def first_steps(trainer, state, spec: Spec, seed: int):
    """The first ``CHECKED_STEPS`` steps through the window's own call and
    feed, and what the output check reads of them: each step's loss, the
    norm of each leaf's first clipped gradient (Adam's first moment after
    one step, over ``1 - b1``), and of each leaf's change after the last."""
    b1 = optimizer(spec)["b1"]
    losses, grads = [], {}
    for k in range(CHECKED_STEPS):
        state, loss = one_step(trainer, state, k)
        losses.append(loss)
        if k == 0:
            grads = {p: n / (1 - b1) for p, n in leaf_norms(state["opt"].mu).items()}
    return state, (losses, grads, change_norms(state["params"], spec, seed))


def reference_steps(spec: Spec, seed: int, quant=None, batch_rows=None):
    """The reference's losses, first clipped gradient norms and change
    norms over the first ``CHECKED_STEPS`` steps on the trainer's batches,
    by program leaf. ``batch_rows`` keeps only those rows of each batch (a
    fault: the mean over part of the batch)."""
    from repro.data.pipeline import DataConfig, SyntheticLM

    data = SyntheticLM(spec.cfg, DataConfig(batch=spec.cell["batch"],
                                            seq_len=spec.mix["seq_len"], seed=seed))
    mk = dict(qk_norm=spec.qk_norm, vocab_rows=spec.cfg.padded_vocab, dtype="float32")
    w = weights.make(spec.hf, seed, **mk)
    m = {k: jnp.zeros_like(x) for k, x in w.items()}
    v = {k: jnp.zeros_like(x) for k, x in w.items()}
    kw = dict(hf_items=weights.hf_items(spec.hf), qk_norm=spec.qk_norm,
              opt_items=tuple(sorted(optimizer(spec).items())), quant=quant)
    losses, grads = [], {}
    with jax.default_matmul_precision("highest"):
        for k in range(CHECKED_STEPS):
            toks = data.batch_at(k)["tokens"]
            if batch_rows is not None:
                toks = toks[batch_rows]
            w, m, v, loss, gn = reference.train_step(w, m, v, jnp.asarray(toks), k + 1, **kw)
            losses.append(float(loss))
            if k == 0:
                grads = by_program_leaf(gn, spec)
    del m, v
    w0 = weights.make(spec.hf, seed, **mk)
    delta = {k: jnp.sqrt(jnp.sum(jnp.square(w[k] - w0[k]))) for k in w}
    return losses, grads, by_program_leaf(delta, spec)


def by_program_leaf(scalars: dict, spec: Spec) -> dict:
    """Per-weight numbers keyed as the program's parameter leaves."""
    tree = weights.to_program(scalars, spec.hf, grads=True)
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(p): float(x) for p, x in flat}


def worst_leaf_gap(got: dict, ref: dict, leaves) -> float:
    """The largest gap between the program's and the reference's norm of
    a leaf, over the larger of that leaf's reference norm and the median
    leaf's."""
    med = float(np.median([ref[k] for k in ref]))
    return max(abs(got[k] - ref[k]) / max(ref[k], med) for k in leaves)


def compare(spec: Spec, losses, grads, changes, ref) -> dict:
    """The three numbers compared (without limits)."""
    r_losses, r_grads, r_changes = ref
    med = float(np.median(list(r_grads.values())))
    moving = [k for k in r_grads if r_grads[k] >= STILL_LEAF * med]
    return {
        "loss_gap": max(abs(a - b) / abs(b) for a, b in zip(losses, r_losses)),
        "grad_gap": worst_leaf_gap(grads, r_grads, r_grads),
        "change_gap": worst_leaf_gap(changes, r_changes, moving),
    }


def with_limits(spec: Spec, got: dict) -> dict:
    lim = spec.cell["limits"]
    return {k: check(v, lim[k]) for k, v in got.items()}


def check_steps(spec: Spec, seed: int, losses, grads, changes) -> dict:
    return with_limits(spec, compare(spec, losses, grads, changes, reference_steps(spec, seed)))
