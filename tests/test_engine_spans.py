"""What a profiler trace of the serving and training programs holds: the
``engine.*`` host spans of ``ContinuousBatchingEngine`` (read back with
``jax.profiler.ProfileData``) and the named scopes that the compiled
decode, prefill and train programs carry in their op metadata."""
import dataclasses
import glob
import os
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from chipbench.scopes import hlo_scopes
from jax.profiler import ProfileData

from repro.configs import get_arch
from repro.models.registry import build_model
from repro.serve.engine import ContinuousBatchingEngine, Request
from repro.train.step import TrainConfig, init_train_state, make_optimizer, make_train_step

SLOTS, MAX_LEN, PROMPT = 2, 48, 12
#: the requests of the traced run: (rid, max_new); three are queued before
#: the first tick, two more after it
FIRST, LATER = [(0, 3), (1, 5), (2, 2)], [(7, 4), (8, 2)]
WAIT_S = 0.03
ADMIT_PARTS = ("engine.prefill", "engine.pad_cache", "engine.slot_write", "engine.first_token")
TICK_PARTS = ("engine.admit", "engine.decode", "engine.sample", "engine.retire")


@dataclasses.dataclass
class Span:
    name: str
    start: int  # ns on the trace's clock
    end: int
    args: dict

    def holds(self, other: "Span") -> bool:
        return self.start <= other.start and other.end <= self.end


def engine_spans(trace_dir: str) -> list[Span]:
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("engine."):
                    out.append(Span(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                                    dict(ev.stats)))
    return sorted(out, key=lambda s: (s.start, -s.end))


def _request(cfg, rid, max_new, rng):
    return Request(rid=rid, prompt=rng.integers(1, cfg.vocab_size, PROMPT).astype(np.int32),
                   max_new=max_new)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """A tiny engine served under ``jax.profiler.trace``: its spans, the
    filled slots at each decode call, and what each ``step`` returned."""
    cfg = get_arch("qwen3-0.6b").smoke()
    eng = ContinuousBatchingEngine(cfg, slots=SLOTS, max_len=MAX_LEN)
    rng = np.random.default_rng(0)
    eng.submit(_request(cfg, -1, 2, rng))  # compiles every shape outside the trace
    eng.run_to_completion()

    filled = []
    decode = eng._runner.decode

    def spy(caches, toks, pos):
        filled.append(sum(not s.free for s in eng.slots))
        return decode(caches, toks, pos)

    eng._runner.decode = spy
    trace_dir = str(tmp_path_factory.mktemp("engine_trace"))
    returned = []
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(trace_dir, profiler_options=opts):
        for rid, n in FIRST:
            eng.submit(_request(cfg, rid, n, rng))
        time.sleep(WAIT_S)
        returned.append(eng.step())
        for rid, n in LATER:
            eng.submit(_request(cfg, rid, n, rng))
        while eng.queue or any(not s.free for s in eng.slots):
            returned.append(eng.step())
    results = {r.rid: r for r in eng.run_to_completion()}
    return engine_spans(trace_dir), filled, returned, results


def named(spans, name):
    return [s for s in spans if s.name == name]


def test_one_step_span_per_step_call(traced):
    spans, _, returned, _ = traced
    assert len(named(spans, "engine.step")) == len(returned)


def test_one_admit_and_one_retire_per_request_sharing_its_rid(traced):
    spans, _, _, results = traced
    rids = sorted(rid for rid, _ in FIRST + LATER)
    assert sorted(s.args["rid"] for s in named(spans, "engine.admit")) == rids
    assert sorted(s.args["rid"] for s in named(spans, "engine.retire")) == rids
    assert sorted(results) == rids
    for s in named(spans, "engine.retire"):
        r = results[s.args["rid"]]
        assert (s.args["tokens"], s.args["ticks"]) == (len(r.tokens), r.ticks)
    for rid in rids:
        (admit,) = [s for s in named(spans, "engine.admit") if s.args["rid"] == rid]
        (retire,) = [s for s in named(spans, "engine.retire") if s.args["rid"] == rid]
        assert admit.end <= retire.start


def test_admit_args(traced):
    spans, _, _, _ = traced
    admits = named(spans, "engine.admit")
    assert all(s.args["prompt_len"] == PROMPT for s in admits)
    # the first tick fills both slots from a queue of three, after WAIT_S
    first = admits[:SLOTS]
    assert [s.args["rid"] for s in first] == [0, 1]
    assert [s.args["queued"] for s in first] == [2, 1]
    assert all(s.args["queue_wait_ms"] >= WAIT_S * 1e3 for s in first)
    assert all(s.args["queue_wait_ms"] >= 0 for s in admits)


def test_one_decode_span_per_tick_with_the_filled_slots(traced):
    spans, filled, returned, _ = traced
    decodes = named(spans, "engine.decode")
    assert len(decodes) == sum(returned) == len(filled)
    assert [s.args["active"] for s in decodes] == filled
    assert all(s.args["slots"] == SLOTS and 1 <= s.args["kv"] <= MAX_LEN for s in decodes)
    samples = named(spans, "engine.sample")
    assert [s.args["n"] for s in samples] == filled


def test_spans_nest(traced):
    spans, _, _, _ = traced
    steps = named(spans, "engine.step")
    for name in TICK_PARTS:
        for s in named(spans, name):
            assert sum(step.holds(s) for step in steps) == 1, s
    admits = named(spans, "engine.admit")
    for name in ADMIT_PARTS:
        parts = named(spans, name)
        assert len(parts) == len(admits)
        assert all(sum(a.holds(p) for a in admits) == 1 for p in parts), name
    for step in steps:
        inner = [s.name for s in spans if step.holds(s) and s.name in TICK_PARTS]
        # admissions first, then one decode and its sampling, then retirements
        order = [TICK_PARTS.index(n) for n in inner]
        assert order == sorted(order), inner


def _decode_text(cfg):
    eng = ContinuousBatchingEngine(cfg, slots=SLOTS, max_len=MAX_LEN)
    toks = jnp.zeros((SLOTS,), jnp.int32)
    return eng._runner._jit_decode.lower(eng.params, eng.caches, toks, toks).compile().as_text()


def _prefill_text(cfg):
    eng = ContinuousBatchingEngine(cfg, slots=SLOTS, max_len=MAX_LEN)
    batch = {"tokens": jnp.zeros((1, PROMPT), jnp.int32)}
    return eng._runner._jit_prefill.lower(eng.params, batch).compile().as_text()


def _train_text(cfg):
    api = build_model(cfg)
    tc = TrainConfig()
    opt = make_optimizer(tc)
    state = jax.eval_shape(lambda: init_train_state(api, opt, jax.random.PRNGKey(0)))
    batch = {"tokens": jax.ShapeDtypeStruct((2, 16), jnp.int32)}
    step = jax.jit(make_train_step(api, opt, tc), donate_argnums=(0,))
    return step.lower(state, batch).compile().as_text()


LAYER = {"attention", "ffn", "norm", "embed", "lm_head", "layer_scan"}


@pytest.mark.parametrize("program,text,scopes", [
    ("jit_decode", _decode_text, LAYER | {"kv_cache"}),
    ("jit_prefill", _prefill_text, LAYER),
    ("jit_train_step", _train_text, LAYER | {"loss"}),
], ids=["decode", "prefill", "train_step"])
def test_programs_carry_the_scopes_and_keep_their_jit_names(program, text, scopes):
    hlo = text(get_arch("qwen3-0.6b").smoke())
    assert hlo.startswith(f"HloModule {program},")
    assert scopes <= set(hlo_scopes(hlo).values())


def _stack_writes(hlo: str, shape) -> list[tuple[str, str]]:
    """(opcode, op_name) of each instruction that writes or copies a whole
    array of ``shape`` (any dtype, in any computation)."""
    dims = ",".join(map(str, shape))
    pat = re.compile(rf"%[\w.-]+ = \w+\[{dims}\]\S* (dynamic-update-slice|scatter|copy)\((.*)")
    out = []
    for m in pat.finditer(hlo):
        name = re.search(r'op_name="([^"]*)"', m.group(2))
        out.append((m.group(1), name.group(1) if name else ""))
    return out


@pytest.mark.parametrize("arch", ["stablelm-3b", "qwen3-0.6b"])
def test_decode_writes_only_the_new_rows_into_the_stacked_cache(arch):
    """The decode scan carries the stacked cache: no layer's cache is
    restacked and the whole stack is never copied; the only writes of its
    shape are the new rows', under ``layer_scan`` and ``kv_cache``."""
    cfg = get_arch(arch).smoke()
    hlo = _decode_text(cfg)
    caches = jax.eval_shape(lambda: build_model(cfg).init_cache(SLOTS, MAX_LEN))
    writes = _stack_writes(hlo, jax.tree.leaves(caches)[0].shape)
    assert writes
    row_write = re.compile(r"^jit\(decode\)/layer_scan/while/body/.*attention/kv_cache/")
    for op, name in writes:
        assert op != "copy" and row_write.match(name), (op, name)
