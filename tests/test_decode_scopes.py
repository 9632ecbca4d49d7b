"""The benchmark's reader of the decode program's named scopes
(``chipbench/scopes.py``) and the per-layer metric built on it,
``decode_kv_ms_per_tick.itl``: scopes from compiled HLO, and device time
per decode run worked out by hand on a synthetic trace."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest
from chipbench import run, scopes, serve
from chipbench.common import Record, compile_counter
from chipbench.tests import smoke
from chipbench.trace import Event, TraceSummary
from jax.experimental.compilation_cache import compilation_cache

from repro.models.registry import build_model

KV = "decode_kv_ms_per_tick.itl"


@pytest.mark.parametrize("op_name,scope", [
    ("jit(decode)/layer_scan/while/body/closed_call/attention/kv_cache/vmap(vmap())/scatter",
     "kv_cache"),
    ("jit(decode)/layer_scan/while/body/dynamic_update_slice", "layer_scan"),
    ("jit(train_step)/transpose(jvp(layer_scan))/while/body/closed_call/checkpoint/norm/mul",
     "norm"),
    ("jit(prefill)/lm_head/dot_general", "lm_head"),
    ("jit(decode)/add", "(none)"),
    ("x", "(none)"),
])
def test_innermost_scope(op_name, scope):
    assert scopes.innermost(op_name) == scope


def test_hlo_scopes_reads_each_instruction():
    text = "\n".join([
        'HloModule jit_decode, is_scheduled=true',
        '  %copy.76 = bf16[12,4096,8,128]{3,1,2,0} copy(%p), '
        'metadata={op_name="jit(decode)/layer_scan/while/body/closed_call/attention/kv_cache/'
        'vmap(vmap())/scatter" stack_frame_id=3}',
        '  ROOT %fusion.5 = bf16[28,12,4096,8,128]{4,3,2,1,0} fusion(%a, %b), kind=kLoop, '
        'metadata={op_name="jit(decode)/layer_scan/while/body/dynamic_update_slice"}',
        '  %param.1 = s32[12]{0} parameter(1)',
    ])
    assert scopes.hlo_scopes(text) == {"copy.76": "kv_cache", "fusion.5": "layer_scan"}


def kv_trace():
    """Three decode runs; the first is the trace's first program run (cut by
    the profiler's start) and the last ends after the window."""
    E = Event
    mods = [[E("jit_decode(1)", 1.0, 1.4), E("jit_decode(1)", 2.0, 2.5),
             E("jit_scatter(2)", 2.6, 2.7), E("jit_decode(1)", 3.0, 3.6),
             E("jit_decode(1)", 4.0, 4.5), E("jit_prefill(3)", 4.9, 5.2)]]
    ops = [[
        E("%copy.76 = c(x)", 1.0, 1.1),  # first run: not counted
        E("%while.1 = w(x)", 2.0, 2.5),  # holds the body's ops
        E("%copy.76 = c(x)", 2.0, 2.1), E("%fusion.5 = f(x)", 2.1, 2.3),
        E("%copy.77 = c(x)", 2.3, 2.35), E("%copy.76 = c(x)", 2.6, 2.7),  # jit_scatter's own
        E("%copy.76 = c(x)", 3.0, 3.2), E("%fusion.9 = f(x)", 3.2, 3.6),
        E("%copy.77 = c(x)", 4.0, 4.2),  # last run: ends after the window
    ]]
    spans = [E("chipbench.window", 0.5, 4.3), E("chipbench.step", 1.9, 2.8)]
    return TraceSummary.build(ops, mods, spans)


TABLE = {"copy.76": "kv_cache", "copy.77": "kv_cache", "fusion.5": "layer_scan",
         "fusion.9": "ffn", "while.1": "layer_scan"}


def test_scope_time_per_whole_decode_run_by_hand():
    t = kv_trace()
    assert [(e.start, e.end) for e in scopes.whole_runs(t, "decode")] == [(2.0, 2.5), (3.0, 3.6)]
    # run 1: copy.76 0.1 + copy.77 0.05; run 2: copy.76 0.2 (jit_scatter's copy lies outside)
    assert scopes.scope_s_per_run(t, "decode", "kv_cache", TABLE) == pytest.approx(0.35 / 2)
    assert scopes.scope_s_per_run(t, "decode", "ffn", TABLE) == pytest.approx(0.4 / 2)
    assert scopes.scope_s_per_run(t, "prefill", "ffn", TABLE) is None


def _record(trace):
    spec = dataclasses.replace(smoke.serve_spec(), name="qwen3-0.6b.conv")
    rec = Record(spec=spec, seconds=4.0)
    rec.trace = trace
    return rec


def test_reader_by_hand(monkeypatch):
    monkeypatch.setattr(scopes, "decode_scopes", lambda spec: TABLE)
    assert run.reader(KV)(_record(kv_trace())) == pytest.approx(1e3 * 0.35 / 2)


def test_reader_reads_nothing_from_a_program_without_the_scope(monkeypatch):
    monkeypatch.setattr(scopes, "decode_scopes", lambda spec: {"copy.76": "(none)"})
    assert run.reader(KV)(_record(kv_trace())) is None
    assert run.reader(KV)(_record(None)) is None


def test_decode_scopes_match_the_engines_own_program():
    """The reader compiles the decode program again from the cell's shapes;
    its instructions must be the ones the engine runs, under the same
    scopes."""
    spec = smoke.serve_spec()
    engine = serve.build(spec, 2**31 + 5)
    z = jnp.zeros((len(engine.slots),), jnp.int32)
    own = engine._runner._jit_decode.lower(engine.params, engine.caches, z, z)
    table = scopes.decode_scopes(spec)
    assert table == scopes.hlo_scopes(own.compile().as_text())
    assert {"kv_cache", "attention", "ffn", "layer_scan", "norm", "embed", "lm_head"} <= set(
        table.values())


def test_decode_scopes_compile_afresh_past_a_warm_cache(tmp_path):
    """A persistent cache keys programs without their metadata, so a warm
    one may hold the decode program compiled without scopes: the reader
    compiles its own, and leaves the cache on."""
    spec = smoke.serve_spec()
    api = build_model(spec.cfg)
    slots, max_len = spec.cell["slots"], spec.cell["max_len"]
    params = jax.eval_shape(api.init, jax.random.PRNGKey(0))
    caches = jax.eval_shape(lambda: api.init_cache(slots, max_len))
    pos = jax.ShapeDtypeStruct((slots,), jnp.int32)

    def compile_decode():
        jax.jit(api.decode, donate_argnums=(1,)).lower(params, caches, pos, pos).compile()

    saved = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir", "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    try:
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        compilation_cache.reset_cache()
        compile_decode()
        with compile_counter() as warm:
            compile_decode()
        with compile_counter() as reader:
            scopes.decode_scopes(spec)
        with compile_counter() as after:
            compile_decode()
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
    assert (warm["compiles"], reader["compiles"], after["compiles"]) == (0, 1, 0)
