"""Named scopes of the decode program's device operations.

The profiler's device events name each operation only by its HLO
instruction (``%copy.76``); the named scope it ran under is in the compiled
program's ``op_name`` metadata (``jit(decode)/layer_scan/while/body/
closed_call/attention/kv_cache/...``). ``decode_scopes`` compiles the
cell's decode program again from its shapes, which gives the instructions
the names they have in the engine's program, and maps every instruction to
its innermost scope; a program without named scopes maps every instruction
to ``(none)``.
"""
from __future__ import annotations

import bisect
import re

from chipbench.trace import CONTAINERS, short

#: the model step's named scopes (``repro.models``), innermost wins
SCOPES = ("embed", "norm", "attention", "kv_cache", "ffn", "lm_head", "loss", "layer_scan")
NONE = "(none)"
_INSTR = re.compile(r'^\s*(?:ROOT )?%([\w.-]+) = .*?op_name="([^"]*)"', re.M)


def innermost(op_name: str) -> str:
    """The innermost scope of an ``op_name`` path, with the wrappers that
    autodiff and vmap put round a name (``transpose(jvp(layer_scan))``)
    taken off; ``(none)`` where it names none."""
    inner = NONE
    for part in op_name.split("/"):
        bare = re.sub(r"^(\w+\()+|\)+$", "", part)
        if bare in SCOPES:
            inner = bare
    return inner


def hlo_scopes(hlo_text: str) -> dict[str, str]:
    """Instruction name (without ``%``) -> innermost scope, from compiled
    HLO text."""
    return {m.group(1): innermost(m.group(2)) for m in _INSTR.finditer(hlo_text)}


def decode_scopes(spec) -> dict[str, str]:
    """The scope of every instruction of the cell's decode program, as the
    engine jits it: ``api.decode`` over the cell's slots and ``max_len``,
    caches donated."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache

    from repro.models.registry import build_model

    api = build_model(spec.cfg)
    slots, max_len = spec.cell["slots"], spec.cell["max_len"]
    params = jax.eval_shape(api.init, jax.random.PRNGKey(0))
    caches = jax.eval_shape(lambda: api.init_cache(slots, max_len))
    pos = jax.ShapeDtypeStruct((slots,), jnp.int32)
    lowered = jax.jit(api.decode, donate_argnums=(1,)).lower(params, caches, pos, pos)
    # compiled afresh: the persistent cache keys a program without its
    # metadata, so it may hold this program compiled from code without
    # these scopes
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return hlo_scopes(lowered.compile().as_text())
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def whole_runs(trace, prog: str, device: int = 0) -> list:
    """The program's runs that ``TraceSummary.program_run_s`` averages:
    inside the window, and not the trace's first or last program run."""
    lo, hi = trace.window
    mods = trace.modules[device] if trace.modules else []
    edges = (min(mods, key=lambda e: e.start), max(mods, key=lambda e: e.end)) if mods else ()
    return [e for e in trace.program_events(prog, device)
            if lo < e.start and e.end < hi and all(e is not x for x in edges)]


def scope_s_per_run(trace, prog: str, scope: str, table: dict[str, str],
                    device: int = 0) -> float | None:
    """Device seconds of the operations under ``scope`` (by ``table``)
    inside each whole run of ``prog``, averaged over those runs."""
    runs = whole_runs(trace, prog, device)
    if not runs or device >= trace.n_devices:
        return None
    ops = sorted(trace.ops[device], key=lambda e: e.start)
    starts = [e.start for e in ops]
    total = 0.0
    for run in runs:
        for e in ops[bisect.bisect_left(starts, run.start):bisect.bisect_right(starts, run.end)]:
            if e.end <= run.end and not e.name.startswith(CONTAINERS) \
                    and table.get(short(e.name).lstrip("%"), NONE) == scope:
                total += e.end - e.start
    return total / len(runs)
