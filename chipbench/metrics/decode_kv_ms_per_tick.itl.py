"""Device time of the decode program's operations under the ``kv_cache``
named scope (the KV cache update in ``attention_decode``) per decode tick:
over the decode program's runs that lie wholly inside the traced window, as
``decode_step_ms.itl`` (device trace, scopes from the compiled program's
``op_name`` metadata). Nothing where the program names no such scope."""
from chipbench import scopes


def read(rec):
    if rec.trace is None or not rec.trace.n_devices:
        return None
    table = scopes.decode_scopes(rec.spec)
    if "kv_cache" not in table.values():
        return None
    t = scopes.scope_s_per_run(rec.trace, "decode", "kv_cache", table)
    return 1e3 * t if t is not None else None
