"""Seeded random weights of a dense decoder, made by the benchmark on the
device in one jitted call, in the type they are served in.

They are held in the published model's own terms (``layers/wq``,
``attn_norm/scale``, ...), which the plain reference reads; ``to_program``
lays the same arrays out as the program's parameter tree, which the engine
and the trainer take as ``params=``. The program makes no weight itself.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


def prng_key(seed: int):
    """A JAX key from a seed of any size (``PRNGKey`` keeps 32 bits)."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


def norm_kind(hf: dict) -> str:
    return "layernorm" if "layer_norm_eps" in hf else "rmsnorm"


def dims(hf: dict) -> dict:
    heads = hf["num_attention_heads"]
    return dict(
        n=hf["num_hidden_layers"], d=hf["hidden_size"], h=heads,
        kv=hf.get("num_key_value_heads", heads),
        hd=hf.get("head_dim") or hf["hidden_size"] // heads,
        f=hf["intermediate_size"], v=hf["vocab_size"],
    )


def layer_shapes(hf: dict, qk_norm: bool) -> dict:
    """Shapes of one layer's weights: name -> (shape, fan_in or None for a
    norm scale, or "bias")."""
    m = dims(hf)
    d, q, kv, f = m["d"], m["h"] * m["hd"], m["kv"] * m["hd"], m["f"]
    out = {
        "attn_norm.scale": ((d,), None), "wq": ((d, q), d), "wk": ((d, kv), d),
        "wv": ((d, kv), d), "wo": ((q, d), q), "mlp_norm.scale": ((d,), None),
        "w_gate": ((d, f), d), "w_up": ((d, f), d), "w_down": ((f, d), f),
    }
    if norm_kind(hf) == "layernorm":
        out["attn_norm.bias"] = ((d,), "bias")
        out["mlp_norm.bias"] = ((d,), "bias")
    if qk_norm:
        out["q_norm.scale"] = ((m["hd"],), None)
        out["k_norm.scale"] = ((m["hd"],), None)
    return out


def _value(key, shape, init, dtype):
    if init is None:  # a norm's scale, around 1
        x = 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
    elif init == "bias":
        x = 0.1 * jax.random.normal(key, shape, jnp.float32)
    else:  # a matrix, fan-in scaled
        x = jax.random.normal(key, shape, jnp.float32) / jnp.sqrt(float(init))
    return x.astype(dtype)


@partial(jax.jit, static_argnames=("hf_items", "qk_norm", "vocab_rows", "dtype"))
def _make(key, *, hf_items, qk_norm, vocab_rows, dtype):
    hf = dict(hf_items)
    m = dims(hf)
    dt = jnp.dtype(dtype)
    ks = jax.random.split(key, 4)
    w = {
        "embed": (0.02 * jax.random.normal(ks[0], (vocab_rows, m["d"]), jnp.float32)).astype(dt),
        "head": _value(ks[1], (m["d"], vocab_rows), m["d"], dt),
        "final_norm.scale": _value(jax.random.fold_in(ks[2], 0), (m["d"],), None, dt),
    }
    if norm_kind(hf) == "layernorm":
        w["final_norm.bias"] = _value(jax.random.fold_in(ks[2], 1), (m["d"],), "bias", dt)
    shapes = layer_shapes(hf, qk_norm)

    def one_layer(k):  # one layer at a time keeps the f32 draws small
        return {name: _value(jax.random.fold_in(k, i), shape, init, dt)
                for i, (name, (shape, init)) in enumerate(sorted(shapes.items()))}

    layers = jax.lax.map(one_layer, jax.random.split(ks[3], m["n"]))
    w.update({"layers." + k: v for k, v in layers.items()})
    return w


def hf_items(hf: dict) -> tuple:
    """The published config as a hashable static argument of a jit."""
    return tuple(sorted((k, v) for k, v in hf.items() if isinstance(v, (int, float, str))))


def make(hf: dict, seed: int, *, qk_norm: bool, vocab_rows: int, dtype: str) -> dict:
    """The weights of ``seed``, flat ``name -> array``, stacked over layers
    under ``layers.<name>``. ``vocab_rows`` is the program's padded
    vocabulary (the rows past ``vocab_size`` are never a served token)."""
    return _make(prng_key(seed), hf_items=hf_items(hf), qk_norm=qk_norm,
                 vocab_rows=vocab_rows, dtype=dtype)


def to_program(w: dict, hf: dict, *, grads: bool = False) -> dict:
    """The program's parameter tree of the dense family over the same
    arrays. The program's RMSNorm stores ``scale - 1``; a gradient is the
    same under both (``grads=True`` leaves it unshifted)."""
    rms = norm_kind(hf) == "rmsnorm"

    def norm(prefix):
        if rms:
            s = w[prefix + ".scale"]
            return {"w": s if grads else s - jnp.ones((), s.dtype)}
        return {"w": w[prefix + ".scale"], "b": w[prefix + ".bias"]}

    layer = {
        "ln1": norm("layers.attn_norm"),
        "attn": {k: w["layers." + k] for k in ("wq", "wk", "wv", "wo")},
        "ln2": norm("layers.mlp_norm"),
        "ffn": {k: w["layers." + k] for k in ("w_gate", "w_up", "w_down")},
    }
    if "layers.q_norm.scale" in w:
        for k in ("q_norm", "k_norm"):
            s = w[f"layers.{k}.scale"]
            layer["attn"][k] = s if grads else s - jnp.ones((), s.dtype)
    return {
        "embed": {"tok": w["embed"], "head": w["head"]},
        "final_norm": norm("final_norm"),
        "segments": [layer],
    }
