"""The plain reference of a dense decoder: the published architecture in
float32 ``jax.numpy`` at the highest matmul precision, with no cache, no
batching and no kernel. It reads the benchmark's own weights
(``weights.make``) and imports nothing of the program.

``quant="fp8"`` is the control: every matmul operand, in the layers and the
head, rounded to float8 (e4m3) first, the precision a later change could be
tempted to serve in.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from chipbench.weights import dims, norm_kind

F32 = jnp.float32


def _q(x, quant):
    if quant is None:
        return x.astype(F32)
    if quant == "fp8":
        return x.astype(jnp.float8_e4m3fn).astype(F32)
    raise ValueError(f"unknown precision {quant!r}")


def _mm(a, b, quant):
    return jnp.matmul(_q(a, quant), _q(b, quant), precision="highest")


def _norm(x, scale, bias, kind, eps):
    if kind == "layernorm":
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + eps) * scale.astype(F32) + bias.astype(F32)
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale.astype(F32)


def _rope(x, pos, theta, pct):
    """Rotary embedding of the first ``head_dim * pct`` dims (rotate-half)."""
    hd = x.shape[-1]
    rot = int(hd * pct) // 2 * 2
    half = rot // 2
    inv = 1.0 / theta ** (jnp.arange(0, rot, 2, dtype=F32) / rot)
    ang = pos[:, None].astype(F32) * inv  # (S, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:rot]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., rot:]], -1)


def _layer(hf, qk_norm, quant):
    m = dims(hf)
    kind = norm_kind(hf)
    eps = hf.get("rms_norm_eps", hf.get("layer_norm_eps"))
    theta = float(hf["rope_theta"])
    pct = hf.get("partial_rotary_factor", 1.0)
    group = m["h"] // m["kv"]

    def body(x, lw):
        S = x.shape[0]
        pos = jnp.arange(S)
        h = _norm(x, lw["attn_norm.scale"], lw.get("attn_norm.bias"), kind, eps)
        q = _mm(h, lw["wq"], quant).reshape(S, m["h"], m["hd"])
        k = _mm(h, lw["wk"], quant).reshape(S, m["kv"], m["hd"])
        v = _mm(h, lw["wv"], quant).reshape(S, m["kv"], m["hd"])
        if qk_norm:
            q = _norm(q, lw["q_norm.scale"], None, "rmsnorm", eps)
            k = _norm(k, lw["k_norm.scale"], None, "rmsnorm", eps)
        q, k = _rope(q, pos, theta, pct), _rope(k, pos, theta, pct)
        k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
        s = jnp.einsum("qhd,khd->hqk", _q(q, quant), _q(k, quant),
                       precision="highest") / math.sqrt(m["hd"])
        s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("hqk,khd->qhd", _q(p, quant), _q(v, quant), precision="highest")
        x = x + _mm(o.reshape(S, -1), lw["wo"], quant)
        h = _norm(x, lw["mlp_norm.scale"], lw.get("mlp_norm.bias"), kind, eps)
        g = jax.nn.silu(_mm(h, lw["w_gate"], quant)) * _mm(h, lw["w_up"], quant)
        return x + _mm(g, lw["w_down"], quant)

    return body


def hidden(w, hf, qk_norm, tokens, quant=None, remat=False):
    """Final-norm hidden states ``(S, d)`` of one sequence ``tokens``."""
    kind = norm_kind(hf)
    eps = hf.get("rms_norm_eps", hf.get("layer_norm_eps"))
    layers = {k[len("layers."):]: v for k, v in w.items() if k.startswith("layers.")}
    body = _layer(hf, qk_norm, quant)
    if remat:
        body = jax.checkpoint(body)
    x = w["embed"][tokens].astype(F32)
    x, _ = jax.lax.scan(lambda x, lw: (body(x, lw), None), x, layers)
    return _norm(x, w["final_norm.scale"], w.get("final_norm.bias"), kind, eps)


@partial(jax.jit, static_argnames=("hf_items", "qk_norm", "quant"))
def logits_at(w, tokens, out_pos, *, hf_items, qk_norm, quant=None):
    """Logits ``(n, vocab_size)`` at positions ``out_pos`` of ``tokens``
    (``(S,)``; positions past a sequence's end are padding that causal
    attention keeps from the positions before it)."""
    hf = dict(hf_items)
    x = hidden(w, hf, qk_norm, tokens, quant)[out_pos]
    return _mm(x, w["head"][:, : hf["vocab_size"]], quant)


# ----------------------------------------------------------------------
# training
# ----------------------------------------------------------------------


def loss(w, hf, qk_norm, tokens, quant=None, block: int = 512):
    """Mean next-token cross entropy over every position of ``tokens``
    ``(B, S)`` but the last, over the unpadded vocabulary. Each layer and
    each block of positions is recomputed in the backward pass, to fit."""
    head = w["head"][:, : hf["vocab_size"]]

    @jax.checkpoint
    def block_nll(x, labels, keep):
        z = _mm(x, head, quant)
        nll = jax.nn.logsumexp(z, -1) - jnp.take_along_axis(z, labels[:, None], -1)[:, 0]
        return jnp.sum(jnp.where(keep, nll, 0.0))

    def one(seq):
        x = hidden(w, hf, qk_norm, seq, quant, remat=True)[:-1]
        n = x.shape[0]
        pad = -n % block
        x = jnp.pad(x, ((0, pad), (0, 0))).reshape(-1, block, x.shape[-1])
        labels = jnp.pad(seq[1:], (0, pad)).reshape(-1, block)
        keep = (jnp.arange(n + pad) < n).reshape(-1, block)
        tot, _ = jax.lax.scan(lambda acc, xs: (acc + block_nll(*xs), None), 0.0,
                              (x, labels, keep))
        return tot

    total = sum(one(tokens[b]) for b in range(tokens.shape[0]))
    return total / (tokens.shape[0] * (tokens.shape[1] - 1))


def lr_at(opt: dict, t):
    """Linear warm-up to ``lr`` over ``warmup`` steps, then cosine decay to
    ``floor * lr`` at ``total_steps``; ``t`` counts updates from 1."""
    t = jnp.asarray(t, F32)
    warm = opt["lr"] * t / max(opt["warmup"], 1)
    frac = jnp.clip((t - opt["warmup"]) / max(opt["total_steps"] - opt["warmup"], 1), 0.0, 1.0)
    cos = opt["lr"] * (opt["floor"] + (1 - opt["floor"]) * 0.5 * (1 + jnp.cos(jnp.pi * frac)))
    return jnp.where(t < opt["warmup"], warm, cos)


def decayed(name: str) -> bool:
    """AdamW's weight decay applies to the matrices, not to norms."""
    return "norm" not in name


@partial(jax.jit, static_argnames=("hf_items", "qk_norm", "opt_items", "quant"),
         donate_argnums=(0, 1, 2))
def train_step(w, m, v, tokens, t, *, hf_items, qk_norm, opt_items, quant=None):
    """One AdamW step (global-norm clipping, bias correction, decoupled
    decay) on the mean loss of ``tokens``. Returns the new weights and
    moments, the loss, and the norm of each weight's clipped gradient, the
    gradient as the update took it."""
    hf, opt = dict(hf_items), dict(opt_items)
    lval, g = jax.value_and_grad(loss)(w, hf, qk_norm, tokens, quant)
    gnorm = jnp.sqrt(sum(jnp.sum(x * x) for x in g.values()))
    g = {k: x * jnp.minimum(1.0, opt["clip_norm"] / jnp.maximum(gnorm, 1e-9)) for k, x in g.items()}
    b1, b2 = opt["b1"], opt["b2"]
    m = {k: b1 * m[k] + (1 - b1) * g[k] for k in g}
    v = {k: b2 * v[k] + (1 - b2) * g[k] * g[k] for k in g}
    lr = lr_at(opt, t)
    c1, c2 = 1 - b1 ** jnp.asarray(t, F32), 1 - b2 ** jnp.asarray(t, F32)

    def upd(k):
        u = (m[k] / c1) / (jnp.sqrt(v[k] / c2) + opt["eps"])
        if decayed(k):
            u = u + opt["weight_decay"] * w[k]
        return w[k] - lr * u

    gnorms = {k: jnp.sqrt(jnp.sum(x * x)) for k, x in g.items()}
    return {k: upd(k) for k in w}, m, v, lval, gnorms
