"""Core neural-net layers shared by the model zoo.

Everything is a pure function over explicit parameter pytrees. Attention is
implemented flash-style (chunked over query blocks with block-local masked
softmax) so peak memory stays bounded for 32k prefill and the pure-jnp path
doubles as the numerical oracle for the Pallas flash-attention kernel.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ArchConfig

# ----------------------------------------------------------------------
# initialisation helpers
# ----------------------------------------------------------------------


def dense_init(key, shape, dtype, in_axis: int = 0):
    """Truncated-normal fan-in init."""
    fan_in = shape[in_axis]
    std = 1.0 / math.sqrt(fan_in)
    return (std * jax.random.truncated_normal(key, -2.0, 2.0, shape)).astype(dtype)


def embed_init(key, shape, dtype):
    return (0.02 * jax.random.normal(key, shape)).astype(dtype)


# ----------------------------------------------------------------------
# norms
# ----------------------------------------------------------------------


def rmsnorm(x, weight, eps: float = 1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    out = x * lax.rsqrt(var + eps) * (1.0 + weight.astype(jnp.float32))
    return out.astype(dt)


def layernorm(x, weight, bias, eps: float = 1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    out = (x - mu) * lax.rsqrt(var + eps) * weight.astype(jnp.float32) + bias.astype(
        jnp.float32
    )
    return out.astype(dt)


def init_norm(key, cfg: ArchConfig, d: int, dtype):
    if cfg.norm == "layernorm":
        return {"w": jnp.ones((d,), dtype), "b": jnp.zeros((d,), dtype)}
    return {"w": jnp.zeros((d,), dtype)}  # rmsnorm stores (scale - 1)


@jax.named_scope("norm")
def apply_norm(p, x, cfg: ArchConfig):
    if cfg.norm == "layernorm":
        return layernorm(x, p["w"], p["b"])
    return rmsnorm(x, p["w"])


# ----------------------------------------------------------------------
# rotary position embeddings (with partial-rotary support)
# ----------------------------------------------------------------------


def rope(x, positions, theta: float, pct: float = 1.0):
    """x: (..., S, H, D); positions: broadcastable to (..., S)."""
    if pct <= 0.0:
        return x
    d = x.shape[-1]
    rot = int(d * pct) // 2 * 2
    x_rot, x_pass = x[..., :rot], x[..., rot:]
    half = rot // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None, None].astype(jnp.float32) * freqs  # (..., S, 1, half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x_rot[..., :half], x_rot[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    ).astype(x.dtype)
    return jnp.concatenate([out, x_pass], axis=-1) if rot < d else out


# ----------------------------------------------------------------------
# attention (chunked / flash-style, GQA, sliding window, softcap)
# ----------------------------------------------------------------------

NEG_INF = -2.0e38


def _block_attend(
    qb,  # (B, bq, Hkv, G, D)
    k,  # (B, Skv, Hkv, D)
    v,  # (B, Skv, Hkv, D)
    qpos,  # (B, bq) int32
    kpos,  # (B, Skv) int32
    *,
    causal: bool,
    window: Optional[int],
    softcap: Optional[float],
    scale: float,
    kv_valid=None,  # (B, Skv) bool — cache validity
    prefix: int = 0,  # always-visible global prefix (hymba meta tokens)
):
    """Full-row masked attention for one query block. fp32 softmax."""
    s = jnp.einsum(
        "bqhgd,bkhd->bhgqk", qb, k, preferred_element_type=jnp.float32
    )
    s = s * scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    mask = jnp.ones((qpos.shape[0], qpos.shape[1], kpos.shape[1]), bool)
    if causal:
        mask &= kpos[:, None, :] <= qpos[:, :, None]
    if window is not None:
        win_ok = kpos[:, None, :] > (qpos[:, :, None] - window)
        if prefix:
            win_ok |= (kpos < prefix)[:, None, :]
        mask &= win_ok
    if kv_valid is not None:
        mask &= kv_valid[:, None, :]
    s = jnp.where(mask[:, None, None, :, :], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    m = jnp.maximum(m, -1e30)  # rows that are fully masked stay finite
    p = jnp.exp(s - m)
    denom = jnp.sum(p, axis=-1, keepdims=True)
    p = p / jnp.maximum(denom, 1e-30)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v.dtype), v)
    return out


def triangular_attention(
    qg,  # (B, Sq, Hkv, G, D) grouped queries
    k,  # (B, Sq, Hkv, D)
    v,
    qpos,  # (B, Sq)
    kpos,  # (B, Sq)
    *,
    softcap: Optional[float],
    scale: float,
    q_block: int,
):
    """Block-sparse causal schedule (§Perf beyond-paper): instead of every
    query block scanning the full KV row (masked-out upper triangle still
    costs FLOPs and score-tensor traffic), scan the STATIC list of
    lower-triangular (q-block, kv-block) pairs — nb(nb+1)/2 block pairs
    instead of nb^2 — with online-softmax state per query block. Halves both
    the causal attention compute and the materialized score bytes.

    Requires Sq == Skv, no window/prefix/validity mask.
    """
    B, Sq, Hkv, G, D = qg.shape
    nb = Sq // q_block
    qb = q_block
    pairs = [(i, j) for i in range(nb) for j in range(i + 1)]
    iqs = jnp.array([p[0] for p in pairs], jnp.int32)
    jks = jnp.array([p[1] for p in pairs], jnp.int32)

    qg_b = jnp.moveaxis(qg.reshape(B, nb, qb, Hkv, G, D), 1, 0)  # (nb,B,qb,Hkv,G,D)
    k_b = jnp.moveaxis(k.reshape(B, nb, qb, Hkv, D), 1, 0)
    v_b = jnp.moveaxis(v.reshape(B, nb, qb, Hkv, D), 1, 0)
    qpos_b = jnp.moveaxis(qpos.reshape(B, nb, qb), 1, 0)
    kpos_b = jnp.moveaxis(kpos.reshape(B, nb, qb), 1, 0)

    f32 = jnp.float32
    m0 = jnp.full((nb, B, Hkv, G, qb, 1), NEG_INF, f32)
    l0 = jnp.zeros((nb, B, Hkv, G, qb, 1), f32)
    a0 = jnp.zeros((nb, B, Hkv, G, qb, D), f32)

    def step(carry, xs):
        m, l, acc = carry
        iq, j = xs
        qt = qg_b[iq]  # (B,qb,Hkv,G,D)
        kt, vt = k_b[j], v_b[j]
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qt, kt, preferred_element_type=f32)
        s = s * scale
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        mask = kpos_b[j][:, None, :] <= qpos_b[iq][:, :, None]  # (B,qb,qb)
        s = jnp.where(mask[:, None, None, :, :], s, NEG_INF)
        m_prev = m[iq]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        m_new = jnp.maximum(m_new, -1e30)
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = corr * l[iq] + jnp.sum(p, axis=-1, keepdims=True)
        pv = jnp.einsum("bhgqk,bkhd->bhgqd", p.astype(vt.dtype), vt).astype(f32)
        a_new = corr * acc[iq] + pv
        return (m.at[iq].set(m_new), l.at[iq].set(l_new), acc.at[iq].set(a_new)), None

    (m, l, acc), _ = lax.scan(step, (m0, l0, a0), (iqs, jks))
    out = acc / jnp.maximum(l, 1e-30)  # (nb,B,Hkv,G,qb,D)
    out = jnp.moveaxis(out, 0, 3)  # (B,Hkv,G,nb,qb,D)
    out = out.reshape(B, Hkv, G, Sq, D).transpose(0, 3, 1, 2, 4)
    return out.astype(qg.dtype)


def chunked_attention(
    q,  # (B, Sq, Hq, D)
    k,  # (B, Skv, Hkv, D)
    v,
    qpos,  # (B, Sq)
    kpos,  # (B, Skv)
    *,
    causal: bool = True,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    q_block: int = 512,
    kv_valid=None,
    prefix: int = 0,
    flash_remat: bool = False,
    causal_sparse: bool = False,
):
    """Flash-style attention: scan over query blocks; each block sees either
    the full KV row (global) or a statically-sized sliding slice (local), so
    peak memory is O(bq * Skv) instead of O(Sq * Skv).

    flash_remat: rematerialize each block's scores/probabilities in the
    backward pass (the FA2 backward strategy) instead of letting autodiff
    stash stacked (nb, B, H, bq, Skv) f32 score tensors through HBM —
    §Perf iteration 1."""
    B, Sq, Hq, D = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    scale = 1.0 / math.sqrt(D)
    qg = q.reshape(B, Sq, Hkv, G, D)

    if (
        causal_sparse
        and causal
        and window is None
        and kv_valid is None
        and prefix == 0
        and Sq == Skv
        and Sq % q_block == 0
        and Sq // q_block >= 2
    ):
        out = triangular_attention(
            qg, k, v, qpos, kpos, softcap=softcap, scale=scale, q_block=q_block
        )
        return out.reshape(B, Sq, Hq, D)

    def attend_call(qb, kk, vv, qp, kp, kvv):
        return _block_attend(
            qb, kk, vv, qp, kp, causal=causal, window=window, softcap=softcap,
            scale=scale, prefix=prefix, kv_valid=kvv,
        )

    if flash_remat:
        attend_call = jax.checkpoint(attend_call)

    if Sq <= q_block:
        out = attend_call(qg, k, v, qpos, kpos, kv_valid)
        return out.reshape(B, Sq, Hq, D)

    if Sq % q_block:  # pad to a whole number of blocks; sliced off below
        pad = q_block - Sq % q_block
        qg = jnp.pad(qg, ((0, 0), (0, pad), (0, 0), (0, 0), (0, 0)))
        qpos = jnp.pad(qpos, ((0, 0), (0, pad)), constant_values=0)
        Sq_padded = Sq + pad
    else:
        Sq_padded = Sq
    nb = Sq_padded // q_block
    # (nb, B, bq, ...) blocked views
    qg_b = jnp.moveaxis(qg.reshape(B, nb, q_block, Hkv, G, D), 1, 0)
    qpos_b = jnp.moveaxis(qpos.reshape(B, nb, q_block), 1, 0)

    local = window is not None and (prefix + window + q_block) < Skv and causal
    if local:
        # statically-sized KV slice per block: the always-visible prefix plus
        # [qstart - window, qstart + bq)
        span = window + q_block

        def slice_kv(arr, start):
            tail = lax.dynamic_slice_in_dim(arr, start, span, axis=1)
            if prefix:
                return jnp.concatenate([arr[:, :prefix], tail], axis=1)
            return tail

        def body(_, xs):
            qb, qp, idx = xs
            start = jnp.clip(idx * q_block - window, prefix, Skv - span)
            ks, vs, kp = slice_kv(k, start), slice_kv(v, start), slice_kv(kpos, start)
            kvv = slice_kv(kv_valid, start) if kv_valid is not None else None
            return None, attend_call(qb, ks, vs, qp, kp, kvv)
    else:

        def body(_, xs):
            qb, qp, idx = xs
            return None, attend_call(qb, k, v, qp, kpos, kv_valid)

    _, out = lax.scan(body, None, (qg_b, qpos_b, jnp.arange(nb)))
    out = jnp.moveaxis(out, 0, 1).reshape(B, Sq_padded, Hq, D)
    return out[:, :Sq] if Sq_padded != Sq else out


# ----------------------------------------------------------------------
# attention layer (projections + rope + cache handling)
# ----------------------------------------------------------------------


def init_attention(key, cfg: ArchConfig, dtype):
    d, hd = cfg.d_model, cfg.resolved_head_dim
    kq, kk, kv, ko, kn = jax.random.split(key, 5)
    p = {
        "wq": dense_init(kq, (d, cfg.n_heads * hd), dtype),
        "wk": dense_init(kk, (d, cfg.n_kv_heads * hd), dtype),
        "wv": dense_init(kv, (d, cfg.n_kv_heads * hd), dtype),
        "wo": dense_init(ko, (cfg.n_heads * hd, d), dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((hd,), dtype)
        p["k_norm"] = jnp.zeros((hd,), dtype)
    return p


def _project_qkv(p, x, cfg: ArchConfig, positions):
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim
    q = (x @ p["wq"]).reshape(B, S, cfg.n_heads, hd)
    k = (x @ p["wk"]).reshape(B, S, cfg.n_kv_heads, hd)
    v = (x @ p["wv"]).reshape(B, S, cfg.n_kv_heads, hd)
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"])
        k = rmsnorm(k, p["k_norm"])
    q = rope(q, positions, cfg.rope_theta, cfg.rope_pct)
    k = rope(k, positions, cfg.rope_theta, cfg.rope_pct)
    return q, k, v


@jax.named_scope("attention")
def attention_layer(
    p,
    x,
    cfg: ArchConfig,
    positions,
    *,
    window: Optional[int],
    causal: bool = True,
    shard_hint: Optional[bool] = None,
    causal_sparse: Optional[bool] = None,
):
    """Self-attention for train/prefill. Returns (out, (k, v)) for caching."""
    q, k, v = _project_qkv(p, x, cfg, positions)
    if shard_hint if shard_hint is not None else cfg.attn_shard_hint is True:
        # keep attention internals batch+head sharded; without this, the
        # seq-sharded prefill cache out-sharding propagates backwards and
        # GSPMD inserts per-q-block gathers/psums (§Perf iterations 2-3).
        # q is only pinned when its head dim actually shards — pinning a
        # non-divisible head count (gemma2's 8 on a 16-way axis) replicates
        # the whole attention compute across the model axis.
        from repro.dist.sharding import active_mesh, constrain, resolve_pspec

        k = constrain(k, ("batch", None, "tp", None))
        v = constrain(v, ("batch", None, "tp", None))
        mesh = active_mesh()
        if mesh is not None and resolve_pspec(q.shape, ("batch", None, "tp", None), mesh)[2] is not None:
            q = constrain(q, ("batch", None, "tp", None))
    out = chunked_attention(
        q, k, v, positions, positions,
        causal=causal, window=window, softcap=cfg.attn_softcap,
        q_block=cfg.q_block, prefix=cfg.meta_tokens,
        flash_remat=cfg.flash_remat,
        causal_sparse=(
            causal_sparse if causal_sparse is not None else cfg.causal_sparse is True
        ),
    )
    B, S = x.shape[:2]
    out = out.reshape(B, S, -1) @ p["wo"]
    return out, (k, v)


#: lanes of a TPU vector register
LANES = 128


def _write_rows(stack, layer, row, new):
    """Write ``new[b]`` (Hkv, D) at ``(*layer, b, row[b])`` of the stacked
    cache ``stack`` in place.

    A head dim of whole lanes stays the minor axis of the cache in TPU
    memory, and one scatter writes every slot's row. Otherwise the device
    keeps the sequence axis minor, where a scatter would have the whole
    stack relaid out on the way in and out; each slot then rewrites the
    lane-wide block of positions that holds its row."""
    new = new.astype(stack.dtype)
    B, S = new.shape[0], stack.shape[-3]
    if new.shape[-1] % LANES == 0:
        return stack.at[(*layer, jnp.arange(B), row)].set(
            new, unique_indices=True, mode="promise_in_bounds"
        )
    w = min(LANES, S)
    zero = jnp.zeros((), jnp.int32)
    for b in range(B):
        start = jnp.clip(row[b] // w * w, 0, S - w)
        at = (*layer, jnp.int32(b), start, zero, zero)
        block = lax.dynamic_slice(stack, at, (1,) * (len(layer) + 1) + (w, *new.shape[1:]))
        hit = (jnp.arange(w) == row[b] - start)[:, None, None]
        stack = lax.dynamic_update_slice(stack, jnp.where(hit, new[b], block), at)
    return stack


@jax.named_scope("attention")
def attention_decode(
    p,
    x,  # (B, 1, d)
    cfg: ArchConfig,
    cache_k,  # (*layers, B, Smax, Hkv, D): every layer's keys
    cache_v,
    layer,  # tuple of scalar indices into the leading layer axes
    positions,  # (B,) current absolute position of the new token
    *,
    window: Optional[int],
):
    """Single-token decode against one layer of the stacked KV caches.

    Writes the B new rows in place at ``(*layer, slot, position)``, a
    position past the end to the last row as ``dynamic_update_slice``
    clamps it, then attends over that layer's slab read from the stack.
    Returns (out, cache_k, cache_v) with the stacks updated."""
    B = x.shape[0]
    q, k, v = _project_qkv(p, x, cfg, positions[:, None])
    Smax = cache_k.shape[-3]
    with jax.named_scope("kv_cache"):
        row = jnp.clip(positions, 0, Smax - 1)
        cache_k = _write_rows(cache_k, layer, row, k[:, 0])
        cache_v = _write_rows(cache_v, layer, row, v[:, 0])
    slab_k, slab_v = cache_k, cache_v
    for i in layer:
        slab_k = lax.dynamic_index_in_dim(slab_k, i, 0, keepdims=False)
        slab_v = lax.dynamic_index_in_dim(slab_v, i, 0, keepdims=False)
    kpos = jnp.broadcast_to(jnp.arange(Smax, dtype=jnp.int32)[None], (B, Smax))
    valid = kpos <= positions[:, None]
    out = chunked_attention(
        q, slab_k, slab_v, positions[:, None], kpos,
        causal=True, window=window, softcap=cfg.attn_softcap,
        q_block=cfg.q_block, kv_valid=valid, prefix=cfg.meta_tokens,
    )
    out = out.reshape(B, 1, -1) @ p["wo"]
    return out, cache_k, cache_v


def init_cross_attention(key, cfg: ArchConfig, dtype):
    return init_attention(key, cfg, dtype)


@jax.named_scope("attention")
def cross_attention_layer(p, x, kv_src, cfg: ArchConfig):
    """Cross-attention: queries from x, keys/values from kv_src (no RoPE)."""
    B, S, _ = x.shape
    Skv = kv_src.shape[1]
    hd = cfg.resolved_head_dim
    q = (x @ p["wq"]).reshape(B, S, cfg.n_heads, hd)
    k = (kv_src @ p["wk"]).reshape(B, Skv, cfg.n_kv_heads, hd)
    v = (kv_src @ p["wv"]).reshape(B, Skv, cfg.n_kv_heads, hd)
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"])
        k = rmsnorm(k, p["k_norm"])
    qpos = jnp.zeros((B, S), jnp.int32)
    kpos = jnp.zeros((B, Skv), jnp.int32)
    out = chunked_attention(
        q, k, v, qpos, kpos, causal=False, window=None, softcap=None,
        q_block=cfg.q_block,
    )
    return out.reshape(B, S, -1) @ p["wo"], (k, v)


@jax.named_scope("attention")
def cross_attention_cached(p, x, ck, cv, cfg: ArchConfig):
    """Cross-attention at decode time against precomputed source K/V."""
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim
    q = (x @ p["wq"]).reshape(B, S, cfg.n_heads, hd)
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"])
    Skv = ck.shape[1]
    qpos = jnp.zeros((B, S), jnp.int32)
    kpos = jnp.zeros((B, Skv), jnp.int32)
    out = chunked_attention(
        q, ck, cv, qpos, kpos, causal=False, window=None, softcap=None,
        q_block=cfg.q_block,
    )
    return out.reshape(B, S, -1) @ p["wo"]


# ----------------------------------------------------------------------
# feed-forward
# ----------------------------------------------------------------------


def init_ffn(key, cfg: ArchConfig, dtype, d_ff: Optional[int] = None):
    d, f = cfg.d_model, d_ff or cfg.d_ff
    k1, k2, k3 = jax.random.split(key, 3)
    if cfg.act in ("silu", "geglu"):
        return {
            "w_gate": dense_init(k1, (d, f), dtype),
            "w_up": dense_init(k2, (d, f), dtype),
            "w_down": dense_init(k3, (f, d), dtype),
        }
    return {"w_up": dense_init(k1, (d, f), dtype), "w_down": dense_init(k2, (f, d), dtype)}


@jax.named_scope("ffn")
def ffn(p, x, cfg: ArchConfig, use_pallas: bool = False):
    if cfg.act in ("silu", "geglu"):
        g = x @ p["w_gate"]
        u = x @ p["w_up"]
        if use_pallas:
            from repro.kernels.silu_mul import ops as silu_ops

            h = silu_ops.act_mul(g, u, act=cfg.act)
        else:
            act = jax.nn.silu if cfg.act == "silu" else partial(jax.nn.gelu, approximate=True)
            h = act(g) * u
        return h @ p["w_down"]
    h = jax.nn.gelu(x @ p["w_up"], approximate=True)
    return h @ p["w_down"]


# ----------------------------------------------------------------------
# embedding / unembedding
# ----------------------------------------------------------------------


def init_embed(key, cfg: ArchConfig, dtype):
    k1, k2 = jax.random.split(key)
    V, d = cfg.padded_vocab, cfg.d_model
    return {
        "tok": embed_init(k1, (V, d), dtype),
        "head": dense_init(k2, (d, V), dtype),
    }


@jax.named_scope("embed")
def embed_tokens(p, tokens, cfg: ArchConfig, compute_dtype):
    x = jnp.take(p["tok"], tokens, axis=0).astype(compute_dtype)
    if cfg.embed_scale:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), compute_dtype)
    return x


@jax.named_scope("lm_head")
def lm_logits(p, x, cfg: ArchConfig):
    logits = (x @ p["head"].astype(x.dtype)).astype(jnp.float32)
    if cfg.final_softcap is not None:
        c = cfg.final_softcap
        logits = c * jnp.tanh(logits / c)
    return logits


def cross_entropy(logits, labels, valid, vocab_size: int):
    """Mean next-token cross entropy over valid positions. Padded vocab slots
    are masked out of the softmax."""
    V = logits.shape[-1]
    if V > vocab_size:
        pad_mask = jnp.arange(V) < vocab_size
        logits = jnp.where(pad_mask[None, None, :], logits, NEG_INF)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = (logz - gold) * valid
    return jnp.sum(nll) / jnp.maximum(jnp.sum(valid), 1.0)


def chunked_cross_entropy(
    x,  # (B, S, d) final hidden states (positions predicting labels)
    embed_params,
    labels,  # (B, S) int32
    valid,  # (B, S) float
    cfg,
    block: int = 512,
):
    """Next-token CE without materializing (B, S, V) logits: scan over
    sequence blocks, rematerializing each block's logits in the backward pass
    (jax.checkpoint). Peak logits memory drops from S*V to block*V per batch
    row — the difference between ~TB and ~GB at 4k x 256k vocab."""
    B, S, d = x.shape
    if S % block:
        pad = block - S % block
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)))
        valid = jnp.pad(valid, ((0, 0), (0, pad)))
        S += pad
    nb = S // block
    xb = jnp.moveaxis(x.reshape(B, nb, block, d), 1, 0)
    lb = jnp.moveaxis(labels.reshape(B, nb, block), 1, 0)
    vb = jnp.moveaxis(valid.reshape(B, nb, block), 1, 0)

    @jax.checkpoint
    def blk(xi, li, vi):
        logits = lm_logits(embed_params, xi, cfg)
        V = logits.shape[-1]
        if V > cfg.vocab_size:
            pad_mask = jnp.arange(V) < cfg.vocab_size
            logits = jnp.where(pad_mask[None, None, :], logits, NEG_INF)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, li[..., None], axis=-1)[..., 0]
        return jnp.sum((logz - gold) * vi), jnp.sum(vi)

    def body(acc, xs):
        xi, li, vi = xs
        s, n = blk(xi, li, vi)
        return (acc[0] + s, acc[1] + n), None

    (tot, n), _ = lax.scan(body, (0.0, 0.0), (xb, lb, vb))
    return tot / jnp.maximum(n, 1.0)
