"""Model zoo assembly: segmented layer stacks for all six families.

A model is a list of *segments*; each segment is a homogeneous stack of
layers scanned with ``lax.scan`` over stacked parameters (keeps HLO small and
compile times tractable for 95-layer models on 512 devices). Heterogeneous
layer patterns (gemma2 local/global alternation, hymba global islands,
llama-vision cross-attention groups) become multiple segments or composite
block bodies, so every scan body stays static — no traced branching on layer
kind.

Modes: 'train' (no cache), 'prefill' (build KV/SSM caches), 'decode'
(one token against caches).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ArchConfig
from repro.dist.sharding import constrain
from repro.models import layers as L
from repro.models import moe as M
from repro.models import ssm as S


@dataclasses.dataclass
class Ctx:
    cfg: ArchConfig
    train: bool
    positions: Optional[jax.Array] = None  # (B, S) train/prefill
    dec_positions: Optional[jax.Array] = None  # (B,) decode
    img: Optional[jax.Array] = None  # VLM patch embeddings (B, P, d)
    enc_out: Optional[jax.Array] = None  # whisper encoder output (B, F, d)


@partial(jax.tree_util.register_dataclass, data_fields=["stack", "layer"], meta_fields=[])
@dataclasses.dataclass
class KVStack:
    """One layer's self-attention K or V cache as decode sees it: the whole
    stacked cache ``(*layers, B, S, H, D)`` and the layer's index on each
    leading layer axis, so the layer writes its new rows into the stack."""

    stack: jax.Array
    layer: tuple


def _is_stacked(a) -> bool:
    return isinstance(a, KVStack)


def _leaf_name(path) -> str:
    return path[-1].key if hasattr(path[-1], "key") else str(path[-1])


def _cast(p, dtype, keep_f32=("A_log", "dt_bias", "D")):
    def f(path, a):
        name = _leaf_name(path)
        if a.dtype == jnp.float32 and name in keep_f32:
            return a
        return a.astype(dtype) if jnp.issubdtype(a.dtype, jnp.floating) else a

    return jax.tree_util.tree_map_with_path(f, p)


# ======================================================================
# block bodies — fwd(p, x, ctx, cache, mode) -> (x, aux, new_cache)
# ======================================================================


def _self_attn(p, x, ctx: Ctx, cache, mode, *, window, causal=True):
    cfg = ctx.cfg
    if mode == "decode":
        k, v = cache["k"], cache["v"]
        out, ck, cv = L.attention_decode(
            p, x, cfg, k.stack, v.stack, k.layer, ctx.dec_positions, window=window
        )
        return out, {"k": KVStack(ck, k.layer), "v": KVStack(cv, v.layer)}
    # attn_shard_hint: True = always, "train" = training only (§Perf It-7:
    # the prefill cache out-sharding interplay made the hint regress on
    # gemma2 prefill, while training-graph psums still benefit)
    hint = cfg.attn_shard_hint is True or (
        cfg.attn_shard_hint == "train" and mode == "train"
    )
    sparse = cfg.causal_sparse is True or (
        cfg.causal_sparse == "prefill" and mode == "prefill"
    )
    out, (k, v) = L.attention_layer(
        p, x, cfg, ctx.positions, window=window, causal=causal,
        shard_hint=hint, causal_sparse=sparse,
    )
    if mode == "prefill":
        return out, {"k": k, "v": v}
    return out, None


def dense_block(p, x, ctx: Ctx, cache, mode, *, window):
    cfg = ctx.cfg
    p = _cast(p, x.dtype)
    h = L.apply_norm(p["ln1"], x, cfg)
    attn_out, new_cache = _self_attn(p["attn"], h, ctx, cache, mode, window=window)
    if cfg.post_norms:
        attn_out = L.apply_norm(p["post_ln1"], attn_out, cfg)
    x = constrain(x + attn_out, ("batch", None, None))
    h = L.apply_norm(p["ln2"], x, cfg)
    ffn_out = L.ffn(p["ffn"], h, cfg, use_pallas=cfg.use_pallas)
    if cfg.post_norms:
        ffn_out = L.apply_norm(p["post_ln2"], ffn_out, cfg)
    x = constrain(x + ffn_out, ("batch", None, None))
    return x, 0.0, new_cache


def init_dense_block(key, cfg: ArchConfig, dtype):
    ks = jax.random.split(key, 4)
    p = {
        "ln1": L.init_norm(ks[0], cfg, cfg.d_model, dtype),
        "attn": L.init_attention(ks[1], cfg, dtype),
        "ln2": L.init_norm(ks[2], cfg, cfg.d_model, dtype),
        "ffn": L.init_ffn(ks[3], cfg, dtype),
    }
    if cfg.post_norms:
        p["post_ln1"] = L.init_norm(ks[0], cfg, cfg.d_model, dtype)
        p["post_ln2"] = L.init_norm(ks[2], cfg, cfg.d_model, dtype)
    return p


def pair_block(p, x, ctx: Ctx, cache, mode, *, window):
    """gemma2: one sliding-window layer followed by one global layer."""
    cache = cache or {"local": None, "global": None}
    x, a1, c1 = dense_block(p["local"], x, ctx, cache["local"], mode, window=window)
    x, a2, c2 = dense_block(p["global"], x, ctx, cache["global"], mode, window=None)
    new_cache = None if c1 is None else {"local": c1, "global": c2}
    return x, a1 + a2, new_cache


def moe_block(p, x, ctx: Ctx, cache, mode, *, window):
    cfg = ctx.cfg
    p = _cast(p, x.dtype)
    h = L.apply_norm(p["ln1"], x, cfg)
    attn_out, new_cache = _self_attn(p["attn"], h, ctx, cache, mode, window=window)
    x = constrain(x + attn_out, ("batch", None, None))
    h = L.apply_norm(p["ln2"], x, cfg)
    moe_out, aux = M.moe_layer(p["moe"], h, cfg, train=ctx.train)
    x = constrain(x + moe_out, ("batch", None, None))
    return x, aux, new_cache


def init_moe_block(key, cfg: ArchConfig, dtype):
    ks = jax.random.split(key, 4)
    return {
        "ln1": L.init_norm(ks[0], cfg, cfg.d_model, dtype),
        "attn": L.init_attention(ks[1], cfg, dtype),
        "ln2": L.init_norm(ks[2], cfg, cfg.d_model, dtype),
        "moe": M.init_moe(ks[3], cfg, dtype),
    }


def ssm_block(p, x, ctx: Ctx, cache, mode):
    cfg = ctx.cfg
    p = _cast(p, x.dtype)
    h = L.apply_norm(p["ln1"], x, cfg)
    if mode == "decode":
        out, st = S.ssm_decode(p["mix"], h, cfg, S.SSMState(cache["conv"], cache["ssm"]))
        new_cache = {"conv": st.conv, "ssm": st.ssm}
    else:
        out, st = S.ssm_layer(p["mix"], h, cfg)
        new_cache = {"conv": st.conv, "ssm": st.ssm} if mode == "prefill" else None
    x = constrain(x + out, ("batch", None, None))
    return x, 0.0, new_cache


def init_ssm_block(key, cfg: ArchConfig, dtype):
    k1, k2 = jax.random.split(key)
    return {
        "ln1": L.init_norm(k1, cfg, cfg.d_model, dtype),
        "mix": S.init_ssm(k2, cfg, dtype),
    }


def hybrid_block(p, x, ctx: Ctx, cache, mode, *, window):
    """hymba: parallel attention + SSM heads, mean of per-branch norms."""
    cfg = ctx.cfg
    p = _cast(p, x.dtype)
    cache = cache or {"attn": None, "ssm": None}
    h = L.apply_norm(p["ln1"], x, cfg)
    attn_out, attn_cache = _self_attn(
        p["attn"], h, ctx, cache.get("attn"), mode, window=window
    )
    if mode == "decode":
        ssm_out, st = S.ssm_decode(
            p["mix"], h, cfg, S.SSMState(cache["ssm"]["conv"], cache["ssm"]["ssm"])
        )
    else:
        ssm_out, st = S.ssm_layer(p["mix"], h, cfg)
    mixed = 0.5 * (
        L.rmsnorm(attn_out, p["norm_attn"]) + L.rmsnorm(ssm_out, p["norm_ssm"])
    )
    x = constrain(x + mixed, ("batch", None, None))
    h = L.apply_norm(p["ln2"], x, cfg)
    x = constrain(x + L.ffn(p["ffn"], h, cfg, use_pallas=cfg.use_pallas), ("batch", None, None))
    new_cache = None
    if mode != "train":
        new_cache = {"attn": attn_cache, "ssm": {"conv": st.conv, "ssm": st.ssm}}
    return x, 0.0, new_cache


def init_hybrid_block(key, cfg: ArchConfig, dtype):
    ks = jax.random.split(key, 6)
    return {
        "ln1": L.init_norm(ks[0], cfg, cfg.d_model, dtype),
        "attn": L.init_attention(ks[1], cfg, dtype),
        "mix": S.init_ssm(ks[2], cfg, dtype),
        "norm_attn": jnp.zeros((cfg.d_model,), dtype),
        "norm_ssm": jnp.zeros((cfg.d_model,), dtype),
        "ln2": L.init_norm(ks[3], cfg, cfg.d_model, dtype),
        "ffn": L.init_ffn(ks[4], cfg, dtype),
    }


def cross_block(p, x, ctx: Ctx, cache, mode):
    """llama-3.2-vision gated cross-attention layer (queries: text; kv: image)."""
    cfg = ctx.cfg
    p = _cast(p, x.dtype)
    h = L.apply_norm(p["ln1"], x, cfg)
    if mode == "decode":
        out = L.cross_attention_cached(p["attn"], h, cache["ck"], cache["cv"], cfg)
        new_cache = cache
    else:
        out, (ck, cv) = L.cross_attention_layer(p["attn"], h, ctx.img, cfg)
        new_cache = {"ck": ck, "cv": cv} if mode == "prefill" else None
    x = x + jnp.tanh(p["gate_attn"]).astype(x.dtype) * out
    h = L.apply_norm(p["ln2"], x, cfg)
    x = x + jnp.tanh(p["gate_ffn"]).astype(x.dtype) * L.ffn(p["ffn"], h, cfg)
    return constrain(x, ("batch", None, None)), 0.0, new_cache


def init_cross_block(key, cfg: ArchConfig, dtype):
    ks = jax.random.split(key, 4)
    return {
        "ln1": L.init_norm(ks[0], cfg, cfg.d_model, dtype),
        "attn": L.init_cross_attention(ks[1], cfg, dtype),
        "gate_attn": jnp.zeros((), jnp.float32),
        "ln2": L.init_norm(ks[2], cfg, cfg.d_model, dtype),
        "ffn": L.init_ffn(ks[3], cfg, dtype),
        "gate_ffn": jnp.zeros((), jnp.float32),
    }


def vlm_group(p, x, ctx: Ctx, cache, mode):
    """cross_every self-attn layers followed by one gated cross-attn layer."""
    cache = cache or {"self": None, "cross": None}
    self_block = partial(dense_block, window=None)
    if mode == "decode":
        x, self_caches = _decode_scan(self_block, p["self"], x, ctx, cache["self"])
        aux = 0.0
    else:

        def inner(carry, lp):
            x, aux = carry
            x, a, c = self_block(lp, x, ctx, None, mode)
            return (x, aux + a), c

        (x, aux), self_caches = lax.scan(inner, (x, 0.0), p["self"])
    x, a2, cross_cache = cross_block(p["cross"], x, ctx, cache["cross"], mode)
    new_cache = None
    if mode != "train":
        new_cache = {"self": self_caches, "cross": cross_cache}
    return x, aux + a2, new_cache


def init_vlm_group(key, cfg: ArchConfig, dtype):
    k1, k2 = jax.random.split(key)
    inner_keys = jax.random.split(k1, cfg.cross_every)
    return {
        "self": jax.vmap(lambda k: init_dense_block(k, cfg, dtype))(inner_keys),
        "cross": init_cross_block(k2, cfg, dtype),
    }


def encdec_block(p, x, ctx: Ctx, cache, mode):
    """whisper decoder layer: causal self-attn + cross-attn(enc) + FFN."""
    cfg = ctx.cfg
    p = _cast(p, x.dtype)
    cache = cache or {"self": None, "cross": None}
    h = L.apply_norm(p["ln1"], x, cfg)
    attn_out, self_cache = _self_attn(p["attn"], h, ctx, cache["self"], mode, window=None)
    x = x + attn_out
    h = L.apply_norm(p["ln_x"], x, cfg)
    if mode == "decode":
        xo = L.cross_attention_cached(
            p["xattn"], h, cache["cross"]["ck"], cache["cross"]["cv"], cfg
        )
        cross_cache = cache["cross"]
    else:
        xo, (ck, cv) = L.cross_attention_layer(p["xattn"], h, ctx.enc_out, cfg)
        cross_cache = {"ck": ck, "cv": cv} if mode == "prefill" else None
    x = x + xo
    h = L.apply_norm(p["ln2"], x, cfg)
    x = constrain(x + L.ffn(p["ffn"], h, cfg), ("batch", None, None))
    new_cache = None
    if mode != "train":
        new_cache = {"self": self_cache, "cross": cross_cache}
    return x, 0.0, new_cache


def init_encdec_block(key, cfg: ArchConfig, dtype):
    ks = jax.random.split(key, 6)
    return {
        "ln1": L.init_norm(ks[0], cfg, cfg.d_model, dtype),
        "attn": L.init_attention(ks[1], cfg, dtype),
        "ln_x": L.init_norm(ks[2], cfg, cfg.d_model, dtype),
        "xattn": L.init_cross_attention(ks[3], cfg, dtype),
        "ln2": L.init_norm(ks[4], cfg, cfg.d_model, dtype),
        "ffn": L.init_ffn(ks[5], cfg, dtype),
    }


def enc_block(p, x, ctx: Ctx, cache, mode):
    """whisper encoder layer: bidirectional self-attn + FFN (no cache)."""
    cfg = ctx.cfg
    p = _cast(p, x.dtype)
    h = L.apply_norm(p["ln1"], x, cfg)
    out, _ = _self_attn(p["attn"], h, ctx, None, "train", window=None, causal=False)
    x = x + out
    h = L.apply_norm(p["ln2"], x, cfg)
    x = x + L.ffn(p["ffn"], h, cfg)
    return x, 0.0, None


# ======================================================================
# segment machinery
# ======================================================================


def _layer_view(cache, i):
    """Layer ``i`` of a stacked cache as the block bodies take it: K/V
    leaves with a sequence axis (as ``pad_cache`` tells them) become
    ``KVStack``s, every other leaf (SSM state, cross-attention K/V) is
    sliced out."""

    def f(path, a):
        if _is_stacked(a):
            return KVStack(a.stack, (*a.layer, i))
        if _leaf_name(path) in ("k", "v") and a.ndim >= 5:
            return KVStack(a, (i,))
        return lax.dynamic_index_in_dim(a, i, 0, keepdims=False)

    return jax.tree_util.tree_map_with_path(f, cache, is_leaf=_is_stacked)


def _restack(cache, view, new, i):
    """Fold layer ``i``'s returned cache back into the stack: K/V stacks
    were updated in place, a leaf returned as it was given stays, and any
    other leaf is written at ``i``."""

    def f(old, given, got):
        if _is_stacked(got):
            return KVStack(got.stack, old.layer) if _is_stacked(old) else got.stack
        if got is given:
            return old
        return lax.dynamic_update_index_in_dim(old, got.astype(old.dtype), i, 0)

    return jax.tree.map(f, cache, view, new, is_leaf=_is_stacked)


def _decode_scan(fwd, params, x, ctx: Ctx, cache):
    """Decode one token through a stack of layers, carrying the stacked
    cache and the layer index: each layer writes only its new K/V rows, so
    no layer's cache is sliced out, restacked or copied at the end."""

    def body(carry, lp):
        x, cache, i = carry
        view = _layer_view(cache, i)
        x, _, new = fwd(lp, x, ctx, view, "decode")
        return (x, _restack(cache, view, new, i), i + 1), None

    (x, cache, _), _ = lax.scan(body, (x, cache, jnp.int32(0)), params)
    return x, cache


@dataclasses.dataclass
class Segment:
    name: str
    n: int
    init_one: Callable[[Any], Any]
    fwd: Callable  # (p, x, ctx, cache, mode) -> (x, aux, cache)

    def init(self, key):
        return jax.vmap(self.init_one)(jax.random.split(key, self.n))

    @jax.named_scope("layer_scan")
    def apply(self, params, x, ctx: Ctx, mode: str, cache=None, remat=False):
        """Scan the stack under the ``layer_scan`` scope: the loop, its
        slicing of each layer's weights and, in decode, the carried cache
        with its in-place row writes (``_decode_scan``)."""
        fwd = self.fwd

        if mode == "train":

            def one(lp, xx):
                y, a, _ = fwd(lp, xx, ctx, None, mode)
                return y, a

            if remat:
                one = jax.checkpoint(one)

            def body(carry, lp):
                x, aux = carry
                y, a = one(lp, x)
                return (y, aux + a), None

            (x, aux), _ = lax.scan(body, (x, 0.0), params)
            return x, aux, None

        if mode == "prefill":

            def body(carry, lp):
                x, aux = carry
                x, a, c = fwd(lp, x, ctx, None, mode)
                return (x, aux + a), c

            (x, aux), caches = lax.scan(body, (x, 0.0), params)
            return x, aux, caches

        x, cache = _decode_scan(fwd, params, x, ctx, cache)
        return x, 0.0, cache


def build_segments(cfg: ArchConfig) -> list[Segment]:
    dt = jnp.dtype(cfg.param_dtype)
    if cfg.family == "dense":
        if cfg.layer_pattern == "alt_local_global":
            assert cfg.n_layers % 2 == 0
            init = lambda k: {
                "local": init_dense_block(jax.random.fold_in(k, 0), cfg, dt),
                "global": init_dense_block(jax.random.fold_in(k, 1), cfg, dt),
            }
            return [
                Segment(
                    "pairs",
                    cfg.n_layers // 2,
                    init,
                    partial(pair_block, window=cfg.window),
                )
            ]
        return [
            Segment(
                "dense",
                cfg.n_layers,
                lambda k: init_dense_block(k, cfg, dt),
                partial(dense_block, window=cfg.window),
            )
        ]
    if cfg.family == "moe":
        return [
            Segment(
                "moe",
                cfg.n_layers,
                lambda k: init_moe_block(k, cfg, dt),
                partial(moe_block, window=cfg.window),
            )
        ]
    if cfg.family == "ssm":
        return [
            Segment("ssm", cfg.n_layers, lambda k: init_ssm_block(k, cfg, dt), ssm_block)
        ]
    if cfg.family == "hybrid":
        # global attention islands at first / middle / last layer
        n = cfg.n_layers
        init = lambda k: init_hybrid_block(k, cfg, dt)
        gl = partial(hybrid_block, window=None)
        loc = partial(hybrid_block, window=cfg.window)
        globals_at = sorted(set([0, n // 2, n - 1]))
        segs, prev = [], -1
        for gi, g in enumerate(globals_at):
            run = g - prev - 1
            if run > 0:
                segs.append(Segment(f"loc_{gi}", run, init, loc))
            segs.append(Segment(f"g_{gi}", 1, init, gl))
            prev = g
        tail = n - 1 - globals_at[-1]
        if tail > 0:
            segs.append(Segment("loc_tail", tail, init, loc))
        return segs
    if cfg.family == "vlm":
        n_groups = cfg.n_layers // cfg.cross_every
        return [
            Segment("vlm", n_groups, lambda k: init_vlm_group(k, cfg, dt), vlm_group)
        ]
    if cfg.family == "audio":
        return [
            Segment(
                "dec", cfg.n_layers, lambda k: init_encdec_block(k, cfg, dt), encdec_block
            )
        ]
    raise ValueError(cfg.family)


# ======================================================================
# full model
# ======================================================================

MAX_DEC_POS = 32768  # whisper learned decoder-position table size


def init_params(cfg: ArchConfig, key):
    dt = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 8)
    params = {
        "embed": L.init_embed(ks[0], cfg, dt),
        "final_norm": L.init_norm(ks[1], cfg, cfg.d_model, dt),
        "segments": [seg.init(jax.random.fold_in(ks[2], i)) for i, seg in enumerate(build_segments(cfg))],
    }
    if cfg.meta_tokens:
        params["meta"] = L.embed_init(ks[3], (cfg.meta_tokens, cfg.d_model), dt)
    if cfg.family == "audio":
        params["enc"] = Segment(
            "enc", cfg.n_enc_layers, lambda k: init_encdec_enc(k, cfg, dt), enc_block
        ).init(ks[4])
        params["enc_pos"] = L.embed_init(ks[5], (cfg.enc_frames, cfg.d_model), dt)
        params["dec_pos"] = L.embed_init(ks[6], (MAX_DEC_POS, cfg.d_model), dt)
        params["enc_norm"] = L.init_norm(ks[7], cfg, cfg.d_model, dt)
    return params


def init_encdec_enc(key, cfg: ArchConfig, dtype):
    ks = jax.random.split(key, 4)
    return {
        "ln1": L.init_norm(ks[0], cfg, cfg.d_model, dtype),
        "attn": L.init_attention(ks[1], cfg, dtype),
        "ln2": L.init_norm(ks[2], cfg, cfg.d_model, dtype),
        "ffn": L.init_ffn(ks[3], cfg, dtype),
    }


def _run_encoder(params, cfg: ArchConfig, frames, ctx: Ctx):
    cdt = jnp.dtype(cfg.compute_dtype)
    F = frames.shape[1]
    x = frames.astype(cdt) + params["enc_pos"][:F][None].astype(cdt)
    seg = Segment("enc", cfg.n_enc_layers, lambda k: None, enc_block)
    enc_ctx = dataclasses.replace(
        ctx, positions=jnp.broadcast_to(jnp.arange(F, dtype=jnp.int32)[None], (x.shape[0], F))
    )
    x, _, _ = seg.apply(params["enc"], x, enc_ctx, "train", remat=cfg.remat == "layer")
    return L.apply_norm(params["enc_norm"], x, cfg)


def _embed_input(params, cfg: ArchConfig, tokens, base_positions):
    cdt = jnp.dtype(cfg.compute_dtype)
    x = L.embed_tokens(params["embed"], tokens, cfg, cdt)
    if cfg.meta_tokens:
        B = tokens.shape[0]
        meta = jnp.broadcast_to(
            params["meta"][None].astype(cdt), (B, cfg.meta_tokens, cfg.d_model)
        )
        x = jnp.concatenate([meta, x], axis=1)
        m = cfg.meta_tokens
        pos = jnp.concatenate(
            [
                jnp.broadcast_to(jnp.arange(m, dtype=jnp.int32)[None], (B, m)),
                base_positions + m,
            ],
            axis=1,
        )
    else:
        pos = base_positions
    if cfg.family == "audio":
        x = x + jnp.take(params["dec_pos"], base_positions, axis=0).astype(cdt)
    return x, pos


def forward(params, cfg: ArchConfig, batch, mode: str):
    """train/prefill forward. batch: dict(tokens, [frames|image_embeds]).

    Returns (hidden, aux, caches) — hidden is the post-final-norm residual
    stream (meta tokens stripped); callers turn it into logits (chunked CE
    for training, last-position logits for prefill) so the (B, S, V) logits
    tensor is never materialized at scale."""
    tokens = batch["tokens"]
    B, Stok = tokens.shape
    base_pos = jnp.broadcast_to(jnp.arange(Stok, dtype=jnp.int32)[None], (B, Stok))
    ctx = Ctx(cfg=cfg, train=(mode == "train"))
    if cfg.family == "vlm":
        ctx.img = batch["image_embeds"].astype(jnp.dtype(cfg.compute_dtype))
    if cfg.family == "audio":
        ctx.enc_out = _run_encoder(params, cfg, batch["frames"], ctx)
    x, pos = _embed_input(params, cfg, tokens, base_pos)
    ctx.positions = pos
    x = constrain(x, ("batch", None, None))

    caches = []
    aux = 0.0
    for seg, seg_params in zip(build_segments(cfg), params["segments"]):
        x, a, c = seg.apply(
            seg_params, x, ctx, mode, remat=(cfg.remat == "layer" and mode == "train")
        )
        aux = aux + a
        caches.append(c)
    x = L.apply_norm(params["final_norm"], x, cfg)
    if cfg.meta_tokens:
        x = x[:, cfg.meta_tokens :, :]
    return x, aux, (caches if mode == "prefill" else None)


def full_logits(params, cfg: ArchConfig, hidden):
    """Materialize logits for every position (smoke tests / tiny models)."""
    return L.lm_logits(params["embed"], hidden, cfg)


def decode_step(params, cfg: ArchConfig, caches, tokens, positions):
    """One decode step. tokens: (B,) int32; positions: (B,) absolute position
    of the new token (0-based, excluding meta tokens). Returns (logits, caches)."""
    cdt = jnp.dtype(cfg.compute_dtype)
    B = tokens.shape[0]
    x = L.embed_tokens(params["embed"], tokens[:, None], cfg, cdt)
    if cfg.family == "audio":
        x = x + jnp.take(params["dec_pos"], positions[:, None], axis=0).astype(cdt)
    dec_pos = positions + (cfg.meta_tokens or 0)
    ctx = Ctx(cfg=cfg, train=False, dec_positions=dec_pos)
    new_caches = []
    for seg, seg_params, seg_cache in zip(build_segments(cfg), params["segments"], caches):
        x, _, c = seg.apply(seg_params, x, ctx, "decode", cache=seg_cache)
        new_caches.append(c)
    x = L.apply_norm(params["final_norm"], x, cfg)
    logits = L.lm_logits(params["embed"], x, cfg)
    return logits[:, 0, :], new_caches


def train_loss(params, cfg: ArchConfig, batch):
    hidden, aux, _ = forward(params, cfg, batch, "train")
    tokens = batch["tokens"]
    labels = tokens[:, 1:]
    valid = jnp.ones_like(labels, jnp.float32)
    with jax.named_scope("loss"):
        ce = L.chunked_cross_entropy(
            hidden[:, :-1, :], params["embed"], labels, valid, cfg, block=cfg.q_block
        )
        loss = ce + 0.01 * aux
    return loss, {"ce": ce, "aux": aux}


def pad_cache(caches, cfg: ArchConfig, max_len: int):
    """Pad prefill-produced self-attention KV caches (seq dim) out to
    ``max_len`` (+ meta tokens) so decode steps can append. Cross-attention
    KV and SSM states are fixed-size and pass through."""
    target = max_len + (cfg.meta_tokens or 0)

    def f(path, leaf):
        if _leaf_name(path) in ("k", "v") and leaf.ndim >= 4:
            cur = leaf.shape[-3]
            if cur < target:
                pads = [(0, 0)] * leaf.ndim
                pads[-3] = (0, target - cur)
                return jnp.pad(leaf, pads)
        return leaf

    return jax.tree_util.tree_map_with_path(f, caches)


# ----------------------------------------------------------------------
# cache construction (zeros; used via eval_shape for dry-run input specs)
# ----------------------------------------------------------------------


def init_cache(cfg: ArchConfig, batch: int, max_len: int):
    """Zero caches matching decode_step's expectations. max_len includes the
    token about to be written (excluding meta tokens, which are added here)."""
    cdt = jnp.dtype(cfg.compute_dtype)
    hd = cfg.resolved_head_dim
    S_cache = max_len + (cfg.meta_tokens or 0)

    def kv():
        return {
            "k": jnp.zeros((batch, S_cache, cfg.n_kv_heads, hd), cdt),
            "v": jnp.zeros((batch, S_cache, cfg.n_kv_heads, hd), cdt),
        }

    def ssm_state():
        return {
            "conv": jnp.zeros((batch, S.conv_dim(cfg), cfg.conv_width - 1), cdt),
            "ssm": jnp.zeros(
                (batch, cfg.ssm_heads, cfg.ssm_headdim, cfg.ssm_state), jnp.float32
            ),
        }

    def stack(tree_fn, n):
        return jax.tree.map(lambda a: jnp.broadcast_to(a[None], (n, *a.shape)), tree_fn())

    caches = []
    for seg in build_segments(cfg):
        if seg.name in ("dense", "moe"):
            caches.append(stack(kv, seg.n))
        elif seg.name == "pairs":
            caches.append(stack(lambda: {"local": kv(), "global": kv()}, seg.n))
        elif seg.name == "ssm":
            caches.append(stack(ssm_state, seg.n))
        elif seg.name.startswith(("g_", "loc_")):
            caches.append(stack(lambda: {"attn": kv(), "ssm": ssm_state()}, seg.n))
        elif seg.name == "vlm":
            caches.append(
                stack(
                    lambda: {
                        "self": jax.tree.map(
                            lambda a: jnp.broadcast_to(a[None], (cfg.cross_every, *a.shape)),
                            kv(),
                        ),
                        "cross": {
                            "ck": jnp.zeros((batch, cfg.n_img_tokens, cfg.n_kv_heads, hd), cdt),
                            "cv": jnp.zeros((batch, cfg.n_img_tokens, cfg.n_kv_heads, hd), cdt),
                        },
                    },
                    seg.n,
                )
            )
        elif seg.name == "dec":
            caches.append(
                stack(
                    lambda: {
                        "self": kv(),
                        "cross": {
                            "ck": jnp.zeros((batch, cfg.enc_frames, cfg.n_kv_heads, hd), cdt),
                            "cv": jnp.zeros((batch, cfg.enc_frames, cfg.n_kv_heads, hd), cdt),
                        },
                    },
                    seg.n,
                )
            )
        else:
            raise ValueError(seg.name)
    return caches
