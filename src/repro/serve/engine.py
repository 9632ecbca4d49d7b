"""Serving engine: batched prefill + decode with a KV cache, request queue
and sampler — the inference-side driver (the paper's subject is inference
performance, so the end-to-end example serves batched requests).

Single-process implementation with the same structure a multi-host server
uses: admission by batch, one prefill per admitted batch (right-padded to the
batch max), then lock-step decode with per-sequence stop handling.

Both engines share a :class:`_ModelRunner` that owns params, caches, the
jitted prefill/decode steps and sampling — and optionally a mesh. With
``mesh=`` the engines are *mesh-native*: parameters are placed with
``dist.sharding.param_pspecs``, KV caches with ``cache_pspecs``, and every
step traces under ``use_mesh(mesh)`` so the models' ``constrain``
annotations become real sharding constraints — prefill and decode then
genuinely execute sharded (verify on CPU with
``XLA_FLAGS=--xla_force_host_platform_device_count=8``). The engine's
parallel degrees (``engine.tp``/``engine.pp``, the mesh's "model"/"pipe"
axis sizes) flow into an attached ``TraceRecorder`` and into predicted
admission, so traces and admission decisions are priced at the mesh the
engine actually runs on rather than a caller-declared one.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
import warnings
from collections import deque
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

import repro.models.transformer as T
from repro.configs.base import ArchConfig
from repro.dist.sharding import (
    cache_pspecs,
    mesh_degrees,
    param_pspecs,
    to_named,
    use_mesh,
)
from repro.models.registry import build_model


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray  # (L,) int32
    max_new: int = 16
    temperature: float = 0.0


@dataclasses.dataclass
class Result:
    rid: int
    tokens: list
    prefill_s: float
    decode_s: float
    #: scheduler steps the request was resident for (its admission prefill
    #: plus every decode tick it took a token in) — comparable across the
    #: batch and continuous engines, and to fleet-simulator service ticks
    ticks: int = 0
    #: admission-to-retire wall-clock of this process, on whichever backend
    #: the engine runs (CPU or TPU); the fleet simulator's queueing latency
    #: is the *predicted* analogue on the predictor's hardware.
    latency_s: float = 0.0


class _ModelRunner:
    """Shared prefill/decode/sample machinery for the serving engines.

    Owns the model api, parameters, the jitted step functions and the
    engine's base PRNG key. With ``mesh=`` the runner places parameters
    (``param_pspecs``) and caches (``cache_pspecs``) on the mesh and runs
    every jitted step inside ``use_mesh(mesh)``, so the models' activation
    ``constrain`` hints resolve against it at trace time. ``tp``/``pp``
    are the mesh's "model"/"pipe" axis sizes (1 without a mesh) — the
    degrees every consumer (trace recorder, predicted admission) prices
    this engine's steps at.
    """

    def __init__(self, cfg: ArchConfig, *, params=None, seed: int = 0, mesh=None):
        self.cfg = cfg
        self.api = build_model(cfg)
        self.mesh = mesh
        self.tp, self.pp = mesh_degrees(mesh)
        if params is None:
            params = self.api.init(jax.random.PRNGKey(seed))
        if mesh is not None:
            params = jax.device_put(params, to_named(param_pspecs(params, mesh), mesh))
        self.params = params
        self.base_key = jax.random.PRNGKey(seed)
        self._jit_decode = jax.jit(self.api.decode, donate_argnums=(1,))
        self._jit_prefill = jax.jit(self.api.prefill)

    def _ctx(self):
        return use_mesh(self.mesh) if self.mesh is not None else contextlib.nullcontext()

    def prefill(self, batch):
        with self._ctx():
            return self._jit_prefill(self.params, batch)

    def decode(self, caches, tokens, positions):
        with self._ctx():
            return self._jit_decode(self.params, caches, tokens, positions)

    def shard_cache(self, caches):
        """Place a cache tree on the mesh (identity without one)."""
        if self.mesh is None:
            return caches
        return jax.device_put(caches, to_named(cache_pspecs(caches, self.mesh), self.mesh))

    def grow_cache(self, caches, max_len: int):
        """``pad_cache`` to ``max_len`` and (re)place on the mesh — padding
        concatenates host zeros, which would otherwise decommit the
        sharding prefill produced."""
        return self.shard_cache(T.pad_cache(caches, self.cfg, max_len))

    def init_cache(self, batch: int, max_len: int):
        return self.shard_cache(self.api.init_cache(batch, max_len))

    def sample(self, logits, temperatures, key):
        """Greedy/categorical per row: ``logits (B, V_padded) -> (B,) int32``.
        Rows with temperature 0 take the argmax; others sample."""
        logits = logits[:, : self.cfg.vocab_size]
        temps = jnp.asarray(temperatures)[:, None]
        greedy = jnp.argmax(logits, axis=-1)
        sampled = jax.random.categorical(key, logits / jnp.maximum(temps, 1e-3))
        return jnp.where(temps[:, 0] > 0, sampled, greedy).astype(jnp.int32)


class _EngineBase:
    """Queue + runner plumbing common to both engines. Exposes the runner's
    identity (``params``/``mesh``/``tp``/``pp``) and binds an attached
    recorder to the engine's mesh degrees, so a recorder never needs the
    caller to declare ``tp=``/``pp=`` for a mesh-native engine."""

    def __init__(self, cfg: ArchConfig, *, params, seed, recorder, mesh):
        self.cfg = cfg
        self._runner = _ModelRunner(cfg, params=params, seed=seed, mesh=mesh)
        self.api = self._runner.api
        self.queue: deque[Request] = deque()
        # optional serve.trace.TraceRecorder: every executed step also emits
        # its decomposer call sequence (actual launched shapes)
        self.recorder = recorder
        if recorder is not None and mesh is not None:
            recorder.bind_mesh(self._runner.tp, self._runner.pp)

    @property
    def params(self):
        return self._runner.params

    @params.setter
    def params(self, value):
        self._runner.params = value

    @property
    def mesh(self):
        return self._runner.mesh

    @property
    def tp(self) -> int:
        """Tensor-parallel degree the engine executes at (the mesh's
        "model" axis size; 1 single-process)."""
        return self._runner.tp

    @property
    def pp(self) -> int:
        return self._runner.pp

    def submit(self, req: Request):
        self.queue.append(req)


class ServeEngine(_EngineBase):
    def __init__(self, cfg: ArchConfig, params=None, seed: int = 0, max_batch: int = 8,
                 recorder=None, mesh=None):
        super().__init__(cfg, params=params, seed=seed, recorder=recorder, mesh=mesh)
        self.max_batch = max_batch
        self._batch_idx = 0  # folds into the engine seed for per-batch keys

    # ------------------------------------------------------------------
    def _pad_batch(self, prompts: list[np.ndarray]):
        B = len(prompts)
        L = max(len(p) for p in prompts)
        toks = np.zeros((B, L), np.int32)
        lens = np.zeros((B,), np.int32)
        for i, p in enumerate(prompts):
            toks[i, L - len(p):] = p  # left-pad so last token aligns
            lens[i] = len(p)
        return jnp.asarray(toks), jnp.asarray(lens), L

    def _extra_inputs(self, B: int, key):
        extra = {}
        if self.cfg.family == "audio":
            extra["frames"] = 0.1 * jax.random.normal(
                key, (B, self.cfg.enc_frames, self.cfg.d_model)
            ).astype(self.cfg.compute_dtype)
        if self.cfg.family == "vlm":
            extra["image_embeds"] = 0.1 * jax.random.normal(
                key, (B, self.cfg.n_img_tokens, self.cfg.d_model)
            ).astype(self.cfg.compute_dtype)
        return extra

    def step_batch(self) -> list[Result]:
        """Admit up to max_batch requests, serve them to completion."""
        if not self.queue:
            return []
        batch_reqs = [
            self.queue.popleft()
            for _ in range(min(self.max_batch, len(self.queue)))
        ]
        B = len(batch_reqs)
        toks, lens, L = self._pad_batch([r.prompt for r in batch_reqs])
        max_new = max(r.max_new for r in batch_reqs)
        # every batch samples under its own key chain: the engine seed
        # folded with a batch counter (identical seeds still reproduce)
        key = jax.random.fold_in(self._runner.base_key, self._batch_idx)
        self._batch_idx += 1
        key, extra_key = jax.random.split(key)

        t0 = time.perf_counter()
        if self.recorder is not None:
            self.recorder.record_step(
                f"prefill[b{B}xL{L}]", self.cfg, B, L, L, phase="prefill"
            )
        batch = {"tokens": toks, **self._extra_inputs(B, extra_key)}
        logits, caches = self._runner.prefill(batch)
        caches = self._runner.grow_cache(caches, L + max_new)
        jax.block_until_ready(logits)
        prefill_s = time.perf_counter() - t0
        if self.recorder is not None:
            # stamp the prefill step with its wall-clock: measured-vs-
            # predicted residuals (serve.monitor) pair this with the
            # recorded call group
            self.recorder.mark_measured(prefill_s)

        outputs: list[list[int]] = [[] for _ in range(B)]
        t0 = time.perf_counter()
        key, sub = jax.random.split(key)
        cur = self._sample(logits, batch_reqs, sub)
        for i in range(B):
            outputs[i].append(int(cur[i]))
        for step in range(max_new - 1):
            pos = jnp.full((B,), L + step, jnp.int32)
            if self.recorder is not None:
                # the step attends the prompt plus every generated token
                # including the one being written at pos; `active` counts
                # the sequences that still accept a token this tick
                # (shorter-max_new rows ride along in the padded batch)
                still = sum(
                    1 for i in range(B)
                    if len(outputs[i]) < batch_reqs[i].max_new
                )
                self.recorder.record_step(
                    f"decode@{L + step}", self.cfg, B, 1, L + step + 1,
                    phase="decode", active=still,
                )
            t_step = time.perf_counter()
            logits, caches = self._runner.decode(caches, cur, pos)
            key, sub = jax.random.split(key)
            cur = self._sample(logits, batch_reqs, sub)
            for i in range(B):
                if len(outputs[i]) < batch_reqs[i].max_new:
                    outputs[i].append(int(cur[i]))
            if self.recorder is not None:
                # int(cur[i]) above synced the step; this is real wall-clock
                self.recorder.mark_measured(time.perf_counter() - t_step)
        jax.block_until_ready(cur)
        decode_s = time.perf_counter() - t0
        return [
            Result(
                r.rid, outputs[i], prefill_s, decode_s,
                ticks=len(outputs[i]), latency_s=prefill_s + decode_s,
            )
            for i, r in enumerate(batch_reqs)
        ]

    def _sample(self, logits, reqs, key):
        return self._runner.sample(
            logits, [r.temperature for r in reqs], key
        )


@dataclasses.dataclass
class _Slot:
    req: Optional[Request] = None
    pos: int = 0  # next write position (absolute, excl. meta)
    emitted: Optional[list] = None
    cur: int = 0  # last sampled token
    t_admit: float = 0.0  # perf_counter at admission (residency metrics)
    prefill_s: float = 0.0
    ticks: int = 0  # scheduler steps this request took a token in

    @property
    def free(self) -> bool:
        return self.req is None


class ContinuousBatchingEngine(_EngineBase):
    """In-flight batching: a fixed pool of decode slots steps in lock-step;
    finished requests free their slot and waiting requests are admitted at
    the next step boundary (each admission prefills into its slot's region
    of the shared KV cache). This is the vLLM/Orca-style scheduler shape on
    top of the same pjit-able decode step.

    Shape conventions (they matter for anything consuming traces or
    predictions of this engine):

      * every decode tick launches the **full padded slot pool** — the
        launched batch is ``slots`` regardless of how many are active, and
        a tick generates one token per *active* slot;
      * the *attended* KV span of a tick is ``max(active positions) + 1``
        (the logical work the decomposer and the hwsim oracle price); the
        masked decode step physically sweeps the padded cache, so the
        measured tick time on CPU or TPU is not the modeled latency;
      * all latencies in the admission machinery are **seconds predicted
        on the admission predictor's hardware**, not measured wall-clock —
        the predictor is the model of the serving fleet, whatever backend
        this engine runs on.

    Admission policy (``admission=``):

      * ``"fixed"`` (default): admit whenever a slot is free — the classic
        fixed slot-count heuristic;
      * ``"predicted"``: before each admission, ask ``predictor`` (any
        ``repro.predict`` backend) for the decode-tick latency of the
        would-be batch at its **worst-case future KV span** (every active
        slot and the candidate projected to their final positions), and
        admit only while that stays within ``decode_slo_s``. Steps are
        priced at the engine's actual parallel degrees (``self.tp`` — the
        mesh's "model" axis size for a mesh-native engine). Predicted
        latency grows with the KV span (up to scheduler-quantization
        wiggle of a fraction of a percent — size the SLO with that
        margin), so a request admitted under the SLO keeps every
        subsequent tick under it too. A request that violates the
        SLO even alone in the pool is admitted anyway with a warning
        (progress guarantee; counted in ``slo_forced_admits``). If the
        predictor cannot price a step (unfitted comm regressor, untrained
        kernel family under ``fallback="error"``), the engine warns once
        and falls back cleanly to fixed admission
        (``admission_fallback_reason``). Decisions are logged in
        ``admission_log`` (one dict per considered candidate).

    Implementation notes for the single-process reference: the shared cache
    is (B_slots, max_len, ...); per-slot prefill recomputes the prompt with
    the slot's row batched alone and writes its KV into the slot row
    (dynamic_update_slice), so running requests are never interrupted.

    Profiler spans (``jax.profiler.TraceAnnotation``, no-ops unless a
    profiler session is running; docs/serving.md shows how to read them):
    ``engine.step`` around each tick, holding one ``engine.admit`` per
    admitted request (args ``rid``, ``prompt_len``, ``queue_wait_ms`` since
    ``submit``, ``queued`` left behind it; children ``engine.prefill``,
    ``engine.pad_cache``, ``engine.slot_write``, ``engine.first_token``),
    ``engine.decode`` (``active``, ``slots``, ``kv``), ``engine.sample``
    around the tick's per-slot sampling (``n``) and one ``engine.retire``
    per finished request (``rid``, ``tokens``, ``ticks``).
    """

    def __init__(self, cfg: ArchConfig, *, slots: int = 4, max_len: int = 128,
                 params=None, seed: int = 0, recorder=None,
                 admission: str = "fixed", predictor=None,
                 decode_slo_s: Optional[float] = None, mesh=None,
                 audit=None, tuned: Optional[dict] = None):
        assert cfg.family not in ("ssm", "hybrid", "audio", "vlm"), (
            "reference continuous-batching engine supports KV-cache LMs"
        )
        if admission not in ("fixed", "predicted"):
            raise ValueError(f"admission must be 'fixed' or 'predicted', got {admission!r}")
        if admission == "predicted" and (predictor is None or decode_slo_s is None):
            raise ValueError(
                "admission='predicted' needs predictor= (a repro.predict "
                "backend for the target hardware) and decode_slo_s= (the "
                "per-tick decode latency SLO in predicted seconds)"
            )
        if audit and predictor is not None:
            # audit=True: pre-flight coverage lint — a predictor that cannot
            # price the decode workload (stale CommRegressor, untrained
            # family) fails construction instead of the first admission tick.
            # A callable substitutes a custom lint:
            # audit(predictor, hw_name) -> list[Diagnostic].
            from repro.analysis import AuditError, audit_predictor

            found = (
                audit_predictor(predictor)
                if audit is True
                else audit(predictor, getattr(getattr(predictor, "hw", None), "name", ""))
            )
            errors = [d for d in found if d.severity == "error"]
            if errors:
                raise AuditError(errors)
        super().__init__(cfg, params=params, seed=seed, recorder=recorder, mesh=mesh)
        self.max_len = max_len
        self.admission = admission
        self.predictor = predictor
        self.decode_slo_s = decode_slo_s
        #: autotuned kernel block table for this engine's hardware
        #: (``repro.tune.TunedConfigs.for_hw(hw)``); predicted admission
        #: prices decode ticks with these blocks merged in
        self.tuned = tuned
        #: one dict per admission decision: rid, projected kv, predicted_s,
        #: slo_s, admitted, forced (admitted despite violating, alone in pool)
        self.admission_log: list[dict] = []
        self.slo_forced_admits = 0
        self.admission_fallback_reason: Optional[str] = None
        self.slots = [_Slot() for _ in range(slots)]
        self.caches = self._runner.init_cache(slots, max_len)
        self.done: list[Result] = []
        self._key = jax.random.PRNGKey(seed + 1)
        # perf_counter at submit, by id() of the queued request: the
        # admission span's queue wait
        self._submitted: dict[int, float] = {}

    def submit(self, req: Request):
        self._submitted[id(req)] = time.perf_counter()
        super().submit(req)

    # ------------------------------------------------------------------
    # predicted admission

    def _projected_kv(self, req: Request) -> int:
        """Worst-case attended KV span of any future tick of the would-be
        batch: every active slot and the candidate projected to their
        final write positions (conservative within one token). Predicted
        tick latency grows with this span (modulo sub-percent scheduler
        quantization), so one check at admission covers the request's
        whole residency."""
        cap = self.max_len - 1
        spans = [min(len(req.prompt) + req.max_new, cap)]
        for s in self.slots:
            if not s.free:
                spans.append(min(s.pos + max(s.req.max_new - len(s.emitted), 0), cap))
        return max(spans) + 1

    def _predicted_tick_s(self, kv: int) -> Optional[float]:
        """Predicted decode-tick latency (seconds on the predictor's
        hardware) for the full slot pool attending ``kv``, priced at the
        engine's actual tensor-parallel degree; None when the predictor
        cannot price the step (the engine has then already fallen back to
        fixed admission)."""
        from repro.core.e2e import model_calls

        try:
            return self.predictor.predict(
                model_calls(self.cfg, len(self.slots), 1, kv, tp=self.tp,
                            tuned=self.tuned)
            ).total_s
        except RuntimeError as e:  # unfitted estimator / comm regressor
            self.admission_fallback_reason = f"{type(e).__name__}: {e}"
            self.admission = "fixed"
            warnings.warn(
                f"predicted admission unavailable ({e}); falling back to "
                "fixed slot admission",
                stacklevel=4,
            )
            return None

    def _admit_ok(self, req: Request) -> bool:
        """One admission decision under the predicted policy (always True
        for fixed admission). Logged in ``admission_log``."""
        if self.admission != "predicted":
            return True
        kv = self._projected_kv(req)
        pred = self._predicted_tick_s(kv)
        if pred is None:
            return True  # fell back to fixed admission mid-run
        ok = pred <= self.decode_slo_s
        forced = False
        if not ok and all(s.free for s in self.slots):
            # the request violates the SLO even alone: admit anyway so the
            # queue cannot deadlock, but say so loudly
            forced, ok = True, True
            self.slo_forced_admits += 1
            warnings.warn(
                f"request {req.rid} cannot meet decode_slo_s="
                f"{self.decode_slo_s:.4g}s even alone in the pool "
                f"(predicted {pred:.4g}s); admitting anyway",
                stacklevel=3,
            )
        self.admission_log.append(
            {
                "rid": req.rid,
                "kv": kv,
                "predicted_s": pred,
                "slo_s": self.decode_slo_s,
                "admitted": ok,
                "forced": forced,
            }
        )
        return ok

    # ------------------------------------------------------------------
    def _admit(self):
        for i, slot in enumerate(self.slots):
            if not slot.free or not self.queue:
                continue
            if not self._admit_ok(self.queue[0]):
                break  # FIFO: a deferred head is retried next tick
            req = self.queue.popleft()
            L = len(req.prompt)
            t0 = time.perf_counter()
            wait_ms = (t0 - self._submitted.pop(id(req), t0)) * 1e3
            with TraceAnnotation("engine.admit", rid=req.rid, prompt_len=L,
                                 queue_wait_ms=wait_ms, queued=len(self.queue)):
                self._admit_into(i, slot, req, t0)

    def _admit_into(self, i: int, slot: _Slot, req: Request, t0: float):
        """Prefill ``req`` alone, write its KV into slot ``i`` and sample
        its first token."""
        L = len(req.prompt)
        if self.recorder is not None:
            # per-slot admission prefills recompute the prompt alone
            self.recorder.record_step(
                f"admit#{req.rid}[L{L}]", self.cfg, 1, L, L, phase="prefill"
            )
        with TraceAnnotation("engine.prefill"):
            batch = {"tokens": jnp.asarray(req.prompt, jnp.int32)[None, :]}
            logits, cache1 = self._runner.prefill(batch)
        with TraceAnnotation("engine.pad_cache"):
            cache1 = self._runner.grow_cache(cache1, self.max_len)
        # copy this request's KV rows into slot i of the shared cache
        # (supported families' cache leaves are (n_layers, B, S, H, D):
        # the slot axis is always 1)
        with TraceAnnotation("engine.slot_write"):
            self.caches = jax.tree.map(
                lambda full, one: full.at[:, i].set(one[:, 0]),
                self.caches,
                cache1,
            )
        self._key, sub = jax.random.split(self._key)
        with TraceAnnotation("engine.first_token"):
            tok = self._sample_one(logits[0], req, sub)
        now = time.perf_counter()
        slot.req, slot.pos, slot.emitted, slot.cur = req, L, [tok], tok
        slot.t_admit, slot.prefill_s, slot.ticks = t0, now - t0, 1
        if self.recorder is not None:
            # the admit step's wall-clock == the slot's prefill_s, so
            # trace residuals reproduce Result-derived ones exactly
            self.recorder.mark_measured(slot.prefill_s)

    def _sample_one(self, logits, req, key) -> int:
        logits = logits[: self.cfg.vocab_size]
        if req.temperature > 0:
            return int(jax.random.categorical(key, logits / req.temperature))
        return int(jnp.argmax(logits))

    def _retire(self, i: int):
        s = self.slots[i]
        with TraceAnnotation("engine.retire", rid=s.req.rid, tokens=len(s.emitted),
                             ticks=s.ticks):
            now = time.perf_counter()
            self.done.append(
                Result(
                    s.req.rid, s.emitted, s.prefill_s,
                    max(now - s.t_admit - s.prefill_s, 0.0),
                    ticks=s.ticks, latency_s=now - s.t_admit,
                )
            )
            self.slots[i] = _Slot()

    def step(self):
        """One scheduler tick: admit, decode all active slots, retire."""
        with TraceAnnotation("engine.step"):
            return self._step()

    def _step(self) -> bool:
        self._admit()
        active = [i for i, s in enumerate(self.slots) if not s.free]
        if not active:
            return False
        toks = jnp.asarray([s.cur if not s.free else 0 for s in self.slots], jnp.int32)
        pos = jnp.asarray(
            [min(s.pos, self.max_len - 1) for s in self.slots], jnp.int32
        )
        # lock-step decode launches over the full slot pool; the padded
        # batch attends up to the most advanced active position
        kv = max(min(self.slots[i].pos, self.max_len - 1) for i in active) + 1
        if self.recorder is not None:
            self.recorder.record_step(
                f"tick[{len(active)}/{len(self.slots)}]",
                self.cfg, len(self.slots), 1, kv,
                phase="decode", active=len(active),
            )
        t_tick = time.perf_counter()
        with TraceAnnotation("engine.decode", active=len(active), slots=len(self.slots),
                             kv=kv):
            logits, self.caches = self._runner.decode(self.caches, toks, pos)
        finished = []
        with TraceAnnotation("engine.sample", n=len(active)):
            for i in active:
                s = self.slots[i]
                self._key, sub = jax.random.split(self._key)
                tok = self._sample_one(logits[i], s.req, sub)
                s.emitted.append(tok)
                s.pos += 1
                s.cur = tok
                s.ticks += 1
                if len(s.emitted) >= s.req.max_new or s.pos >= self.max_len - 1:
                    finished.append(i)
        for i in finished:
            self._retire(i)
        if self.recorder is not None:
            # the per-slot int() sampling above synced the tick
            self.recorder.mark_measured(time.perf_counter() - t_tick)
        return True

    def run_to_completion(self) -> list[Result]:
        while self.queue or any(not s.free for s in self.slots):
            self.step()
        out, self.done = self.done, []
        return out
