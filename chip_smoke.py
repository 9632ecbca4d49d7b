"""Bring-up smoke run: serve and train qwen3-0.6b at full width on a TPU.

    python chip_smoke.py               # one chip: serve phase, then train phase
    python chip_smoke.py --four-chips  # 2x2 (data, model) mesh against one chip

Serve phase: ``ContinuousBatchingEngine`` (8 slots, 1024-token cache) serves
16 greedy requests. Then two prompts go through the engine's own programs
once more (prefill, per-slot KV write, one decode tick fed a given token),
and those prefill and decode logits are checked against a float32 forward
of the same parameters on the host CPU backend of this process. Train
phase: the ``Trainer`` behind ``python -m repro.launch.train`` takes 3
steps. ``--four-chips`` runs only the mesh path: sharded serving (greedy
tokens, prefill and teacher-forced decode logits) and 2 sharded train
steps, each against the same work on one chip.

Exits non-zero, printing no result line, unless JAX's first device is a TPU.
The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import logging
import os
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

#: Scale-relative logit error ``max|got - ref| / max|ref|`` allowed between
#: two runs of the same parameters: bf16 compute against the float32
#: reference, and the sharded engine against the one-chip one. Sound runs
#: read 0.018 (bf16 vs f32) and 0.024 (sharded) at full width on TPU v5e,
#: and up to 0.026 with the ``.smoke()`` model on the CPU; skipping one
#: layer reads 0.20 at full width (the last of 28) and 0.74 and over at
#: smoke size. The limit sits between them (``tests/test_chip_smoke.py``
#: plants the fault).
LOGIT_TOL = 6e-2
#: Relative train-loss difference allowed between the sharded and the
#: one-chip run. Sound runs read 1.2e-4 (2x2 v5e mesh) and 6.1e-5 (four
#: host devices, ``.smoke()``); gradients taken from half of each batch, as
#: a step missing its data-axis reduction would take them, read 8.3e-3 at
#: full width and 1.4e-3 at smoke size, at step 2 (step 1's loss comes
#: before any update).
LOSS_TOL = 4e-4
#: Serving: slot pool, cache length, requests and their shape.
SLOTS, MAX_LEN = 8, 1024
N_REQUESTS, PROMPT_LENS, MAX_NEW = 16, (128, 512), 32
SEED = 0
#: Train batch x sequence for full-width qwen3-0.6b on one 16 GB v5e: f32
#: params + Adam moments + grads take ~12 GB; this leaves >= 1 GiB free in
#: the compiled step's memory analysis.
TRAIN_BATCH, TRAIN_SEQ = 8, 512
TRAIN_STEPS, FOUR_CHIP_TRAIN_STEPS = 3, 2
CKPT_DIR = ROOT / "chip_smoke_ckpt"


@contextlib.contextmanager
def compile_counter():
    """Count XLA backend compiles (persistent-cache hits included) and their
    seconds while the block runs."""
    import jax

    stats = {"compiles": 0, "compile_s": 0.0, "cache_hits": 0}

    def on_duration(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            stats["compiles"] += 1
            stats["compile_s"] += secs

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            stats["cache_hits"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    try:
        yield stats
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
        jax.monitoring.unregister_event_listener(on_event)


def memory(device) -> dict:
    """``bytes_in_use``/``peak_bytes_in_use`` of ``device`` (empty where the
    backend keeps no statistics, as the CPU does)."""
    stats = device.memory_stats() or {}
    return {k: stats[k] for k in ("bytes_in_use", "peak_bytes_in_use") if k in stats}


def rel_err(got, ref) -> float:
    """Scale-relative error ``max|got - ref| / max|ref|`` along the last
    (vocabulary) axis; the largest over any leading axes."""
    import numpy as np

    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    return float(np.max(np.max(np.abs(got - ref), -1) / np.max(np.abs(ref), -1)))


def make_requests(cfg):
    import numpy as np

    from repro.serve.engine import Request

    rng = np.random.default_rng(SEED)
    return [
        Request(
            rid=i,
            prompt=rng.integers(3, cfg.vocab_size, PROMPT_LENS[i % len(PROMPT_LENS)],
                                dtype=np.int32),
            max_new=MAX_NEW,
        )
        for i in range(N_REQUESTS)
    ]


def serve(cfg, reqs, mesh=None):
    """A fixed-admission ``ContinuousBatchingEngine`` (seeded params, placed
    on ``mesh`` when given) after serving ``reqs`` to completion, with each
    request's greedy tokens and the loop's wall-clock."""
    from repro.serve.engine import ContinuousBatchingEngine

    eng = ContinuousBatchingEngine(cfg, slots=SLOTS, max_len=MAX_LEN, admission="fixed",
                                   seed=SEED, mesh=mesh)
    for r in reqs:
        eng.submit(dataclasses.replace(r))
    t0 = time.perf_counter()
    results = eng.run_to_completion()
    wall_s = time.perf_counter() - t0
    return eng, {r.rid: list(r.tokens) for r in results}, wall_s


def engine_logits(eng, prompts, fed):
    """Logits of ``eng``'s own programs for ``prompts`` continued by the
    given tokens ``fed`` (``(n, T)``): each prompt's last-position prefill
    logits ``(n, V)``, and the decode logits after each fed token
    ``(T, n, V)``. The prompts are admitted into the slots of the idle
    engine, as many at a time as it has slots (per-slot prefill, cache
    growth and KV write into the shared cache), and ``fed[i, t]`` is
    decoded at position ``len(prompts[i]) + t``. The probe requests are
    retired afterwards."""
    import jax.numpy as jnp
    import numpy as np

    from repro.serve.engine import Request

    V, S = eng.cfg.vocab_size, len(eng.slots)
    runner = eng._runner
    if eng.queue or not all(s.free for s in eng.slots):
        raise RuntimeError("engine_logits needs an idle engine")

    def host(logits):
        return np.asarray(logits[..., :V].astype(jnp.float32))

    pre = np.stack([host(runner.prefill({"tokens": jnp.asarray(p, jnp.int32)[None, :]})[0][0])
                    for p in prompts])
    dec = np.zeros((fed.shape[1], len(prompts), V), np.float32)
    for c in range(0, len(prompts), S):
        chunk = prompts[c:c + S]
        for k, p in enumerate(chunk):
            eng.submit(Request(rid=-1 - c - k, prompt=p, max_new=1))
        eng._admit()  # fills slots 0..len(chunk)-1 of the idle engine, in order
        toks, pos = np.zeros(S, np.int32), np.zeros(S, np.int32)
        for t in range(fed.shape[1]):
            toks[:len(chunk)] = fed[c:c + S, t]
            pos[:len(chunk)] = [len(p) + t for p in chunk]
            logits, eng.caches = runner.decode(eng.caches, jnp.asarray(toks),
                                               jnp.asarray(pos))
            dec[t, c:c + S] = host(logits[:len(chunk)])
        eng.run_to_completion()
    return pre, dec


def reference_logits(cfg, params, prompts, fed, ref_device):
    """The plain reference: a float32 full forward of ``prompt + [fed[i]]``
    at the highest matmul precision on ``ref_device``, with ``params``
    copied there. Returns the logits at the prompt's last position
    (``(n, V)``, what prefill gives) and at the fed token (``(n, V)``,
    what one decode tick after the prompt gives)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import transformer as T

    cfg32 = dataclasses.replace(cfg, compute_dtype="float32")

    @jax.jit
    def last_two(params, tokens):
        hidden, _, _ = T.forward(params, cfg32, {"tokens": tokens}, "prefill")
        return T.full_logits(params, cfg32, hidden[:, -2:, :])[0, :, : cfg.vocab_size]

    params = jax.device_put(params, ref_device)
    with jax.default_matmul_precision("highest"):
        out = np.stack([
            np.asarray(last_two(params, jax.device_put(
                jnp.asarray(np.append(p, t), jnp.int32)[None, :], ref_device)))
            for p, t in zip(prompts, fed)
        ])
    return out[:, 0], out[:, 1]


def serve_phase(cfg, *, device, ref_device) -> dict:
    """Serve the requests through a fixed-admission ``ContinuousBatchingEngine``
    on the default device (``device``), then check two prompts' prefill and
    teacher-forced decode logits, taken through the engine's programs,
    against the float32 reference on ``ref_device``. Raises if the error
    exceeds ``LOGIT_TOL``."""
    import numpy as np

    reqs = make_requests(cfg)
    probes = reqs[:2]
    with compile_counter() as comp:
        eng, tokens, wall_s = serve(cfg, reqs)
        # feed each probe the token the engine picked first for it
        fed = np.array([tokens[r.rid][:1] for r in probes], np.int32)
        pre, dec = engine_logits(eng, [r.prompt for r in probes], fed)
    out = {
        "requests": len(tokens),
        "tokens_served": sum(map(len, tokens.values())),
        "serve_wall_s": wall_s,
        **comp,
        **memory(device),
    }
    ref_pre, ref_dec = reference_logits(cfg, eng.params, [r.prompt for r in probes],
                                        fed[:, 0], ref_device)
    out["logit_err_prefill"] = rel_err(pre, ref_pre)
    out["logit_err_decode"] = rel_err(dec[0], ref_dec)
    out["logit_tol"] = LOGIT_TOL
    if out["requests"] != N_REQUESTS or out["tokens_served"] != N_REQUESTS * MAX_NEW:
        raise RuntimeError(f"served {out['requests']} requests / {out['tokens_served']} tokens")
    err = max(out["logit_err_prefill"], out["logit_err_decode"])
    if not err <= LOGIT_TOL:
        raise RuntimeError(f"logit error {err:.3g} exceeds {LOGIT_TOL}")
    return out


def train_losses(cfg, *, steps, ckpt_dir, mesh=None) -> list:
    """Losses of ``steps`` fresh ``Trainer`` steps (``launch.train.train``),
    checkpoint included; ``ckpt_dir`` is emptied before and after."""
    from repro.data.pipeline import DataConfig
    from repro.launch.train import train
    from repro.train.step import TrainConfig
    from repro.train.trainer import TrainerConfig

    shutil.rmtree(ckpt_dir, ignore_errors=True)
    try:
        _, _, losses = train(
            cfg, DataConfig(batch=TRAIN_BATCH, seq_len=TRAIN_SEQ),
            TrainConfig(total_steps=steps, warmup=1),
            TrainerConfig(total_steps=steps, ckpt_every=steps, ckpt_dir=str(ckpt_dir),
                          log_every=1),
            mesh=mesh,
        )
    finally:
        shutil.rmtree(ckpt_dir, ignore_errors=True)
    return losses


def train_phase(cfg, *, device, ckpt_dir=CKPT_DIR) -> dict:
    """``TRAIN_STEPS`` train steps on the default device; raises unless
    every loss is finite."""
    import math

    t0 = time.perf_counter()
    with compile_counter() as comp:
        losses = train_losses(cfg, steps=TRAIN_STEPS, ckpt_dir=ckpt_dir)
    out = {"losses": losses, "train_wall_s": time.perf_counter() - t0, **comp,
           **memory(device)}
    if len(losses) != TRAIN_STEPS or not all(math.isfinite(x) for x in losses):
        raise RuntimeError(f"train losses {losses}")
    return out


def divergence_margins(tok_ref, tok_got, pre, dec) -> list:
    """For each request whose greedy tokens ``tok_got[i]`` leave
    ``tok_ref[i]``: the reference logits' margin between the two picks at
    the first difference, over ``max|logits|``. ``pre``/``dec`` are the
    reference run's prefill and teacher-forced decode logits
    (``engine_logits`` fed ``tok_ref``)."""
    import numpy as np

    margins = []
    for i, (a, b) in enumerate(zip(tok_ref, tok_got)):
        j = next((j for j, (x, y) in enumerate(zip(a, b)) if x != y), None)
        if j is None:
            continue
        logits = pre[i] if j == 0 else dec[j - 1, i]
        margins.append(float((logits[a[j]] - logits[b[j]]) / np.max(np.abs(logits))))
    return margins


def four_chip_phase(cfg, devices, *, ckpt_dir=CKPT_DIR) -> dict:
    """Mesh-native serving and sharded training on a 2x2 ``("data",
    "model")`` mesh over ``devices[:4]``, each against the same work on
    ``devices[0]`` alone. Both engines serve the requests, then are fed
    the one-chip engine's greedy tokens (``engine_logits``), so prefill,
    KV writes and every decode tick see the same inputs. Raises if the
    prefill or decode logits differ by more than ``LOGIT_TOL``, if a
    request's greedy tokens part where the one-chip logits are not tied
    within the error that tolerance allows (``2 * LOGIT_TOL``: each of the
    two picks' logits may move by ``LOGIT_TOL``), or if the losses differ
    by more than ``LOSS_TOL``."""
    import numpy as np

    from repro.launch.mesh import make_mesh

    mesh = make_mesh((2, 2), ("data", "model"), devices=devices[:4])
    reqs = make_requests(cfg)
    prompts = [r.prompt for r in reqs]

    def run(mesh, fed=None):
        eng, tokens, _ = serve(cfg, reqs, mesh)
        tokens = [tokens[r.rid] for r in reqs]
        # read after the run: by then the transfers that placed the params
        # have landed and their unsharded source on device 0 is freed
        bytes_in_use = [memory(d).get("bytes_in_use") for d in devices[:4]]
        if fed is None:  # the last token is never fed back
            fed = np.array([t[:-1] for t in tokens], np.int32)
        return tokens, fed, engine_logits(eng, prompts, fed), bytes_in_use

    tok1, fed, (pre1, dec1), bytes1 = run(None)
    tok4, _, (pre4, dec4), bytes4 = run(mesh, fed)
    margins = divergence_margins(tok1, tok4, pre1, dec1)
    loss1 = train_losses(cfg, steps=FOUR_CHIP_TRAIN_STEPS, ckpt_dir=ckpt_dir)
    loss4 = train_losses(cfg, steps=FOUR_CHIP_TRAIN_STEPS, ckpt_dir=ckpt_dir, mesh=mesh)
    out = {
        "logit_err_prefill": rel_err(pre4, pre1),
        "logit_err_decode": rel_err(dec4, dec1),
        "logit_tol": LOGIT_TOL,
        "token_agreement": float(np.mean(np.equal(tok1, tok4))),
        "divergent_requests": len(margins),
        "max_divergence_margin": max(margins, default=0.0),
        "margin_limit": 2 * LOGIT_TOL,
        "bytes_in_use_one_chip": bytes1,
        "bytes_in_use_sharded": bytes4,
        "losses_one_chip": loss1,
        "losses_sharded": loss4,
        "loss_rel_diff": max(abs(a - b) / abs(b) for a, b in zip(loss4, loss1)),
        "loss_tol": LOSS_TOL,
    }
    err = max(out["logit_err_prefill"], out["logit_err_decode"])
    if not err <= LOGIT_TOL:
        raise RuntimeError(f"sharded logits differ by {err:.3g}, over {LOGIT_TOL}")
    if not out["max_divergence_margin"] <= out["margin_limit"]:
        raise RuntimeError(f"sharded greedy tokens part at a margin of "
                           f"{out['max_divergence_margin']:.3g}, over {out['margin_limit']}")
    if not out["loss_rel_diff"] <= LOSS_TOL:
        raise RuntimeError(f"sharded losses {loss4} vs one-chip {loss1}")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 2x2 mesh path against one chip")
    args = ap.parse_args(argv)

    # the float32 reference runs on the host CPU backend of this process
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "cpu" not in platforms.split(","):
        os.environ["JAX_PLATFORMS"] = platforms + ",cpu"
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}", file=sys.stderr)
        return 1
    if args.four_chips and len(devices) < 4:
        print(f"chip_smoke: --four-chips needs 4 devices, found {len(devices)}",
              file=sys.stderr)
        return 1

    sys.path.insert(0, str(ROOT / "src"))
    logging.basicConfig(level=logging.INFO, format="%(asctime)s %(name)s %(message)s")
    from repro.configs import get_arch
    from repro.launch.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    cfg = get_arch("qwen3-0.6b")
    print(f"device: {dev.device_kind} x{len(devices)}; model {cfg.name} "
          f"({cfg.n_params() / 1e9:.3f} B params, {cfg.n_layers} layers, "
          f"d_model {cfg.d_model}, vocab {cfg.vocab_size})", flush=True)
    if args.four_chips:
        print("four_chips:", json.dumps(four_chip_phase(cfg, devices)), flush=True)
    else:
        ref_device = jax.devices("cpu")[0]
        print("serve:", json.dumps(serve_phase(cfg, device=dev, ref_device=ref_device)),
              flush=True)
        print("train:", json.dumps(train_phase(cfg, device=dev)), flush=True)
    print(json.dumps({"ok": True, "device": {"platform": dev.platform,
                                             "kind": dev.device_kind,
                                             "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
