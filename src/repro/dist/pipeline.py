"""Pipeline parallelism via ``shard_map`` + collective permutes: GPipe,
interleaved-1F1B and zero-bubble ZB-H1 schedules, plus the analytical
bubble models the predictor uses (``core.e2e.pp_bubble``).

All schedules stream microbatches around a ring of ``S`` pipeline stages
(one device per stage along the pipeline mesh axis). The layer stack
(leaves stacked along a leading layer dim, the layout ``Segment.init``
produces) is split into contiguous chunks in layer order; at every tick a
device applies one chunk to the activation it holds, then ``ppermute``
shifts activations one stage down the ring. The schedules differ only
in how many chunks each device owns and how long a microbatch occupies
its ring slot:

``schedule="gpipe"``
    One chunk per device (``n_layers / S`` layers). A microbatch makes
    ``S`` hops; with ``M`` microbatches the schedule runs ``M + S - 1``
    ticks — bubble fraction ``(S - 1) / (M + S - 1)`` (fill + drain).

``schedule="1f1b"``
    The interleaved schedule: each device owns ``V = interleave`` chunks
    (``n_layers / (S * V)`` layers each), placed round-robin so global
    chunk ``g`` lives on device ``g mod S`` — a microbatch makes ``V * S``
    hops through the same ring, visiting every device ``V`` times. Each
    tick now moves ``1/V`` of a GPipe stage, so fill/drain cost shrinks by
    ``V`` relative to the work: for ``S | M`` the schedule runs
    ``V*M + S - 1`` ticks of ``1/V`` stage-time each — bubble fraction
    ``(S - 1) / (V*M + S - 1)``, strictly below GPipe's whenever ``S > 1``.
    (This is the forward pass of Megatron's interleaved 1F1B; the name is
    kept because the *schedule geometry* — virtual stages on a ring — is
    what sets the bubble, for forward-only serving exactly as for
    training.)

``schedule="zb-h1"``
    The zero-bubble three-phase schedule (ZB-H1 lineage): backward is
    split into B (input-grad) and W (weight-grad) ticks, so each
    microbatch's ring lifecycle is ``3*V*S`` chunk-ticks — ``V*S``
    F ticks that apply the layer chunks in order, ``V*S`` B occupancy
    ticks (the input-grad wave re-crossing every chunk boundary in the
    same ring direction), and ``V*S`` W ticks whose weight-grad work is
    what fills the warmup/cooldown slots that 1F1B leaves idle. All
    three phases are useful per-device work, so with three times the
    work amortizing the *same* straggler drain the bubble shrinks:
    ``1 - 3*V*M / ticks`` with
    ``ticks = 3*V*S*ceil(M/S) + (M-1) mod S`` — for ``S | M`` and
    ``V = 1`` that is ``3M + S - 1`` ticks, the canonical ZB-H1
    makespan. The executed forward applies chunks only during the F
    phase and carries the finished activation through the B/W occupancy
    ticks, so numerics still equal the sequential scan exactly.

    Ordering theorem (pinned by ``tests/test_zero_bubble.py``): with
    ``r = (M-1) mod S``, ``bubble(zb-h1) <= bubble(1f1b)`` iff
    ``3 * ticks_1f1b >= ticks_zb`` iff ``2r >= 0`` — always true, and
    *strict* exactly when ``r != 0`` (at ``M ≡ 1 (mod S)`` the lone
    straggler drains identically under both and they tie, the same tie
    region as 1F1B-vs-GPipe).

Every analytical quantity here is *exact*, not asymptotic:
:func:`schedule_ticks` is the precise number of ring ticks the shard_map
implementation scans, :func:`simulate_schedule` re-derives it by stepping
the ring event by event (the property tests pin closed form == simulation
== executed scan length for both schedules), and :func:`bubble_fraction`
is ``1 - ideal_work / ticks`` in consistent tick units.

Numerics match a sequential ``lax.scan`` over the full stack exactly for
all schedules: each microbatch sees the same layer order and the same
per-microbatch operand shapes, only interleaved in time across devices.
"""
from __future__ import annotations

import math
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

__all__ = [
    "pipeline_forward",
    "pipeline_bubble_fraction",
    "schedule_ticks",
    "bubble_fraction",
    "simulate_schedule",
    "SCHEDULES",
]

#: schedules pipeline_forward / schedule_ticks / bubble_fraction understand
SCHEDULES = ("gpipe", "1f1b", "zb-h1")

#: lifecycle phases per ring slot: 1F1B runs forward only (F); ZB-H1 adds
#: the B (input-grad) and W (weight-grad) occupancy phases — 3x the
#: per-microbatch chunk-ticks on the same slot machine
_PHASES = {"gpipe": 1, "1f1b": 1, "zb-h1": 3}


def _check_schedule(schedule: str) -> None:
    if schedule not in SCHEDULES:
        raise ValueError(f"schedule must be one of {SCHEDULES}, got {schedule!r}")


def schedule_ticks(
    n_stages: int, n_micro: int, schedule: str = "gpipe", interleave: int = 2
) -> int:
    """Exact ring-tick count of the executed :func:`pipeline_forward`
    schedule (the length of its ``lax.scan``).

    GPipe: ``M + S - 1``. The ring schedules hold at most ``S`` in-flight
    microbatches (one slot per device); a microbatch occupies its slot
    for its full lifecycle ``L`` and a new one can enter stage 0 only
    when the incoming slot is free — giving

        ``L * ceil(M/S) + (M-1) mod S``

    with ``L = V*S`` for interleaved 1F1B (``V*M + S - 1`` when ``S``
    divides ``M``, the Megatron interleaved form) and ``L = 3*V*S`` for
    ZB-H1 (the F/B/W three-phase lifecycle; ``3M + S - 1`` at ``V = 1``
    and ``S | M``, the canonical ZB-H1 makespan). With ``interleave=1``
    the 1F1B count degenerates to GPipe's ``M + S - 1`` — the ring is
    the same machine. Note a ring tick is ``1/V`` of a GPipe tick (a
    chunk is ``1/V`` of a stage); :func:`bubble_fraction` normalizes for
    that.
    """
    _check_schedule(schedule)
    S, M = int(n_stages), int(n_micro)
    if S < 1 or M < 1:
        raise ValueError(f"need n_stages >= 1 and n_micro >= 1, got {S}, {M}")
    if schedule == "gpipe":
        return M + S - 1
    V = int(interleave)
    if V < 1:
        raise ValueError(f"interleave must be >= 1, got {V}")
    return _PHASES[schedule] * V * S * math.ceil(M / S) + (M - 1) % S


def bubble_fraction(
    n_stages: int, n_micro: int, schedule: str = "gpipe", interleave: int = 2
) -> float:
    """Idle fraction of the schedule: ``1 - ideal_work / ticks``.

    Per-device ideal work is ``M`` stage-ticks for GPipe, ``V*M``
    chunk-ticks for 1F1B and ``3*V*M`` for ZB-H1 (F + B + W are all
    useful per-device compute; a chunk-tick is ``1/V`` of a stage-tick),
    so the fractions are directly comparable across schedules. For all
    ``(S, M >= 1)``: the 1F1B fraction is <= GPipe's, strictly smaller
    whenever ``S > 1``, ``interleave >= 2`` and ``M mod S != 1`` (at
    ``M ≡ 1 (mod S)`` the straggler microbatch drains alone under both
    schedules and they tie); and the ZB-H1 fraction is <= 1F1B's at the
    same ``V``, strictly smaller exactly when ``(M - 1) mod S != 0`` —
    pinned by the property tests in ``tests/test_parallelism.py`` and
    ``tests/test_zero_bubble.py``.
    """
    ticks = schedule_ticks(n_stages, n_micro, schedule, interleave)
    V = 1 if schedule == "gpipe" else int(interleave)
    work = n_micro * V * _PHASES[schedule]
    return (ticks - work) / ticks


def pipeline_bubble_fraction(n_stages: int, n_micro: int) -> float:
    """Idle fraction of the GPipe schedule (fill + drain). Kept for
    backward compatibility; equals ``bubble_fraction(S, M, "gpipe")``."""
    return bubble_fraction(n_stages, n_micro, "gpipe")


def simulate_schedule(
    n_stages: int, n_micro: int, schedule: str = "gpipe", interleave: int = 2
) -> int:
    """Event-driven reference simulation of the activation ring.

    Steps the exact machine :func:`pipeline_forward` implements — one
    in-flight slot per device, stage-0 injection only into a free slot,
    one lifecycle tick per ring tick, then a ring shift — and returns the
    tick at which the **last** microbatch completes. For ZB-H1 a slot's
    lifecycle spans the three phases (``g // (V*S)`` is 0 during F, 1
    during B, 2 during W); occupancy and completion are what set the tick
    count, so the same machine covers all ring schedules. This is an
    independent derivation of :func:`schedule_ticks` (no shared
    arithmetic); the property tests assert simulation == closed form for
    every schedule across the whole ``(S, M, V)`` grid, which is what
    licenses using the closed form as the analytical bubble model in
    ``core.e2e``.
    """
    _check_schedule(schedule)
    S, M = int(n_stages), int(n_micro)
    V = int(interleave) if schedule != "gpipe" else 1
    total_stages = _PHASES[schedule] * V * S
    slots: list = [None] * S  # per-device in-flight (microbatch, next stage)
    next_m = done = ticks = 0
    while done < M:
        if slots[0] is None and next_m < M:
            slots[0] = (next_m, 0)  # stage-0 injection into the free slot
            next_m += 1
        shifted: list = [None] * S
        for d in range(S):
            if slots[d] is None:
                continue
            m, g = slots[d]
            assert g % S == d, "chunk placement invariant: stage g lives on g mod S"
            g += 1
            if g == total_stages:
                done += 1  # finished on device S-1; slot recycles via the ring
            else:
                shifted[(d + 1) % S] = (m, g)
        slots = shifted
        ticks += 1
    return ticks


# ----------------------------------------------------------------------
# executed schedules (shard_map + ppermute)
# ----------------------------------------------------------------------


def pipeline_forward(
    layer_fn: Callable,
    params: Any,
    x,
    mesh,
    axis: Optional[str] = None,
    *,
    schedule: str = "gpipe",
    interleave: int = 2,
    ticks: Optional[int] = None,
):
    """Run a stacked layer pytree as a pipeline over ``mesh``.

    Schedule contract:

    * ``schedule="gpipe"`` (default): one contiguous stage per device;
      ``n_layers`` must divide by the pipeline axis size ``S``. Runs
      exactly ``schedule_ticks(S, M, "gpipe")`` ticks.
    * ``schedule="1f1b"``: interleaved virtual stages; ``n_layers`` must
      divide by ``S * interleave``. Runs exactly
      ``schedule_ticks(S, M, "1f1b", interleave)`` ticks. Any ``M >= 1``
      is supported (non-divisible microbatch counts pay the straggler
      drain the analytical model prices).
    * ``schedule="zb-h1"``: the zero-bubble three-phase ring; same layer
      divisibility as 1F1B. Chunks are applied during the F phase
      (lifecycle ticks ``< V*S``); the B/W phases carry the finished
      activation as occupancy ticks, so the output still equals the
      sequential scan. Runs exactly
      ``schedule_ticks(S, M, "zb-h1", interleave)`` ticks.

    Args:
      layer_fn: ``(layer_params, h) -> h`` for a single layer; applied to
        per-microbatch activations, so ``h`` has shape ``x.shape[1:]``.
      params: pytree whose leaves are stacked ``(n_layers, ...)``.
      x: ``(n_micro, *per_microbatch_shape)`` microbatched inputs.
      mesh: mesh containing the pipeline axis (defaults to its first axis).
      ticks: test/debug override of the scan length. The default (None)
        uses the analytical :func:`schedule_ticks`; the exactness tests
        run with ``ticks - 1`` to prove the analytical count is minimal,
        not merely sufficient.

    Returns ``(n_micro, *per_microbatch_shape)`` outputs, replicated across
    the pipeline axis — equal to scanning every layer over each microbatch
    (both schedules preserve layer order exactly).
    """
    _check_schedule(schedule)
    axis = axis or mesh.axis_names[0]
    if schedule in ("1f1b", "zb-h1"):
        return _forward_ring(
            layer_fn, params, x, mesh, axis, interleave, ticks, schedule
        )
    return _forward_gpipe(layer_fn, params, x, mesh, axis, ticks)


def _forward_gpipe(layer_fn, params, x, mesh, axis, ticks=None):
    n_stages = mesh.shape[axis]
    n_layers = jax.tree.leaves(params)[0].shape[0]
    if n_layers % n_stages != 0:
        raise ValueError(f"{n_layers} layers not divisible into {n_stages} stages")
    n_micro = x.shape[0]
    n_ticks = schedule_ticks(n_stages, n_micro, "gpipe") if ticks is None else ticks
    ring = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def stage_fn(stage_params, x_all):
        stage = lax.axis_index(axis)

        def apply_stage(h):
            def body(c, lp):
                return layer_fn(lp, c), None

            h, _ = lax.scan(body, h, stage_params)
            return h

        def tick(carry, t):
            state, outputs = carry
            # stage 0 ingests microbatch t while the schedule is filling
            inp = lax.dynamic_index_in_dim(x_all, jnp.clip(t, 0, n_micro - 1), keepdims=False)
            h = jnp.where(jnp.logical_and(stage == 0, t < n_micro), inp, state)
            y = apply_stage(h)
            # the last stage finishes microbatch t - (S - 1) at tick t
            idx = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
            cur = lax.dynamic_index_in_dim(outputs, idx, keepdims=False)
            take = jnp.logical_and(stage == n_stages - 1, t >= n_stages - 1)
            outputs = lax.dynamic_update_index_in_dim(
                outputs, jnp.where(take, y, cur), idx, 0
            )
            state = lax.ppermute(y, axis, ring)
            return (state, outputs), None

        init = (jnp.zeros_like(x_all[0]), jnp.zeros_like(x_all))
        (_, outputs), _ = lax.scan(tick, init, jnp.arange(n_ticks))
        # only the last stage holds real outputs; psum broadcasts them so the
        # result is replicated (out_specs P() below)
        return lax.psum(jnp.where(stage == n_stages - 1, outputs, 0.0), axis)

    pspecs = jax.tree.map(lambda _: P(axis), params)
    return jax.shard_map(
        stage_fn,
        mesh=mesh,
        in_specs=(pspecs, P()),
        out_specs=P(),
        check_vma=False,  # ppermute-carried state is intentionally unreplicated
    )(params, x)


def _forward_ring(layer_fn, params, x, mesh, axis, interleave, ticks=None,
                  schedule="1f1b"):
    n_stages = mesh.shape[axis]
    V = int(interleave)
    if V < 1:
        raise ValueError(f"interleave must be >= 1, got {V}")
    n_layers = jax.tree.leaves(params)[0].shape[0]
    if n_layers % (n_stages * V) != 0:
        raise ValueError(
            f"{n_layers} layers not divisible into {n_stages} stages x "
            f"{V} interleaved chunks"
        )
    per_chunk = n_layers // (n_stages * V)
    n_micro = x.shape[0]
    # forward chunk-stages apply layers; ZB-H1 extends the slot lifecycle
    # with the B/W occupancy phases (chunks applied only while g < V*S)
    forward_stages = V * n_stages
    total_stages = _PHASES[schedule] * forward_stages
    n_ticks = (
        schedule_ticks(n_stages, n_micro, schedule, V) if ticks is None else ticks
    )
    ring = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    # round-robin chunk placement: global chunk g = j * S + d lives on
    # device d, local slot j — reshape (L, ...) -> (V, S, per_chunk, ...)
    # and shard dim 1 so each device holds its V interleaved chunks
    chunked = jax.tree.map(
        lambda p: p.reshape(V, n_stages, per_chunk, *p.shape[1:]), params
    )

    def stage_fn(chunk_params, x_all):
        stage = lax.axis_index(axis)
        local = jax.tree.map(lambda p: p[:, 0], chunk_params)  # (V, per_chunk, ...)

        def apply_chunk(j, h):
            def run(jj):
                def f(hh):
                    def body(c, lp):
                        return layer_fn(lp, c), None

                    out, _ = lax.scan(body, hh, jax.tree.map(lambda p: p[jj], local))
                    return out

                return f

            return lax.switch(j, [run(jj) for jj in range(V)], h)

        def tick(carry, _t):
            h, g, m, live, next_m, outputs = carry
            # stage-0 injection: only into a free (non-live) incoming slot
            inject = jnp.logical_and(
                jnp.logical_and(stage == 0, live == 0), next_m < n_micro
            )
            inp = lax.dynamic_index_in_dim(
                x_all, jnp.clip(next_m, 0, n_micro - 1), keepdims=False
            )
            h = jnp.where(inject, inp, h)
            g = jnp.where(inject, 0, g)
            m = jnp.where(inject, next_m, m)
            live = jnp.where(inject, 1, live)
            next_m = next_m + inject.astype(jnp.int32)
            # process the local chunk this slot's next stage maps to; B/W
            # occupancy ticks (zb-h1, g >= V*S) carry h through unchanged
            j = jnp.clip(g // n_stages, 0, V - 1)
            y = apply_chunk(j, h)
            h = jnp.where(
                jnp.logical_and(live == 1, g < forward_stages), y, h
            )
            g = g + 1
            # the final lifecycle tick (g == phases*V*S) lands on device S-1
            fin = jnp.logical_and(live == 1, g >= total_stages)
            idx = jnp.clip(m, 0, n_micro - 1)
            cur = lax.dynamic_index_in_dim(outputs, idx, keepdims=False)
            outputs = lax.dynamic_update_index_in_dim(
                outputs, jnp.where(fin, h, cur), idx, 0
            )
            live = jnp.where(fin, 0, live)
            h = lax.ppermute(h, axis, ring)
            g = lax.ppermute(g, axis, ring)
            m = lax.ppermute(m, axis, ring)
            live = lax.ppermute(live, axis, ring)
            return (h, g, m, live, next_m, outputs), None

        zero = jnp.zeros((), jnp.int32)
        init = (
            jnp.zeros_like(x_all[0]),
            zero,  # g: next global chunk-stage of the held slot
            zero,  # m: microbatch index of the held slot
            zero,  # live: slot occupancy flag (int32 so ppermute is uniform)
            zero,  # next_m: injection counter (meaningful on stage 0 only)
            jnp.zeros_like(x_all),
        )
        (_, _, _, _, _, outputs), _ = lax.scan(tick, init, jnp.arange(n_ticks))
        return lax.psum(jnp.where(stage == n_stages - 1, outputs, 0.0), axis)

    pspecs = jax.tree.map(lambda _: P(None, axis), chunked)
    return jax.shard_map(
        stage_fn,
        mesh=mesh,
        in_specs=(pspecs, P()),
        out_specs=P(),
        check_vma=False,  # ppermute-carried state is intentionally unreplicated
    )(chunked, x)
