"""Reduce a profiler trace (``.xplane.pb``) to what the per-layer metrics
read: the device's busy intervals, device time per jitted program, the
costliest device operations, and the idle gaps, each labelled with the
harness span the host was in.

The harness marks its own host spans with ``jax.profiler.TraceAnnotation``
under the prefix ``chipbench.``, among them one ``chipbench.window`` around
the traced window, so host spans and device events share the trace's clock.
Programs are found by their jit names, kept in ``PROGRAMS`` alone.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
from collections import defaultdict

#: the engine's and the trainer's jitted programs, by the names the harness
#: uses for them; a module event belongs to a program when its name starts
#: with the jit name followed by ``(`` or ``.`` or ends there
PROGRAMS = {"decode": "jit_decode", "prefill": "jit_prefill", "train_step": "jit_train_step"}
SPAN_PREFIX = "chipbench."
WINDOW_SPAN = SPAN_PREFIX + "window"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float  # seconds on the trace's clock
    end: float


def merge(intervals) -> list[tuple[float, float]]:
    """The union of ``(start, end)`` intervals, sorted and disjoint."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(events, lo: float, hi: float) -> list[Event]:
    return [Event(e.name, max(e.start, lo), min(e.end, hi))
            for e in events if e.end > lo and e.start < hi]


def short(name: str) -> str:
    """A module's name without its hash (``jit_decode(123)``), an
    operation's without its HLO text (``%copy.7 = bf16[...] copy(...)``)."""
    return name.split(" = ")[0].split("(")[0]


#: operations that hold others (a loop's body runs inside its event)
CONTAINERS = ("%while", "%conditional", "%call")


def program_of(module_name: str) -> str | None:
    for prog, jit_name in PROGRAMS.items():
        if module_name == jit_name or module_name.startswith((jit_name + "(", jit_name + ".")):
            return prog
    return None


@dataclasses.dataclass
class TraceSummary:
    """One traced window. ``ops`` and ``modules`` hold one list per device
    that ran anything; ``spans`` are the harness's host spans (prefix
    stripped), all clipped to the window."""

    window: tuple[float, float]
    ops: list[list[Event]]
    modules: list[list[Event]]
    spans: list[Event]

    @classmethod
    def build(cls, device_ops, device_modules, host_spans) -> TraceSummary:
        """From raw events per device and the host's spans; the window is
        the ``chipbench.window`` span."""
        windows = [e for e in host_spans if e.name == WINDOW_SPAN]
        if len(windows) != 1:
            raise ValueError(f"expected one {WINDOW_SPAN} span, found {len(windows)}")
        lo, hi = windows[0].start, windows[0].end
        keep = [i for i, ops in enumerate(device_ops) if ops]
        spans = [Event(e.name[len(SPAN_PREFIX):], e.start, e.end)
                 for e in clip(host_spans, lo, hi)
                 if e.name.startswith(SPAN_PREFIX) and e.name != WINDOW_SPAN]
        return cls(
            window=(lo, hi),
            ops=[clip(device_ops[i], lo, hi) for i in keep],
            modules=[clip(device_modules[i] if i < len(device_modules) else [], lo, hi)
                     for i in keep],
            spans=sorted(spans, key=lambda e: (e.start, -e.end)),
        )

    # -- the device -------------------------------------------------------
    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    @property
    def n_devices(self) -> int:
        return len(self.ops)

    def busy(self, device: int = 0) -> list[tuple[float, float]]:
        """The union of the device's operation intervals (none where the
        trace holds no device)."""
        if device >= self.n_devices:
            return []
        cache = self.__dict__.setdefault("_busy", {})
        if device not in cache:
            cache[device] = merge((e.start, e.end) for e in self.ops[device])
        return cache[device]

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        if not self.ops:
            return 0.0
        return sum(sum(e - s for s, e in self.busy(d)) for d in range(self.n_devices)) \
            / self.n_devices

    def busy_within(self, start: float, end: float, device: int = 0) -> float:
        busy = self.busy(device)
        i = max(0, bisect.bisect_right([s for s, _ in busy], start) - 1)
        total = 0.0
        for s, e in busy[i:]:
            if s >= end:
                break
            total += max(0.0, min(e, end) - max(s, start))
        return total

    # -- programs -----------------------------------------------------------
    def program_events(self, prog: str, device: int = 0) -> list[Event]:
        if not self.modules:
            return []
        return [e for e in self.modules[device] if program_of(e.name) == prog]

    def program_s(self, prog: str, device: int = 0) -> float:
        return sum(e.end - e.start for e in self.program_events(prog, device))

    def program_count(self, prog: str, device: int = 0) -> int:
        return len(self.program_events(prog, device))

    def program_run_s(self, prog: str, device: int = 0) -> float | None:
        """Mean device time of the program's whole runs: those inside the
        window, other than the trace's first and last program runs, which
        the profiler's start and stop may cut."""
        lo, hi = self.window
        mods = self.modules[device] if self.modules else []
        edges = (min(mods, key=lambda e: e.start), max(mods, key=lambda e: e.end)) if mods else ()
        whole = [e.end - e.start for e in self.program_events(prog, device)
                 if lo < e.start and e.end < hi and all(e is not x for x in edges)]
        return sum(whole) / len(whole) if whole else None

    # -- the host's spans -----------------------------------------------------
    def spans_named(self, name: str) -> list[Event]:
        return [e for e in self.spans if e.name == name]

    def span_at(self, t: float) -> str:
        """The innermost harness span that holds time ``t``."""
        inner = None
        for e in self.spans:
            if e.start <= t <= e.end and (inner is None or e.end - e.start < inner.end - inner.start):
                inner = e
        return inner.name if inner else "none"

    # -- breakdown --------------------------------------------------------------
    def top_ops(self, k: int = 10, device: int = 0) -> list[list]:
        """The ``k`` device operations that took most time, each named
        ``<program>:<op>`` after the module it ran in."""
        if device >= self.n_devices:
            return []
        mods = sorted(self.modules[device], key=lambda e: e.start) if self.modules else []
        starts = [m.start for m in mods]
        total: dict[str, float] = defaultdict(float)
        for e in self.ops[device]:
            if e.name.startswith(CONTAINERS):
                continue
            i = bisect.bisect_right(starts, e.start) - 1
            mod = short(mods[i].name) if i >= 0 and mods[i].end >= e.start else "?"
            total[f"{mod}:{short(e.name)}"] += e.end - e.start
        return [[n, s] for n, s in sorted(total.items(), key=lambda x: -x[1])[:k]]

    def idle_gaps(self, k: int = 10, device: int = 0) -> list[list]:
        """The ``k`` longest stretches of the window with no device
        operation, each labelled with the harness span the host was in at
        its middle and the program that ran last before it."""
        if device >= self.n_devices:
            return []
        lo, hi = self.window
        busy = self.busy(device)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        mods = sorted(self.modules[device], key=lambda e: e.end) if self.modules else []
        ends = [m.end for m in mods]
        out = []
        for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:k]:
            i = bisect.bisect_right(ends, s) - 1
            before = short(mods[i].name) if i >= 0 else "start"
            out.append([f"{self.span_at((s + e) / 2)} after {before}", e - s])
        return out


def load(trace_dir: str) -> TraceSummary:
    """Read the one ``.xplane.pb`` under ``trace_dir`` with JAX's own
    reader. Device planes are the ``/device:`` planes other than the CPU;
    on each, ``XLA Ops`` holds the operations and ``XLA Modules`` the
    programs they belong to."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if len(files) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {trace_dir}, found {files}")
    pd = ProfileData.from_file(files[0])
    device_ops, device_modules, host_spans = [], [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            ops, mods = [], []
            for line in plane.lines:
                target = ops if line.name == OPS_LINE else mods if line.name == MODULES_LINE else None
                if target is None:
                    continue
                for ev in line.events:
                    s = ev.start_ns * 1e-9
                    target.append(Event(ev.name, s, s + ev.duration_ns * 1e-9))
            device_ops.append(ops)
            device_modules.append(mods)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        s = ev.start_ns * 1e-9
                        host_spans.append(Event(ev.name, s, s + ev.duration_ns * 1e-9))
    return TraceSummary.build(device_ops, device_modules, host_spans)
