"""What the serving and the training loops share: the cell's spec, the
run's record, host spans, compile counting and device memory."""
from __future__ import annotations

import contextlib
import dataclasses
import json
import time
from pathlib import Path
from typing import Any, Optional

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent

#: the traced window starts this far into the measured one and lasts this long
TRACE_AT_S, TRACE_S = 5.0, 5.0


@dataclasses.dataclass
class Spec:
    """One cell: its published config (``hf``) and the benchmark's settings
    for it (``bench``), its traffic mix and its own sizes, and the
    program's ``ArchConfig`` built from them."""

    name: str
    hf: dict
    bench: dict
    mix: dict
    cell: dict
    cfg: Any
    chips: int = 1

    @property
    def qk_norm(self) -> bool:
        return bool(self.bench.get("qk_norm", False))


@dataclasses.dataclass
class Record:
    """What one run measured, for the metric readers."""

    spec: Spec
    seconds: float
    setup_s: float = 0.0
    window: tuple = (0.0, 0.0)  # host perf_counter start and end of the window
    trace_window: Optional[tuple] = None  # host perf_counter start and end
    trace: Any = None  # trace.TraceSummary of the traced window
    sizes: Any = None  # count.Sizes
    peaks: Optional[dict] = None
    requests: dict = dataclasses.field(default_factory=dict)
    steps: list = dataclasses.field(default_factory=list)
    attempted: int = 0
    failed: int = 0
    memory_peak_bytes: Optional[int] = None
    compiles_in_window: int = 0
    backlog_at_close: int = 0
    checks: dict = dataclasses.field(default_factory=dict)

    def in_trace(self, t0: float, t1: float) -> bool:
        """Whether host interval ``[t0, t1]`` lies inside the traced window."""
        return self.trace_window is not None and \
            self.trace_window[0] <= t0 and t1 <= self.trace_window[1]


def load_json(path: Path) -> dict:
    return json.loads(Path(path).read_text())


class Tracer:
    """Host spans (``jax.profiler.TraceAnnotation`` named ``chipbench.<x>``)
    and the traced window; spans cost nothing when the run is not traced."""

    def __init__(self, enabled: bool, trace_dir: Optional[str] = None, seconds: float = 60.0):
        self.enabled = enabled
        self.trace_dir = trace_dir
        # a short window is traced from its first quarter for half its length
        self.at, self.length = min(TRACE_AT_S, seconds / 4), min(TRACE_S, seconds / 2)
        self.state = "before"  # before -> on -> done
        self._window = None
        self.host_window = None

    def span(self, name: str):
        if not self.enabled:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation("chipbench." + name)

    def maybe_start(self, now: float, t0: float):
        if self.enabled and self.state == "before" and now >= t0 + self.at:
            import jax

            jax.profiler.start_trace(self.trace_dir)
            self._window = jax.profiler.TraceAnnotation("chipbench.window")
            self._window.__enter__()
            self.host_window = [time.perf_counter(), None]
            self.state = "on"

    def maybe_stop(self, now: float, t0: float, force: bool = False):
        if self.state == "on" and (force or now >= t0 + self.at + self.length):
            import jax

            self.host_window[1] = time.perf_counter()
            self._window.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.state = "done"


@contextlib.contextmanager
def compile_counter():
    """Count XLA backend compiles while the block runs."""
    import jax

    stats = {"compiles": 0}

    def on_duration(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            stats["compiles"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        yield stats
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)


def memory_peak(device) -> Optional[int]:
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def check(value: float, limit: float) -> dict:
    return {"value": value, "limit": limit}


def correct(checks: dict) -> bool:
    """A run is correct when it compared something and every number lies
    within its limit."""
    return bool(checks) and all(c["value"] <= c["limit"] for c in checks.values())


def program_config(base, cell: dict):
    """The program's config for a cell: its parameters in the dtype the
    harness makes the weights in (the cell's ``weights_dtype``)."""
    return dataclasses.replace(base, param_dtype=cell["weights_dtype"])
