"""What every driver shares: the run's context, the measured window (with
its compile counter and, in a traced run, the profiler), host spans,
percentiles, and the device's memory reading."""

from __future__ import annotations

import contextlib
import math
import os
import time
from dataclasses import dataclass, field

import jax

from cells import Cell

__all__ = ["Run", "span", "nearest_rank", "TRACE_SECONDS", "say"]

# A traced run measures (and traces) at most this long: the per-layer
# metrics need some seconds of steady state, not the whole window.
TRACE_SECONDS = 10.0

# Traces and compiles (or loads from the compile cache) while a window
# is open; the listener is registered once, by the first window.
_compiles = {"on": False, "n": 0, "listening": False}


def _on_compile(event: str, duration: float, **kw) -> None:
    if _compiles["on"] and event in (
            "/jax/core/compile/jaxpr_trace_duration",
            "/jax/core/compile/backend_compile_duration"):
        _compiles["n"] += 1


def say(key: str, value) -> None:
    """An information line on standard output (never the last line)."""
    print(f"# {key}: {value}", flush=True)


def span(name: str, **args):
    """A host span in the profiler's trace (``bench.<name>``)."""
    return jax.profiler.TraceAnnotation(f"bench.{name}", **args)


def nearest_rank(values, q: float) -> float:
    """The ``q`` quantile by nearest rank (infinite values sort last)."""
    xs = sorted(values)
    if not xs:
        raise ValueError("no values")
    return float(xs[max(0, math.ceil(q * len(xs)) - 1)])


@dataclass
class Run:
    cell: Cell
    seed: int
    seconds: float
    trace: bool
    t_process: float               # perf_counter when the process began
    out_dir: str                   # scratch for this run (the trace)
    program: object                # the program's ArchConfig
    peaks: dict
    device: object
    e2e: dict = field(default_factory=dict)
    counters: dict = field(default_factory=dict)
    checks: list = field(default_factory=list)   # (name, value, limit)
    attempted: int = 0
    failed: int = 0
    excluded_s: float = 0.0        # set-up time spent on correctness reads
    window_s: float = 0.0
    trace_dir: str | None = None
    compiles_in_window: int = 0
    trace_summary: object = None   # trace_reduce.Trace of a traced run

    @property
    def cfg(self) -> dict:
        return self.cell.config

    @property
    def wl(self) -> dict:
        return self.cell.workload

    @property
    def window_seconds(self) -> float:
        return min(self.seconds, TRACE_SECONDS) if self.trace else self.seconds

    @contextlib.contextmanager
    def excluded(self):
        """Set-up time that reads correctness numbers: not set-up."""
        t = time.perf_counter()
        try:
            yield
        finally:
            self.excluded_s += time.perf_counter() - t

    @contextlib.contextmanager
    def window(self):
        """The measured window: set-up ends as it opens; compiles inside
        are counted; a traced run records the profiler around it."""
        if self.trace:
            self.trace_dir = os.path.join(self.out_dir, "trace")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0     # host spans only, no call tracing
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self.e2e["setup_s"] = (time.perf_counter() - self.t_process
                               - self.excluded_s)
        if not _compiles["listening"]:
            jax.monitoring.register_event_duration_secs_listener(_on_compile)
            _compiles["listening"] = True
        _compiles["n"], _compiles["on"] = 0, True
        try:
            with span("window"):
                yield
        finally:
            _compiles["on"] = False
            self.compiles_in_window = _compiles["n"]
            if self.trace:
                jax.profiler.stop_trace()
            say("compiles_in_window", self.compiles_in_window)

    def check(self, name: str, value: float, limit: float) -> None:
        self.checks.append((name, float(value), float(limit)))

    def memory_peak(self) -> int:
        stats = self.device.memory_stats() or {}
        peak = int(stats.get("peak_bytes_in_use", 0))
        self.counters["memory_peak_bytes"] = peak
        say("peak_hbm_bytes", peak)
        return peak
