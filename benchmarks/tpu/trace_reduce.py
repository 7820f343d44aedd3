"""Reduction of a JAX profiler trace (``*.xplane.pb``) to the numbers the
per-layer metrics read.

* The traced window is the host span ``bench.window`` that the harness
  opens and closes around the part of the run it traces.
* A device is a plane named ``/device:TPU:<n>``; its operations are the
  events on its ``XLA Ops`` line, each named by its HLO text
  (``%name = shape op(...), ...``).  Busy time is the union of those
  events' intervals inside the window, so overlapping or nested events
  count once.
* The device's clock is put on the host's: each program run on the
  ``XLA Modules`` line is linked by its flow id (``_c``) to the host's
  ``CompleteCallbacks`` event, and the smallest gap from a run's end to
  its completion on the host is taken as the offset.
* A Pallas (Mosaic) kernel is an operation whose HLO is a
  ``tpu_custom_call``; it is found by its instruction name, which
  carries the name of the jitted function that wraps the kernel (the
  ``paged_decode`` kernel is ``%_paged_decode_jit.<n>``).
* An idle gap is a stretch of the window in which no operation runs on
  the device; it is named after the innermost ``bench.*`` host span
  that covers its midpoint.
"""

from __future__ import annotations

import glob
import os
from collections import defaultdict
from dataclasses import dataclass, field

__all__ = ["Interval", "union", "clip", "Trace", "load", "find_xplane",
           "WINDOW", "SPAN_PREFIX"]

WINDOW = "bench.window"
SPAN_PREFIX = "bench."
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COMPLETION = "CompleteCallbacks"
MOSAIC = 'custom_call_target="tpu_custom_call"'

Interval = tuple[int, int]          # (start_ns, end_ns)


def union(intervals) -> list[Interval]:
    """Merged, sorted union of half-open intervals."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: int, hi: int) -> list[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def _length(intervals) -> int:
    return sum(e - s for s, e in intervals)


def _stat(event, key: str):
    for k, v in event.stats:
        if k == key:
            return v
    return None


@dataclass
class Op:
    name: str             # the HLO instruction's name
    mosaic: bool          # a Pallas kernel (tpu_custom_call)
    start: int
    end: int

    @classmethod
    def of(cls, event, offset: float) -> "Op":
        text = event.name
        name = text.split(" = ", 1)[0].lstrip("%")
        return cls(name, MOSAIC in text, event.start_ns + offset,
                   event.end_ns + offset)


@dataclass
class Trace:
    window: Interval
    ops: dict[str, list[Op]]                 # device plane → operations
    spans: list[tuple[str, int, int]]        # host bench.* spans
    _busy: dict = field(default_factory=dict)

    # -- the window -------------------------------------------------------

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def devices(self) -> list[str]:
        return sorted(self.ops)

    def busy(self, device: str) -> list[Interval]:
        if device not in self._busy:
            self._busy[device] = clip(
                union((o.start, o.end) for o in self.ops[device]),
                *self.window)
        return self._busy[device]

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        if not self.ops:
            return 0.0
        return sum(_length(self.busy(d)) for d in self.devices) * 1e-9 / len(
            self.ops)

    def idle_share(self) -> float | None:
        if not self.ops or self.window_s <= 0:
            return None
        return 1.0 - self.busy_s() / self.window_s

    # -- operations -------------------------------------------------------

    def op_seconds(self) -> dict[str, float]:
        """Device seconds per operation name inside the window, summed
        over the devices."""
        out: dict[str, float] = defaultdict(float)
        lo, hi = self.window
        for ops in self.ops.values():
            for o in ops:
                d = min(o.end, hi) - max(o.start, lo)
                if d > 0:
                    out[o.name] += d * 1e-9
        return dict(out)

    def kernel(self, name: str) -> tuple[float, int]:
        """(device seconds, events) of Pallas kernels whose instruction
        name holds ``name``, inside the window, over all devices."""
        lo, hi = self.window
        secs, n = 0.0, 0
        for ops in self.ops.values():
            for o in ops:
                if o.mosaic and name in o.name:
                    d = min(o.end, hi) - max(o.start, lo)
                    if d > 0:
                        secs += d * 1e-9
                        n += 1
        return secs, n

    # -- idle time ------------------------------------------------------------

    def idle_gaps(self, device: str | None = None) -> list[tuple[str, float]]:
        """Idle seconds of one device (the first by default) in the
        window, summed by the innermost host span covering each gap."""
        device = device or (self.devices[0] if self.devices else None)
        if device is None:
            return []
        lo, hi = self.window
        gaps, t = [], lo
        for s, e in self.busy(device):
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if hi > t:
            gaps.append((t, hi))
        spans = [sp for sp in self.spans if sp[0] != WINDOW]
        out: dict[str, float] = defaultdict(float)
        for s, e in gaps:
            mid = (s + e) // 2
            cover = [(e2 - s2, n) for n, s2, e2 in spans if s2 <= mid < e2]
            out[min(cover)[1] if cover else WINDOW] += (e - s) * 1e-9
        return sorted(out.items(), key=lambda kv: -kv[1])

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_seconds().items(), key=lambda kv: -kv[1])
        return {"device_ops": [[n, s] for n, s in ops[:top]],
                "idle_gaps": [[n, s] for n, s in self.idle_gaps()[:top]]}


def find_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise FileNotFoundError(f"{len(files)} xplane files under {trace_dir}")
    return files[0]


def _device_offset(pd) -> float:
    """Nanoseconds to add to device times to put them on the host's clock
    (0 where no program run is linked to its completion)."""
    ends, done = {}, {}
    for plane in pd.planes:
        for line in plane.lines:
            if plane.name.startswith(DEVICE_PREFIX) and line.name == MODULES_LINE:
                for e in line.events:
                    ends[_stat(e, "_c")] = e.end_ns
            elif line.name != OPS_LINE:
                for e in line.events:
                    if e.name == COMPLETION:
                        done[_stat(e, "_c")] = e.start_ns
    gaps = [done[c] - t for c, t in ends.items() if c is not None and c in done]
    return min(gaps) if gaps else 0.0


def from_profile(pd) -> Trace:
    offset = _device_offset(pd)
    window = None
    spans = []
    ops: dict[str, list[Op]] = {}
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.setdefault(plane.name, []).extend(
                        Op.of(e, offset) for e in line.events)
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(SPAN_PREFIX):
                    spans.append((e.name, e.start_ns, e.end_ns))
                    if e.name == WINDOW:
                        window = (e.start_ns, e.end_ns)
    if window is None:
        raise ValueError(f"the trace holds no {WINDOW!r} span")
    return Trace(window, ops, spans)


def load(path: str) -> Trace:
    """A trace from an ``.xplane.pb`` file or the directory it is under."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = find_xplane(path)
    return from_profile(ProfileData.from_file(path))
