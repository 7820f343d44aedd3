"""Engine phases and named device programs in a profiler trace.

``trace_reduce`` reduces a trace to the benchmark's own ``bench.*`` spans
and the device's operations.  This module reads, from the same trace,
what the serving engine writes into it (docs/serve.md "Observability"):

* host spans ``serve.*``: one ``serve.step`` per engine step, with its
  phases (``serve.admit``, ``serve.decode``, ``serve.sync`` ...) nested
  in it, beside the ``bench.*`` spans;
* runs of the engine's named device programs (``jit_serve_decode``,
  ``jit_serve_chunk`` ...): the events of a device's ``XLA Modules``
  line, named ``<program>(<fingerprint>)``, put on the host's clock by
  the offset ``trace_reduce`` puts the operations on it with;
* ``links``: the program runs linked by their flow id to their
  completion on the host, the links that offset is taken from.  Zero
  means the device's times were left on the device's clock.

Idle time is split over the host spans: each part of an idle stretch of
the device is put down to the innermost ``bench.*`` or ``serve.*`` span
that covers that part, so that a gap inside an engine step is named by
the phase that held the host.
"""

from __future__ import annotations

import bisect
import os
from collections import defaultdict
from dataclasses import dataclass

import trace_reduce as T

__all__ = ["Phases", "from_profile", "load", "of_run", "PREFIXES"]

PREFIXES = ("bench.", "serve.")
STEP = "serve.step"
SYNC = "serve.sync"


def _program(name: str) -> str:
    """``jit_serve_decode(1234)`` → ``jit_serve_decode``."""
    return name.split("(", 1)[0]


def _innermost(spans) -> list[tuple[int, int, str]]:
    """The host's timeline as pieces ``(start, end, name)``, each named
    by the shortest span that covers it; stretches no span covers are
    left out."""
    cuts = sorted({t for _, s, e in spans for t in (s, e)})
    opens = sorted(spans, key=lambda sp: sp[1])
    active, out, k = [], [], 0
    for a, b in zip(cuts, cuts[1:]):
        while k < len(opens) and opens[k][1] <= a:
            active.append(opens[k])
            k += 1
        active = [sp for sp in active if sp[2] > a]
        if active:
            n = min((e - s, n) for n, s, e in active)[1]
            if out and out[-1][2] == n and out[-1][1] == a:
                out[-1] = (out[-1][0], b, n)
            else:
                out.append((a, b, n))
    return out


@dataclass
class Phases:
    trace: T.Trace                              # window, operations, busy
    spans: list[tuple[str, int, int]]           # bench.* and serve.*
    modules: dict[str, list[T.Interval]]        # program → runs, host clock
    links: int                                  # runs linked to the host

    def module_runs(self, name: str) -> list[T.Interval]:
        """Device intervals of the program ``name``, on the host clock."""
        return self.modules.get(name, [])

    def program_seconds(self) -> dict[str, float]:
        """Device seconds per program inside the window."""
        lo, hi = self.trace.window
        out = {n: T._length(T.clip(runs, lo, hi)) * 1e-9
               for n, runs in self.modules.items()}
        return {n: s for n, s in sorted(out.items(), key=lambda kv: -kv[1])
                if s > 0}

    def idle_gaps(self, device: str | None = None) -> list[tuple[str, float]]:
        """Idle seconds of one device (the first by default) in the
        window, each part summed under the innermost host span that
        covers it (``bench.window`` where none does)."""
        tr = self.trace
        device = device or (tr.devices[0] if tr.devices else None)
        if device is None:
            return []
        lo, hi = tr.window
        gaps, t = [], lo
        for s, e in tr.busy(device):
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if hi > t:
            gaps.append((t, hi))
        pieces = _innermost([sp for sp in self.spans if sp[0] != T.WINDOW])
        starts = [p[0] for p in pieces]
        out: dict[str, float] = defaultdict(float)
        for s, e in gaps:
            covered = 0
            i = max(0, bisect.bisect_right(starts, s) - 1)
            while i < len(pieces) and pieces[i][0] < e:
                a, b, n = pieces[i]
                d = min(b, e) - max(a, s)
                if d > 0:
                    out[n] += d * 1e-9
                    covered += d
                i += 1
            if e - s > covered:
                out[T.WINDOW] += (e - s - covered) * 1e-9
        return sorted(out.items(), key=lambda kv: -kv[1])

    def step_host_s(self) -> list[float]:
        """Per ``serve.step`` span wholly inside the window: its length
        less the union of the ``serve.sync`` spans inside it (host work
        of the step that does not wait on the chip), in seconds."""
        lo, hi = self.trace.window
        syncs = sorted((s, e) for n, s, e in self.spans if n == SYNC)
        starts = [s for s, _ in syncs]
        out = []
        for n, s, e in self.spans:
            if n != STEP or s < lo or e > hi:
                continue
            i = bisect.bisect_left(starts, s)
            inner = [(a, b) for a, b in syncs[i:bisect.bisect_right(starts, e)]
                     if b <= e]
            out.append((e - s - T._length(T.union(inner))) * 1e-9)
        return out


def _read(pd) -> tuple[list, dict, int, float]:
    """Host spans, program runs (on the device's clock), and the flow
    links between runs and their completion on the host."""
    spans, runs, ends, done = [], [], {}, {}
    for plane in pd.planes:
        device = plane.name.startswith(T.DEVICE_PREFIX)
        for line in plane.lines:
            if line.name == T.OPS_LINE:
                continue
            if device:
                if line.name == T.MODULES_LINE:
                    for e in line.events:
                        runs.append((_program(e.name), e.start_ns, e.end_ns))
                        ends[T._stat(e, "_c")] = e.end_ns
                continue
            for e in line.events:
                if e.name.startswith(PREFIXES):
                    spans.append((e.name, e.start_ns, e.end_ns))
                elif e.name == T.COMPLETION:
                    done[T._stat(e, "_c")] = e.start_ns
    gaps = [done[c] - t for c, t in ends.items()
            if c is not None and c in done]
    return spans, runs, len(gaps), min(gaps) if gaps else 0.0


def from_profile(pd, trace: T.Trace | None = None) -> Phases:
    """The phases of a loaded ``ProfileData``; ``trace`` is its
    ``trace_reduce`` reduction, if already made."""
    trace = trace if trace is not None else T.from_profile(pd)
    spans, runs, links, offset = _read(pd)
    modules: dict[str, list[T.Interval]] = defaultdict(list)
    for n, s, e in runs:
        modules[n].append((s + offset, e + offset))
    return Phases(trace, sorted(spans, key=lambda sp: sp[1]),
                  {n: sorted(r) for n, r in modules.items()}, links)


def load(path: str) -> Phases:
    """The phases of an ``.xplane.pb`` file or the directory it is under."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = T.find_xplane(path)
    return from_profile(ProfileData.from_file(path))


def of_run(run) -> Phases | None:
    """The phases of a traced run, read from its trace once (None for a
    run without a trace).  The first read prints the clock links, the
    idle gaps by phase and the device seconds per program as information
    lines."""
    trace, trace_dir = (getattr(run, "trace_summary", None),
                        getattr(run, "trace_dir", None))
    if trace is None or trace_dir is None:
        return None
    ph = getattr(run, "serve_phases", None)
    if ph is None:
        from jax.profiler import ProfileData

        from harness import say

        ph = from_profile(ProfileData.from_file(T.find_xplane(trace_dir)),
                          trace)
        run.serve_phases = ph
        say("clock_links", ph.links)
        say("phase_gaps", [[n, s] for n, s in ph.idle_gaps()[:12]])
        say("program_seconds", ph.program_seconds())
    return ph
