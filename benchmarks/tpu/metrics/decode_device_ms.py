"""Median device time of one run of the engine's decode program
(``jit_serve_decode``, all slots one token) over its runs wholly inside
the traced window.  None where the trace holds no such program."""

import statistics

import serve_trace

PROGRAM = "jit_serve_decode"


def read(run):
    ph = serve_trace.of_run(run)
    if ph is None:
        return None
    lo, hi = ph.trace.window
    ns = [e - s for s, e in ph.module_runs(PROGRAM) if lo <= s and e <= hi]
    return 1e-6 * statistics.median(ns) if ns else None
