"""Median over the engine steps wholly inside the traced window of each
step's host time that does not wait on the chip: the ``serve.step`` span
less the union of the ``serve.sync`` spans nested in it (the blocking
reads of sampled tokens).  None where the trace holds no engine spans."""

import statistics

import serve_trace


def read(run):
    ph = serve_trace.of_run(run)
    xs = ph.step_host_s() if ph is not None else []
    return 1e3 * statistics.median(xs) if xs else None
