"""The paged decode attention kernel's share of its roofline in the traced
window: the least time its work could take on the chip (bytes of the
live keys and values plus each row's query and output, over peak HBM
bandwidth, or operations over peak compute, whichever is larger) over
the kernel's device time in the trace.  Decode attention is bound by
HBM: two operations per byte against the chip's 240.  The bytes and
operations are the model family's (``counts/<family>.py``)."""

KERNEL = "paged_decode"


def read(run):
    tr = run.trace_summary
    if tr is None:
        return None
    secs, n = tr.kernel(KERNEL)
    live = [(k, r) for _, k, r in run.counters.get("live", [])]
    if not n or not live:
        return None
    cost = run.cell.family.counts.paged_decode_cost
    flops = sum(cost(run.cfg, k, r)[0] for k, r in live)
    hbm = sum(cost(run.cfg, k, r)[1] for k, r in live)
    floor = max(flops / run.peaks["peak_flops_bf16"],
                hbm / run.peaks["hbm_bytes_per_s"])
    return 100.0 * floor / secs
