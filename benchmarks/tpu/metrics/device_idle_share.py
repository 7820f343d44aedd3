"""Share of the traced window in which no operation ran on the device
(1 minus the union of the device's operation intervals)."""


def read(run):
    tr = run.trace_summary
    share = tr.idle_share() if tr is not None else None
    return None if share is None else 100.0 * share
