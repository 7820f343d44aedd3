"""95th percentile over the requests due in the window of the engine steps
between a request's submission and its first token (a failed request
counts as infinitely many): admission and chunked prefill, free of the
host clock."""

from harness import nearest_rank


def read(run):
    steps = run.counters.get("ttft_steps")
    return nearest_rank(steps, 0.95) if steps else None
