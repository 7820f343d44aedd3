"""Share of decode-step rows that produced a token: tokens decoded in the
window over decode steps times slots (engine counters)."""


def read(run):
    c = run.counters
    if not c.get("decode_steps"):
        return None
    return 100.0 * c["decode_tokens"] / (c["decode_steps"] * c["n_slots"])
