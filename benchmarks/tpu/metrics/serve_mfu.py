"""Model operations of the tokens processed in the window (prompt tokens
once, generated tokens; no padding, no re-prefill), as the model family's
``counts`` reckons them, over the window times the chip's peak."""


def read(run):
    c = run.counters
    if "prefill_tokens" not in c or not c.get("window_s"):
        return None
    forward_flops = run.cell.family.counts.forward_flops
    flops = forward_flops(run.cfg, c["prefill_tokens"] + c["decode_tokens"],
                          c["prefill_pairs"] + c["decode_pairs"],
                          c["first_tokens"] + c["decode_tokens"])
    return 100.0 * flops / (c["window_s"] * run.peaks["peak_flops_bf16"])
