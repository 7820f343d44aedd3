"""Model operations of the tokens processed in the window (prompt tokens
once, generated tokens; no padding, no re-prefill) over the window times
the chip's peak."""

from counts.transformer import forward_flops


def read(run):
    c = run.counters
    if "prefill_tokens" not in c or not c.get("window_s"):
        return None
    flops = forward_flops(run.cfg, c["prefill_tokens"] + c["decode_tokens"],
                          c["prefill_pairs"] + c["decode_pairs"],
                          c["first_tokens"] + c["decode_tokens"])
    return 100.0 * flops / (c["window_s"] * run.peaks["peak_flops_bf16"])
