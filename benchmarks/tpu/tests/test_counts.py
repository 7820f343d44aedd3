"""Operation and byte counts from shapes."""

import json
import os

import numpy as np
import pytest

from counts.transformer import (causal_pairs, forward_flops,
                                matmul_params, paged_decode_cost)

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cfg(name):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)


def test_causal_pairs_counts_every_query_key_pair():
    assert causal_pairs(4) == 1 + 2 + 3 + 4
    assert causal_pairs(3, p0=10) == 11 + 12 + 13
    assert causal_pairs(5) == causal_pairs(2) + causal_pairs(3, p0=2)


def test_forward_flops_match_the_hand_count():
    cfg = _cfg("qwen3-4b")
    per_layer = 2560 * 4096 + 2 * 2560 * 1024 + 4096 * 2560 + 3 * 2560 * 9728
    assert matmul_params(cfg) == 36 * per_layer + 2560 * 151936
    assert matmul_params(cfg) == pytest.approx(4.02e9, rel=1e-3)
    # one decode token over 1,000 keys: 2 per multiply-add of every matrix,
    # 4 x H x Dh per key in every layer
    assert forward_flops(cfg, 1, 1000, 1) == (
        2 * matmul_params(cfg) + 4 * 32 * 128 * 36 * 1000)


def _live_by_blocks(contexts, bs):
    """Live tokens counted through a paged block table of block size
    ``bs``: each row's keys are its blocks' slots up to its length."""
    live = 0
    for ctx in contexts:
        n_blocks = -(-ctx // bs)
        table = np.arange(n_blocks)
        slots = (table[:, None] * bs + np.arange(bs)[None, :]).ravel()
        live += int((slots < ctx).sum())
    return live


@pytest.mark.parametrize("bs", [16, 64, 128, 256, 512])
def test_paged_decode_bytes_do_not_change_with_block_size(bs):
    cfg = _cfg("qwen3-4b")
    contexts = [1024, 1025, 1536, 1791, 2047, 1, 300]
    flops, hbm = paged_decode_cost(cfg, _live_by_blocks(contexts, bs),
                                   len(contexts))
    ref_flops, ref_hbm = paged_decode_cost(cfg, sum(contexts), len(contexts))
    assert (flops, hbm) == (ref_flops, ref_hbm)
    # K and V of every live token in every layer, plus q and out per row
    per_tok = 36 * 2 * 8 * 128 * 2
    assert ref_hbm == per_tok * sum(contexts) + 36 * 2 * 32 * 128 * 2 * 7
