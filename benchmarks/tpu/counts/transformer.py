"""Operations and bytes of the dense decoder, from a configuration file's
sizes alone.  What the algorithm needs, not what an implementation
happens to do: no padding, no recomputation, no re-prefill, and the
same count whatever block size or kernel produces it.

A multiply-add is 2 operations.  Attention of one query over ``k`` keys
costs ``4 * H * Dh * k`` (scores and the weighted sum); a sequence of
``n`` tokens processed causally from position ``p0`` has
``sum(p0 + t + 1 for t < n)`` query-key pairs.
"""

from __future__ import annotations

__all__ = ["layer_matmul_params", "head_params", "matmul_params",
           "causal_pairs", "forward_flops",
           "paged_decode_cost", "BF16"]

BF16 = 2


def layer_matmul_params(cfg: dict) -> int:
    D, F = cfg["hidden_size"], cfg["intermediate_size"]
    H, Hkv, Dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    return D * H * Dh + 2 * D * Hkv * Dh + H * Dh * D + 3 * D * F


def head_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def matmul_params(cfg: dict) -> int:
    """Parameters that take part in a matrix product per token: every
    layer's projections and the head (the embedding is a gather)."""
    return cfg["num_hidden_layers"] * layer_matmul_params(cfg) + head_params(cfg)


def causal_pairs(n: int, p0: int = 0) -> int:
    """Query-key pairs of ``n`` tokens at positions ``p0 .. p0+n-1``."""
    return n * p0 + n * (n + 1) // 2


def _attn_flops(cfg: dict, pairs: int) -> int:
    return (4 * cfg["num_attention_heads"] * cfg["head_dim"]
            * cfg["num_hidden_layers"] * pairs)


def forward_flops(cfg: dict, tokens: int, pairs: int, logits: int) -> int:
    """Forward operations for ``tokens`` through every layer with
    ``pairs`` query-key pairs in all, and ``logits`` rows of the head."""
    return (2 * cfg["num_hidden_layers"] * layer_matmul_params(cfg) * tokens
            + _attn_flops(cfg, pairs) + 2 * head_params(cfg) * logits)


def paged_decode_cost(cfg: dict, live_tokens: int, rows: int) -> tuple[int, int]:
    """(operations, HBM bytes) of single-query decode attention over every
    layer for ``rows`` sequences holding ``live_tokens`` keys in all: the
    live tokens' K and V, each row's query read and output written, in
    bfloat16."""
    H, Hkv, Dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    L = cfg["num_hidden_layers"]
    flops = 4 * H * Dh * live_tokens * L
    kv = 2 * Hkv * Dh * BF16 * live_tokens
    qo = 2 * H * Dh * BF16 * rows
    return flops, (kv + qo) * L
