"""The dense decoder that InternLM2 and Qwen3 share: a configuration
file's keys mapped onto the program's ``ArchConfig``, and the weights
tree the program takes for it.

The tree has layers stacked on a leading axis under ``blocks/sub0``, the
embedding (and an untied head) padded to ``weights.padded_vocab`` rows,
and norm weights stored as the offset ``w`` of a gain ``1 + w``.
"""

from __future__ import annotations

import dataclasses

from weights import padded_vocab

__all__ = ["FIELDS", "program_config", "shapes"]

# configuration file key → the program's ArchConfig field
FIELDS = {
    "num_hidden_layers": "n_layers", "hidden_size": "d_model",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "head_dim": "head_dim", "intermediate_size": "d_ff",
    "vocab_size": "vocab", "rope_theta": "rope_theta",
    "tie_word_embeddings": "tie_embeddings", "qk_norm": "qk_norm",
    "attention_bias": "attn_bias",
}


def program_config(cfg: dict):
    """The program's ArchConfig for a configuration file: its registry
    entry with every size set from the file."""
    from repro.configs.registry import get_config

    base = get_config(cfg["arch"])
    if base.family != "dense" or base.n_experts or base.attention != "full":
        raise ValueError(f"{cfg['arch']} is not a dense full-attention LM")
    if cfg["torch_dtype"] != base.dtype:
        raise ValueError(f"{cfg['name']} states {cfg['torch_dtype']}, the "
                         f"program runs {base.dtype}")
    return dataclasses.replace(
        base, **{f: cfg[k] for k, f in FIELDS.items()})


def shapes(cfg: dict) -> dict:
    """{path: (shape, fan_in or None for a norm)} of every leaf."""
    D, F = cfg["hidden_size"], cfg["intermediate_size"]
    H, Hkv, Dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    L, V = cfg["num_hidden_layers"], padded_vocab(cfg)
    out = {
        "embed": ((V, D), D),
        "final_norm": ((D,), None),
        "blocks/sub0/ln1": ((L, D), None),
        "blocks/sub0/ln2": ((L, D), None),
        "blocks/sub0/attn/wq": ((L, D, H * Dh), D),
        "blocks/sub0/attn/wk": ((L, D, Hkv * Dh), D),
        "blocks/sub0/attn/wv": ((L, D, Hkv * Dh), D),
        "blocks/sub0/attn/wo": ((L, H * Dh, D), H * Dh),
        "blocks/sub0/mlp/gate": ((L, D, F), D),
        "blocks/sub0/mlp/up": ((L, D, F), D),
        "blocks/sub0/mlp/down": ((L, F, D), F),
    }
    if cfg["qk_norm"]:
        out["blocks/sub0/attn/q_norm"] = ((L, Dh), None)
        out["blocks/sub0/attn/k_norm"] = ((L, Dh), None)
    if not cfg["tie_word_embeddings"]:
        out["lm_head"] = ((D, V), D)
    return out
