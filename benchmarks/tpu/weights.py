"""Model weights made on the device from ``--seed``, in one jitted call,
in the type they are served in (bfloat16).

The tree has the layout the program takes: layers stacked on a leading
axis under ``blocks/sub0``, the embedding (and an untied head) padded to
a multiple of 256 rows with zero rows, and norm weights stored as the
offset ``w`` of a gain ``1 + w``.  The plain reference reads the same
tree by these keys; the harness checks the layout against the program's
own parameter shapes before it uses it.

Scales: every matrix N(0, 1/fan_in), the embedding N(0, 1/hidden_size)
(so tied logits have unit scale), norm offsets N(0, 0.1).
"""

from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp

__all__ = ["padded_vocab", "shapes", "make", "make_fn", "seed_key"]

NORM_STD = 0.1


def padded_vocab(cfg: dict) -> int:
    return -(-cfg["vocab_size"] // 256) * 256


def shapes(cfg: dict) -> dict:
    """{path: (shape, fan_in or None for a norm, std)} of every leaf."""
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


def _nest(flat: dict) -> dict:
    tree: dict = {}
    for path, leaf in flat.items():
        node = tree
        *parents, name = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[name] = leaf
    return tree


def _make(cfg: dict, key):
    V = cfg["vocab_size"]
    flat = {}
    items = sorted(shapes(cfg).items())
    for k, (path, (shape, fan_in)) in zip(jax.random.split(key, len(items)),
                                          items):
        std = NORM_STD if fan_in is None else fan_in ** -0.5
        w = jax.random.normal(k, shape, jnp.float32) * std
        if path == "embed":
            w = w.at[V:].set(0.0)            # padded vocabulary rows
        elif path == "lm_head":
            w = w.at[:, V:].set(0.0)
        flat[path] = w.astype(jnp.bfloat16)
    return _nest(flat)


@lru_cache(maxsize=None)
def _jitted(frozen: tuple):
    cfg = dict(frozen)
    return jax.jit(lambda key: _make(cfg, key))


def make_fn(cfg: dict):
    """The jitted maker for ``cfg``: ``fn(seed_key(seed))``."""
    keys = ("hidden_size", "intermediate_size", "num_attention_heads",
            "num_key_value_heads", "head_dim", "num_hidden_layers",
            "vocab_size", "qk_norm", "tie_word_embeddings")
    return _jitted(tuple((k, cfg[k]) for k in keys))


def seed_key(seed: int):
    """A JAX key from a seed of any size (more than 32 bits hold)."""
    return jax.random.fold_in(jax.random.key(seed % 2**32), seed // 2**32)


def make(cfg: dict, seed: int) -> dict:
    return make_fn(cfg)(seed_key(seed))
