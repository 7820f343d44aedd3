"""Model weights made on the device from ``--seed``, in one jitted call,
in the type they are served in (bfloat16).

The tree's layout is the configuration's model family's
(``families/<family>.py``, ``shapes``): every leaf by its path, with its
shape and fan-in.  Whatever the family, the embedding (and an untied
head) is padded to a multiple of 256 rows with zero rows, and a norm
weight is stored as the offset ``w`` of a gain ``1 + w``.  The plain
reference reads the same tree by these keys; the harness checks the
layout against the program's own parameter shapes before it uses it.

Scales: every matrix N(0, 1/fan_in), the embedding N(0, 1/hidden_size)
(so tied logits have unit scale), norm offsets N(0, 0.1).
"""

from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp

import cells

__all__ = ["padded_vocab", "make", "make_fn", "seed_key"]

NORM_STD = 0.1


def padded_vocab(cfg: dict) -> int:
    return -(-cfg["vocab_size"] // 256) * 256


def _nest(flat: dict) -> dict:
    tree: dict = {}
    for path, leaf in flat.items():
        node = tree
        *parents, name = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[name] = leaf
    return tree


def _make(layout: tuple, V: int, key):
    flat = {}
    for k, (path, (shape, fan_in)) in zip(jax.random.split(key, len(layout)),
                                          layout):
        std = NORM_STD if fan_in is None else fan_in ** -0.5
        w = jax.random.normal(k, shape, jnp.float32) * std
        if path == "embed":
            w = w.at[V:].set(0.0)            # padded vocabulary rows
        elif path == "lm_head":
            w = w.at[:, V:].set(0.0)
        flat[path] = w.astype(jnp.bfloat16)
    return _nest(flat)


@lru_cache(maxsize=None)
def _jitted(layout: tuple, V: int):
    return jax.jit(lambda key: _make(layout, V, key))


def make_fn(cfg: dict, root: str = cells.HERE):
    """The jitted maker of ``cfg``'s weights, in the layout of its model
    family under ``root``: ``fn(seed_key(seed))``."""
    layout = cells.family(cfg, root).arch.shapes(cfg)
    return _jitted(tuple(sorted(layout.items())), cfg["vocab_size"])


def seed_key(seed: int):
    """A JAX key from a seed of any size (more than 32 bits hold)."""
    return jax.random.fold_in(jax.random.key(seed % 2**32), seed // 2**32)


def make(cfg: dict, seed: int, root: str = cells.HERE) -> dict:
    return make_fn(cfg, root)(seed_key(seed))
