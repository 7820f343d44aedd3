"""Pure-jnp oracle for paged single-query decode attention.

Semantically identical to the serve path's gather fallback
(``models/layers.py`` paged branch: ``pool[block_table]`` → dense
``blocked_attention``), restated as one f32 masked softmax so the kernel
has an XLA-only reference for correctness tests and the CPU dispatch
path.  Key positions run over the *logical* gathered view
``NB·bs``; position ``k`` is attended iff ``k <= cache_len[b]`` — the
freshly scattered token at ``cache_len`` included, everything beyond
(junk blocks, scratch padding) masked out.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

__all__ = ["paged_decode_ref"]

NEG_INF = -1e30


def paged_decode_ref(q, k_pool, v_pool, block_table, cache_len, layer=0, *,
                     scale: float | None = None):
    """q: (B, H, Dh); k/v_pool: (L, P, Hkv, bs, Dh), or (P, Hkv, bs, Dh)
    as the one-layer case; block_table: (B, NB) int32; cache_len: (B,)
    int32; layer: int32 scalar index into the pool's leading axis →
    (B, H, Dh).

    ``cache_len[b]`` is row b's highest valid logical position (the
    decode step's freshly written token), so ``cache_len[b] + 1`` keys
    are attended.  GQA: consecutive groups of ``H // Hkv`` query heads
    share one KV head.
    """
    if k_pool.ndim == 4:
        k_pool, v_pool = k_pool[None], v_pool[None]
    B, H, Dh = q.shape
    Hkv, bs = k_pool.shape[2], k_pool.shape[3]
    NB = block_table.shape[1]
    rep = H // Hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(Dh)

    def logical(pool):  # (B, NB, Hkv, bs, Dh) → (B, NB·bs, Hkv, Dh)
        return (pool[layer, block_table].transpose(0, 1, 3, 2, 4)
                .reshape(B, NB * bs, Hkv, Dh).astype(jnp.float32))

    k, v = logical(k_pool), logical(v_pool)
    qr = (q.astype(jnp.float32) * scale).reshape(B, Hkv, rep, Dh)

    s = jnp.einsum("bgrd,bkgd->bgrk", qr, k)               # (B, Hkv, rep, L)
    pos = jnp.arange(NB * bs)
    valid = pos[None, :] <= cache_len[:, None]             # (B, L)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bgrk,bkgd->bgrd", p, v)
    return o.reshape(B, H, Dh).astype(q.dtype)
