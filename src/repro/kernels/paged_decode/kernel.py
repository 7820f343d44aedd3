"""Paged single-query decode attention — Pallas TPU kernel.

TPU mapping: grid = (B, Hkv, n_splits, NB/n_splits), block axis innermost.
The block table and per-row cache lengths ride in scalar-prefetch SMEM
(``PrefetchScalarGridSpec``) so the K/V ``BlockSpec`` index maps can chase
``block_table[b, i]`` — each grid step DMAs ONE physical pool block for
one KV head straight from HBM; the gathered ``(B, NB·bs)`` logical view
the XLA fallback materialises never exists.

The pool is head-major, ``(L, P, Hkv, bs, Dh)``: the serve path's pool
stacks every layer's blocks, and the layer index rides in SMEM as a
third scalar-prefetch operand, so the kernel reads one layer straight
out of the stacked buffer and no per-layer slice is ever materialised.
One head's block is a ``(bs, Dh)`` tile whose last two dims are whole
array dims.  Mosaic refuses the token-major ``(P, bs, Hkv, Dh)`` layout:
a one-head block there has a second-minor block dim of 1 against Hkv,
and indexing the head inside an all-heads block is an unaligned sublane
slice.  The running (m, l) stats are written out padded to a full
128-lane row for the same reason.

Early exit is block-granular: row ``b`` owns ``cache_len[b]//bs + 1``
live blocks, and the index map *clamps* dead steps to the last live
block — consecutive dead steps fetch the same block, which Pallas's
revisit elision turns into zero HBM traffic — while ``pl.when`` skips
their compute entirely.  Inside a live block the score loop runs in
``block_kv``-wide chunks (``block_kv`` divides the pool block size; the
serve_kv tiling resolves the two jointly) with per-position
``pos <= cache_len`` masking, so the freshly written token at
``cache_len`` is attended and nothing past it is.

Split-KV: with ``n_splits > 1`` each (b, kv head) is cut into
``n_splits`` independent partial reductions (flash-decode style — a
single query exposes only ``H/Hkv`` MXU rows, so long contexts need the
KV axis for parallelism).  The kernel emits per-split unnormalised
accumulators plus running (m, l) stats; :func:`combine_splits` merges
them in one tiny jnp pass.

VMEM per program (bf16, bs=64, Dh=128, rep=4): q/o 2 KiB + k/v blocks
32 KiB + f32 acc/stats ~3 KiB ≈ 37 KiB « 16 MiB.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.autotune import largest_dividing_block

__all__ = ["paged_decode_kernel", "combine_splits"]

NEG_INF = -1e30
_STAT_LANES = 128  # f32 stats padded to one full lane row


def _decode_body(bt_ref, cl_ref, layer_ref, q_ref, k_ref, v_ref,
                 o_ref, m_ref, l_ref,
                 acc_scr, m_scr, l_scr, *,
                 scale, bs, block_kv, npb):
    """One (batch row, kv head, split, block-step) program."""
    b = pl.program_id(0)
    s = pl.program_id(2)
    j = pl.program_id(3)
    i = s * npb + j                                 # global block index
    rep, dh = q_ref.shape[-2], q_ref.shape[-1]

    @pl.when(j == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)

    cl = cl_ref[b]
    n_live = cl // bs + 1                           # row's live block count

    @pl.when(i < n_live)
    def _live():
        q = q_ref[0, 0].astype(jnp.float32) * scale             # (rep, dh)

        def chunk(c, _):
            start = pl.multiple_of(c * block_kv, block_kv)
            k = k_ref[0, pl.dslice(start, block_kv), :].astype(
                jnp.float32)                                    # (bkv, dh)
            v = v_ref[0, pl.dslice(start, block_kv), :].astype(
                jnp.float32)
            sc = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)             # (rep, bkv)
            pos = (i * bs + c * block_kv
                   + jax.lax.broadcasted_iota(jnp.int32, (rep, block_kv), 1))
            sc = jnp.where(pos <= cl, sc, NEG_INF)
            m_prev = m_scr[:, 0]
            l_prev = l_scr[:, 0]
            m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1))
            p = jnp.exp(sc - m_new[:, None])
            alpha = jnp.exp(m_prev - m_new)
            l_new = l_prev * alpha + p.sum(axis=-1)
            acc_scr[...] = (acc_scr[...] * alpha[:, None]
                            + jax.lax.dot_general(
                                p, v, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32))
            m_scr[...] = jnp.broadcast_to(m_new[:, None], m_scr.shape)
            l_scr[...] = jnp.broadcast_to(l_new[:, None], l_scr.shape)
            return 0

        jax.lax.fori_loop(0, bs // block_kv, chunk, 0)

    # Unnormalised partials flush when the split's output block rotates.
    o_ref[0, 0, 0] = acc_scr[...]
    m_ref[0, 0, 0] = m_scr[...]
    l_ref[0, 0, 0] = l_scr[...]


def combine_splits(acc, m, l, out_dtype):
    """Merge per-split partials: acc is (B, Hkv, n_splits, rep, Dh) and
    m/l are (B, Hkv, n_splits, rep), all f32 → (B, H, Dh).  Dead splits
    carry (acc=0, m=NEG_INF, l=0) and vanish under the global-max
    renormalisation (NEG_INF is finite, so the exp underflows to exactly
    0 instead of producing NaN)."""
    B, Hkv, n_splits, rep, Dh = acc.shape
    m_g = jnp.max(m, axis=2, keepdims=True)                 # (B, Hkv, 1, rep)
    w = jnp.exp(m - m_g)                                    # (B, Hkv, s, rep)
    l_g = jnp.sum(w * l, axis=2)                            # (B, Hkv, rep)
    o = jnp.sum(w[..., None] * acc, axis=2)                 # (B, Hkv, rep, Dh)
    l_g = jnp.where(l_g == 0.0, 1.0, l_g)  # fully-masked rows (idle slots)
    return (o / l_g[..., None]).reshape(B, Hkv * rep, Dh).astype(out_dtype)


def paged_decode_kernel(q, k_pool, v_pool, block_table, cache_len, layer=0,
                        *,
                        scale: float | None = None,
                        block_kv: int | None = None,
                        n_splits: int = 1,
                        interpret: bool = False):
    """q: (B, H, Dh); k/v_pool: (L, P, Hkv, bs, Dh), or (P, Hkv, bs, Dh)
    as the one-layer case; block_table: (B, NB); cache_len: (B,); layer:
    int32 scalar index into the pool's leading axis → (B, H, Dh).
    Attends positions ``<= cache_len[b]`` of layer ``layer``.
    """
    if k_pool.ndim == 4:
        k_pool, v_pool = k_pool[None], v_pool[None]
    B, H, Dh = q.shape
    Hkv, bs = k_pool.shape[2], k_pool.shape[3]
    NB = block_table.shape[1]
    assert H % Hkv == 0, (H, Hkv)
    rep = H // Hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(Dh)
    block_kv = largest_dividing_block(bs, block_kv or 128)
    n_splits = max(1, min(int(n_splits), NB))
    npb = -(-NB // n_splits)                       # blocks per split

    qr = q.reshape(B, Hkv, rep, Dh)

    def kv_index(b, h, s, j, bt_ref, cl_ref, layer_ref):
        i = s * npb + j
        n_live = cl_ref[b] // bs + 1
        live = jnp.minimum(i, n_live - 1)          # clamp dead steps →
        return (layer_ref[0], bt_ref[b, live], h, 0, 0)  # revisit elision

    def split_index(b, h, s, j, bt_ref, cl_ref, layer_ref):
        return (b, h, s, 0, 0)

    kv_block = (pl.squeezed, pl.squeezed, 1, bs, Dh)

    grid = (B, Hkv, n_splits, npb)
    kernel = functools.partial(_decode_body, scale=scale, bs=bs,
                               block_kv=block_kv, npb=npb)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,            # block_table, cache_len, layer
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, rep, Dh),
                         lambda b, h, s, j, bt, cl, ly: (b, h, 0, 0)),
            pl.BlockSpec(kv_block, kv_index),
            pl.BlockSpec(kv_block, kv_index),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, rep, Dh), split_index),
            pl.BlockSpec((1, 1, 1, rep, _STAT_LANES), split_index),
            pl.BlockSpec((1, 1, 1, rep, _STAT_LANES), split_index),
        ],
        scratch_shapes=[
            pltpu.VMEM((rep, Dh), jnp.float32),          # acc
            pltpu.VMEM((rep, _STAT_LANES), jnp.float32),  # running max
            pltpu.VMEM((rep, _STAT_LANES), jnp.float32),  # running sum
        ],
    )
    acc, m, l = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, Hkv, n_splits, rep, Dh), jnp.float32),
            jax.ShapeDtypeStruct((B, Hkv, n_splits, rep, _STAT_LANES),
                                 jnp.float32),
            jax.ShapeDtypeStruct((B, Hkv, n_splits, rep, _STAT_LANES),
                                 jnp.float32),
        ],
        interpret=interpret,
        name="paged_decode",
    )(block_table, cache_len, jnp.reshape(layer, (1,)).astype(jnp.int32),
      qr, k_pool, v_pool)
    return combine_splits(acc, m[..., 0], l[..., 0], q.dtype)
