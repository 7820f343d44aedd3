"""Transformer / MoE / SSD building blocks (pure JAX, GSPMD-friendly).

Design notes
------------
* All matmul-bearing ops are written as einsums over named dims so the GSPMD
  partitioner propagates shardings cleanly (heads / experts / ffn on "model",
  batch on "pod"+"data").
* Attention is *blocked*: a ``lax.scan`` over query blocks with full-row
  softmax per block.  This bounds the score tensor to
  (B, H, block_q, S_kv) — the XLA fallback of the Pallas flash-attention
  kernel in ``repro.kernels.flash_attention`` (used on real TPU).
* MoE uses capacity-based dispatch (GShard-style): sort tokens by expert,
  scatter into an (E, C, D) buffer (sharded E→model, C→data; the scatter is
  the all-to-all), batched-einsum the experts, gather back.  Compute overhead
  over the ideal is exactly the capacity factor.
* The SSD (Mamba-2) mixer is the chunked state-space-duality algorithm:
  quadratic attention-like compute inside chunks, linear state passing across
  chunks; single-step recurrence for decode.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "rms_norm",
    "rope",
    "blocked_attention",
    "attention_block",
    "mlp_block",
    "moe_block",
    "ssd_block",
    "moe_capacity",
]

_NEG_INF = -1e30


_HINT_MESH = None  # set by the launcher (dryrun/train) for activation hints
SP_HINT = True     # sequence-parallel residual stream (helps dense, hurts MoE
                   # collectives — see EXPERIMENTS.md §Perf iteration A2)


def set_hint_mesh(mesh, *, sp: bool = True) -> None:
    """Install the mesh used for activation sharding hints inside model code
    (launcher-only; smoke tests leave it unset and hints become no-ops)."""
    global _HINT_MESH, SP_HINT
    _HINT_MESH = mesh
    SP_HINT = sp


def _maybe_constrain(x, *spec_dims):
    """with_sharding_constraint against the launcher-installed hint mesh, or
    a no-op when none is set / axes are missing.

    spec dims may be None, an axis name, or the special "dp" marker resolved
    to the data-parallel axes present on the mesh (("pod","data")/("data",)).
    Divisibility is checked per dim; non-divisible dims fall back to None.
    """
    mesh = _HINT_MESH
    if mesh is None:
        return x
    names = tuple(mesh.axis_names)
    sizes = dict(zip(names, mesh.devices.shape))
    dims = []
    for i, d in enumerate(spec_dims):
        if d == "dp":
            dp = tuple(a for a in ("pod", "data") if a in names)
            n = 1
            for a in dp:
                n *= sizes[a]
            dims.append(dp if dp and x.shape[i] % n == 0 else None)
        elif d is not None and d in names and x.shape[i] % sizes[d] == 0:
            dims.append(d)
        else:
            dims.append(None)
    from jax.sharding import NamedSharding, PartitionSpec as _P

    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, _P(*dims)))


def _tuned_attention_block_q(q, k, causal: bool) -> int:
    """Query-block size for :func:`blocked_attention`, from the autotuner.

    Shares the flash-attention tiling model (and its device-keyed cache)
    with the Pallas kernel — the XLA fallback blocks over the same q axis,
    so the same roofline/working-set trade-off picks its block.  Runs at
    trace time (shapes are static); falls back to the historical 512.
    """
    from repro.kernels.autotune import tuned_config
    from repro.kernels.flash_attention import tiling

    B, Sq, Hkv, rep, Dh = q.shape  # (B, S, G, R, Dh) pre-blocking layout
    shape = tiling.shape_key((B, Hkv * rep, Sq, Dh),
                             (B, Hkv, k.shape[1], Dh),
                             causal=causal, dtype=q.dtype)
    return int(tuned_config("flash_attention", shape,
                            tiling.default(shape)).get("block_q", 512))


def _tuned_moe_dispatch(B: int, S: int, cfg, dtype) -> tuple[int, float]:
    """(groups, capacity_factor) for :func:`moe_block`, from the autotuner
    (the ``moe_dispatch`` tiling model; trace-time only).

    Falls back to the historical constants — ``gcd(B, moe_groups or 32)``
    groups at the configured capacity factor.  The tuned factor is clamped
    to never fall below the configured one: capacity controls token drops
    (model quality), so the tuner may only add slack, never remove it.

    Reproducibility contract: unlike the attention/SSM block sizes, these
    knobs change the routing arithmetic (group segmentation, slot counts),
    so the SAME checkpoint can produce numerically different logits under
    a different tuning cache or device.  Bit-reproducibility across
    machines therefore requires either ``REPRO_AUTOTUNE=0`` (config
    constants everywhere) or shipping the tuning-cache file with the
    checkpoint — the cache is content-keyed and device-salted exactly so
    it CAN be shipped.
    """
    from repro.kernels.autotune import tuned_config
    from repro.kernels.moe_dispatch import tiling

    g_default = math.gcd(B, getattr(cfg, "moe_groups", 32) or 32)
    shape = tiling.shape_key(B, S, cfg.d_model, cfg.n_experts,
                             cfg.experts_per_token, cfg.moe_d_ff_,
                             cfg.capacity_factor, dtype)
    tuned = tuned_config("moe_dispatch", shape,
                         {"groups": g_default,
                          "capacity_factor": cfg.capacity_factor})
    groups = math.gcd(B, int(tuned.get("groups", g_default)) or g_default)
    factor = max(float(tuned.get("capacity_factor", cfg.capacity_factor)),
                 cfg.capacity_factor)
    return groups, factor


def _tuned_ssm_chunk(xh, n_state: int, default_chunk: int) -> int:
    """Chunk length for :func:`ssd_scan`, from the autotuner (the
    ``ssm_scan`` tiling model; trace-time only, falls back to the config
    constant)."""
    from repro.kernels.autotune import tuned_config
    from repro.kernels.ssm_scan import tiling

    shape = tiling.shape_key(xh.shape, n_state, dtype=xh.dtype)
    return int(tuned_config("ssm_scan", shape,
                            {"chunk": default_chunk}).get("chunk",
                                                          default_chunk))


def rms_norm(x, w, eps: float = 1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * (1.0 + w.astype(jnp.float32))).astype(dt)


def rope(x, positions, theta: float):
    """Rotary embedding.  x: (..., S, H, Dh), positions: (..., S)."""
    dh = x.shape[-1]
    half = dh // 2
    freqs = jnp.exp(-jnp.arange(half, dtype=jnp.float32) * (math.log(theta) / half))
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., S, half)
    cos = jnp.cos(ang)[..., None, :]  # (..., S, 1, half)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------


def _mask_bias(q_pos, k_pos, kind: str, chunk: int, prefix: int, kv_len=None):
    """Additive mask bias (0 or -inf).

    q_pos: (Sq,) or (B, Sq); k_pos: (Sk,) or (B, Sk) — leading batch dims
    broadcast, so ragged (per-row) positions yield a (B, Sq, Sk) bias.
    Negative key positions mark left-padding slots and are always masked
    out.  ``kv_len`` may be a scalar or a per-row (B,) vector.
    """
    q = q_pos[..., :, None]
    k = k_pos[..., None, :]
    if kind == "causal":
        ok = k <= q
    elif kind == "chunked":  # causal within a local chunk window
        ok = (k <= q) & (q - k < chunk) & (q // chunk == k // chunk)
    elif kind == "prefix":   # bidirectional over first `prefix`, causal after
        ok = (k <= q) | (k < prefix)
    elif kind == "full":
        ok = jnp.ones_like(k <= q)
    else:
        raise ValueError(kind)
    if kind != "full":
        ok = ok & (k >= 0)  # left-padding slots carry negative positions
    if kv_len is not None:  # decode: only attend to valid cache entries
        kv = jnp.asarray(kv_len)
        if kv.ndim:
            kv = kv[..., None, None]
        ok = ok & (k <= kv)
    return jnp.where(ok, 0.0, _NEG_INF).astype(jnp.float32)


def blocked_attention(
    q, k, v, *,
    q_positions, k_positions,
    mask_kind: str = "causal",
    chunk: int = 8192,
    prefix: int = 0,
    kv_len=None,
    block_q: int | None = None,
    scale: float | None = None,
):
    """GQA attention, scanned over query blocks (memory-bounded).

    q: (B, Sq, H, Dh);  k, v: (B, Sk, Hkv, Dh).  Returns (B, Sq, H, Dh).
    ``block_q=None`` → autotuned (shared flash-attention tiling cache).
    """
    B, Sq, H, Dh = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    rep = H // Hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(Dh)
    qr = (q * scale).reshape(B, Sq, Hkv, rep, Dh)
    if block_q is None:
        block_q = _tuned_attention_block_q(qr, k, mask_kind != "full")

    def expand(bias):
        # (B, Sq, Sk) per-row bias → broadcast over (G, R); 2-D passes through
        return bias[:, None, None] if bias.ndim == 3 else bias

    if Sq <= block_q:
        bias = _mask_bias(q_positions, k_positions, mask_kind, chunk, prefix, kv_len)
        s = jnp.einsum("bqgrd,bkgd->bgrqk", qr, k, preferred_element_type=jnp.float32)
        s = s + expand(bias)  # (B, G, R, Sq, Sk)
        p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        o = jnp.einsum("bgrqk,bkgd->bqgrd", p, v)
        return o.reshape(B, Sq, H, Dh)

    nb = -(-Sq // block_q)
    pad = nb * block_q - Sq
    if pad:
        qr = jnp.pad(qr, ((0, 0), (0, pad), (0, 0), (0, 0), (0, 0)))
        # padded query rows are sliced off below; their positions are junk
        q_positions = jnp.pad(q_positions, [(0, 0)] * (q_positions.ndim - 1)
                              + [(0, pad)])
    qb = qr.reshape(B, nb, block_q, Hkv, rep, Dh).transpose(1, 0, 2, 3, 4, 5)
    if q_positions.ndim == 2:  # ragged: per-row positions ride along per block
        pb = q_positions.reshape(B, nb, block_q).transpose(1, 0, 2)
    else:
        pb = q_positions.reshape(nb, block_q)

    def body(_, blk):
        qblk, qpos = blk
        bias = _mask_bias(qpos, k_positions, mask_kind, chunk, prefix, kv_len)
        s = jnp.einsum("bqgrd,bkgd->bgrqk", qblk, k, preferred_element_type=jnp.float32)
        s = s + expand(bias)
        p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        o = jnp.einsum("bgrqk,bkgd->bqgrd", p, v)
        return None, o

    _, ob = jax.lax.scan(body, None, (qb, pb))  # (nb, B, block_q, Hkv, rep, Dh)
    o = ob.transpose(1, 0, 2, 3, 4, 5).reshape(B, nb * block_q, H, Dh)
    return o[:, :Sq]


def _paged_decode_fast_path(q, k_pool, v_pool, block_table, cache_len,
                            layer=0):
    """Dispatch the S == 1 paged decode step to the specialised kernel,
    or return ``None`` to fall through to the gather + dense path.

    ``REPRO_PAGED_DECODE`` (read per call, so tests can flip it):
      ``auto``      kernel on TPU, gather elsewhere (default — keeps the
                    CPU path bit-identical to the pre-kernel behaviour)
      ``kernel``    always the Pallas kernel (interpret mode off-TPU)
      ``interpret`` force interpret mode (debugging/tests)
      ``gather``    always the gather + dense fallback

    Any other value raises.
    """
    import os

    mode = os.environ.get("REPRO_PAGED_DECODE", "auto").lower()
    impls = {"auto": "kernel", "kernel": "kernel", "interpret": "interpret",
             "gather": None}
    if mode not in impls:
        raise ValueError(f"REPRO_PAGED_DECODE={mode!r}; expected one of "
                         f"{sorted(impls)}")
    if mode == "gather" or (mode == "auto"
                            and jax.default_backend() != "tpu"):
        return None
    from repro.kernels.paged_decode import paged_decode_attention

    o = paged_decode_attention(q[:, 0], k_pool, v_pool, block_table,
                               cache_len, layer, impl=impls[mode])
    return o[:, None]  # (B, 1, H, Dh)


def attention_block(
    x, p, cfg, *,
    positions,
    mask_kind: str,
    cache=None,          # (k_cache, v_cache): (B, Smax, Hkv, Dh) or None, or
    #                      the stacked paged pool {"k_pool","v_pool"}:
    #                      (n_scan, P, Hkv, bs, Dh)
    cache_len=None,      # int32 scalar OR per-row (B,) vector: cache fill
    kv_source=None,      # cross-attention memory (B, Sm, D)
    pos_offset=None,     # (B,) left-padding per row (ragged prompts)
    block_table=None,    # (B, NB) logical→physical block map (paged cache)
    layer=None,          # int32 scalar: this layer's index in the paged pool
):
    """Full attention sublayer: projections + RoPE + blocked attention.

    Returns (out, new_cache).  ``p`` holds wq/wk/wv/wo (+q_norm/k_norm/biases).

    Ragged support: ``positions`` may be per-row (B, S) with negative values
    marking left-padding (masked out of the keys, clamped for RoPE), and
    ``cache_len`` may be a per-row vector — decode slots at different fill
    levels write their new KV at per-row offsets (continuous batching).
    With a paged cache, K/V live in a fixed-size block pool indexed through
    ``block_table``; the pool stacks every layer, and ``layer`` picks this
    one.  The step scatters the new tokens' KV into their blocks of that
    layer, in place, and attends either via the decode-specialised paged
    kernel (S == 1, ``REPRO_PAGED_DECODE``), which reads the layer out of
    the stacked pool, or over the gathered logical view (fallback, and
    the S > 1 chunked-prefill path).
    """
    B, S, D = x.shape
    H, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_

    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].reshape(D, H, Dh))
    src = kv_source if kv_source is not None else x
    k = jnp.einsum("bsd,dhk->bshk", src, p["wk"].reshape(D, Hkv, Dh))
    v = jnp.einsum("bsd,dhk->bshk", src, p["wv"].reshape(D, Hkv, Dh))
    if cfg.attn_bias:
        q = q + p["bq"].reshape(H, Dh)
        k = k + p["bk"].reshape(Hkv, Dh)
        v = v + p["bv"].reshape(Hkv, Dh)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"])
        k = rms_norm(k, p["k_norm"])

    if kv_source is None:  # self-attention: RoPE on q and k
        rope_pos = jnp.maximum(positions, 0)  # pad slots: masked, not rotated
        q = rope(q, rope_pos, cfg.rope_theta)
        k = rope(k, rope_pos, cfg.rope_theta)
        if cache is None:
            k_pos = positions
            new_cache = None
            kv_len = None
            k_full, v_full = k, v
        elif "k_pool" in cache:
            # Paged path: scatter the S new tokens' KV into their blocks.
            # Slot i's token t lands at logical position cache_len[i] + t =
            # physical (layer, block_table[i, pos//bs], :, pos % bs) of the
            # stacked head-major pool (n_scan, P, Hkv, bs, Dh).  The pool is
            # the layer scan's carry, so this one scatter updates the
            # donated buffer in place; no per-layer slice is taken.  S == 1
            # is the decode step; S > 1 is a chunked-prefill chunk riding
            # the same path (right-padded rows route their junk positions
            # to block indices past the row's live table entries — the
            # caller sizes the table so those columns exist and point at
            # scratch).
            kp, vp = cache["k_pool"], cache["v_pool"]
            bs_blk = kp.shape[3]
            cl = (cache_len if jnp.ndim(cache_len)
                  else jnp.full((B,), cache_len, jnp.int32))
            tok_pos = cl[:, None] + jnp.arange(S)            # (B, S)
            blk = tok_pos // bs_blk
            off = tok_pos % bs_blk
            phys = block_table[jnp.arange(B)[:, None], blk]  # (B, S)
            # One (Dh,) row per (token, head): a window of the minor dim
            # alone keeps the pool's default layout through the scatter,
            # where an (Hkv, Dh) window makes XLA re-lay the whole pool.
            at = (layer, phys[..., None], jnp.arange(Hkv), off[..., None])
            kp = kp.at[at].set(k.astype(kp.dtype))           # (B,S,Hkv,Dh)
            vp = vp.at[at].set(v.astype(vp.dtype))
            new_cache = {"k_pool": kp, "v_pool": vp}
            kv_len = cl + S - 1                              # (B,)
            if S == 1 and mask_kind == "causal":
                # Decode fast path: single-query paged attention reads K/V
                # straight from the pool (no gathered logical view), with
                # block-granular early exit at each row's last live block.
                # REPRO_PAGED_DECODE picks the impl; the gather fallback
                # below stays the CPU default and exactness oracle.
                o = _paged_decode_fast_path(q, kp, vp, block_table, kv_len,
                                            layer)
                if o is not None:
                    out = jnp.einsum("bshk,hkd->bsd", o,
                                     p["wo"].reshape(H, Dh, D))
                    return out, new_cache
            # (B, NB, Hkv, bs, Dh) → logical view (B, NB·bs, Hkv, Dh)
            k_full = kp[layer, block_table].transpose(0, 1, 3, 2, 4).reshape(
                B, -1, Hkv, Dh)
            v_full = vp[layer, block_table].transpose(0, 1, 3, 2, 4).reshape(
                B, -1, Hkv, Dh)
            k_pos = jnp.arange(k_full.shape[1])
        else:
            kc, vc = cache["k"], cache["v"]
            k_pos = jnp.arange(kc.shape[1])
            if jnp.ndim(cache_len):
                # per-row fill (continuous batching): each slot writes its
                # single new token at its own offset
                assert S == 1, "per-row cache_len is a single-token decode path"
                kc = kc.at[jnp.arange(B), cache_len].set(k[:, 0].astype(kc.dtype))
                vc = vc.at[jnp.arange(B), cache_len].set(v[:, 0].astype(vc.dtype))
            else:
                kc = jax.lax.dynamic_update_slice_in_dim(kc, k.astype(kc.dtype), cache_len, axis=1)
                vc = jax.lax.dynamic_update_slice_in_dim(vc, v.astype(vc.dtype), cache_len, axis=1)
            new_cache = {"k": kc, "v": vc}
            kv_len = cache_len + S - 1
            if pos_offset is not None:
                # left-padded rows: cache slot j holds logical position
                # j - pad, pad slots (< 0) masked out by _mask_bias
                k_pos = k_pos[None, :] - pos_offset[:, None]
                kv_len = kv_len - pos_offset
            k_full, v_full = kc, vc
    else:  # cross-attention: no RoPE, full mask over memory
        k_pos = jnp.arange(src.shape[1])
        new_cache = None
        kv_len = None
        k_full, v_full = k, v
        mask_kind = "full"

    o = blocked_attention(
        q, k_full, v_full,
        q_positions=positions, k_positions=k_pos,
        mask_kind=mask_kind, chunk=cfg.chunk_size, prefix=cfg.n_prefix,
        kv_len=kv_len,
    )
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"].reshape(H, Dh, D))
    return out, new_cache


# ---------------------------------------------------------------------------
# FFN: dense SwiGLU and capacity-dispatch MoE
# ---------------------------------------------------------------------------


def mlp_block(x, p):
    h = jax.nn.silu(jnp.einsum("bsd,df->bsf", x, p["gate"]))
    h = h * jnp.einsum("bsd,df->bsf", x, p["up"])
    return jnp.einsum("bsf,fd->bsd", h, p["down"])


def moe_capacity(tokens: int, n_experts: int, k: int, factor: float) -> int:
    c = int(math.ceil(tokens * k / n_experts * factor))
    return max(8, -(-c // 8) * 8)  # multiple of 8, floor 8


def _moe_dispatch_group(xt, gates, ids, p, E, K, C):
    """Capacity dispatch for one token group.  xt: (T,D); gates/ids: (T,K).

    Gather-only formulation (perf iteration A1, EXPERIMENTS.md §Perf): the
    (E, C, D) buffer is built by *gathering* tokens through a per-expert
    slot-index matrix instead of scattering — GSPMD lowers cross-shard
    scatters into full-buffer all-reduces (measured 48×4.3 GB/step on
    qwen3-moe), while gathers stay as slices/all-gathers of the shard."""
    T, D = xt.shape
    flat_e = ids.reshape(-1)                                  # (T·K,)
    sort_idx = jnp.argsort(flat_e)                            # stable
    sorted_e = flat_e[sort_idx]
    seg_starts = jnp.searchsorted(sorted_e, jnp.arange(E))    # (E,)
    seg_ends = jnp.append(seg_starts[1:], T * K)
    # slot (e, c) holds sorted position seg_starts[e]+c while inside segment
    pos = seg_starts[:, None] + jnp.arange(C)[None, :]        # (E, C)
    valid = pos < seg_ends[:, None]
    tok_for_slot = sort_idx[jnp.clip(pos, 0, T * K - 1)] // K
    buf = jnp.where(valid[..., None], xt[tok_for_slot], 0)    # gather (E,C,D)
    pos_in_e = jnp.arange(T * K) - seg_starts[sorted_e]
    dest_c = jnp.where(pos_in_e < C, pos_in_e, C)             # C ⇒ dropped
    return buf, (sorted_e, dest_c, sort_idx)


def _moe_combine_group(out_buf, route, gates, K):
    sorted_e, dest_c, sort_idx = route
    T = gates.shape[0]
    slot_out = out_buf.at[sorted_e, dest_c].get(
        mode="fill", fill_value=0)                            # gather (T·K, D)
    inv = jnp.argsort(sort_idx)
    unsorted = slot_out[inv]                                  # gather un-sort
    return (unsorted.reshape(T, K, -1)
            * gates[..., None].astype(out_buf.dtype)).sum(axis=1)


def moe_block(x, p, cfg):
    """Top-k capacity MoE: GShard-style dispatch, SwiGLU experts.

    Tokens are split into ``G`` groups along the (data-sharded) batch axis and
    dispatch/sort/scatter run *per group* (vmapped) — each group lives on one
    data shard, so routing stays device-local under GSPMD and only the
    (G, E, C, ·) expert buffer crosses the mesh (the all-to-all), exactly the
    GShard communication pattern.  Expert FFNs run as batched einsums over the
    expert-sharded (model-axis) weights.
    """
    B, S, D = x.shape
    E, K = cfg.n_experts, cfg.experts_per_token
    G, factor = _tuned_moe_dispatch(B, S, cfg, x.dtype)
    Tg = (B // G) * S
    C = moe_capacity(Tg, E, K, factor)
    xg = x.reshape(G, Tg, D)

    logits = jnp.einsum("gtd,de->gte", xg.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gates, ids = jax.lax.top_k(probs, K)                      # (G, Tg, K)
    gates = gates / jnp.clip(gates.sum(-1, keepdims=True), 1e-9)

    buf, route = jax.vmap(
        lambda xt, g, i: _moe_dispatch_group(xt, g, i, p, E, K, C)
    )(xg, gates, ids)                                          # buf: (G, E, C, D)
    # Expert-parallel layout: groups on DP, experts on the model axis.  The
    # reshard from (G@dp, E) to (G@dp, E@model) IS the GShard all-to-all.
    buf = _maybe_constrain(buf, "dp", "model", None, None)

    h = jax.nn.silu(jnp.einsum("gecd,edf->gecf", buf, p["gate"]))
    h = h * jnp.einsum("gecd,edf->gecf", buf, p["up"])
    h = _maybe_constrain(h, "dp", "model", None, None)
    out_buf = jnp.einsum("gecf,efd->gecd", h, p["down"])       # (G, E, C, D)
    out_buf = _maybe_constrain(out_buf, "dp", "model", None, None)

    out = jax.vmap(
        lambda ob, rt, g: _moe_combine_group(ob, rt, g, K)
    )(out_buf, route, gates)                                   # (G, Tg, D)
    out = _maybe_constrain(out, "dp", None, None)
    aux = _load_balance_loss(probs.reshape(-1, E), ids.reshape(-1, K), E)
    return out.reshape(B, S, D), aux


def _load_balance_loss(probs, ids, E):
    """Switch-style auxiliary load-balancing loss (returned for the trainer)."""
    T = probs.shape[0]
    frac_tokens = jnp.zeros(E).at[ids.reshape(-1)].add(1.0) / ids.size
    frac_probs = probs.mean(axis=0)
    return E * jnp.sum(frac_tokens * frac_probs)


# ---------------------------------------------------------------------------
# Mamba-2 SSD mixer
# ---------------------------------------------------------------------------


def _segsum(a):
    """Stable segment-sum: out[..., i, j] = sum a[..., j+1..i], -inf for j>i."""
    L = a.shape[-1]
    cs = jnp.cumsum(a, axis=-1)
    out = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((L, L), bool), k=0)
    return jnp.where(mask, out, -jnp.inf)


def ssd_scan(xh, a, Bm, Cm, chunk: int, initial_state=None):
    """Chunked SSD (Mamba-2 alg. 1 / "minimal ssd").

    xh: (B, S, H, P) inputs (already dt-scaled)
    a:  (B, S, H)    log-decay per step (dt · A, negative)
    Bm, Cm: (B, S, G, N) state in/out projections (G groups, broadcast to H)
    Returns y: (B, S, H, P), final_state: (B, H, P, N).
    """
    B, S0, H, P = xh.shape
    G, N = Bm.shape[2], Bm.shape[3]
    pad = (-S0) % chunk
    if pad:  # zero-pad: a=0 ⇒ decay 1, x=0 ⇒ no state contribution
        xh = jnp.pad(xh, ((0, 0), (0, pad), (0, 0), (0, 0)))
        a = jnp.pad(a, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0), (0, 0)))
    S = S0 + pad
    nc = S // chunk
    rep = H // G

    def c(t):  # (B, S, ...) -> (B, nc, chunk, ...)
        return t.reshape(t.shape[0], nc, chunk, *t.shape[2:])

    xc, ac, Bc, Cc = c(xh), c(a), c(Bm), c(Cm)
    ac = jnp.moveaxis(ac, -1, 2)            # (B, nc, H, chunk)
    cum_a = jnp.cumsum(ac, axis=-1)         # (B, nc, H, chunk)

    # 1. intra-chunk (diagonal blocks)
    L = jnp.exp(_segsum(ac.astype(jnp.float32)))                  # (B,nc,H,l,l)
    Cr = jnp.repeat(Cc, rep, axis=3) if G != H else Cc            # broadcast groups
    Br = jnp.repeat(Bc, rep, axis=3) if G != H else Bc
    s = jnp.einsum("bclhn,bcshn->bchls", Cr, Br, preferred_element_type=jnp.float32)
    y_diag = jnp.einsum("bchls,bchls,bcshp->bclhp", s, L, xc.astype(jnp.float32))

    # 2. per-chunk final states
    decay_states = jnp.exp(cum_a[..., -1:] - cum_a)               # (B,nc,H,l)
    states = jnp.einsum(
        "bclhn,bchl,bclhp->bchpn", Br, decay_states.astype(jnp.float32),
        xc.astype(jnp.float32),
    )                                                              # (B,nc,H,P,N)

    # 3. inter-chunk recurrence (scan over chunks)
    chunk_decay = jnp.exp(cum_a[..., -1])                          # (B,nc,H)
    s0 = (
        initial_state.astype(jnp.float32)
        if initial_state is not None
        else jnp.zeros((B, H, P, N), jnp.float32)
    )

    def step(carry, inp):
        st, dec = inp  # (B,H,P,N), (B,H)
        new = carry * dec[..., None, None] + st
        return new, carry  # emit the *previous* state (state entering chunk)

    st_seq = jnp.moveaxis(states, 1, 0)         # (nc, B, H, P, N)
    dec_seq = jnp.moveaxis(chunk_decay, 1, 0)   # (nc, B, H)
    final_state, prev_states = jax.lax.scan(step, s0, (st_seq, dec_seq))
    prev_states = jnp.moveaxis(prev_states, 0, 1)  # (B, nc, H, P, N)

    # 4. state → output contribution
    state_decay = jnp.exp(cum_a)                                   # (B,nc,H,l)
    y_off = jnp.einsum(
        "bclhn,bchpn,bchl->bclhp", Cr, prev_states, state_decay.astype(jnp.float32)
    )
    y = (y_diag + y_off).reshape(B, S, H, P)[:, :S0]
    return y.astype(xh.dtype), final_state


def ssd_block(x, p, cfg, *, cache=None, valid=None):
    """Mamba-2 block: in_proj → causal conv1d → SSD → gated norm → out_proj.

    cache (decode): dict(conv=(B, W-1, d_conv_ch), state=(B, H, P, N)).
    ``valid`` (B, S) bool marks real tokens in a left-padded ragged batch:
    invalid steps contribute zero conv taps, zero state input and unit
    decay (a = 0), so the recurrence matches an unpadded run exactly.
    Returns (out, new_cache).
    """
    B, S, D = x.shape
    d_inner = cfg.ssm_expand * D
    P = cfg.ssm_head_dim
    H = d_inner // P
    N = cfg.ssm_state
    G = 1  # single B/C group
    d_conv_ch = d_inner + 2 * G * N
    W = cfg.ssm_conv_width

    z = jnp.einsum("bsd,de->bse", x, p["w_z"])
    xbc = jnp.concatenate(
        [jnp.einsum("bsd,de->bse", x, p["w_x"]),
         jnp.einsum("bsd,de->bse", x, p["w_B"]),
         jnp.einsum("bsd,de->bse", x, p["w_C"])], axis=-1)
    dt = jnp.einsum("bsd,de->bse", x, p["w_dt"])
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
    if valid is not None:  # pad steps feed zero taps into the causal conv
        xbc = jnp.where(valid[..., None], xbc, 0)

    # causal depthwise conv over (x, B, C) channels
    if cache is None:
        pad = jnp.zeros((B, W - 1, d_conv_ch), xbc.dtype)
        conv_in = jnp.concatenate([pad, xbc], axis=1)
        new_conv = None
    else:
        conv_in = jnp.concatenate([cache["conv"].astype(xbc.dtype), xbc], axis=1)
        new_conv = conv_in[:, -(W - 1):]
    stack = [conv_in[:, i : i + S] for i in range(W)]
    xbc = sum(s * p["conv_w"][i] for i, s in enumerate(stack)) + p["conv_b"]
    xbc = jax.nn.silu(xbc)

    xs, Bm, Cm = jnp.split(xbc, [d_inner, d_inner + G * N], axis=-1)
    xs = xs.reshape(B, S, H, P)
    Bm = Bm.reshape(B, S, G, N)
    Cm = Cm.reshape(B, S, G, N)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))              # (H,), negative
    a = dt * A                                                 # (B,S,H) log-decay
    xh = xs * dt[..., None].astype(xs.dtype)
    if valid is not None:  # pad steps: no state input, unit decay
        xh = jnp.where(valid[..., None, None], xh, 0)
        a = jnp.where(valid[..., None], a, 0.0)

    ssm_chunk = (_tuned_ssm_chunk(xh, N, cfg.ssm_chunk)
                 if S > 1 else cfg.ssm_chunk)
    if cache is None:
        y, final_state = ssd_scan(xh, a, Bm, Cm, ssm_chunk)
        new_cache = None
    elif S > 1:  # prefill with cache: chunked scan seeded by cached state
        y, final_state = ssd_scan(
            xh, a, Bm, Cm, ssm_chunk, initial_state=cache["state"]
        )
        new_cache = {"conv": new_conv, "state": final_state}
    else:
        # single-step recurrence (S == 1)
        st = cache["state"].astype(jnp.float32)                # (B,H,P,N)
        dec = jnp.exp(a[:, 0])                                 # (B,H)
        Br = jnp.repeat(Bm[:, 0], H // G, axis=1) if G != H else Bm[:, 0]
        Cr = jnp.repeat(Cm[:, 0], H // G, axis=1) if G != H else Cm[:, 0]
        upd = jnp.einsum("bhp,bhn->bhpn", xh[:, 0].astype(jnp.float32), Br.astype(jnp.float32))
        st = st * dec[..., None, None] + upd
        y = jnp.einsum("bhpn,bhn->bhp", st, Cr.astype(jnp.float32))[:, None]
        new_cache = {"conv": new_conv, "state": st}

    y = y + xs.astype(y.dtype) * p["D"].astype(y.dtype)[None, None, :, None]
    y = y.reshape(B, S, d_inner).astype(x.dtype)
    y = rms_norm(y * jax.nn.silu(z), p["norm"])                # gated RMSNorm
    return jnp.einsum("bse,ed->bsd", y, p["out_proj"]), new_cache
