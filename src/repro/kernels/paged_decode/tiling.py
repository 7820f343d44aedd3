"""Tiling search space + static cost model for paged decode attention.

Executable form of the traffic story in ``kernel.py``'s docstring.  Grid
= (B, Hkv/G, n_splits, NB/n_splits), G = :func:`head_group`; costs are
evaluated at the pool's steady state — rows half full
(``ctx = NB·bs/2``) — because that is what a continuously batched serve
loop actually runs at, not the worst-case full table the gather fallback
always pays for:

* ``block_kv`` — inner ``fori_loop`` chunk inside one pool block; wider
  chunks cut loop trips and fill MXU columns at 4·rep·bkv extra f32
  score bytes.  Candidates divide the pool block size by construction,
  which is the structural half of the serve_kv ⇄ paged_decode joint
  resolution (serve_kv's cost model is the other half — it prices each
  candidate pool block through :func:`cost` at this model's default).
* ``n_splits`` — flash-decode KV-axis splits, at the price of f32
  partial (acc, m, l) traffic and a combine pass.  They shorten no
  sequenced chain on a chip with one TensorCore, as the v5e has: there
  every split count above 1 ran slower than 1 at the serving cells'
  shapes, so the model prices splits by their traffic alone.

:func:`gather_cost` models the XLA gather fallback at the same shape —
three full passes over the ``NB·bs`` logical view regardless of
``cache_len`` — giving kernel_bench an honest modelled baseline row.
"""

from __future__ import annotations

from repro.kernels.autotune import (
    VMEM_BYTES,
    KernelCost,
    TilingModel,
    bytes_per_element,
    largest_dividing_block,
    register_tiling,
)

__all__ = ["shape_key", "candidates", "cost", "default", "gather_cost",
           "head_group"]

_BLOCK_SEEDS = (16, 32, 64, 128, 256, 512)
_SPLIT_SEEDS = (1, 2, 4, 8)

# Share of the scoped VMEM that one grid step's double-buffered K and V
# blocks may take; q, the f32 partials and Mosaic's own scratch get the
# rest.
_KV_VMEM_SHARE = 0.5


def head_group(Hkv, bs, Dh, dtype) -> int:
    """KV heads one grid step reads (G): the largest divisor of ``Hkv``
    whose double-buffered ``(G, bs, Dh)`` K and V blocks fit
    ``_KV_VMEM_SHARE`` of the scoped VMEM; 1 if not even one head fits."""
    per_head = 2 * 2 * bs * Dh * bytes_per_element(dtype)
    budget = _KV_VMEM_SHARE * VMEM_BYTES
    return max((g for g in range(1, Hkv + 1)
                if Hkv % g == 0 and g * per_head <= budget), default=1)


def shape_key(B, H, Hkv, Dh, NB, bs, dtype) -> dict:
    # G is implied by the rest; it is in the key so that tiles tuned for
    # the one-head-per-step kernel, which had no G, are never served to
    # this one.
    return {"B": int(B), "H": int(H), "Hkv": int(Hkv), "Dh": int(Dh),
            "NB": int(NB), "bs": int(bs), "dtype": str(dtype),
            "G": head_group(Hkv, bs, Dh, dtype)}


def candidates(shape: dict) -> list[dict]:
    bs, NB = shape["bs"], shape["NB"]
    bkvs = sorted({largest_dividing_block(bs, b) for b in _BLOCK_SEEDS} | {bs})
    splits = sorted({min(s, NB) for s in _SPLIT_SEEDS})
    return [{"block_kv": bkv, "n_splits": ns} for bkv in bkvs for ns in splits]


def default(shape: dict) -> dict:
    # the kernel's own argument defaults: 128-wide chunks, no split
    return {"block_kv": largest_dividing_block(shape["bs"], 128),
            "n_splits": 1}


def _steady_live_blocks(shape: dict) -> int:
    # rows half full: ctx = NB·bs/2 valid positions ⇒ live = ctx//bs + 1
    return (shape["NB"] * shape["bs"] // 2) // shape["bs"] + 1


def cost(shape: dict, config: dict) -> KernelCost:
    B, H, Hkv, Dh = shape["B"], shape["H"], shape["Hkv"], shape["Dh"]
    NB, bs = shape["NB"], shape["bs"]
    rep = H // Hkv
    bkv = largest_dividing_block(bs, config.get("block_kv"))
    ns = max(1, min(int(config.get("n_splits", 1)), NB))
    bpe = bytes_per_element(shape["dtype"])
    live = _steady_live_blocks(shape)

    # qk^T + pv over live keys only (early exit) for every query head
    flops = 4.0 * B * H * live * bs * Dh
    # touched KV (live (G, bs, Dh) head-group blocks of the head-major
    # pool, once per group via revisit elision) + q in / combined o out
    # + f32 split partials (acc, and m/l padded to a 128-lane row)
    # written by the kernel and re-read by the combine + the int32
    # table/cache_len
    hbm = (bpe * 2.0 * B * Hkv * live * bs * Dh
           + bpe * 2.0 * B * H * Dh
           + 4.0 * 2.0 * B * H * ns * (Dh + 2 * 128)
           + 4.0 * (B * NB + B))
    G = head_group(Hkv, bs, Dh, shape["dtype"])
    vmem = (bpe * G * (rep * Dh + 2 * 2 * bs * Dh)  # q + 2-buffered k/v blocks
            + 4.0 * G * rep * Dh * 2            # f32 acc scratch + o partial
            + 4.0 * G * rep * bkv               # f32 score/prob chunk
            + 4.0 * 2 * G * rep * 128 * 2)      # m/l lane-padded scratch + out
    # Sequenced chain per (b, head group): live blocks (dead ones are
    # clamped revisits — free) × loop trips.
    npb = -(-NB // ns)
    live_steps = min(live, npb * ns)
    n_steps = B * (Hkv // G) * live_steps * (1 + bs // bkv)
    return KernelCost(
        op="paged_decode", op_class="matmul", origin="kernel",
        flops=flops, hbm_bytes=hbm, vmem_bytes=vmem,
        n_steps=int(max(n_steps, 1)),
        mxu_min_dim=min(rep, bkv, Dh),
    )


def gather_cost(shape: dict) -> KernelCost:
    """The XLA fallback at the same shape: materialise the full
    ``(B, NB·bs)`` logical K and V views (pool read + gathered write),
    then dense attention re-reads them — cache_len-oblivious."""
    B, H, Hkv, Dh = shape["B"], shape["H"], shape["Hkv"], shape["Dh"]
    L = shape["NB"] * shape["bs"]
    bpe = bytes_per_element(shape["dtype"])
    flops = 4.0 * B * H * L * Dh                     # full width, no exit
    hbm = (bpe * 2.0 * B * Hkv * L * Dh * 3.0        # gather r+w, attn read
           + bpe * 2.0 * B * H * Dh)
    return KernelCost(
        op="paged_decode_gather", op_class="matmul", origin="fallback",
        flops=flops, hbm_bytes=hbm, vmem_bytes=0.0,
        n_steps=1, mxu_min_dim=min(H // Hkv, Dh),
    )


def _runner(shape: dict, config: dict):
    import jax.numpy as jnp
    import numpy as np

    from .ops import paged_decode_attention

    rng = np.random.default_rng(0)
    B, Hkv, Dh = shape["B"], shape["Hkv"], shape["Dh"]
    NB, bs = shape["NB"], shape["bs"]
    P = B * NB + 1
    q = jnp.asarray(rng.standard_normal((B, shape["H"], Dh)), shape["dtype"])
    kp = jnp.asarray(rng.standard_normal((P, Hkv, bs, Dh)), shape["dtype"])
    vp = jnp.asarray(rng.standard_normal((P, Hkv, bs, Dh)), shape["dtype"])
    bt = jnp.asarray(1 + np.arange(B * NB).reshape(B, NB), jnp.int32)
    cl = jnp.asarray(np.full(B, NB * bs // 2, np.int32))  # steady state
    bkv, ns = config["block_kv"], config["n_splits"]
    return lambda: paged_decode_attention(
        q, kp, vp, bt, cl, block_kv=bkv, n_splits=ns)


register_tiling(TilingModel(
    name="paged_decode", candidates=candidates, cost=cost, default=default,
    runner=_runner,
), overwrite=True)
