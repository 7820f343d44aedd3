"""Tiling search space + static cost model for the paged serve KV cache.

The serving engine stores K/V in fixed-size blocks (``PagedKVCache``);
``block_size`` is the one knob.  Since the decode-specialised
``paged_decode`` kernel landed, the consumer of the pool layout is that
kernel, so this model resolves the pool block size **jointly** with it:
each candidate ``bs`` is priced by running the paged_decode cost model
at its own default config for a pool of ``bs``-sized blocks spanning the
context window.  Two things follow structurally:

* the kernel's ``block_kv`` candidates divide the pool block size by
  construction (``largest_dividing_block`` over the same seed list), so
  the two tuners cannot pick incompatible blockings;
* the fragmentation/step-overhead trade-off the old hand-rolled model
  priced is inherited — the kernel streams each *live* block in full
  (``ceil(ctx/bs)`` blocks ≈ ctx + fragmentation tokens) and pays
  sequenced steps per live block, so big blocks still cost dead-token
  bandwidth and small blocks still cost loop trips and MXU underfill.

On top of the kernel launch the pool itself pays the step's scatter
write and the block-table re-read, added here.  Costs are modelled at
the expected steady-state occupancy ``max_len/2`` (uniform admission
over the context window), matching the serve bench's mixed-length
traces.
"""

from __future__ import annotations

from repro.kernels.autotune import (
    KernelCost,
    TilingModel,
    bytes_per_element,
    register_tiling,
)
from repro.kernels.paged_decode import tiling as pd_tiling

__all__ = ["shape_key", "candidates", "cost", "default"]

_BLOCK_SEEDS = (16, 32, 64, 128, 256, 512)


def shape_key(n_slots: int, max_len: int, n_kv_heads: int, head_dim: int,
              dtype, n_heads: int | None = None) -> dict:
    return {"B": int(n_slots), "L": int(max_len), "Hkv": int(n_kv_heads),
            "H": int(n_heads if n_heads is not None else n_kv_heads),
            "Dh": int(head_dim), "dtype": str(dtype)}


def candidates(shape: dict) -> list[dict]:
    cands = [{"block_size": b} for b in _BLOCK_SEEDS if b <= shape["L"]]
    return cands or [{"block_size": shape["L"]}]


def default(shape: dict) -> dict:
    # dense-cache parity: one block spans a quarter of the window, the
    # hand-picked constant the engine used before the pool existed
    return {"block_size": max(16, min(shape["L"] // 4, 256))}


def cost(shape: dict, config: dict) -> KernelCost:
    B, L = shape["B"], shape["L"]
    H = shape.get("H", shape["Hkv"])
    Hkv, Dh = shape["Hkv"], shape["Dh"]
    bs = max(1, min(int(config.get("block_size", L)), L))
    bpe = bytes_per_element(shape["dtype"])
    NB = max(1, -(-L // bs))           # full-window table width

    # Joint resolution: price this pool layout through the decode
    # kernel's own cost model at the kernel's default config for bs.
    pd_shape = pd_tiling.shape_key(B, H, Hkv, Dh, NB, bs, shape["dtype"])
    pd = pd_tiling.cost(pd_shape, pd_tiling.default(pd_shape))

    # + the pool's own per-step work: scatter the step's K/V row in,
    # re-read the block tables
    hbm = pd.hbm_bytes + bpe * 2.0 * B * Hkv * Dh + 4.0 * B * NB
    return KernelCost(
        op="serve_kv", op_class="matmul", origin="kernel",
        flops=pd.flops, hbm_bytes=hbm, vmem_bytes=pd.vmem_bytes,
        # The pool block keeps the sequenced chain per KV head that the
        # kernel had before a grid step read a block for G heads: a
        # chain per head group ties 128 with 256 at 10 slots over 2048
        # tokens and picks 128 at 32, which moves the pool, the tables
        # and the chunk programs with it (docs/kernels.md "Flash-decode").
        n_steps=pd.n_steps * pd_shape["G"],
        mxu_min_dim=pd.mxu_min_dim,
    )


register_tiling(TilingModel(
    name="serve_kv", candidates=candidates, cost=cost, default=default,
    runner=None,
), overwrite=True)
