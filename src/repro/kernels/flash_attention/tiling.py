"""Tiling search space + static cost model for flash attention.

Executable form of the VMEM budget in ``kernel.py``'s docstring.  Grid =
(B, H, Sq/block_q), K/V for the head fully VMEM-resident — so HBM
traffic is block-independent (q/o once, K/V once per kv head via revisit
elision) and the blocks trade sequenced-step count and MXU fill against
the q/accumulator/score-tile working set:

* ``block_q`` — programs per (b, h); bigger blocks amortise grid steps
  and fill MXU rows, at the cost of ``block_q``-row f32 values.
* ``block_k`` — inner ``fori_loop`` trips; bigger chunks cut loop
  overhead and fill MXU columns, at 4·bq·bk f32 score-tile bytes.

The VMEM model is an upper bound fitted to what the v5e compiler
allocates (scoped VMEM reported for bq, bk ∈ 128…2048, Sk ∈ {512,
2048}, Dh 128): every block double-buffered by the pipeline, plus a
stack of six f32 ``(bq, max(Dh, 128))`` values (q, the accumulator
carry, and the lane-padded running stats and their broadcasts) and one
f32 ``(bq, bk)`` score tile.  A config over budget is refused by the
chip's compiler, so the tuner must never time it.
"""

from __future__ import annotations

from repro.kernels.autotune import (
    KernelCost,
    TilingModel,
    bytes_per_element,
    largest_dividing_block,
    register_tiling,
)

__all__ = ["shape_key", "candidates", "cost", "default"]

_BLOCK_SEEDS = (64, 128, 256, 512, 1024)


def shape_key(q_shape, k_shape, *, causal: bool, dtype) -> dict:
    B, H, Sq, Dh = (int(d) for d in q_shape)
    Hkv, Sk = int(k_shape[1]), int(k_shape[2])
    return {"B": B, "H": H, "Hkv": Hkv, "Sq": Sq, "Sk": Sk, "Dh": Dh,
            "causal": bool(causal), "dtype": str(dtype)}


def _snap(n: int) -> list[int]:
    return sorted({largest_dividing_block(n, b) for b in _BLOCK_SEEDS} | {n})


def candidates(shape: dict) -> list[dict]:
    return [{"block_q": bq, "block_k": bk}
            for bq in _snap(shape["Sq"]) for bk in _snap(shape["Sk"])]


def default(shape: dict) -> dict:
    # the kernel's hand-picked constants, after its own min(·, S) clamp
    return {"block_q": largest_dividing_block(shape["Sq"], 512),
            "block_k": largest_dividing_block(shape["Sk"], 512)}


def cost(shape: dict, config: dict) -> KernelCost:
    B, H, Hkv = shape["B"], shape["H"], shape["Hkv"]
    Sq, Sk, Dh = shape["Sq"], shape["Sk"], shape["Dh"]
    bq = largest_dividing_block(Sq, config.get("block_q"))
    bk = largest_dividing_block(Sk, config.get("block_k"))
    bpe = bytes_per_element(shape["dtype"])

    frac = 0.5 if shape["causal"] else 1.0  # masked-out score work skipped
    flops = 4.0 * B * H * Sq * Sk * Dh * frac
    # q/o once per program = once total; K/V once per kv head (consecutive
    # q-heads sharing a kv head revisit the same block — no re-fetch)
    hbm = bpe * (2.0 * B * H * Sq * Dh + 2.0 * B * Hkv * Sk * Dh)
    vmem = (2.0 * bpe * (2 * bq * Dh + 2 * Sk * Dh)  # q, o, K/V, x2 buffers
            + 4.0 * 6 * bq * max(Dh, 128)            # f32 row values
            + 4.0 * bq * bk)                         # f32 score/prob tile
    n_programs = B * H * (Sq // bq)
    return KernelCost(
        op="flash_attention", op_class="matmul", origin="kernel",
        flops=flops, hbm_bytes=hbm, vmem_bytes=vmem,
        n_steps=n_programs * (1 + Sk // bk),
        mxu_min_dim=min(bq, bk, Dh),
    )


def _runner(shape: dict, config: dict):
    import jax.numpy as jnp
    import numpy as np

    from .ops import flash_attention

    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal(
        (shape["B"], shape["H"], shape["Sq"], shape["Dh"])), shape["dtype"])
    kv = (shape["B"], shape["Hkv"], shape["Sk"], shape["Dh"])
    k = jnp.asarray(rng.standard_normal(kv), shape["dtype"])
    v = jnp.asarray(rng.standard_normal(kv), shape["dtype"])
    bq, bk = config["block_q"], config["block_k"]
    return lambda: flash_attention(q, k, v, causal=shape["causal"],
                                   block_q=bq, block_k=bk)


register_tiling(TilingModel(
    name="flash_attention", candidates=candidates, cost=cost, default=default,
    runner=_runner,
), overwrite=True)
