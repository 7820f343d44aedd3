"""Paged KV cache: a block pool + host-side allocator for the serve path.

Dense serving caches are sized ``n_slots × max_len`` and mostly hold
zeros — a 512-long window with 8 slots allocates 4096 token slots even
when typical occupancy is a few hundred.  The pool instead holds a
*budget* of fixed-size KV blocks (``paged_cache_shapes``); each running
request owns a list of physical blocks, and the decode step routes reads
and writes through a per-slot block table (``decode_step``'s
``block_table``).  Physical block 0 is reserved as scratch: idle slots
point every table entry (and their single-token write) at it.

The block size is not hard-coded — it is resolved through the kernel
autotuner's ``serve_kv`` tiling model, so it is roofline-ranked for the
configured device and memoised in the device-fingerprint-keyed
``TuningCache`` like any kernel block size.  That model prices each
candidate through the ``paged_decode`` kernel's own cost model (joint
resolution), and the kernel's ``block_kv`` candidates divide the pool
block size by construction — the two tuners cannot disagree on
blocking.

Prefill packing: prompts prefill through the ordinary dense path (at a
bucketed length, left-padded), then ``pack_prefill`` rolls the padding
off, chops the sequence into blocks, and scatters them into the pool in
one jitted donate-in-place call.  Traces are memoised per bucketed
length, so a long-lived engine compiles a handful of pack functions.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.kernels.autotune import tuned_config
from repro.kernels.serve_kv.tiling import default as _default_config
from repro.kernels.serve_kv.tiling import shape_key
from repro.models import transformer as T

__all__ = ["PagedKVCache", "resolve_block_size"]


def resolve_block_size(cfg: ArchConfig, *, n_slots: int, max_len: int,
                       tuner=None) -> int:
    """KV block size for this serving cell, via the ``serve_kv`` tiling
    model.  With an explicit ``tuner`` the lookup is authoritative (tests
    assert cache hits); otherwise it goes through the best-effort
    process-default path and falls back to the model's default config."""
    shape = shape_key(n_slots, max_len, cfg.n_kv_heads, cfg.head_dim_,
                      T.DTYPE, n_heads=cfg.n_heads)
    if tuner is not None:
        config = tuner.tune("serve_kv", shape)
    else:
        config = tuned_config("serve_kv", shape, _default_config(shape))
    return int(config["block_size"])


class PagedKVCache:
    def __init__(self, cfg: ArchConfig, *, n_slots: int, max_len: int,
                 block_size: int | None = None, pool_tokens: int | None = None,
                 tuner=None, faults=None):
        self.cfg = cfg
        self.n_slots = int(n_slots)
        self.max_len = int(max_len)
        if block_size is None:
            block_size = resolve_block_size(cfg, n_slots=n_slots,
                                            max_len=max_len, tuner=tuner)
        self.block_size = bs = max(1, int(block_size))
        if pool_tokens is None:
            # expected steady-state occupancy (the serve_kv cost model's
            # operating point) — half the dense footprint
            pool_tokens = max((self.n_slots * self.max_len) // 2,
                              self.max_len)
        # An explicit pool_tokens is honoured as given (no silent
        # inflation to max_len): requests whose lifetime footprint cannot
        # fit the pool are the *engine's* job to REFUSE with a
        # pool-capacity reason, not the pool's to paper over.
        pool_tokens = max(int(pool_tokens), bs)
        self.n_blocks = 1 + -(-pool_tokens // bs)      # +1: scratch block 0
        self.blocks_per_seq = -(-self.max_len // bs)   # table width ceiling
        self.pool = T.init_paged_cache(cfg, self.n_blocks, bs)
        self._free = list(range(self.n_blocks - 1, 0, -1))
        self._allocated: set[int] = set()
        self._pack_fns: dict[int, object] = {}
        self.faults = faults               # FaultPlan: injected alloc failures

    # ------------------------------------------------------------------
    # host-side block accounting

    @property
    def n_free_blocks(self) -> int:
        return len(self._free)

    @property
    def usable_blocks(self) -> int:
        """Total allocatable blocks (pool minus the reserved scratch) —
        the hard ceiling on any single request's lifetime footprint."""
        return self.n_blocks - 1

    def blocks_for(self, n_tokens: int) -> int:
        return -(-max(1, int(n_tokens)) // self.block_size)

    def alloc(self, n: int) -> list[int] | None:
        """n physical blocks, or None if the pool can't cover them now
        (caller defers, preempts, or refuses; nothing is allocated
        partially).  An injected ``"alloc"`` fault denies the request
        exactly as a genuinely empty free list would."""
        if self.faults is not None and self.faults.fire("alloc"):
            return None
        if n > len(self._free):
            return None
        taken = self._free[-n:]
        del self._free[-n:]
        self._allocated.update(taken)
        return taken

    def free(self, blocks: list[int]) -> None:
        """Return blocks to the pool.  Conservation is load-bearing under
        preemption/expiry (the same block list can reach multiple exit
        paths), so a double-free or foreign block is an error, not a
        silent free-list corruption."""
        assert 0 not in blocks, "physical block 0 is reserved scratch"
        bad = [b for b in blocks if b not in self._allocated]
        if bad:
            raise ValueError(f"free of unallocated block(s) {bad} "
                             f"(double free or foreign block)")
        self._allocated.difference_update(blocks)
        self._free.extend(blocks)

    @property
    def bytes(self) -> int:
        return sum(leaf.size * leaf.dtype.itemsize
                   for leaf in jax.tree.leaves(self.pool))

    @property
    def dense_bytes(self) -> int:
        """What the dense ``(n_slots, max_len)`` layout would have cost —
        the savings the paged layout exists to bank."""
        per_token = self.bytes / (self.n_blocks * self.block_size)
        return int(per_token * self.n_slots * self.max_len)

    def table_array(self, block_lists: list[list[int]], width: int) -> jnp.ndarray:
        """(n_slots, width) int32 block table; short rows and idle slots
        pad with scratch block 0."""
        table = np.zeros((self.n_slots, width), np.int32)
        for row, blocks in enumerate(block_lists):
            if blocks:
                table[row, : len(blocks)] = blocks[:width]
        return jnp.asarray(table)

    # ------------------------------------------------------------------
    # prefill → pool packing

    def _pack_fn(self, cache_len_dim: int):
        bs, fn = self.block_size, self._pack_fns.get(cache_len_dim)
        if fn is not None:
            return fn
        assert cache_len_dim % bs == 0
        nb = cache_len_dim // bs

        def pack(pool, dense, phys, pad):
            def one(pool_leaf, dense_leaf):
                # dense_leaf: (n_scan, 1, L, Hkv, Dh) — drop the B=1 axis,
                # roll the left-padding off so real token i lands at slot i
                d = jnp.roll(dense_leaf[:, 0], -pad, axis=1)
                # (n_scan, nb, bs, Hkv, Dh) → head-major pool blocks
                blocks = d.reshape(d.shape[0], nb, bs, *d.shape[2:]).transpose(
                    0, 1, 3, 2, 4)
                return pool_leaf.at[:, phys].set(blocks.astype(pool_leaf.dtype))

            return {
                sub: {"k_pool": one(leaves["k_pool"], dense[sub]["k"]),
                      "v_pool": one(leaves["v_pool"], dense[sub]["v"])}
                for sub, leaves in pool.items()
            }

        fn = jax.jit(pack, donate_argnums=(0,))
        self._pack_fns[cache_len_dim] = fn
        return fn

    def pack_prefill(self, dense_cache, blocks: list[int], *,
                     prompt_len: int, pad: int) -> None:
        """Scatter a B=1 dense prefill cache into the pool at ``blocks``.

        ``dense_cache`` comes from ``T.prefill(..., max_len=L)`` with L a
        multiple of the block size; the prompt sits left-padded by
        ``pad``.  Only the first ``ceil(prompt_len/block_size)`` blocks
        carry prompt KV; trailing dense blocks (stale pad KV after the
        roll) are routed to scratch block 0, and the request's remaining
        blocks fill incrementally during decode.
        """
        leaf = next(iter(dense_cache.values()))["k"]
        cache_len_dim = leaf.shape[2]
        nb_dense = cache_len_dim // self.block_size
        used = min(self.blocks_for(prompt_len), len(blocks), nb_dense)
        phys = np.zeros(nb_dense, np.int32)
        phys[:used] = blocks[:used]
        self.pool = self._pack_fn(cache_len_dim)(
            self.pool, dense_cache, jnp.asarray(phys), jnp.int32(pad))
