"""Jitted public wrapper for paged decode attention.

``impl`` selects the execution path (mirrors the ``REPRO_PAGED_DECODE``
env knob the serve layer reads):

* ``None`` / ``"auto"`` — Pallas kernel on TPU, pure-jnp ref elsewhere
  (the ref is XLA-only, so CPU containers stay fast and exact).
* ``"kernel"`` — always the Pallas kernel (interpret mode off-TPU).
* ``"interpret"`` — force interpret mode even on TPU (debugging).
* ``"ref"`` — always the jnp reference.

``block_kv=None`` / ``n_splits=None`` consult the process autotuner
(roofline-ranked, device-keyed cache — ``repro.kernels.autotune``) for
this launch shape; explicit values always win.  Resolution happens
outside the jit so tuned values participate in the static-arg cache key.
"""

from __future__ import annotations

from functools import partial

import jax

from repro.kernels.autotune import tuned_config

from . import tiling
from .kernel import paged_decode_kernel
from .ref import paged_decode_ref

__all__ = ["paged_decode_attention"]


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@partial(jax.jit, static_argnames=("scale", "block_kv", "n_splits", "impl"))
def _paged_decode_jit(q, k_pool, v_pool, block_table, cache_len, layer, *,
                      scale, block_kv, n_splits, impl):
    use_kernel = impl in ("kernel", "interpret") or (
        impl in (None, "auto") and _on_tpu())
    if use_kernel:
        return paged_decode_kernel(
            q, k_pool, v_pool, block_table, cache_len, layer, scale=scale,
            block_kv=block_kv, n_splits=n_splits,
            interpret=impl == "interpret" or not _on_tpu(),
        )
    return paged_decode_ref(q, k_pool, v_pool, block_table, cache_len,
                            layer, scale=scale)


def paged_decode_attention(q, k_pool, v_pool, block_table, cache_len,
                           layer=0, *, scale=None, block_kv=None,
                           n_splits=None, impl=None):
    """q: (B, H, Dh); k/v_pool: (L, P, Hkv, bs, Dh), or (P, Hkv, bs, Dh)
    as the one-layer case; block_table: (B, NB) int32; cache_len: (B,)
    int32; layer: int32 scalar → (B, H, Dh), attending logical positions
    ``<= cache_len[b]`` of each row's paged KV history in layer
    ``layer`` of the pool."""
    if block_kv is None or n_splits is None:
        B, H, Dh = q.shape
        shape = tiling.shape_key(B, H, k_pool.shape[-3], Dh,
                                 block_table.shape[1], k_pool.shape[-2],
                                 q.dtype)
        tuned = tuned_config("paged_decode", shape, tiling.default(shape))
        block_kv = block_kv if block_kv is not None else tuned.get(
            "block_kv", 128)
        n_splits = n_splits if n_splits is not None else tuned.get(
            "n_splits", 1)
    return _paged_decode_jit(q, k_pool, v_pool, block_table, cache_len,
                             layer, scale=scale, block_kv=int(block_kv),
                             n_splits=int(n_splits), impl=impl)
