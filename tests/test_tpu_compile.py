"""Compile the main path's Pallas kernels for a described TPU v5e chip.

No chip is needed: the TPU compiler compiles for a topology that is
described and not attached, and refuses what the chip would refuse
(block shapes that break the (8, 128) tiling, unaligned slices, too
much VMEM).  Interpret mode on the CPU catches none of that.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and each test worker
imports every test file.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.registry import get_config
from repro.kernels.paged_decode.kernel import paged_decode_kernel


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("B,bs,NB", [(8, 128, 16), (10, 256, 8),
                                      (32, 256, 8)])
@pytest.mark.parametrize("n_splits", [1, 2])
@pytest.mark.parametrize("arch", ["internlm2-1.8b", "qwen3-4b"])
def test_paged_decode_compiles_for_v5e(one_chip, arch, n_splits, B, bs, NB):
    """Serving widths over a 2048-token table: 8 slots at pool block
    128, and the qwen3-4b cells' 10 (decode_long) and 32 (chat_poisson)
    slots at pool block 256, where one grid step reads a block for all
    KV heads."""
    cfg = get_config(arch)
    H, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    P = B * NB + 1

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = spec((P, Hkv, bs, Dh), jnp.bfloat16)
    fn = jax.jit(lambda q, k, v, bt, cl: paged_decode_kernel(
        q, k, v, bt, cl, n_splits=n_splits))
    compiled = fn.lower(spec((B, H, Dh), jnp.bfloat16), pool, pool,
                        spec((B, NB), jnp.int32),
                        spec((B,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("S", [1, 256])
def test_serve_step_updates_pool_in_place_for_v5e(one_chip, monkeypatch, S):
    """The donated decode (S == 1, the paged_decode kernel) and chunk
    (S > 1, the gather path) steps at qwen3-4b's widths, two layers:
    the compiled program keeps the pool in place.  No copy, dynamic
    slice or dynamic update slice of the pool's shape, stacked or one
    layer's, and less scratch than one pool."""
    import dataclasses
    import re

    from repro.kernels.paged_decode import ops
    from repro.models import transformer as T

    # code that asks for the backend sees the CPU here: steer it to the
    # kernel as the chip would take it
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    monkeypatch.setenv("REPRO_PAGED_DECODE", "kernel")
    cfg = dataclasses.replace(get_config("qwen3-4b"), n_layers=2)
    B, n_blocks, bs, NB = 4 if S == 1 else 1, 17, 256, 4

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree.map(lambda s: spec(s.shape, s.dtype), T.param_specs(cfg))
    pool = jax.tree.map(lambda s: spec(s, T.DTYPE),
                        T.paged_cache_shapes(cfg, n_blocks, bs),
                        is_leaf=lambda x: isinstance(x, tuple))
    batch = {"tokens": spec((B, S), jnp.int32),
             "cache_len": spec((B,), jnp.int32),
             "block_table": spec((B, NB), jnp.int32)}
    compiled = jax.jit(lambda p, c, b: T.decode_step(p, c, b, cfg),
                       donate_argnums=(1,)).lower(params, pool, batch).compile()
    text = compiled.as_text()
    assert ("tpu_custom_call" in text) == (S == 1)
    leaf = T.paged_cache_shapes(cfg, n_blocks, bs)["sub0"]["k_pool"]
    dims = "|".join(re.escape(",".join(map(str, s))) for s in (leaf, leaf[1:]))
    # a pool-shaped instruction, or fusion, that copies or slices
    moves = [m for m in re.findall(
        rf"%(\S+) = bf16\[(?:{dims})\]\S* ([\w-]+)\(", text)
        if re.search(r"copy|dynamic", " ".join(m))]
    assert not moves, moves
    pool_bytes = 2 * 2 * leaf[0] * leaf[1] * leaf[2] * leaf[3] * leaf[4]
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes // 2
