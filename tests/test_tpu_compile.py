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


@pytest.mark.parametrize("n_splits", [1, 2])
@pytest.mark.parametrize("arch", ["internlm2-1.8b", "qwen3-4b"])
def test_paged_decode_compiles_for_v5e(one_chip, arch, n_splits):
    """Serving widths: 8 slots, pool block 128, a 2048-token table."""
    cfg = get_config(arch)
    B, bs, NB = 8, 128, 16
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
