"""Kernel autotuner: cache round-trip + corruption tolerance, device-
fingerprint salting, VMEM pruning, tuned-vs-default parity in interpret
mode, and the divisibility fallbacks that replaced the hard asserts."""

import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from repro.engine.devices import DeviceSpec, get_device
from repro.kernels.autotune import (
    VMEM_BUDGET_FRACTION,
    VMEM_BYTES,
    KernelCost,
    KernelTuner,
    TuningCache,
    get_tiling,
    largest_dividing_block,
    list_tilings,
    roofline_seconds,
    set_tuner,
    vmem_ok,
)
from repro.kernels.conv_mm import tiling as conv_tiling
from repro.kernels.conv_mm.kernel import conv_mm_kernel
from repro.kernels.conv_mm.ref import conv_ref
from repro.kernels.flash_attention import tiling as flash_tiling
from repro.kernels.flash_attention.kernel import flash_attention_kernel
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.paged_decode import tiling as pd_tiling
from repro.kernels.ssm_scan import tiling as ssm_tiling
from repro.kernels.ssm_scan.ops import ssd
from repro.kernels.ssm_scan.ref import ssd_ref

TPU = "tpu_v5e"

CONV_SHAPE = conv_tiling.shape_key(
    (2, 16, 16, 32), (3, 3, 32, 64), stride=1, padding=1, dtype="float32")
FLASH_SHAPE = flash_tiling.shape_key(
    (1, 4, 512, 64), (1, 2, 512, 64), causal=True, dtype="bfloat16")
SSM_SHAPE = ssm_tiling.shape_key((1, 256, 4, 32), 32, dtype="float32")
PD_SHAPE = pd_tiling.shape_key(4, 8, 2, 64, 8, 32, dtype="bfloat16")


@pytest.fixture
def tuner(tmp_path):
    return KernelTuner(device=get_device(TPU),
                       cache=str(tmp_path / "tune.json"), measure=False)


@pytest.fixture(autouse=True)
def _isolated_default_tuner(tmp_path):
    """Keep implicit ops/model lookups off the user-level cache file."""
    set_tuner(KernelTuner(device=get_device(TPU),
                          cache=str(tmp_path / "default_tune.json"),
                          measure=False))
    yield
    set_tuner(None)


# ---------------------------------------------------------------------------
# helpers / registry
# ---------------------------------------------------------------------------


def test_largest_dividing_block():
    assert largest_dividing_block(96, 256) == 96
    assert largest_dividing_block(96, 64) == 48
    assert largest_dividing_block(384, 512) == 384
    assert largest_dividing_block(384, 128) == 128
    assert largest_dividing_block(7, 4) == 1
    assert largest_dividing_block(128, None) == 128
    with pytest.raises(ValueError):
        largest_dividing_block(0, 8)


def test_all_kernels_register_tilings():
    assert list_tilings() == ["conv_mm", "flash_attention", "moe_dispatch",
                              "paged_decode", "serve_kv", "ssm_scan"]


@pytest.mark.parametrize("kernel,shape", [
    ("conv_mm", CONV_SHAPE),
    ("flash_attention", FLASH_SHAPE),
    ("ssm_scan", SSM_SHAPE),
    ("paged_decode", PD_SHAPE),
])
def test_default_config_is_a_candidate(kernel, shape):
    tiling = get_tiling(kernel)
    assert tiling.default(shape) in list(tiling.candidates(shape))


# ---------------------------------------------------------------------------
# cache round-trip + corruption tolerance
# ---------------------------------------------------------------------------


def test_tuning_cache_roundtrip(tmp_path, tuner):
    cfg1 = tuner.tune("conv_mm", CONV_SHAPE)
    assert tuner.misses == 1
    # same tuner: in-memory hit
    assert tuner.tune("conv_mm", CONV_SHAPE) == cfg1
    assert (tuner.hits, tuner.misses) == (1, 1)
    # fresh tuner on the same file: disk hit, no re-search
    t2 = KernelTuner(device=get_device(TPU),
                     cache=str(tmp_path / "tune.json"), measure=False)
    assert t2.tune("conv_mm", CONV_SHAPE) == cfg1
    assert (t2.hits, t2.misses) == (1, 0)


def test_tuning_cache_corrupt_file_tolerated(tmp_path):
    path = tmp_path / "tune.json"
    path.write_text("{definitely not json")
    t = KernelTuner(device=get_device(TPU), cache=str(path), measure=False)
    cfg = t.tune("conv_mm", CONV_SHAPE)   # restarts from empty, re-tunes
    assert t.misses == 1 and cfg
    assert os.path.exists(str(path) + ".corrupt")
    # the re-tuned winner was flushed atomically over the quarantined file
    assert json.loads(path.read_text())


def test_tuning_cache_entries_are_json_round_trippable(tmp_path, tuner):
    for kernel, shape in [("conv_mm", CONV_SHAPE),
                          ("flash_attention", FLASH_SHAPE),
                          ("ssm_scan", SSM_SHAPE)]:
        tuner.tune(kernel, shape)
    data = json.loads((tmp_path / "tune.json").read_text())
    assert len(data) == 3
    for entry in data.values():
        assert entry["source"] == "model"
        assert entry["config"]
        assert entry["model_us"] <= entry["default_model_us"] * (1 + 1e-9)


# ---------------------------------------------------------------------------
# device-fingerprint salting
# ---------------------------------------------------------------------------


def test_device_fingerprint_salts_keys(tmp_path):
    """Two specs never alias: same shape tunes independently per device."""
    path = str(tmp_path / "tune.json")
    a = KernelTuner(device=get_device(TPU), cache=path, measure=False)
    b = KernelTuner(device=get_device("tx2_like"), cache=path, measure=False)
    assert a.key("conv_mm", CONV_SHAPE) != b.key("conv_mm", CONV_SHAPE)
    a.tune("conv_mm", CONV_SHAPE)
    b.tune("conv_mm", CONV_SHAPE)
    assert b.misses == 1          # a's entry was NOT served to b
    assert len(TuningCache(path)) == 2


def test_fingerprint_sensitive_to_constants(tmp_path):
    base = get_device(TPU)
    slower = DeviceSpec(name=base.name, peak_flops=base.peak_flops / 2,
                        hbm_bw=base.hbm_bw)
    a = KernelTuner(device=base, cache=None)
    b = KernelTuner(device=slower, cache=None)
    assert a.key("conv_mm", CONV_SHAPE) != b.key("conv_mm", CONV_SHAPE)


# ---------------------------------------------------------------------------
# VMEM pruning + ranking
# ---------------------------------------------------------------------------


def test_vmem_infeasible_candidates_rejected(tuner):
    # big image × wide channels: large block_o working sets blow VMEM
    shape = conv_tiling.shape_key((1, 64, 64, 256), (3, 3, 256, 512),
                                  stride=1, padding=1, dtype="float32")
    entry = tuner.explain("conv_mm", shape)
    assert entry["rejected_vmem"] > 0
    cost = get_tiling("conv_mm").cost(shape, entry["config"])
    assert vmem_ok(cost)
    assert cost.vmem_bytes <= VMEM_BYTES * VMEM_BUDGET_FRACTION
    # and the infeasible configs really are over budget
    big = get_tiling("conv_mm").cost(shape, {"block_o": 512})
    assert not vmem_ok(big)


def test_all_infeasible_falls_back_to_smallest_working_set(tuner):
    # pathological: even block_o=1's padded image exceeds a tiny budget
    t = KernelTuner(device=get_device(TPU), cache=None,
                    vmem_budget_bytes=1024)
    cfg = t.tune("conv_mm", CONV_SHAPE)
    costs = {json.dumps(c, sort_keys=True):
             get_tiling("conv_mm").cost(CONV_SHAPE, c)
             for c in get_tiling("conv_mm").candidates(CONV_SHAPE)}
    assert (get_tiling("conv_mm").cost(CONV_SHAPE, cfg).vmem_bytes
            == min(c.vmem_bytes for c in costs.values()))


def test_tuned_never_worse_than_default_by_model(tuner):
    for kernel, shape in [("conv_mm", CONV_SHAPE),
                          ("flash_attention", FLASH_SHAPE),
                          ("ssm_scan", SSM_SHAPE)]:
        entry = tuner.explain(kernel, shape)
        tiling = get_tiling(kernel)
        tuned_t = roofline_seconds(tiling.cost(shape, entry["config"]),
                                   tuner.device)
        default_t = roofline_seconds(tiling.cost(shape, entry["default_config"]),
                                     tuner.device)
        assert tuned_t <= default_t * (1 + 1e-9), (kernel, entry)


def test_roofline_prefers_fewer_steps_at_equal_traffic():
    dev = get_device(TPU)
    small = KernelCost(flops=1e9, hbm_bytes=1e6, vmem_bytes=1e3,
                       n_steps=1000, mxu_min_dim=128)
    big = KernelCost(flops=1e9, hbm_bytes=1e6, vmem_bytes=1e3,
                     n_steps=10, mxu_min_dim=128)
    assert roofline_seconds(big, dev) < roofline_seconds(small, dev)


def test_mxu_underfill_penalised():
    dev = get_device(TPU)
    narrow = KernelCost(flops=1e9, hbm_bytes=1e6, vmem_bytes=1e3,
                        n_steps=10, mxu_min_dim=8)
    full = KernelCost(flops=1e9, hbm_bytes=1e6, vmem_bytes=1e3,
                      n_steps=10, mxu_min_dim=128)
    assert roofline_seconds(narrow, dev) > roofline_seconds(full, dev)


# ---------------------------------------------------------------------------
# tuned vs default kernel outputs (interpret mode)
# ---------------------------------------------------------------------------


def _rand(rng, shape, dtype=jnp.float32):
    return jnp.asarray(rng.standard_normal(shape), dtype)


def test_conv_tuned_config_parity(tuner):
    rng = np.random.default_rng(0)
    x = _rand(rng, (2, 16, 16, 32))
    w = _rand(rng, (3, 3, 32, 64)) * 0.2
    bo = tuner.tune("conv_mm", CONV_SHAPE)["block_o"]
    out = conv_mm_kernel(x, w, stride=1, padding=1, block_o=bo, interpret=True)
    ref = conv_ref(x, w, stride=1, padding=1)
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)


def test_flash_tuned_config_parity(tuner):
    rng = np.random.default_rng(1)
    q = _rand(rng, (1, 4, 512, 64))
    k = _rand(rng, (1, 2, 512, 64))
    v = _rand(rng, (1, 2, 512, 64))
    shape = flash_tiling.shape_key(q.shape, k.shape, causal=True,
                                   dtype="float32")
    cfg = tuner.tune("flash_attention", shape)
    out = flash_attention_kernel(q, k, v, causal=True, interpret=True, **cfg)
    ref = attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)


def test_ssd_tuned_config_parity(tuner):
    rng = np.random.default_rng(2)
    xh = _rand(rng, (1, 256, 4, 32)) * 0.5
    a = -jnp.abs(_rand(rng, (1, 256, 4))) * 0.3
    Bm = _rand(rng, (1, 256, 32)) * 0.5
    cfg = tuner.tune("ssm_scan", SSM_SHAPE)
    y, st = ssd(xh, a, Bm, Bm, chunk=cfg["chunk"], interpret=True)
    y_ref, st_ref = ssd_ref(xh, a, Bm, Bm, chunk=64)
    np.testing.assert_allclose(y, y_ref, rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(st, st_ref, rtol=1e-3, atol=1e-3)


def test_ops_autotuned_dispatch_matches_ref():
    """chunk=None → the op pulls its chunk from the (isolated) default
    tuner and still matches the reference."""
    rng = np.random.default_rng(3)
    xh = _rand(rng, (1, 96, 2, 16)) * 0.5
    a = -jnp.abs(_rand(rng, (1, 96, 2))) * 0.3
    Bm = _rand(rng, (1, 96, 16)) * 0.5
    y, st = ssd(xh, a, Bm, Bm, interpret=True)
    y_ref, st_ref = ssd_ref(xh, a, Bm, Bm, chunk=32)
    np.testing.assert_allclose(y, y_ref, rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(st, st_ref, rtol=1e-3, atol=1e-3)


# ---------------------------------------------------------------------------
# divisibility fallbacks (previously hard asserts)
# ---------------------------------------------------------------------------


def test_conv_nondividing_block_o_runs():
    rng = np.random.default_rng(4)
    x = _rand(rng, (1, 8, 8, 4))
    w = _rand(rng, (3, 3, 4, 96)) * 0.2   # O=96 with the old min(O,256)=96… force 256
    out = conv_mm_kernel(x, w, stride=1, padding=1, block_o=256, interpret=True)
    ref = conv_ref(x, w, stride=1, padding=1)
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)


def test_conv_nondividing_small_block_o_runs():
    rng = np.random.default_rng(5)
    x = _rand(rng, (1, 8, 8, 4))
    w = _rand(rng, (3, 3, 4, 24)) * 0.2
    out = conv_mm_kernel(x, w, stride=1, padding=1, block_o=16,  # → 12? no: 8
                         interpret=True)
    ref = conv_ref(x, w, stride=1, padding=1)
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)


def test_flash_nondividing_blocks_run():
    rng = np.random.default_rng(6)
    q = _rand(rng, (1, 2, 384, 32))       # Sq=384 with block_q=512
    k = _rand(rng, (1, 2, 384, 32))
    v = _rand(rng, (1, 2, 384, 32))
    out = flash_attention_kernel(q, k, v, causal=True, block_q=512,
                                 block_k=512, interpret=True)
    ref = attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(out.astype(np.float32),
                               ref.astype(np.float32), rtol=2e-4, atol=2e-4)


def test_flash_nondividing_block_k_runs():
    rng = np.random.default_rng(7)
    q = _rand(rng, (1, 2, 64, 32))
    k = _rand(rng, (1, 2, 96, 32))        # Sk=96, block_k=64 → 48
    v = _rand(rng, (1, 2, 96, 32))
    out = flash_attention_kernel(q, k, v, causal=True, q_offset=32,
                                 block_q=64, block_k=64, interpret=True)
    ref = attention_ref(q, k, v, causal=True, q_offset=32)
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)


def test_ssd_nondividing_chunk_runs():
    rng = np.random.default_rng(8)
    xh = _rand(rng, (1, 96, 2, 16)) * 0.5
    a = -jnp.abs(_rand(rng, (1, 96, 2))) * 0.3
    Bm = _rand(rng, (1, 96, 16)) * 0.5
    y, st = ssd(xh, a, Bm, Bm, chunk=64, interpret=True)  # 96 % 64 → 48
    y_ref, st_ref = ssd_ref(xh, a, Bm, Bm, chunk=32)
    np.testing.assert_allclose(y, y_ref, rtol=1e-3, atol=1e-3)


# ---------------------------------------------------------------------------
# model warm-up entry point
# ---------------------------------------------------------------------------


def test_warm_autotune_populates_cache(tmp_path):
    from repro.configs.registry import get_config
    from repro.kernels.autotune import get_tuner
    from repro.models.transformer import warm_autotune

    cfg = get_config("qwen3-4b", reduced=True)
    stats = warm_autotune(cfg, batch_size=2, seq_len=32,
                          stages=("prefill", "decode"))
    assert stats["misses"] >= 1          # attention shapes were tuned
    tuner = get_tuner()
    assert len(tuner.cache) >= 1
    # second warm pass: everything already cached
    stats2 = warm_autotune(cfg, batch_size=2, seq_len=32,
                           stages=("prefill", "decode"))
    assert stats2["misses"] == 0 and stats2["hits"] >= 1


def test_tuned_config_propagates_kernel_errors(tmp_path, monkeypatch):
    """A candidate that fails while it is timed (as a kernel the chip's
    compiler refuses would) fails the lookup instead of silently becoming
    the default config."""
    from repro.kernels import autotune as at

    def refused(shape, config):
        raise RuntimeError("Mosaic refused the block shape")

    monkeypatch.setitem(at._TILINGS, "refused_probe", at.TilingModel(
        name="refused_probe", candidates=lambda s: [{"b": 1}, {"b": 2}],
        cost=lambda s, c: KernelCost(op="probe", flops=1.0, hbm_bytes=1.0),
        default=lambda s: {"b": 1}, runner=refused))
    set_tuner(KernelTuner(device=get_device(TPU),
                          cache=str(tmp_path / "t.json"), measure=True))
    with pytest.raises(RuntimeError, match="Mosaic refused"):
        at.tuned_config("refused_probe", {"n": 1}, {"b": 1})


def test_tuned_config_unwritable_cache_returns_default(tmp_path):
    from repro.kernels.autotune import tuned_config

    blocker = tmp_path / "not_a_dir"
    blocker.write_text("")
    set_tuner(KernelTuner(device=get_device(TPU),
                          cache=str(blocker / "t.json"), measure=False))
    assert tuned_config("paged_decode", PD_SHAPE, {"sentinel": 1}) == {
        "sentinel": 1}
