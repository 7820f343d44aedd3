"""DeviceSpec registry + property tests: fingerprint sensitivity to every
fitted constant, serialization round-trips, roofline monotonicity."""

import dataclasses
import json
import os

import pytest

from repro.core.features import ConvLayerSpec, NetworkSpec
from repro.engine import (
    AnalyticalBackend,
    CostEngine,
    CostQuery,
    DeviceSpec,
    from_jax_device,
    get_device,
    list_devices,
    load_device_spec,
    register_device,
    resolve_device,
    save_device_spec,
)
from repro.engine.devices import FITTED_FIELDS
from tests._hypothesis import given, settings, st

NET = NetworkSpec("probe", (
    ConvLayerSpec(n=8, m=3, k=3, stride=1, padding=1, ip=16),
    ConvLayerSpec(n=16, m=8, k=3, stride=2, padding=1, ip=16),
))


def _phi(device: DeviceSpec, bs: int = 8) -> float:
    backend = AnalyticalBackend(device=device)
    return backend.estimate([CostQuery(spec=NET, bs=bs)])[0].phi_ms


# -- registry -----------------------------------------------------------------


def test_builtin_registry():
    for name in ("host_cpu", "tx2_like", "tpu_v5e"):
        assert name in list_devices()
        spec = get_device(name)
        assert spec.name == name and not spec.calibrated
    # host_cpu carries the pre-registry HOST_CPU constants
    hc = get_device("host_cpu")
    assert (hc.peak_flops, hc.hbm_bw) == (5e10, 2e10)


def test_get_device_unknown_names_registered():
    with pytest.raises(KeyError, match="host_cpu"):
        get_device("nope")


def test_register_device_no_silent_overwrite():
    spec = DeviceSpec(name="test_dev_reg", peak_flops=1e12, hbm_bw=1e11)
    register_device(spec)
    with pytest.raises(ValueError):
        register_device(spec)
    assert register_device(spec, overwrite=True) is spec


def test_resolve_device_forms(tmp_path):
    assert resolve_device(None).name == "host_cpu"
    assert resolve_device("tx2_like").name == "tx2_like"
    spec = DeviceSpec(name="inline", peak_flops=1e12, hbm_bw=1e11)
    assert resolve_device(spec) is spec
    legacy = resolve_device({"peak_flops_bf16": 2e12, "hbm_bw": 3e11})
    assert legacy.peak_flops == 2e12 and legacy.hbm_bw == 3e11
    path = str(tmp_path / "dev.json")
    save_device_spec(path, spec)
    assert resolve_device(path).fingerprint() == spec.fingerprint()
    with pytest.raises(TypeError):
        resolve_device(42)


def test_from_jax_device_registers_uncalibrated_spec():
    spec = from_jax_device()
    assert spec.name.startswith("jax_") and not spec.calibrated
    assert spec.name in list_devices()
    assert spec.peak_flops > 0 and spec.hbm_bytes > 0


class _FakeJaxDevice:
    def __init__(self, platform, device_kind, stats=None):
        self.platform, self.device_kind, self._stats = (
            platform, device_kind, stats)

    def memory_stats(self):
        return self._stats


@pytest.mark.parametrize("platform,kind,spec", [
    ("tpu", "TPU v5 lite", "tpu_v5e"),
    ("tpu", "TPU v5e", "tpu_v5e"),
    ("cpu", "cpu", "host_cpu"),
])
def test_from_jax_device_looks_up_device_kind(platform, kind, spec):
    got = from_jax_device(_FakeJaxDevice(platform, kind,
                                         {"bytes_limit": 15e9}))
    want = get_device(spec)
    assert (got.peak_flops, got.hbm_bw) == (want.peak_flops, want.hbm_bw)
    assert got.hbm_bytes == 15e9
    assert got.meta == {"platform": platform, "device_kind": kind}


@pytest.mark.parametrize("platform,kind", [
    ("tpu", "TPU v4"), ("gpu", "NVIDIA A100-SXM4-40GB"), ("tpu", "")])
def test_from_jax_device_unknown_kind_raises(platform, kind):
    with pytest.raises(KeyError, match="no device spec"):
        from_jax_device(_FakeJaxDevice(platform, kind))


def test_validation():
    with pytest.raises(ValueError):
        DeviceSpec(name="bad", peak_flops=0.0, hbm_bw=1e9)
    with pytest.raises(ValueError):
        DeviceSpec(name="bad", peak_flops=1e9, hbm_bw=1e9, combine="mean")
    with pytest.raises(ValueError):
        DeviceSpec(name="bad", peak_flops=1e9, hbm_bw=1e9, alloc_granularity=0)


# -- fingerprint sensitivity --------------------------------------------------


def _bumped(spec: DeviceSpec, field: str) -> DeviceSpec:
    v = getattr(spec, field)
    if field == "combine":
        return dataclasses.replace(spec, combine="sum" if v == "max" else "max")
    if field == "calibrated":
        return dataclasses.replace(spec, calibrated=not v)
    if field == "alloc_granularity":
        return dataclasses.replace(spec, alloc_granularity=int(v) + 1)
    if field == "class_coeffs":
        bumped = dict(v)
        bumped["cnn_latency"] = {"_intercept": bumped.get(
            "cnn_latency", {}).get("_intercept", 0.0) + 1e-3}
        return dataclasses.replace(spec, class_coeffs=bumped)
    if field == "power_modes":
        bumped = dict(v)
        bumped["_BUMP"] = {"peak_w": spec.peak_w + 1.0}
        return dataclasses.replace(spec, power_modes=bumped)
    return dataclasses.replace(spec, **{field: v * 1.5 + 1e-6})


def test_fingerprint_sensitive_to_every_fitted_constant():
    base = get_device("tx2_like")
    for field in FITTED_FIELDS:
        assert _bumped(base, field).fingerprint() != base.fingerprint(), field
    # name and meta are NOT prediction-relevant: same constants, same key
    assert dataclasses.replace(base, name="alias").fingerprint() == base.fingerprint()


def test_spec_stays_hashable_with_class_coeffs():
    # frozen specs are used as set members / dict keys; the class_coeffs
    # dict must not break the generated __hash__ (eq still covers it)
    spec = _bumped(get_device("host_cpu"), "class_coeffs")
    assert spec in {spec}
    assert spec != get_device("host_cpu")


def test_analytical_cache_salt_tracks_device_fingerprint():
    base = AnalyticalBackend(device="host_cpu")
    for field in FITTED_FIELDS:
        bumped = AnalyticalBackend(device=_bumped(get_device("host_cpu"), field))
        assert bumped.cache_salt() != base.cache_salt(), field


def test_engine_level_device_salts_keys():
    backend = AnalyticalBackend()
    e1 = CostEngine(backend, device="host_cpu")
    e2 = CostEngine(backend, device="tx2_like")
    assert e1._salt() != e2._salt()


# -- serialization ------------------------------------------------------------


@pytest.mark.parametrize("ext", ["json", "npz"])
def test_save_load_roundtrip(tmp_path, ext):
    spec = DeviceSpec(
        name="fitted", peak_flops=1.23e12, hbm_bw=4.56e10, ici_bw=7e9,
        hbm_bytes=8e9, launch_overhead_s=2.5e-3, alloc_granularity=512,
        mem_weight_scale=4.1, mem_act_scale=1.7, mem_base_mb=0.4,
        combine="sum", calibrated=True, meta={"phi_mape": 0.12})
    path = str(tmp_path / f"spec.{ext}")
    save_device_spec(path, spec)
    loaded = load_device_spec(path)
    assert loaded == spec
    assert loaded.fingerprint() == spec.fingerprint()
    assert loaded.meta["phi_mape"] == 0.12
    # predictions are identical through the backend
    a = AnalyticalBackend(device=spec).estimate([CostQuery(spec=NET, bs=4)])[0]
    b = AnalyticalBackend(device=loaded).estimate([CostQuery(spec=NET, bs=4)])[0]
    assert (a.gamma_mb, a.phi_ms) == (b.gamma_mb, b.phi_ms)


def test_json_spec_file_is_plain_json(tmp_path):
    path = str(tmp_path / "spec.json")
    save_device_spec(path, get_device("tx2_like"))
    with open(path) as f:
        d = json.load(f)
    assert d["name"] == "tx2_like"
    assert os.path.getsize(path) > 0


# -- property tests (hypothesis; skip cleanly without it) ---------------------

spec_strategy = st.builds(
    DeviceSpec,
    name=st.just("prop"),
    peak_flops=st.floats(1e9, 1e15),
    hbm_bw=st.floats(1e8, 1e13),
    ici_bw=st.floats(1e7, 1e12),
    hbm_bytes=st.floats(1e8, 1e12),
    launch_overhead_s=st.floats(0, 1e-2),
    alloc_granularity=st.integers(1, 4096),
    mem_weight_scale=st.floats(0, 10),
    mem_act_scale=st.floats(0, 10),
    mem_base_mb=st.floats(0, 100),
    combine=st.sampled_from(["max", "sum"]),
    calibrated=st.booleans(),
)


@given(spec=spec_strategy)
@settings(max_examples=40, deadline=None)
def test_prop_dict_roundtrip(spec):
    again = DeviceSpec.from_dict(json.loads(json.dumps(spec.to_dict())))
    assert again == spec
    assert again.fingerprint() == spec.fingerprint()


@given(spec=spec_strategy, factor=st.floats(1.0, 1e3))
@settings(max_examples=40, deadline=None)
def test_prop_more_flops_never_slower(spec, factor):
    faster = dataclasses.replace(spec, peak_flops=spec.peak_flops * factor)
    assert _phi(faster) <= _phi(spec)


@given(spec=spec_strategy, factor=st.floats(1.0, 1e3))
@settings(max_examples=40, deadline=None)
def test_prop_more_bandwidth_never_slower(spec, factor):
    faster = dataclasses.replace(spec, hbm_bw=spec.hbm_bw * factor)
    assert _phi(faster) <= _phi(spec)


@given(spec=spec_strategy, field=st.sampled_from(list(FITTED_FIELDS)))
@settings(max_examples=60, deadline=None)
def test_prop_fingerprint_sensitive(spec, field):
    assert _bumped(spec, field).fingerprint() != spec.fingerprint()
