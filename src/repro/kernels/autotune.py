"""Kernel autotuner: roofline-pruned block-size search, device-keyed cache.

perf4sight's core move — *predict cheaply, measure only what matters*
(paper §5–6) — applied to our own Pallas hot paths.  Brute-force timing
every (block_q, block_k, block_o, chunk) point on-device is exactly the
cost the paper's toolflow exists to avoid, so the tuner works in three
stages:

1. **Enumerate** — each kernel package exports a :class:`TilingModel`
   whose ``candidates(shape)`` generates the legal block configurations
   for a concrete launch shape (always including the kernel's static
   default, so tuning can never regress the modelled time).
2. **Prune + rank** — the model's ``cost(shape, config)`` returns a
   static :class:`KernelCost` (FLOPs, HBM bytes, VMEM working set, grid
   steps — the same formulas as the kernel docstrings and
   ``benchmarks/kernel_bench.py``, now executable).  Candidates whose
   working set exceeds the VMEM budget are rejected outright; the rest
   are ranked by roofline time under the calibrated
   :class:`~repro.engine.devices.DeviceSpec`.
3. **Measure (TPU only)** — the top-K survivors are wall-clock timed
   through the tiling model's ``runner``.  Off-TPU (interpret mode)
   wall-clock is meaningless, so the model ranking alone decides.

Winners persist in a :class:`TuningCache` — the same atomic, corrupt-
tolerant JSON contract as ``engine/cache.py`` (via ``core/fileio``),
with every key salted by the device fingerprint so two specs can never
alias an entry.  A second ``tune()`` for the same (kernel, shape,
device) is a pure cache hit: no re-ranking, no re-timing.
"""

from __future__ import annotations

import hashlib
import json
import os
import time
from dataclasses import dataclass, field
from typing import Callable

from repro.core.fileio import atomic_write_json, load_json_tolerant
from repro.costmodel import OpCost

__all__ = [
    "KernelCost",
    "TilingModel",
    "TuningCache",
    "KernelTuner",
    "register_tiling",
    "get_tiling",
    "list_tilings",
    "roofline_seconds",
    "vmem_ok",
    "largest_dividing_block",
    "autotune_enabled",
    "get_tuner",
    "set_tuner",
    "tuned_config",
]

# TPU v5e-class VMEM per core; the budget leaves headroom for compiler
# scratch, register spills and double-buffered pipeline copies that the
# static working-set formulas don't see.
VMEM_BYTES = 16 * 1024 * 1024
VMEM_BUDGET_FRACTION = 0.9

# MXU systolic-array edge: matmul operand dims below this underfill the
# unit, scaling effective peak FLOP/s by ~dim/128 (see docs/kernels.md).
MXU_DIM = 128

# Per sequenced step (grid program or inner loop trip): block-index
# bookkeeping + pipeline bubble.  Order-of-magnitude constant — it only
# needs to break ties between configs with identical roofline terms
# (favouring fewer, larger blocks), not predict absolute latency.
STEP_OVERHEAD_S = 2e-7

BYTES_PER_ELEMENT = {
    "float32": 4, "bfloat16": 2, "float16": 2, "float64": 8, "int8": 1,
}


def bytes_per_element(dtype: str) -> int:
    return BYTES_PER_ELEMENT.get(str(dtype), 4)


def largest_dividing_block(n: int, requested: int | None) -> int:
    """Largest block size that divides ``n`` and is ≤ ``requested``.

    The documented fallback for every block-size argument: a requested
    block that doesn't tile the dimension evenly degrades to the nearest
    legal (dividing) size instead of crashing the launch.  ``None`` or a
    request ≥ n yields n itself (single block)."""
    n = int(n)
    if n <= 0:
        raise ValueError(f"cannot block a non-positive dim: {n}")
    b = max(1, min(int(requested) if requested else n, n))
    while n % b:
        b -= 1
    return b


@dataclass(frozen=True, kw_only=True)
class KernelCost(OpCost):
    """Static cost of one kernel launch under one block configuration — a
    thin view over the shared :class:`~repro.costmodel.OpCost` record, so
    tuner rows and calibration rows carry one schema (a timed winner feeds
    ``engine/calibrate.timed_tuning_rows`` as an op-class-attributed
    latency row, exactly like a parsed HLO instruction).

    On top of the OpCost fields (``flops``, ``hbm_bytes``, ``vmem_bytes``,
    ``op_class``, …): ``n_steps`` counts sequenced steps — grid programs
    plus inner-loop trips — each paying ``STEP_OVERHEAD_S``, and
    ``mxu_min_dim`` is the smallest matmul operand dim the tiling
    produces; it scales effective MXU peak by ``min(1, dim/128)``."""

    n_steps: int = 1
    mxu_min_dim: int = MXU_DIM

    @property
    def arithmetic_intensity(self) -> float:
        return self.flops / max(self.hbm_bytes, 1.0)


def vmem_ok(cost: KernelCost, *, budget_bytes: float | None = None) -> bool:
    limit = (VMEM_BYTES * VMEM_BUDGET_FRACTION
             if budget_bytes is None else budget_bytes)
    return cost.vmem_bytes <= limit


def roofline_seconds(cost: KernelCost, device) -> float:
    """Modelled launch time on ``device`` (a DeviceSpec).

    Classic roofline over the device's calibrated denominators — via
    ``DeviceSpec.combine_terms``, so a calibrated spec's additive
    relaxation and launch overhead apply here exactly as they do in the
    cost engine — plus the per-step sequencing overhead."""
    util = min(1.0, max(int(cost.mxu_min_dim), 1) / MXU_DIM)
    t = device.combine_terms(
        cost.flops / (device.peak_flops * util),
        cost.hbm_bytes / device.hbm_bw,
    )
    return t + cost.n_steps * STEP_OVERHEAD_S


@dataclass(frozen=True)
class TilingModel:
    """One kernel's tiling search space and static cost model.

    ``candidates(shape) -> list[dict]`` — legal block configs (must
    include ``default(shape)``).
    ``cost(shape, config) -> KernelCost`` — static launch cost.
    ``default(shape) -> dict`` — the hand-picked constants the kernel
    used before autotuning (the tuner's baseline).
    ``runner(shape, config) -> Callable[[], None]`` — optional: builds a
    zero-arg closure running the real kernel (for on-TPU timing).
    """

    name: str
    candidates: Callable
    cost: Callable
    default: Callable
    runner: Callable | None = None


_TILINGS: dict[str, TilingModel] = {}
_BUILTIN_MODULES = (
    "repro.kernels.conv_mm.tiling",
    "repro.kernels.flash_attention.tiling",
    "repro.kernels.ssm_scan.tiling",
    "repro.kernels.moe_dispatch.tiling",
    "repro.kernels.serve_kv.tiling",
    "repro.kernels.paged_decode.tiling",
)


def register_tiling(model: TilingModel, *, overwrite: bool = False) -> TilingModel:
    if model.name in _TILINGS and not overwrite:
        raise ValueError(f"tiling {model.name!r} already registered")
    _TILINGS[model.name] = model
    return model


def _ensure_builtin() -> None:
    import importlib

    for mod in _BUILTIN_MODULES:
        importlib.import_module(mod)


def get_tiling(name: str) -> TilingModel:
    if name not in _TILINGS:
        _ensure_builtin()
    try:
        return _TILINGS[name]
    except KeyError:
        raise KeyError(
            f"unknown kernel tiling {name!r}; registered: {sorted(_TILINGS)}"
        ) from None


def list_tilings() -> list[str]:
    _ensure_builtin()
    return sorted(_TILINGS)


# ---------------------------------------------------------------------------
# Persistence: the tuning cache (engine/cache.py idiom on core/fileio).
# ---------------------------------------------------------------------------


class TuningCache:
    """Content-keyed on-disk winners: {key: {"config": ..., meta...}}.

    Keys are sha1(kernel | canonical shape json | device fingerprint) —
    built by :meth:`KernelTuner.key` — so entries tuned for one device
    spec can never be served to another.  Atomic writes, corrupt files
    quarantined and restarted from empty (``core/fileio`` contract)."""

    def __init__(self, path: str):
        self.path = path
        self._data: dict[str, dict] = load_json_tolerant(path)

    def get(self, key: str) -> dict | None:
        entry = self._data.get(key)
        return dict(entry) if entry else None

    def put(self, key: str, entry: dict) -> None:
        self._data[key] = dict(entry)

    def entries(self) -> list[dict]:
        """All cached winners (copies) — the calibration residual feed
        (``engine/calibrate.timed_tuning_rows``) iterates these."""
        return [dict(e) for e in self._data.values()]

    def flush(self) -> None:
        # Merge-on-flush: re-read the file and lay our entries over it, so
        # concurrent tuners sharing one path (multi-process launch, or two
        # devices salting into the same file) append rather than clobber.
        # Keys are content hashes — a colliding key carries the same shape
        # and device, so last-writer-wins on an entry is benign.
        on_disk = load_json_tolerant(self.path)
        if on_disk:
            self._data = {**on_disk, **self._data}
        atomic_write_json(self.path, self._data)

    def __len__(self) -> int:
        return len(self._data)

    def __contains__(self, key: str) -> bool:
        return key in self._data


# ---------------------------------------------------------------------------
# The tuner.
# ---------------------------------------------------------------------------


class KernelTuner:
    """Roofline-pruned block-size search with per-device memoization.

    ``tune(kernel, shape)`` resolution order: in-process memo → on-disk
    :class:`TuningCache` → model-pruned search (→ top-K wall-clock only
    when ``measure`` and a runner are available).  ``hits``/``misses``/
    ``timed`` count those paths for benchmarks and tests.
    """

    def __init__(self, device=None, cache: TuningCache | str | None = None,
                 *, top_k: int = 3, measure: bool | None = None,
                 vmem_budget_bytes: float | None = None):
        self._device = device
        self.cache = TuningCache(cache) if isinstance(cache, str) else cache
        self.top_k = max(1, int(top_k))
        self.measure = measure
        self.vmem_budget_bytes = vmem_budget_bytes
        self._memo: dict[str, dict] = {}
        self.hits = 0
        self.misses = 0
        self.timed = 0

    # -- device ------------------------------------------------------------

    @property
    def device(self):
        """The DeviceSpec keys are salted with (lazily derived from the
        live jax backend when not configured)."""
        if self._device is None:
            from repro.engine.devices import from_jax_device

            self._device = from_jax_device()
        elif isinstance(self._device, (str, dict)):
            from repro.engine.devices import resolve_device

            self._device = resolve_device(self._device)
        return self._device

    def _should_measure(self) -> bool:
        if self.measure is not None:
            return self.measure
        import jax

        return jax.default_backend() == "tpu"

    # -- keys --------------------------------------------------------------

    def key(self, kernel: str, shape: dict) -> str:
        blob = f"{kernel}|{json.dumps(shape, sort_keys=True)}|{self.device.fingerprint()}"
        return hashlib.sha1(blob.encode()).hexdigest()

    # -- search ------------------------------------------------------------

    def tune(self, kernel: str, shape: dict) -> dict:
        """Best block config for one concrete launch shape (a plain dict
        of static kwargs for the kernel, e.g. ``{"block_o": 128}``)."""
        key = self.key(kernel, shape)
        entry = self._memo.get(key)
        if entry is None and self.cache is not None:
            entry = self.cache.get(key)
            if entry is not None:
                self._memo[key] = entry
        if entry is not None:
            self.hits += 1
            return dict(entry["config"])
        self.misses += 1
        entry = self._search(get_tiling(kernel), shape)
        self._memo[key] = entry
        if self.cache is not None:
            self.cache.put(key, entry)
            self.cache.flush()
        return dict(entry["config"])

    def explain(self, kernel: str, shape: dict) -> dict:
        """The full cached entry (config + modelled times + provenance)."""
        self.tune(kernel, shape)
        return dict(self._memo[self.key(kernel, shape)])

    def _search(self, tiling: TilingModel, shape: dict) -> dict:
        device = self.device
        default = tiling.default(shape)
        cands = list(tiling.candidates(shape))
        if default not in cands:
            cands.append(default)

        scored = []
        rejected_vmem = 0
        for cfg in cands:
            cost = tiling.cost(shape, cfg)
            if not vmem_ok(cost, budget_bytes=self.vmem_budget_bytes):
                rejected_vmem += 1
                continue
            scored.append((roofline_seconds(cost, device), cost, cfg))
        if not scored:
            # Nothing fits the budget (huge shape): least-infeasible
            # candidate, flagged — the kernel may still spill but runs.
            cost_cfgs = [(tiling.cost(shape, c), c) for c in cands]
            cost, cfg = min(cost_cfgs, key=lambda t: t[0].vmem_bytes)
            scored = [(roofline_seconds(cost, device), cost, cfg)]
        scored.sort(key=lambda t: (t[0], json.dumps(t[2], sort_keys=True)))

        best_t, best_cost, best_cfg = scored[0]
        source = "model"
        if self._should_measure() and tiling.runner is not None:
            best_t, best_cfg = self._time_top_k(tiling, shape, scored)
            best_cost = tiling.cost(shape, best_cfg)
            source = "timed"

        default_cost = tiling.cost(shape, default)
        return {
            "kernel": tiling.name,
            "config": dict(best_cfg),
            "shape": dict(shape),  # lets calibration rebuild the cost terms
            "source": source,
            "device": device.name,
            "model_us": best_t * 1e6 if source == "model" else
            roofline_seconds(best_cost, device) * 1e6,
            "measured_us": best_t * 1e6 if source == "timed" else None,
            "default_config": dict(default),
            "default_model_us": roofline_seconds(default_cost, device) * 1e6,
            "vmem_kb": best_cost.vmem_bytes / 1024,
            "candidates": len(cands),
            "rejected_vmem": rejected_vmem,
        }

    def _time_top_k(self, tiling: TilingModel, shape: dict, scored) -> tuple[float, dict]:
        import jax

        best = (float("inf"), scored[0][2])
        for _, _, cfg in scored[: self.top_k]:
            fn = tiling.runner(shape, cfg)
            jax.block_until_ready(fn())  # compile
            ts = []
            for _ in range(3):
                t0 = time.perf_counter()
                jax.block_until_ready(fn())
                ts.append(time.perf_counter() - t0)
            self.timed += 1
            t = min(ts)
            if t < best[0]:
                best = (t, cfg)
        return best


# ---------------------------------------------------------------------------
# Process-default tuner: what the ops wrappers and model code consult when
# no explicit block sizes are passed.
# ---------------------------------------------------------------------------

_DEFAULT_TUNER: KernelTuner | None = None


def autotune_enabled() -> bool:
    """Gate for implicit tuning in ops/model call sites (REPRO_AUTOTUNE=0
    restores the hand-picked constants everywhere)."""
    return os.environ.get("REPRO_AUTOTUNE", "1") != "0"


def _default_cache_path() -> str:
    from repro.core.cache_dirs import TUNING_CACHE_PATH

    return os.environ.get("REPRO_TUNING_CACHE", TUNING_CACHE_PATH)


def get_tuner() -> KernelTuner:
    global _DEFAULT_TUNER
    if _DEFAULT_TUNER is None:
        _DEFAULT_TUNER = KernelTuner(cache=_default_cache_path())
    return _DEFAULT_TUNER


def set_tuner(tuner: KernelTuner | None) -> None:
    """Install (or with None, reset) the process-default tuner — tests and
    benchmarks point it at a scratch cache/device."""
    global _DEFAULT_TUNER
    _DEFAULT_TUNER = tuner


def tuned_config(kernel: str, shape: dict, default: dict | None = None) -> dict:
    """Tuned config for implicit call sites: returns ``default`` (or {})
    when autotuning is disabled or the tuning cache cannot be read or
    written — a model forward must not die because a cache directory is
    read-only.  Every other error propagates: a candidate kernel that the
    chip's compiler refuses while it is being timed fails the run instead
    of quietly becoming the default block config."""
    if not autotune_enabled():
        return dict(default or {})
    try:
        return get_tuner().tune(kernel, shape)
    except (OSError, json.JSONDecodeError):
        return dict(default or {})
