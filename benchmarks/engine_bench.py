"""Engine benchmark: batched CostBackend.estimate vs per-candidate scalar
prediction, on a search-shaped workload (acceptance check for the unified
engine: ≥5× on a 100-candidate population).

Both paths do identical work per candidate — feature extraction + forest
prediction for (Γ, Φ) — but the batched path builds ONE feature matrix
(vectorized over every layer of every candidate) and walks the packed
forest once, while the scalar path pays N Python round-trips.  Also
reports the on-disk estimate cache hit path (second population visit) and
— so the bench trajectory records prediction ERROR, not just speed — the
calibrated-vs-uncalibrated AnalyticalBackend accuracy against the
checked-in profiler ground truth (ISSUE 2).

    PYTHONPATH=src python -m benchmarks.engine_bench
"""

from __future__ import annotations

import os
import time

import numpy as np

from repro.core.dataset import Datapoint, DatasetCache
from repro.core.features import network_features
from repro.core.predictor import Perf4Sight
from repro.core.search import sample_subnetwork
from repro.engine import (
    AnalyticalBackend,
    CostEngine,
    CostQuery,
    EstimateCache,
    ForestBackend,
    ProfilerBackend,
    calibrate,
    default_workloads,
    evaluate_accuracy,
)
from repro.models.cnn import build_resnet50

from .common import csv_line

PROFILE_CACHE = os.path.join(os.path.dirname(__file__), "cache",
                             "cnn_profile.json")

POPULATION = 100
BS = 16
WM, HW = 0.25, 16


def _fitted_predictor(n_points: int = 60, seed: int = 0) -> Perf4Sight:
    """Fit on synthetic feature-driven targets (no profiling needed — this
    bench measures prediction throughput, not accuracy)."""
    from repro.core.pruning import pruned_model

    rng = np.random.default_rng(seed)
    dps = []
    for _ in range(n_points):
        level = float(rng.uniform(0, 0.9))
        bs = int(rng.integers(2, 33))
        m = pruned_model("resnet50", level, "uniform", seed=0,
                         width_mult=WM, input_hw=HW)
        f = network_features(m.conv_specs(), bs)
        dps.append(Datapoint(
            family="resnet50", level=level, strategy="uniform", bs=bs,
            width_mult=WM, input_hw=HW, seed=0,
            gamma_mb=5.0 + f[4] / 1e5, phi_ms=2.0 + f[14] / 1e7,
            features=[float(v) for v in f]))
    return Perf4Sight(n_estimators=100).fit(dps)


def run(print_fn=print, population: int = POPULATION, repeats: int = 3) -> dict:
    predictor = _fitted_predictor()
    base = build_resnet50(width_mult=WM, input_hw=HW)
    rng = np.random.default_rng(1)
    specs = [
        build_resnet50(widths=sample_subnetwork(base.widths, rng),
                       input_hw=HW).conv_specs()
        for _ in range(population)
    ]
    queries = [CostQuery(spec=s, bs=BS, stage="train") for s in specs]
    backend = ForestBackend(train=predictor)

    # warm both paths (forest packing, numpy dispatch)
    backend.estimate(queries[:2])
    predictor.predict(specs[0], BS)

    t_batch = min(
        _timed(lambda: backend.estimate(queries)) for _ in range(repeats))
    t_scalar = min(
        _timed(lambda: [predictor.predict(s, BS) for s in specs])
        for _ in range(repeats))
    speedup = t_scalar / t_batch

    # parity: the batched path must agree with the scalar path exactly
    ests = backend.estimate(queries)
    scalar = [predictor.predict(s, BS) for s in specs]
    max_dev = max(
        max(abs(e.gamma_mb - g), abs(e.phi_ms - p))
        for e, (g, p) in zip(ests, scalar))

    # cache path: second visit to the same population is pure dict lookups
    cache_path = "/tmp/perf4sight_engine_bench_cache.json"
    if os.path.exists(cache_path):
        os.unlink(cache_path)
    engine = CostEngine(backend, cache=EstimateCache(cache_path))
    engine.estimate(queries)
    t_cached = _timed(lambda: engine.estimate(queries))

    print_fn(csv_line("engine/scalar_ms_per_100", t_scalar * 1e3,
                      f"pop={population}"))
    print_fn(csv_line("engine/batched_ms_per_100", t_batch * 1e3,
                      f"speedup={speedup:.1f}x"))
    print_fn(csv_line("engine/cached_ms_per_100", t_cached * 1e3,
                      f"hits={engine.hits}"))
    print_fn(csv_line("engine/parity_max_abs_dev", max_dev, "expect=0"))
    ledger = ledger_breakdown_parity(print_fn)
    accuracy = calibration_accuracy(print_fn)
    return {"speedup": speedup, "t_scalar_s": t_scalar, "t_batch_s": t_batch,
            "t_cached_s": t_cached, "max_dev": max_dev,
            **ledger, **accuracy}


def ledger_breakdown_parity(print_fn=print) -> dict:
    """Cost-ledger parity on a compiled golden program: the per-op ledger's
    class sums must reproduce the legacy HloCost scalars (the costmodel
    contract every downstream breakdown relies on).  Reported as a
    RELATIVE deviation: the scalars are sequential ledger sums by
    construction, but the class-grouped re-sum associates float additions
    differently, which is only bit-exact while partial sums stay
    integer-representable (< 2^53) — production-scale cells can exceed
    that.  One tiny scan-over-dots compile — seconds, not minutes."""
    import jax
    import jax.numpy as jnp

    from repro.core.hlo_cost import parse_hlo_cost

    def f(x, ws):
        y = jax.lax.scan(lambda c, w: (jnp.tanh(c @ w), None), x, ws)[0]
        return y.sum()

    x = jnp.zeros((64, 64))
    ws = jnp.zeros((8, 64, 64))
    cost = parse_hlo_cost(jax.jit(jax.grad(f)).lower(x, ws).compile().as_text())
    sums = cost.by_class()
    dev = max(
        abs(sum(s["flops"] for s in sums.values()) - cost.flops)
        / max(abs(cost.flops), 1.0),
        abs(sum(s["hbm_bytes"] for s in sums.values()) - cost.hbm_bytes)
        / max(abs(cost.hbm_bytes), 1.0),
        abs(sum(s["collective_bytes"] for s in sums.values())
            - cost.collective_bytes) / max(abs(cost.collective_bytes), 1.0),
    )
    matmul_share = (sums.get("matmul", {}).get("flops", 0.0)
                    / cost.flops if cost.flops else 0.0)
    print_fn(csv_line("engine/ledger_breakdown_parity_dev", dev,
                      f"relative expect=0 records={len(cost.ledger)} "
                      f"matmul_flops_share={matmul_share:.2f}"))
    # Energy parity (docs/engine.md "Energy"): price the same ledger under
    # a power envelope and require the per-class joule sums to reproduce
    # the ledger aggregate (same relative tolerance, same reordering
    # caveat).
    from repro.engine import get_device
    from repro.engine.decompose import price_ledger_energy

    eled = price_ledger_energy(cost.ledger, get_device("tx2_like"))
    esums = eled.class_sums()
    edev = (abs(sum(s["energy_j"] for s in esums.values()) - eled.energy_j)
            / max(abs(eled.energy_j), 1e-30))
    print_fn(csv_line("engine/ledger_energy_parity_dev", edev,
                      f"relative expect=0 total={eled.energy_j:.3g}J"))
    return {"ledger_parity_dev": dev, "ledger_energy_parity_dev": edev}


def calibration_accuracy(print_fn=print) -> dict:
    """AnalyticalBackend prediction error vs profiler ground truth, before
    and after device calibration.

    Strictly read-only on the golden fixture: workloads missing from it are
    skipped (never live-profiled with bench-grade repeats and written back —
    that would pollute the ground truth tests/test_calibration.py asserts
    against)."""
    if not os.path.exists(PROFILE_CACHE):
        print_fn(csv_line("engine/calibration_skipped", 1.0, "no cache"))
        return {}
    cache = DatasetCache(PROFILE_CACHE)
    dps = [cache.get(w.key) for w in default_workloads()]
    missing = sum(d is None for d in dps)
    dps = [d for d in dps if d is not None]
    if missing:
        print_fn(csv_line("engine/calibration_workloads_missing", missing,
                          "fixture stale; skipped, not re-profiled"))
    if len(dps) < 3:
        print_fn(csv_line("engine/calibration_skipped", 1.0,
                          "fixture too sparse"))
        return {}
    backend = AnalyticalBackend()
    before = evaluate_accuracy(backend, dps)
    spec = calibrate(backend, ProfilerBackend(repeats=1, warmup=0), [],
                     datapoints=dps)
    after = evaluate_accuracy(backend, dps)
    print_fn(csv_line("engine/phi_mape_uncalibrated", before["phi_mape"],
                      f"device={spec.meta['base_device']}"))
    print_fn(csv_line("engine/phi_mape_calibrated", after["phi_mape"],
                      f"device={spec.name} fit={spec.meta['latency_fit']}"))
    # class-wise vs aggregate attribution rows (the cost-ledger refactor):
    # the applied fit is whichever MAPE is lower, so classwise-vs-aggregate
    # regressions show up here before they can skew phi_mape_calibrated
    print_fn(csv_line("engine/phi_mape_cal_aggregate",
                      spec.meta["phi_mape_aggregate"], "3-term fallback"))
    print_fn(csv_line("engine/phi_mape_cal_classwise",
                      spec.meta["phi_mape_classwise"],
                      "per-op-class columns"))
    print_fn(csv_line("engine/gamma_mape_uncalibrated", before["gamma_mape"],
                      f"n={before['n']}"))
    print_fn(csv_line("engine/gamma_mape_calibrated", after["gamma_mape"],
                      "target<=0.10"))
    out = {"phi_mape_uncal": before["phi_mape"],
           "phi_mape_cal": after["phi_mape"],
           "phi_mape_cal_aggregate": spec.meta["phi_mape_aggregate"],
           "phi_mape_cal_classwise": spec.meta["phi_mape_classwise"],
           "gamma_mape_uncal": before["gamma_mape"],
           "gamma_mape_cal": after["gamma_mape"]}
    # Energy fit accuracy (docs/engine.md "Energy"): same aggregate vs
    # class-wise pair as latency.  The golden fixture predates energy
    # measurement, so these targets are the watts-proxy integral —
    # energy_proxied says how many; the never-worse gate still binds.
    if spec.meta.get("energy_fit", "none") != "none":
        print_fn(csv_line("engine/energy_mape_cal_aggregate",
                          spec.meta["energy_mape_aggregate"],
                          f"proxied={spec.meta['energy_proxied']}"))
        print_fn(csv_line("engine/energy_mape_cal_classwise",
                          spec.meta["energy_mape_classwise"],
                          f"fit={spec.meta['energy_fit']}"))
        out["energy_mape_cal"] = spec.meta["energy_mape"]
        out["energy_mape_cal_aggregate"] = spec.meta["energy_mape_aggregate"]
    return out


def campaign_accuracy(print_fn=print, *, ledger_path: str | None = None,
                      subsample: int | None = None) -> dict:
    """LM-forest accuracy rows: run (or resume) the host-CPU smoke campaign,
    fit the forest, and compare held-out-cell MAPE against the uncalibrated
    analytical path (which pays an AOT compile per cell for its answer —
    the forest pays none).

    The ledger persists across bench runs (``/tmp``), so after the first
    nightly run this is resume + fit + a few analytical compiles.  Compiles
    real reduced-config steps: seconds per cold cell — nightly-gate
    territory, which is why ``run()`` doesn't call it."""
    from repro.campaign import (
        CampaignLedger,
        CampaignRunner,
        fit_lm_forest,
        smoke_plan,
    )
    from repro.engine.types import STAGE_INFER, STAGE_TRAIN

    ledger_path = ledger_path or "/tmp/perf4sight_campaign_smoke.jsonl"
    plan = smoke_plan(subsample=subsample)
    runner = CampaignRunner(plan, ledger_path, repeats=2, warmup=1)
    summary = runner.run_campaign(print_fn=lambda *_: None)
    print_fn(csv_line("campaign/cells_measured", summary["measured"],
                      f"grid={len(plan)} quarantined={summary['failed']}"))

    # Membership by cell KEY, not plan_hash: the persistent ledger may hold
    # records from an earlier plan revision whose cells still overlap this
    # one — those resumes are valid measurements of today's cells, while a
    # plan_hash filter would orphan them forever (the runner never
    # re-measures a recorded key).
    plan_keys = {c.key for c in plan.cells}
    records = [r for r in runner.ledger.records("ok")
               if r.get("key") in plan_keys]
    if len(records) < 6:
        print_fn(csv_line("campaign/skipped", 1.0, "grid too sparse"))
        return {}
    try:
        forest = fit_lm_forest(records, holdout_frac=0.25, seed=0)
    except ValueError as e:
        # The /tmp ledger deliberately persists across bench runs; a stale
        # one (fingerprint drift after a DeviceSpec change) must degrade to
        # the documented SKIP, not crash the gate.  Deleting the ledger
        # re-measures from scratch.
        print_fn(csv_line("campaign/skipped", 1.0, f"fit refused: {e}"))
        return {}
    meta = forest.meta

    # Cost-ledger rows: per-record breakdown parity (class sums re-sum to
    # the scalar aggregates; relative dev, since grouped float addition is
    # only bit-exact while partial sums stay integer-representable) +
    # class-wise vs aggregate HLO-constant fit MAPE.  Records predating
    # the v2 schema carry no breakdown; they are skipped (re-measuring
    # them is just deleting the ledger).
    with_classes = [r for r in records if r.get("cost_classes")]
    extra = {}
    if with_classes:
        def rel_dev(rec, key):
            total = sum(s.get(key, 0.0) for s in rec["cost_classes"].values())
            return abs(total - rec[key]) / max(abs(rec[key]), 1.0)

        parity_dev = max(
            max(rel_dev(r, k) for k in ("flops", "hbm_bytes",
                                        "collective_bytes"))
            for r in with_classes)
        print_fn(csv_line("campaign/breakdown_parity_dev", parity_dev,
                          f"relative expect=0 n={len(with_classes)}"))
        extra["breakdown_parity_dev"] = parity_dev
        from repro.campaign import fit_hlo_constants

        try:
            spec = fit_hlo_constants(with_classes)
        except ValueError as e:
            # e.g. a mixed v1/v2 ledger leaving < 4 executed v2 cells
            print_fn(csv_line("campaign/hlo_fit_skipped", 1.0, str(e)))
            spec = None
        if spec is not None:
            print_fn(csv_line("campaign/hlo_phi_mape_aggregate",
                              spec.meta["phi_mape_aggregate"],
                              "4-term fallback"))
            if spec.meta["phi_mape_classwise"] is not None:
                print_fn(csv_line("campaign/hlo_phi_mape_classwise",
                                  spec.meta["phi_mape_classwise"],
                                  f"fit={spec.meta['latency_fit']}"))
            # the APPLIED fit, RE-PRICED through the same decompose paths
            # the analytical backend uses (classwise_seconds for a
            # class-wise spec, the roofline terms for the fallback) — an
            # independent recomputation, so the never-worse gate catches a
            # pricing regression instead of comparing fit-time meta to
            # itself
            from repro.core.predictor import mape
            from repro.engine.decompose import (
                classwise_seconds,
                ledger_latency_columns,
                lm_roofline_terms,
            )

            executed = [r for r in with_classes if r.get("phi_ms", 0) > 0]
            phi_true = np.array([r["phi_ms"] for r in executed]) / 1e3
            coeffs = spec.class_coeffs.get("lm_latency")
            if coeffs:
                pred = classwise_seconds(ledger_latency_columns(
                    [r["cost_classes"] for r in executed]), coeffs)
            else:
                terms = lm_roofline_terms(
                    np.array([r["flops"] for r in executed]),
                    np.array([r["hbm_bytes"] for r in executed]),
                    np.array([r["collective_bytes"] for r in executed]),
                    spec)
                pred = spec.launch_overhead_s + sum(terms)
            applied = float(mape(np.asarray(pred), phi_true))
            print_fn(csv_line("campaign/hlo_phi_mape_applied", applied,
                              f"fit={spec.meta['latency_fit']} re-priced"))
            extra["hlo_phi_mape_applied"] = applied
            extra["hlo_phi_mape_aggregate"] = spec.meta["phi_mape_aggregate"]
            # Energy fit rows (v3 ledgers; v2 records carry no energy and
            # gate the fit off — skip, never fail, on a stale /tmp ledger).
            if spec.meta.get("energy_fit", "none") != "none":
                print_fn(csv_line("campaign/hlo_energy_mape_aggregate",
                                  spec.meta["energy_mape_aggregate"],
                                  "tied fallback"))
                print_fn(csv_line("campaign/hlo_energy_mape_applied",
                                  spec.meta["energy_mape"],
                                  f"fit={spec.meta['energy_fit']}"))
                extra["hlo_energy_mape_applied"] = spec.meta["energy_mape"]
                extra["hlo_energy_mape_aggregate"] = \
                    spec.meta["energy_mape_aggregate"]

    # Held-out cells through BOTH paths.  Same split seed as the fit, so
    # the forest has never seen these cells.
    from repro.campaign.fit import split_records

    _, heldout = split_records(records, holdout_frac=0.25, seed=0)
    queries = [
        CostQuery(arch=r["arch"], bs=r["shape"]["global_batch"],
                  seq=r["shape"]["seq_len"],
                  stage=STAGE_TRAIN if r["shape"]["kind"] == "train"
                  else STAGE_INFER,
                  reduced=True)
        for r in heldout
    ]
    analytical = AnalyticalBackend(reduced=True, lm_device="host_cpu")
    ests = analytical.estimate(queries)
    phi_true = np.array([r["phi_ms"] for r in heldout])
    gamma_true = np.array([r["gamma_mb"] for r in heldout])
    from repro.core.predictor import mape

    anal_phi = mape(np.array([e.phi_ms for e in ests]), phi_true)
    anal_gamma = mape(np.array([e.gamma_mb for e in ests]), gamma_true)
    out = {
        "forest_phi_mape": meta["holdout_phi_mape"],
        "forest_gamma_mape": meta["holdout_gamma_mape"],
        "analytical_phi_mape": anal_phi,
        "analytical_gamma_mape": anal_gamma,
        "n_heldout": len(heldout),
        **extra,
    }
    print_fn(csv_line("campaign/phi_mape_forest", out["forest_phi_mape"],
                      f"heldout={len(heldout)} zero-compile"))
    print_fn(csv_line("campaign/phi_mape_analytical", anal_phi,
                      "AOT compile per cell"))
    print_fn(csv_line("campaign/gamma_mape_forest", out["forest_gamma_mape"],
                      ""))
    print_fn(csv_line("campaign/gamma_mape_analytical", anal_gamma, ""))
    if meta.get("holdout_energy_mape") is not None:
        print_fn(csv_line("campaign/energy_mape_forest",
                          meta["holdout_energy_mape"], "zero-compile"))
        out["forest_energy_mape"] = meta["holdout_energy_mape"]
    return out


def planner_bench(print_fn=print, *, n_devices: int = 256) -> dict:
    """Auto-sharding planner rows (docs/planner.md): size of the layout
    space, wall-clock to price ALL of it through the engine, predicted
    speedup of the chosen layout over the hard-coded production mesh
    (1x16x16) — with the jax compiler booby-trapped for the whole run, so
    the zero-compile guarantee is measured, not assumed.

    The base query is answered by a planted forest (known Γ/Φ), making the
    rows deterministic and engine-path-realistic: the planner sees exactly
    what a campaign-fitted deployment would hand it."""
    from repro.engine import EnsembleBackend, get_device
    from repro.engine.backends import AnalyticalBackend as _AB
    from repro.planner import LayoutPlanner

    class _PlantedLMForest:
        """Fitted-forest stand-in: constant (Γ, Φ), no jax anywhere."""

        fitted = True
        meta: dict = {}

        def __init__(self, gamma_mb, phi_ms):
            self.gamma_mb, self.phi_ms = gamma_mb, phi_ms
            self.default_device = get_device("tpu_v5e")

        def content_hash(self):
            return f"planted-{self.gamma_mb}-{self.phi_ms}"

        def predict_queries(self, queries):
            n = len(queries)
            return (np.full(n, self.gamma_mb), np.full(n, self.phi_ms))

    compiles = {"n": 0}
    orig = _AB._compile_arch

    def boom(*a, **k):
        compiles["n"] += 1
        raise AssertionError("planner pricing invoked the jax compiler")

    _AB._compile_arch = boom
    try:
        engine = CostEngine(
            EnsembleBackend([
                ForestBackend(lm=_PlantedLMForest(40_000.0, 1000.0)),
                AnalyticalBackend(),
            ]),
            device=get_device("tpu_v5e"))
        planner = LayoutPlanner(engine)
        t0 = time.perf_counter()
        plan = planner.plan("qwen3-4b", "train_4k", n_devices, n_micro=8)
        wall_s = time.perf_counter() - t0
    finally:
        _AB._compile_arch = orig

    chosen = plan.chosen
    default = plan.decision_for("1x16x16") if n_devices == 256 else None
    speedup = (default.phi_ms / chosen.phi_ms
               if (chosen and default) else float("nan"))
    print_fn(csv_line("planner/layouts_enumerated", plan.meta["n_layouts"],
                      f"devices={n_devices} ranked={plan.meta['n_ranked']} "
                      f"refused={plan.meta['n_refused']}"))
    print_fn(csv_line("planner/pricing_wall_ms", wall_s * 1e3,
                      f"target<1000 compiles={compiles['n']}"))
    if chosen and default:
        print_fn(csv_line("planner/chosen_vs_default_speedup", speedup,
                          f"chosen={chosen.layout.descriptor} "
                          f"phi={chosen.phi_ms:.2f}ms vs 1x16x16 "
                          f"{default.phi_ms:.2f}ms"))
    return {
        "layouts": plan.meta["n_layouts"],
        "wall_s": wall_s,
        "compiles": compiles["n"],
        "chosen": chosen.layout.descriptor if chosen else None,
        "chosen_phi_ms": chosen.phi_ms if chosen else float("inf"),
        "default_phi_ms": default.phi_ms if default else float("nan"),
        "speedup": speedup,
    }


def collective_calibration(print_fn=print, *, ledger_path: str | None = None
                           ) -> dict:
    """Collective-coefficient rows: run the >1-device calibration grid
    (``campaign.plan.collective_smoke_plan`` — the same cells on 1x1,
    2x1 and 1x2 meshes) in a subprocess with a forced 2-device host, then
    fit the HLO constants over the ledger and report whether the
    collective column entered the fit on real measurements.

    Subprocess because ``xla_force_host_platform_device_count`` must be
    set before jax initializes — this process has already done so.  The
    child is a host-CPU grid by design and runs with ``JAX_PLATFORMS=cpu``:
    on an accelerator host the chip belongs to this process.  The
    /tmp ledger persists, so after the first nightly run this is
    resume + fit.  Skips (empty dict) instead of failing when the
    subprocess or the fit can't run — same degraded contract as
    ``campaign_accuracy``."""
    import json
    import subprocess
    import sys
    import textwrap

    ledger_path = ledger_path or "/tmp/perf4sight_campaign_collective.jsonl"
    script = textwrap.dedent(f"""
        from repro.campaign import CampaignRunner
        from repro.campaign.plan import collective_smoke_plan
        plan = collective_smoke_plan()
        runner = CampaignRunner(plan, {ledger_path!r}, repeats=2, warmup=1)
        out = runner.run_campaign()
        print("CELLS", out["measured"], out["failed"], out["remaining"])
    """)
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(os.path.dirname(__file__), "..", "src")]
                   + os.environ.get("PYTHONPATH", "").split(os.pathsep)))
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=900)
    if proc.returncode != 0:
        print_fn(csv_line("campaign/collective_skipped", 1.0,
                          f"measure subprocess failed: "
                          f"{proc.stderr.strip().splitlines()[-1:] or '?'}"))
        return {}

    from repro.campaign import CampaignLedger, fit_hlo_constants
    from repro.campaign.plan import collective_smoke_plan

    plan_keys = {c.key for c in collective_smoke_plan().cells}
    records = [r for r in CampaignLedger(ledger_path).records("ok")
               if r.get("key") in plan_keys]
    try:
        spec = fit_hlo_constants(records)
    except ValueError as e:
        print_fn(csv_line("campaign/collective_skipped", 1.0,
                          f"fit refused: {e}"))
        return {}
    meta = spec.meta
    coeff = (meta["collective_coeff_classwise"]
             if meta["collective_coeff_classwise"] is not None
             else meta["collective_coeff_aggregate"])
    print_fn(csv_line("campaign/collective_cells", meta["collective_cells"],
                      f"of {len(records)} fitted (meshes 1x1/2x1/1x2)"))
    print_fn(csv_line("campaign/collective_column_fitted",
                      float(meta["collective_column_fitted"]),
                      f"classwise_columns={len(meta['classwise_columns'])}"))
    print_fn(csv_line("campaign/collective_coeff_s_per_byte", coeff,
                      json.dumps({"aggregate":
                                  meta["collective_coeff_aggregate"]})))
    return {
        "collective_cells": meta["collective_cells"],
        "collective_column_fitted": meta["collective_column_fitted"],
        "collective_coeff": coeff,
        "collective_coeff_aggregate": meta["collective_coeff_aggregate"],
        "n_records": len(records),
    }


def _timed(fn) -> float:
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


if __name__ == "__main__":
    out = run()
    planner_bench()
    print(f"\nbatched speedup: {out['speedup']:.1f}x "
          f"(target >=5x on {POPULATION} candidates)")
