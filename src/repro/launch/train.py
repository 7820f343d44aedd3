"""Training launcher with perf4sight admission control.

    PYTHONPATH=src python -m repro.launch.train --arch internlm2-1.8b \
        --reduced --steps 50 --ckpt-dir /tmp/ck

Before building the jitted step, the launcher asks the unified cost engine
(``repro.engine``) for the training-step footprint — the AnalyticalBackend's
AOT ``lower().compile()`` + trip-count-aware HLO roofline, no execution —
and refuses jobs over the budget: the paper's §6.4 safety property.
Estimates are cached on disk (``--estimate-cache``), so re-launching the
same cell readmits instantly without recompiling.
"""

from __future__ import annotations

import argparse
import json

from repro.configs.base import ShapeSpec
from repro.configs.registry import ARCH_IDS, get_config
from repro.core.cache_dirs import use_compile_cache
from repro.optim.optimizer import OptimizerConfig
from repro.train.trainer import Trainer, TrainerConfig


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=ARCH_IDS)
    ap.add_argument("--reduced", action="store_true",
                    help="smoke-scale same-family config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--grad-compression", type=float, default=None)
    ap.add_argument("--device", default=None,
                    help="device registry name (host_cpu, tx2_like, tpu_v5e) "
                         "or path to a calibrated DeviceSpec (.json/.npz) — "
                         "sets the admission roofline constants and, absent "
                         "--memory-budget-gb, the memory capacity budget")
    ap.add_argument("--memory-budget-gb", type=float, default=None,
                    help="admission gate: refuse if predicted HBM (inflated "
                         "by --admission-margin) exceeds this; defaults to "
                         "the --device capacity when a device is given")
    ap.add_argument("--energy-budget-j", type=float, default=None,
                    help="admission gate: refuse if the predicted step "
                         "energy (inflated by --admission-margin) exceeds "
                         "this many joules — the edge power/thermal "
                         "envelope check")
    ap.add_argument("--admission-margin", type=float, default=0.1,
                    help="safety margin applied to the predicted footprint "
                         "before comparing to the budget (0 = exact)")
    ap.add_argument("--estimate-cache", default=None,
                    help="JSON path for the engine's on-disk estimate cache")
    ap.add_argument("--lm-forest", default=None,
                    help="campaign-fitted LM forest (.npz/.json from "
                         "`python -m repro.campaign fit`): admission is then "
                         "answered by the forest with zero compiles, falling "
                         "back to the analytical AOT path only for cells the "
                         "forest cannot answer")
    ap.add_argument("--auto-mesh", type=int, default=None, metavar="N",
                    help="let the auto-sharding planner (repro.planner) pick "
                         "the cheapest data×model layout of N devices for "
                         "this cell (max_pipe=1: the trainer has no pipeline "
                         "schedule); builds the winning mesh when N devices "
                         "are visible, otherwise reports the plan and trains "
                         "unsharded")
    ap.add_argument("--n-micro", type=int, default=8,
                    help="microbatches per step assumed by the planner's "
                         "pipeline-bubble model")
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_config(args.arch, reduced=args.reduced)
    shape = ShapeSpec("cli", args.seq, args.batch, "train")

    admission = None
    plan = None
    if (args.memory_budget_gb is not None or args.device is not None
            or args.lm_forest is not None or args.energy_budget_j is not None
            or args.auto_mesh is not None):
        from repro.engine import (
            AnalyticalBackend,
            CostEngine,
            CostQuery,
            EnsembleBackend,
            ForestBackend,
            resolve_device,
        )

        device = resolve_device(args.device) if args.device else None
        chain = []
        if args.lm_forest:
            from repro.campaign import LMForest

            chain.append(ForestBackend(lm=LMForest.load(args.lm_forest)))
        chain.append(AnalyticalBackend(reduced=args.reduced, lm_device=device))
        engine = CostEngine(
            EnsembleBackend(chain),
            cache=args.estimate_cache,
            device=device,
        )

        if args.auto_mesh is not None:
            from repro.planner import LayoutPlanner

            plan = LayoutPlanner(engine, reduced=args.reduced).plan(
                args.arch, shape, args.auto_mesh,
                max_pipe=1, n_micro=args.n_micro)
            print(plan.table(top=5))
            if plan.chosen is None:
                raise RuntimeError(
                    f"auto-mesh: no runnable layout of {args.auto_mesh} "
                    f"devices for {args.arch} × {shape.name}; refused: "
                    + "; ".join(f"{r.layout.descriptor}: {r.reason}"
                                for r in plan.refused))

        def admission(cfg, shape):
            ok, info = engine.admit(
                CostQuery(arch=args.arch, bs=shape.global_batch,
                          seq=shape.seq_len, stage="train",
                          reduced=args.reduced),
                gamma_budget_mb=(args.memory_budget_gb * 1e3
                                 if args.memory_budget_gb is not None else None),
                energy_budget_j=args.energy_budget_j,
                safety_margin=args.admission_margin,
            )
            info["predicted_gb"] = info["gamma_mb"] / 1e3
            info["predicted_energy_j"] = info["energy_j"]
            if device is not None:
                info["device"] = device.name
            if plan is not None and plan.chosen is not None:
                # The planner-selected layout's predicted costs, reported
                # at admission time alongside the single-device gate.
                c = plan.chosen
                info["auto_mesh"] = {
                    "layout": c.layout.descriptor,
                    "phi_ms": c.phi_ms,
                    "gamma_mb": c.gamma_mb,
                    "energy_j": c.energy_j,
                }
            return ok, info

    # Pre-tune kernel block sizes for this cell (abstract trace, no
    # compile): the jitted step then reads every block size from the
    # device-keyed tuning cache instead of the hand-picked constants.
    from repro.models.transformer import warm_autotune

    warm = warm_autotune(cfg, batch_size=args.batch, seq_len=args.seq,
                         stages=("train",))
    if warm["misses"]:
        print(f"autotune: {warm['misses']} kernel configs tuned "
              f"({warm['hits']} cached)")

    # Build the planner's winning mesh when the host actually has the
    # devices; a short host still gets the full plan report above (the
    # structured MeshSpecError names the deficit if forced).
    mesh = None
    if plan is not None and plan.chosen is not None:
        import jax

        from repro.launch.mesh import make_mesh

        chosen = plan.chosen.layout
        if len(jax.devices()) >= chosen.n_devices:
            mesh = make_mesh(chosen.mesh_shape, chosen.mesh_axes)
            print(f"auto-mesh: built {chosen.descriptor} "
                  f"({chosen.data}-way data × {chosen.model}-way model)")
        else:
            print(f"auto-mesh: {chosen.descriptor} needs "
                  f"{chosen.n_devices} devices, host has "
                  f"{len(jax.devices())} — plan reported, training unsharded")

    opt = OptimizerConfig(kind="adamw", lr=args.lr, warmup_steps=10,
                          total_steps=max(args.steps, 100))
    tcfg = TrainerConfig(ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
                         grad_compression=args.grad_compression)
    trainer = Trainer(cfg, shape, opt, tcfg, mesh=mesh, admission=admission)
    out = trainer.train(args.steps)
    h = out["history"]
    print(json.dumps({
        "arch": cfg.name,
        "steps": len(h),
        "first_loss": h[0]["loss"] if h else None,
        "last_loss": h[-1]["loss"] if h else None,
        "mean_step_ms": sum(r["dt"] for r in h) / max(len(h), 1) * 1e3,
        "stragglers": len(out["stragglers"]),
    }, indent=2))


if __name__ == "__main__":
    main()
