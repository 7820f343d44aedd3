"""Fault-tolerant training loop.

Production behaviours, all exercised by tests:
  * auto-resume from the latest valid checkpoint (atomic dirs — a killed run
    restarts exactly),
  * periodic checkpointing with keep-N GC,
  * straggler monitor: per-step wall-time EWMA; steps slower than
    ``straggler_factor``× the EWMA are logged and counted (on a real fleet
    this feeds the scheduler's replace-node decision),
  * failure injection (``fail_at_step``) for crash/restart tests,
  * optional top-k gradient compression with error feedback across the
    slow (pod/DCI) axis,
  * perf4sight admission gate: refuse to even build the jitted step when the
    predicted per-device HBM exceeds the budget (the paper's §6.4 safety
    argument, applied to the launcher),
  * data/tensor parallelism: given a ``mesh``, the step is jitted with the
    ``distributed.sharding`` state and batch shardings, state and batches
    are placed on the mesh, and the model's activation hints see the mesh
    while the step traces.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig, ShapeSpec
from repro.data.pipeline import TokenPipeline, make_batch
from repro.distributed import sharding as sh
from repro.models import layers as L
from repro.models import transformer as T
from repro.optim.compression import compress_grads, init_error_state
from repro.optim.optimizer import OptimizerConfig, apply_updates, init_opt_state
from repro.train import checkpoint as ckpt

__all__ = ["TrainerConfig", "Trainer", "StragglerMonitor"]


@dataclass
class TrainerConfig:
    ckpt_dir: str | None = None
    ckpt_every: int = 50
    keep: int = 3
    log_every: int = 10
    straggler_factor: float = 2.0
    grad_compression: float | None = None    # top-k ratio, None = off
    fail_at_step: int | None = None          # failure injection (tests)
    seed: int = 0


class StragglerMonitor:
    """EWMA step-time tracker; flags outlier steps (straggler mitigation's
    detection half — the mitigation itself is a scheduler action)."""

    def __init__(self, factor: float = 2.0, alpha: float = 0.2):
        self.factor, self.alpha = factor, alpha
        self.ewma: float | None = None
        self.flagged: list[tuple[int, float]] = []

    def observe(self, step: int, dt: float) -> bool:
        slow = self.ewma is not None and dt > self.factor * self.ewma
        if slow:
            self.flagged.append((step, dt))
        self.ewma = dt if self.ewma is None else (
            (1 - self.alpha) * self.ewma + self.alpha * dt
        )
        return slow


class Trainer:
    def __init__(
        self,
        cfg: ArchConfig,
        shape: ShapeSpec,
        opt_cfg: OptimizerConfig | None = None,
        tcfg: TrainerConfig | None = None,
        *,
        mesh=None,
        admission=None,   # callable(cfg, shape) -> (ok, info)
    ):
        self.cfg = cfg
        self.shape = shape
        self.opt_cfg = opt_cfg or OptimizerConfig(kind="adamw", warmup_steps=10,
                                                  total_steps=1000)
        self.tcfg = tcfg or TrainerConfig()
        self.mesh = mesh
        self.monitor = StragglerMonitor(self.tcfg.straggler_factor)
        self.history: list[dict] = []

        if admission is not None:
            ok, info = admission(cfg, shape)
            if not ok:
                raise RuntimeError(f"admission denied: {info}")

        self._compression = self.tcfg.grad_compression
        # NamedSharding trees on ``mesh`` (None without one)
        self.state_shardings = self.batch_shardings = None
        if mesh is None:
            self._step_fn = jax.jit(self._make_step(), donate_argnums=(0,))
        else:
            specs = sh.state_pspecs(cfg, mesh, kind=self.opt_cfg.kind)
            if self._compression is not None:
                specs["err"] = specs["params"]
            self.state_shardings = sh.to_named(mesh, specs)
            self.batch_shardings = sh.to_named(mesh, sh.batch_pspecs(cfg, shape, mesh))
            self._step_fn = jax.jit(
                self._make_step(),
                in_shardings=(self.state_shardings, self.batch_shardings),
                out_shardings=(self.state_shardings, None),
                donate_argnums=(0,))

    # ------------------------------------------------------------------

    def _make_step(self):
        cfg, opt_cfg, ratio = self.cfg, self.opt_cfg, self._compression

        def step_fn(state, batch):
            (loss, metrics), grads = jax.value_and_grad(
                T.loss_fn, has_aux=True)(state["params"], batch, cfg)
            if ratio is not None:
                grads, err = compress_grads(grads, state["err"], ratio=ratio)
            new_params, new_opt, om = apply_updates(
                state["params"], grads, state["opt"], opt_cfg)
            out = {"params": new_params, "opt": new_opt}
            if ratio is not None:
                out["err"] = err
            return out, {"loss": loss, "ce": metrics["ce"], **om}

        return step_fn

    def _slots(self, params) -> dict:
        """Optimizer (and error-feedback) state for ``params``."""
        slots = {"opt": init_opt_state(params, self.opt_cfg)}
        if self._compression is not None:
            slots["err"] = init_error_state(params)
        return slots

    def init_state(self) -> dict:
        params = T.init_params(self.cfg, self.tcfg.seed)
        if self.state_shardings is None:
            params = jax.tree.map(jnp.asarray, params)
            return {"params": params, **self._slots(params)}
        # Place each shard straight from the host and build the slots
        # sharded, so no device ever holds the whole state.
        shard = dict(self.state_shardings)
        params = jax.device_put(params, shard.pop("params"))
        return {"params": params,
                **jax.jit(self._slots, out_shardings=shard)(params)}

    def restore_or_init(self) -> tuple[int, dict]:
        d = self.tcfg.ckpt_dir
        if d and ckpt.latest_step(d) is not None:
            template = self.init_state()
            step, state = ckpt.restore_checkpoint(d, template=template,
                                                  shardings=self.state_shardings)
            return step + 1, state
        return 0, self.init_state()

    def _run_step(self, state, batch):
        if self.mesh is None:
            return self._step_fn(state, batch)
        batch = jax.device_put(batch, self.batch_shardings)
        # The hint mesh is read while the step traces (first call only);
        # restore the caller's afterwards so other models stay unsharded.
        prev = (L._HINT_MESH, L.SP_HINT)
        L.set_hint_mesh(self.mesh)
        try:
            return self._step_fn(state, batch)
        finally:
            L.set_hint_mesh(prev[0], sp=prev[1])

    # ------------------------------------------------------------------

    def train(self, num_steps: int) -> dict:
        start, state = self.restore_or_init()
        for step in range(start, num_steps):
            if self.tcfg.fail_at_step is not None and step == self.tcfg.fail_at_step:
                raise RuntimeError(f"injected failure at step {step}")
            batch = make_batch(self.cfg, self.shape, step, self.tcfg.seed)
            t0 = time.perf_counter()
            state, metrics = self._run_step(state, batch)
            jax.block_until_ready(metrics["loss"])
            dt = time.perf_counter() - t0
            slow = self.monitor.observe(step, dt)
            rec = {"step": step, "loss": float(metrics["loss"]),
                   "ce": float(metrics["ce"]), "dt": dt, "straggler": slow}
            self.history.append(rec)
            if self.tcfg.ckpt_dir and (step + 1) % self.tcfg.ckpt_every == 0:
                ckpt.save_checkpoint(self.tcfg.ckpt_dir, step, state,
                                     keep=self.tcfg.keep)
        if self.tcfg.ckpt_dir and num_steps > start:
            ckpt.save_checkpoint(self.tcfg.ckpt_dir, num_steps - 1, state,
                                 keep=self.tcfg.keep)
        return {"state": state, "history": self.history,
                "stragglers": self.monitor.flagged}
