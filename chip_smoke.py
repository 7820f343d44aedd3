#!/usr/bin/env python3
"""Smoke test of the main path on one TPU v5e.

    python3 chip_smoke.py              # one chip: serve, train, cnn phases
    python3 chip_smoke.py --chips 4    # train on a 4-way data mesh vs 1 chip

One process drives every phase, so it alone holds the chip.  It first
checks that JAX sees a TPU and fails otherwise: no phase falls back to
the CPU.

* serve — ``ContinuousEngine`` serves ``internlm2-1.8b`` at its published
  widths (random weights from ``--seed``): 8 slots, chunked prefill of
  256 tokens, 8 greedy requests with 128 to 1024 prompt tokens and 32 new
  tokens each.  The compiled decode program must hold the ``paged_decode``
  Pallas kernel (``tpu_custom_call``), and the kernel must match
  ``paged_decode_ref`` at the serving shapes.
* train — ``Trainer`` takes 5 AdamW steps on ``internlm2-1.8b`` at its
  published widths and full vocabulary, seq 2048, cut only in depth to
  what fits one chip.  Losses must be finite.
* cnn — ``core.profiler.profile_training`` on ResNet-50 at 32x32, batch
  64: the paper's Γ (compiled memory plan) and Φ (step latency).

With ``--chips 4`` only the train phase runs, once unsharded on one chip
and once on a 4-way data mesh, from the same seed; the losses must agree
within bf16 tolerance, the batch must be split over 4 devices, and every
device must report a nonzero peak.

Each phase prints one line of facts.  The last line of standard output is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``,
printed only when every check passed.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(HERE, "src")

ARCH = "internlm2-1.8b"
# Deepest internlm2-1.8b train step (AdamW, batch 4, seq 2048) whose
# compiled memory_analysis() for one v5e fits 16 GB: 15.44 GB at 9
# layers, 16.40 GB at 10.  All 24 layers would need about 1.89B x 12 B
# of parameters, gradients and AdamW slots alone.
TRAIN_LAYERS = 9
TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = 4, 2048, 5
# Multiples of 128: every chunk of a 256-token chunked prefill is 128 or
# 256 wide, which bounds the number of distinct programs to compile.
SERVE_PROMPTS = (128, 256, 384, 512, 640, 768, 896, 1024)
SERVE_NEW_TOKENS = 32
KERNEL_TOL = 3e-2          # bf16 inputs, f32 accumulation
LOSS_RTOL = 2e-2           # bf16 training step, sharded vs unsharded


def require_tpu():
    """The first device JAX sees, which must be a TPU."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise RuntimeError(
            f"chip_smoke needs a TPU; JAX found platform {dev.platform!r} "
            f"({dev.device_kind})")
    return dev


def _use_repo_source() -> None:
    if not os.path.isdir(os.path.join(SRC, "repro")):
        raise RuntimeError(f"no repository source at {SRC}")
    sys.path.insert(0, SRC)


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def _emit(phase: str, facts: dict) -> None:
    print(f"{phase} {json.dumps(facts)}", flush=True)


def _memory(dev) -> dict:
    stats = dev.memory_stats() or {}
    return {k: stats.get(k) for k in ("peak_bytes_in_use", "bytes_in_use",
                                      "largest_alloc_size", "bytes_limit")}


# ---------------------------------------------------------------------------
# serve


def serve_phase(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.registry import get_config
    from repro.kernels.paged_decode import paged_decode_attention, paged_decode_ref
    from repro.models import transformer as T
    from repro.serve import ContinuousConfig, ContinuousEngine, Request

    cfg = get_config(ARCH)
    t0 = time.perf_counter()
    params = jax.block_until_ready(
        jax.tree.map(jnp.asarray, T.init_params(cfg, seed)))
    init_s = time.perf_counter() - t0

    engine = ContinuousEngine(cfg, params, ContinuousConfig(
        max_len=2048, n_slots=8, prefill_chunk=256, eos_id=-1, seed=seed))
    rng = np.random.default_rng(seed)
    requests = [Request(prompt=rng.integers(2, cfg.vocab, n).astype(np.int32),
                        max_new_tokens=SERVE_NEW_TOKENS)
                for n in SERVE_PROMPTS]
    t0 = time.perf_counter()
    engine.run(requests)
    run_s = time.perf_counter() - t0
    m = engine.metrics()
    _check(m["finished"] == len(requests) and m["lost"] == 0,
           f"serve: {m['finished']} of {len(requests)} requests finished")
    for r in requests:
        _check(r.n_generated == SERVE_NEW_TOKENS
               and all(0 <= t < cfg.vocab for t in r.tokens),
               f"serve: request {r.rid} tokens {r.tokens}")

    # The compiled decode program of the widest table the run used.
    nb = max(engine._decodes)
    B = engine.scfg.n_slots
    batch = {"tokens": jnp.zeros((B, 1), jnp.int32),
             "cache_len": jnp.zeros((B,), jnp.int32),
             "block_table": jnp.zeros((B, nb), jnp.int32)}
    hlo = engine._decode_fn(nb).lower(params, engine.kv.pool, batch) \
        .compile().as_text()
    n_kernel = hlo.count("tpu_custom_call")
    _check(n_kernel > 0, "serve: no Pallas kernel in the decode program")

    # paged_decode_attention (the kernel, on TPU) against the jnp ref at
    # the serving shapes: the engine's pool block and full table width.
    bs, NB = engine.kv.block_size, engine.kv.blocks_per_seq
    H, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    P = B * NB + 1
    q = jnp.asarray(rng.standard_normal((B, H, Dh)), jnp.bfloat16)
    kp = jnp.asarray(rng.standard_normal((P, Hkv, bs, Dh)), jnp.bfloat16)
    vp = jnp.asarray(rng.standard_normal((P, Hkv, bs, Dh)), jnp.bfloat16)
    bt = jnp.asarray(1 + rng.permutation(B * NB).reshape(B, NB), jnp.int32)
    cl = jnp.asarray(rng.integers(0, NB * bs, B), jnp.int32)
    ker = paged_decode_attention(q, kp, vp, bt, cl).astype(jnp.float32)
    ref = paged_decode_ref(q, kp, vp, bt, cl).astype(jnp.float32)
    err = float(jnp.max(jnp.abs(ker - ref)))
    _check(math.isfinite(err) and err <= KERNEL_TOL,
           f"serve: paged_decode kernel vs ref max abs err {err}")

    _emit("serve", {
        "arch": ARCH, "n_slots": B, "prefill_chunk": engine.scfg.prefill_chunk,
        "block_size": bs, "requests": len(requests),
        "finished": m["finished"], "tokens_out": m["tokens_out"],
        "decode_steps": m["decode_steps"],
        "prefill_chunks": m["prefill_chunks"],
        "decode_tpu_custom_calls": n_kernel, "decode_table_width": nb,
        "paged_decode_max_abs_err": err, "param_init_s": init_s,
        "run_s": run_s, **_memory(jax.devices()[0])})
    del engine, params, requests
    gc.collect()


# ---------------------------------------------------------------------------
# train


def _train(seed: int, mesh=None):
    from repro.configs.base import ShapeSpec
    from repro.configs.registry import get_config
    from repro.train.trainer import Trainer, TrainerConfig

    full = get_config(ARCH)
    cfg = dataclasses.replace(full, n_layers=TRAIN_LAYERS)
    shape = ShapeSpec("chip_smoke", TRAIN_SEQ, TRAIN_BATCH, "train")
    trainer = Trainer(cfg, shape, tcfg=TrainerConfig(seed=seed), mesh=mesh)
    t0 = time.perf_counter()
    out = trainer.train(TRAIN_STEPS)
    wall_s = time.perf_counter() - t0
    losses = [r["loss"] for r in out["history"]]
    _check(len(losses) == TRAIN_STEPS and all(map(math.isfinite, losses)),
           f"train: losses {losses}")
    facts = {
        "arch": ARCH, "reduced": {"n_layers": [full.n_layers, cfg.n_layers]},
        "params": cfg.param_count(), "batch": TRAIN_BATCH, "seq": TRAIN_SEQ,
        "vocab": cfg.vocab, "losses": losses,
        "step_s": [r["dt"] for r in out["history"]], "wall_s": wall_s,
    }
    shardings = trainer.batch_shardings
    del out, trainer
    gc.collect()
    return cfg, shape, facts, shardings


def train_phase(seed: int) -> None:
    import jax

    _, _, facts, _ = _train(seed)
    _emit("train", {**facts, **_memory(jax.devices()[0])})


def train_mesh_phase(seed: int) -> None:
    """The train phase unsharded on one chip, then on a 4-way data mesh."""
    import jax

    from repro.data.pipeline import make_batch
    from repro.launch.mesh import make_mesh

    devs = jax.devices()
    _check(len(devs) >= 4, f"--chips 4 needs 4 devices, JAX sees {len(devs)}")
    _, _, single, _ = _train(seed)
    mesh = make_mesh((4, 1), ("data", "model"))
    cfg, shape, sharded, batch_sh = _train(seed, mesh)

    tokens = jax.device_put(make_batch(cfg, shape, 0), batch_sh)["tokens"]
    n_batch_devices = len(tokens.sharding.device_set)
    shard_rows = sorted(s.data.shape[0] for s in tokens.addressable_shards)
    _check(n_batch_devices == 4 and shard_rows == [TRAIN_BATCH // 4] * 4,
           f"train: batch spans {n_batch_devices} devices, rows {shard_rows}")
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in mesh.devices.flat]
    _check(all(p > 0 for p in peaks), f"train: device peaks {peaks}")
    rel = [abs(a - b) / abs(a)
           for a, b in zip(single["losses"], sharded["losses"])]
    _check(max(rel) <= LOSS_RTOL, f"train: sharded vs 1-chip loss rel {rel}")
    _emit("train_mesh", {
        "mesh": {"data": 4, "model": 1}, "reduced": single["reduced"],
        "losses_1chip": single["losses"], "losses_mesh": sharded["losses"],
        "max_rel_loss_diff": max(rel), "batch_devices": n_batch_devices,
        "batch_shard_rows": shard_rows, "peak_bytes_in_use": peaks,
        "step_s_1chip": single["step_s"], "step_s_mesh": sharded["step_s"]})


# ---------------------------------------------------------------------------
# cnn


def cnn_phase(seed: int) -> None:
    import jax

    from repro.core.profiler import profile_training
    from repro.models.cnn import build_resnet50

    model = build_resnet50()
    r = profile_training(model, 64, seed=seed)
    _check(math.isfinite(r.gamma_mb) and r.gamma_mb > 0
           and math.isfinite(r.phi_ms) and r.phi_ms > 0,
           f"cnn: gamma {r.gamma_mb} MB, phi {r.phi_ms} ms")
    _emit("cnn", {
        "model": model.name, "input_hw": model.input_hw, "batch": 64,
        "gamma_mb": r.gamma_mb, "phi_ms": r.phi_ms, "compile_s": r.compile_s,
        "flops": r.flops, **_memory(jax.devices()[0])})


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = require_tpu()
    _use_repo_source()
    from repro.core.cache_dirs import use_compile_cache

    import jax

    _emit("device", {"platform": dev.platform, "kind": dev.device_kind,
                     "count": len(jax.devices()),
                     "compile_cache": use_compile_cache()})
    if args.chips == 4:
        train_mesh_phase(args.seed)
    else:
        serve_phase(args.seed)
        train_phase(args.seed)
        cnn_phase(args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
