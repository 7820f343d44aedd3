"""Kernel micro-benchmarks: static roofline stats per Pallas kernel config
(FLOPs, HBM bytes, arithmetic intensity, VMEM working set), CPU oracle
wall-time as a correctness-path sanity check, and — per kernel — the
autotuner's pick vs the hand-coded default under the same roofline model
(tuned modelled time must never be worse: the default is always in the
candidate set).

Wall-clock of interpret-mode Pallas is meaningless (Python interpreter), so
the perf numbers reported are the *structural* ones the TPU roofline uses.

    PYTHONPATH=src python -m benchmarks.kernel_bench
"""

from __future__ import annotations

import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.engine.devices import get_device
from repro.kernels.autotune import KernelTuner
from repro.kernels.conv_mm import tiling as conv_tiling
from repro.kernels.conv_mm.ref import conv_ref
from repro.kernels.flash_attention import tiling as flash_tiling
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.moe_dispatch import tiling as moe_tiling
from repro.kernels.paged_decode import tiling as pd_tiling
from repro.kernels.serve_kv import tiling as kv_tiling
from repro.kernels.ssm_scan import tiling as ssm_tiling
from repro.kernels.ssm_scan.ref import ssd_ref

from .common import csv_line

TUNING_CACHE = "/tmp/perf4sight_kernel_bench_tuning.json"


def _time(fn, *args, n=3):
    fn(*args)  # compile
    ts = []
    for _ in range(n):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts)) * 1e6


def _fmt(config: dict) -> str:
    return " ".join(f"{k}={v}" for k, v in sorted(config.items()))


def _tuned_rows(tuner: KernelTuner, kernel: str, shape: dict, print_fn) -> dict:
    """Emit model_default_us / model_tuned_us rows for one kernel shape."""
    entry = tuner.explain(kernel, shape)
    default_us = entry["default_model_us"]
    tuned_us = entry["model_us"]   # modelled time of the chosen config
    speedup = default_us / max(tuned_us, 1e-12)
    print_fn(csv_line(f"kernel/{kernel}/model_default_us", default_us,
                      _fmt(entry["default_config"])))
    print_fn(csv_line(f"kernel/{kernel}/model_tuned_us", tuned_us,
                      f"{_fmt(entry['config'])} speedup={speedup:.2f}x "
                      f"vmem_kb={entry['vmem_kb']:.0f} "
                      f"cands={entry['candidates']} "
                      f"rejected_vmem={entry['rejected_vmem']} "
                      f"source={entry['source']}"))
    return {"default_us": default_us, "tuned_us": tuned_us,
            "speedup": speedup, "config": entry["config"]}


def run(print_fn=print) -> dict:
    v5e = get_device("tpu_v5e")
    peak, bw = v5e.peak_flops, v5e.hbm_bw
    if os.path.exists(TUNING_CACHE):
        os.unlink(TUNING_CACHE)
    tuner = KernelTuner(device=v5e, cache=TUNING_CACHE,
                        measure=False)
    results: dict = {}
    rng = np.random.default_rng(0)

    # flash attention: (B,H,S,Dh) production-ish tile
    B, H, S, Dh, bq, bk = 1, 8, 2048, 128, 512, 512
    flops = 4.0 * B * H * S * S * Dh * 0.5  # causal
    bytes_ = 2.0 * (B * H * S * Dh * 3 + B * H * S * Dh)
    vmem = (bq * Dh + 2 * bk * Dh) * 2 + bq * Dh * 4
    q = jnp.asarray(rng.standard_normal((B, H, S, Dh)), jnp.bfloat16)
    us = _time(jax.jit(lambda q: attention_ref(q, q, q, causal=True)), q)
    print_fn(csv_line("kernel/flash_attn/ref_us", us,
                      f"AI={flops / bytes_:.0f} tpu_roofline_us="
                      f"{max(flops / peak, bytes_ / bw) * 1e6:.1f} vmem_kb={vmem / 1024:.0f}"))
    results["flash_attention"] = _tuned_rows(
        tuner, "flash_attention",
        flash_tiling.shape_key((B, H, S, Dh), (B, H, S, Dh), causal=True,
                               dtype="bfloat16"),
        print_fn)

    # conv_mm: ResNet-ish layer
    N, HW, C, K, O = 8, 32, 128, 3, 128
    flops = 2.0 * N * HW * HW * O * K * K * C
    bytes_ = 2.0 * (N * HW * HW * C + K * K * C * O + N * HW * HW * O)
    x = jnp.asarray(rng.standard_normal((N, HW, HW, C)), jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal((K, K, C, O)), jnp.bfloat16)
    us = _time(jax.jit(lambda x, w: conv_ref(x, w, stride=1, padding=1)), x, w)
    print_fn(csv_line("kernel/conv_mm/ref_us", us,
                      f"AI={flops / bytes_:.0f} tpu_roofline_us="
                      f"{max(flops / peak, bytes_ / bw) * 1e6:.1f}"))
    results["conv_mm"] = _tuned_rows(
        tuner, "conv_mm",
        conv_tiling.shape_key((N, HW, HW, C), (K, K, C, O), stride=1,
                              padding=1, dtype="bfloat16"),
        print_fn)

    # ssd: mamba2-780m layer tile
    B2, S2, Hh, P, Nst, ch = 1, 2048, 24, 64, 128, 128
    flops = 2.0 * B2 * S2 * ch * Hh * (Nst + P) + 2.0 * B2 * S2 * Hh * P * Nst
    bytes_ = 2.0 * B2 * S2 * Hh * P * 2 + 4.0 * B2 * S2 * Hh
    xh = jnp.asarray(rng.standard_normal((B2, S2, Hh, P)), jnp.float32)
    a = -jnp.abs(jnp.asarray(rng.standard_normal((B2, S2, Hh)), jnp.float32)) * 0.1
    Bm = jnp.asarray(rng.standard_normal((B2, S2, Nst)), jnp.float32)
    us = _time(jax.jit(lambda xh, a, Bm: ssd_ref(xh, a, Bm, Bm, chunk=ch)[0]),
               xh, a, Bm)
    print_fn(csv_line("kernel/ssd/ref_us", us,
                      f"AI={flops / bytes_:.0f} tpu_roofline_us="
                      f"{max(flops / peak, bytes_ / bw) * 1e6:.1f}"))
    results["ssm_scan"] = _tuned_rows(
        tuner, "ssm_scan",
        ssm_tiling.shape_key((B2, S2, Hh, P), Nst, dtype="float32"),
        print_fn)

    # moe dispatch: qwen3-moe-30b-ish layer (groups × capacity factor knobs;
    # XLA-lowered, so only the model rows — there is no standalone oracle)
    Bm_, Sm_, Dm_, Em_, Km_, Fm_ = 8, 2048, 2048, 128, 8, 768
    moe_shape = moe_tiling.shape_key(Bm_, Sm_, Dm_, Em_, Km_, Fm_, 1.25,
                                     "bfloat16")
    results["moe_dispatch"] = _tuned_rows(tuner, "moe_dispatch", moe_shape,
                                          print_fn)

    # paged decode: serving hot path — 8 slots, long KV, GQA, paged pool.
    # The gather baseline is the XLA fallback at the same shape, priced by
    # the same roofline; tuned kernel must never be slower (it touches only
    # live blocks where the gather streams the whole logical view).  The
    # pool block size matches what serve_kv's joint model resolves for
    # this window (asserted below) — small pool blocks would drown the
    # win in per-block grid-step overhead, which is exactly why the two
    # are resolved jointly.
    Bp, Hp, Hkvp, Dhp, NBp, bsp = 8, 32, 8, 128, 16, 256
    pd_shape = pd_tiling.shape_key(Bp, Hp, Hkvp, Dhp, NBp, bsp, "bfloat16")
    results["paged_decode"] = _tuned_rows(tuner, "paged_decode", pd_shape,
                                          print_fn)
    from repro.kernels.autotune import roofline_seconds
    gather_us = roofline_seconds(pd_tiling.gather_cost(pd_shape), v5e) * 1e6
    results["paged_decode"]["gather_us"] = gather_us
    results["paged_decode"]["vs_gather"] = (
        gather_us / max(results["paged_decode"]["tuned_us"], 1e-12))
    print_fn(csv_line("kernel/paged_decode/model_gather_us", gather_us,
                      f"vs_tuned={results['paged_decode']['vs_gather']:.2f}x "
                      f"(full {NBp * bsp}-token logical view, no early exit)"))

    # serve_kv ⇄ paged_decode joint resolution: the pool block size the
    # serve_kv model picks must admit the kernel's tuned block_kv as a
    # divisor (structural — candidates snap to the pool block).
    kv_shape = kv_tiling.shape_key(Bp, NBp * bsp, Hkvp, Dhp, "bfloat16",
                                   n_heads=Hp)
    kv_bs = int(tuner.tune("serve_kv", kv_shape)["block_size"])
    pd_joint_shape = pd_tiling.shape_key(
        Bp, Hp, Hkvp, Dhp, -(-NBp * bsp // kv_bs), kv_bs, "bfloat16")
    kv_bkv = int(tuner.tune("paged_decode", pd_joint_shape)["block_kv"])
    results["serve_kv_joint"] = {
        "block_size": kv_bs, "block_kv": kv_bkv,
        "aligned": kv_bs % kv_bkv == 0,
    }
    print_fn(csv_line("kernel/serve_kv/joint_block_size", kv_bs,
                      f"paged_decode_block_kv={kv_bkv} "
                      f"aligned={kv_bs % kv_bkv == 0}"))

    # second visit to the whole grid must be pure cache hits (no re-search)
    h0, m0 = tuner.hits, tuner.misses
    for kernel, shape in (
        ("flash_attention", flash_tiling.shape_key(
            (B, H, S, Dh), (B, H, S, Dh), causal=True, dtype="bfloat16")),
        ("conv_mm", conv_tiling.shape_key(
            (N, HW, HW, C), (K, K, C, O), stride=1, padding=1,
            dtype="bfloat16")),
        ("ssm_scan", ssm_tiling.shape_key(
            (B2, S2, Hh, P), Nst, dtype="float32")),
        ("moe_dispatch", moe_shape),
        ("paged_decode", pd_shape),
        ("serve_kv", kv_shape),
    ):
        tuner.tune(kernel, shape)
    results["second_call_hits"] = tuner.hits - h0
    results["second_call_misses"] = tuner.misses - m0
    print_fn(csv_line("kernel/autotune/second_call_hits",
                      results["second_call_hits"],
                      f"misses={results['second_call_misses']} expect=6/0"))
    return results


if __name__ == "__main__":
    run()
