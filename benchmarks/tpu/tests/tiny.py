"""A small copy of the benchmark for tests on the CPU: the real harness
files, the real ``BENCHMARK.json`` with tiny cells added, and tiny
configurations of InternLM2 and Qwen3 (the ``transformer`` family), in a
checkout of its own whose
``src`` is the program's."""

from __future__ import annotations

import json
import os
import shutil

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
CHECKOUT = os.path.dirname(os.path.dirname(BENCH))

SMALL = dict(hidden_size=64, intermediate_size=128, num_attention_heads=4,
             num_key_value_heads=2, head_dim=16, num_hidden_layers=2,
             vocab_size=512, torch_dtype="bfloat16", attention_bias=False)

CONFIGS = {
    "tiny-internlm2": dict(SMALL, name="tiny-internlm2", arch="internlm2-1.8b",
                           family="transformer", rms_norm_eps=1e-6, rope_theta=1e6,
                           tie_word_embeddings=False, qk_norm=False),
    "tiny-qwen3": dict(SMALL, name="tiny-qwen3", arch="qwen3-4b",
                       family="transformer", rms_norm_eps=1e-6, rope_theta=1e6,
                       tie_word_embeddings=True, qk_norm=True),
}

ENGINE = {"n_slots": 4, "max_len": 256, "pool_tokens": 1024,
          "prefill_chunk": 32}

WORKLOADS = {
    "tiny.decode": {
        "config": "tiny-internlm2", "traffic": "decode", "driver": "closed_loop",
        "engine": ENGINE,
        "mix": {"clients": 4, "per_client": 40,
                "prompt": {"dist": "uniform", "lo": 40, "hi": 80},
                "output": {"dist": "uniform", "lo": 8, "hi": 16}},
        "check": {"max_logit_gap": 0.05, "min_served_tokens": 40,
                  "max_requests": 4},
        "e2e": ["output_tokens_per_s"]},
    "tiny.chat": {
        "config": "tiny-qwen3", "traffic": "chat", "driver": "open_loop",
        "engine": ENGINE, "drain_s": 60,
        "mix": {"rate_per_s": 4.0, "block_s": 1.0, "pattern": 7,
                "prompt": {"dist": "lognormal", "median": 40, "sigma": 0.8,
                           "lo": 8, "hi": 120},
                "output": {"dist": "lognormal", "median": 8, "sigma": 0.5,
                           "lo": 2, "hi": 24}},
        "check": {"max_logit_gap": 0.05, "min_served_tokens": 40,
                  "max_requests": 4},
        "e2e": ["ttft_p95_ms", "tpot_p95_ms"]},
}


def real_workload(cell: str) -> dict:
    """A real cell's file."""
    with open(os.path.join(BENCH, "workloads", f"{cell}.json")) as f:
        return json.load(f)


def build(dest: str, workloads=WORKLOADS) -> str:
    """A checkout at ``dest`` holding the benchmark with the tiny cells;
    returns the benchmark's directory there."""
    root = os.path.join(dest, "benchmarks", "tpu")
    shutil.copytree(BENCH, root, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    os.symlink(os.path.join(CHECKOUT, "src"), os.path.join(dest, "src"))
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for name, cfg in CONFIGS.items():
        with open(os.path.join(root, "configs", f"{name}.json"), "w") as f:
            json.dump(cfg, f)
    for name, wl in workloads.items():
        wl = dict(wl, name=name, chips=1, why="test")
        e2e = wl.pop("e2e")
        with open(os.path.join(root, "workloads", f"{name}.json"), "w") as f:
            json.dump(wl, f)
        for m in bench["end_to_end"]:
            if m["name"] in e2e:
                m["workloads"].append(name)
        for m in bench["per_layer"]:
            if m["moves"] in e2e:
                m["workloads"].append(name)
    with open(os.path.join(dest, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root
