"""The run refuses to measure anything but the chip."""

import os
import shutil
import subprocess
import sys

import tiny

RUN = os.path.join(tiny.BENCH, "run.py")


def _run(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "benchmarks", "tpu", "run.py"),
         "--workload", "qwen3-4b.decode_long", "--seed", "2147483701",
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_no_result():
    p = _run(tiny.CHECKOUT, {})
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not any(l.startswith("{") for l in p.stdout.splitlines())


def test_benchmark_alone_is_no_run(tmp_path):
    shutil.copytree(tiny.BENCH, tmp_path / "benchmarks" / "tpu",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(tiny.CHECKOUT, "BENCHMARK.json"), tmp_path)
    p = _run(str(tmp_path), {})
    assert p.returncode != 0
    assert not any(l.startswith("{") for l in p.stdout.splitlines())
