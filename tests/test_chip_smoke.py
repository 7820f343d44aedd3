"""chip_smoke.py refuses to run anywhere but on a TPU: its device check
raises, and the script exits non-zero without printing the ok line.
Its compile cache (and every entry point's) follows
``JAX_COMPILATION_CACHE_DIR`` when set, else a fixed checkout path."""

import importlib.util
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


def _load():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_require_tpu_raises_without_tpu():
    with pytest.raises(RuntimeError, match="needs a TPU"):
        _load().require_tpu()


def test_script_fails_without_tpu_and_prints_no_ok_line():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, SCRIPT], capture_output=True,
                       text=True, env=env, timeout=300, cwd=ROOT)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "needs a TPU" in r.stderr


def test_compile_cache_env_wins_else_fixed_checkout_dir(monkeypatch):
    import jax

    from repro.core import cache_dirs
    from repro.kernels import autotune

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/jax")
    assert cache_dirs.use_compile_cache() == "/elsewhere/jax"
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        assert cache_dirs.use_compile_cache() == os.path.join(ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == cache_dirs.JAX_CACHE_DIR
    finally:
        jax.config.update("jax_compilation_cache_dir", before)

    monkeypatch.delenv("REPRO_TUNING_CACHE", raising=False)
    assert autotune._default_cache_path() == os.path.join(
        ROOT, ".cache", "kernel_tuning.json")
