"""Fixed on-disk cache locations inside the checkout.

Both caches live at fixed paths in the repository checkout (listed in
``.gitignore``), never under the user's home, a temp directory or a
pid/time-stamped path: JAX's persistent compilation cache keys include
nothing that survives a moved directory, so a cache that moves never
hits.

Nothing here runs at import.  Entry points (``chip_smoke.py``,
``repro.launch.train``, ``benchmarks.run``) call
:func:`use_compile_cache` once at start-up; library code never does.
"""

from __future__ import annotations

import os

__all__ = ["CHECKOUT", "JAX_CACHE_DIR", "TUNING_CACHE_PATH",
           "use_compile_cache"]

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
JAX_CACHE_DIR = os.path.join(CHECKOUT, ".jax_cache")
TUNING_CACHE_PATH = os.path.join(CHECKOUT, ".cache", "kernel_tuning.json")


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it
    as its own setting and nothing else is set here; otherwise the cache
    goes to the checkout's ``.jax_cache/``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", JAX_CACHE_DIR)
    return JAX_CACHE_DIR
