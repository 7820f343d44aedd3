"""Suite-wide isolation: the kernel autotuner's implicit lookups (model
tracing, ops wrappers) must never write to the checkout's tuning cache
(``.cache/kernel_tuning.json``) from tests.  Redirect the default cache file to a
per-session scratch path before any tuner is created."""

import os
import tempfile

os.environ.setdefault(
    "REPRO_TUNING_CACHE",
    os.path.join(tempfile.mkdtemp(prefix="repro-test-tuning-"),
                 "kernel_tuning.json"),
)
