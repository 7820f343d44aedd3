"""The control — the plain reference computed in float8, the precision
below the configuration's bfloat16, put in the program's place — must
fail each cell's check at a size a test run holds.  The same readings
at the cells' own sizes on the chip set the limits (``calibrate.py``)."""


import numpy as np
import pytest

import cells
import tiny
import weights


def _limits(cell):
    return tiny.real_workload(cell)["check"]


@pytest.mark.parametrize("cell,config", [
    ("qwen3-4b.decode_long", "tiny-internlm2"),
    ("qwen3-4b.chat_poisson", "tiny-qwen3")])
def test_serving_control_fails(cell, config):
    cfg = tiny.CONFIGS[config]
    R = cells.family(cfg).reference
    params = weights.make(cfg, 2**31 + 17)
    rng = np.random.default_rng(17)
    worst = 0.0
    for _ in range(3):
        tok = rng.integers(0, cfg["vocab_size"], 240).astype(np.int32)
        _, _, pick = R.served_gaps(cfg, "fp8", params, tok[:80], tok[80:])
        _, gap, _ = R.served_gaps(cfg, "float32", params, tok[:80],
                                  tok[80:], pick)
        worst = max(worst, float(gap.max()))
    assert worst > _limits(cell)["max_logit_gap"]
