"""A whole run on the CPU at a tiny size, with the timed path broken
underneath, must come out not correct; without the fault, correct.  The
tiny cells hold the real cells' limits."""

import json
import os

import pytest

import calibrate
import tiny

REAL = {"tiny.decode": "qwen3-4b.decode_long",
        "tiny.chat": "qwen3-4b.chat_poisson"}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    wls = {}
    for name, wl in tiny.WORKLOADS.items():
        real = tiny.real_workload(REAL[name])
        limits = {k: v for k, v in real["check"].items()
                  if k in wl["check"] and k not in ("min_served_tokens",
                                                    "max_requests")}
        wls[name] = dict(wl, check=dict(wl["check"], **limits))
    return tiny.build(str(tmp_path_factory.mktemp("checkout")), wls)


def _run(root, cell, fault=None, seed=2**31 + 101):
    import run as runner

    undo = calibrate._plant(fault) if fault else None
    try:
        return runner.run_cell(["--workload", cell, "--seed", str(seed),
                                "--seconds", "2"], root=root,
                               allow_cpu=True)
    finally:
        if undo:
            undo()


@pytest.mark.parametrize("cell,fault", [
    ("tiny.decode", "stale_cache"),
    ("tiny.chat", "stale_cache"),
    ("tiny.decode", "token"),
    ("tiny.chat", "token"),
])
def test_fault_is_not_correct(root, cell, fault):
    res = _run(root, cell, fault)
    assert res["correct"] is False, res["checks"]


@pytest.mark.parametrize("cell", sorted(tiny.WORKLOADS))
def test_sound_run_is_correct(root, cell):
    res = _run(root, cell)
    assert res["correct"] is True, res["checks"]
    assert set(res) == {"correct", "attempted", "failed", "metrics",
                        "device", "checks"}
    assert list(res)[-1] == "checks"
    assert res["failed"] == 0 and res["attempted"] > 0
    assert "setup_s" in res["metrics"]
