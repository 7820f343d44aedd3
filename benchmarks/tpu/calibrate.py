"""Readings that set a cell's correctness limits, not run by the benchmark.

    python3 benchmarks/tpu/calibrate.py --workload <cell> --seconds 10 \\
        --seeds 1 2 3 ... [--control] [--fault stale_cache|token]

For each seed, in one process: a whole run of the cell (set-up, a short
window at the cell's own load, the check), printing the program's
numbers; with ``--control`` also the control's, the plain reference in
the next precision below the configuration's (float8 for bfloat16) put
in the program's place; with ``--fault`` the program with that fault
planted under the timed path.  One JSON line per seed goes to standard
output.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

T_PROCESS = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run as runner  # noqa: E402


def _serving_control(readings: dict):
    """Wrap the served-token check so that it also reads the control: at
    every position of the same sample, the gap (under the float32
    reference) of the token the float8 reference puts first."""
    import numpy as np

    import serving
    from reference import transformer as R

    check = serving.check_served

    def wrapped(run_, sent, params):
        check(run_, sent, params)
        worst = 0.0
        for r in readings.pop("sample"):
            served = np.asarray(r.tokens, np.int32)
            _, _, pick = R.served_gaps(run_.cfg, "fp8", params, r.prompt,
                                       served)
            _, g, _ = R.served_gaps(run_.cfg, "float32", params, r.prompt,
                                    served, pick)
            worst = max(worst, float(g.max()))
        readings["control"] = {"max_logit_gap": worst}

    return wrapped


def _sample_spy(readings: dict):
    import serving
    from reference import transformer as R

    gaps = R.served_gaps

    def spy(cfg, quant, params, prompt, served, other=None):
        if quant == "float32" and other is None:
            readings.setdefault("sample", []).append(
                type("Req", (), {"prompt": prompt, "tokens": served}))
        return gaps(cfg, quant, params, prompt, served, other)

    return spy


def _plant(fault: str):
    """Break the timed path underneath: returns an undo function.

    ``stale_cache``: every decode and chunk step hands back the KV pool it
    was given (a step that returns its state unchanged); ``token``: one
    sampled token in seven altered where it is produced."""
    from repro.models import transformer as T
    from repro.serve import continuous as C

    if fault == "stale_cache":
        step = T.decode_step

        def stale(params, cache, batch, cfg):
            return step(params, cache, batch, cfg)[0], cache

        T.decode_step = stale
        return lambda: setattr(T, "decode_step", step)
    if fault == "token":
        init = C.ContinuousEngine.__init__

        def altered(self, *a, **kw):
            init(self, *a, **kw)
            sample, vocab = self._sample, self.cfg.vocab

            def bump(logits, key):
                tok = sample(logits, key)
                return (tok + (tok % 7 == 0)) % vocab   # one token in seven
            self._sample = bump

        C.ContinuousEngine.__init__ = altered
        return lambda: setattr(C.ContinuousEngine, "__init__", init)
    raise ValueError(f"unknown fault {fault!r}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--fault")
    args = ap.parse_args(argv)

    undo = None
    for seed in args.seeds:
        t0 = time.perf_counter()
        readings: dict = {}
        patches = []
        if args.fault and undo is None:
            runner._paths(HERE)
            undo = _plant(args.fault)
        if args.control:
            runner._paths(HERE)
            import serving
            from reference import transformer as R

            patches.append((serving, "check_served", serving.check_served))
            patches.append((R, "served_gaps", R.served_gaps))
            R.served_gaps = _sample_spy(readings)
            serving.check_served = _serving_control(readings)
        res = runner.run_cell(
            ["--workload", args.workload, "--seed", str(seed), "--seconds",
             str(args.seconds)], t_process=time.perf_counter())
        for mod, name, val in patches:
            setattr(mod, name, val)
        line = {"workload": args.workload, "seed": seed,
                "fault": args.fault, "correct": res["correct"],
                "checks": {k: v["value"] for k, v in res["checks"].items()},
                "control": readings.get("control"),
                "metrics": {k: v["value"] for k, v in res["metrics"].items()},
                "seconds": time.perf_counter() - t0}
        print(json.dumps(line), flush=True)
    if undo:
        undo()
    return 0


if __name__ == "__main__":
    sys.exit(main())
