"""Find an open-loop cell's knee once, when the cell is defined: serve the
cell's traffic mix at each of several rates for a window each, on one
engine in one process, and print per rate how many requests were due,
how many were still waiting or running when the window closed, how long
the rest took to drain, and the tails; stop after the first rate past the
knee.  Not run by the benchmark.

    python3 benchmarks/tpu/sweep.py --workload <cell> --seed 1 \\
        --seconds 30 --rates 4 6 8 10
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

T_PROCESS = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run as runner  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--max-queued", type=int, default=4,
                    help="stop after the first rate that leaves more "
                         "requests than this waiting for a slot at close")
    args = ap.parse_args(argv)

    checkout = runner._paths(HERE)
    import cells
    import serving
    import traffic
    from harness import Run, nearest_rank

    cell = cells.load_cell(args.workload)
    dev, _ = runner._device(cell.workload["chips"], False)
    runner._caches(checkout)
    run = Run(cell=cell, seed=args.seed, seconds=args.seconds, trace=False,
              t_process=T_PROCESS, out_dir=checkout,
              program=cells.program_config(cell.config), peaks={},
              device=dev)
    drv = cells.load_module(HERE, "drivers", cell.driver)
    engine, _ = serving.build_engine(run)
    e, mix = cell.workload["engine"], cell.workload["mix"]
    serving.warm_up(run, engine, range(mix["prompt"]["lo"], e["max_len"]))
    for rate in args.rates:
        specs = traffic.open_loop(mix, args.seed, args.seconds,
                                  cell.config["vocab_size"], e["max_len"],
                                  rate=rate)
        out = drv.serve(engine, specs, args.seconds, 120.0,
                        cell.family.counts)
        queued = out["queued"]
        sent = out["sent"]
        ok = [r for r in sent if r.state.value == "finished"]
        ttft = [r.t_first_token - r.t_arrival for r in ok] + [math.inf] * (
            len(sent) - len(ok))
        tpot = [(r.t_finished - r.t_first_token) / max(1, r.n_generated - 1)
                for r in ok]
        print(json.dumps({
            "rate_per_s": rate, "due": len(sent), "finished": len(ok),
            "backlog_at_close": out["backlog"], "queued_at_close": queued,
            "drain_s": out["drain_s"],
            "ttft_p50_ms": nearest_rank(ttft, 0.5) * 1e3,
            "ttft_p95_ms": nearest_rank(ttft, 0.95) * 1e3,
            "tpot_p95_ms": nearest_rank(tpot, 0.95) * 1e3 if tpot else None,
            "preemptions": engine.counters["preemptions"],
            "late_ms_p95": nearest_rank(out["late"], 0.95) * 1e3}),
            flush=True)
        if queued > args.max_queued or len(ok) < len(sent):
            break       # past the knee: the queue grows through the window
    return 0


if __name__ == "__main__":
    sys.exit(main())
