"""Benchmark driver — one function per paper table/figure.

Prints ``name,value,derived`` CSV lines.  All CNN benchmarks read the
profiling cache (populated by ``benchmarks.collect_cnn_data``; missing points
are profiled lazily).  The roofline table reads the dry-run JSONL.

    PYTHONPATH=src python -m benchmarks.run [--skip-slow]
"""

from __future__ import annotations

import argparse
import sys
import time
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated benchmark names")
    ap.add_argument("--skip-slow", action="store_true",
                    help="skip benches that may profile new configs")
    args = ap.parse_args()

    from repro.core.cache_dirs import use_compile_cache

    use_compile_cache()
    from . import (dnnmem_comparison, engine_bench, fig3_same_network,
                   fig4_basis, kernel_bench, roofline_table,
                   strategy_variation, table2_case_study, trainset_sweep)

    benches = {
        "fig3": fig3_same_network.run,            # Fig. 3
        "fig4": fig4_basis.run,                   # Fig. 4
        "trainset": trainset_sweep.run,           # §6.1
        "dnnmem": dnnmem_comparison.run,          # §6.2.1
        "strategies": strategy_variation.run,     # §6.2 (100 strategies)
        "table2": table2_case_study.run,          # Table 2 / §6.4
        "roofline": roofline_table.run,           # §Roofline (beyond paper)
        "kernels": kernel_bench.run,              # kernel μ-bench
        "engine": engine_bench.run,               # batched CostBackend API
    }
    slow = {"strategies", "table2"}
    selected = (args.only.split(",") if args.only else list(benches))

    failures = []
    for name in selected:
        if args.skip_slow and name in slow:
            continue
        print(f"\n=== {name} ===", flush=True)
        t0 = time.time()
        try:
            benches[name]()
        except Exception:
            traceback.print_exc()
            failures.append(name)
        print(f"=== {name} done in {time.time() - t0:.1f}s ===", flush=True)

    if failures:
        print(f"\nFAILED benchmarks: {failures}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
