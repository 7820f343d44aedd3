"""Run one benchmark cell once on the chip and print its result.

    python3 benchmarks/tpu/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

The cell is ``workloads/<cell>.json``; its configuration, driver and
per-layer metric readers are found by name (``cells.py``).  The run
fails, printing no result, when JAX finds no TPU or fewer chips than the
cell asks for.  ``--trace 0`` reports the cell's end-to-end metrics;
``--trace 1`` measures a window of at most ``harness.TRACE_SECONDS``
under the profiler and reports its per-layer metrics.  Either way the
run checks what the timed path produced against the plain reference and
prints each number compared beside its limit, as the last lines of
standard error and under ``checks`` in the result.  The result is the
last line of standard output, one JSON object.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
# libtpu otherwise writes its logs to a fixed path under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")


class NoChip(RuntimeError):
    pass


def _paths(root: str) -> str:
    """Put the benchmark and the program's source on the path; return the
    checkout."""
    sys.path.insert(0, root)
    import cells

    checkout = cells._checkout(root)
    src = os.path.join(checkout, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise FileNotFoundError(f"no program source at {src}")
    sys.path.insert(0, src)
    return checkout


def _caches(checkout: str) -> str:
    """JAX's compilation cache and the kernel tuner's cache at fixed paths
    in the checkout (or where ``JAX_COMPILATION_CACHE_DIR`` says)."""
    os.environ["REPRO_TUNING_CACHE"] = os.path.join(
        checkout, ".cache", "kernel_tuning.json")
    import jax
    from repro.core.cache_dirs import use_compile_cache

    where = use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return where


def _device(chips: int, allow_cpu: bool):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" and not allow_cpu:
        raise NoChip(f"no TPU: JAX found {devs[0].platform} "
                     f"({devs[0].device_kind})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs[0], len(devs)


def run_cell(argv=None, *, root: str = HERE, allow_cpu: bool = False,
             t_process: float = T_PROCESS) -> dict:
    """Everything ``main`` does but print the result; returns it."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    checkout = _paths(root)
    import cells
    from harness import Run, say

    cell = cells.load_cell(args.workload, root)
    dev, count = _device(cell.workload["chips"], allow_cpu)
    say("device", {"platform": dev.platform, "device_kind": dev.device_kind,
                   "count": count})
    say("compile_cache", _caches(checkout))
    out_dir = os.path.join(checkout, ".bench_run")
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    run = Run(cell=cell, seed=args.seed, seconds=args.seconds,
              trace=bool(args.trace), t_process=t_process, out_dir=out_dir,
              program=cells.program_config(cell.config),
              peaks=cells.peaks(dev.device_kind, root)
              if dev.platform == "tpu" else {"peak_flops_bf16": 1.0,
                                             "hbm_bytes_per_s": 1.0},
              device=dev)
    cells.load_module(root, "drivers", cell.driver).run(run)
    _tiles()

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": count,
              "memory_peak_bytes": run.counters["memory_peak_bytes"]}
    result = {"correct": all(v <= lim for _, v, lim in run.checks)
              and bool(run.checks),
              "attempted": run.attempted, "failed": run.failed}
    if run.trace:
        import trace_reduce

        run.trace_summary = trace_reduce.load(run.trace_dir)
        device["busy_s"] = run.trace_summary.busy_s()
        device["window_s"] = run.trace_summary.window_s
        metrics = {}
        for m in cell.per_layer:
            v = cells.metric_reader(root, m["name"]).read(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        result["metrics"] = metrics
        result["device"] = device
        result["breakdown"] = run.trace_summary.breakdown()
        shutil.rmtree(run.trace_dir, ignore_errors=True)
    else:
        result["metrics"] = {m["name"]: {"value": run.e2e[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end}
        result["device"] = device
    for name, m in result["metrics"].items():
        if not math.isfinite(m["value"]):
            raise ValueError(f"{name} is {m['value']}")
    result["checks"] = {n: {"value": v, "limit": lim}
                        for n, v, lim in run.checks}
    return result


def _tiles() -> None:
    """The kernel tuner's chosen tiles, on an information line."""
    from harness import say
    from repro.kernels.autotune import get_tuner

    cache = get_tuner().cache
    say("tuned_tiles", [
        {"kernel": e["kernel"], "shape": e["shape"], "config": e["config"],
         "source": e["source"], "measured_us": e.get("measured_us")}
        for e in (cache.entries() if cache is not None else [])])


def main(argv=None) -> int:
    try:
        result = run_cell(argv)
    except NoChip as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 3
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
