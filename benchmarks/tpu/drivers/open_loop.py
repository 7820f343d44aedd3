"""Open-loop serving: requests arrive on a Poisson schedule at the cell's
fixed rate whether or not the engine keeps up, each timed from when it
was due.  After the window closes the engine drains what was due in it,
for at most ``drain_s`` seconds; a request refused, expired or still
unfinished then counts as infinitely late."""

from __future__ import annotations

import contextlib
import gc
import math
import time

import serving
import traffic
from harness import Run, nearest_rank, say, span


def serve(engine, specs, seconds: float, drain_s: float,
          window=contextlib.nullcontext) -> dict:
    """Submit ``specs`` as they fall due over ``seconds`` while stepping
    the engine, inside ``window()``; then drain for at most ``drain_s``."""
    log = serving.StepLog()
    sent, late, inflight = [], [], []
    with window():
        t0 = time.perf_counter()
        t_end = t0 + seconds
        i = 0
        while True:
            now = time.perf_counter()
            while i < len(specs) and t0 + specs[i].due <= now:
                due = t0 + specs[i].due
                with span("submit"):
                    r = engine.submit(serving.request_from(specs[i], due))
                sent.append(r)
                inflight.append(r)
                late.append(now - due)
                i += 1
            if now >= t_end:
                break
            if engine.idle:
                nxt = t0 + specs[i].due if i < len(specs) else t_end
                time.sleep(max(0.0, min(nxt, t_end) - time.perf_counter()))
                continue
            log.step(engine, inflight)
            inflight = [r for r in inflight if not r.terminal]
        t1 = time.perf_counter()
    backlog, queued = len(inflight), len(engine.queue)
    drain = serving.StepLog()
    drain_end = time.perf_counter() + drain_s
    while inflight and time.perf_counter() < drain_end:
        drain.step(engine, inflight)
        inflight = [r for r in inflight if not r.terminal]
    return {"sent": sent, "late": late, "log": log, "drain_log": drain,
            "window_s": t1 - t0, "inflight": inflight, "backlog": backlog,
            "queued": queued,
            "drain_s": time.perf_counter() - t1}


def run(run: Run) -> None:
    wl, cfg = run.wl, run.cfg
    e, tr = wl["engine"], wl["mix"]
    engine, params = serving.build_engine(run)
    specs = traffic.open_loop(tr, run.seed, run.window_seconds,
                              cfg["vocab_size"], e["max_len"])
    serving.warm_up(run, engine, range(tr["prompt"]["lo"], e["max_len"]))
    say("traffic", {"requests": len(specs), "rate_per_s": tr["rate_per_s"],
                    "prompt_tokens": int(sum(len(s.prompt) for s in specs)),
                    "max_new_tokens": int(sum(s.max_new_tokens
                                              for s in specs))})

    out = serve(engine, specs, run.window_seconds, wl["drain_s"],
                run.window)
    sent, late, window_log, log = (out["sent"], out["late"], out["log"],
                                   out["drain_log"])
    inflight = out["inflight"]
    run.window_s = out["window_s"]

    ttft, tpot = [], []
    for r in sent:
        ok = r.state.value == "finished"
        ttft.append(r.t_first_token - r.t_arrival if ok else math.inf)
        tpot.append((r.t_finished - r.t_first_token) / (r.n_generated - 1)
                    if ok and r.n_generated > 1 else math.inf if not ok
                    else 0.0)
    run.attempted = len(sent)
    run.failed = sum(r.state.value != "finished" for r in sent)
    run.e2e["ttft_p95_ms"] = nearest_rank(ttft, 0.95) * 1e3
    run.e2e["tpot_p95_ms"] = nearest_rank(tpot, 0.95) * 1e3
    steps = [r.step_first_token - r.step_submitted for r in sent
             if r.step_first_token is not None]
    run.counters.update(
        ttft_steps=[float(s) for s in steps] + [math.inf] * (
            len(sent) - len(steps)),
        steps=window_log.steps, decode_steps=len(window_log.live),
        decode_tokens=window_log.decode_tokens,
        first_tokens=window_log.first_tokens, n_slots=e["n_slots"],
        window_s=run.window_s)
    say("generator_late_ms_p95", nearest_rank(late, 0.95) * 1e3
        if late else 0.0)
    say("window", {"seconds": run.window_s, "requests": len(sent),
                   "failed": run.failed, "drain_steps": log.steps,
                   "ttft_ms_p50": nearest_rank(ttft, 0.5) * 1e3,
                   "preemptions": engine.counters["preemptions"],
                   "resumes": engine.counters["resumes"]})
    if any(math.isinf(x) for x in (run.e2e["ttft_p95_ms"],
                                   run.e2e["tpot_p95_ms"])):
        raise RuntimeError(
            f"{run.failed} of {len(sent)} requests failed: the 95th "
            f"percentile is a failed request, so the rate is past what the "
            f"engine sustains")
    run.memory_peak()
    del engine, inflight
    gc.collect()
    serving.check_served(run, sent, params)
