"""Closed-loop serving: ``clients`` callers, each sending its next request
the moment its previous one finishes, so every slot stays busy.  Set-up
fills every slot (all prompts prefilled) before the window opens; the
window counts every token the engine makes inside it."""

from __future__ import annotations

import gc
import time

import serving
import traffic
from harness import Run, say


def run(run: Run) -> None:
    wl, cfg = run.wl, run.cfg
    e = wl["engine"]
    engine, params = serving.build_engine(run)
    queues = traffic.closed_loop(wl["mix"], run.seed, cfg["vocab_size"],
                                 e["max_len"])
    p = wl["mix"]["prompt"]
    serving.warm_up(run, engine, range(p["lo"], e["max_len"]))

    nxt = [0] * len(queues)
    current, sent = [], []

    def send(c: int):
        # a client that reaches the end of its queue starts it again
        r = serving.request_from(queues[c][nxt[c] % len(queues[c])])
        nxt[c] += 1
        engine.submit(r)
        sent.append(r)
        return r

    for c in range(len(queues)):
        current.append(send(c))
    log = serving.StepLog(run.cell.family.counts)
    while any(r.n_generated == 0 for r in current):
        log.step(engine, current)
    say("filled", {"slots": engine.n_running, "steps": log.steps})

    log = serving.StepLog(run.cell.family.counts)
    with run.window():
        t0 = time.perf_counter()
        t_end = t0 + run.window_seconds
        while True:
            log.step(engine, current)
            if time.perf_counter() >= t_end:
                break
            for c, r in enumerate(current):
                if r.terminal:
                    current[c] = send(c)
        t1 = time.perf_counter()
    run.window_s = t1 - t0
    made = log.decode_tokens + log.first_tokens
    run.e2e["output_tokens_per_s"] = made / run.window_s
    run.attempted = sum(nxt)
    run.failed = len(engine.refused) + len(engine.expired)
    run.counters.update(
        steps=log.steps, decode_steps=len(log.live),
        decode_tokens=log.decode_tokens, first_tokens=log.first_tokens,
        prefill_tokens=log.prefill_tokens, prefill_pairs=log.prefill_pairs,
        decode_pairs=log.decode_pairs, live=log.live, n_slots=e["n_slots"],
        window_s=run.window_s)
    say("window", {"seconds": run.window_s, "tokens": made,
                   "steps": log.steps, "requests_sent": run.attempted,
                   "preemptions": engine.counters["preemptions"]})
    run.memory_peak()
    del engine, current
    gc.collect()
    serving.check_served(run, sent, params)
