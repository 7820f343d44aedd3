"""The serving side shared by the serving drivers: the engine built on the
seed's weights, warm-up of exactly the shapes the cell's traffic
reaches, per-step accounting, and the check of served tokens against
the plain reference.

Shapes follow the engine's documented bucketing (``ContinuousEngine``):
a prompt of at most ``prefill_chunk`` tokens prefills densely at a
power-of-two width of at least one block; a longer one prefills in
chunks, each at its power-of-two width and power-of-two table width;
decode runs at the power-of-two table width of the longest context.
"""

from __future__ import annotations

import time

import jax
import numpy as np

import weights
from harness import Run, say, span
from reference import transformer as R

__all__ = ["build_engine", "warm_up", "StepLog", "check_served",
           "request_from", "shape_keys"]


def _pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def build_engine(run: Run):
    """The program's engine on weights made from the seed."""
    from repro.models import transformer as T
    from repro.serve import ContinuousConfig, ContinuousEngine

    params = weights.make(run.cfg, run.seed)
    want = jax.tree.map(lambda s: (s.shape, s.dtype), T.param_specs(run.program))
    got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    if want != got:
        raise ValueError("weights.py's layout is not the program's")
    e = run.wl["engine"]
    engine = ContinuousEngine(run.program, params, ContinuousConfig(
        max_len=e["max_len"], n_slots=e["n_slots"],
        pool_tokens=e["pool_tokens"], prefill_chunk=e["prefill_chunk"],
        temperature=0.0, eos_id=-1, seed=run.seed % 2**31))
    say("kv_block_size", engine.kv.block_size)
    say("kv_pool_bytes", engine.kv.bytes)
    return engine, params


def shape_keys(S: int, bs: int, chunk: int, max_len: int) -> set:
    """The compiled programs a prompt (or resumed sequence) of ``S``
    tokens reaches, and the decode table width right after it."""
    keys = set()
    if S <= chunk:
        keys.add(("prefill", min(_pow2(max(S, bs)), -(-max_len // bs) * bs)))
    else:
        for s0 in range(0, S, chunk):
            w = _pow2(min(chunk, S - s0))
            keys.add(("chunk", w, _pow2((s0 + w - 1) // bs + 1)))
    keys.add(("decode", min(_pow2(S // bs + 1), -(-max_len // bs))))
    return keys


def warm_up(run: Run, engine, lengths: range) -> None:
    """Run one request per needed program, alone, through the engine's
    public path.  Every sequence length in ``lengths`` (the prompts, and
    up to ``max_len`` for sequences that a preemption re-prefills) maps
    to the programs it reaches; one length per program is served, one
    request at a time, so that each decode table width is the widest in
    its step."""
    from repro.serve import Request

    e = run.wl["engine"]
    bs, chunk, max_len = engine.kv.block_size, e["prefill_chunk"], e["max_len"]
    need: dict = {}
    for S in lengths:
        for k in shape_keys(S, bs, chunk, max_len):
            need.setdefault(k, S)
    chosen = sorted(set(need.values()))
    rng = np.random.default_rng(np.random.SeedSequence([run.seed, 9]))
    V = run.cfg["vocab_size"]
    t = time.perf_counter()
    for S in chosen:
        n_new = min(2, max_len - S)
        if n_new < 1:
            S, n_new = max_len - 1, 1
        r = Request(prompt=rng.integers(0, V, S, dtype=np.int32),
                    max_new_tokens=n_new)
        engine.run([r])
    say("warmup", {"programs": len(need), "requests": len(chosen),
                   "seconds": round(time.perf_counter() - t, 3)})


def request_from(spec, t_due: float | None = None):
    from repro.serve import Request

    r = Request(prompt=spec.prompt, max_new_tokens=spec.max_new_tokens)
    if t_due is not None:
        r.t_arrival = t_due
    return r


class StepLog:
    """Per engine step: which in-flight requests decoded, and over how
    many live keys (prompt + generated so far); prompt tokens whose
    prefill completed; first tokens.  Read from the requests' own token
    counts, so it costs no device time."""

    def __init__(self):
        self.decode_tokens = 0        # tokens made by decode steps
        self.first_tokens = 0         # tokens made by the end of a prefill
        self.prefill_tokens = 0       # prompt tokens of completed prefills
        self.prefill_pairs = 0
        self.decode_pairs = 0
        self.steps = 0
        self.live = []                # (t, live keys, rows) per decode step

    def step(self, engine, inflight: list) -> None:
        from counts.transformer import causal_pairs

        before = [r.n_generated for r in inflight]
        with span("step"):
            engine.step()
        self.steps += 1
        live = rows = 0
        for r, n0 in zip(inflight, before):
            n1 = r.n_generated
            if n1 == n0:
                continue
            decoded = n1 - n0 if n0 else n1 - n0 - 1
            if n0 == 0:
                self.first_tokens += 1
                self.prefill_tokens += r.prompt_len
                self.prefill_pairs += causal_pairs(r.prompt_len)
            if decoded:
                keys = r.prompt_len + n1 - 1
                live += keys
                rows += 1
                self.decode_tokens += 1
                self.decode_pairs += keys
        if rows:
            self.live.append((time.perf_counter(), live, rows))


def check_served(run: Run, sent: list, params) -> None:
    """Compare a seeded sample of the requests the run finished, with the
    longest, against the plain reference: the widest gap by which a
    served token's logit lies below the reference's best at its
    position."""
    chk = run.wl["check"]
    served_reqs = [r for r in sent
                   if r.state.value == "finished" and r.n_generated > 0]
    if not served_reqs:
        run.check("max_logit_gap", float("inf"), chk["max_logit_gap"])
        return
    rng = np.random.default_rng(np.random.SeedSequence([run.seed, 11]))
    longest = max(served_reqs, key=lambda r: r.prompt_len + r.n_generated)
    order = [served_reqs[i] for i in rng.permutation(len(served_reqs))]
    sample, served = [longest], longest.n_generated
    for r in order:
        if served >= chk["min_served_tokens"] or len(sample) >= chk["max_requests"]:
            break
        if r is not longest:
            sample.append(r)
            served += r.n_generated
    gaps = []
    for r in sample:
        g, _, _ = R.served_gaps(run.cfg, "float32", params, r.prompt,
                                np.asarray(r.tokens, np.int32))
        gaps.append(float(g.max()))
    say("checked", {"requests": len(sample), "served_tokens": served,
                    "gaps": gaps})
    run.check("max_logit_gap", max(gaps), chk["max_logit_gap"])
