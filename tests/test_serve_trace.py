"""Observability of the continuous-batching engine (docs/serve.md
"Observability"): the request marks of first admission, the host spans
each phase of ``ContinuousEngine.step`` writes into the profiler's trace,
and the names of the engine's device programs."""

import glob

import jax
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.models import transformer as T
from repro.serve import (ContinuousConfig, ContinuousEngine, Request,
                         RequestState)

SPANS = {"serve.step", "serve.expire", "serve.admit", "serve.prefill",
         "serve.chunk", "serve.grow", "serve.decode", "serve.sync",
         "serve.commit"}


@pytest.fixture(scope="module")
def model():
    cfg = get_config("internlm2-1.8b", reduced=True)
    return cfg, T.init_params(cfg, 0)


def _prompts(lens, seed=0, vocab=128):
    rng = np.random.default_rng(seed)
    return [rng.integers(2, vocab, (n,)).astype(np.int32) for n in lens]


def _assert_marks_in_order(r):
    assert r.t_arrival <= r.t_admitted <= r.t_first_token <= r.t_finished
    assert r.step_submitted <= r.step_admitted <= r.step_first_token
    # TTFT splits exactly into queueing and prefill
    queue = r.t_admitted - r.t_arrival
    prefill = r.t_first_token - r.t_admitted
    assert queue + prefill == pytest.approx(r.ttft_s, rel=0, abs=1e-12)


@pytest.mark.parametrize("chunk", [None, 16])
def test_finished_requests_carry_marks_in_order(model, chunk):
    cfg, params = model
    ce = ContinuousEngine(cfg, params, ContinuousConfig(
        max_len=128, n_slots=2, seed=0, prefill_chunk=chunk))
    reqs = [Request(p, max_new_tokens=6) for p in _prompts((5, 37, 21, 50))]
    ce.run(reqs)
    assert all(r.state is RequestState.FINISHED for r in reqs)
    for r in reqs:
        _assert_marks_in_order(r)
    # two slots, four requests: the last two queued behind the first two
    assert reqs[3].step_admitted > reqs[3].step_submitted


def test_resume_keeps_the_first_admission(model):
    """A preempted request re-enters through admission; its marks stay
    those of its first admission."""
    cfg, params = model
    # 64-token pool = 4 usable blocks; each request lives 45 tokens = 3
    # blocks, so both cannot finish without a preemption.
    ce = ContinuousEngine(cfg, params, ContinuousConfig(
        max_len=64, n_slots=2, eos_id=0, block_size=16, pool_tokens=64))
    reqs = [Request(prompt=p, max_new_tokens=40)
            for p in _prompts([5, 5], seed=3)]
    for r in reqs:
        ce.submit(r)
    first = {}
    while not ce.idle:
        ce.step()
        for r in reqs:
            if r.t_admitted is not None:
                first.setdefault(r.rid, (r.t_admitted, r.step_admitted))
    assert ce.counters["resumes"] >= 1
    victim = max(reqs, key=lambda r: r.preemptions)
    assert victim.preemptions >= 1
    for r in reqs:
        assert r.state is RequestState.FINISHED
        assert (r.t_admitted, r.step_admitted) == first[r.rid]
        _assert_marks_in_order(r)


def test_refused_requests_have_no_admission(model):
    cfg, params = model
    ce = ContinuousEngine(cfg, params, ContinuousConfig(
        max_len=64, n_slots=1, seed=0, max_queue=1))
    too_long = Request(prompt=_prompts([60])[0], max_new_tokens=8)
    ok = Request(prompt=_prompts([5])[0], max_new_tokens=2)
    shed = Request(prompt=_prompts([5])[0], max_new_tokens=2)
    ce.submit(too_long)
    ce.step()              # refused at admission: 68 tokens > max_len
    ce.submit(ok)
    ce.submit(shed)        # refused at submit: the wait queue is full
    ce.run()
    assert too_long.state is RequestState.REFUSED
    assert shed.state is RequestState.REFUSED
    assert ok.state is RequestState.FINISHED
    for r in (too_long, shed):
        assert r.t_admitted is None and r.step_admitted is None
        assert r.t_first_token is None
    _assert_marks_in_order(ok)


def _host_spans(trace_dir):
    """(name, start_ns, end_ns, stats) of every ``serve.*`` event."""
    from jax.profiler import ProfileData

    files = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    assert len(files) == 1, files
    out = []
    for plane in ProfileData.from_file(files[0]).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("serve."):
                    out.append((e.name, e.start_ns, e.end_ns,
                                dict(e.stats)))
    return out


def test_each_step_writes_its_phases_into_the_trace(model, tmp_path):
    cfg, params = model
    ce = ContinuousEngine(cfg, params, ContinuousConfig(
        max_len=128, n_slots=2, seed=0, prefill_chunk=16))
    # one dense prefill (5 ≤ 16) and one chunked (37 > 16); warm up first,
    # so that no compile lands inside a span of the traced steps
    ce.run([Request(p, max_new_tokens=4) for p in _prompts((5, 37))])
    for r in (Request(p, max_new_tokens=4) for p in _prompts((5, 37), 1)):
        ce.submit(r)
    decoded = set()
    with jax.profiler.trace(str(tmp_path)):
        while not ce.idle:
            before = ce.decode_steps
            ce.step()
            if ce.decode_steps > before:
                decoded.add(ce._step)
    spans = _host_spans(tmp_path)
    assert {n for n, *_ in spans} == SPANS
    steps = {int(st["step"]): (s, e) for n, s, e, st in spans
             if n == "serve.step"}
    assert len(steps) == sum(n == "serve.step" for n, *_ in spans)
    assert decoded and decoded <= set(steps)
    for k in decoded:
        s, e = steps[k]
        inside = {n for n, s2, e2, _ in spans if s <= s2 and e2 <= e}
        assert {"serve.decode", "serve.sync", "serve.commit"} <= inside, k
    # spans of one request join on its identifier
    rids = {int(st["rid"]) for n, *_, st in spans
            if n in ("serve.prefill", "serve.chunk")}
    assert len(rids) == 2
    for n, *_, st in spans:
        if n == "serve.chunk":
            assert {"rid", "width", "nb"} <= set(st)
        if n == "serve.decode":
            assert {"rows", "nb"} <= set(st)


def test_programs_lower_under_their_names(model):
    cfg, params = model
    ce = ContinuousEngine(cfg, params, ContinuousConfig(
        max_len=128, n_slots=2, seed=0, prefill_chunk=16))
    ce.run([Request(p, max_new_tokens=3) for p in _prompts((5, 37))])
    assert ce._prefills and ce._chunks and ce._decodes
    i32 = np.int32
    lowered = []
    for width, fn in ce._prefills.items():
        lowered.append(("jit_serve_prefill", fn.lower(params, {
            "tokens": np.zeros((1, width), i32),
            "pos_offset": np.zeros((1,), i32)})))
    for (width, nb), fn in ce._chunks.items():
        lowered.append(("jit_serve_chunk", fn.lower(params, ce.kv.pool, {
            "tokens": np.zeros((1, width), i32),
            "cache_len": np.zeros((1,), i32),
            "block_table": np.zeros((1, nb), i32)})))
    for nb, fn in ce._decodes.items():
        lowered.append(("jit_serve_decode", fn.lower(params, ce.kv.pool, {
            "tokens": np.zeros((2, 1), i32),
            "cache_len": np.zeros((2,), i32),
            "block_table": np.zeros((2, nb), i32)})))
    lowered.append(("jit_serve_sample", ce._sample.lower(
        np.zeros((2, 1, cfg.vocab), np.float32), jax.random.PRNGKey(0))))
    for name, low in lowered:
        assert f"module @{name}" in low.as_text(), name
