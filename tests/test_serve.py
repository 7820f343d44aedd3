"""Serving-path tests: admission edge cases, on-device sampling, ragged
prompts, EOS trimming, the continuous-batching engine + paged KV cache,
and the zero-compile SLO scheduler (ISSUE 6)."""

import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.engine import (
    BackendUnavailable,
    CostEngine,
    CostEstimate,
    ForestBackend,
    get_device,
)
from repro.kernels.autotune import KernelTuner
from repro.models import transformer as T
from repro.serve import (
    ContinuousConfig,
    ContinuousEngine,
    Decision,
    PagedKVCache,
    PlacementRefused,
    Request,
    RequestState,
    ServeConfig,
    ServeEngine,
    SLOScheduler,
    pad_ragged,
    resolve_block_size,
)


def _cfg():
    return get_config("internlm2-1.8b", reduced=True)


@pytest.fixture(scope="module")
def model():
    cfg = _cfg()
    return cfg, T.init_params(cfg, 0)


def _prompts(lens=(5, 9, 13), seed=0, vocab=128):
    rng = np.random.default_rng(seed)
    return [rng.integers(2, vocab, (n,)).astype(np.int32) for n in lens]


# ---------------------------------------------------------------------------
# admission edge cases (legacy engine)
# ---------------------------------------------------------------------------


class _StubCostEngine:
    def __init__(self, ok=True, gamma_mb=100.0):
        self.ok, self.gamma_mb = ok, gamma_mb
        self.queries, self.budgets = [], []

    def admit(self, query, *, gamma_budget_mb=None, phi_budget_ms=None,
              safety_margin=0.1):
        self.queries.append(query)
        self.budgets.append(gamma_budget_mb)
        return self.ok, {"gamma_mb": self.gamma_mb, "phi_ms": 1.0,
                         "gamma_eff": self.gamma_mb * (1 + safety_margin),
                         "phi_eff": 1.1, "source": "stub"}


class _UnavailableCostEngine:
    def admit(self, query, **kw):
        raise BackendUnavailable("no backend can score this arch")

    def estimate_one(self, query):
        raise BackendUnavailable("no backend can score this arch")


def test_external_engine_without_device_keeps_budget_none(model):
    """gamma_budget_mb=None + external cost_engine + no device: the gate
    still runs, but with an unbounded budget (nothing to cap against)."""
    cfg, params = model
    gate = _StubCostEngine(ok=True)
    eng = ServeEngine(cfg, params, ServeConfig(max_len=64, n_slots=2),
                      cost_engine=gate)
    assert gate.budgets == [None]
    assert eng.admission_info["source"] == "stub"


def test_backend_unavailable_skips_gate(model):
    cfg, params = model
    eng = ServeEngine(cfg, params, ServeConfig(max_len=64, n_slots=2),
                      cost_engine=_UnavailableCostEngine())
    assert "no backend can score" in eng.admission_info["skipped"]


def test_placement_refused_message_and_info(model):
    cfg, params = model
    with pytest.raises(PlacementRefused) as ei:
        ServeEngine(cfg, params,
                    ServeConfig(max_len=64, n_slots=2, gamma_budget_mb=1.0),
                    cost_engine=_StubCostEngine(ok=False))
    msg = str(ei.value)
    assert "internlm2-1.8b-smoke" in msg and "n_slots=2" in msg
    assert "110MB effective" in msg            # gamma_eff = 100 * 1.1
    assert ei.value.info["source"] == "stub"   # evidence travels on .info


# ---------------------------------------------------------------------------
# on-device sampling (seeded-reproducibility contract, both paths)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("temperature", [0.0, 0.8])
def test_sampling_deterministic_under_fixed_seed(model, temperature):
    cfg, params = model
    prompts = np.random.default_rng(1).integers(
        1, cfg.vocab, (2, 8)).astype(np.int32)

    def gen(seed):
        scfg = ServeConfig(max_len=64, n_slots=2, temperature=temperature,
                           seed=seed)
        return ServeEngine(cfg, params, scfg).generate(
            prompts, max_new_tokens=6)

    np.testing.assert_array_equal(gen(3)["tokens"], gen(3)["tokens"])
    if temperature > 0:
        assert not np.array_equal(gen(3)["tokens"], gen(4)["tokens"])


# ---------------------------------------------------------------------------
# ragged prompts + EOS trimming (legacy engine)
# ---------------------------------------------------------------------------


def test_pad_ragged_left_pads():
    tokens, lens = pad_ragged([np.array([7, 8]), np.array([1, 2, 3, 4])])
    np.testing.assert_array_equal(lens, [2, 4])
    np.testing.assert_array_equal(tokens[0], [0, 0, 7, 8])
    np.testing.assert_array_equal(tokens[1], [1, 2, 3, 4])


def test_ragged_generate_matches_solo_rows(model):
    """Each row of a mixed-length batch must decode exactly what it would
    decode alone — the garbage-position bug ragged support fixes."""
    cfg, params = model
    prompts = _prompts()
    eng = ServeEngine(cfg, params, ServeConfig(max_len=64, n_slots=3,
                                               eos_id=0))
    out = eng.generate(prompts, max_new_tokens=6)
    np.testing.assert_array_equal(out["prompt_lens"], [5, 9, 13])
    for i, p in enumerate(prompts):
        solo = ServeEngine(cfg, params, ServeConfig(
            max_len=64, n_slots=3, eos_id=0)).generate(
                p[None, :], max_new_tokens=6)
        n = min(solo["tokens"].shape[1], out["tokens"].shape[1])
        np.testing.assert_array_equal(out["tokens"][i, :n],
                                      solo["tokens"][0, :n])


def test_eos_trimmed_outputs_and_counts(model):
    cfg, params = model
    prompt = _prompts(lens=(6,))[0]
    ref = ServeEngine(cfg, params, ServeConfig(
        max_len=64, n_slots=1, eos_id=0)).generate(
            prompt[None, :], max_new_tokens=6)
    # re-generate with eos = the 3rd greedy token: trim must cut there
    eos = int(ref["tokens"][0, 2])
    out = ServeEngine(cfg, params, ServeConfig(
        max_len=64, n_slots=1, eos_id=eos)).generate(
            prompt[None, :], max_new_tokens=6)
    assert out["token_counts"][0] == 2
    np.testing.assert_array_equal(out["outputs"][0], ref["tokens"][0, :2])
    assert out["finished"][0]


def test_request_output_trims_at_first_eos():
    req = Request(prompt=np.array([5], np.int32))
    req.tokens = [3, 9, 7, 9, 4]
    np.testing.assert_array_equal(req.output(eos_id=7), [3, 9])
    np.testing.assert_array_equal(req.output(eos_id=1), [3, 9, 7, 9, 4])


# ---------------------------------------------------------------------------
# paged KV cache + serve_kv tiling through the TuningCache
# ---------------------------------------------------------------------------


def test_serve_kv_block_size_resolved_through_tuning_cache(tmp_path):
    cfg = _cfg()
    path = str(tmp_path / "tuning.json")
    t1 = KernelTuner(cache=path)
    b1 = resolve_block_size(cfg, n_slots=4, max_len=128, tuner=t1)
    assert b1 >= 1 and (t1.hits, t1.misses) == (0, 1)
    assert resolve_block_size(cfg, n_slots=4, max_len=128, tuner=t1) == b1
    assert (t1.hits, t1.misses) == (1, 1)      # in-process memo hit
    t2 = KernelTuner(cache=path)               # fresh tuner, same disk cache
    assert resolve_block_size(cfg, n_slots=4, max_len=128, tuner=t2) == b1
    assert (t2.hits, t2.misses) == (1, 0)      # on-disk TuningCache hit
    # device-fingerprint-keyed: another device's entry never aliases
    t3 = KernelTuner(device=get_device("tx2_like"), cache=path)
    resolve_block_size(cfg, n_slots=4, max_len=128, tuner=t3)
    assert t3.misses == 1


@pytest.mark.parametrize("n_slots", [10, 32])
def test_serve_kv_block_size_for_qwen3_4b_cells_on_v5e(n_slots):
    """The pool block the qwen3-4b cells run (10 and 32 slots over a
    2048-token window) on the v5e spec: 256, priced through the
    paged_decode cost model with a step chain per KV head."""
    tuner = KernelTuner(device=get_device("tpu_v5e"), cache=None,
                        measure=False)
    assert resolve_block_size(get_config("qwen3-4b"), n_slots=n_slots,
                              max_len=2048, tuner=tuner) == 256


def test_paged_pool_allocator_and_footprint():
    cfg = _cfg()
    kv = PagedKVCache(cfg, n_slots=8, max_len=512, block_size=64)
    assert kv.bytes < kv.dense_bytes           # the point of paging
    free0 = kv.n_free_blocks
    a = kv.alloc(kv.blocks_for(100))
    assert len(a) == 2 and 0 not in a          # block 0 is reserved scratch
    assert kv.alloc(free0) is None             # over-ask: nothing allocated
    assert kv.n_free_blocks == free0 - 2
    kv.free(a)
    assert kv.n_free_blocks == free0


# ---------------------------------------------------------------------------
# continuous engine
# ---------------------------------------------------------------------------


def test_continuous_matches_lockstep_greedy(model):
    """Strongest correctness check: the paged, ragged, slot-indexed decode
    must reproduce the legacy engine's greedy tokens per request."""
    cfg, params = model
    prompts = _prompts()
    legacy = ServeEngine(cfg, params, ServeConfig(
        max_len=64, n_slots=3, eos_id=0)).generate(prompts, max_new_tokens=8)
    ce = ContinuousEngine(cfg, params, ContinuousConfig(
        max_len=64, n_slots=3, eos_id=0, block_size=16))
    reqs = [Request(prompt=p, max_new_tokens=8) for p in prompts]
    ce.run(reqs)
    for i, r in enumerate(reqs):
        assert r.state is RequestState.FINISHED
        np.testing.assert_array_equal(
            r.tokens, legacy["tokens"][i, : len(r.tokens)])


def test_continuous_slot_reuse_and_pool_reclaim(model):
    """More requests than slots, mixed token budgets, a pool smaller than
    n_slots × max_len: slots and blocks must recycle until the queue
    drains, and every block must return to the free list."""
    cfg, params = model
    rng = np.random.default_rng(2)
    reqs = [Request(prompt=rng.integers(2, 128, (l,)).astype(np.int32),
                    max_new_tokens=m)
            for l, m in [(4, 3), (7, 10), (3, 5), (11, 2), (6, 8), (5, 4)]]
    ce = ContinuousEngine(cfg, params, ContinuousConfig(
        max_len=64, n_slots=2, eos_id=0, block_size=16, pool_tokens=64))
    done = ce.run(reqs)
    assert len(done) == len(reqs)
    assert all(r.n_generated <= r.max_new_tokens for r in reqs)
    assert all(r.ttft_s is not None and r.ttft_s >= 0 for r in reqs)
    assert ce.kv.n_free_blocks == ce.kv.n_blocks - 1


def test_continuous_temperature_seeded(model):
    cfg, params = model
    prompt = _prompts(lens=(6,))[0]

    def gen(seed):
        ce = ContinuousEngine(cfg, params, ContinuousConfig(
            max_len=64, n_slots=2, eos_id=0, block_size=16,
            temperature=0.8, seed=seed))
        req = Request(prompt=prompt, max_new_tokens=6)
        ce.run([req])
        return req.tokens

    assert gen(7) == gen(7)


# ---------------------------------------------------------------------------
# SLO scheduler: cost-model-driven decisions, zero compiles
# ---------------------------------------------------------------------------


class _FakeLMForest:
    """Fitted-forest stand-in: constant (Γ, Φ) per query, no jax anywhere."""

    fitted = True
    meta: dict = {}

    def __init__(self, gamma_mb, phi_ms=1.0):
        self.gamma_mb, self.phi_ms = gamma_mb, phi_ms
        self.default_device = get_device("host_cpu")

    def content_hash(self):
        return f"fake-{self.gamma_mb}-{self.phi_ms}"

    def predict_queries(self, queries):
        n = len(queries)
        return (np.full(n, self.gamma_mb), np.full(n, self.phi_ms))


def _scheduler(gamma_mb, budget_mb, phi_ms=1.0, **kw):
    engine = CostEngine(ForestBackend(lm=_FakeLMForest(gamma_mb, phi_ms)))
    return SLOScheduler(_cfg(), engine, max_len=64, n_slots=4,
                        gamma_budget_mb=budget_mb, **kw)


def test_scheduler_cost_driven_zero_compiles(monkeypatch):
    """Over-budget composition refused, fitting one admitted — and the
    whole decision path triggers zero JAX compilations (forest chain)."""
    import jax

    from repro.engine import AnalyticalBackend

    def boom(*a, **k):
        raise AssertionError("admission path invoked the jax compiler")

    monkeypatch.setattr(jax, "jit", boom)
    monkeypatch.setattr(AnalyticalBackend, "_compile_arch", boom)

    req = Request(prompt=np.arange(1, 9, dtype=np.int32), max_new_tokens=8)
    dec, info = _scheduler(gamma_mb=500.0, budget_mb=100.0).admit(
        req, n_running=1)
    assert dec is Decision.REFUSE
    assert "budget" in info["reason"] and info["bs"] == 2
    assert info["source"] == "forest"

    dec, info = _scheduler(gamma_mb=50.0, budget_mb=100.0).admit(
        req, n_running=1)
    assert dec is Decision.ADMIT and info["gamma_eff"] == pytest.approx(55.0)


def test_scheduler_refusal_carries_ledger_breakdown():
    class _BreakdownEngine:
        def estimate_one(self, query):
            return CostEstimate(
                gamma_mb=900.0, phi_ms=5.0, source="analytical",
                detail={"cost_classes": {"matmul": 700.0, "elementwise": 150.0,
                                         "collective": 50.0}})

    sched = SLOScheduler(_cfg(), _BreakdownEngine(), max_len=64, n_slots=4,
                         gamma_budget_mb=100.0)
    req = Request(prompt=np.arange(1, 6, dtype=np.int32), max_new_tokens=4)
    dec, info = sched.admit(req, n_running=0)
    assert dec is Decision.REFUSE
    err = sched.refusal(req, info)
    assert isinstance(err, PlacementRefused)
    assert err.info["cost_classes"]["matmul"] == 700.0
    assert "matmul=700" in str(err)            # breakdown in the message


def test_scheduler_slo_and_window_refusals():
    req_big = Request(prompt=np.arange(1, 60, dtype=np.int32),
                      max_new_tokens=32)
    dec, info = _scheduler(10.0, 1e6).admit(req_big, n_running=0)
    assert dec is Decision.REFUSE and "max_len" in info["reason"]

    # per-request SLO: phi 640ms over a 64-token window → 10ms/token proxy
    req = Request(prompt=np.arange(1, 9, dtype=np.int32), max_new_tokens=8,
                  slo_ms=1.0)
    dec, info = _scheduler(10.0, 1e6, phi_ms=640.0).admit(req, n_running=0)
    assert dec is Decision.REFUSE and "SLO" in info["reason"]
    req.slo_ms = 100.0
    dec, _ = _scheduler(10.0, 1e6, phi_ms=640.0).admit(req, n_running=0)
    assert dec is Decision.ADMIT


def test_scheduler_backend_unavailable_admits_ungated():
    sched = SLOScheduler(_cfg(), _UnavailableCostEngine(), max_len=64,
                         n_slots=4, gamma_budget_mb=1.0)
    req = Request(prompt=np.arange(1, 9, dtype=np.int32), max_new_tokens=4)
    dec, info = sched.admit(req, n_running=0)
    assert dec is Decision.ADMIT and "skipped" in info


def test_continuous_engine_refuses_via_scheduler(model):
    cfg, params = model
    engine = CostEngine(ForestBackend(lm=_FakeLMForest(5000.0)))
    ce = ContinuousEngine(cfg, params, ContinuousConfig(
        max_len=64, n_slots=2, eos_id=0, block_size=16,
        gamma_budget_mb=100.0), cost_engine=engine)
    req = Request(prompt=np.arange(1, 9, dtype=np.int32), max_new_tokens=4)
    ce.run([req])
    assert req.state is RequestState.REFUSED
    assert isinstance(req.refusal, PlacementRefused)
    assert ce.metrics()["refused"] == 1 and ce.metrics()["finished"] == 0


# ---------------------------------------------------------------------------
# admission decision bugfixes (ISSUE 7): DEFER, TTFT, oversized prompts
# ---------------------------------------------------------------------------


class _BsFakeLMForest(_FakeLMForest):
    """Batch-sensitive stand-in: Γ grows linearly with the priced bs, so
    a composition can be over budget at bs=2 yet fit alone at bs=1."""

    def content_hash(self):
        return f"bsfake-{self.gamma_mb}-{self.phi_ms}"

    def predict_queries(self, queries):
        g = np.array([self.gamma_mb * q.bs for q in queries])
        return g, np.full(len(queries), self.phi_ms)


def _bs_scheduler(gamma_per_slot, budget_mb, **kw):
    engine = CostEngine(ForestBackend(lm=_BsFakeLMForest(gamma_per_slot)))
    return SLOScheduler(_cfg(), engine, max_len=64, n_slots=4,
                        gamma_budget_mb=budget_mb, **kw)


def test_scheduler_defers_occupancy_transient_misses():
    """An over-budget composition that fits alone at bs=1 is DEFERred
    (retry as slots drain), not refused for good; one that cannot fit
    even alone is still REFUSE.  Pre-fix the DEFER branch was dead: the
    scheduler returned only ADMIT/REFUSE."""
    req = Request(prompt=np.arange(1, 9, dtype=np.int32), max_new_tokens=8)

    # 60MB/slot, 100MB budget: bs=2 → 132MB eff (miss), bs=1 → 66MB (fits)
    dec, info = _bs_scheduler(60.0, 100.0).admit(req, n_running=1)
    assert dec is Decision.DEFER
    assert "defer" in info and "bs=1" in info["defer"]
    assert "budget" in info["reason"]           # the transient miss, kept

    # same request with the slot free → straight ADMIT
    dec, _ = _bs_scheduler(60.0, 100.0).admit(req, n_running=0)
    assert dec is Decision.ADMIT

    # 120MB/slot: over budget even alone → REFUSE, occupancy irrelevant
    dec, info = _bs_scheduler(120.0, 100.0).admit(req, n_running=1)
    assert dec is Decision.REFUSE and "defer" not in info


def test_continuous_engine_defer_retries_and_finishes(model):
    """End to end: the second arrival DEFERs while the first occupies its
    slot, stays queued (not refused), and is admitted once the first
    drains — both finish."""
    cfg, params = model
    engine = CostEngine(ForestBackend(lm=_BsFakeLMForest(60.0)))
    ce = ContinuousEngine(cfg, params, ContinuousConfig(
        max_len=64, n_slots=2, eos_id=0, block_size=16,
        gamma_budget_mb=100.0), cost_engine=engine)
    a = Request(prompt=np.arange(1, 6, dtype=np.int32), max_new_tokens=2)
    b = Request(prompt=np.arange(1, 6, dtype=np.int32), max_new_tokens=2)
    ce.run([a, b])
    assert ce.metrics()["refused"] == 0
    assert ce.metrics()["finished"] == 2
    assert a.state is RequestState.FINISHED
    assert b.state is RequestState.FINISHED


def test_scheduler_ttft_slo_refusal():
    """ServeSLO.ttft_ms is actually enforced now: the request's own
    prefill (priced at bs=1 over its prompt) over the target → REFUSE.
    Pre-fix the field was stored but never read."""
    from repro.serve import ServeSLO

    req = Request(prompt=np.arange(1, 9, dtype=np.int32), max_new_tokens=8)
    dec, info = _scheduler(10.0, 1e6, phi_ms=100.0,
                           slo=ServeSLO(ttft_ms=50.0)).admit(
        req, n_running=0)
    assert dec is Decision.REFUSE and "TTFT" in info["reason"]
    assert info["ttft_proxy_ms"] == pytest.approx(110.0)

    dec, _ = _scheduler(10.0, 1e6, phi_ms=100.0,
                        slo=ServeSLO(ttft_ms=200.0)).admit(req, n_running=0)
    assert dec is Decision.ADMIT


def test_ungated_engine_refuses_oversized_prompt(model):
    """cost_engine=None: an oversized prompt must be REFUSED cleanly by
    the engine's own context-window check.  Pre-fix this crashed in
    ``_prefill_into`` (width − prompt_len goes negative)."""
    cfg, params = model
    ce = ContinuousEngine(cfg, params, ContinuousConfig(
        max_len=32, n_slots=2, eos_id=0, block_size=16))
    big = Request(prompt=np.arange(1, 41, dtype=np.int32), max_new_tokens=4)
    ok = Request(prompt=np.arange(1, 6, dtype=np.int32), max_new_tokens=2)
    ce.run([big, ok])
    assert big.state is RequestState.REFUSED
    assert isinstance(big.refusal, PlacementRefused)
    assert "max_len" in str(big.refusal)
    # the engine stays healthy: the normal request still completes
    assert ok.state is RequestState.FINISHED
    m = ce.metrics()
    assert m["refused"] == 1 and m["finished"] == 1


def test_gated_engine_refuses_oversized_prompt_before_scheduler(model):
    """With a scheduler attached the window check fires in the engine
    first — the cost model is never consulted for a request that cannot
    fit regardless of price."""
    cfg, params = model

    class _CountingEngine:
        calls = 0

        def estimate_one(self, query):
            type(self).calls += 1
            return CostEstimate(gamma_mb=1.0, phi_ms=1.0, source="stub")

    ce = ContinuousEngine(cfg, params, ContinuousConfig(
        max_len=32, n_slots=2, eos_id=0, block_size=16,
        gamma_budget_mb=1e6), cost_engine=_CountingEngine())
    big = Request(prompt=np.arange(1, 41, dtype=np.int32), max_new_tokens=4)
    ce.run([big])
    assert big.state is RequestState.REFUSED
    assert _CountingEngine.calls == 0


# ---------------------------------------------------------------------------
# per-request query helper
# ---------------------------------------------------------------------------


def test_estimate_requests_buckets_ragged_lens():
    class _CountingBackend:
        name = "counting"

        def __init__(self):
            self.batches = []

        def estimate(self, queries):
            self.batches.append(queries)
            return [CostEstimate(gamma_mb=float(q.seq), phi_ms=1.0,
                                 source=self.name) for q in queries]

    backend = _CountingBackend()
    engine = CostEngine(backend)
    ests = engine.estimate_requests("internlm2-1.8b", [3, 60, 70, 5],
                                    bucket=64)
    # 4 ragged lengths collapse onto 2 bucketed queries in one batch
    assert len(backend.batches) == 1 and len(backend.batches[0]) == 2
    assert [e.gamma_mb for e in ests] == [64.0, 64.0, 128.0, 64.0]
