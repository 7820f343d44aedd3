"""Continuous-batching serve engine over the paged KV cache.

The lockstep ``ServeEngine.generate`` admits one batch and holds every
slot hostage until the longest member finishes.  Here slots join and
leave the running batch *every step*:

* arrivals queue in ``submit`` and are priced by the
  :class:`~repro.serve.scheduler.SLOScheduler` (cost-model admission —
  REFUSE attaches a :class:`PlacementRefused` to the request);
* admitted requests prefill **individually** into a free slot (B=1 at a
  power-of-two bucketed length, left-padded) while other slots keep
  decoding — the prefill/decode split;
* prompts longer than ``prefill_chunk`` (when set) prefill in **chunks**
  interleaved with decode steps: each engine step advances every
  mid-prefill slot by one chunk through the paged S>1 decode path, so a
  running slot's inter-token gap is bounded by one chunk's cost instead
  of a whole long prompt's (greedy streams are unchanged — the first new
  token is sampled at the same logical position);
* the KV lands in the block pool (:class:`PagedKVCache`) and grows
  **incrementally**: admission allocates only the blocks the prefill
  needs, and decode allocates one more each time a request's write
  position crosses a block boundary;
* EOS / token-budget completion frees the slot and its blocks
  immediately for the next arrival.

Mispredicted load is a handled event, not a crash or a livelock
(docs/serve.md "Failure semantics"):

* **preemption** — when the pool cannot supply a growing request, the
  youngest running request is evicted (blocks freed, generated tokens
  retained) and re-queued at the head; it resumes by re-prefilling over
  prompt + generated tokens through the ordinary bucketed prefill.  The
  oldest running request is never chosen as a victim while younger ones
  exist, and resumed requests hold the queue head — the oldest admitted
  request always makes progress (anti-livelock);
* **deadlines + watchdog** — ``Request.deadline_ms`` and the engine-wide
  ``watchdog_ms`` TTL expire queued *and* running requests into the
  typed terminal ``EXPIRED`` state; a bounded wait queue (``max_queue``)
  refuses overflow at submit (backpressure); a DEFERred head retries
  with exponential backoff instead of re-pricing every step;
* **backend failover** — scheduler backend crashes step a
  :class:`~repro.engine.engine.HealthState` down the chain
  (forest → analytical → static degraded mode) via
  :class:`~repro.serve.health.FailoverChain`;
* a seeded :class:`~repro.serve.faults.FaultPlan` injects allocation
  failures, backend exceptions, and slow steps deterministically, and
  per-step robustness counters (``metrics()["preemptions"]``, …)
  let tests and the chaos bench assert on all of the above.

Shape stability: prefill retraces once per prompt-length bucket, decode
once per power-of-two block-table width, chunked prefill once per
(pow2 chunk width, pow2 table width) pair — a long-lived engine compiles
O(log² max_len) functions total, independent of traffic.
"""

from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs.base import ArchConfig
from repro.models import transformer as T
from repro.serve.health import FailoverChain
from repro.serve.kv_cache import PagedKVCache
from repro.serve.request import Request, RequestState
from repro.serve.scheduler import (
    Decision,
    PlacementRefused,
    ServeSLO,
    SLOScheduler,
)

__all__ = ["ContinuousConfig", "ContinuousEngine"]


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


@dataclass
class ContinuousConfig:
    max_len: int = 512
    n_slots: int = 8
    temperature: float = 0.0
    eos_id: int = 1
    seed: int = 0
    block_size: int | None = None     # None → serve_kv tiling via TuningCache
    pool_tokens: int | None = None    # None → n_slots·max_len / 2 budget
    prefill_chunk: int | None = None  # chunked prefill: max tokens prefilled
    #                                   per engine step (None = whole prompt)
    gamma_budget_mb: float | None = None
    energy_budget_j: float | None = None   # per-step power/thermal envelope
    safety_margin: float = 0.1
    slo: ServeSLO = field(default_factory=ServeSLO)
    # --- fault tolerance (docs/serve.md "Failure semantics") ---
    max_queue: int | None = None      # bounded wait queue; None = unbounded
    watchdog_ms: float | None = None  # engine-wide TTL; None = off
    defer_backoff_cap: int = 8        # max steps between DEFER retries
    degraded_slots: int | None = None  # static budget; None → n_slots // 2
    health_fail_threshold: int = 3    # consecutive crashes per failover step
    health_probe_every: int = 8       # estimate calls between recovery probes


class ContinuousEngine:
    def __init__(self, cfg: ArchConfig, params,
                 scfg: ContinuousConfig | None = None, *,
                 cost_engine=None, tuner=None, faults=None, clock=None):
        self.cfg = cfg
        self.scfg = scfg = scfg or ContinuousConfig()
        self.params = params
        self.faults = faults
        self._clock = clock or time.perf_counter
        self._skew_s = 0.0                 # virtual stall from "slow" faults
        self.kv = PagedKVCache(
            cfg, n_slots=scfg.n_slots, max_len=scfg.max_len,
            block_size=scfg.block_size, pool_tokens=scfg.pool_tokens,
            tuner=tuner, faults=faults)
        self.scheduler = None
        self.failover = None
        if cost_engine is not None:
            self.failover = FailoverChain(
                cost_engine, fail_threshold=scfg.health_fail_threshold,
                probe_every=scfg.health_probe_every, faults=faults)
            self.scheduler = SLOScheduler(
                cfg, cost_engine,
                max_len=scfg.max_len, n_slots=scfg.n_slots,
                gamma_budget_mb=scfg.gamma_budget_mb,
                energy_budget_j=scfg.energy_budget_j,
                safety_margin=scfg.safety_margin, slo=scfg.slo,
                failover=self.failover,
                degraded_slots=scfg.degraded_slots)

        self.queue: deque[Request] = deque()
        self.slots: list[Request | None] = [None] * scfg.n_slots
        self.finished: list[Request] = []
        self.refused: list[Request] = []
        self.expired: list[Request] = []
        self.submitted = 0
        self._admit_seq = 0
        self._cache_len = np.zeros(scfg.n_slots, np.int64)
        self._last_tok = np.zeros(scfg.n_slots, np.int32)
        self._prefilling = np.zeros(scfg.n_slots, bool)  # mid-chunked-prefill
        self._step = 0
        self.decode_steps = 0
        # Decode-path observability (metrics()): stall = a step where a
        # decodable slot existed but no decode ran (0 by construction —
        # chunked prefill interleaves, it never starves decode).
        self._stall_run = 0
        self.max_decode_stall_steps = 0
        # Widest prefill forward (padded tokens) run while decodable slots
        # were waiting — the deterministic stall bound a running slot can
        # see between two of its tokens.  Chunked prefill caps this at
        # _next_pow2(prefill_chunk); unchunked it is the whole prompt.
        self.max_prefill_stall_tokens = 0
        self.kv_gathered_bytes = 0.0   # (B · nb) blocks the gather path reads
        self.kv_touched_bytes = 0.0    # live blocks the decode kernel touches
        # Robustness counters — surfaced via metrics() so tests and the
        # chaos bench assert on events instead of log-scraping.
        self.counters = {
            "preemptions": 0,        # running requests evicted for blocks
            "resumes": 0,            # preempted requests re-admitted
            "expired_queued": 0,     # deadline/watchdog sheds from the queue
            "expired_running": 0,    # watchdog kills of running requests
            "shed_backpressure": 0,  # bounded-queue refusals at submit
            "defer_backoffs": 0,     # DEFER decisions (head now backs off)
            "alloc_denied": 0,       # pool alloc failures (real or injected)
            "failovers": 0,          # health step-downs (mirror of health)
            "degraded_steps": 0,     # steps taken in static degraded mode
            "prefill_chunks": 0,     # chunked-prefill chunks processed
        }

        self._key = jax.random.PRNGKey(scfg.seed)
        temp = float(scfg.temperature)

        def serve_sample(logits, key):
            z = logits[:, -1].astype(jnp.float32)
            if temp <= 0:
                return jnp.argmax(z, axis=-1).astype(jnp.int32)
            return jax.random.categorical(key, z / temp, axis=-1).astype(
                jnp.int32)

        self._sample = jax.jit(serve_sample)
        self._prefills: dict[int, object] = {}
        self._decodes: dict[int, object] = {}
        self._chunks: dict[tuple[int, int], object] = {}

    # ------------------------------------------------------------------

    @property
    def n_running(self) -> int:
        return sum(r is not None for r in self.slots)

    def _has_decodable(self) -> bool:
        return any(r is not None and not self._prefilling[i]
                   for i, r in enumerate(self.slots))

    @property
    def idle(self) -> bool:
        return not self.queue and self.n_running == 0

    def _now(self) -> float:
        return self._clock() + self._skew_s

    @property
    def lost(self) -> int:
        """Zero-lost accounting: submitted requests not in a terminal
        state and no longer queued or running.  Must be 0 always."""
        in_flight = len(self.queue) + self.n_running
        terminal = len(self.finished) + len(self.refused) + len(self.expired)
        return self.submitted - in_flight - terminal

    def submit(self, request: Request) -> Request:
        self.submitted += 1
        request.step_submitted = self._step
        if (self.scfg.max_queue is not None
                and len(self.queue) >= self.scfg.max_queue):
            # Bounded wait queue: shed at the door with a typed refusal
            # rather than queueing work that will only expire later.
            request.state = RequestState.REFUSED
            request.refusal = PlacementRefused(
                f"request {request.rid} refused: wait queue full "
                f"({self.scfg.max_queue} deep) — backpressure",
                {"reason": "queue full", "max_queue": self.scfg.max_queue})
            self.refused.append(request)
            self.counters["shed_backpressure"] += 1
            return request
        self.queue.append(request)
        return request

    # ------------------------------------------------------------------
    # jit memos.  Each program is a named function, so that a profiler
    # trace names its runs ``jit_serve_prefill``, ``jit_serve_chunk``,
    # ``jit_serve_decode`` and ``jit_serve_sample``.

    def _prefill_fn(self, width: int):
        fn = self._prefills.get(width)
        if fn is None:
            cache_len_dim = -(-width // self.kv.block_size) * self.kv.block_size

            def serve_prefill(p, b):
                return T.prefill(p, b, self.cfg, max_len=cache_len_dim)

            fn = jax.jit(serve_prefill)
            self._prefills[width] = fn
        return fn

    def _decode_fn(self, nb: int):
        fn = self._decodes.get(nb)
        if fn is None:
            def serve_decode(p, c, b):
                return T.decode_step(p, c, b, self.cfg)

            fn = jax.jit(serve_decode, donate_argnums=(1,))
            self._decodes[nb] = fn
        return fn

    def _chunk_fn(self, width: int, nb: int):
        # One chunked-prefill trace per (pow2 chunk width, pow2 table
        # width) pair — a B=1, S=width pass through the same paged
        # decode_step path (scatter S tokens, attend causally).
        fn = self._chunks.get((width, nb))
        if fn is None:
            def serve_chunk(p, c, b):
                return T.decode_step(p, c, b, self.cfg)

            fn = jax.jit(serve_chunk, donate_argnums=(1,))
            self._chunks[(width, nb)] = fn
        return fn

    # ------------------------------------------------------------------
    # deadlines, TTL, shedding (requests leave without a crash)

    def _deadline_reason(self, req: Request, now: float) -> str | None:
        t_dl = req.t_deadline
        if t_dl is not None and now > t_dl:
            return f"deadline ({req.deadline_ms:.0f}ms TTL) passed"
        wd = self.scfg.watchdog_ms
        if wd is not None and now > req.t_arrival + wd / 1e3:
            return f"watchdog ({wd:.0f}ms) expired stuck request"
        return None

    def _expire_request(self, req: Request, reason: str) -> None:
        """Typed terminal EXPIRED state: blocks and slot are released, the
        partial output (req.tokens) is retained for the caller."""
        req.state = RequestState.EXPIRED
        req.expiry = reason
        req.t_finished = self._now()
        if req.blocks:
            self.kv.free(req.blocks)
            req.blocks = []
        if req.slot is not None:
            self.slots[req.slot] = None
            self._cache_len[req.slot] = 0
            self._last_tok[req.slot] = 0
            self._prefilling[req.slot] = False
            req.slot = None
        req.prefill_pos = 0
        self.expired.append(req)

    def _expire_sweep(self) -> None:
        now = self._now()
        if self.queue:
            keep: deque[Request] = deque()
            for req in self.queue:
                reason = self._deadline_reason(req, now)
                if reason is None:
                    keep.append(req)
                else:
                    self._expire_request(req, reason)
                    self.counters["expired_queued"] += 1
            self.queue = keep
        for req in list(self.slots):
            if req is None:
                continue
            reason = self._deadline_reason(req, now)
            if reason is not None:
                self._expire_request(req, reason)
                self.counters["expired_running"] += 1

    # ------------------------------------------------------------------
    # admission + prefill (slots join)

    def _refuse(self, req: Request, reason: str, info: dict | None = None,
                *, pop: bool = True) -> None:
        if pop:
            self.queue.popleft()
        req.state = RequestState.REFUSED
        req.refusal = PlacementRefused(
            f"request {req.rid} (prompt={req.prompt_len}, "
            f"max_new={req.max_new_tokens}) refused: {reason}",
            dict(info or {}, reason=reason))
        self.refused.append(req)

    def _admissions(self) -> None:
        while self.queue and None in self.slots:
            req = self.queue[0]
            if req.retry_at_step > self._step:
                break        # DEFER backoff: FIFO head holds the line
            if req.state is not RequestState.PREEMPTED:
                # Context-window check in the engine itself, not only the
                # scheduler: an ungated engine (cost_engine=None) must
                # REFUSE an oversized prompt cleanly instead of crashing
                # in ``_prefill_into`` (width - prompt_len goes negative).
                need = req.prompt_len + req.max_new_tokens
                if need > self.scfg.max_len:
                    self._refuse(req, f"needs {need} tokens > "
                                      f"max_len={self.scfg.max_len}")
                    continue
                # Pool-capacity check: a request whose lifetime footprint
                # exceeds the ENTIRE pool can never be packed — retrying
                # it every step is a livelock, so REFUSE it now.
                need_blocks = self.kv.blocks_for(min(need, self.scfg.max_len))
                if need_blocks > self.kv.usable_blocks:
                    self._refuse(
                        req, f"pool capacity: needs {need_blocks} KV blocks "
                             f"> pool of {self.kv.usable_blocks}",
                        {"need_blocks": need_blocks,
                         "pool_blocks": self.kv.usable_blocks})
                    continue
                if self.scheduler is not None:
                    decision, info = self.scheduler.admit(
                        req, n_running=self.n_running)
                    if decision is Decision.REFUSE:
                        self.queue.popleft()
                        req.state = RequestState.REFUSED
                        req.refusal = self.scheduler.refusal(req, info)
                        self.refused.append(req)
                        continue
                    if decision is Decision.DEFER:
                        # Exponential backoff: don't re-price the same
                        # head every step while occupancy drains.
                        req.defer_retries += 1
                        req.retry_at_step = self._step + min(
                            1 << (req.defer_retries - 1),
                            self.scfg.defer_backoff_cap)
                        self.counters["defer_backoffs"] += 1
                        break
            # Incremental allocation: only what the prefill itself needs
            # (+ the first decode write) — the rest is allocated as the
            # request grows, with preemption backstopping shortfalls.
            total = req.prompt_len + req.n_generated
            blocks = self.kv.alloc(self.kv.blocks_for(
                min(total + 1, self.scfg.max_len)))
            if blocks is None:
                self.counters["alloc_denied"] += 1
                break                      # pool busy: retry next step
            self.queue.popleft()
            req.blocks = blocks
            if req.state is RequestState.PREEMPTED:
                self.counters["resumes"] += 1
            req.state = RequestState.ADMITTED
            if req.admit_seq is None:      # age = FIRST admission order
                req.admit_seq = self._admit_seq
                self._admit_seq += 1
                req.t_admitted = self._now()
                req.step_admitted = self._step
            self._prefill_into(req, self.slots.index(None))

    def _prefill_into(self, req: Request, slot: int) -> None:
        # A resumed request re-prefills over prompt + generated tokens
        # (recompute-on-resume): the logits at the last position then
        # continue the decode exactly where preemption cut it.
        seq = req.sequence()
        S = len(seq)
        chunk = self.scfg.prefill_chunk
        if chunk is not None and S > chunk:
            # Chunked prefill: occupy the slot now and feed the prompt in
            # ``chunk``-sized pieces interleaved with decode steps
            # (``_prefill_chunks``) — running slots' TPOT is bounded by
            # one chunk's cost, not this whole prompt's.  Resumed
            # requests restart from 0 (recompute-on-resume, same as the
            # solo path).
            req.state = RequestState.RUNNING
            req.slot = slot
            req.prefill_pos = 0
            self.slots[slot] = req
            self._prefilling[slot] = True
            self._cache_len[slot] = 0
            self._last_tok[slot] = 0
            return
        width = min(_next_pow2(max(S, self.kv.block_size)),
                    -(-self.scfg.max_len // self.kv.block_size)
                    * self.kv.block_size)
        with TraceAnnotation("serve.prefill", rid=req.rid, width=width):
            if self._has_decodable():
                self.max_prefill_stall_tokens = max(
                    self.max_prefill_stall_tokens, width)
            pad = width - S
            tokens = np.zeros((1, width), np.int32)
            tokens[0, pad:] = seq
            out = self._prefill_fn(width)(self.params, {
                "tokens": jnp.asarray(tokens),
                "pos_offset": jnp.asarray([pad], jnp.int32),
            })
        self._key, sub = jax.random.split(self._key)
        with TraceAnnotation("serve.sync"):
            tok = int(np.asarray(self._sample(out["logits"], sub))[0])
        req.state = RequestState.RUNNING
        req.slot = slot
        req.tokens.append(tok)
        if req.t_first_token is None:
            req.t_first_token = self._now()
            req.step_first_token = self._step
        self.kv.pack_prefill(out["cache"], req.blocks,
                             prompt_len=S, pad=pad)
        self.slots[slot] = req
        self._cache_len[slot] = S
        self._last_tok[slot] = tok
        self._retire_if_done(req)   # max_new_tokens=1 / instant EOS

    def _prefill_chunks(self) -> None:
        """Advance every mid-prefill slot by one chunk.

        Chunks ride the paged S > 1 ``decode_step`` path: the chunk's KV
        scatters straight into the request's blocks (no ``pack_prefill``),
        right-padded to a pow2 width.  Junk positions sit beyond every
        real token, so the causal mask never attends them, and the table
        is sized to cover the padded width — writes past the row's own
        blocks route to scratch block 0.  The final chunk samples the
        first new token from the last *real* position, exactly where the
        solo prefill samples, so greedy streams are unchanged."""
        chunk = self.scfg.prefill_chunk
        bs = self.kv.block_size
        for slot in np.flatnonzero(self._prefilling):
            slot = int(slot)
            req = self.slots[slot]
            seq = req.sequence()
            s0 = req.prefill_pos
            clen = min(chunk, len(seq) - s0)
            width = _next_pow2(clen)
            nb = _next_pow2((s0 + width - 1) // bs + 1)
            with TraceAnnotation("serve.chunk", rid=req.rid, width=width,
                                 nb=nb):
                if self._has_decodable():
                    self.max_prefill_stall_tokens = max(
                        self.max_prefill_stall_tokens, width)
                tokens = np.zeros((1, width), np.int32)
                tokens[0, :clen] = seq[s0:s0 + clen]
                table = np.zeros((1, nb), np.int32)   # pad → scratch block 0
                table[0, :len(req.blocks[:nb])] = req.blocks[:nb]
                table = jnp.asarray(table)
                logits, self.kv.pool = self._chunk_fn(width, nb)(
                    self.params, self.kv.pool, {
                        "tokens": jnp.asarray(tokens),
                        "cache_len": jnp.asarray([s0], jnp.int32),
                        "block_table": table,
                    })
            self.counters["prefill_chunks"] += 1
            req.prefill_pos = s0 + clen
            self._cache_len[slot] = req.prefill_pos
            if req.prefill_pos < len(seq):
                continue
            # Final chunk: sample the first new token; the slot joins the
            # decodable set from the next _decode_once on.
            self._key, sub = jax.random.split(self._key)
            with TraceAnnotation("serve.sync"):
                tok = int(np.asarray(self._sample(logits[:, clen - 1:clen],
                                                  sub))[0])
            self._prefilling[slot] = False
            req.prefill_pos = 0
            req.tokens.append(tok)
            if req.t_first_token is None:
                req.t_first_token = self._now()
                req.step_first_token = self._step
            self._cache_len[slot] = len(seq)
            self._last_tok[slot] = tok
            self._retire_if_done(req)

    # ------------------------------------------------------------------
    # preemption under pool pressure (slots leave involuntarily)

    def _preempt(self, req: Request) -> None:
        """Evict a running request: blocks back to the pool, generated
        tokens retained, re-queued at the head (resume priority over new
        arrivals — and over younger preemptees pushed earlier)."""
        self.counters["preemptions"] += 1
        req.preemptions += 1
        if req.blocks:
            self.kv.free(req.blocks)
            req.blocks = []
        if req.slot is not None:
            self.slots[req.slot] = None
            self._cache_len[req.slot] = 0
            self._last_tok[req.slot] = 0
            self._prefilling[req.slot] = False
            req.slot = None
        req.prefill_pos = 0          # chunked prefill restarts on resume
        req.state = RequestState.PREEMPTED
        self.queue.appendleft(req)

    def _youngest_running(self) -> Request | None:
        alive = [r for r in self.slots if r is not None]
        if not alive:
            return None
        return max(alive, key=lambda r: r.admit_seq)

    def _grow_blocks(self) -> None:
        """Before decoding, make sure every occupied slot owns the block
        its next KV write lands in.  A pool shortfall preempts the
        youngest running request (possibly the grower itself) — never the
        oldest while younger victims exist, so the oldest always
        progresses."""
        order = sorted(
            (i for i, r in enumerate(self.slots) if r is not None),
            key=lambda i: self.slots[i].admit_seq)
        for i in order:
            req = self.slots[i]
            if req is None:
                continue               # already taken as a victim
            need_idx = int(self._cache_len[i]) // self.kv.block_size
            while req.slot is not None and len(req.blocks) <= need_idx:
                got = self.kv.alloc(1)
                if got is not None:
                    req.blocks.extend(got)
                    continue
                self.counters["alloc_denied"] += 1
                victim = self._youngest_running()
                if victim is None or victim is req:
                    self._preempt(req)     # nobody younger: yield itself
                    break
                self._preempt(victim)      # then retry the alloc

    # ------------------------------------------------------------------
    # decode (all occupied slots advance one token)

    def _decode_once(self) -> None:
        with TraceAnnotation("serve.grow"):
            self._grow_blocks()
        # Mid-prefill slots are occupied but not decodable: their table
        # rows stay empty (scratch) and cache_len is masked to 0, so the
        # batched step writes their junk token to scratch block 0.
        active = [i for i, r in enumerate(self.slots)
                  if r is not None and not self._prefilling[i]]
        if not active:
            return
        nb_need = max(int(self._cache_len[i]) // self.kv.block_size + 1
                      for i in active)
        nb = min(_next_pow2(nb_need), self.kv.blocks_per_seq)
        with TraceAnnotation("serve.decode", rows=len(active), nb=nb):
            decodable = np.array([r is not None and not self._prefilling[i]
                                  for i, r in enumerate(self.slots)])
            table = self.kv.table_array(
                [r.blocks[:nb] if decodable[i] else []
                 for i, r in enumerate(self.slots)], nb)
            batch = {
                "tokens": jnp.asarray(self._last_tok[:, None]),
                "cache_len": jnp.asarray(
                    np.where(decodable, self._cache_len, 0).astype(np.int32)),
                "block_table": table,
            }
            per_block = self.kv.bytes / self.kv.n_blocks
            self.kv_gathered_bytes += len(self.slots) * nb * per_block
            self.kv_touched_bytes += per_block * sum(
                int(self._cache_len[i]) // self.kv.block_size + 1
                for i in active)
            logits, self.kv.pool = self._decode_fn(nb)(
                self.params, self.kv.pool, batch)
        self._key, sub = jax.random.split(self._key)
        with TraceAnnotation("serve.sync"):
            toks = np.asarray(self._sample(logits, sub))
        self.decode_steps += 1
        with TraceAnnotation("serve.commit"):
            now = self._now()
            for i in active:
                req = self.slots[i]
                tok = int(toks[i])
                req.tokens.append(tok)
                self._cache_len[i] += 1
                self._last_tok[i] = tok
                self._retire_if_done(req, now)

    def _retire_if_done(self, req: Request, now: float | None = None) -> None:
        done = (req.tokens[-1] == self.scfg.eos_id
                or req.n_generated >= req.max_new_tokens
                or req.prompt_len + req.n_generated >= self.scfg.max_len)
        if not done:
            return
        req.state = RequestState.FINISHED
        req.t_finished = now if now is not None else self._now()
        self.kv.free(req.blocks)
        req.blocks = []
        if req.slot is not None:
            self.slots[req.slot] = None
            self._cache_len[req.slot] = 0
            self._last_tok[req.slot] = 0
        self.finished.append(req)

    # ------------------------------------------------------------------

    def step(self) -> None:
        """One engine iteration: expire stale work, admit+prefill into
        free slots, then one ragged decode step for every occupied slot.

        Each phase is a host span in the profiler's trace (``serve.*``,
        docs/serve.md "Observability"); with the profiler off a span
        costs under a microsecond.

        Every failure the fault plan can inject here — pool-allocation
        denial, backend exceptions, slow steps — is handled inside the
        call: nothing escapes ``step`` short of a real model bug."""
        self._step += 1
        with TraceAnnotation("serve.step", step=self._step):
            if self.faults is not None:
                self.faults.begin_step(self._step)
                self._skew_s += float(self.faults.fire("slow"))
            with TraceAnnotation("serve.expire"):
                self._expire_sweep()
            with TraceAnnotation("serve.admit"):
                self._admissions()
            self._prefill_chunks()
            decodable_before = self._has_decodable()
            before = self.decode_steps
            self._decode_once()
            if (decodable_before and self.decode_steps == before
                    and self._has_decodable()):
                # A decodable slot existed, survived the step, and still
                # no decode ran — a genuine stall (0 by construction:
                # chunked prefill interleaves with decode instead of
                # displacing it).
                self._stall_run += 1
                self.max_decode_stall_steps = max(
                    self.max_decode_stall_steps, self._stall_run)
            else:
                self._stall_run = 0
            if self.failover is not None:
                self.counters["failovers"] = self.failover.health.failovers
                if self.failover.degraded:
                    self.counters["degraded_steps"] += 1

    def run(self, requests: list[Request] | None = None, *,
            max_steps: int = 100_000) -> list[Request]:
        """Drain: submit ``requests`` (if given) and step until idle."""
        for r in requests or ():
            self.submit(r)
        for _ in range(max_steps):
            if self.idle:
                break
            self.step()
        return self.finished

    # ------------------------------------------------------------------

    def metrics(self) -> dict:
        ttfts = [r.ttft_s for r in self.finished if r.ttft_s is not None]
        tpots = [r.tpot_s for r in self.finished if r.tpot_s is not None]

        def pct(xs, q):
            return float(np.percentile(xs, q)) if xs else float("nan")

        out = {
            "finished": len(self.finished),
            "refused": len(self.refused),
            "expired": len(self.expired),
            "submitted": self.submitted,
            "lost": self.lost,
            "decode_steps": self.decode_steps,
            "tokens_out": sum(r.n_generated for r in self.finished),
            "ttft_p50_ms": pct(ttfts, 50) * 1e3,
            "ttft_p99_ms": pct(ttfts, 99) * 1e3,
            "tpot_p50_ms": pct(tpots, 50) * 1e3,
            "tpot_p99_ms": pct(tpots, 99) * 1e3,
            "kv_bytes": self.kv.bytes,
            "kv_dense_bytes": self.kv.dense_bytes,
            "block_size": self.kv.block_size,
            "max_decode_stall_steps": self.max_decode_stall_steps,
            "max_prefill_stall_tokens": self.max_prefill_stall_tokens,
            "kv_gathered_bytes": self.kv_gathered_bytes,
            "kv_touched_bytes": self.kv_touched_bytes,
            **self.counters,
        }
        if self.failover is not None:
            out["health"] = self.failover.metrics()
        if self.faults is not None:
            out["faults"] = self.faults.summary()
        return out
