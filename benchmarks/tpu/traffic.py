"""The one traffic generator: requests (and their arrival times) from a
cell's ``traffic`` parameters and ``--seed``.

Every seed gets the same multiset of sizes and gaps: lengths are the
distribution's quantiles at ``(i + 0.5) / n`` and Poisson inter-arrival
gaps the exponential's quantiles.  So two seeds differ in order and
token ids, not in how much work a run holds.  The order is stratified:
the sorted quantiles are dealt round-robin into consecutive blocks (a
closed loop's rounds, an open loop's stretches of ``block_s`` seconds)
and shuffled within each block, so every block holds one value from
each stratum.

A closed loop's order is the seed's.  Its clients start at even phases
of a request's life, as a loop that has run for a while has them:
client ``c`` of ``C`` sends its first request with the share
``(c + 0.5) / C`` of its output already served, moved into its prompt.
Completions, admissions and chunked prefills then come at a steady rate
from the first step, instead of the whole cohort finishing together.

An open loop's order is one drawn from the traffic's ``pattern``
number, the same for every seed, which the seed rotates.  Every seed
then sends the same sequence of bursts and long prompts, begun at
another point of it, so that a tail over a few dozen requests reads the
system, not which requests the seed made neighbours.

Length distributions (one JSON object each):

* ``{"dist": "uniform", "lo": a, "hi": b}`` — integers in ``[a, b]``;
* ``{"dist": "lognormal", "median": m, "sigma": s, "lo": a, "hi": b}`` —
  ``exp(N(log m, s))`` clipped to ``[a, b]``.

A closed loop's traffic gives ``clients`` and ``per_client``; an open
loop's ``rate_per_s``, ``block_s`` and ``pattern``.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist

import numpy as np

__all__ = ["Spec", "lengths", "closed_loop", "open_loop", "gaps"]


@dataclass
class Spec:
    """One request: ``due`` seconds after the window opens (open loop) or
    ``None`` (closed loop: sent when its client's previous one ends)."""
    prompt: np.ndarray
    max_new_tokens: int
    due: float | None = None


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed), stream]))


def _quantiles(dist: dict, n: int) -> np.ndarray:
    u = (np.arange(n) + 0.5) / n
    kind = dist["dist"]
    if kind == "uniform":
        lo, hi = int(dist["lo"]), int(dist["hi"])
        return np.floor(lo + u * (hi - lo + 1)).astype(np.int64)
    if kind == "lognormal":
        nd = NormalDist(math.log(dist["median"]), dist["sigma"])
        x = np.exp([nd.inv_cdf(p) for p in u])
        return np.clip(np.rint(x), dist["lo"], dist["hi"]).astype(np.int64)
    raise ValueError(f"unknown length distribution {kind!r}")


def _stratified(q: np.ndarray, blocks: int, rng) -> np.ndarray:
    """Sorted ``q`` dealt round-robin into ``blocks`` consecutive blocks,
    each shuffled."""
    return np.concatenate([rng.permutation(q[b::blocks])
                           for b in range(blocks)])


def lengths(dist: dict, n: int, seed: int, stream: int,
            blocks: int = 1) -> np.ndarray:
    """``n`` lengths: the distribution's quantiles in a seeded order."""
    return _stratified(_quantiles(dist, n), blocks, _rng(seed, stream))


def gaps(rate: float, n: int, seed: int, blocks: int = 1) -> np.ndarray:
    """``n`` Poisson inter-arrival gaps (s): exponential quantiles at
    ``rate``, in a seeded order."""
    u = (np.arange(n) + 0.5) / n
    return _stratified(-np.log1p(-u) / rate, blocks, _rng(seed, 3))


def _specs(p: np.ndarray, o: np.ndarray, seed: int, vocab: int,
           max_len: int) -> list[Spec]:
    # A request never asks for more than the context holds: the output is
    # cut to what the prompt leaves, so no request is refused for length.
    o = np.minimum(o, max_len - p)
    if (o < 1).any():
        raise ValueError("a prompt fills the whole context window")
    tok = _rng(seed, 4)
    return [Spec(tok.integers(0, vocab, int(pl), dtype=np.int32), int(ol))
            for pl, ol in zip(p, o)]


def _requests(traffic: dict, n: int, seed: int, vocab: int,
              max_len: int, blocks: int = 1) -> list[Spec]:
    return _specs(lengths(traffic["prompt"], n, seed, 1, blocks),
                  lengths(traffic["output"], n, seed, 2, blocks), seed,
                  vocab, max_len)


def closed_loop(traffic: dict, seed: int, vocab: int,
                max_len: int) -> list[list[Spec]]:
    """One queue of requests per client: ``clients`` queues of
    ``per_client`` requests each, which the client sends in turn and
    then again from the start.  The ``r``-th requests of all clients are
    one block of the stratified order; the first ones are staggered."""
    c, k = int(traffic["clients"]), int(traffic["per_client"])
    reqs = _requests(traffic, c * k, seed, vocab, max_len, blocks=k)
    queues = [reqs[i::c] for i in range(c)]
    tok = _rng(seed, 5)
    for i, q in enumerate(queues):
        r = q[0]
        served = int((i + 0.5) / c * r.max_new_tokens)
        q[0] = Spec(np.concatenate([r.prompt, tok.integers(
            0, vocab, served, dtype=np.int32)]), r.max_new_tokens - served)
    return queues


def open_loop(traffic: dict, seed: int, seconds: float, vocab: int,
              max_len: int, rate: float | None = None) -> list[Spec]:
    """Requests due inside a window of ``seconds`` at ``rate`` per second
    (the cell's fixed ``rate_per_s`` unless given): ``round(rate *
    seconds)`` arrivals whose gaps are the exponential's quantiles,
    stratified over blocks of about ``block_s`` seconds, in the
    ``pattern``'s order rotated by the seed."""
    rate = float(traffic["rate_per_s"] if rate is None else rate)
    n = max(1, round(rate * seconds))
    blocks = max(1, min(n, round(seconds / traffic["block_s"])))
    order = traffic["pattern"]
    turn = int(_rng(seed, 6).integers(n))
    g, p, o = (np.roll(x, -turn) for x in (
        gaps(rate, n, order, blocks),
        lengths(traffic["prompt"], n, order, 1, blocks),
        lengths(traffic["output"], n, order, 2, blocks)))
    # scale the gaps so the last arrival falls inside the window
    due = np.cumsum(g) * (seconds / (g.sum() + g.mean()))
    reqs = _specs(p, o, seed, vocab, max_len)
    for r, t in zip(reqs, due):
        r.due = float(t)
    return reqs
