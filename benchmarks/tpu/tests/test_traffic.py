"""The traffic generator: seeds change the order, not the work."""

import numpy as np
import pytest

import traffic

MIX = {"rate_per_s": 6.0, "block_s": 5.0, "pattern": 3,
       "prompt": {"dist": "lognormal", "median": 384, "sigma": 0.8,
                  "lo": 32, "hi": 1792},
       "output": {"dist": "lognormal", "median": 96, "sigma": 0.8,
                  "lo": 16, "hi": 512}}


def test_seeds_share_sizes_and_gaps():
    a = traffic.open_loop(MIX, 1, 30.0, 1000, 2048)
    b = traffic.open_loop(MIX, 2**31 + 77, 30.0, 1000, 2048)
    assert len(a) == len(b) == 180
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in b)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    assert np.allclose(sorted(np.diff([0] + [r.due for r in a])),
                       sorted(np.diff([0] + [r.due for r in b])))
    assert all(0 < r.due < 30.0 for r in a)
    assert all(len(r.prompt) + r.max_new_tokens <= 2048 for r in a)


def test_same_seed_same_requests():
    a = traffic.open_loop(MIX, 5, 10.0, 1000, 2048)
    b = traffic.open_loop(MIX, 5, 10.0, 1000, 2048)
    assert all((x.prompt == y.prompt).all() and x.due == y.due
               and x.max_new_tokens == y.max_new_tokens for x, y in zip(a, b))


def test_lengths_follow_the_distribution():
    p = traffic.lengths({"dist": "uniform", "lo": 1024, "hi": 1536}, 512,
                        3, 1)
    assert p.min() == 1024 and p.max() == 1536
    q = traffic.lengths(MIX["prompt"], 1001, 3, 1)
    assert np.median(q) == 384 and q.min() >= 32 and q.max() <= 1792


def test_closed_loop_queues():
    qs = traffic.closed_loop({"clients": 3, "per_client": 4,
                              "prompt": {"dist": "uniform", "lo": 8, "hi": 9},
                              "output": {"dist": "uniform", "lo": 2,
                                         "hi": 3}}, 7, 100, 64)
    assert [len(q) for q in qs] == [4, 4, 4]
    assert all(r.due is None for q in qs for r in q)


def test_closed_loop_rounds_hold_the_same_sizes_for_every_seed():
    mix = {"clients": 24, "per_client": 16,
           "prompt": {"dist": "uniform", "lo": 1024, "hi": 1536},
           "output": {"dist": "uniform", "lo": 384, "hi": 512}}
    a = traffic.closed_loop(mix, 1, 100, 2048)
    b = traffic.closed_loop(mix, 2**31 + 77, 100, 2048)
    for r in range(1, 16):
        pa = sorted(len(q[r].prompt) for q in a)
        assert pa == sorted(len(q[r].prompt) for q in b)
        assert sorted(q[r].max_new_tokens for q in a) == sorted(
            q[r].max_new_tokens for q in b)
    assert [len(q[1].prompt) for q in a] != [len(q[1].prompt) for q in b]
    allp = sorted(len(r.prompt) for q in a for r in q[1:])
    quant = traffic._quantiles(mix["prompt"], 24 * 16)
    assert allp == sorted(int(x) for r in range(1, 16) for x in quant[r::16])


def test_first_requests_start_at_even_phases():
    mix = {"clients": 10, "per_client": 4,
           "prompt": {"dist": "uniform", "lo": 1024, "hi": 1536},
           "output": {"dist": "uniform", "lo": 384, "hi": 512}}
    qs = traffic.closed_loop(mix, 11, 1000, 2048)
    p = traffic.lengths(mix["prompt"], 40, 11, 1, blocks=4)[:10]
    o = traffic.lengths(mix["output"], 40, 11, 2, blocks=4)[:10]
    for c, q in enumerate(qs):
        served = int((c + 0.5) / 10 * o[c])
        assert len(q[0].prompt) == p[c] + served
        assert q[0].max_new_tokens == o[c] - served
        assert [len(r.prompt) for r in q[1:]] == [
            int(x) for x in traffic.lengths(mix["prompt"], 40, 11, 1,
                                            blocks=4)[10 + c::10]]


def test_pattern_is_one_order_rotated_by_the_seed():
    mix = MIX
    a = traffic.open_loop(mix, 1, 50.0, 1000, 2048)
    b = traffic.open_loop(mix, 2**31 + 77, 50.0, 1000, 2048)
    pa, pb = [len(r.prompt) for r in a], [len(r.prompt) for r in b]
    oa, ob = [r.max_new_tokens for r in a], [r.max_new_tokens for r in b]
    assert pa != pb
    turn = next(k for k in range(len(pa))
                if pa[k:] + pa[:k] == pb and oa[k:] + oa[:k] == ob)
    ga = np.diff([0] + [r.due for r in a])
    gb = np.diff([0] + [r.due for r in b])
    assert np.allclose(np.roll(ga, -turn), gb)
    assert not (a[0].prompt[:8] == b[turn].prompt[:8]).all()
    c = traffic.open_loop(mix, 1, 50.0, 1000, 2048)
    assert [len(r.prompt) for r in c] == pa
