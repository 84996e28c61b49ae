"""The traffic generator and the percentile arithmetic."""
from __future__ import annotations

import json
import os

import numpy as np
import pytest

from bench import generate
from bench.spec import BENCH_DIR
from bench.stats import percentile

MIXES = sorted(f[:-5] for f in os.listdir(os.path.join(BENCH_DIR, "traffic")))
SEED = 2**33 + 17            # seeds run past 32 bits


def _mix(name):
    with open(os.path.join(BENCH_DIR, "traffic", name + ".json")) as f:
        return json.load(f)


def _ranges(mix):
    c = mix
    pre = c.get("prefix")
    lo, hi = c["prompt"]["min"], c["prompt"]["max"]
    if pre:
        lo += pre["length"]["min"]
        hi += pre["length"]["max"]
    return lo, hi, c["output"]["min"], c["output"]["max"]


def _in_flight(mix):
    return mix["clients"] if mix["loop"] == "closed" else \
        mix.get("start_in_flight", 0)


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_schedule(name):
    mix = _mix(name)
    a = generate.plan(mix, SEED, 1000, 45)
    b = generate.plan(mix, SEED, 1000, 45)
    c = generate.plan(mix, SEED + 1, 1000, 45)
    key = [(p.rid, p.due, p.client, p.max_new, p.tokens()) for p in a[:40]]
    assert key == [(p.rid, p.due, p.client, p.max_new, p.tokens())
                   for p in b[:40]]
    assert key != [(p.rid, p.due, p.client, p.max_new, p.tokens())
                   for p in c[:40]]


@pytest.mark.parametrize("name", MIXES)
def test_lengths_stay_in_their_ranges(name):
    mix = _mix(name)
    lo, hi, olo, ohi = _ranges(mix)
    reqs = generate.plan(mix, SEED, 1000, 45)
    plen = [len(p.tokens()) for p in reqs[:200]]
    n0 = _in_flight(mix)
    out = [p.max_new for p in reqs[n0:]]
    assert lo <= min(plen) and max(plen) <= hi
    assert olo <= min(out) and max(out) <= ohi
    assert all(1 <= p.max_new <= ohi for p in reqs[:n0])
    assert plen[0] + ohi < mix["engine"]["max_len"] or \
        hi + ohi < mix["engine"]["max_len"]
    assert hi + ohi < mix["engine"]["max_len"]   # no request is refused
    toks = reqs[0].tokens()
    assert min(toks) >= generate.FIRST_ID and max(toks) < 1000


@pytest.mark.parametrize("name", MIXES)
def test_two_seeds_send_the_same_work(name):
    """Each block of requests has the same multiset of lengths and, in
    an open loop, of gaps between arrivals: the seed changes the order
    and the ids, not the work."""
    mix = _mix(name)
    a = generate.plan(mix, 1, 1000, 45)
    b = generate.plan(mix, 2, 1000, 45)
    n0, n = _in_flight(mix), generate.BLOCK
    for part in (slice(0, n0), slice(n0, n0 + n)):
        assert sorted(p.max_new for p in a[part]) == \
            sorted(p.max_new for p in b[part])
        assert sorted(p.prompt_len for p in a[part]) == \
            sorted(p.prompt_len for p in b[part])
    if mix["loop"] == "open":
        ga = np.diff([0.0] + [p.due for p in a[n0:n0 + n]])
        gb = np.diff([0.0] + [p.due for p in b[n0:n0 + n]])
        np.testing.assert_allclose(sorted(ga), sorted(gb), rtol=1e-9)
        assert len(a) == len(b)


def test_open_loop_rate_and_closed_loop_clients():
    mix = _mix("chat")
    reqs = generate.plan(mix, SEED, 1000, 45)
    due = [p.due for p in reqs]
    assert due == sorted(due)
    n_win = sum(10 <= t < 55 for t in due)
    assert abs(n_win - mix["rate_rps"] * 45) <= 0.1 * mix["rate_rps"] * 45
    closed = _mix("batch32")
    reqs = generate.plan(closed, SEED, 1000, 45)
    assert {p.client for p in reqs} == set(range(closed["clients"]))
    assert all(p.due is None for p in reqs)


def test_shared_prefixes_follow_zipf():
    mix = _mix("sharedprefix")
    reqs = generate.plan(mix, SEED, 1000, 45)[:generate.BLOCK]
    pre = mix["prefix"]
    heads = {}
    for p in reqs:
        key = tuple(p.prefix[:8])
        heads[key] = heads.get(key, 0) + 1
    assert len(heads) == pre["count"]
    counts = sorted(heads.values(), reverse=True)
    w = 1.0 / np.arange(1, pre["count"] + 1)
    want = w / w.sum() * len(reqs)
    assert all(abs(c - x) <= 1 for c, x in zip(counts, want, strict=True))
    assert {len(p.prefix) for p in reqs} <= set(
        range(pre["length"]["min"], pre["length"]["max"] + 1))


@pytest.mark.parametrize("dist", [
    {"dist": "lognormal", "median": 256, "sigma": 0.7, "min": 32,
     "max": 1024},
    {"dist": "uniform", "min": 16, "max": 64}])
def test_residual_lengths_are_the_length_biased_remainder(dist):
    """What is left of an answer caught mid-way has the mean
    E[L(L + 1)] / (2 E[L]) and never exceeds the longest answer."""
    L = generate.lengths(dist, 4096).astype(float)
    r = generate.residual_lengths(dist, 4096)
    # a token drawn uniformly from the answers leaves 1..L of its own
    want = (L * L + L).mean() / (2 * L.mean())
    assert r.mean() == pytest.approx(want, rel=0.01)
    assert 1 <= r.min() and r.max() <= dist["max"]


def test_requests_in_flight_at_the_start():
    mix = dict(_mix("chat"), start_in_flight=20)
    reqs = generate.plan(mix, SEED, 1000, 45)
    assert [p.due for p in reqs[:20]] == [0.0] * 20
    assert all(p.due > 0 for p in reqs[20:])
    want = sorted(generate.residual_lengths(mix["output"], 20))
    assert sorted(p.max_new for p in reqs[:20]) == want
    closed = _mix("batch32")
    reqs = generate.plan(closed, SEED, 1000, 45)
    first = reqs[:closed["clients"]]
    assert sorted(p.client for p in first) == list(range(closed["clients"]))
    assert sorted(p.max_new for p in first) == sorted(
        generate.residual_lengths(closed["output"], closed["clients"]))


@pytest.mark.parametrize("n", [1, 2, 7, 100, 1001])
def test_percentile_matches_the_front_end(n):
    from repro.serving.frontend.metrics import _percentile
    v = list(np.random.default_rng(n).exponential(size=n))
    for q in (0, 50, 90, 95, 99, 100):
        assert percentile(v, q) == _percentile(v, q)
    assert percentile([], 50) is None
