"""Async serving front end: radix prefix cache, SLO scheduler,
metrics, and the thread-pumped AsyncEngine — including the acceptance
properties (async greedy outputs bit-identical to ``Engine.run``,
preemption+resume losslessness, prefix forks from *historical*
requests).

Unit layers (allocator-only radix, fake-engine scheduler, fake-clock
metrics) need no jax graphs; the integration layer reuses one reduced
2-layer model per module like tests/test_paged.py.
"""
import asyncio
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs.base import get_arch, reduced
from repro.models.model import build_model
from repro.serving.engine import Engine, Request
from repro.serving.frontend import (AsyncEngine, FIFOScheduler, RadixCache,
                                    ServingMetrics, SLOScheduler, Ticket)
from repro.serving.paged import BlockAllocator

BS = 4


# ------------------------------------------------------ radix cache (unit)

def test_radix_insert_match_pin_lifecycle():
    a = BlockAllocator(num_blocks=10, block_size=BS)
    rc = RadixCache(a, BS)
    ids = a.alloc(2)
    toks = list(range(100, 108))            # 2 full blocks
    assert rc.insert(toks, ids) == 2
    assert a.pincount(ids[0]) == a.pincount(ids[1]) == 1
    a.free(ids)                             # owner finishes...
    assert a.num_free == 7                  # ...pins keep blocks live
    assert rc.match(toks + [7, 8]) == ids   # whole-prefix hit
    assert rc.match(toks[:BS] + [55] * BS) == ids[:1]   # partial hit
    assert rc.match([55] * 8) == []
    # max_blocks caps both the result and the offered-stats
    before = rc.lookup_blocks
    assert rc.match(toks, max_blocks=1) == ids[:1]
    assert rc.lookup_blocks == before + 1
    # dedup: same path inserted again keeps the incumbent, pins nothing
    ids2 = a.alloc(2)
    assert rc.insert(toks, ids2) == 0
    a.free(ids2)
    assert rc.match(toks + [9]) == ids
    # whole blocks only
    with pytest.raises(ValueError, match="whole blocks"):
        rc.insert(toks[:BS + 1], ids[:1])
    assert rc.clear() == 2                  # unpins everything
    assert a.num_free == a.num_usable
    assert len(rc) == 0


def test_radix_lru_evicts_least_recent_leaf():
    a = BlockAllocator(num_blocks=10, block_size=BS)
    rc = RadixCache(a, BS)
    cold = a.alloc(1)
    hot = a.alloc(2)                        # shared root + hot leaf
    rc.insert([1] * BS, cold)
    rc.insert([2] * BS + [3] * BS, hot)
    a.free(cold), a.free(hot)
    rc.match([2] * BS + [3] * BS)           # touch the hot path
    assert rc.evict(1) == 1                 # cold leaf goes first
    assert rc.match([1] * BS) == []
    assert rc.match([2] * BS + [3] * BS) == hot
    # evicting again removes the hot *leaf* before its parent
    assert rc.evict(1) == 1
    assert rc.match([2] * BS + [3] * BS) == hot[:1]
    assert rc.evict(5) == 1                 # parent now a leaf; tree empty
    assert len(rc) == 0 and a.num_free == a.num_usable


# -------------------------------------------------------- scheduler (unit)

class _FakeEngine:
    """Slot/budget admission stub: a request costs ``len(tokens)``
    budget units — enough to exercise scan-past-blocked-head and
    preemption without jax."""

    def __init__(self, slots=2, budget=10):
        self.slot_req = [None] * slots
        self.budget = budget

    def _free_slot(self):
        free = [i for i, r in enumerate(self.slot_req) if r is None]
        return free[0] if free else None

    def admit(self, req):
        s = self._free_slot()
        if s is None or len(req.tokens) > self.budget:
            return False
        self.budget -= len(req.tokens)
        self.slot_req[s] = req
        return True

    def preempt(self, slot):
        req = self.slot_req[slot]
        req.finish_reason = "preempted"
        self.budget += len(req.tokens)
        self.slot_req[slot] = None
        return req


def _ticket(rid, cost, priority=0, deadline=None, seq=0):
    return Ticket(req=Request(rid=rid, tokens=[1] * cost),
                  priority=priority, deadline=deadline, seq=seq)


def test_fifo_head_blocks_slo_scans_past():
    big, small = _ticket(0, 9, seq=1), _ticket(1, 2, seq=2)
    fifo = FIFOScheduler()
    fifo.submit(big), fifo.submit(small)
    rep = fifo.step(_FakeEngine(budget=4))
    assert rep.admitted == [] and len(fifo) == 2   # head-of-line block

    slo = SLOScheduler()
    slo.submit(_ticket(0, 9, seq=1)), slo.submit(_ticket(1, 2, seq=2))
    rep = slo.step(_FakeEngine(budget=4))
    assert [t.req.rid for t in rep.admitted] == [1]
    assert [t.req.rid for t in slo.pending] == [0]


def test_slo_orders_by_priority_then_deadline():
    eng = _FakeEngine(slots=1, budget=100)
    slo = SLOScheduler()
    slo.submit(_ticket(0, 2, priority=0, seq=1))
    slo.submit(_ticket(1, 2, priority=1, deadline=9.0, seq=2))
    slo.submit(_ticket(2, 2, priority=1, deadline=3.0, seq=3))
    rep = slo.step(eng)
    # one slot: the highest-priority earliest-deadline ticket wins it
    assert [t.req.rid for t in rep.admitted] == [2]
    assert [t.req.rid for t in slo.pending] == [1, 0]


def test_slo_preempts_lower_priority_for_urgent():
    eng = _FakeEngine(slots=2, budget=10)
    slo = SLOScheduler()
    slo.submit(_ticket(0, 6, priority=0, seq=1))
    slo.submit(_ticket(1, 4, priority=0, seq=2))
    slo.step(eng)
    assert eng._free_slot() is None and eng.budget == 0
    slo.submit(_ticket(9, 4, priority=5, seq=3))
    rep = slo.step(eng)
    # victim = lowest priority, newest arrival (least progress lost)
    assert [t.req.rid for t in rep.preempted] == [1]
    assert rep.preempted[0].req.finish_reason == "preempted"
    assert [t.req.rid for t in rep.admitted] == [9]
    assert [t.req.rid for t in slo.pending] == [1]   # requeued
    # equal priority never preempts: urgent==0 finds no victims
    rep2 = slo.step(eng)
    assert rep2.preempted == [] and len(slo.pending) == 1


# ---------------------------------------------------------- metrics (unit)

def test_metrics_fake_clock_accounting():
    t = [0.0]
    m = ServingMetrics(clock=lambda: t[0])
    m.submitted(7)
    t[0] = 1.0
    m.admitted(7)
    t[0] = 1.5
    m.token(7)
    t[0] = 2.0
    m.token(7)
    m.preempted(7)
    t[0] = 4.0
    m.admitted(7)            # re-admission must keep the FIRST admit
    m.token(7)
    m.finished(7, "length")
    snap = m.snapshot()
    assert snap["requests"] == {"submitted": 1, "finished": 1,
                                "preemptions": 1, "tokens": 3}
    assert snap["queue_wait_s"]["p50"] == 1.0
    assert snap["ttft_s"]["p50"] == 1.5
    assert snap["inter_token_s"]["p99"] == 2.0   # the preemption gap
    (detail,) = snap["requests_detail"]
    assert detail["rid"] == 7 and detail["preemptions"] == 1
    assert detail["finish_reason"] == "length"


class _SlowAdmit:
    """Fake engine whose every admission (its prefill) takes 1 s on the
    shared fake clock."""

    def __init__(self, t):
        self.t = t
        self.slot_req = [None, None]

    def _free_slot(self):
        return 0

    def admit(self, req):
        self.t[0] += 1.0
        return True


@pytest.mark.parametrize("mk_sched", [FIFOScheduler, SLOScheduler],
                         ids=["fifo", "slo"])
def test_queue_wait_ends_where_admission_starts(mk_sched):
    """Two requests admitted in one scheduler step: each one's queue
    wait ends when its own admission starts, so the second's includes
    the first's prefill but neither includes its own."""
    t = [0.0]
    sched = mk_sched(clock=lambda: t[0])
    m = ServingMetrics(clock=lambda: t[0])
    for rid in (0, 1):
        m.submitted(rid)
        sched.submit(Ticket(req=Request(rid=rid, tokens=[1]), seq=rid))
    t[0] = 2.0
    rep = sched.step(_SlowAdmit(t))
    assert t[0] == 4.0
    for tk in rep.admitted:
        m.admitted(tk.req.rid, tk.admit_started)
    assert [m.requests[r].queue_wait for r in (0, 1)] == [2.0, 3.0]


def test_front_end_refuses_a_scheduler_on_another_clock():
    with pytest.raises(ValueError, match="clock"):
        AsyncEngine(_SlowAdmit([0.0]),
                    scheduler=FIFOScheduler(clock=lambda: 0.0))


# ------------------------------------------------------------- integration

def _mk_model(**over):
    cfg = reduced(get_arch("qwen2.5-14b"), num_layers=2, **over)
    cfg = dataclasses.replace(cfg, dtype="float32")
    model = build_model(cfg)
    return model, model.init(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def setup():
    return _mk_model()


def _engine(setup, **over):
    model, params = setup
    kw = dict(max_slots=2, max_len=64, paged=True, block_size=8,
              prefill_chunk=16)
    kw.update(over)
    return Engine(model, params, **kw)


def _reqs(n, seed=0, max_new=6, plens=(3, 9, 17, 33)):
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        toks = [1] + rng.integers(3, 500, plens[i % len(plens)] - 1).tolist()
        out.append(Request(rid=i, tokens=toks, max_new_tokens=max_new))
    return out


@pytest.mark.parametrize("mk_sched", [FIFOScheduler, SLOScheduler],
                         ids=["fifo", "slo"])
def test_async_greedy_matches_sync(setup, mk_sched):
    """The acceptance property: streamed tokens concatenate to exactly
    the sync engine's outputs, under either scheduler, radix on."""
    sync = _engine(setup)
    ra = _reqs(5)
    sync.run(ra)
    ref = [r.output for r in ra]

    eng = _engine(setup, radix_cache=True)

    async def go():
        async with AsyncEngine(eng, scheduler=mk_sched()) as srv:
            streams = [srv.submit(r) for r in _reqs(5)]
            return [await s.collect() for s in streams]

    got = asyncio.run(go())
    assert got == ref


def test_streaming_is_incremental(setup):
    """Tokens arrive one per tick, not in one burst at finish: the
    stream must yield its first token while the request is still
    running."""
    eng = _engine(setup)
    seen_before_done = []

    async def go():
        async with AsyncEngine(eng) as srv:
            req = Request(rid=0, tokens=[1, 5, 9], max_new_tokens=6)
            stream = srv.submit(req)
            async for _tok in stream:
                seen_before_done.append(req.done)
            return req

    req = asyncio.run(go())
    assert len(seen_before_done) == len(req.output) == 6
    assert seen_before_done[0] is False   # first token beat completion


def test_submit_rejects_never_servable(setup):
    eng = _engine(setup, num_blocks=5)     # 4 usable blocks total

    async def go():
        async with AsyncEngine(eng) as srv:
            with pytest.raises(ValueError, match="prompt length"):
                srv.submit(Request(rid=0, tokens=[1] * 70))
            with pytest.raises(ValueError, match="blocks"):
                srv.submit(Request(rid=1, tokens=[1] * 20,
                                   max_new_tokens=44))

    asyncio.run(go())


def test_preempt_resume_bit_identical(setup):
    """Evict-to-queue then resume must replay the identical greedy
    continuation (cache rows depend only on the token prefix)."""
    eng = _engine(setup, max_slots=1)
    low = Request(rid=0, tokens=[1] + list(range(5, 14)),
                  max_new_tokens=12)
    hi = Request(rid=1, tokens=[1, 7, 8], max_new_tokens=4)

    async def go():
        async with AsyncEngine(eng, scheduler=SLOScheduler()) as srv:
            s_low = srv.submit(low, priority=0)
            while not low.output:          # let the long job start
                await asyncio.sleep(0.001)
            s_hi = srv.submit(hi, priority=5)
            return await s_hi.collect(), await s_low.collect(), \
                srv.metrics.snapshot(eng)

    o_hi, o_low, snap = asyncio.run(go())
    assert eng.preemptions >= 1
    assert snap["requests"]["preemptions"] >= 1

    solo = _engine(setup, max_slots=1)
    rl = Request(rid=0, tokens=[1] + list(range(5, 14)), max_new_tokens=12)
    rh = Request(rid=1, tokens=[1, 7, 8], max_new_tokens=4)
    solo.run([rl])
    solo.run([rh])
    assert (o_low, o_hi) == (rl.output, rh.output)
    assert low.finish_reason == "length"   # "preempted" was transient


def test_preempt_slot_guards(setup):
    eng = _engine(setup)
    with pytest.raises(ValueError, match="no preemptible request"):
        eng.preempt(0)


def test_radix_fork_from_finished_request(setup):
    """The tentpole radix property: a request admitted AFTER its donor
    fully finished still forks the donor's prefix blocks — and its
    greedy output matches a cold engine exactly."""
    eng = _engine(setup, block_size=4, prefill_chunk=8, radix_cache=True)
    prefix = [1] + list(range(5, 20))      # 16 toks = 4 full blocks

    async def go():
        async with AsyncEngine(eng) as srv:
            s1 = srv.submit(Request(rid=0, tokens=prefix + [101],
                                    max_new_tokens=4))
            await s1.collect()
            await srv.drain()              # donor finished, blocks freed
            s2 = srv.submit(Request(rid=1, tokens=prefix + [102],
                                    max_new_tokens=4))
            return await s2.collect()

    o2 = asyncio.run(go())
    st = eng.radix.stats()
    assert st["hit_blocks"] >= 4 and st["hit_rate"] > 0

    cold = _engine(setup, block_size=4, prefill_chunk=8)
    r = Request(rid=9, tokens=prefix + [102], max_new_tokens=4)
    cold.run([r])
    assert r.output == o2


def test_radix_hit_rate_on_sequential_shared_prefix_trace(setup):
    """Sixteen requests share a 4-block system prompt and arrive one
    after another, each finished before the next is submitted, so only
    the radix tree's pinned blocks can serve the prefix: at least half
    the offered blocks hit."""
    eng = _engine(setup, radix_cache=True)
    rng = np.random.default_rng(2)
    prefix = [1] + rng.integers(3, 500, 4 * 8 - 1).tolist()

    async def go():
        async with AsyncEngine(eng) as srv:
            for i in range(16):
                tail = rng.integers(3, 500, 3).tolist()
                await srv.submit(Request(rid=i, tokens=prefix + tail,
                                         max_new_tokens=4)).collect()
        return srv.metrics.snapshot(eng)

    radix = asyncio.run(go())["radix"]
    assert radix["hit_rate"] >= 0.5, radix


def test_radix_evicts_under_allocator_pressure(setup):
    """Pinned historical blocks must yield (LRU) when admission needs
    the pool: a disjoint-prefix request still gets served."""
    eng = _engine(setup, block_size=4, prefill_chunk=8, num_blocks=13,
                  radix_cache=True, max_slots=1)
    a = Request(rid=0, tokens=[1] + list(range(5, 20)), max_new_tokens=4)
    eng.run([a])
    assert len(eng.radix) >= 4             # prefix now pinned resident
    # a request needing nearly the whole pool with a different prefix
    b = Request(rid=1, tokens=[2] + list(range(200, 231)),
                max_new_tokens=4)
    eng.run([b])
    assert b.done and b.finish_reason == "length"
    assert eng.radix.evicted_blocks >= 1
    # part of a's cached prefix was sacrificed to admit b
    assert len(eng.radix.match(a.tokens)) < 4
    alloc = eng.allocator
    assert alloc.num_free + alloc.num_live == alloc.num_usable


def test_serve_launcher_stream_smoke(setup, capsys, monkeypatch):
    """launch/serve.py --stream end-to-end (arrival trace + metrics
    printout) without spawning a process."""
    import json
    import sys

    from repro.launch import serve
    trace = [{"t": 0.0, "prompt_len": 4, "priority": 1},
             {"t": 0.01, "prompt_len": 6, "max_new": 3}]
    import tempfile
    with tempfile.NamedTemporaryFile("w", suffix=".json",
                                     delete=False) as f:
        json.dump(trace, f)
        path = f.name
    monkeypatch.setattr(sys, "argv", [
        "serve", "--arch", "qwen2.5-14b", "--reduced", "--max-new", "2",
        "--slots", "2", "--max-len", "64", "--paged", "--block-size",
        "8", "--stream", "--radix-cache", "--arrival-trace", path,
        "--slo-ttft-ms", "1000"])
    serve.main()
    out = capsys.readouterr().out
    assert "[serve] metrics:" in out and '"ttft_s"' in out


def test_snapshot_gauges_read_the_engine(setup):
    """The tick gauges are the engine's own counts, not a second tally
    kept by the front end."""
    eng = _engine(setup)

    async def go():
        async with AsyncEngine(eng) as srv:
            streams = [srv.submit(r) for r in _reqs(3)]
            for s in streams:
                await s.collect()
            return srv.metrics.snapshot(eng)

    g = asyncio.run(go())["gauges"]
    assert g["ticks"] == eng.ticks > 0
    assert g["active_mean"] == eng.slot_ticks / eng.ticks
    assert 1 <= g["active_mean"] <= g["active_max"] == eng.peak_active == 2
    assert g["free_blocks"] == eng.allocator.num_free
