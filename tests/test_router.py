"""Replica router + disaggregated prefill/decode workers.

Covers the ISSUE-10 acceptance surface on the host device: placement
policies rank (never admit), routed greedy outputs are bit-identical
to the single-engine oracle, ``export_sequence``/``adopt_sequence``
round-trips conserve refcounts / CoW prefix sharing / radix pins,
preempt-on-A-resume-on-B is bit-identical, ``DisaggReplica`` preempts
all three residencies, the async front end drives a router unchanged,
and the mesh-spec parser rejects every malformed spec with a targeted
error. The real multi-device paths (2x2 mesh routing, disaggregated
handoff across a sharded pool, non-dividing device counts) run in a
forced-4-device subprocess.
"""
import dataclasses
import subprocess
import sys

import jax
import numpy as np
import pytest

from conftest import forced_devices_env
from repro.configs.base import get_arch, reduced
from repro.launch.mesh import _parse_mesh_spec, parse_mesh
from repro.models.model import build_model
from repro.serving.engine import Engine, Request
from repro.serving.router import (POLICIES, DisaggReplica, FusedReplica,
                                  ReplicaRouter, make_policy)
from repro.serving.router.policies import (LeastLoaded, RadixAffinity,
                                           RoundRobin)

ENGINE_KW = dict(max_len=128, paged=True, block_size=8, prefill_chunk=16)


@pytest.fixture(scope="module")
def mp():
    cfg = reduced(get_arch("qwen2.5-14b"), num_layers=2)
    cfg = dataclasses.replace(cfg, dtype="float32")
    model = build_model(cfg)
    return model, model.init(jax.random.PRNGKey(0))


def _reqs(n=6, seed=7, rid0=0, max_new=None):
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        toks = [1] + rng.integers(3, 500, 11 + (i % 3) * 7).tolist()
        out.append(Request(rid=rid0 + i, tokens=toks,
                           max_new_tokens=max_new or 6 + i % 3,
                           eos_id=None))
    return out


def _oracle(mp, reqs_fn=_reqs, max_slots=4):
    model, params = mp
    eng = Engine(model, params, max_slots=max_slots, **ENGINE_KW)
    reqs = reqs_fn()
    eng.run(reqs)
    return [r.output for r in reqs]


# ------------------------------------------------------------- policies
class _FakeRep:
    def __init__(self, free, active, prefix=0):
        self._f, self._a, self._p = free, active, prefix

    def free_blocks(self):
        return self._f

    def active(self):
        return self._a

    def peek_prefix(self, tokens):
        return self._p


class _FakeRouter:
    def __init__(self, reps):
        self.replicas = reps


def test_least_loaded_ranks_by_blocks_then_active_then_index():
    router = _FakeRouter([_FakeRep(5, 1), _FakeRep(9, 3),
                          _FakeRep(9, 1), _FakeRep(5, 1)])
    req = Request(rid=0, tokens=[1, 2], max_new_tokens=2)
    assert LeastLoaded().rank(router, req) == [2, 1, 0, 3]


def test_radix_affinity_prefers_prefix_then_falls_back():
    req = Request(rid=0, tokens=[1, 2, 3], max_new_tokens=2)
    router = _FakeRouter([_FakeRep(9, 0, prefix=0),
                          _FakeRep(2, 3, prefix=2),
                          _FakeRep(9, 0, prefix=0)])
    # the loaded replica that knows the prefix still wins
    assert RadixAffinity().rank(router, req) == [1, 0, 2]
    # nobody knows the prefix: pure least-loaded order
    router2 = _FakeRouter([_FakeRep(2, 3), _FakeRep(9, 0)])
    assert RadixAffinity().rank(router2, req) == [1, 0]


def test_round_robin_rotates_full_ring():
    router = _FakeRouter([_FakeRep(1, 0)] * 3)
    req = Request(rid=0, tokens=[1], max_new_tokens=1)
    p = RoundRobin()
    assert p.rank(router, req) == [0, 1, 2]
    assert p.rank(router, req) == [1, 2, 0]
    assert p.rank(router, req) == [2, 0, 1]
    assert p.rank(router, req) == [0, 1, 2]


def test_make_policy_registry_and_errors():
    assert set(POLICIES) == {"least_loaded", "radix_affinity",
                             "round_robin"}
    assert isinstance(make_policy("round_robin"), RoundRobin)
    inst = LeastLoaded()
    assert make_policy(inst) is inst
    with pytest.raises(ValueError, match="unknown placement policy"):
        make_policy("bogus")
    with pytest.raises(TypeError, match="rank"):
        make_policy(object())


# ------------------------------------------------- routed-vs-oracle parity
@pytest.mark.parametrize("policy", sorted(POLICIES))
def test_routed_outputs_match_single_engine_oracle(mp, policy):
    """Any placement, same tokens: per-slot sampling is (seed, rid,
    index)-keyed and cache rows depend only on their prefix."""
    model, params = mp
    ref = _oracle(mp)
    router = ReplicaRouter(
        [FusedReplica(Engine(model, params, max_slots=2, **ENGINE_KW))
         for _ in range(2)], policy=policy)
    reqs = _reqs()
    router.run(reqs)
    assert [r.output for r in reqs] == ref
    # the fleet actually spread: nobody served everything
    assert all(e.peak_active >= 1 for e in router.engines)


def test_disagg_replica_matches_oracle_with_handoffs(mp):
    model, params = mp
    ref = _oracle(mp)
    pre = Engine(model, params, max_slots=2, prefill_only=True,
                 **ENGINE_KW)
    dec = Engine(model, params, max_slots=4, **ENGINE_KW)
    rep = DisaggReplica(pre, dec)
    router = ReplicaRouter([rep])
    reqs = _reqs()
    router.run(reqs)
    assert [r.output for r in reqs] == ref
    assert rep.handoffs == len(reqs)


def test_router_requires_paged_engines(mp):
    model, params = mp
    dense = Engine(model, params, max_slots=2, max_len=64, paged=False)
    with pytest.raises(ValueError, match="paged"):
        FusedReplica(dense)
    with pytest.raises(ValueError, match="prefill_only"):
        DisaggReplica(dense, dense)


# ------------------------------------------------- export/adopt round-trip
def _decode_some(eng, req, ticks=3):
    assert eng.admit(req)
    for _ in range(ticks):
        eng.tick()
    return eng.slot_req.index(req)


def test_export_adopt_conserves_blocks_and_refcounts(mp):
    model, params = mp
    a = Engine(model, params, max_slots=2, **ENGINE_KW)
    b = Engine(model, params, max_slots=2, **ENGINE_KW)
    req = _reqs(1, max_new=8)[0]
    slot = _decode_some(a, req)
    held = len(a.seq_blocks[slot].ids)
    assert a.allocator.num_live == held
    h = a.export_sequence(slot)
    # source fully released: nothing floats between engines
    assert a.allocator.num_live == 0
    assert a.allocator.num_free == a.allocator.num_usable
    assert a.slot_req[slot] is None
    assert b.can_adopt(h)
    bslot = b.adopt_sequence(h)
    assert bslot is not None
    ids = b.seq_blocks[bslot].ids
    # full fused-equivalent reservation, every block exclusively owned
    assert len(ids) == max(b._handoff_blocks(req), h.n_blocks)
    assert b.allocator.num_live == len(ids)
    assert all(b.allocator.refcount(bid) == 1 for bid in ids)
    b.run([])                            # continue to completion
    assert req.done and len(req.output) == 8


def test_cow_prefix_sharing_survives_export(mp):
    """Exporting one of two CoW-sharing sequences must not corrupt the
    stay-behind: the donor keeps its rows, the migrant re-owns fresh
    blocks, and both finish bit-identically to a never-migrated run."""
    model, params = mp
    rng = np.random.default_rng(5)
    shared = [1] + rng.integers(3, 500, 23).tolist()

    def mk():
        return [Request(rid=0, tokens=list(shared), max_new_tokens=8,
                        eos_id=None),
                Request(rid=1, tokens=list(shared[:16]) + [7, 9, 11],
                        max_new_tokens=8, eos_id=None)]

    ref = mk()
    eng = Engine(model, params, max_slots=2, **ENGINE_KW)
    eng.run(ref)

    a = Engine(model, params, max_slots=2, **ENGINE_KW)
    b = Engine(model, params, max_slots=2, **ENGINE_KW)
    r0, r1 = mk()
    assert a.admit(r0)
    assert a.admit(r1)                   # forks r0's whole-block prefix
    shared_ids = set(a.seq_blocks[0].ids) & set(a.seq_blocks[1].ids)
    assert shared_ids, "prompts should CoW-share prefix blocks"
    assert all(a.allocator.refcount(bid) == 2 for bid in shared_ids)
    a.tick()
    h = a.export_sequence(0)             # migrate the donor
    # stay-behind now owns the once-shared blocks alone
    assert all(a.allocator.refcount(bid) == 1 for bid in shared_ids)
    assert b.adopt_sequence(h) is not None
    while not (r0.done and r1.done):
        if any(r is not None for r in a.slot_req):
            a.tick()
        if any(r is not None for r in b.slot_req):
            b.tick()
    assert [r0.output, r1.output] == [r.output for r in ref]


def test_export_preserves_radix_pins_for_future_admissions(mp):
    """With the radix cache attached, exporting a sequence inserts its
    written prefix (pinned) on the SOURCE — a later identical prompt
    forks locally instead of recomputing."""
    model, params = mp
    a = Engine(model, params, max_slots=2, radix_cache=True, **ENGINE_KW)
    b = Engine(model, params, max_slots=2, **ENGINE_KW)
    req = _reqs(1, max_new=8)[0]
    slot = _decode_some(a, req)
    h = a.export_sequence(slot)
    assert a.allocator.num_pinned > 0    # prefix stayed, pinned
    assert a.radix.peek(req.tokens) > 0
    assert b.adopt_sequence(h) is not None
    b.run([])
    # identical prompt admitted on the source hits the radix tree
    before = a.radix.stats()["hit_blocks"]
    twin = Request(rid=50, tokens=list(req.tokens), max_new_tokens=4,
                   eos_id=None)
    a.run([twin])
    assert a.radix.stats()["hit_blocks"] > before
    assert twin.output[:4] == req.output[:4]


def test_preempt_on_a_resume_on_b_bit_identical(mp):
    """Evict-to-queue on one replica, re-admit on ANOTHER: the resumed
    continuation replays the prefix and matches the never-preempted
    oracle token for token."""
    model, params = mp
    oracle_req = _reqs(1, max_new=10)[0]
    eng = Engine(model, params, max_slots=2, **ENGINE_KW)
    eng.run([oracle_req])

    a = Engine(model, params, max_slots=2, **ENGINE_KW)
    b = Engine(model, params, max_slots=2, **ENGINE_KW)
    req = _reqs(1, max_new=10)[0]
    slot = _decode_some(a, req, ticks=4)
    assert 0 < len(req.output) < 10
    got = a.preempt(slot)
    assert got is req and req.finish_reason == "preempted"
    assert a.allocator.num_live == 0
    assert b.admit(req)                  # resume replays on replica B
    b.run([])
    assert req.output == oracle_req.output


def test_router_preempt_resume_through_flattened_slots(mp):
    """The router's flattened slot index maps across replica
    boundaries; a preempted request re-admits anywhere and the final
    outputs still match the oracle."""
    model, params = mp
    ref = _oracle(mp)
    router = ReplicaRouter(
        [FusedReplica(Engine(model, params, max_slots=2, **ENGINE_KW))
         for _ in range(2)])
    reqs = _reqs()
    pending = list(reqs)
    router.admit_from(pending)
    for _ in range(3):
        router.tick()
    # preempt the LAST resident (an index past the first replica)
    victims = [i for i, r in enumerate(router.slot_req) if r is not None]
    victim = router.preempt(victims[-1])
    assert victim.finish_reason == "preempted"
    assert router.preemptions == 1
    pending.append(victim)
    router.run(pending)                  # drains pending + residents
    assert [r.output for r in reqs] == ref


# ------------------------------------------- disagg three-zone preemption
def test_disagg_preempts_all_three_residencies(mp):
    model, params = mp
    pre = Engine(model, params, max_slots=2, prefill_only=True,
                 **ENGINE_KW)
    dec = Engine(model, params, max_slots=2, **ENGINE_KW)
    rep = DisaggReplica(pre, dec)
    nd, npre = len(dec.slot_req), len(pre.slot_req)

    # zone 1: decoding on the decode worker
    r0 = _reqs(1, max_new=8)[0]
    assert rep.admit(r0)
    while r0 not in dec.slot_req:
        rep.step()
    rep.step()
    v = rep.preempt_at(dec.slot_req.index(r0))
    assert v is r0 and r0.finish_reason == "preempted"
    assert dec.allocator.num_live == 0

    # zone 3: an in-flight prefill job (long prompt, chunked)
    long = Request(rid=60, tokens=[1] + list(range(3, 100)),
                   max_new_tokens=4, eos_id=None)
    assert rep.admit(long)
    rep.step()                           # one chunk in, job not done
    assert len(pre._prefilling) == 1
    jobs_idx = nd + npre                 # first in-flight job
    v = rep.preempt_at(jobs_idx)
    assert v is long and long.finish_reason == "preempted"
    assert not pre._prefilling and pre.allocator.num_live == 0

    # zone 2: completed prefill awaiting adoption (decode side full)
    blockers = _reqs(2, seed=9, rid0=70, max_new=24)
    for rb in blockers:
        assert rep.admit(rb)
    while any(r is None for r in dec.slot_req):
        rep.step()                       # both decode slots occupied
    waiter = Request(rid=80, tokens=[1, 4, 6, 8], max_new_tokens=4,
                     eos_id=None)
    assert rep.admit(waiter)
    while waiter not in pre.slot_req:
        rep.step()                       # prefill done, nowhere to go
    v = rep.preempt_at(nd + pre.slot_req.index(waiter))
    assert v is waiter and waiter.finish_reason == "preempted"
    # resume later: re-admission replays bit-identically
    oracle = Request(rid=81, tokens=[1, 4, 6, 8], max_new_tokens=4,
                     eos_id=None)
    eng = Engine(model, params, max_slots=2, **ENGINE_KW)
    eng.run([oracle])
    router = ReplicaRouter([rep])
    router.run([waiter, r0, long])
    assert waiter.output == oracle.output


# ------------------------------------------------- async front end on top
def test_async_engine_streams_over_router(mp):
    import asyncio

    from repro.serving.frontend import AsyncEngine

    model, params = mp
    ref = _oracle(mp)
    router = ReplicaRouter(
        [FusedReplica(Engine(model, params, max_slots=2, **ENGINE_KW))
         for _ in range(2)])

    async def go():
        async with AsyncEngine(router) as srv:
            streams = [srv.submit(r) for r in _reqs()]
            return [await s.collect() for s in streams]

    assert asyncio.run(go()) == ref


class _Pinned:
    """Placement policy that ranks one chosen replica first."""

    def __init__(self):
        self.first = 0

    def rank(self, router, req):
        n = len(router.replicas)
        return [(self.first + k) % n for k in range(n)]


def test_router_gauges_count_the_fleet(mp):
    """``ServingMetrics`` reads a router's gauges as it reads one
    engine's: slot-ticks summed over the engines, over the router's own
    steps, and the most slots the fleet held at once. The replicas peak
    at different steps, so their own peaks add up to more."""
    from repro.serving.frontend import ServingMetrics

    model, params = mp
    policy = _Pinned()
    router = ReplicaRouter(
        [FusedReplica(Engine(model, params, max_slots=2, **ENGINE_KW))
         for _ in range(2)], policy=policy)
    for first, rid0 in ((0, 0), (1, 10)):
        policy.first = first
        router.run(_reqs(n=2, rid0=rid0, max_new=3))
    engines = router.engines
    assert [e.peak_active for e in engines] == [2, 2]
    assert router.peak_active == 2
    assert router.slot_ticks == sum(e.slot_ticks for e in engines) > 0
    g = ServingMetrics().snapshot(router)["gauges"]
    assert g["ticks"] == router.ticks
    assert g["active_mean"] == router.slot_ticks / router.ticks
    assert g["active_max"] == router.peak_active


# ---------------------------------------------------------- mesh parsing
def test_parse_mesh_spec_named_axes():
    assert _parse_mesh_spec("2x4") == (2, 4)
    assert _parse_mesh_spec("data=2,model=4") == (2, 4)
    assert _parse_mesh_spec("model=4,data=2") == (2, 4)
    assert _parse_mesh_spec("model=4") == (1, 4)
    assert _parse_mesh_spec("data=2") == (2, 1)
    assert _parse_mesh_spec(" DATA=2 x MODEL=3 ") == (2, 3)


@pytest.mark.parametrize("spec,err", [
    ("foo", "expected 'DxM'"),
    ("1x2x3", "expected 'DxM'"),
    ("data=2,bogus=2", "unknown axis"),
    ("data=2,data=2", "given twice"),
    ("data=two", "integer"),
    ("model=", "integer"),
])
def test_parse_mesh_spec_rejects(spec, err):
    with pytest.raises(ValueError, match=err):
        _parse_mesh_spec(spec)


def test_parse_mesh_device_checks():
    with pytest.raises(ValueError, match="axes must be >= 1"):
        parse_mesh("0x1")
    with pytest.raises(ValueError, match="XLA_FLAGS"):
        parse_mesh("64x64")
    m = parse_mesh("1x1")
    assert m.axis_names == ("data", "model")
    assert parse_mesh("data=1,model=1").shape == {"data": 1, "model": 1}


# ----------------------------------------------- forced-4-device subprocess
_MESH_SCRIPT = r"""
import dataclasses
import jax, numpy as np
from repro.configs.base import get_arch, reduced
from repro.launch.mesh import parse_mesh, replica_submeshes
from repro.models.model import build_model
from repro.serving.engine import Engine, Request
from repro.serving.router import ReplicaRouter

assert len(jax.devices()) == 4, jax.devices()

# ---- validation that needs a real multi-device view
try:
    parse_mesh("1x3")
    raise SystemExit("1x3 should not divide 4 devices")
except ValueError as e:
    assert "divide" in str(e), e
try:
    parse_mesh("3x3")
    raise SystemExit("3x3 should exceed 4 devices")
except ValueError as e:
    assert "XLA_FLAGS" in str(e), e
mesh = parse_mesh("data=2,model=2")
assert mesh.shape == {"data": 2, "model": 2}
subs = replica_submeshes(mesh)
assert len(subs) == 2
ids = [sorted(d.id for d in np.asarray(s.devices).ravel()) for s in subs]
assert ids[0] != ids[1] and not (set(ids[0]) & set(ids[1])), ids
assert all(s.shape == {"data": 1, "model": 2} for s in subs)

# ---- routed parity on disjoint device groups, fused and disaggregated
cfg = dataclasses.replace(reduced(get_arch("qwen2.5-14b"), num_layers=2),
                          dtype="float32")
model = build_model(cfg)
params = model.init(jax.random.PRNGKey(0))
kw = dict(max_slots=2, max_len=128, paged=True, block_size=8,
          prefill_chunk=16)


def reqs():
    rng = np.random.default_rng(7)
    return [Request(rid=i,
                    tokens=[1] + rng.integers(3, 500, 11 + (i % 3) * 7
                                              ).tolist(),
                    max_new_tokens=6 + i % 3, eos_id=None)
            for i in range(6)]


oracle = reqs()
Engine(model, params, **dict(kw, max_slots=4)).run(oracle)
ref = [r.output for r in oracle]

for disagg in (False, True):
    router = ReplicaRouter.for_mesh(model, params, mesh,
                                    disaggregate=disagg, **kw)
    rs = reqs()
    router.run(rs)
    assert [r.output for r in rs] == ref, ("disagg" if disagg else "fused")
    if disagg:
        assert sum(rep.handoffs for rep in router.replicas) == len(rs)
print("ROUTER_MESH_OK")
"""


def test_router_on_2x2_mesh_subprocess():
    """2x2 forced host devices: parse_mesh division errors, disjoint
    replica submeshes, and routed fused + disaggregated parity against
    the single-device oracle (the disagg leg exercises the sharded-pool
    handoff device_put path)."""
    r = subprocess.run([sys.executable, "-c", _MESH_SCRIPT],
                       capture_output=True, text=True, timeout=1200,
                       env=forced_devices_env(4))
    assert "ROUTER_MESH_OK" in r.stdout, r.stdout + r.stderr
