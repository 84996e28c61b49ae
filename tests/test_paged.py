"""Paged decode cache: allocator semantics, paged/dense parity, and
engine lifecycle edge cases (chunk-boundary EOS, block reuse after
eviction, allocator exhaustion, copy-on-write prefix sharing)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_arch, reduced
from repro.models.model import build_model
from repro.serving import kvcache
from repro.serving.engine import Engine, Request
from repro.serving.paged import (BlockAllocator, NULL_BLOCK, blocks_for,
                                 shared_prefix_blocks)


# ---------------------------------------------------------------- allocator

def test_allocator_alloc_free_roundtrip():
    a = BlockAllocator(num_blocks=8, block_size=4)
    assert a.num_usable == 7 and a.num_free == 7
    ids = a.alloc(3)
    assert len(ids) == 3 and NULL_BLOCK not in ids
    assert a.num_free == 4
    assert a.alloc(5) is None            # all-or-nothing: 4 < 5
    assert a.num_free == 4               # failed alloc left state intact
    a.free(ids)
    assert a.num_free == 7
    with pytest.raises(ValueError):
        a.free(ids[:1])                  # double free


def test_allocator_fork_refcounts():
    a = BlockAllocator(num_blocks=8, block_size=4)
    ids = a.alloc(2)
    shared = a.fork(ids)
    assert shared == ids
    assert all(a.refcount(b) == 2 for b in ids)
    a.free(ids)                          # donor finishes first...
    assert a.num_free == 5               # ...blocks survive for borrower
    a.free(shared)
    assert a.num_free == 7


def test_allocator_copy_on_write():
    a = BlockAllocator(num_blocks=8, block_size=4)
    copies = []
    (bid,) = a.alloc(1)
    assert a.ensure_exclusive(bid, lambda s, d: copies.append((s, d))) == bid
    assert copies == []                  # exclusive: no copy
    a.fork([bid])
    fresh = a.ensure_exclusive(bid, lambda s, d: copies.append((s, d)))
    assert fresh != bid and copies == [(bid, fresh)]
    assert a.refcount(bid) == 1          # our ref moved to the copy
    assert a.refcount(fresh) == 1


def test_shared_prefix_blocks_math():
    BS = 4
    assert shared_prefix_blocks([1, 2, 3, 4, 5], [1, 2, 3, 4, 9], BS) == 1
    assert shared_prefix_blocks([1, 2, 3, 9], [1, 2, 3, 4], BS) == 0
    # full-prompt match is capped so the borrower still prefills its
    # last token itself (admission logits must be its own)
    assert shared_prefix_blocks([1, 2, 3, 4], [1, 2, 3, 4], BS) == 0
    assert shared_prefix_blocks([1, 2, 3, 4] * 3, [1, 2, 3, 4] * 3, BS) == 2
    assert blocks_for(0, BS) == 0 and blocks_for(1, BS) == 1
    assert blocks_for(4, BS) == 1 and blocks_for(5, BS) == 2


def test_paged_budget_block_math():
    """DESIGN.md §7: blocks/byte follow the same X-cache crossover as
    dense rows — whisper's x layout shrinks the block by 2·Hkv·dh/D."""
    wh = dataclasses.replace(get_arch("whisper-tiny"), cache_mode=None)
    qw = get_arch("qwen2.5-14b")
    pb_wh = kvcache.paged_budget_for(wh, block_size=16)
    pb_qw = kvcache.paged_budget_for(qw, block_size=16)
    assert pb_wh.mode == "x" and pb_qw.mode == "kv"
    assert pb_wh.bytes_per_block == pb_wh.bytes_per_token * 16
    # same budget buys more x-layout blocks than kv would on whisper geom
    kv_row = 2 * wh.num_kv_heads * wh.head_dim
    assert wh.d_model < kv_row
    assert pb_wh.max_blocks(1 << 20) > (1 << 20) // (
        kv_row * 2 * pb_wh.layers * 16)
    # usable tokens quantize to whole blocks
    assert pb_qw.max_tokens(1 << 20) == pb_qw.max_blocks(1 << 20) * 16


# ----------------------------------------------------------------- fixtures

def _mk_model(**over):
    cfg = reduced(get_arch("qwen2.5-14b"), num_layers=2, **over)
    cfg = dataclasses.replace(cfg, dtype="float32")
    model = build_model(cfg)
    return model, model.init(jax.random.PRNGKey(0))


# one config per decode-cache layout: standard scores vs the paper's
# folded W_QK with the xv or x-only cache
_LAYOUTS = {"kv": {"score_mode": "standard"},
            "xv": {"score_mode": "wqk", "cache_mode": "xv"},
            "x": {"score_mode": "wqk", "cache_mode": "x"}}


@pytest.fixture(scope="module")
def setup():
    return _mk_model()


def _reqs(n, seed=0, max_new=6, plens=(3, 9, 17, 33), eos=None):
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        toks = [1] + rng.integers(3, 500, plens[i % len(plens)] - 1).tolist()
        out.append(Request(rid=i, tokens=toks, max_new_tokens=max_new,
                           eos_id=eos))
    return out


# ------------------------------------------------------------------- parity

@pytest.mark.parametrize("score_mode", ["standard", "wqk"])
def test_paged_engine_matches_dense(score_mode):
    """Same requests through the paged and dense engines produce
    identical greedy outputs across kv and x cache layouts."""
    model, params = _mk_model(score_mode=score_mode)
    dense = Engine(model, params, max_slots=2, max_len=64, paged=False)
    pagede = Engine(model, params, max_slots=2, max_len=64, paged=True,
                    block_size=8, prefill_chunk=16)
    ra, rb = _reqs(5), _reqs(5)
    dense.run(ra)
    pagede.run(rb)
    for x, y in zip(ra, rb, strict=True):
        assert x.output == y.output, (x.rid, x.output, y.output)


@pytest.mark.parametrize("layout", list(_LAYOUTS))
def test_paged_admits_twice_dense_concurrency_at_equal_hbm(layout):
    """At the HBM of four worst-case dense sequences, the paged pool
    admits at least twice the dense engine's peak concurrency on mixed
    prompt lengths, for every cache layout: dense reserves max_len per
    slot, paged only the blocks a sequence fills."""
    model, params = _mk_model(**_LAYOUTS[layout])
    max_len, block = 128, 8
    budget = kvcache.budget_for(model.cfg)
    hbm = 4 * max_len * budget.bytes_per_token
    dense = Engine(model, params, paged=False, max_len=max_len,
                   max_slots=max(1, int(budget.max_tokens(hbm)) // max_len))
    pagede = Engine(model, params, max_slots=16, max_len=max_len,
                    paged=True, block_size=block, prefill_chunk=2 * block,
                    num_blocks=kvcache.paged_budget_for(
                        model.cfg, block).max_blocks(hbm))
    plens = (4, 9, 17, 26, 33, 40)
    ra, rb = _reqs(24, max_new=8, plens=plens), _reqs(24, max_new=8,
                                                      plens=plens)
    dense.run(ra)
    pagede.run(rb)
    assert all(r.done for r in ra + rb)
    assert [r.output for r in ra] == [r.output for r in rb]
    assert pagede.peak_active >= 2 * dense.peak_active, \
        (dense.peak_active, pagede.peak_active)


def paged_vs_dense_logits(model, params, prompt, *, max_len, block_size,
                          chunk, steps, schedule="gather"):
    """Greedy per-token logits from the dense prefill+decode path vs the
    paged chunked-prefill+decode graph on the same prompt. Returns
    (ref, got): lists of numpy (vocab,) logit rows — the admission
    logit plus ``steps`` decode steps each.

    schedule: 'gather' runs the dense-view oracle schedule; 'stream'
    passes per-step used-block counts so the block-streamed path (the
    serving default) is what gets checked against the dense reference.
    """
    batch = {"tokens": jnp.asarray([prompt], jnp.int32),
             "lengths": jnp.asarray([len(prompt)], jnp.int32)}
    logits, cache = model.prefill(params, batch, max_len)
    ref = [np.asarray(logits[0])]
    tok, pos = int(jnp.argmax(logits, -1)[0]), len(prompt)
    for _ in range(steps):
        logits, cache = model.decode_step(
            params, cache, jnp.asarray([tok], jnp.int32),
            jnp.asarray([pos], jnp.int32))
        ref.append(np.asarray(logits[0]))
        tok, pos = int(jnp.argmax(logits, -1)[0]), pos + 1

    nbk = blocks_for(max_len, block_size)
    pool = model.init_paged_cache(num_blocks=nbk + 1,
                                  block_size=block_size)
    nres = blocks_for(len(prompt) + steps + 1, block_size)
    tables = np.zeros((1, nbk), np.int32)
    tables[0, :nres] = range(1, 1 + nres)
    tables = jnp.asarray(tables)

    def used(last_pos):
        if schedule != "stream":
            return None
        return jnp.asarray([min(last_pos // block_size + 1, nbk)],
                           np.int32)

    for c0 in range(0, len(prompt), chunk):
        buf = np.zeros((1, chunk), np.int32)
        piece = prompt[c0:c0 + chunk]
        buf[0, :len(piece)] = piece
        lg, pool = model.decode_paged(params, pool, tables,
                                      jnp.asarray(buf),
                                      jnp.asarray([c0], np.int32),
                                      used(c0 + chunk - 1))
    got = [np.asarray(lg[0, len(prompt) - 1 - c0])]
    tok, pos = int(np.argmax(got[-1])), len(prompt)
    for _ in range(steps):
        lg, pool = model.decode_paged(
            params, pool, tables, jnp.asarray([[tok]], jnp.int32),
            jnp.asarray([pos], np.int32), used(pos))
        got.append(np.asarray(lg[0, 0]))
        tok, pos = int(np.argmax(got[-1])), pos + 1
    return ref, got


@pytest.mark.parametrize("layout,schedule", [
    pytest.param(lay, sched, id=sched if lay == "kv" else f"{lay}-{sched}")
    for lay in _LAYOUTS for sched in ("gather", "stream")])
def test_paged_logits_match_dense(layout, schedule):
    """Per-token logits through the paged graph match the dense
    prefill+decode path to fp tolerance (incl. a chunk-crossing
    prompt) — for every cache layout (kv / xv / x) on BOTH decode
    schedules: the dense gather view and the block-streamed early-exit
    path."""
    model, params = _mk_model(**_LAYOUTS[layout])
    prompt = [1] + list(range(5, 22))            # 18 tokens, chunks of 8
    ref, got = paged_vs_dense_logits(model, params, prompt, max_len=48,
                                     block_size=4, chunk=8, steps=4,
                                     schedule=schedule)
    assert len(ref) == len(got) == 5
    for r, g in zip(ref, got, strict=True):
        np.testing.assert_allclose(r, g, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("over", [
    {"score_mode": "standard"},
    {"score_mode": "standard", "cache_quant": "int8"},
    {"score_mode": "wqk"},
    {"score_mode": "wqk", "cache_mode": "x"},
    {"score_mode": "wqk_int8", "cache_quant": "int8"},
], ids=["kv", "kv-int8", "xv", "x", "x-int8"])
def test_stream_matches_gather_all_layouts(over):
    """Block-streamed decode == dense gather-view oracle on greedy
    outputs, at ragged per-slot lengths, for every cache layout
    (kv / xv / x, float and int8)."""
    model, params = _mk_model(**over)
    outs = {}
    for sched in ("stream", "gather"):
        eng = Engine(model, params, max_slots=2, max_len=64, paged=True,
                     block_size=8, prefill_chunk=16,
                     decode_schedule=sched)
        assert eng.decode_schedule == sched
        rr = _reqs(5)
        eng.run(rr)
        assert all(r.done for r in rr)
        outs[sched] = [r.output for r in rr]
    assert outs["stream"] == outs["gather"]


def test_streamed_eos_at_block_boundary(setup):
    """EOS landing exactly on a block boundary under the streamed
    schedule terminates identically to gather and frees every block."""
    model, params = setup
    BS, C = 4, 8
    prompt = [1] + list(range(7, 14))
    runs = {}
    for sched in ("stream", "gather"):
        probe = Request(rid=0, tokens=list(prompt), max_new_tokens=6,
                        eos_id=None)
        eng = Engine(model, params, max_slots=2, max_len=32, paged=True,
                     block_size=BS, prefill_chunk=C,
                     decode_schedule=sched)
        eng.run([probe])
        runs[sched] = probe.output
    assert runs["stream"] == runs["gather"]
    i_boundary = (BS - len(prompt) % BS) % BS or BS
    eos_tok = runs["stream"][i_boundary]
    eng = Engine(model, params, max_slots=2, max_len=32, paged=True,
                 block_size=BS, prefill_chunk=C, decode_schedule="stream")
    req = Request(rid=1, tokens=list(prompt), max_new_tokens=6,
                  eos_id=eos_tok)
    eng.run([req])
    assert req.done and req.finish_reason == "eos"
    assert req.output == runs["stream"][:i_boundary + 1]
    assert (len(prompt) + i_boundary) % BS == 0
    assert eng.allocator.num_free == eng.allocator.num_usable


def test_stream_schedule_rejected_without_backend_support():
    """Forcing 'stream' on a backend without block-stream support fails
    loudly at engine construction instead of silently gathering."""
    model, params = _mk_model(score_mode="factored")
    with pytest.raises(ValueError, match="block stream"):
        Engine(model, params, max_slots=2, max_len=32, paged=True,
               block_size=8, decode_schedule="stream")
    eng = Engine(model, params, max_slots=2, max_len=32, paged=True,
                 block_size=8)                       # auto degrades
    assert eng.decode_schedule == "gather"


# ----------------------------------------------------------------- sampling

def test_temperature_zero_is_greedy_and_seed_independent(setup):
    model, params = setup

    def run(seed):
        eng = Engine(model, params, max_slots=2, max_len=64, paged=True,
                     block_size=8, prefill_chunk=16, rng_seed=seed)
        rr = _reqs(3)
        eng.run(rr)
        return [r.output for r in rr]

    assert run(0) == run(1)


def test_temperature_sampling_deterministic_under_seed(setup):
    """temp>0: categorical sampling — deterministic given the engine
    seed, different across seeds, different from greedy; temp-0 rows in
    a mixed batch keep their greedy outputs."""
    model, params = setup

    def run(temp, seed):
        eng = Engine(model, params, max_slots=3, max_len=64, paged=True,
                     block_size=8, prefill_chunk=16, rng_seed=seed)
        rr = [Request(rid=i, tokens=[1, 5 + i, 9], max_new_tokens=8,
                      eos_id=None, temperature=temp) for i in range(3)]
        eng.run(rr)
        return [r.output for r in rr]

    hot_a, hot_b = run(1.0, 0), run(1.0, 0)
    assert hot_a == hot_b                       # seeded => reproducible
    assert run(1.0, 1) != hot_a                 # seed actually matters
    greedy = run(0.0, 0)
    assert hot_a != greedy                      # temperature matters

    # mixed batch: the greedy slot must be unaffected by hot neighbors
    eng = Engine(model, params, max_slots=2, max_len=64, paged=True,
                 block_size=8, prefill_chunk=16, rng_seed=0)
    rr = [Request(rid=0, tokens=[1, 5, 9], max_new_tokens=8, eos_id=None,
                  temperature=0.0),
          Request(rid=1, tokens=[1, 6, 9], max_new_tokens=8, eos_id=None,
                  temperature=1.5)]
    eng.run(rr)
    assert rr[0].output == greedy[0]


# ------------------------------------------------------------ finish reason

def test_finish_reasons(setup):
    """eos / length / truncated are distinguishable on completion."""
    model, params = setup
    # length: runs out of max_new_tokens
    eng = Engine(model, params, max_slots=2, max_len=64, paged=True,
                 block_size=8, prefill_chunk=16)
    r_len = Request(rid=0, tokens=[1, 5, 9], max_new_tokens=4, eos_id=None)
    eng.run([r_len])
    assert r_len.finish_reason == "length"
    # eos: replay with eos_id set to an observed token
    r_eos = Request(rid=1, tokens=[1, 5, 9], max_new_tokens=4,
                    eos_id=r_len.output[1])
    Engine(model, params, max_slots=2, max_len=64, paged=True,
           block_size=8, prefill_chunk=16).run([r_eos])
    assert r_eos.finish_reason == "eos"
    assert r_eos.output == r_len.output[:2]
    # truncated: hits the max_len-1 context wall with budget left
    eng3 = Engine(model, params, max_slots=1, max_len=16, paged=True,
                  block_size=8, prefill_chunk=8)
    r_tr = Request(rid=2, tokens=list(range(1, 11)), max_new_tokens=100,
                   eos_id=None)
    eng3.run([r_tr])
    assert r_tr.done and r_tr.finish_reason == "truncated"
    assert len(r_tr.output) < 100
    # an admission-completed request gets a reason too
    r_one = Request(rid=3, tokens=[1, 5, 9], max_new_tokens=1, eos_id=None)
    Engine(model, params, max_slots=2, max_len=64, paged=True,
           block_size=8, prefill_chunk=16).run([r_one])
    assert r_one.finish_reason == "length" and len(r_one.output) == 1


# ---------------------------------------------------------------- lifecycle

def test_eos_at_chunk_and_block_boundary(setup):
    """EOS landing exactly on a block/chunk boundary frees the slot and
    every block. Prompt length == prefill chunk exercises the full-final-
    chunk path; the EOS position is arranged to sit at pos % BS == 0."""
    model, params = setup
    BS, C = 4, 8
    prompt = [1] + list(range(7, 14))            # plen=8: exactly one chunk
    eng = Engine(model, params, max_slots=2, max_len=32, paged=True,
                 block_size=BS, prefill_chunk=C)
    probe = Request(rid=0, tokens=list(prompt), max_new_tokens=6,
                    eos_id=None)
    eng.run([probe])
    assert probe.done and len(probe.output) == 6
    assert eng.allocator.num_free == eng.allocator.num_usable

    # deterministic greedy: re-running with eos_id set to the token that
    # lands exactly on the boundary terminates right there.
    # output[i] sits at position plen + i; choose i with (plen+i) % BS == 0
    # (i >= 1: only tick-sampled tokens are EOS-checked)
    i_boundary = (BS - len(prompt) % BS) % BS or BS
    eos_tok = probe.output[i_boundary]
    assert eos_tok not in probe.output[1:i_boundary]  # no earlier EOS hit
    eng2 = Engine(model, params, max_slots=2, max_len=32, paged=True,
                  block_size=BS, prefill_chunk=C)
    req = Request(rid=1, tokens=list(prompt), max_new_tokens=6,
                  eos_id=eos_tok)
    eng2.run([req])
    assert req.done
    assert req.output == probe.output[:i_boundary + 1]
    assert (len(prompt) + i_boundary) % BS == 0
    assert eng2.allocator.num_free == eng2.allocator.num_usable
    assert eng2.slot_req == [None, None]


def test_block_reuse_after_eviction(setup):
    """More requests than slots: evicted sequences' blocks are recycled
    and a second wave on the same engine matches a fresh engine."""
    model, params = setup
    eng = Engine(model, params, max_slots=2, max_len=64, paged=True,
                 block_size=8, prefill_chunk=16)
    wave1 = _reqs(4, seed=1)
    eng.run(wave1)
    assert all(r.done for r in wave1)
    assert eng.allocator.num_free == eng.allocator.num_usable

    wave2 = _reqs(3, seed=2)
    fresh = _reqs(3, seed=2)
    eng.run(wave2)
    eng_fresh = Engine(model, params, max_slots=2, max_len=64, paged=True,
                       block_size=8, prefill_chunk=16)
    eng_fresh.run(fresh)
    for a, b in zip(wave2, fresh, strict=True):
        assert a.output == b.output       # recycled blocks are clean


def test_allocator_exhaustion_queues_requests(setup):
    """A pool too small for all requests at once serves them anyway —
    admission fails over to the queue, never crashes."""
    model, params = setup
    # each request: plen 17 + 6 new -> 3 blocks of 8; pool holds 7 usable
    eng = Engine(model, params, max_slots=4, max_len=64, paged=True,
                 block_size=8, num_blocks=8, prefill_chunk=16)
    rr = _reqs(4, plens=(17,), max_new=6)
    eng.run(rr)
    assert all(r.done for r in rr)
    assert eng.peak_active <= 2           # pool capped concurrency at 2
    assert eng.allocator.num_free == eng.allocator.num_usable

    # a request that can NEVER fit raises instead of spinning forever
    big = Request(rid=99, tokens=list(range(1, 60)), max_new_tokens=6,
                  eos_id=None)
    with pytest.raises(ValueError):
        eng.admit(big)


def test_admission_token_completes_request(setup):
    """max_new_tokens=1 yields exactly ONE token (the admission sample),
    and an EOS sampled straight out of prefill terminates immediately —
    in both cache regimes (a tick must never append a second token)."""
    model, params = setup
    for paged in (True, False):
        eng = Engine(model, params, max_slots=2, max_len=32, paged=paged,
                     block_size=8, prefill_chunk=16)
        r = Request(rid=0, tokens=[1, 5, 9], max_new_tokens=1,
                    eos_id=None)
        eng.run([r])
        assert r.done and len(r.output) == 1
        if paged:
            assert eng.allocator.num_free == eng.allocator.num_usable
        eng2 = Engine(model, params, max_slots=2, max_len=32, paged=paged,
                      block_size=8, prefill_chunk=16)
        r2 = Request(rid=1, tokens=[1, 5, 9], max_new_tokens=4,
                     eos_id=r.output[0])
        eng2.run([r2])
        assert r2.done and r2.output == r.output[:1]


def test_oversized_prompt_rejected(setup):
    """plen >= max_len is rejected up front in BOTH regimes — it would
    otherwise truncate the prompt into garbage output (paged: tail
    tokens routed to the null block)."""
    model, params = setup
    for paged in (True, False):
        eng = Engine(model, params, max_slots=2, max_len=32, paged=paged,
                     block_size=8, prefill_chunk=16)
        with pytest.raises(ValueError, match="prompt length"):
            eng.admit(Request(rid=0, tokens=list(range(1, 40)),
                              max_new_tokens=4, eos_id=None))


def test_prefix_sharing_correctness_and_reuse(setup):
    """Requests sharing a 24-token prompt prefix fork its full blocks:
    outputs are identical to unshared execution and the allocator hands
    out fewer fresh blocks."""
    model, params = setup
    rng = np.random.default_rng(7)
    prefix = [1] + rng.integers(3, 500, 23).tolist()

    def mk_reqs():
        return [Request(rid=i, tokens=prefix + [10 + i], max_new_tokens=5,
                        eos_id=None) for i in range(3)]

    shared = Engine(model, params, max_slots=3, max_len=64, paged=True,
                    block_size=8, prefill_chunk=16, prefix_sharing=True)
    plain = Engine(model, params, max_slots=3, max_len=64, paged=True,
                   block_size=8, prefill_chunk=16, prefix_sharing=False)

    # admit manually to observe the allocator mid-flight
    rs, rp = mk_reqs(), mk_reqs()
    for r in rs:
        assert shared.admit(r)
    for r in rp:
        assert plain.admit(r)
    # 25-token prompt + 5 new = 30 tokens -> 4 blocks each; sharing forks
    # the 3 full prefix blocks, so only the tail block is fresh
    assert shared.seq_blocks[1].num_shared == 3
    assert shared.seq_blocks[2].num_shared == 3
    used_shared = shared.allocator.num_usable - shared.allocator.num_free
    used_plain = plain.allocator.num_usable - plain.allocator.num_free
    assert used_shared == used_plain - 2 * 3
    for b in shared.seq_blocks[0].ids[:3]:
        assert shared.allocator.refcount(b) == 3

    shared.run(rs)
    plain.run(rp)
    for a, b in zip(rs, rp, strict=True):
        assert a.done and a.output == b.output
    assert shared.allocator.num_free == shared.allocator.num_usable


# --------------------------------------------- admission queue scanning

def test_admission_scans_past_blocked_head(setup):
    """Head-of-line fix: a pending head too big for the current pool
    must not starve a smaller request behind it — ``admit_from`` scans
    the queue (bounded by ``admit_scan``) and admits whatever fits."""
    model, params = setup

    def mk(admit_scan=8):
        eng = Engine(model, params, max_slots=2, max_len=64, paged=True,
                     block_size=8, num_blocks=6, prefill_chunk=16,
                     admit_scan=admit_scan)
        hog = Request(rid=0, tokens=[1] + list(range(5, 21)),
                      max_new_tokens=8)        # 4 of the 5 blocks
        assert eng.admit(hog)
        head = Request(rid=1, tokens=[1] + list(range(30, 38)),
                       max_new_tokens=8)       # needs 3: blocked
        small = Request(rid=2, tokens=[1, 5, 6], max_new_tokens=4)
        return eng, hog, head, small

    eng, hog, head, small = mk()
    pending = [head, small]
    assert eng.admit_from(pending) == 1
    assert pending == [head] and eng.slot_req.count(None) == 0

    # run() drains everything: head admitted once the hog finishes
    eng.run(pending)
    for r in (hog, head, small):
        assert r.done and r.finish_reason == "length"

    # the scan bound is honored: admit_scan=1 is the old head-only rule
    eng, hog, head, small = mk(admit_scan=1)
    pending = [head, small]
    assert eng.admit_from(pending) == 0
    assert pending == [head, small]


def test_temperature_sampling_slot_independent(setup):
    """Per-slot rid-keyed sampling: a temperature>0 request draws the
    same tokens whether it runs solo or co-batched with strangers —
    the property that keeps async admission reordering reproducible."""
    model, params = setup

    def engine():
        return Engine(model, params, max_slots=4, max_len=64, paged=True,
                      block_size=8, prefill_chunk=16, rng_seed=11)

    def mk(rid, seed, temp=0.9):
        rng = np.random.default_rng(seed)
        return Request(rid=rid, tokens=[1] + rng.integers(3, 500, 8).tolist(),
                       max_new_tokens=8, temperature=temp)

    solo = mk(5, seed=5)
    engine().run([solo])
    batched = mk(5, seed=5)
    others = [mk(i, seed=i) for i in (0, 1, 2)]
    engine().run(others + [batched])
    assert solo.output == batched.output
    # sanity: co-batched strangers drew per-slot streams, not copies
    assert len({tuple(r.output) for r in others}) == len(others)


# ------------------------------------------- allocator stateful fuzzing

try:
    from hypothesis import settings as h_settings, strategies as h_st
    from hypothesis.stateful import (RuleBasedStateMachine, invariant,
                                     rule, run_state_machine_as_test)
except ImportError:                       # CI container has no hypothesis
    from _hypothesis_fallback import (RuleBasedStateMachine, invariant,
                                      rule, run_state_machine_as_test,
                                      settings as h_settings,
                                      strategies as h_st)


class AllocatorMachine(RuleBasedStateMachine):
    """Adversarial alloc/fork/free/pin/unpin/ensure_exclusive
    interleavings against a reference model of who holds which block
    reference. Invariants after every step: refcounts exactly equal
    the model's reference multiset (never negative), conservation
    ``num_free + num_live == num_usable``, and copy-on-write never
    leaves one block exclusively owned by two holders."""

    def __init__(self):
        super().__init__()
        self.a = BlockAllocator(num_blocks=10, block_size=4)
        self.refs: list[int] = []      # one entry per sequence ref held
        self.pins: list[int] = []      # one entry per cache pin held

    @rule(n=h_st.integers(min_value=1, max_value=4))
    def alloc(self, n):
        ids = self.a.alloc(n)
        if ids is None:
            assert self.a.num_free < n       # all-or-nothing
        else:
            assert len(set(ids)) == n and NULL_BLOCK not in ids
            assert all(self.a.refcount(b) == 1 for b in ids)
            self.refs.extend(ids)

    @rule(i=h_st.integers(min_value=0, max_value=10 ** 6))
    def fork(self, i):
        if not self.refs:
            return
        bid = self.refs[i % len(self.refs)]
        assert self.a.fork([bid]) == [bid]
        self.refs.append(bid)

    @rule(i=h_st.integers(min_value=0, max_value=10 ** 6))
    def free(self, i):
        if not self.refs:
            return
        self.a.free([self.refs.pop(i % len(self.refs))])

    @rule(i=h_st.integers(min_value=0, max_value=10 ** 6))
    def pin(self, i):
        if not self.refs:
            return
        bid = self.refs[i % len(self.refs)]
        self.a.pin([bid])
        self.pins.append(bid)

    @rule(i=h_st.integers(min_value=0, max_value=10 ** 6))
    def unpin(self, i):
        if not self.pins:
            return
        self.a.unpin([self.pins.pop(i % len(self.pins))])

    @rule(i=h_st.integers(min_value=0, max_value=10 ** 6))
    def cow(self, i):
        if not self.refs:
            return
        idx = i % len(self.refs)
        bid = self.refs[idx]
        was_shared = self.a.refcount(bid) > 1
        copies = []
        got = self.a.ensure_exclusive(bid,
                                      lambda s, d: copies.append((s, d)))
        if got is None:                      # pool exhausted mid-CoW
            assert was_shared and self.a.num_free == 0
            return                           # our ref on bid survives
        self.refs[idx] = got
        if was_shared:
            # exclusivity: the writer got a fresh private block — no
            # block is ever exclusively owned by two holders
            assert got != bid and copies == [(bid, got)]
        else:
            assert got == bid and copies == []
        assert self.a.refcount(got) == 1

    @invariant()
    def refcounts_match_reference_model(self):
        held = {}
        for b in self.refs + self.pins:
            held[b] = held.get(b, 0) + 1
        for bid in range(1, self.a.num_blocks):
            assert self.a.refcount(bid) == held.get(bid, 0) >= 0
            assert self.a.pincount(bid) == self.pins.count(bid)
        assert self.a.refcount(NULL_BLOCK) == 0

    @invariant()
    def conservation(self):
        a = self.a
        assert a.num_free + a.num_live == a.num_usable


def test_allocator_stateful_invariants():
    run_state_machine_as_test(
        AllocatorMachine,
        settings=h_settings(max_examples=12, stateful_step_count=60))
