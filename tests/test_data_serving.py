"""Data pipeline determinism/resume + serving engine behaviour."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:                       # CI container has no hypothesis
    from _hypothesis_fallback import given, settings, strategies as st

from repro.configs.base import get_arch, reduced
from repro.data import pipeline, tokenizer
from repro.models.model import build_model
from repro.serving import kvcache
from repro.serving.engine import Engine, Request


def test_batch_deterministic():
    dc = pipeline.DataConfig(vocab_size=1000, seq_len=64, global_batch=4)
    a = pipeline.make_batch(dc, 17)
    b = pipeline.make_batch(dc, 17)
    for k in a:
        np.testing.assert_array_equal(a[k], b[k])
    c = pipeline.make_batch(dc, 18)
    assert not np.array_equal(a["tokens"], c["tokens"])


def test_iterator_stateless_resume():
    dc = pipeline.DataConfig(vocab_size=500, seq_len=32, global_batch=2)
    it = pipeline.DataIterator(dc)
    stream = [next(it) for _ in range(5)]
    it2 = pipeline.DataIterator(dc, start_step=3)
    resumed = next(it2)
    np.testing.assert_array_equal(stream[3]["tokens"], resumed["tokens"])


def test_host_slicing_partitions():
    dc = pipeline.DataConfig(vocab_size=500, seq_len=16, global_batch=8)
    full = pipeline.make_batch(dc, 0)
    parts = [pipeline.host_slice(full, h, 4) for h in range(4)]
    recon = np.concatenate([p["tokens"] for p in parts], axis=0)
    np.testing.assert_array_equal(full["tokens"], recon)


def test_unpacked_padding_stats():
    dc = pipeline.DataConfig(vocab_size=500, seq_len=256, global_batch=8,
                             pack=False, mean_doc_len=64)
    b = pipeline.make_batch(dc, 0)
    pf = pipeline.pad_fraction(b)
    assert 0.3 < pf < 0.99           # heavy padding: the zero-skip regime
    # labels under mask are PAD (zero) — the macro's zero-rich inputs
    assert np.all(b["labels"][b["loss_mask"] == 0] == tokenizer.PAD_ID)


def test_packing_removes_padding():
    """Sequence packing is the token-level zero-skip: the same documents
    that leave an unpacked batch padded fill a packed one to < 2%."""
    kw = dict(vocab_size=50000, seq_len=512, global_batch=16)
    unpacked = pipeline.make_batch(
        pipeline.DataConfig(pack=False, mean_doc_len=160, **kw), 0)
    packed = pipeline.make_batch(pipeline.DataConfig(pack=True, **kw), 0)
    assert pipeline.pad_fraction(packed) < 0.02 < \
        pipeline.pad_fraction(unpacked)


@settings(max_examples=20, deadline=None)
@given(st.text(min_size=0, max_size=60))
def test_tokenizer_roundtrip(s):
    tok = tokenizer.ByteTokenizer()
    assert tok.decode(tok.encode(s)) == s


def test_tokenizer_spread_roundtrip():
    tok = tokenizer.ByteTokenizer(vocab_size=152064, spread=True)
    s = "hello CIM macro"
    ids = tok.encode(s)
    assert max(ids) > 1000           # disperses into the big vocab
    assert tok.decode(ids) == s


# ------------------------------------------------------------- serving

@pytest.fixture(scope="module")
def engine_setup():
    cfg = reduced(get_arch("qwen2.5-14b"), num_layers=2)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return model, params


def test_engine_continuous_batching(engine_setup):
    model, params = engine_setup
    eng = Engine(model, params, max_slots=2, max_len=48)
    reqs = [Request(rid=i, tokens=[1, 4 + i, 9], max_new_tokens=6,
                    eos_id=None) for i in range(5)]
    out = eng.run(reqs)
    assert all(r.done for r in out)
    assert all(len(r.output) == 6 for r in out)
    # 5 requests x 5 decode ticks each on 2 slots -> ~13-16 ticks, far
    # fewer than sequential (25): continuous batching actually batched
    assert eng.ticks < 20


def test_engine_matches_offline_greedy(engine_setup):
    """Engine greedy decode == offline prefill+decode loop."""
    model, params = engine_setup
    prompt = [1, 7, 42, 9]
    eng = Engine(model, params, max_slots=1, max_len=32)
    req = Request(rid=0, tokens=list(prompt), max_new_tokens=5, eos_id=None)
    eng.run([req])

    batch = {"tokens": jnp.asarray([prompt], jnp.int32),
             "lengths": jnp.asarray([len(prompt)], jnp.int32)}
    logits, cache = model.prefill(params, batch, 32)
    toks = [int(jnp.argmax(logits, -1)[0])]
    pos = len(prompt)
    for _ in range(4):
        logits, cache = model.decode_step(
            params, cache, jnp.asarray([toks[-1]], jnp.int32),
            jnp.asarray([pos], jnp.int32))
        toks.append(int(jnp.argmax(logits, -1)[0]))
        pos += 1
    assert req.output == toks, (req.output, toks)


def test_cache_budget_paper_crossover():
    """DESIGN.md §4: X-cache wins iff D < 2·Hkv·dh — true for whisper
    (384 < 768), false for wide-GQA qwen (5120 > 2048)."""
    import dataclasses
    wh = get_arch("whisper-tiny")
    qw = get_arch("qwen2.5-14b")
    cmp_wh = kvcache.compare_modes(wh)
    cmp_qw = kvcache.compare_modes(qw)
    assert cmp_wh["x"] < cmp_wh["kv"]
    assert cmp_qw["x"] > cmp_qw["kv"]
    # auto rule picks pure-x (paper dataflow) from the crossover...
    b = kvcache.budget_for(dataclasses.replace(wh, cache_mode=None))
    assert b.mode == "x"
    assert b.max_tokens(16 << 30) > 0
    # ...while the production config pins xv for long contexts
    # (V-recompute crossover, EXPERIMENTS.md §Perf hillclimb C)
    assert kvcache.budget_for(wh).mode == "xv"
