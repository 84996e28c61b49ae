"""kernels/paged_attention: block-streamed decode vs the dense
gather-view oracle — every cache layout (kv / x / xv, float + int8),
the jnp while-loop reference AND the Pallas kernel (interpret mode on
CPU), ragged per-sequence lengths, windowed masks, chunk-shaped (n>1)
queries, and the ``blocks_used`` early exit (proved by NaN-poisoning
the blocks past the live region: the stream must never touch them).
The Pallas kernel's grid is the batch's live blocks in steps of K,
flattened: its step arrays, and tables that forks share, empty slots,
one-block rows, a row at the full table width and a windowed chunk,
each against the oracle where one step covers the whole table
(nbk < 128 // BS), where rows end inside a step of K < nbk blocks, and
where the VMEM rule halves K."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_arch, reduced
from repro.core import quant
from repro.core import score_backend as sb
from repro.kernels.paged_attention import kernel as pkernel
from repro.kernels.paged_attention import ops as pops

IMPLS = ("jnp", "pallas")
B, H, Hkv, dh, D = 3, 4, 2, 8, 12
BS, NBK, NB = 4, 6, 24
POS = np.array([5, 11, 21])           # ragged: 2 / 3 / 6 used blocks
# (block size, K, VMEM budget) of the Pallas kernel's grid over
# NBK-block tables: one step covers the whole table (K = NBK < 128 //
# BS), or steps of K = 128 // BS = 4 blocks, which the 2-, 3- and
# 6-block rows end inside, or, under a 3-MiB budget, steps the VMEM
# rule halves from K = NBK to 3 (the 64-token chunk's from 16 to 2)
GEOMETRY = {"one_step": (BS, NBK, None), "k4": (32, 4, None),
            "vmem": (8, 3, 3 << 20)}


def _rng():
    return np.random.default_rng(7)


def _tables(rng):
    # distinct physical blocks per sequence, never the null block 0
    ids = rng.permutation(np.arange(1, NB))[:B * NBK].reshape(B, NBK)
    return jnp.asarray(ids, jnp.int32)


def _used(pos, n, bs=BS):
    return jnp.asarray(-(-(pos + n) // bs), jnp.int32)


def _geometry(name, monkeypatch):
    """(block size, positions of POS's blocks at that size), after
    setting the geometry's VMEM budget and checking the kernel takes
    the K the geometry names."""
    bs, K, budget = GEOMETRY[name]
    if budget is not None:
        monkeypatch.setattr(pkernel, "VMEM_BYTES", budget)
    q = jax.ShapeDtypeStruct((B, H, 1, dh), jnp.float32)
    kp = jax.ShapeDtypeStruct((NB, bs, Hkv, dh), jnp.float32)
    assert pkernel.blocks_per_step(q, kp, NBK, v_pool=kp) == K
    return bs, POS * bs // BS


def _dense_oracle(q, kv, vv, qpos, scale, window=None):
    """The gather-view formula of models/attention._decode_attend."""
    S = kv.shape[1]
    n = q.shape[2]
    qg = q.reshape(B, Hkv if kv.shape[2] > 1 else 1, -1, n, q.shape[-1])
    s = jnp.einsum("bgrne,bsge->bgrns", qg, kv).reshape(B, H, n, S) * scale
    idx = jnp.arange(S)[None, None, :]
    ok = idx <= qpos[:, :, None]
    if window is not None:
        ok = ok & (idx > qpos[:, :, None] - window)
    s = s + jnp.where(ok, 0.0, -1e30)[:, None, :, :]
    a = jax.nn.softmax(s, axis=-1)
    ag = a.reshape(B, Hkv, H // Hkv, n, S)
    return jnp.einsum("bgrns,bsge->bgrne", ag, vv).reshape(B, H, n, -1)


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("n", [1, 3])
@pytest.mark.parametrize("int8", [False, True])
def test_kv_layout_matches_gather_oracle(impl, n, int8):
    rng = _rng()
    q = jnp.asarray(rng.normal(size=(B, H, n, dh)), jnp.float32)
    tables = _tables(rng)
    qpos = jnp.asarray(POS[:, None] + np.arange(n)[None, :])
    used = _used(POS, n)
    kf = rng.normal(size=(NB, BS, Hkv, dh)).astype(np.float32)
    vf = rng.normal(size=(NB, BS, Hkv, dh)).astype(np.float32)
    if int8:
        kp, ks = quant.quantize(jnp.asarray(kf), axis=-1)
        vp, vs = quant.quantize(jnp.asarray(vf), axis=-1)
        kd = kp.astype(jnp.float32) * ks
        vd = vp.astype(jnp.float32) * vs
    else:
        kp, vp, ks, vs = jnp.asarray(kf), jnp.asarray(vf), None, None
        kd, vd = kp, vp
    kv = jnp.take(kd, tables, axis=0).reshape(B, NBK * BS, Hkv, dh)
    vv = jnp.take(vd, tables, axis=0).reshape(B, NBK * BS, Hkv, dh)
    want = _dense_oracle(q, kv, vv, qpos, 0.25)
    got = pops.paged_attend(q, kp, tables, used, qpos, v_pool=vp,
                            k_scale=ks, v_scale=vs, scale=0.25, impl=impl)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("int8", [False, True])
@pytest.mark.parametrize("with_vpool", [False, True])
def test_x_layout_matches_gather_oracle(impl, int8, with_vpool):
    """X-consuming stream: [X 1] augmentation, per-row W8A8 requant, and
    pure-X V-recompute (the paper's weight-stationary dataflow) against
    the same math on the materialized view."""
    _check_x_layout(impl, int8, with_vpool, lambda rng: (
        _tables(rng), jnp.asarray(POS[:, None]), _used(POS, 1)))


def _check_x_layout(impl, int8, with_vpool, rows, bs=BS):
    """The X-layout stream against the gather oracle, over the
    ``(tables, qpos, blocks_used)`` that ``rows(rng)`` draws."""
    rng = _rng()
    n = 1
    xf = rng.normal(size=(NB, bs, D)).astype(np.float32)
    if int8:
        xq, xs = quant.quantize(jnp.asarray(xf), axis=-1)
        xdeq = xq.astype(jnp.float32) * xs
        kp, ks = xq[:, :, None, :], xs[:, :, None, :]
    else:
        kp, ks = jnp.asarray(xf)[:, :, None, :], None
        xdeq = jnp.asarray(xf)
    tables, qpos, used = rows(rng)
    g = jnp.asarray(rng.normal(size=(B, H, n, D + 1)), jnp.float32)
    wv = jnp.asarray(rng.normal(size=(D, Hkv, dh)), jnp.float32)
    bv = jnp.asarray(rng.normal(size=(Hkv, dh)), jnp.float32)

    xv = jnp.take(xdeq, tables, axis=0).reshape(B, NBK * bs, D)
    xaug = jnp.concatenate([xv, jnp.ones_like(xv[..., :1])], -1)
    # requant per row == the wqk_int8 score path on the gathered view
    qy, sy = quant.quantize(xaug, axis=-1)
    kvo = (qy.astype(jnp.float32) * sy)[:, :, None, :]
    if with_vpool:
        vf = jnp.asarray(rng.normal(size=(NB, bs, Hkv, dh)), jnp.float32)
        vv = jnp.take(vf, tables, axis=0).reshape(B, NBK * bs, Hkv, dh)
        vkw = dict(v_pool=vf)
    else:
        vv = jnp.einsum("bsd,dhe->bshe", xv, wv) + bv
        vkw = dict(wv=wv, bv=bv)
    want = _dense_oracle(g, kvo, vv, qpos, 0.25)
    got = pops.paged_attend(g, kp, tables, used, qpos, k_scale=ks,
                            scale=0.25, augment=True, requant=True,
                            impl=impl, **vkw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("geometry", sorted(GEOMETRY))
@pytest.mark.parametrize("impl", IMPLS)
def test_window_mask_matches_oracle(impl, geometry, monkeypatch):
    rng = _rng()
    bs, pos = _geometry(geometry, monkeypatch)
    q = jnp.asarray(rng.normal(size=(B, H, 1, dh)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(NB, bs, Hkv, dh)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(NB, bs, Hkv, dh)), jnp.float32)
    tables = _tables(rng)
    qpos = jnp.asarray(pos[:, None])
    used = _used(pos, 1, bs)
    kv = jnp.take(kp, tables, axis=0).reshape(B, NBK * bs, Hkv, dh)
    vv = jnp.take(vp, tables, axis=0).reshape(B, NBK * bs, Hkv, dh)
    for window in (5, jnp.asarray(7)):        # python int and traced
        want = _dense_oracle(q, kv, vv, qpos, 0.25, window=window)
        got = pops.paged_attend(q, kp, tables, used, qpos, v_pool=vp,
                                scale=0.25, window=window, impl=impl)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("impl", IMPLS)
def test_blocks_used_early_exit_skips_dead_blocks(impl):
    """Physical blocks past every sequence's ``blocks_used`` are
    NaN-poisoned; the stream must return finite, correct output — proof
    it genuinely never reads them (the gather view would propagate the
    NaN through its additive mask)."""
    rng = _rng()
    q = jnp.asarray(rng.normal(size=(B, H, 1, dh)), jnp.float32)
    kp = np.asarray(rng.normal(size=(NB, BS, Hkv, dh)), np.float32)
    vp = np.asarray(rng.normal(size=(NB, BS, Hkv, dh)), np.float32)
    tables = _tables(rng)
    qpos = jnp.asarray(POS[:, None])
    used = _used(POS, 1)
    want = _dense_oracle(
        q, jnp.take(jnp.asarray(kp), tables, 0).reshape(B, NBK * BS, Hkv, dh),
        jnp.take(jnp.asarray(vp), tables, 0).reshape(B, NBK * BS, Hkv, dh),
        qpos, 0.25)
    # poison every block no sequence can reach: per-sequence dead table
    # entries j >= used[b] are redirected to the null block by the
    # stream, so ONLY blocks live for some sequence may hold real data
    tab = np.asarray(tables)
    live = {0} | {tab[b, j] for b in range(B) for j in range(int(used[b]))}
    for pb in set(range(NB)) - live:
        kp[pb] = np.nan
        vp[pb] = np.nan
    got = pops.paged_attend(q, jnp.asarray(kp), tables, used, qpos,
                            v_pool=jnp.asarray(vp), scale=0.25, impl=impl)
    assert bool(jnp.all(jnp.isfinite(got)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def _flat_case(case, rng, bs, pos):
    """(tables, blocks_used, qpos, n, nbk, window) for one shape of the
    flattened grid at ``bs``-token blocks, rows ending at ``pos``.
    ``forked``: two rows share the first physical blocks of a third, as
    radix prefix forks make them; ``empty``: slots with no live block
    (the wrapper clips 0 to 1, an empty slot's position is 0);
    ``one_block``: rows of one live block each; ``full``: one row uses
    the whole table; ``chunk``: one 64-token chunk (B = 1) under a
    window, 21 of 22 blocks live."""
    if case == "chunk":
        nbk, n = 22, 64
        tables = rng.permutation(np.arange(1, NB))[:nbk].reshape(1, nbk)
        last = np.array([20 * bs + bs // 2])
        window = 37
    else:
        nbk, n, window = NBK, 1, None
        tables = np.array(_tables(rng))
        last = pos.copy()
        if case == "forked":
            tables[1, :3] = tables[0, :3]
            tables[2, :2] = tables[0, :2]
        if case == "full":
            last[1] = NBK * bs - 1
        if case == "empty":
            last[[0, 2]] = 0
        if case == "one_block":
            last = np.array([0, bs // 2, bs - 1])
    used = last // bs + 1
    if case == "empty":
        used[[0, 2]] = 0
    qpos = last[:, None] - n + 1 + np.arange(n)[None, :]
    return (jnp.asarray(tables, jnp.int32), jnp.asarray(used, jnp.int32),
            jnp.asarray(np.maximum(qpos, 0)), n, nbk, window)


def _oracle_view(pool, tables, nbk):
    return jnp.take(pool, tables, axis=0).reshape(
        (tables.shape[0], nbk * pool.shape[1]) + pool.shape[2:])


@pytest.mark.parametrize("geometry", sorted(GEOMETRY))
@pytest.mark.parametrize("case", ["forked", "empty", "one_block", "full",
                                  "chunk"])
def test_flattened_grid_matches_gather_oracle(case, geometry,
                                               monkeypatch):
    rng = _rng()
    bs, pos = _geometry(geometry, monkeypatch)
    tables, used, qpos, n, nbk, window = _flat_case(case, rng, bs, pos)
    b = tables.shape[0]
    q = jnp.asarray(rng.normal(size=(b, H, n, dh)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(NB, bs, Hkv, dh)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(NB, bs, Hkv, dh)), jnp.float32)
    kv, vv = _oracle_view(kp, tables, nbk), _oracle_view(vp, tables, nbk)
    S = nbk * bs
    s = jnp.einsum("bgrne,bsge->bgrns", q.reshape(b, Hkv, -1, n, dh),
                   kv).reshape(b, H, n, S) * 0.25
    idx = jnp.arange(S)[None, None, :]
    ok = idx <= qpos[:, :, None]
    if window is not None:
        ok = ok & (idx > qpos[:, :, None] - window)
    a = jax.nn.softmax(s + jnp.where(ok, 0.0, -1e30)[:, None], axis=-1)
    want = jnp.einsum("bgrns,bsge->bgrne", a.reshape(b, Hkv, -1, n, S),
                      vv).reshape(b, H, n, dh)
    got = pops.paged_attend(q, kp, tables, used, qpos, v_pool=vp,
                            scale=0.25, window=window, impl="pallas")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("geometry", sorted(GEOMETRY))
def test_flattened_grid_int8_x_pool_with_forks(geometry, monkeypatch):
    """The int8 X pool (shared stream, per-row scales, V recomputed
    through W_V) over forked rows, an empty slot and a full row."""
    bs, _ = _geometry(geometry, monkeypatch)

    def rows(rng):
        tables = np.array(_tables(rng))
        tables[2, :2] = tables[1, :2]
        return (jnp.asarray(tables),
                jnp.asarray([[0], [NBK * bs - 1], [bs + 2]]),
                jnp.asarray([0, NBK, 2], jnp.int32))
    _check_x_layout("pallas", True, False, rows, bs)


def test_stream_blocks_per_step_reads_one_devices_operands():
    """The engine's K is the kernel's pick on the operands each device
    is handed: at qwen2.5-14b's widths (bf16 pool of 16-token blocks,
    256-block tables) the 64-token chunk takes 2 blocks a step on one
    chip and 8 on each device of a 4-way model axis, which streams 2
    of the 8 kv heads; the decode tick takes 8 either way."""
    import types

    from repro.models import attention
    cfg = get_arch("qwen2.5-14b")
    be = sb.plan(cfg).backend
    sds = jax.ShapeDtypeStruct
    rows = sds((2048, 16, cfg.num_kv_heads, cfg.head_dim), jnp.bfloat16)
    pool = attention.KVCache(k=rows, v=rows)
    mesh = types.SimpleNamespace(shape={"model": 4})

    def K(n, mesh=None):
        return attention.stream_blocks_per_step({}, pool, cfg, be, 256,
                                                n=n, mesh=mesh)
    local = sds((2048, 16, 2, cfg.head_dim), jnp.bfloat16)
    assert K(64) == 2
    assert K(64, mesh) == 8 == pkernel.blocks_per_step(
        sds((1, 10, 64, cfg.head_dim), jnp.float32), local, 256,
        v_pool=local)
    assert K(1) == K(1, mesh) == 8


def test_step_arrays_walk_the_live_blocks_in_sequence_order():
    """ceil(used / K) steps per row (more than the pool holds, for
    forked rows), sequence-major, each row's steps restarting at its
    first block, and sub-block t of each step on its row's block j·K + t
    while live, on the null block past the row's used blocks and past
    the table's width (the wrapper pads the table to whole steps)."""
    nbk, nb, K = 6, 8, 4
    tables = np.arange(24).reshape(4, nbk) % (nb - 1) + 1
    tables[3] = tables[1]                          # a fork of row 1
    used = np.array([0, 3, 6, 5])
    clipped = np.clip(used, 1, nbk)
    seq, start, total = (np.asarray(a) for a in pkernel.step_arrays(
        jnp.asarray(clipped, jnp.int32), nbk, K))
    steps = -(-clipped // K)                       # 1, 1, 2, 2
    assert seq.shape == (4 * 2,)
    assert int(total) == steps.sum() == 6
    want_seq = np.repeat(np.arange(4), steps)
    want_j = np.concatenate([np.arange(u) for u in steps])
    np.testing.assert_array_equal(seq[:total], want_seq)
    np.testing.assert_array_equal(start, np.cumsum(steps) - steps)
    np.testing.assert_array_equal(np.arange(total) - start[seq[:total]],
                                  want_j)
    assert (seq[total:] == 3).all()
    padded = np.pad(tables, ((0, 0), (0, (-nbk) % K)))
    maps = pkernel.sub_block_maps(K)
    got = np.array([[imap(i, seq, start, clipped, padded,
                          _where=np.where)[0] for imap in maps]
                    for i in range(total)])
    blk = want_j[:, None] * K + np.arange(K)[None, :]
    live = blk < clipped[want_seq][:, None]
    want = np.where(live, padded[want_seq[:, None], blk], 0)
    np.testing.assert_array_equal(got, want)
    # every live block is fetched once, by its own row's step
    assert live.sum() == clipped.sum() > nb


def test_stream_q_matches_backend_scores():
    """Every block-stream-capable X backend's ``stream_q`` reproduces
    its own quadratic ``scores`` against requantized cache rows — the
    identity the streamed schedule relies on."""
    rng = _rng()
    cfg = dataclasses.replace(
        reduced(get_arch("qwen2.5-14b"), num_layers=2), dtype="float32")
    n, m = 2, 9
    x_q = jnp.asarray(rng.normal(size=(1, n, cfg.d_model)), jnp.float32)
    x_kv = jnp.asarray(rng.normal(size=(1, m, cfg.d_model)), jnp.float32)
    wq = jnp.asarray(rng.normal(size=(cfg.d_model, cfg.num_heads, 16)),
                     jnp.float32)
    wk = jnp.asarray(rng.normal(size=(cfg.d_model, cfg.num_kv_heads, 16)),
                     jnp.float32)
    bq = jnp.asarray(rng.normal(size=(cfg.num_heads, 16)), jnp.float32)
    bk = jnp.asarray(rng.normal(size=(cfg.num_kv_heads, 16)), jnp.float32)
    sw = sb.ScoreWeights(wq=wq, wk=wk, bq=bq, bk=bk)
    for name in ("wqk", "wqk_int8", "wqk_int8_pallas"):
        be = sb.get_backend(name)
        assert be.supports_block_stream
        want = be.scores(x_q, x_kv, sw, scale=0.125)
        qe = be.stream_q(sw, x_q)                  # (1, H, n, Daug)
        xaug = jnp.concatenate([x_kv, jnp.ones_like(x_kv[..., :1])], -1)
        if be.quantized:
            qy, sy = quant.quantize(xaug, axis=-1)
            got = jnp.einsum("bhne,bme->bhnm", qe,
                             qy.astype(jnp.float32)) * sy[..., 0][:, None, None, :]
        else:
            got = jnp.einsum("bhne,bme->bhnm", qe, xaug)
        np.testing.assert_allclose(np.asarray(got * 0.125),
                                   np.asarray(want), rtol=2e-4, atol=1e-4)
    assert not sb.get_backend("factored").supports_block_stream


def test_planner_decode_schedule():
    base = dataclasses.replace(reduced(get_arch("qwen2.5-14b")))
    assert sb.plan(base).decode_schedule == "stream"
    assert sb.plan(dataclasses.replace(
        base, decode_schedule="gather")).decode_schedule == "gather"
    # factored can't stream: explicit 'stream' request degrades to
    # gather with the reason recorded, instead of crashing decode
    fac = dataclasses.replace(base, score_mode="factored",
                              decode_schedule="stream")
    p = sb.plan(fac)
    assert p.decode_schedule == "gather" and "gather" in p.reason
    with pytest.raises(ValueError, match="decode_schedule"):
        sb.plan(dataclasses.replace(base, decode_schedule="bogus"))
