"""Attention layers: MHA/GQA, causal / sliding-window / local:global masks,
full-sequence and cached-decode paths, with the paper's score paths plumbed
through the ``core.score_backend`` registry.

Which backend evaluates S — and whether the quadratic or blockwise-flash
schedule runs — is decided by ``score_backend.plan``; this module only
keys off capability flags (never score-mode strings).

Layouts: x (B, N, D); wq (D, H, dh); wk/wv (D, Hkv, dh); wo (H, dh, D).
Head axes shard over the "model" mesh axis; D over "data" (FSDP).
"""
from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import score_backend as sb
from repro.core.score_backend import ScoreWeights
from repro.models import layers

NEG_INF = -1e30


def init_attention(rng, cfg, dtype, cross: bool = False) -> dict:
    d, H, Hkv, dh = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(rng, 4)
    s = 1.0 / math.sqrt(d)
    p = {
        "wq": jax.random.normal(ks[0], (d, H, dh), dtype) * s,
        "wk": jax.random.normal(ks[1], (d, Hkv, dh), dtype) * s,
        "wv": jax.random.normal(ks[2], (d, Hkv, dh), dtype) * s,
        "wo": jax.random.normal(ks[3], (H, dh, d), dtype) * (1.0 / math.sqrt(H * dh)),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H, dh), dtype)
        p["bk"] = jnp.zeros((Hkv, dh), dtype)
        p["bv"] = jnp.zeros((Hkv, dh), dtype)
    return p


def score_weights(p: dict) -> ScoreWeights:
    return ScoreWeights(wq=p["wq"], wk=p["wk"],
                        bq=p.get("bq"), bk=p.get("bk"),
                        wqk=p.get("wqk"))


def _mask_bias(positions_q, positions_kv, kind: str,
               window: int | None) -> jax.Array:
    """Additive mask bias (..., Nq, Nk). kind: causal|window|none."""
    if kind == "none":
        iq = positions_q[..., :, None]
        ik = positions_kv[..., None, :]
        return jnp.zeros(jnp.broadcast_shapes(iq.shape, ik.shape), jnp.float32)
    iq = positions_q[..., :, None]
    ik = positions_kv[..., None, :]
    ok = ik <= iq
    if window is not None:
        # window may be a traced per-layer scalar (gemma local:global
        # scan); BIG_WINDOW makes it a no-op arithmetically
        ok = ok & (ik > iq - window)
    return jnp.where(ok, 0.0, NEG_INF)


def _values(p: dict, x_kv: jax.Array, H: int) -> jax.Array:
    """V projection, repeated to H query heads: (..., H, Nk, dh)."""
    Hkv = p["wv"].shape[1]
    v = jnp.einsum("...nd,dhe->...hne", x_kv, p["wv"].astype(x_kv.dtype))
    if "bv" in p:
        v = v + p["bv"][:, None, :].astype(v.dtype)
    return jnp.repeat(v, H // Hkv, axis=-3)


def attention_full(p: dict, x_q: jax.Array, x_kv: jax.Array, cfg, *,
                   positions_q: jax.Array, positions_kv: jax.Array,
                   mask_kind: str = "causal",
                   window: jax.Array | None = None,
                   backend=None) -> jax.Array:
    """Full-sequence attention (training / prefill). -> (..., Nq, D).

    The planner picks the backend and the schedule: long sequences take
    the blockwise online-softmax path (flash schedule in portable jnp —
    S never materializes) when the backend supports it; per-batch 2-D
    positions force the quadratic path (the shared flash K-stream needs
    1-D positions)."""
    plan = sb.plan(cfg, backend=backend,
                   seq_len=x_kv.shape[-2] if positions_q.ndim == 1 else None,
                   mask_kind=mask_kind)
    be = plan.backend
    if plan.blockwise:
        return _attention_full_blockwise(
            p, x_q, x_kv, cfg, positions_q=positions_q,
            positions_kv=positions_kv, mask_kind=mask_kind,
            window=window, plan=plan)
    H, dh = cfg.num_heads, cfg.head_dim
    scale = 1.0 / math.sqrt(dh)
    rope_fn = None
    if cfg.pos_emb == "rope" and be.needs_rope:
        rope_fn = lambda t, which: layers.apply_rope(
            t, positions_q if which == "q" else positions_kv, cfg.rope_theta)
    s = be.scores(x_q, x_kv, score_weights(p), scale=scale, rope_fn=rope_fn)
    if cfg.logit_softcap:
        s = jnp.tanh(s / cfg.logit_softcap) * cfg.logit_softcap
    bias = _mask_bias(positions_q, positions_kv, mask_kind, window)
    s = s + bias[..., None, :, :]          # broadcast over head axis
    a = jax.nn.softmax(s, axis=-1).astype(x_q.dtype)
    v = _values(p, x_kv, H)
    o = jnp.einsum("...hnm,...hme->...hne", a, v)
    return jnp.einsum("...hne,hed->...nd", o, p["wo"].astype(x_q.dtype))


# ------------------------------------------------- blockwise (flash) path

def _blockwise_core(q, k, v, pos_q, pos_k, valid_k, *, scale, causal,
                    window, softcap, block_m):
    """Online-softmax attention over KV blocks with a custom-VJP
    backward (models/flash.py) — neither forward scores nor backward
    score-gradients ever materialize.

    q (B, Gs, Rs, N, E): score groups (standard GQA: Gs=Hkv, Rs=q_per_kv;
    wqk mode: Gs=1, Rs=H — one shared raw-X K-stream, the paper's
    dataflow). k (B, Gs, M, E); v (B, Hkv, M, dv); pos_* 1-D positions;
    valid_k (M,) masks padding. H = Gs*Rs must equal Hkv*Rv.
    """
    from repro.models import flash
    from repro.sharding import act
    q = act.constrain_grouped_q(q)      # row-parallel attention over TP
    return flash.attend(q, k, v, pos_q, pos_k, scale=scale, causal=causal,
                        window=window, softcap=softcap, block_m=block_m,
                        valid_k=valid_k)


def _attention_full_blockwise(p, x_q, x_kv, cfg, *, positions_q,
                              positions_kv, mask_kind, window, plan):
    dh = cfg.head_dim
    scale = 1.0 / math.sqrt(dh)
    dt = x_q.dtype
    xq3 = x_q if x_q.ndim == 3 else x_q[None]
    xk3 = x_kv if x_kv.ndim == 3 else x_kv[None]
    causal = mask_kind == "causal"
    valid = jnp.ones((xk3.shape[-2],), bool)

    v = jnp.einsum("bnd,dhe->bhne", xk3, p["wv"].astype(dt))
    if "bv" in p:
        v = v + p["bv"][:, None, :].astype(dt)

    rope_q = rope_k = None
    if cfg.pos_emb == "rope" and plan.backend.needs_rope:
        rope_q = lambda t: layers.apply_rope(t, positions_q, cfg.rope_theta)
        rope_k = lambda t: layers.apply_rope(t, positions_kv, cfg.rope_theta)
    q, k = plan.backend.blockwise_qk(score_weights(p), xq3, xk3, dtype=dt,
                                     rope_q=rope_q, rope_k=rope_k)
    o = _blockwise_core(q, k, v, positions_q, positions_kv, valid,
                        scale=scale, causal=causal, window=window,
                        softcap=cfg.logit_softcap, block_m=plan.block_m)
    out = jnp.einsum("bhne,hed->bnd", o.astype(dt), p["wo"].astype(dt))
    return out if x_q.ndim == 3 else out[0]


# ------------------------------------------------------------------- decode

class KVCache(NamedTuple):
    """Per-layer decode cache. Exactly one of (k) or (x) is used for
    scores depending on the cache mode; v is None in pure-X mode
    (recomputed from x — the paper's weight-stationary dataflow).
    With cfg.cache_quant == "int8", x is int8 and xs holds per-token
    scales (the macro's own 8-bit input format)."""
    k: jax.Array | None = None   # (B, Smax, Hkv, dh)
    v: jax.Array | None = None   # (B, Smax, Hkv, dh)
    x: jax.Array | None = None   # (B, Smax, D)  raw inputs (wqk modes)
    xs: jax.Array | None = None  # (B, Smax, 1) f32 scales (int8 cache)
    ks: jax.Array | None = None  # (B, Smax, Hkv, 1) scales (int8 kv)
    vs: jax.Array | None = None  # (B, Smax, Hkv, 1) scales (int8 kv)


def cache_mode_for(cfg) -> str:
    """kv: K-consuming backends; xv: X-cache scores + V-cache; x: X only
    (V recomputed). Delegates to the planner (capability-flag keyed)."""
    return sb.plan(cfg).cache_mode


def init_kv_cache(cfg, batch: int, max_len: int, dtype,
                  mode: str | None = None) -> KVCache:
    mode = mode or cache_mode_for(cfg)
    Hkv, dh, D = cfg.num_kv_heads, cfg.head_dim, cfg.d_model
    mk = lambda *shp: jnp.zeros(shp, dtype)
    q8 = getattr(cfg, "cache_quant", None) == "int8"
    if mode == "kv":
        if q8:
            return KVCache(
                k=jnp.zeros((batch, max_len, Hkv, dh), jnp.int8),
                v=jnp.zeros((batch, max_len, Hkv, dh), jnp.int8),
                ks=jnp.ones((batch, max_len, Hkv, 1), jnp.float32),
                vs=jnp.ones((batch, max_len, Hkv, 1), jnp.float32))
        return KVCache(k=mk(batch, max_len, Hkv, dh),
                       v=mk(batch, max_len, Hkv, dh))
    x = (jnp.zeros((batch, max_len, D), jnp.int8) if q8
         else mk(batch, max_len, D))
    xs = jnp.ones((batch, max_len, 1), jnp.float32) if q8 else None
    if mode == "xv":
        return KVCache(v=mk(batch, max_len, Hkv, dh), x=x, xs=xs)
    return KVCache(x=x, xs=xs)


def write_x(cache: KVCache, x_new: jax.Array, cfg, *, pos=None) -> KVCache:
    """Write raw-input rows into the X-cache, quantizing to the macro's
    int8 input format when cfg.cache_quant == 'int8'. pos=None fills
    from the origin (prefill); else per-batch positions (decode)."""
    if cache.xs is not None:
        from repro.core import quant
        q, s = quant.quantize(x_new, axis=-1)
        if pos is None:
            from repro.models.model import _fill_seq
            return cache._replace(x=_fill_seq(cache.x, q),
                                  xs=_fill_seq(cache.xs, s))
        return cache._replace(x=_update_at(cache.x, q, pos),
                              xs=_update_at(cache.xs, s, pos))
    if pos is None:
        from repro.models.model import _fill_seq
        return cache._replace(x=_fill_seq(cache.x, x_new))
    return cache._replace(x=_update_at(cache.x, x_new, pos))


def read_x(cache: KVCache, dtype) -> jax.Array:
    """Dequantized view of the X-cache (fused on TPU; HBM reads int8)."""
    if cache.xs is not None:
        return (cache.x.astype(jnp.float32) * cache.xs).astype(dtype)
    return cache.x


def write_kv(cache: KVCache, k_new, v_new, cfg, *, pos=None) -> KVCache:
    """Write K/V rows (B, n, Hkv, dh), int8-quantizing per (token, head)
    when cfg.cache_quant == 'int8' — the W8A8 storage format applied to
    the conventional cache. pos=None fills from origin (prefill)."""
    q8 = cache.ks is not None
    if q8:
        from repro.core import quant
        if k_new is not None:
            k_new, ks = quant.quantize(k_new, axis=-1)
        if v_new is not None:
            v_new, vs = quant.quantize(v_new, axis=-1)
    if pos is None:
        from repro.models.model import _fill_seq
        upd = _fill_seq
    else:
        upd = lambda c, n: _update_at(c, n, pos)
    if k_new is not None:
        cache = cache._replace(k=upd(cache.k, k_new))
        if q8:
            cache = cache._replace(ks=upd(cache.ks, ks))
    if v_new is not None:
        cache = cache._replace(v=upd(cache.v, v_new))
        if q8:
            cache = cache._replace(vs=upd(cache.vs, vs))
    return cache


def read_kv(cache: KVCache, dtype):
    """(k, v) dequantized views (int8 HBM reads; dequant fuses on TPU)."""
    k, v = cache.k, cache.v
    if cache.ks is not None and k is not None:
        k = (k.astype(jnp.float32) * cache.ks).astype(dtype)
    if cache.vs is not None and v is not None:
        v = (v.astype(jnp.float32) * cache.vs).astype(dtype)
    return k, v


def _update_at(cache: jax.Array, new: jax.Array,
               pos: jax.Array) -> jax.Array:
    """cache (B, S, ...) <- new (B, 1, ...) at per-batch positions (B,)."""
    def upd(c, n, p):
        return jax.lax.dynamic_update_slice_in_dim(c, n, p, axis=0)
    return jax.vmap(upd)(cache, new, pos)


# ----------------------------------------------------- paged decode cache

def gather_block_view(pool: KVCache, tables: jax.Array) -> KVCache:
    """Materialize per-sequence contiguous cache views from a block pool.

    pool leaves (NB, BS, ...): physical block id x offset-in-block.
    tables (B, nbk) int32: logical block i of sequence b lives in
    physical block tables[b, i]. Returns a KVCache whose leaves are
    (B, nbk*BS, ...) — logical-position order, so every dense decode
    formula (masks, scores, value gathers) applies unchanged.
    """
    B = tables.shape[0]

    def g(leaf):
        v = jnp.take(leaf, tables, axis=0)          # (B, nbk, BS, ...)
        return v.reshape((B, -1) + leaf.shape[2:])
    return jax.tree_util.tree_map(g, pool)


def _scatter_rows(leaf: jax.Array, rows: jax.Array, bids: jax.Array,
                  offs: jax.Array) -> jax.Array:
    """leaf (NB, BS, ...) <- rows (B, n, ...) at physical (bids, offs),
    both (B, n). The engine guarantees distinct (bid, off) pairs across
    live rows (blocks are exclusively owned for writing); padding rows
    all target the null block, where last-write-wins is harmless."""
    return leaf.at[bids, offs].set(rows.astype(leaf.dtype))


def paged_write_x(pool: KVCache, x_new: jax.Array, bids: jax.Array,
                  offs: jax.Array) -> KVCache:
    """Scatter raw-input rows (B, n, D) into the pooled X-cache,
    int8-quantizing exactly like ``write_x`` when the pool is int8."""
    if pool.xs is not None:
        from repro.core import quant
        q, s = quant.quantize(x_new, axis=-1)
        return pool._replace(x=_scatter_rows(pool.x, q, bids, offs),
                             xs=_scatter_rows(pool.xs, s, bids, offs))
    return pool._replace(x=_scatter_rows(pool.x, x_new, bids, offs))


def paged_write_kv(pool: KVCache, k_new, v_new, bids: jax.Array,
                   offs: jax.Array) -> KVCache:
    """Scatter K/V rows (B, n, Hkv, dh) into the pooled cache (int8
    per-(token, head) quantization mirrors ``write_kv``)."""
    q8 = pool.ks is not None
    if q8:
        from repro.core import quant
        if k_new is not None:
            k_new, ks = quant.quantize(k_new, axis=-1)
        if v_new is not None:
            v_new, vs = quant.quantize(v_new, axis=-1)
    if k_new is not None:
        pool = pool._replace(k=_scatter_rows(pool.k, k_new, bids, offs))
        if q8:
            pool = pool._replace(ks=_scatter_rows(pool.ks, ks, bids, offs))
    if v_new is not None:
        pool = pool._replace(v=_scatter_rows(pool.v, v_new, bids, offs))
        if q8:
            pool = pool._replace(vs=_scatter_rows(pool.vs, vs, bids, offs))
    return pool


def _decode_qkv(p: dict, x_new: jax.Array, cfg, be, qpos: jax.Array):
    """Q/K/V projections (+bias, +RoPE at qpos) for n new tokens.
    q (B, H, n, dh); k_new/v_new (B, n, Hkv, dh) — token-major, ready
    for a cache write. Used only by K-consuming backends."""
    dt = x_new.dtype
    q = jnp.einsum("bnd,dhe->bhne", x_new, p["wq"].astype(dt))
    k_new = jnp.einsum("bnd,dhe->bnhe", x_new, p["wk"].astype(dt))
    v_new = _project_v_rows(p, x_new)
    if "bq" in p:
        q = q + p["bq"][:, None, :].astype(dt)
        k_new = k_new + p["bk"][None, None].astype(dt)
    if cfg.pos_emb == "rope" and be.needs_rope:
        q = layers.apply_rope(q, qpos, cfg.rope_theta)
        k_new = layers.apply_rope(
            k_new.swapaxes(1, 2), qpos, cfg.rope_theta).swapaxes(1, 2)
    return q, k_new, v_new


def _project_v_rows(p: dict, x: jax.Array) -> jax.Array:
    """V rows for cache writes: (B, n, D) -> (B, n, Hkv, dh)."""
    v = jnp.einsum("bnd,dhe->bnhe", x, p["wv"].astype(x.dtype))
    if "bv" in p:
        v = v + p["bv"][None, None].astype(v.dtype)
    return v


def _decode_attend(p: dict, x_new: jax.Array, q, view: KVCache,
                   qpos: jax.Array, cfg, be,
                   window: int | None) -> jax.Array:
    """Attention math shared by the dense and paged decode paths.

    view: the post-write cache in logical-position order — the dense
    cache itself, or ``gather_block_view`` of the paged pool. q is the
    pre-projected query (K-consuming backends) or None (X-consuming
    backends score straight from x_new). qpos (B, n) are the query
    positions; every query attends cache positions <= its own, so
    chunked prefill (n=C) and decode ticks (n=1) are the same graph.
    """
    H, Hkv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    scale = 1.0 / math.sqrt(dh)
    B, n, _ = x_new.shape
    dt = x_new.dtype
    leaf = view.k if view.k is not None else (
        view.x if view.x is not None else view.v)
    S = leaf.shape[1]

    if not be.uses_x_cache:
        k_cache, v_src = read_kv(view, dt)
        qg = q.reshape(B, Hkv, H // Hkv, n, dh)
        s = jnp.einsum("bgrne,bsge->bgrns", qg.astype(jnp.float32),
                       k_cache.astype(jnp.float32)).reshape(B, H, n, S) * scale
    else:
        x_cache = read_x(view, dt)
        s = be.scores(x_new, x_cache, score_weights(p), scale=scale)
        if view.v is not None:
            _, v_src = read_kv(view, dt)
        else:                       # pure-X: V recomputed from the cache
            v_src = jnp.einsum("bsd,dhe->bshe", x_cache, p["wv"].astype(dt))
            if "bv" in p:
                v_src = v_src + p["bv"][None, None].astype(dt)

    if cfg.logit_softcap:
        s = jnp.tanh(s / cfg.logit_softcap) * cfg.logit_softcap
    idx = jnp.arange(S)[None, None, :]                    # (1, 1, S)
    ok = idx <= qpos[:, :, None]
    if window is not None:
        ok = ok & (idx > qpos[:, :, None] - window)
    s = s + jnp.where(ok, 0.0, NEG_INF)[:, None, :, :]
    # probabilities and the AV contraction stay float32, as in the
    # streamed kernel: one rounding to the model dtype, at the output
    a = jax.nn.softmax(s, axis=-1)

    ag = a.reshape(B, Hkv, H // Hkv, n, S)
    o = jnp.einsum("bgrns,bsge->bgrne", ag,
                   v_src.astype(jnp.float32)).reshape(B, H, n, dh)
    from repro.sharding import act
    o = act.constrain_heads(o)      # TP: one combine, at the wo einsum
    return jnp.einsum("bhne,hed->bnd", o.astype(dt), p["wo"].astype(dt))


def _decode_attend_streamed(p: dict, x_new: jax.Array, q, pool: KVCache,
                            tables: jax.Array, blocks_used: jax.Array,
                            qpos: jax.Array, cfg, be,
                            window: int | None) -> jax.Array:
    """Block-streamed decode attention (kernels/paged_attention): the
    physical pool is gathered block-by-block through the table inside
    the attention loop, online-softmaxed, and the stream stops at the
    batch's longest ``blocks_used`` — tick cost scales with actual
    sequence length, not the table capacity. Numerics twin of
    ``_decode_attend`` over ``gather_block_view`` (the parity oracle).
    """
    from repro.kernels.paged_attention import paged_attend
    dh = cfg.head_dim
    scale = 1.0 / math.sqrt(dh)
    dt = x_new.dtype
    softcap = float(cfg.logit_softcap or 0.0)

    if not be.uses_x_cache:
        o = _stream_kv(q.astype(jnp.float32), pool, tables, blocks_used,
                       qpos, window, scale=scale, softcap=softcap)
    else:
        qe = be.stream_q(score_weights(p), x_new)       # (B, H, n, Daug)
        aug = qe.shape[-1] == pool.x.shape[-1] + 1
        kp = pool.x[:, :, None, :]                      # shared X stream
        ks = None if pool.xs is None else pool.xs[:, :, None, :]
        common = dict(k_scale=ks, scale=scale, window=window,
                      softcap=softcap, augment=aug, requant=be.quantized)
        if pool.v is not None:
            o = paged_attend(qe, kp, tables, blocks_used, qpos,
                             v_pool=pool.v, v_scale=pool.vs, **common)
        else:                       # pure-X: V recomputed block-by-block
            o = paged_attend(qe, kp, tables, blocks_used, qpos,
                             wv=p["wv"].astype(jnp.float32),
                             bv=None if "bv" not in p else
                             p["bv"].astype(jnp.float32), **common)
    from repro.sharding import act
    o = act.constrain_heads(o)      # TP: one combine, at the wo einsum
    return jnp.einsum("bhne,hed->bnd", o.astype(dt), p["wo"].astype(dt))


def _stream_kv(q, pool: KVCache, tables, blocks_used, qpos, window,
               **kw) -> jax.Array:
    """kv-layout ``paged_attend``. Under an installed mesh it runs per
    device through ``jax.shard_map``: heads on "model", tables,
    ``blocks_used``, ``qpos`` and the window replicated. GSPMD cannot
    partition a Pallas kernel, and the heads need no communication —
    each device streams its own head-slice of the pool. When Hkv does
    not divide the model axis (the ``NonDividingShardWarning`` layout)
    every operand replicates and each device computes all heads."""
    from repro.kernels.paged_attention import paged_attend
    from repro.sharding import act

    def local(q, k, v, ks, vs, tb, bu, qp, win):
        return paged_attend(q, k, tb, bu, qp, v_pool=v, k_scale=ks,
                            v_scale=vs, window=win, **kw)

    args = (q, pool.k, pool.v, pool.ks, pool.vs, tables, blocks_used,
            qpos, window)
    mesh = act.current_mesh()
    if mesh is None:
        return local(*args)
    from jax.sharding import PartitionSpec as P
    head = "model" if pool.k.shape[2] % mesh.shape["model"] == 0 else None
    heads, rows = P(None, head), P(None, None, head)
    # check_vma off: the jnp twin's while-loop carries start from
    # constants, which the varying-axes check rejects against its
    # head-varying updates
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(heads, rows, rows, rows, rows, P(), P(), P(), P()),
        out_specs=heads, check_vma=False)(*args)


def attention_decode_paged(p: dict, x_new: jax.Array, pool: KVCache,
                           tables: jax.Array, pos: jax.Array, cfg, *,
                           window: int | None = None,
                           backend=None,
                           blocks_used: jax.Array | None = None):
    """Decode/chunked-prefill attention through a paged cache.

    x_new (B, n, D): n new tokens per sequence at positions
    pos..pos+n-1 (n = prefill chunk size, or 1 for a decode tick).
    pool: KVCache with (NB, BS, ...) leaves; tables (B, nbk) int32.
    Returns (out (B, n, D), new_pool).

    Writes go first (scatter at the new positions' physical slots),
    then reads follow one of two schedules:

      * **stream**: physical blocks stream through online softmax with
        a per-sequence ``blocks_used`` early exit — tick cost is O(max
        used length). Engaged by passing ``blocks_used`` (B,) int32 =
        live blocks per sequence (the caller's explicit request; the
        serving engine passes it when its resolved schedule is
        'stream', which defaults to the planner's ``decode_schedule``).
        Backends without block-stream support ignore it and gather.
      * **gather** (the parity oracle, blocks_used=None): materialize
        the dense (B, nbk*BS, ...) logical view and run the same
        masked-softmax formula as the dense cache path.

    Both schedules let each query attend positions <= its own, so
    chunked prefill (n=C) and decode ticks (n=1) are the same graph.
    Positions beyond the view (chunk padding past the table) write to
    the null block and are never read back.
    """
    from repro.serving.paged import NULL_BLOCK
    be = sb.plan(cfg, backend=backend).backend
    B, n, _ = x_new.shape
    leaf = pool.k if pool.k is not None else (
        pool.x if pool.x is not None else pool.v)
    BS = leaf.shape[1]
    nbk = tables.shape[1]
    S = nbk * BS

    qpos = pos[:, None] + jnp.arange(n)[None, :]          # (B, n)
    bidx = jnp.minimum(qpos // BS, nbk - 1)
    bids = jnp.take_along_axis(tables, bidx, axis=1)
    bids = jnp.where(qpos < S, bids, NULL_BLOCK)          # pad -> trash
    offs = qpos % BS

    if not be.uses_x_cache:
        q, k_new, v_new = _decode_qkv(p, x_new, cfg, be, qpos)
        new_pool = paged_write_kv(pool, k_new, v_new, bids, offs)
    else:
        q = None
        new_pool = paged_write_x(pool, x_new, bids, offs)
        if pool.v is not None:
            new_pool = paged_write_kv(new_pool, None, _project_v_rows(
                p, x_new), bids, offs)
    if blocks_used is not None and be.supports_block_stream:
        out = _decode_attend_streamed(p, x_new, q, new_pool, tables,
                                      blocks_used, qpos, cfg, be, window)
    else:
        view = gather_block_view(new_pool, tables)
        out = _decode_attend(p, x_new, q, view, qpos, cfg, be, window)
    return out, new_pool


def attention_decode(p: dict, x_new: jax.Array, cache: KVCache,
                     pos: jax.Array, cfg, *,
                     window: int | None = None,
                     backend=None):
    """One decode step. x_new (B, 1, D); pos (B,) current index.
    Returns (out (B, 1, D), new_cache). The cache layout follows the
    backend's ``uses_x_cache`` capability flag: K-consuming backends
    cache rope'd K rows; X-consuming backends (the paper's dataflow)
    cache raw inputs and stream them through the stationary weights."""
    be = sb.plan(cfg, backend=backend).backend
    qpos = pos[:, None]                                   # (B, 1)

    if not be.uses_x_cache:
        q, k_new, v_new = _decode_qkv(p, x_new, cfg, be, qpos)
        new_cache = write_kv(cache, k_new, v_new, cfg, pos=pos)
    else:
        q = None
        new_cache = write_x(cache, x_new, cfg, pos=pos)
        if cache.v is not None:
            new_cache = write_kv(new_cache, None, _project_v_rows(
                p, x_new), cfg, pos=pos)
    out = _decode_attend(p, x_new, q, new_cache, qpos, cfg, be, window)
    return out, new_cache
