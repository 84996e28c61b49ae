"""Ring attention over the sequence axis (shard_map + collective_permute).

Long-context attention where the KV/X cache is sharded over a mesh axis:
each device holds its sequence shard; K/V (or, in the paper's dataflow,
the raw-X stream) blocks rotate around the ring while every device
accumulates its queries' online-softmax state. Peak memory per device is
one block; wire cost is (p-1)/p of one cache pass — the collective-
sequential-parallel variant referenced in DESIGN.md §5.

Paper tie-in: in ``ring_attention_wqk`` the rotating buffer is the raw
input block X (one stream serves every head's scores AND the V
recompute) — the weight-stationary CIM dataflow distributed across a
pod: W_QK and Wv stay resident per chip; only raw inputs move.

Pure-jax (lax.ppermute inside shard_map); exact vs the single-device
oracle (tests/test_ring.py).
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

NEG_INF = -1e30


def _merge(acc, m, l, s_blk, v_blk):
    """Online-softmax merge of one score block (…, N, Bm) with values
    (…, Bm, dv) into the running (acc, m, l)."""
    m_new = jnp.maximum(m, jnp.max(s_blk, -1, keepdims=True))
    p = jnp.exp(s_blk - m_new)
    alpha = jnp.exp(m - m_new)
    l_new = l * alpha + jnp.sum(p, -1, keepdims=True)
    acc_new = acc * alpha + jnp.einsum("...nm,...md->...nd", p, v_blk)
    return acc_new, m_new, l_new


def ring_attention(q, k, v, pos_q, pos_k, mesh: Mesh, axis: str, *,
                   scale: float, causal: bool = True,
                   window: int | None = None):
    """q (H, N, E), k (H, M, E), v (H, M, dv), pos_q (N,), pos_k (M,);
    N and M shard over ``axis``. Returns out (H, N, dv) f32, sharded
    like q. Positions travel with their blocks, so causal/window masks
    stay exact across ring steps."""
    p_sz = mesh.shape[axis]

    def local(q_l, k_l, v_l, pq_l, pk_l):
        H, n_l, E = q_l.shape
        dv = v_l.shape[-1]
        # carries must be marked varying over the ring axis (vma check)
        mark = lambda x: jax.lax.pcast(x, (axis,), to="varying")
        acc = mark(jnp.zeros((H, n_l, dv), jnp.float32))
        m = mark(jnp.full((H, n_l, 1), NEG_INF, jnp.float32))
        l = mark(jnp.zeros((H, n_l, 1), jnp.float32))

        def step(i, carry):
            acc, m, l, k_b, v_b, pk_b = carry
            s = jnp.einsum("hne,hme->hnm", q_l, k_b,
                           preferred_element_type=jnp.float32) * scale
            ok = jnp.ones(s.shape[-2:], bool)
            if causal:
                ok = ok & (pk_b[None, :] <= pq_l[:, None])
            if window is not None:
                ok = ok & (pk_b[None, :] > pq_l[:, None] - window)
            s = jnp.where(ok[None], s, NEG_INF)
            acc, m, l = _merge(acc, m, l, s, v_b.astype(jnp.float32))
            # rotate the K/V/pos blocks one hop around the ring
            perm = [(j, (j + 1) % p_sz) for j in range(p_sz)]
            k_b = jax.lax.ppermute(k_b, axis, perm)
            v_b = jax.lax.ppermute(v_b, axis, perm)
            pk_b = jax.lax.ppermute(pk_b, axis, perm)
            return acc, m, l, k_b, v_b, pk_b

        acc, m, l, *_ = jax.lax.fori_loop(
            0, p_sz, step, (acc, m, l, k_l, v_l, pk_l))
        return acc / jnp.maximum(l, 1e-30)

    shard = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(None, axis, None), P(None, axis, None),
                  P(None, axis, None), P(axis), P(axis)),
        out_specs=P(None, axis, None))
    return shard(q, k, v, pos_q.astype(jnp.int32), pos_k.astype(jnp.int32))


def ring_attention_wqk(g, x_kv, wv, pos_q, pos_k, mesh: Mesh, axis: str, *,
                       scale: float, causal: bool = True):
    """The paper's dataflow on the ring: g = X_q·W_QK (weight-stationary
    first pass, H per-head rows), and the ROTATING buffer is the raw
    X_kv stream — each hop, the local chip computes scores g·x_blkᵀ AND
    recomputes that block's V = x_blk·Wv through its resident weights.
    One rotating tensor serves all heads (vs H K-streams + V-cache).

    g (H, N, D); x_kv (M, D); wv (D, Hkv, dh) resident; returns
    (H, N, dh) with GQA head mapping H = Hkv·rep.
    """
    H = g.shape[0]
    Hkv = wv.shape[1]
    rep = H // Hkv
    p_sz = mesh.shape[axis]

    def local(g_l, x_l, pq_l, pk_l):
        n_l = g_l.shape[1]
        dh = wv.shape[-1]
        mark = lambda x: jax.lax.pcast(x, (axis,), to="varying")
        acc = mark(jnp.zeros((H, n_l, dh), jnp.float32))
        m = mark(jnp.full((H, n_l, 1), NEG_INF, jnp.float32))
        l = mark(jnp.zeros((H, n_l, 1), jnp.float32))

        def step(i, carry):
            acc, m, l, x_b, pk_b = carry
            s = jnp.einsum("hnd,md->hnm", g_l, x_b,
                           preferred_element_type=jnp.float32) * scale
            ok = jnp.ones(s.shape[-2:], bool)
            if causal:
                ok = ok & (pk_b[None, :] <= pq_l[:, None])
            s = jnp.where(ok[None], s, NEG_INF)
            # V recomputed from the SAME rotating raw-X block
            v_b = jnp.einsum("md,dke->mke", x_b, wv,
                             preferred_element_type=jnp.float32)
            v_rep = jnp.repeat(v_b, rep, axis=1)        # (Bm, H, dh)
            acc, m, l = _merge(acc, m, l, s,
                               jnp.moveaxis(v_rep, 1, 0))
            perm = [(j, (j + 1) % p_sz) for j in range(p_sz)]
            return (acc, m, l, jax.lax.ppermute(x_b, axis, perm),
                    jax.lax.ppermute(pk_b, axis, perm))

        acc, m, l, *_ = jax.lax.fori_loop(
            0, p_sz, step, (acc, m, l, x_l, pk_l))
        return acc / jnp.maximum(l, 1e-30)

    shard = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(None, axis, None), P(axis, None), P(axis), P(axis)),
        out_specs=P(None, axis, None))
    return shard(g, x_kv, pos_q.astype(jnp.int32), pos_k.astype(jnp.int32))
