"""Pallas block-streamed paged decode attention kernel.

The vLLM-PagedAttention dataflow on the TPU grid: each grid step covers
``K`` consecutive logical blocks of one live sequence, with the step
arrays built from the per-sequence ``blocks_used``, and the block table,
as **scalar-prefetch** operands, so the BlockSpec index maps gather each
physical K/V-or-X block straight out of the pooled cache — the
(B, nbk·BS, ...) logical view never materializes in HBM.

``K`` follows from the operand shapes (``blocks_per_step``): one lane
width of tokens (``LANES // BS`` blocks), at most the table's width,
fewer only where the step's double-buffered blocks would not fit VMEM
beside the query, output and stationary operands. Each pool operand
gets ``K`` BlockSpecs, sub-block ``t`` on logical block ``j·K + t`` of
the sequence's step ``j`` (``block_index_map``); a sub-block past the
sequence's ``blocks_used`` reads the null block 0 and is masked by
position.

The grid is flattened to the batch's live steps, the block-granular
form of the paper's skip hierarchy (§III.C — whole all-zero structures
are never visited): it has ``sum(ceil(clip(blocks_used, 1, nbk) / K))``
steps, a traced bound, in sequence-major order. Step ``i`` belongs to
sequence ``b = seq_of_step[i]``, whose steps run from ``start[b]``
(``step_arrays``). A slot with no live block (``blocks_used`` 0) still
gets one step, so every output block is written. Consecutive steps of
one sequence revisit its ``q`` and ``out`` blocks: ``q`` is fetched,
and ``out`` copied out, once per sequence.

Scores and probabilities are laid out ``(kv group, query rows, K·BS)``:
the query heads of one kv head and the chunk's positions on sublanes,
the step's tokens on lanes. Within a sequence the online-softmax state
persists across its consecutive steps (same schedule as
kernels/flash_scores), one update per step: m and l in VMEM scratch,
the accumulator in the float32 output block, normalized on the
sequence's last step. int8 pools (the
macro's 8-bit input format) dequantize in-kernel from their per-row
scales; ``augment``/``requant`` reproduce the folded-bias [X 1]
augmentation and the W8A8 re-quantization of the score path, via the
same helpers as the jnp reference (ref.py) so the two cannot drift.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.paged_attention.ref import (NEG_INF, _block_values,
                                               _dequant_rows, _score_k)

BIG_WINDOW = 1 << 30
LANES = 128                 # tokens a grid step covers: one lane width
VMEM_BYTES = 16 * 2 ** 20   # scoped VMEM of one v5e TensorCore
POOL_OPERANDS = ("k_pool", "k_scale", "v_pool", "v_scale")
# float32 copies of the step's score-side rows (S, G, E) that the body
# holds at once through dequantize / augment / requantize and the score
# einsum: the compiler's scoped allocation for one step of the shared X
# stream (G = 1, D = 512) is twelve of them at K = 1, 2, 4 and 8 (an
# ahead-of-time compile for a v5e)
ROW_VALUES = 12


# --------------------------------------------------------------- index maps
# Named module-level functions so repro.analysis.kernelcheck can import
# and evaluate the EXACT maps the kernel runs (RA107). The grid is the
# flattened step i; the six trailing args are the scalar-prefetch refs
# (seq_of_step, start, used, tables, qpos, win) Pallas passes to every
# index map.

def step_arrays(used, nbk: int, K: int):
    """The flattened grid's step arrays for ``used`` (B,) live blocks
    per sequence, already clipped to [1, nbk], at ``K`` blocks a step.

    Returns ``(seq_of_step, start, total)``: ``seq_of_step`` of shape
    (B·ceil(nbk/K),), the most steps a batch can launch (radix forks
    let rows share physical blocks, so ``total`` may exceed the pool);
    ``start`` (B,) the first step of each sequence; ``total`` the
    grid's length. Steps are sequence-major, so sequence b owns steps
    ``start[b] .. start[b] + ceil(used[b]/K) - 1``, and step i covers
    its logical blocks from ``(i - start[b])·K``. Entries past
    ``total`` are padding (the last sequence). Built with no gather:
    the kernel runs inside the layer loop, which XLA does not hoist
    these arrays out of.
    """
    B = used.shape[0]
    steps = (used + K - 1) // K
    ends = jnp.cumsum(steps)
    step = jnp.arange(B * -(-nbk // K), dtype=jnp.int32)
    seq = jnp.minimum(jnp.searchsorted(ends, step, side="right",
                                       method="compare_all"), B - 1)
    return seq.astype(jnp.int32), ends - steps, ends[-1]


def block_index_map(i, seq_ref, start_ref, used_ref, tables_ref, *_refs,
                    t=0, K=1, _where=jnp.where):
    """Physical pool block of sub-block ``t`` of step i: the table entry
    of its sequence's logical block ``j·K + t`` while live, the null
    block (0) past the used length. The select is what the static
    verifier proves the gather in bounds by; the wrapper pads the table
    to a whole number of steps, so the read beside it is in bounds too.

    ``_where`` exists so the static verifier can substitute its
    abstract-domain select; Pallas always calls with the default.
    """
    b = seq_ref[i]
    j = (i - start_ref[b]) * K + t
    return (_where(j < used_ref[b], tables_ref[b, j], 0), 0, 0, 0)


def q_index_map(i, seq_ref, *_refs):
    """Step i's sequence's query block — revisited across its steps."""
    return (seq_ref[i], 0, 0, 0)


def out_index_map(i, seq_ref, *_refs):
    """Step i's sequence's output block: the accumulator, held in VMEM
    across its steps and copied out when the next sequence's begin."""
    return (seq_ref[i], 0, 0, 0)


def wv_index_map(i, *_refs):
    """The whole W_V tensor, stationary for every grid step."""
    return (0, 0, 0)


def bv_index_map(i, *_refs):
    """The whole b_V tensor, stationary for every grid step."""
    return (0, 0)


def sub_block_maps(K: int) -> tuple:
    """The ``K`` index maps of one pool operand, sub-block t on logical
    block ``j·K + t``. Every pool operand shares these objects, so an
    int8 pool and its scales provably read the same physical blocks."""
    return tuple(functools.partial(block_index_map, t=t, K=K)
                 for t in range(K))


def build_specs(q, k_pool, *, v_pool=None, k_scale=None, v_scale=None,
                wv=None, bv=None, K: int = 1):
    """Single source of truth for the kernel's operand plumbing.

    ``q`` is in the kernel's layout (B, G, (H/G)·n, E): the query heads
    of each score group and their positions as one row axis.
    Accepts arrays or ShapeDtypeStructs. Returns ``(specs, flags)``:
    ``specs`` is a list of ``(name, operand, block_shape, index_maps)``
    in the exact positional order the kernel unpacks its refs — one ref
    per map, ``K`` for each pool operand — and ``flags`` is the
    ``has_*`` kwarg dict for ``_kernel``. Used by both
    ``paged_attend_pallas`` and the static verifier, so the positional
    ref-threading and the proof about it cannot drift.
    """
    _, G, R, E = q.shape
    NB, BS = k_pool.shape[:2]
    Hkv = v_pool.shape[2] if v_pool is not None else wv.shape[1]
    dv = v_pool.shape[3] if v_pool is not None else wv.shape[2]
    subs = sub_block_maps(K)
    specs = [
        ("q", q, (1, G, R, E), (q_index_map,)),
        ("k_pool", k_pool, (1, BS, G, k_pool.shape[3]), subs),
    ]
    if k_scale is not None:
        specs.append(("k_scale", k_scale, (1, BS, G, 1), subs))
    if v_pool is not None:
        specs.append(("v_pool", v_pool, (1, BS, Hkv, dv), subs))
    if v_scale is not None:
        specs.append(("v_scale", v_scale, (1, BS, Hkv, 1), subs))
    if wv is not None:
        specs.append(("wv", wv, tuple(wv.shape), (wv_index_map,)))
    if bv is not None:
        specs.append(("bv", bv, tuple(bv.shape), (bv_index_map,)))
    flags = dict(has_ks=k_scale is not None, has_v=v_pool is not None,
                 has_vs=v_scale is not None, has_wv=wv is not None,
                 has_bv=bv is not None)
    return specs, flags


def vmem_bytes(shape, dtype) -> int:
    """Bytes of a VMEM array: its two minor dims padded to whole
    (sublane, lane) tiles, sublanes packed by the dtype's width."""
    size = jnp.dtype(dtype).itemsize
    *major, rows, cols = (1,) * (2 - len(shape)) + tuple(shape)
    sub = 8 * 4 // size
    return (math.prod(major) * -(-rows // sub) * sub
            * -(-cols // LANES) * LANES * size)


def stats_shape(Hkv: int, R: int) -> tuple:
    """Running max and running sum of the online softmax, per row."""
    return (Hkv, R, 1)


def step_vmem(specs, K: int, *, Hkv: int, R: int, dv: int) -> int:
    """VMEM one grid step holds at ``K`` blocks a step, for ``specs``
    built at any K: two buffers of every block that moves (the query,
    the output, each pool operand's K blocks), one of the stationary
    W_V / b_V, the softmax statistics, and the float32 values of the
    step body (``ROW_VALUES`` copies of the score-side rows, values,
    scores, probabilities, masks, the step's AV)."""
    _, G, _, E = specs[0][1].shape       # the grouped query
    BS = specs[1][2][1]                  # k_pool's block (1, BS, G, Ek)
    S = K * BS
    f32 = jnp.float32
    total = 2 * vmem_bytes((Hkv, R, dv), f32)                # out
    for name, op, block, _ in specs:
        one = vmem_bytes(block, op.dtype)
        total += (2 * K * one if name in POOL_OPERANDS
                  else 2 * one if name == "q" else one)
    total += 2 * vmem_bytes(stats_shape(Hkv, R), f32)
    total += (ROW_VALUES * vmem_bytes((S, G, E), f32)
              + vmem_bytes((S, Hkv, dv), f32)
              + 2 * vmem_bytes((Hkv, R, S), f32)
              + 3 * vmem_bytes((R, S), jnp.int32)
              + vmem_bytes((Hkv, R, dv), f32))
    return total


def blocks_per_step(q, k_pool, nbk: int, *, v_pool=None, k_scale=None,
                    v_scale=None, wv=None, bv=None) -> int:
    """``K``, the logical kv blocks one grid step covers, from the
    operand shapes alone (``q`` as ``paged_attend`` takes it: (B, H, n,
    E)): one lane width of tokens, at most the table's ``nbk`` blocks,
    halved while the step's VMEM (``step_vmem``) would pass seven
    eighths of the scoped limit, the rest left to the compiler."""
    B, H, n, E = q.shape
    BS, G = k_pool.shape[1:3]
    specs, _ = build_specs(
        jax.ShapeDtypeStruct((B, G, (H // G) * n, E), jnp.float32),
        k_pool, v_pool=v_pool, k_scale=k_scale, v_scale=v_scale, wv=wv,
        bv=bv)
    Hkv = v_pool.shape[2] if v_pool is not None else wv.shape[1]
    dv = v_pool.shape[3] if v_pool is not None else wv.shape[2]
    K = min(nbk, max(1, LANES // BS))
    while K > 1 and step_vmem(specs, K, Hkv=Hkv, R=(H // Hkv) * n,
                              dv=dv) > VMEM_BYTES * 7 // 8:
        K //= 2
    return K


def _kernel(seq_ref, start_ref, used_ref, tables_ref, qpos_ref, win_ref,
            *refs,
            K: int, BS: int, Hkv: int, n: int,
            scale: float, softcap: float, augment: bool, requant: bool,
            has_ks: bool, has_v: bool, has_vs: bool, has_wv: bool,
            has_bv: bool):
    it = iter(refs)

    def take(present=True, count=K):
        return [next(it) for _ in range(count)] if present else None
    q_ref, = take(count=1)
    k_refs = take()
    ks_refs = take(has_ks)
    v_refs = take(has_v)
    vs_refs = take(has_vs)
    wv_ref = next(it) if has_wv else None
    bv_ref = next(it) if has_bv else None
    o_ref = next(it)
    m_sc, l_sc = next(it), next(it)

    i = pl.program_id(0)
    b = seq_ref[i]
    j = i - start_ref[b]                 # the step of sequence b
    S = K * BS                           # tokens this step covers

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        o_ref[...] = jnp.zeros_like(o_ref)

    def rows(refs):                      # the K sub-blocks as S rows
        if refs is None:
            return None
        if K == 1:
            return refs[0][0]
        return jnp.concatenate([r[0] for r in refs], axis=0)

    kdeq = _dequant_rows(rows(k_refs), rows(ks_refs))    # (S, G, Ek)
    keff, srow = _score_k(kdeq, augment, requant)        # (S,G,E),(S,G)
    q = q_ref[0].astype(jnp.float32)                     # (G, rep·n, E)
    s = jnp.einsum("gre,sge->grs", q, keff)
    if srow is not None:
        s = s * srow.T[:, None, :]
    s = s * scale
    if softcap:
        s = jnp.tanh(s / softcap) * softcap
    s = s.reshape(Hkv, -1, S)            # rows of each V head's group
    R = s.shape[1]

    # positions of the step's tokens against each row's query position
    idx = j * S + jax.lax.broadcasted_iota(jnp.int32, (R, S), 1)
    if n == 1:
        qcol = jnp.full((R, S), qpos_ref[b, 0], jnp.int32)
    else:                                # row r·n + t holds position t
        qn = jnp.concatenate(
            [jnp.full((1, S), qpos_ref[b, t], jnp.int32)
             for t in range(n)], axis=0)
        qcol = jnp.concatenate([qn] * (R // n), axis=0)
    ok = (idx <= qcol) & (idx > qcol - win_ref[0])
    ok = ok & (idx < used_ref[b] * BS)   # sub-blocks past the live ones
    s = s + jnp.where(ok, 0.0, NEG_INF)[None]

    m_prev = m_sc[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_sc[...] = l_sc[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    v = _block_values(
        kdeq, rows(v_refs), rows(vs_refs),
        None if wv_ref is None else wv_ref[...],
        None if bv_ref is None else bv_ref[...])         # (S, Hkv, dv)
    pv = jnp.einsum("grs,sge->gre", p, v)
    o_ref[0] = o_ref[0] * alpha + pv     # the accumulator
    m_sc[...] = m_new

    # the sequence's last step normalizes the buffer, which is copied
    # out when the next sequence's steps begin
    @pl.when((j + 1) * K >= used_ref[b])
    def _emit():
        o_ref[0] = o_ref[0] / jnp.maximum(l_sc[...], 1e-30)


@functools.partial(
    jax.jit, static_argnames=("scale", "softcap", "augment", "requant",
                              "interpret"))
def paged_attend_pallas(q: jax.Array, k_pool: jax.Array,
                        tables: jax.Array, blocks_used: jax.Array,
                        qpos: jax.Array, *,
                        v_pool: jax.Array | None = None,
                        k_scale: jax.Array | None = None,
                        v_scale: jax.Array | None = None,
                        wv: jax.Array | None = None,
                        bv: jax.Array | None = None,
                        scale: float = 1.0,
                        window=None,
                        softcap: float = 0.0,
                        augment: bool = False,
                        requant: bool = False,
                        interpret: bool = False) -> jax.Array:
    """Same contract as ``ref.paged_attend_ref`` (see there for shapes);
    runs the gather-inside-the-kernel Pallas schedule. ``window`` may be
    a traced scalar (per-layer scan) — it rides in as a scalar-prefetch
    operand, not a static arg."""
    from jax.experimental.pallas import tpu as pltpu

    B, H, n, E = q.shape
    NB, BS, G = k_pool.shape[:3]
    nbk = tables.shape[1]
    Hkv = v_pool.shape[2] if v_pool is not None else wv.shape[1]
    dv = v_pool.shape[3] if v_pool is not None else wv.shape[2]
    pool_ops = dict(v_pool=v_pool, k_scale=k_scale, v_scale=v_scale,
                    wv=wv, bv=bv)
    K = blocks_per_step(q, k_pool, nbk, **pool_ops)
    used = jnp.clip(blocks_used.astype(jnp.int32), 1, nbk)
    seq, start, total = step_arrays(used, nbk, K)
    # a whole number of steps per row: sub-blocks past nbk read entry 0
    tables = jnp.pad(tables.astype(jnp.int32),
                     ((0, 0), (0, (-nbk) % K)))
    win = jnp.asarray(
        BIG_WINDOW if window is None else window).astype(jnp.int32)
    win = win.reshape(1)

    # (B, H, n, E) -> (B, G, (H/G)·n, E), a reshape of contiguous axes
    qg = q.reshape(B, G, (H // G) * n, E)
    specs, flags = build_specs(qg, k_pool, K=K, **pool_ops)
    operands = [op for _, op, _, maps in specs for _ in maps]
    in_specs = [pl.BlockSpec(block, imap)
                for _, _, block, maps in specs for imap in maps]

    R = (H // Hkv) * n
    kern = functools.partial(
        _kernel, K=K, BS=BS, Hkv=Hkv, n=n, scale=scale, softcap=softcap,
        augment=augment, requant=requant, **flags)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(total,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, Hkv, R, dv), out_index_map),
        scratch_shapes=[pltpu.VMEM(stats_shape(Hkv, R), jnp.float32)
                        for _ in ("m", "l")])
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, R, dv), jnp.float32),
        interpret=interpret,
        name="paged_attention",
    )(seq, start, used, tables, qpos.astype(jnp.int32), win, *operands)
    return out.reshape(B, H, n, dv)
