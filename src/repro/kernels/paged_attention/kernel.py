"""Pallas block-streamed paged decode attention kernel.

The vLLM-PagedAttention dataflow on the TPU grid: one live (sequence,
logical block) pair per grid step, with the step arrays built from the
per-sequence ``blocks_used``, and the block table, as **scalar-prefetch**
operands, so the BlockSpec index maps gather each physical K/V-or-X
block straight out of the pooled cache — the (B, nbk·BS, ...) logical
view never materializes in HBM.

The grid is flattened to the batch's live blocks, the block-granular
form of the paper's skip hierarchy (§III.C — whole all-zero structures
are never visited): it has ``sum(clip(blocks_used, 1, nbk))`` steps, a
traced bound, in sequence-major order. Step ``i`` belongs to sequence
``b = seq_of_step[i]``, whose steps run from ``start[b]``
(``step_arrays``), and reads the table entry of its logical block
``i - start[b]`` (``block_index_map``). No step past a sequence's live
length exists, so there is nothing to skip; a slot with no live block
(``blocks_used`` 0) still gets one step, so every output block is
written. Consecutive steps of one sequence revisit its ``q`` and
``out`` blocks: ``q`` is fetched and ``out`` written back once per
sequence.

Within a sequence the online-softmax state (m, l, acc) persists in VMEM
scratch across its consecutive steps (same schedule as
kernels/flash_scores). int8 pools (the macro's 8-bit input format)
dequantize in-kernel from their per-row scales; ``augment``/``requant``
reproduce the folded-bias [X 1] augmentation and the W8A8 re-quantization
of the score path, via the same helpers as the jnp reference (ref.py) so
the two cannot drift.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.paged_attention.ref import (NEG_INF, _block_values,
                                               _dequant_rows, _score_k)

BIG_WINDOW = 1 << 30


# --------------------------------------------------------------- index maps
# Named module-level functions so repro.analysis.kernelcheck can import
# and evaluate the EXACT maps the kernel runs (RA107). The grid is the
# flattened step i; the six trailing args are the scalar-prefetch refs
# (seq_of_step, start, used, tables, qpos, win) Pallas passes to every
# index map.

def step_arrays(used, nbk: int):
    """The flattened grid's step arrays for ``used`` (B,) live blocks
    per sequence, already clipped to [1, nbk].

    Returns ``(seq_of_step, start, total)``: ``seq_of_step`` of shape
    (B·nbk,), the most steps a batch can launch (radix forks let rows
    share physical blocks, so ``total`` may exceed the pool); ``start``
    (B,) the first step of each sequence; ``total`` the grid's length.
    Steps are sequence-major, so sequence b owns steps ``start[b] ..
    start[b] + used[b] - 1`` and its logical block is ``i - start[b]``.
    Entries past ``total`` are padding (the last sequence). Built with
    no gather: the kernel runs inside the layer loop, which XLA does
    not hoist these (B·nbk,) arrays out of.
    """
    B = used.shape[0]
    ends = jnp.cumsum(used)
    step = jnp.arange(B * nbk, dtype=jnp.int32)
    seq = jnp.minimum(jnp.searchsorted(ends, step, side="right",
                                       method="compare_all"), B - 1)
    return seq.astype(jnp.int32), ends - used, ends[-1]


def block_index_map(i, seq_ref, start_ref, used_ref, tables_ref, *_refs,
                    _where=jnp.where):
    """Physical pool block of step i: the table entry of its sequence's
    logical block while live, the null block (0) past the used length.
    Every launched step is live; the select is what the static verifier
    proves the gather in bounds by.

    ``_where`` exists so the static verifier can substitute its
    abstract-domain select; Pallas always calls with the default.
    """
    b = seq_ref[i]
    j = i - start_ref[b]
    return (_where(j < used_ref[b], tables_ref[b, j], 0), 0, 0, 0)


def q_index_map(i, seq_ref, *_refs):
    """Step i's sequence's query block — revisited across its steps."""
    return (seq_ref[i], 0, 0, 0)


def out_index_map(i, seq_ref, *_refs):
    """Step i's sequence's output block; held in VMEM across its steps,
    written back when the next sequence's steps begin."""
    return (seq_ref[i], 0, 0, 0)


def wv_index_map(i, *_refs):
    """The whole W_V tensor, stationary for every grid step."""
    return (0, 0, 0)


def bv_index_map(i, *_refs):
    """The whole b_V tensor, stationary for every grid step."""
    return (0, 0)


def build_specs(q, k_pool, *, v_pool=None, k_scale=None, v_scale=None,
                wv=None, bv=None):
    """Single source of truth for the kernel's operand plumbing.

    Accepts arrays or ShapeDtypeStructs. Returns ``(specs, flags)``:
    ``specs`` is a list of ``(name, operand, block_shape, index_map)``
    in the exact positional order the kernel unpacks its refs, and
    ``flags`` is the ``has_*`` kwarg dict for ``_kernel``. Used by both
    ``paged_attend_pallas`` and the static verifier, so the positional
    ref-threading and the proof about it cannot drift.
    """
    B, H, n, E = q.shape
    NB, BS, G = k_pool.shape[:3]
    Hkv = v_pool.shape[2] if v_pool is not None else wv.shape[1]
    dv = v_pool.shape[3] if v_pool is not None else wv.shape[2]
    specs = [
        ("q", q, (1, H, n, E), q_index_map),
        ("k_pool", k_pool, (1, BS, G, k_pool.shape[3]), block_index_map),
    ]
    if k_scale is not None:
        specs.append(("k_scale", k_scale, (1, BS, G, 1), block_index_map))
    if v_pool is not None:
        specs.append(("v_pool", v_pool, (1, BS, Hkv, dv), block_index_map))
    if v_scale is not None:
        specs.append(("v_scale", v_scale, (1, BS, Hkv, 1), block_index_map))
    if wv is not None:
        specs.append(("wv", wv, tuple(wv.shape), wv_index_map))
    if bv is not None:
        specs.append(("bv", bv, tuple(bv.shape), bv_index_map))
    flags = dict(has_ks=k_scale is not None, has_v=v_pool is not None,
                 has_vs=v_scale is not None, has_wv=wv is not None,
                 has_bv=bv is not None)
    return specs, flags


def _kernel(seq_ref, start_ref, used_ref, tables_ref, qpos_ref, win_ref,
            *refs,
            BS: int, G: int, Hkv: int, H: int, n: int, dv: int,
            scale: float, softcap: float, augment: bool, requant: bool,
            has_ks: bool, has_v: bool, has_vs: bool, has_wv: bool,
            has_bv: bool):
    it = iter(refs)
    q_ref = next(it)
    k_ref = next(it)
    ks_ref = next(it) if has_ks else None
    v_ref = next(it) if has_v else None
    vs_ref = next(it) if has_vs else None
    wv_ref = next(it) if has_wv else None
    bv_ref = next(it) if has_bv else None
    o_ref = next(it)
    m_sc, l_sc, acc_sc = next(it), next(it), next(it)

    i = pl.program_id(0)
    b = seq_ref[i]
    j = i - start_ref[b]                 # logical block of sequence b

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)
        o_ref[...] = jnp.zeros_like(o_ref)

    # every step is live: the grid holds no step past blocks_used
    rep = H // G
    kdeq = _dequant_rows(
        k_ref[0], None if ks_ref is None else ks_ref[0])
    keff, srow = _score_k(kdeq, augment, requant)    # (BS,G,E),(BS,G)
    q = q_ref[0].astype(jnp.float32)                 # (H, n, E)
    s = jnp.einsum("grne,sge->grns", q.reshape(G, rep, n, -1), keff)
    if srow is not None:
        s = s * srow.T[:, None, None, :]
    s = s.reshape(H, n, BS) * scale
    if softcap:
        s = jnp.tanh(s / softcap) * softcap

    idx = j * BS + jax.lax.broadcasted_iota(jnp.int32, (n, BS), 1)
    # (n, BS) query-position grid, element-wise reads from SMEM
    qcol = jnp.concatenate(
        [jnp.full((1, BS), qpos_ref[b, r], jnp.int32)
         for r in range(n)], axis=0)
    ok = idx <= qcol
    ok = ok & (idx > qcol - win_ref[0])
    s = s + jnp.where(ok, 0.0, NEG_INF)[None, :, :]

    m_prev = m_sc[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[..., None])
    alpha = jnp.exp(m_prev - m_new)
    l_sc[...] = l_sc[...] * alpha + jnp.sum(p, axis=-1)
    v = _block_values(
        kdeq, None if v_ref is None else v_ref[0],
        None if vs_ref is None else vs_ref[0],
        None if wv_ref is None else wv_ref[...],
        None if bv_ref is None else bv_ref[...])     # (BS, Hkv, dv)
    pg = p.reshape(Hkv, H // Hkv, n, BS)
    pv = jnp.einsum("grns,sge->grne", pg, v).reshape(H, n, dv)
    acc_sc[...] = acc_sc[...] * alpha[..., None] + pv
    m_sc[...] = m_new
    # write the running normalized output every step: the sequence's
    # last step (j == used[b]-1) leaves the final value in the buffer,
    # so no data-dependent "final step" predicate is needed
    o_ref[0] = acc_sc[...] / jnp.maximum(l_sc[...], 1e-30)[..., None]


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
    used = jnp.clip(blocks_used.astype(jnp.int32), 1, nbk)
    seq, start, total = step_arrays(used, nbk)
    win = jnp.asarray(
        BIG_WINDOW if window is None else window).astype(jnp.int32)
    win = win.reshape(1)

    specs, flags = build_specs(q, k_pool, v_pool=v_pool, k_scale=k_scale,
                               v_scale=v_scale, wv=wv, bv=bv)
    operands = [op for _, op, _, _ in specs]
    in_specs = [pl.BlockSpec(block, imap) for _, _, block, imap in specs]

    kern = functools.partial(
        _kernel, BS=BS, G=G, Hkv=Hkv, H=H, n=n, dv=dv, scale=scale,
        softcap=softcap, augment=augment, requant=requant, **flags)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(total,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, H, n, dv), out_index_map),
        scratch_shapes=[
            pltpu.VMEM((H, n), jnp.float32),
            pltpu.VMEM((H, n), jnp.float32),
            pltpu.VMEM((H, n, dv), jnp.float32),
        ])
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, n, dv), jnp.float32),
        interpret=interpret,
        name="paged_attention",
    )(seq, start, used, tables.astype(jnp.int32), qpos.astype(jnp.int32),
      win, *operands)
