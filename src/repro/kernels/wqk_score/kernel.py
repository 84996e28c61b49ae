"""Fused W8A8 attention-score kernel: S_h = (X_q · W_QK^h) · X_kv^T.

TPU adaptation of the paper's weight-stationary CIM dataflow:
the per-head ``W_QK`` tile is **resident in VMEM** (playing the SRAM
array's role), and the *raw inputs* X stream through it — the dynamic
matrices Q/K never exist. The weight-stationary pass G = X·W_QK runs on
the MXU's native int8×int8→int32 path (the idiomatic port of the
multiplier-free bit-serial MAC; the bit-exact per-bit schedule lives in
kernels/bitplane_mac). The second pass S = G·Yᵀ multiplies an int32 G,
which the MXU does not take (Mosaic refuses an int32×int32 matmul), so
it contracts in float32 at full precision — exactly the arithmetic of
the jnp twin (ops.scores_jnp), and wide enough that S cannot overflow
as an int32 accumulator would at macro-scale D·M.

Grid (H, I, J): heads outer so each head's W_QK is loaded once and
reused for all (I×J) score tiles — weight-stationary across the whole
score matrix exactly like the macro. Block shapes are MXU-aligned
(sublane 8 / lane 128 multiples for int8).

Constraint: the full (D_aug × D_aug) W_QK of one head must fit VMEM
(int8: D ≤ ~2048 within a 16 MB budget incl. tiles). That is the
paper's own regime (macro D=64; whisper D=385 augmented). Larger-D
archs use the factored/standard path (DESIGN.md §4 FLOPs honesty).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


DEFAULT_BLOCK_N = 128
DEFAULT_BLOCK_M = 128


# BlockSpec index maps over grid (h, i, j) — named module-level
# functions so the static verifier (repro.analysis.kernelcheck) can
# import and evaluate the EXACT maps the kernel runs, instead of
# re-deriving them from comments. Keep them pure affine in the grid
# indices (lint rule RA107).

def x_index_map(h, i, j):
    """X_q row-block i streams for every (h, j)."""
    return (i, 0)


def y_index_map(h, i, j):
    """X_kv row-block j streams for every (h, i)."""
    return (j, 0)


def w_index_map(h, i, j):
    """Head h's W_QK tile — stationary across the whole (i, j) sweep."""
    return (h, 0, 0)


def out_index_map(h, i, j):
    """Each (h, i, j) grid step owns exactly one output score tile."""
    return (h, i, j)


def _score_kernel(x_ref, y_ref, w_ref, o_ref):
    """One (BN × BM) int32 score tile for one head.

    x_ref (BN, D) int8; y_ref (BM, D) int8; w_ref (1, D, D) int8;
    o_ref (1, BN, BM) float32.
    """
    x = x_ref[...]
    y = y_ref[...]
    w = w_ref[0]
    # G = X · W_QK : weight-stationary pass (raw inputs hit the array)
    g = jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32)
    # S = G · Y^T : second pass over the same stationary tile's output
    s = jax.lax.dot_general(
        g.astype(jnp.float32), y.astype(jnp.float32),
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST)
    o_ref[0] = s


@functools.partial(jax.jit, static_argnames=("block_n", "block_m",
                                             "interpret"))
def wqk_score_int8(x_q: jax.Array, x_kv: jax.Array, wqk: jax.Array,
                   *, block_n: int = DEFAULT_BLOCK_N,
                   block_m: int = DEFAULT_BLOCK_M,
                   interpret: bool = False) -> jax.Array:
    """x_q (N, D) int8, x_kv (M, D) int8, wqk (H, D, D) int8
    -> (H, N, M) float32 scores (integer G, float32 second pass).

    N and M must be multiples of the block sizes (ops.py pads).
    """
    N, D = x_q.shape
    M = x_kv.shape[0]
    H = wqk.shape[0]
    assert wqk.shape == (H, D, D), (wqk.shape, D)
    assert N % block_n == 0 and M % block_m == 0, (N, M, block_n, block_m)
    grid = (H, N // block_n, M // block_m)
    return pl.pallas_call(
        _score_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, D), x_index_map),
            pl.BlockSpec((block_m, D), y_index_map),
            pl.BlockSpec((1, D, D), w_index_map),
        ],
        out_specs=pl.BlockSpec((1, block_n, block_m), out_index_map),
        out_shape=jax.ShapeDtypeStruct((H, N, M), jnp.float32),
        interpret=interpret,
    )(x_q, x_kv, wqk)
