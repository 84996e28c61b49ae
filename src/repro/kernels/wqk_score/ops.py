"""jit'd public wrapper around the fused W8A8 score kernel.

Handles quantization, padding to block multiples, batch via vmap, and
dequantized f32 output — drop-in for core.wqk.wqk_scores_int8 when the
head-D fits the VMEM-resident regime.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import quant
from repro.kernels.wqk_score.kernel import wqk_score_int8

# Max D for which one head's W_QK stays VMEM-resident (int8 bytes).
VMEM_D_LIMIT = 2048


def _pad_to(x, mult, axis):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _quantize_workload(x_q, x_kv, wqk):
    """The kernel's quantization scheme: per-token X, per-head W_QK.
    Shared by the Pallas path and the jnp twin so they cannot drift."""
    qx, sx = quant.quantize(x_q, axis=-1)
    qy, sy = quant.quantize(x_kv, axis=-1)
    H = wqk.shape[0]
    qw, sw = quant.quantize(wqk.reshape(H, -1), axis=-1)
    return qx, sx, qy, sy, qw.reshape(wqk.shape), sw.reshape(H, 1, 1)


def _dequant(s, sx, sy, sw):
    return s.astype(jnp.float32) * sx[..., None, :, :] \
        * jnp.swapaxes(sy, -1, -2)[..., None, :, :] * sw


@functools.partial(jax.jit, static_argnames=("block_n", "block_m",
                                             "interpret"))
def scores(x_q: jax.Array, x_kv: jax.Array, wqk: jax.Array, *,
           block_n: int = 128, block_m: int = 128,
           interpret: bool = False) -> jax.Array:
    """Float scores S (..., H, N, M) = dequant(int8 kernel).

    x_q (..., N, D) float; x_kv (..., M, D) float; wqk (H, D, D) float.
    Quantization: per-token on X (axis -1), per-head on W_QK.
    """
    N, M = x_q.shape[-2], x_kv.shape[-2]
    qx, sx, qy, sy, qw, sw = _quantize_workload(x_q, x_kv, wqk)

    qxp = _pad_to(qx, block_n, -2)
    qyp = _pad_to(qy, block_m, -2)

    fn = lambda a, b: wqk_score_int8(a, b, qw, block_n=block_n,
                                     block_m=block_m, interpret=interpret)
    for _ in range(x_q.ndim - 2):
        fn = jax.vmap(fn)
    return _dequant(fn(qxp, qyp)[..., :, :N, :M], sx, sy, sw)


def scores_jnp(x_q: jax.Array, x_kv: jax.Array, wqk: jax.Array) -> jax.Array:
    """jnp twin of ``scores`` — same quantization scheme, no Pallas.
    Used for decode-shaped (Nq=1) calls where padding to a kernel block
    would dominate. Second contraction runs in f32 at full precision,
    the kernel's own arithmetic (int32 would overflow at macro-scale
    D·M, and the MXU takes no int32 operands)."""
    qx, sx, qy, sy, qw, sw = _quantize_workload(x_q, x_kv, wqk)
    g = jnp.einsum("...nd,hde->...hne", qx.astype(jnp.int32),
                   qw.astype(jnp.int32))
    s = jnp.einsum("...hne,...me->...hnm", g.astype(jnp.float32),
                   qy.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST)
    return _dequant(s, sx, sy, sw)


def supported(d_aug: int) -> bool:
    return d_aug <= VMEM_D_LIMIT
