"""Pieces a plain float32 reference is built from, with no model in them.

Each architecture module (``bench/arch/``) writes its reference from
the published architecture, not from the serving program, and imports
nothing of the program. Every contraction is float32 at the highest
precision. A reference runs one layer at a time over one whole
sequence, padded (``padded``), its weights made from the seed
(``bench/weights.py``) as it reaches them, queries in blocks
(``causal_attention``), so that it fits on one chip at the published
widths. ``served_gaps`` is the comparison every module's ``gaps`` makes.

``quant="fp8"`` is the control, the reference in the precision below
the configuration's bfloat16: every projection's operands rounded to
float8 e4m3 (weights scaled per output channel, activations per token)
before a float32 product; attention itself stays float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512          # queries per attention block
PAD = 1024             # sequences are padded to a multiple of this
ROWS = 128             # and the rows read out to a multiple of this


def _fp8(x, axis):
    """float8 (e4m3) rounding of ``x`` scaled so that each slice along
    ``axis`` spans the format's range; values and scales."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32), scale


def _proj(x, w, quant):
    """x (T, d) times w (d, ...) in float32, or its fp8 control."""
    w2 = w.reshape(w.shape[0], -1)
    if quant == "fp8":
        xq, xs = _fp8(x, axis=1)
        wq, ws = _fp8(w2, axis=0)
        out = jnp.matmul(xq, wq, precision=HI) * xs * ws
    else:
        out = jnp.matmul(x, w2, precision=HI)
    return out.reshape((x.shape[0],) + w.shape[1:])


def _rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def _rope(x, pos, theta):
    """x (T, heads, dh); HF ``apply_rotary_pos_emb`` with rotate_half."""
    dh = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    emb = jnp.concatenate([ang, ang], -1)[:, None, :]
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    rot = jnp.concatenate([-x2, x1], -1)
    return x * jnp.cos(emb) + rot * jnp.sin(emb)


def padded(tokens) -> np.ndarray:
    """``tokens`` as int32, zero-padded to a multiple of ``PAD``, so few
    sequence lengths compile."""
    ids = np.zeros(-(-len(tokens) // PAD) * PAD, np.int32)
    ids[:len(tokens)] = tokens
    return ids


def causal_attention(q, k, v, scale):
    """Causal attention over a whole (padded) sequence, one query head
    per key/value head: q, k, v (T, heads, dh), T a multiple of
    ``Q_BLOCK``; queries in blocks of ``Q_BLOCK``. Returns (T, heads,
    dh)."""
    T = q.shape[0]
    pos = jnp.arange(T)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK)
        sc = jnp.einsum("qhe,khe->hqk", qb, k, precision=HI) * scale
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        sc = jnp.where(pos[None, None, :] <= qpos[None, :, None], sc,
                       -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("hqk,khe->qhe", p, v, precision=HI)

    return jax.lax.map(block, jnp.arange(T // Q_BLOCK))


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _head_gaps(x, gain_delta, head, served, *, eps: float, quant):
    """Rows x (n, d) through the final norm and the output head; per
    row the reference's best logit minus the served token's, over the
    row's standard deviation, and the row's own first choice."""
    h = _rms(x, 1.0 + gain_delta.astype(jnp.float32), eps)
    logits = _proj(h, head.astype(jnp.float32), quant)
    best = jnp.max(logits, -1)
    got = jnp.take_along_axis(logits, served[:, None], -1)[:, 0]
    return (best - got) / jnp.std(logits, -1), jnp.argmax(logits, -1)


def served_gaps(sequences, hidden, final_norm, head, eps: float,
                quant=None):
    """For each (prompt, served tokens) in ``sequences``: at every
    served token, the gap of the reference's logit of that token below
    its best, in units of the row's standard deviation; with ``quant``
    the control's own first choices are judged instead of the served
    ones. ``hidden(tokens, rows, quant)`` gives a reference's final
    hidden states (float32, before the final RMSNorm, whose gain is
    ``1 + final_norm``) at ``rows`` of ``tokens``; ``head`` (d, vocab)
    is the output head. Returns a float64 array of all gaps."""
    out = []
    for prompt, served in sequences:
        seq = list(prompt) + list(served[:-1])
        n = len(served)
        # pad the rows (repeating the last) so few shapes compile
        rows = np.arange(len(prompt) - 1, len(seq))
        rows = np.pad(rows, (0, -n % ROWS), mode="edge")
        tok = jnp.asarray(np.pad(np.asarray(served, np.int32),
                                 (0, -n % ROWS)))
        h = hidden(seq, rows, None)
        if quant is None:
            g, _ = _head_gaps(h, final_norm, head, tok, eps=eps, quant=None)
        else:
            hq = hidden(seq, rows, quant)
            _, pick = _head_gaps(hq, final_norm, head, tok, eps=eps,
                                 quant=quant)
            g, _ = _head_gaps(h, final_norm, head, pick, eps=eps,
                              quant=None)
        out.append(np.asarray(g, np.float64)[:n])
    return np.concatenate(out) if out else np.zeros(0)
