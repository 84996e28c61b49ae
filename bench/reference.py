"""Plain float32 reference of the dense GQA decoder (Qwen2 / InternLM2).

Written from the published architecture, not from the serving program,
and importing nothing of it: token embedding; per layer RMSNorm, q/k/v
projections (with bias where the configuration has it), rotary
embedding (half-split, ``rotate_half``), causal attention in which
query head h reads key/value head ``h // (heads / kv_heads)``, output
projection, residual; RMSNorm, SiLU-gated feed-forward, residual; a
final RMSNorm and the output head. Every contraction is float32 at the
highest precision. It runs one layer at a time over one whole sequence,
the layer's weights made from the seed (``bench/weights.py``) as it
reaches them, queries in blocks, so that it fits on one chip at the
published widths.

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

from bench import weights as W
from bench.costs import Shapes

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


@functools.partial(jax.jit, static_argnames=("s", "eps", "theta", "quant"))
def _layer(x, w, *, s: Shapes, eps: float, theta: float, quant):
    """One decoder layer over a whole (padded) sequence x (T, d)."""
    T = x.shape[0]
    f = {k: v.astype(jnp.float32) for k, v in w.items()}
    pos = jnp.arange(T)
    h = _rms(x, 1.0 + f["attn_norm"], eps)
    q, k, v = (_proj(h, f[n], quant) for n in ("wq", "wk", "wv"))
    if s.qkv_bias:
        q, k, v = q + f["bq"], k + f["bk"], v + f["bv"]
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    rep = s.heads // s.kv_heads
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    scale = 1.0 / np.sqrt(s.head_dim)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK)
        sc = jnp.einsum("qhe,khe->hqk", qb, k, precision=HI) * scale
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        sc = jnp.where(pos[None, None, :] <= qpos[None, :, None], sc,
                       -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("hqk,khe->qhe", p, v, precision=HI)

    o = jax.lax.map(block, jnp.arange(T // Q_BLOCK))
    o = o.reshape(T, s.heads * s.head_dim)
    x = x + _proj(o, f["wo"].reshape(-1, s.d), quant)
    h = _rms(x, 1.0 + f["mlp_norm"], eps)
    g = _proj(h, f["w_gate"], quant)
    u = _proj(h, f["w_up"], quant)
    return x + _proj(jax.nn.silu(g) * u, f["w_down"], quant)


@functools.partial(jax.jit, static_argnames=("s",))
def _make_layer(key, layer, *, s: Shapes):
    return W.layer_weights(key, s, layer)


@functools.partial(jax.jit, static_argnames=("s", "name"))
def _make_global(key, *, s: Shapes, name: str):
    return W.global_weight(key, s, name)


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


def hidden_rows(config: dict, seed: int, tokens, rows, quant=None):
    """Final-layer hidden states (before the final norm) at ``rows`` of
    the sequence ``tokens``, float32 (len(rows), d)."""
    s = Shapes.of(config)
    eps, theta = float(config["rms_norm_eps"]), float(config["rope_theta"])
    key = W.seed_key(seed)
    T = len(tokens)
    Tp = -(-T // PAD) * PAD
    ids = np.zeros(Tp, np.int32)
    ids[:T] = tokens
    emb = _make_global(key, s=s, name="embed")
    x = jnp.take(emb, jnp.asarray(ids), axis=0).astype(jnp.float32)
    del emb
    for layer in range(s.layers):
        w = _make_layer(key, jnp.int32(layer), s=s)
        x = _layer(x, w, s=s, eps=eps, theta=theta, quant=quant)
        del w
    return x[jnp.asarray(rows)]


def gaps(config: dict, seed: int, sequences, quant=None):
    """For each (prompt, served tokens) in ``sequences``: at every
    served token, the gap of the reference's logit of that token below
    its best, in units of the row's standard deviation; with ``quant``
    the control's own first choices are judged instead of the served
    ones. Returns a float64 array of all gaps."""
    s = Shapes.of(config)
    eps = float(config["rms_norm_eps"])
    key = W.seed_key(seed)
    head = _make_global(key, s=s, name="lm_head")
    fnorm = _make_global(key, s=s, name="final_norm")
    out = []
    for prompt, served in sequences:
        seq = list(prompt) + list(served[:-1])
        n = len(served)
        # pad the rows (repeating the last) so few shapes compile
        rows = np.arange(len(prompt) - 1, len(seq))
        rows = np.pad(rows, (0, -n % ROWS), mode="edge")
        tok = jnp.asarray(np.pad(np.asarray(served, np.int32),
                                 (0, -n % ROWS)))
        h = hidden_rows(config, seed, seq, rows)
        if quant is None:
            g, _ = _head_gaps(h, fnorm, head, tok, eps=eps, quant=None)
        else:
            hq = hidden_rows(config, seed, seq, rows, quant=quant)
            _, pick = _head_gaps(hq, fnorm, head, tok, eps=eps,
                                 quant=quant)
            g, _ = _head_gaps(h, fnorm, head, pick, eps=eps, quant=None)
        out.append(np.asarray(g, np.float64)[:n])
    return np.concatenate(out) if out else np.zeros(0)
