"""Shared neural-net layers: norms, RoPE, MLPs, embeddings.

Pure functions over dict pytrees; all layer params are created by ``init_*``
helpers so stacking for ``lax.scan`` is uniform. Compute in the config
dtype with f32 norm/softmax accumulation.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro import util


# --------------------------------------------------------------------- norms

def rmsnorm(x: jax.Array, w: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    return (out * (1.0 + w.astype(jnp.float32))).astype(x.dtype)


def layernorm(x: jax.Array, w: jax.Array, b: jax.Array,
              eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (out * w.astype(jnp.float32) + b.astype(jnp.float32)).astype(x.dtype)


def norm(x: jax.Array, p: dict, kind: str) -> jax.Array:
    if kind == "rmsnorm":
        return rmsnorm(x, p["scale"])
    return layernorm(x, p["scale"], p["bias"])


def init_norm(kind: str, d: int, dtype) -> dict:
    if kind == "rmsnorm":
        return {"scale": jnp.zeros((d,), dtype)}
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


# ---------------------------------------------------------------------- rope

def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2,
                                       dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x (..., H, N, dh) [or (..., N, dh)], positions (..., N) int32.

    Half-split convention (HF Llama/Qwen): rotate_half = [-x2, x1] over the
    two halves of the head dim.
    """
    dh = x.shape[-1]
    inv = rope_freqs(dh, theta)                          # (dh/2,)
    ang = positions.astype(jnp.float32)[..., None] * inv  # (..., N, dh/2)
    cos = jnp.cos(ang)
    sin = jnp.sin(ang)
    if x.ndim == ang.ndim + 1:                            # head axis present
        cos = cos[..., None, :, :]
        sin = sin[..., None, :, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ----------------------------------------------------------------------- mlp

def mlp(x: jax.Array, p: dict, act: str) -> jax.Array:
    # optional sequence-sharded FFN (EXPERIMENTS.md §Perf hillclimb A):
    # pin the (B, S, F) intermediate S-over-model so tokens stay sharded
    # through the FFN and the (small) weights gather instead of the
    # (large) activations
    from repro import util
    from repro.sharding import act as act_lib
    seq_shard = util.ffn_seq_shard()
    if seq_shard:
        x = act_lib.constrain_tokens(x)
    if act == "swiglu":
        g = jnp.einsum("...d,df->...f", x, p["w_gate"].astype(x.dtype))
        u = jnp.einsum("...d,df->...f", x, p["w_up"].astype(x.dtype))
        h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    else:  # gelu
        u = jnp.einsum("...d,df->...f", x, p["w_up"].astype(x.dtype))
        if "b_up" in p:
            u = u + p["b_up"].astype(x.dtype)
        h = jax.nn.gelu(u.astype(jnp.float32), approximate=True).astype(x.dtype)
    if seq_shard and h.ndim == 3:
        h = act_lib.constrain_tokens(h)
    out = jnp.einsum("...f,fd->...d", h, p["w_down"].astype(x.dtype))
    if "b_down" in p:
        out = out + p["b_down"].astype(x.dtype)
    return out


def init_mlp(rng, d: int, f: int, act: str, dtype, bias: bool = False) -> dict:
    k1, k2, k3 = jax.random.split(rng, 3)
    s_in = 1.0 / math.sqrt(d)
    s_out = 1.0 / math.sqrt(f)
    p = {"w_up": jax.random.normal(k2, (d, f), dtype) * s_in,
         "w_down": jax.random.normal(k3, (f, d), dtype) * s_out}
    if act == "swiglu":
        p["w_gate"] = jax.random.normal(k1, (d, f), dtype) * s_in
    elif bias:
        p["b_up"] = jnp.zeros((f,), dtype)
        p["b_down"] = jnp.zeros((d,), dtype)
    return p


# ----------------------------------------------------------------- embedding

def init_embedding(rng, vocab: int, d: int, dtype) -> jax.Array:
    return jax.random.normal(rng, (vocab, d), dtype) * (1.0 / math.sqrt(d))


def embed(tokens: jax.Array, table: jax.Array) -> jax.Array:
    return jnp.take(table, tokens, axis=0)


def unembed(x: jax.Array, table_or_head: jax.Array,
            tied: bool) -> jax.Array:
    # float32 logits: a bf16 vocabulary row ties its top two entries
    # often enough to make greedy decoding hinge on rounding
    if tied:
        return jnp.einsum("...d,vd->...v", x, table_or_head,
                          preferred_element_type=jnp.float32)
    return jnp.einsum("...d,dv->...v", x, table_or_head,
                      preferred_element_type=jnp.float32)


# ------------------------------------------------------------ chunked x-ent

def cross_entropy_chunked(x: jax.Array, head: jax.Array, labels: jax.Array,
                          tied: bool, mask: jax.Array | None = None,
                          n_chunks: int = 16):
    """Cross-entropy without materializing the full (tokens, vocab) logits.

    Scans over sequence chunks; each chunk computes its logits, the
    logsumexp and the label logit, then the logits die. Keeps peak
    activation memory at (B, S/n_chunks, V) instead of (B, S, V) — the
    memory-roofline fix for 150k-vocab archs (EXPERIMENTS.md §Perf).

    x (B, S, D); labels (B, S) int32; mask (B, S) or None.
    Returns (mean_nll, denom).
    """
    B, S, D = x.shape
    while S % n_chunks:
        n_chunks //= 2
    xc = x.reshape(B, n_chunks, S // n_chunks, D).swapaxes(0, 1)
    lc = labels.reshape(B, n_chunks, S // n_chunks).swapaxes(0, 1)
    mc = (jnp.ones_like(labels, jnp.float32) if mask is None
          else mask.astype(jnp.float32))
    mc = mc.reshape(B, n_chunks, S // n_chunks).swapaxes(0, 1)

    @jax.checkpoint
    def body(carry, inp):
        # remat: per-chunk logits are recomputed in the backward pass
        # instead of being saved by the scan linearization (13+ GB/device
        # for 150k-vocab archs otherwise)
        xs, ls, ms = inp
        logits = unembed(xs, head, tied).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        lab = jnp.take_along_axis(logits, ls[..., None], axis=-1)[..., 0]
        nll = (lse - lab) * ms
        return (carry[0] + jnp.sum(nll), carry[1] + jnp.sum(ms)), None

    (tot, cnt), _ = jax.lax.scan(body, (jnp.zeros(()), jnp.zeros(())),
                                 (xc, lc, mc), unroll=util.scan_unroll())
    return tot / jnp.maximum(cnt, 1.0), cnt
