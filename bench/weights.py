"""Random weights from the run's seed, made by the benchmark.

Each weight is uniform on [-2^e, 2^e), with 2^e the power of two
nearest sqrt(3 / fan_in). A value is built from 16 random bits by exact
float32 steps (integer to float, a power-of-two scale) and one
rounding to bfloat16, so the serving program and the reference see the
same bf16 values, whichever jitted call makes them and however it is
fused.

Every leaf has a key of its own, ``fold_in(seed key, leaf id)``, and a
layer's leaf ``fold_in(leaf key, layer)``: the reference makes one
layer at a time, the program's copy is made whole in one call.

Norm gains are ``1 + delta``; the program stores ``delta`` (its RMSNorm
multiplies by ``1 + scale``), the reference uses the gain.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench.costs import Shapes

# leaf name -> id: the order is part of the weights' definition
LEAF_IDS = {name: i for i, name in enumerate((
    "embed", "final_norm", "lm_head", "attn_norm", "wq", "wk", "wv",
    "bq", "bk", "bv", "wo", "mlp_norm", "w_gate", "w_up", "w_down"))}
NORM_LOG2 = -4         # half-width of a gain's delta
BIAS_LOG2 = -4
EMBED_LOG2 = -3


def seed_key(seed: int):
    """A PRNG key from a seed of any size (the harness's seeds exceed
    32 bits)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _log2_half(fan_in: int) -> int:
    return round(math.log2(math.sqrt(3.0 / fan_in)))


def uniform(key, shape, log2_half: int):
    """bf16 values on [-2^log2_half, 2^log2_half), see the module."""
    bits = jax.random.bits(key, shape, jnp.uint32)
    k = (bits >> 16).astype(jnp.int32) - 32768
    return (k.astype(jnp.float32)
            * jnp.float32(2.0 ** (log2_half - 15))).astype(jnp.bfloat16)


def layer_specs(s: Shapes) -> dict:
    """Per-layer leaf -> (shape, log2 half-width)."""
    d, h, g, e, f = s.d, s.heads, s.kv_heads, s.head_dim, s.ff
    out = {
        "attn_norm": ((d,), NORM_LOG2),
        "wq": ((d, h, e), _log2_half(d)),
        "wk": ((d, g, e), _log2_half(d)),
        "wv": ((d, g, e), _log2_half(d)),
        "wo": ((h, e, d), _log2_half(h * e)),
        "mlp_norm": ((d,), NORM_LOG2),
        "w_gate": ((d, f), _log2_half(d)),
        "w_up": ((d, f), _log2_half(d)),
        "w_down": ((f, d), _log2_half(f)),
    }
    if s.qkv_bias:
        out.update(bq=((h, e), BIAS_LOG2), bk=((g, e), BIAS_LOG2),
                   bv=((g, e), BIAS_LOG2))
    return out


def global_specs(s: Shapes) -> dict:
    return {"embed": ((s.vocab, s.d), EMBED_LOG2),
            "final_norm": ((s.d,), NORM_LOG2),
            "lm_head": ((s.d, s.vocab), _log2_half(s.d))}


def leaf(key, name: str, shape, log2_half: int, layer=None):
    k = jax.random.fold_in(key, LEAF_IDS[name])
    if layer is not None:
        k = jax.random.fold_in(k, layer)
    return uniform(k, shape, log2_half)


def layer_weights(key, s: Shapes, layer) -> dict:
    """One layer's leaves (bf16), by canonical name."""
    return {n: leaf(key, n, shp, e, layer)
            for n, (shp, e) in layer_specs(s).items()}


def global_weight(key, s: Shapes, name: str):
    shp, e = global_specs(s)[name]
    return leaf(key, name, shp, e)


def program_params(seed: int, s: Shapes) -> dict:
    """The serving program's parameter tree (``models/model.Model``
    layout: layer leaves stacked on a leading axis), made on the
    device in one jitted call."""
    def make(key):
        layers = jax.vmap(lambda i: layer_weights(key, s, i))(
            jnp.arange(s.layers))
        attn = {k: layers[c] for k, c in (
            ("wq", "wq"), ("wk", "wk"), ("wv", "wv"), ("wo", "wo"),
            ("bq", "bq"), ("bk", "bk"), ("bv", "bv")) if c in layers}
        return {
            "embed": global_weight(key, s, "embed"),
            "final_ln": {"scale": global_weight(key, s, "final_norm")},
            "lm_head": global_weight(key, s, "lm_head"),
            "layers": {
                "ln1": {"scale": layers["attn_norm"]},
                "attn": attn,
                "ln2": {"scale": layers["mlp_norm"]},
                "mlp": {"w_gate": layers["w_gate"], "w_up": layers["w_up"],
                        "w_down": layers["w_down"]},
            },
        }
    return jax.jit(make)(seed_key(seed))
