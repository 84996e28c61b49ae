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


def leaf(key, leaf_id: int, shape, log2_half: int, layer=None):
    """The leaf ``leaf_id`` (of layer ``layer``): an architecture module
    gives each of its leaves an id of its own."""
    k = jax.random.fold_in(key, leaf_id)
    if layer is not None:
        k = jax.random.fold_in(k, layer)
    return uniform(k, shape, log2_half)
