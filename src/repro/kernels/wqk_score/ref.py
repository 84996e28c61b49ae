"""Pure-jnp oracle for the fused W8A8 score kernel."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def wqk_score_int8_ref(x_q: jax.Array, x_kv: jax.Array,
                       wqk: jax.Array) -> jax.Array:
    """x_q (N, D) int8, x_kv (M, D) int8, wqk (H, D, D) int8
    -> (H, N, M) int32. Exact integer arithmetic: the value the kernel's
    float32 second pass rounds (tests bound the difference)."""
    g = jnp.einsum("nd,hde->hne", x_q.astype(jnp.int32),
                   wqk.astype(jnp.int32))
    return jnp.einsum("hne,me->hnm", g, x_kv.astype(jnp.int32))

