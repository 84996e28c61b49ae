"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs ref.py oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import wqk as wqk_mod


# ----------------------------------------------------------- wqk_score

@pytest.mark.parametrize("shape", [(64, 64, 64, 1), (128, 256, 64, 2),
                                   (256, 128, 128, 3), (64, 192, 256, 2)])
def test_wqk_score_kernel_exact(rng, shape):
    from repro.kernels.wqk_score import ref
    from repro.kernels.wqk_score.kernel import wqk_score_int8
    N, M, D, H = shape
    xq = jnp.asarray(rng.integers(-127, 128, (N, D)), jnp.int8)
    xk = jnp.asarray(rng.integers(-127, 128, (M, D)), jnp.int8)
    w = jnp.asarray(rng.integers(-127, 128, (H, D, D)), jnp.int8)
    out = wqk_score_int8(xq, xk, w, block_n=64, block_m=64, interpret=True)
    exact = np.asarray(ref.wqk_score_int8_ref(xq, xk, w), np.float64)
    # G = X·W_QK is exact int32 (|G| <= D·127² < 2^24, so its float32
    # cast is exact too); S = G·Yᵀ contracts in float32, whose rounding
    # is bounded by gamma_D · Σ|G||Y|, gamma_D = D·u / (1 - D·u)
    g = np.einsum("nd,hde->hne", np.asarray(xq, np.int64),
                  np.asarray(w, np.int64))
    mag = np.einsum("hne,me->hnm", np.abs(g),
                    np.abs(np.asarray(xk, np.int64))).astype(np.float64)
    u = 2.0 ** -24
    bound = D * u / (1 - D * u) * mag
    assert out.dtype == jnp.float32
    assert np.all(np.abs(np.asarray(out, np.float64) - exact) <= bound)


def test_wqk_score_ops_padding_and_batch(rng):
    from repro.kernels.wqk_score import ops
    xq = jnp.asarray(rng.standard_normal((2, 100, 64)), jnp.float32)
    xk = jnp.asarray(rng.standard_normal((2, 130, 64)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((2, 64, 64)), jnp.float32)
    s = ops.scores(xq, xk, w, block_n=64, block_m=64, interpret=True)
    assert s.shape == (2, 2, 100, 130)
    # against the float core path (same per-head quantization)
    s_ref = wqk_mod.wqk_scores(xq, xk, w)
    denom = float(jnp.max(jnp.abs(s_ref))) + 1e-9
    assert float(jnp.max(jnp.abs(s - s_ref))) / denom < 0.05


# --------------------------------------------------------- bitplane_mac

@pytest.mark.parametrize("shape,bits", [((64, 64, 64), 8), ((70, 90, 64), 8),
                                        ((128, 64, 128), 4),
                                        ((64, 64, 192), 2)])
def test_bitplane_kernel_exact(rng, shape, bits):
    from repro.kernels.bitplane_mac import ops, ref
    N, M, D = shape
    lim = 2 ** (bits - 1)
    xa = jnp.asarray(rng.integers(-lim, lim, (N, D)), jnp.int8)
    xb = jnp.asarray(rng.integers(-lim, lim, (M, D)), jnp.int8)
    w = jnp.asarray(rng.integers(-128, 128, (D, D)), jnp.int8)
    out = ops.scores(xa, xb, w, bits=bits, interpret=True)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(ref.direct_ref(xa, xb, w)))
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(ref.bitserial_ref(xa, xb, w, bits=bits)))


# --------------------------------------------------------- flash_scores

@pytest.mark.parametrize("causal,window", [(True, 0), (True, 64),
                                           (False, 0)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_kernel_vs_ref(rng, causal, window, dtype):
    from repro.kernels.flash_scores import ref
    from repro.kernels.flash_scores.kernel import flash_scores
    H, N, M, E, dv = 2, 128, 128, 32, 32
    q = jnp.asarray(rng.standard_normal((H, N, E)), dtype)
    k = jnp.asarray(rng.standard_normal((H, M, E)), dtype)
    v = jnp.asarray(rng.standard_normal((H, M, dv)), dtype)
    out, lse = flash_scores(q, k, v, scale=0.2, causal=causal,
                            window=window, block_n=64, block_m=64,
                            interpret=True)
    eo, el = ref.flash_scores_ref(q, k, v, scale=0.2, causal=causal,
                                  window=window)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(eo, np.float32), atol=tol)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(el), atol=tol)


def test_flash_kernel_shared_k_stream(rng):
    """Hk=1: one raw-X K-stream shared across heads — the paper's
    weight-stationary decode dataflow through the flash schedule."""
    from repro.kernels.flash_scores import ref
    from repro.kernels.flash_scores.kernel import flash_scores
    H, N, M, E = 4, 64, 192, 48
    q = jnp.asarray(rng.standard_normal((H, N, E)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, M, E)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, M, 16)), jnp.float32)
    out, lse = flash_scores(q, k, v, scale=1.0, causal=False,
                            block_n=64, block_m=64, interpret=True)
    eo, el = ref.flash_scores_ref(q, k, v, scale=1.0, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(eo), atol=1e-5)


# ------------------------------------------------- flash custom-vjp (jnp)

def test_flash_vjp_matches_quadratic_grad(rng):
    import dataclasses
    from repro.configs.base import get_arch, reduced
    from repro.models import attention as attn
    cfg = reduced(get_arch("qwen2.5-14b"))
    p = attn.init_attention(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jnp.asarray(rng.standard_normal((2, 96, cfg.d_model)), jnp.float32)
    pos = jnp.arange(96)

    def loss(c):
        def f(pp, xx):
            o = attn.attention_full(pp, xx, xx, c, positions_q=pos,
                                    positions_kv=pos, mask_kind="causal",
                                    window=40)
            return jnp.sum(jnp.sin(o))
        return f

    cq = dataclasses.replace(cfg, blockwise_min_len=1 << 30)
    cb = dataclasses.replace(cfg, blockwise_min_len=1, attn_block_m=32)
    l1, g1 = jax.value_and_grad(loss(cq), argnums=(0, 1))(p, x)
    l2, g2 = jax.value_and_grad(loss(cb), argnums=(0, 1))(p, x)
    assert abs(float(l1 - l2)) < 1e-3
    for a, b in zip(jax.tree_util.tree_leaves(g1),
                    jax.tree_util.tree_leaves(g2), strict=True):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-2, atol=1e-3)
