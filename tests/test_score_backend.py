"""ScoreBackend registry: parity across every registered backend + the
planner's capability-flag behaviour.

Parity ladder: ``standard`` ≡ ``wqk`` ≡ ``factored`` exactly (same
bilinear form, float arithmetic) and ``wqk_int8`` ≡ ``wqk_int8_pallas``
(interpret mode) to quantization tolerance — across GQA ratios,
qkv-bias (the augmented-D fold), and pre-folded weights.
"""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_arch, reduced
from repro.core import score_backend as sb
from repro.core.score_backend import ScoreWeights

EXACT = ("standard", "wqk", "factored")
QUANT = ("wqk_int8", "wqk_int8_pallas")


def _mk(rng, D=32, H=4, Hkv=2, dh=16, bias=False):
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    return ScoreWeights(
        wq=f(D, H, dh), wk=f(D, Hkv, dh),
        bq=f(H, dh) if bias else None,
        bk=f(Hkv, dh) if bias else None)


# ------------------------------------------------------------------ parity

@pytest.mark.parametrize("bias", [False, True])
@pytest.mark.parametrize("gqa", [(4, 4), (4, 2), (8, 1)])
def test_exact_backends_agree(rng, bias, gqa):
    H, Hkv = gqa
    sw = _mk(rng, H=H, Hkv=Hkv, bias=bias)
    x = jnp.asarray(rng.standard_normal((2, 10, 32)), jnp.float32)
    y = jnp.asarray(rng.standard_normal((2, 7, 32)), jnp.float32)
    ref = sb.get_backend("standard").scores(x, y, sw, scale=0.25)
    for name in EXACT[1:]:
        s = sb.get_backend(name).scores(x, y, sw, scale=0.25)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(s),
                                   rtol=2e-4, atol=2e-4, err_msg=name)


@pytest.mark.parametrize("bias", [False, True])
@pytest.mark.parametrize("gqa", [(4, 4), (4, 2)])
def test_quantized_backends_agree(rng, bias, gqa):
    """wqk_int8 ≡ wqk_int8_pallas (interpret mode on CPU) to quant
    tolerance; both within W8A8 noise of the float path."""
    H, Hkv = gqa
    sw = _mk(rng, H=H, Hkv=Hkv, bias=bias)
    x = jnp.asarray(rng.standard_normal((16, 32)), jnp.float32)
    y = jnp.asarray(rng.standard_normal((12, 32)), jnp.float32)
    s_f = sb.get_backend("wqk").scores(x, y, sw, scale=1.0)
    denom = float(jnp.max(jnp.abs(s_f))) + 1e-9
    outs = {}
    for name in QUANT:
        s = sb.get_backend(name).scores(x, y, sw, scale=1.0)
        outs[name] = np.asarray(s)
        rel = float(jnp.max(jnp.abs(s - s_f))) / denom
        assert rel < 0.05, (name, rel)
    rel = np.max(np.abs(outs[QUANT[0]] - outs[QUANT[1]])) / denom
    assert rel < 0.05, rel


def test_all_backends_accept_prefolded(rng):
    """fold() -> scores() matches lazy folding for every backend."""
    sw = _mk(rng, bias=True)
    x = jnp.asarray(rng.standard_normal((3, 8, 32)), jnp.float32)
    for name in sb.list_backends():
        be = sb.get_backend(name)
        folded = be.fold(sw)
        a = be.scores(x, x, sw, scale=1.0)
        b = be.scores(x, x, folded, scale=1.0)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5, err_msg=name)


def test_pallas_decode_shape_consistent(rng):
    """The pallas backend's decode-shaped (Nq=1) fallback matches its
    kernel path on the same inputs (same per-head quantization)."""
    sw = _mk(rng)
    x = jnp.asarray(rng.standard_normal((4, 32)), jnp.float32)
    y = jnp.asarray(rng.standard_normal((9, 32)), jnp.float32)
    be = sb.get_backend("wqk_int8_pallas")
    full = np.asarray(be.scores(x, y, sw, scale=1.0))
    row = np.asarray(be.scores(x[2:3], y, sw, scale=1.0))
    np.testing.assert_allclose(full[:, 2:3], row, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------- registry

def test_unknown_backend_raises():
    with pytest.raises(KeyError, match="unknown score backend"):
        sb.get_backend("does-not-exist")


def test_duplicate_registration_raises():
    with pytest.raises(ValueError, match="already registered"):
        @sb.register_backend("standard")
        class Dup(sb.ScoreBackend):
            pass


def test_registry_contains_all_five():
    assert set(sb.list_backends()) >= {"standard", "wqk", "wqk_int8",
                                       "wqk_int8_pallas", "factored"}


def test_capability_flags():
    std = sb.get_backend("standard")
    assert std.needs_rope and not std.uses_x_cache
    for name in ("wqk", "wqk_int8", "wqk_int8_pallas"):
        be = sb.get_backend(name)
        assert be.folds_bias and be.uses_x_cache and not be.needs_rope
    pal = sb.get_backend("wqk_int8_pallas")
    assert not pal.supports_blockwise and pal.max_d_aug == sb.VMEM_D_LIMIT


# ----------------------------------------------------------------- planner

def test_plan_cache_mode_from_flags():
    whisper = get_arch("whisper-tiny")          # wqk_int8, cache_mode="xv"
    assert sb.plan(whisper).cache_mode == "xv"
    no_override = dataclasses.replace(whisper, cache_mode=None)
    # D=384 < 2*Hkv*dh=768 -> pure-x wins (DESIGN.md §4 crossover)
    assert sb.plan(no_override).cache_mode == "x"
    qwen = get_arch("qwen2.5-14b")              # standard scores
    assert sb.plan(qwen).backend.name == "standard"
    assert sb.plan(qwen).cache_mode == "kv"


def test_plan_ignores_incompatible_cache_override():
    """whisper pins cache_mode='xv'; running it with the standard
    backend must still get a K/V cache (an x-layout cache has no k
    tensor for decode to write into) — and vice versa."""
    whisper = get_arch("whisper-tiny")
    std = dataclasses.replace(whisper, score_mode="standard")
    assert sb.plan(std).cache_mode == "kv"
    kv_override = dataclasses.replace(whisper, cache_mode="kv")
    assert sb.plan(kv_override).cache_mode == "x"   # wqk_int8 needs X
    # budget sizing follows the resolved layout, not the raw override
    from repro.serving import kvcache
    b = kvcache.budget_for(std)
    assert b.mode == "kv"
    assert b.bytes_per_token_layer == \
        2 * std.num_kv_heads * std.head_dim * 2


def test_plan_respects_max_d_aug():
    """Explicit pallas request on a D_aug > VMEM limit arch falls back
    to the jnp int8 backend (capability flag respected)."""
    big = dataclasses.replace(get_arch("qwen2.5-14b"),
                              score_mode="wqk_int8_pallas")
    assert big.d_model > sb.VMEM_D_LIMIT
    assert sb.plan(big).backend.name == "wqk_int8"
    small = dataclasses.replace(reduced(get_arch("qwen2.5-14b")),
                                score_mode="wqk_int8_pallas")
    assert sb.plan(small).backend.name == "wqk_int8_pallas"


def test_plan_blockwise_schedule():
    cfg = reduced(get_arch("qwen2.5-14b"))      # blockwise_min_len=4096
    assert not sb.plan(cfg, seq_len=512).blockwise
    assert sb.plan(cfg, seq_len=8192).blockwise
    # window masks force the quadratic path
    assert not sb.plan(cfg, seq_len=8192, mask_kind="window").blockwise
    # quadratic-only pallas backend swaps to its blockwise sibling
    small = dataclasses.replace(cfg, score_mode="wqk_int8_pallas")
    long_plan = sb.plan(small, seq_len=8192)
    assert long_plan.blockwise and long_plan.backend.name == "wqk_int8"


def test_plan_pallas_only_auto_on_tpu():
    cfg = dataclasses.replace(reduced(get_arch("whisper-tiny")),
                              score_mode="wqk_int8")
    assert sb.plan(cfg, device="cpu").backend.name == "wqk_int8"
    assert sb.plan(cfg, device="tpu").backend.name == "wqk_int8_pallas"


def test_plan_wqk_explicit_false_uses_factored():
    cfg = dataclasses.replace(reduced(get_arch("whisper-tiny")),
                              score_mode="wqk", wqk_explicit=False)
    assert sb.plan(cfg).backend.name == "factored"


def test_memory_bytes_per_token_matches_budget():
    from repro.serving import kvcache
    for arch in ("whisper-tiny", "qwen2.5-14b", "gemma3-27b"):
        cfg = get_arch(arch)
        if not cfg.num_heads:
            continue
        pl = sb.plan(cfg)
        budget = kvcache.budget_for(cfg)
        assert budget.backend == pl.backend.name
        assert budget.bytes_per_token_layer == \
            pl.backend.memory_bytes_per_token(cfg, 2, cache_mode=pl.cache_mode)


@pytest.mark.parametrize("name", sorted(set(sb.list_backends())
                                         - {"standard"}))
def test_x_cache_backends_beat_kv_bytes_on_whisper(name):
    """On whisper geometry (D=384 < 2*Hkv*dh=768) the planner gives
    every backend but standard the pure X-cache, which stores fewer bytes
    per token than the standard backend's K/V cache."""
    cfg = get_arch("whisper-tiny")

    def bytes_per_token(backend):
        pl = sb.plan(dataclasses.replace(cfg, score_mode=backend,
                                         cache_mode=None))
        return pl.cache_mode, sb.get_backend(backend).memory_bytes_per_token(
            cfg, cache_mode=pl.cache_mode)

    kv_mode, kv = bytes_per_token("standard")
    mode, got = bytes_per_token(name)
    assert (kv_mode, mode) == ("kv", "x")
    assert got < kv, (name, got, kv)


def test_deprecated_shim_removed():
    """The stringly-typed compute_scores shim and the SCORE_MODES static
    snapshot were removed this release; the registry is canonical."""
    import importlib
    with pytest.raises(ModuleNotFoundError):
        importlib.import_module("repro.core.attention_scores")
    from repro.configs import base
    assert not hasattr(base, "SCORE_MODES")
    assert set(sb.list_backends()) >= {"standard", "wqk", "wqk_int8",
                                       "wqk_int8_pallas", "factored"}
