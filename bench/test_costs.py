"""The cost functions against counts worked out by hand: the shared
peaks and roofline bound, and the dense GQA decoder's work
(``bench/arch/dense_gqa.py``) for both of its configuration files."""
from __future__ import annotations

import json
import os

import pytest

from bench import costs
from bench.spec import BENCH_DIR, load_arch


def _config(name):
    with open(os.path.join(BENCH_DIR, "configs", name + ".json")) as f:
        return json.load(f)


def _dense(name):
    """(configuration, its architecture module)."""
    cfg = _config(name)
    arch = load_arch(os.path.dirname(BENCH_DIR), cfg)
    assert arch.__name__.endswith("dense_gqa")
    return cfg, arch


def _shapes(name):
    cfg, arch = _dense(name)
    return arch.Shapes.of(cfg)


# per layer, one token: q, k, v, o projections and the three
# feed-forward matrices
QWEN_LAYER = 5120 * 5120 + 2 * 5120 * 1024 + 5120 * 5120 + 3 * 5120 * 13824
INTERN_LAYER = 6144 * 6144 + 2 * 6144 * 1024 + 6144 * 6144 \
    + 3 * 6144 * 16384
CASES = {
    # name: (layers, matmul params per layer, hidden, vocab, heads,
    #        bias entries per layer, bytes of one token's k and v)
    "qwen2.5-14b-8L": (8, QWEN_LAYER, 5120, 152064, 40, 7168, 4096),
    "internlm2-20b-6L": (6, INTERN_LAYER, 6144, 92544, 48, 0, 4096),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_layer_params(name):
    s = _shapes(name)
    L, P, d, V, H, bias, kv = CASES[name]
    assert s.layer_matmul_params == P
    assert s.layer_params == P + bias + 2 * d
    assert s.kv_row_bytes == kv
    assert s.weight_bytes() == 2 * (L * (P + bias + 2 * d) + d + d * V)


def test_qwen_sizes_match_the_configuration_file():
    s = _shapes("qwen2.5-14b-8L")
    assert QWEN_LAYER == 275_251_200
    total = s.layers * s.layer_params + 2 * s.d * s.vocab + s.d
    assert round(total / 1e9, 2) == 3.76        # "3.76 B parameters"


@pytest.mark.parametrize("name", sorted(CASES))
def test_decode_tick(name):
    cfg, arch = _dense(name)
    L, P, d, V, H, bias, kv = CASES[name]
    ctx = [100, 200, 1]
    f, b = arch.decode_tick(cfg, ctx)
    att = 4 * H * 128 * (100 + 200 + 1)          # QK and AV, per layer
    assert f == 3 * 2 * (L * P + d * V) + L * att
    weights = 2 * (L * (P + bias + 2 * d) + d + d * V)
    assert b == weights + 3 * (2 * d + 4 * V) + L * kv * 301


@pytest.mark.parametrize("name", sorted(CASES))
def test_prefill_chunk(name):
    cfg, arch = _dense(name)
    L, P, d, V, H, bias, kv = CASES[name]
    f, b = arch.prefill_chunk(cfg, 0, 64, last=False)
    keys = 64 * 65 // 2                            # causal: 1 + ... + 64
    assert f == 64 * 2 * L * P + L * 4 * H * 128 * keys
    assert b == 2 * L * (P + bias + 2 * d) + 64 * d * 2 + L * kv * 64
    # a later, final chunk: 10 tokens at 64..73, head for the last one
    f2, b2 = arch.prefill_chunk(cfg, 64, 10, last=True)
    keys2 = sum(range(65, 75))
    assert f2 == 10 * 2 * L * P + L * 4 * H * 128 * keys2 + 2 * d * V
    assert b2 == 2 * L * (P + bias + 2 * d) + 10 * d * 2 + L * kv * 74 \
        + 2 * (d + d * V) + 4 * V


@pytest.mark.parametrize("name", sorted(CASES))
def test_paged_kernel_bytes(name):
    cfg, arch = _dense(name)
    L, H = CASES[name][0], CASES[name][4]
    # n = 1: two sequences of 100 and 200 keys, 16-token blocks; one
    # call a layer
    calls = arch.kernel_calls(cfg, [100, 200], 1, 16)
    assert len(calls) == L and len(set(calls)) == 1
    name, f, b = calls[0]
    assert name == "paged_attention"
    assert b == (7 + 13) * 16 * 4096 + 2 * 2 * 1 * H * 128 * 4
    assert f == 4 * H * 128 * 300
    # n = 64: one chunk whose last query attends 1000 keys
    calls = arch.kernel_calls(cfg, [1000], 64, 16)
    assert len(calls) == L and len(set(calls)) == 1
    name, f, b = calls[0]
    assert name == "paged_attention"
    assert b == 63 * 16 * 4096 + 2 * 64 * H * 128 * 4
    assert f == 4 * H * 128 * (64 * 1000 - 63 * 64 // 2)


# (FLOPs, bytes) the harness counted before the dense decoder became an
# architecture module, at the contexts above, a kernel call's under the
# kernel's name: a step of the cells reads the same work
PINNED = {
    "qwen2.5-14b-8L": {
        "decode_tick": (17932779520, 5973161984),
        "prefill_first": (282198016000, 4407050240),
        "prefill_last": (45711196160, 5964578816),
        "kernel_tick": ("paged_attention", 6144000, 1392640),
        "kernel_chunk": ("paged_attention", 1269432320, 6750208)},
    "internlm2-20b-6L": {
        "decode_tick": (17498456064, 5826728448),
        "prefill_first": (299880677376, 4683350016),
        "prefill_last": (48048095232, 5820495360),
        "kernel_tick": ("paged_attention", 7372800, 1409024),
        "kernel_chunk": ("paged_attention", 1523318784, 7274496)},
}


@pytest.mark.parametrize("name", sorted(PINNED))
def test_work_is_what_the_harness_counted_before(name):
    cfg, arch = _dense(name)
    L = CASES[name][0]
    assert {
        "decode_tick": arch.decode_tick(cfg, [100, 200, 1]),
        "prefill_first": arch.prefill_chunk(cfg, 0, 64, False),
        "prefill_last": arch.prefill_chunk(cfg, 64, 10, True),
        "kernel_tick": arch.kernel_calls(cfg, [100, 200], 1, 16)[0],
        "kernel_chunk": arch.kernel_calls(cfg, [1000], 64, 16)[0],
    } == PINNED[name]
    assert len(arch.kernel_calls(cfg, [1000], 64, 16)) == L


def test_roofline_bound_and_peaks():
    p = costs.load_peaks("TPU v5 lite")
    assert p["bf16_flop_s"] == 197e12 and p["hbm_byte_s"] == 819e9
    t, which = costs.min_seconds(197e12, 1.0, p)
    assert (t, which) == (1.0, "compute")
    t, which = costs.min_seconds(1.0, 819e9, p)
    assert (t, which) == (1.0, "memory")


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        costs.load_peaks("TPU v9 imaginary")
