"""A tiny checkout for the benchmark's tests: the real ``bench/``
metric readers, architecture modules and the serving program, with a
small configuration and traffic mix, so a whole run fits a CPU test."""
from __future__ import annotations

import json
import os
import shutil

import pytest

from bench.spec import BENCH_DIR

REPO = os.path.dirname(BENCH_DIR)

TINY_CONFIG = {
    "name": "tiny", "source": "test", "architecture": "dense_gqa",
    "hidden_size": 64,
    "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_hidden_layers": 2, "vocab_size": 512,
    "rms_norm_eps": 1e-06, "rope_theta": 10000.0, "hidden_act": "silu",
    "tie_word_embeddings": False, "torch_dtype": "bfloat16",
    "qkv_bias": True,
    "engine": {"max_slots": 4, "block_size": 16, "hbm_bytes": 1 << 19,
               "max_len": 256},
    "correctness": {"logit_gap": 0.5, "sample_tokens": 48,
                    "sample_max_requests": 4, "min_tokens": 16},
}
TINY_TRAFFIC = {
    "loop": "open", "rate_rps": 12.0, "ramp_s": 0.5,
    "prompt": {"dist": "lognormal", "median": 40, "sigma": 0.6,
               "min": 8, "max": 120},
    "output": {"dist": "uniform", "min": 4, "max": 12},
}


def make_root(path, config=TINY_CONFIG, traffic=TINY_TRAFFIC) -> str:
    """A checkout at ``path`` with one cell, ``tiny.open``."""
    root = str(path)
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    bench["configs"] = [{"name": "tiny", "source": "test",
                         "file": "bench/configs/tiny.json", "reduced": [],
                         "why": "test"}]
    bench["workloads"] = [{"name": "tiny.open", "config": "tiny",
                           "traffic": "tiny", "chips": 1, "why": "test"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        m.pop("workloads", None)
    os.makedirs(os.path.join(root, "bench", "configs"))
    os.makedirs(os.path.join(root, "bench", "traffic"))
    shutil.copytree(os.path.join(BENCH_DIR, "metrics"),
                    os.path.join(root, "bench", "metrics"))
    # linked file by file: the modules load once a process, and a file
    # a test drops in stays in its checkout
    os.makedirs(os.path.join(root, "bench", "arch"))
    for f in os.listdir(os.path.join(BENCH_DIR, "arch")):
        if f.endswith(".py"):
            os.symlink(os.path.join(BENCH_DIR, "arch", f),
                       os.path.join(root, "bench", "arch", f))
    os.symlink(os.path.join(REPO, "src"), os.path.join(root, "src"))
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    with open(os.path.join(root, "bench", "configs", "tiny.json"), "w") as f:
        json.dump(config, f)
    with open(os.path.join(root, "bench", "traffic", "tiny.json"), "w") as f:
        json.dump(traffic, f)
    return root


@pytest.fixture
def tiny_root(tmp_path):
    return make_root(tmp_path)
