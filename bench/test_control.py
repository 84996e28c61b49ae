"""The control of ``correct``: the reference in float8, put in the
program's place, comes out not correct, where the program comes out
correct; at a size a CPU test holds (hidden 256, 4 layers, a 4,096-row
vocabulary), on three seeds."""
from __future__ import annotations

import functools

import pytest

from bench import check, run, spec
from bench.conftest import TINY_CONFIG, TINY_TRAFFIC, make_root

CONFIG = dict(TINY_CONFIG, hidden_size=256, num_attention_heads=8,
              num_key_value_heads=2, intermediate_size=768,
              num_hidden_layers=4, vocab_size=4096,
              correctness=dict(TINY_CONFIG["correctness"], logit_gap=0.15,
                               sample_tokens=400, sample_max_requests=16))
TRAFFIC = dict(TINY_TRAFFIC, rate_rps=8.0)


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    root = make_root(tmp_path_factory.mktemp("control"), CONFIG, TRAFFIC)
    c = spec.load_cell(root, "tiny.open")
    run.open_chip(c, root, require_chip=False)
    return c


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_fails_where_the_program_passes(cell, seed):
    r = run.serve(cell, run.make_weights(cell, seed), seed, 3.0)
    program = check.judge(cell, seed, r)
    control = check.judge(cell, seed, r, gap_fn=functools.partial(
        cell.arch.gaps, quant="fp8"))
    assert program["correct"] is True
    assert control["correct"] is False
    assert control["limits"]["logit_gap"]["value"] > \
        3 * program["limits"]["logit_gap"]["value"]
