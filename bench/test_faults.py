"""A run whose timed path is broken underneath must come out not
correct. The look for a chip is skipped; the rest of a run is the
benchmark's own, at a tiny size on the CPU. The faults a one-chip
serving cell can have: a step that leaves the cache unchanged, half of
the batch left out of a step, a token altered where it is sampled.
(The exchange between chips does not exist on one chip.)"""
from __future__ import annotations

import json

import numpy as np
import pytest

from bench import run

ARGS = ["--workload", "tiny.open", "--seed", "31", "--seconds", "2",
        "--trace", "0"]


def cache_unchanged(engine):
    step = engine._decode_paged

    def broken(params, pool, *a):
        logits, _ = step(params, pool, *a)
        return logits, pool
    engine._decode_paged = broken


def half_batch_left_out(engine):
    step = engine._decode_paged

    def broken(params, pool, tables, tokens, *a):
        logits, pool = step(params, pool, tables, tokens, *a)
        if tokens.shape[0] > 1:        # a decode tick over every slot
            logits = logits.at[1::2].set(logits[0::2])
        return logits, pool
    engine._decode_paged = broken


def token_altered(engine):
    sample = engine._sample

    def broken(logits, reqs):
        out = np.array(sample(logits, reqs))
        out[0] = (out[0] + 1) % logits.shape[-1]
        return out
    engine._sample = broken


def _verdict(root, capsys, faults=None):
    assert run.main(ARGS, root=root, require_chip=False,
                    faults=faults) == 0
    lines = [x for x in capsys.readouterr().out.splitlines()
             if x.startswith("{")]
    return json.loads(lines[-1])


def test_sound_run_is_correct(tiny_root, capsys):
    assert _verdict(tiny_root, capsys)["correct"] is True


@pytest.mark.parametrize("fault", [cache_unchanged, half_batch_left_out,
                                   token_altered])
def test_fault_is_not_correct(tiny_root, capsys, fault):
    res = _verdict(tiny_root, capsys, fault)
    assert res["correct"] is False
    assert res["limits"]["logit_gap"]["value"] > \
        res["limits"]["logit_gap"]["limit"]
