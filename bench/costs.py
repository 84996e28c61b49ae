"""Work the algorithm needs, from a configuration's shapes alone.

Operations count a multiply-add as two. Bytes count what has to cross
HBM at least once: every weight of the step, the key and value rows
that the attention reads and writes, the logits written. Nothing here
reads the program, so a roofline share means the same work whatever
implements it.

Each architecture module (``bench/arch/``) counts its own steps' and
kernel calls' work by these rules, from its configuration's shapes;
this module holds what they share: the sizes of a value, the chip's
peaks, and the least time a piece of work can take. Contexts are
numbers of keys a query attends: a token at position p attends p + 1.
"""
from __future__ import annotations

import json
import os

BF16 = 2
F32 = 4


def load_peaks(device_kind: str) -> dict:
    """Peak rates of ``device_kind`` from ``peaks.json``; a device that
    is not in the table is an error."""
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; the "
                       f"table has {sorted(table)}")
    return table[device_kind]


def min_seconds(flops: float, byt: float, peaks: dict) -> tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    tf, tb = flops / peaks["bf16_flop_s"], byt / peaks["hbm_byte_s"]
    return (tf, "compute") if tf >= tb else (tb, "memory")
