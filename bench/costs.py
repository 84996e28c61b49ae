"""Work the algorithm needs, from a configuration's shapes alone.

Operations count a multiply-add as two. Bytes count what has to cross
HBM at least once: every weight of the step, the key and value rows
that the attention reads and writes, the logits written. Nothing here
reads the program, so a roofline share means the same work whatever
implements it.

``Shapes.of(config)`` reads a configuration file (Hugging Face keys).
Contexts are numbers of keys a query attends: a token at position p
attends p + 1.
"""
from __future__ import annotations

import dataclasses
import json
import math
import os

BF16 = 2
F32 = 4


@dataclasses.dataclass(frozen=True)
class Shapes:
    d: int          # hidden size
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    layers: int
    qkv_bias: bool

    @classmethod
    def of(cls, config: dict) -> Shapes:
        d, h = config["hidden_size"], config["num_attention_heads"]
        return cls(d=d, heads=h, kv_heads=config["num_key_value_heads"],
                   head_dim=config.get("head_dim", d // h),
                   ff=config["intermediate_size"],
                   vocab=config["vocab_size"],
                   layers=config["num_hidden_layers"],
                   qkv_bias=bool(config["qkv_bias"]))

    @property
    def layer_matmul_params(self) -> int:
        """Weights one token multiplies in one layer: q, k, v, o and the
        gated feed-forward."""
        qkv = self.d * (self.heads + 2 * self.kv_heads) * self.head_dim
        return qkv + self.heads * self.head_dim * self.d \
            + 3 * self.d * self.ff

    @property
    def layer_params(self) -> int:
        bias = (self.heads + 2 * self.kv_heads) * self.head_dim \
            if self.qkv_bias else 0
        return self.layer_matmul_params + bias + 2 * self.d

    @property
    def kv_row_bytes(self) -> int:
        """One token's keys and values in one layer, bf16."""
        return 2 * self.kv_heads * self.head_dim * BF16

    def attention_flops(self, context: int, queries: int = 1) -> int:
        """Scores and the weighted sum of values for ``queries``
        consecutive tokens of one sequence, the last attending
        ``context`` keys, in one layer."""
        keys = sum(context - i for i in range(queries))
        return 4 * self.heads * self.head_dim * keys

    def weight_bytes(self) -> int:
        """Every layer, the final norm and the output head."""
        return BF16 * (self.layers * self.layer_params
                       + self.d + self.d * self.vocab)


def decode_tick(s: Shapes, contexts) -> tuple[int, int]:
    """(FLOPs, bytes) of one decode step for sequences attending
    ``contexts`` keys each (the new token's own key included): the
    layers, attention over the cache, the output head, per sequence;
    the weights once, each sequence's cached rows, its new rows and
    its embedding row, float32 logits out."""
    n = len(contexts)
    flops = n * 2 * (s.layers * s.layer_matmul_params + s.d * s.vocab)
    flops += s.layers * sum(s.attention_flops(c) for c in contexts)
    byt = s.weight_bytes() + n * (s.d * BF16 + s.vocab * F32)
    byt += s.layers * s.kv_row_bytes * sum(contexts)
    return flops, byt


def prefill_chunk(s: Shapes, start: int, tokens: int,
                  last: bool) -> tuple[int, int]:
    """(FLOPs, bytes) of one prompt chunk: ``tokens`` prompt tokens at
    positions ``start`` onward, through every layer; the output head
    only for the prompt's last token (``last``), the one whose logits
    are sampled. Bytes: the weights, the cached rows read, the new rows
    written."""
    end = start + tokens
    flops = tokens * 2 * s.layers * s.layer_matmul_params
    flops += s.layers * s.attention_flops(end, tokens)
    byt = BF16 * s.layers * s.layer_params + tokens * s.d * BF16
    byt += s.layers * s.kv_row_bytes * end
    if last:
        flops += 2 * s.d * s.vocab
        byt += BF16 * (s.d + s.d * s.vocab) + s.vocab * F32
    return flops, byt


def paged_kernel(s: Shapes, contexts, queries: int,
                 block: int) -> tuple[int, int]:
    """(FLOPs, bytes) of one call of the paged attention kernel (one
    layer) over sequences whose last query attends ``contexts`` keys:
    every live block of keys and values, float32 queries in and
    outputs out."""
    flops = sum(s.attention_flops(c, queries) for c in contexts)
    blocks = sum(math.ceil(c / block) for c in contexts)
    byt = blocks * block * s.kv_row_bytes
    byt += 2 * len(contexts) * queries * s.heads * s.head_dim * F32
    return flops, byt


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
