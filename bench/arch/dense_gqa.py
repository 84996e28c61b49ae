"""The dense GQA decoder (Qwen2, InternLM2), as the harness knows it.

Shapes from the configuration file (Hugging Face keys); the serving
program's ``ModelConfig``; the weights' leaves; the plain float32
reference and its float8 control; the work of a step from its shapes.
The contract of an architecture module is in ``bench/arch/__init__.py``.

Reference, written from the published architecture, not from the
serving program, and importing nothing of it: token embedding; per
layer RMSNorm, q/k/v projections (with bias where the configuration has
it), rotary embedding (half-split, ``rotate_half``), causal attention
in which query head h reads key/value head ``h // (heads / kv_heads)``,
output projection, residual; RMSNorm, SiLU-gated feed-forward,
residual; a final RMSNorm and the output head. It runs one layer at a
time over one whole sequence, the layer's weights made from the seed as
it reaches them, so that it fits on one chip at the published widths.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference as R
from bench import weights as W
from bench.costs import BF16, F32

KERNEL = "paged_attention"        # the program's Pallas kernel's name=
SPANS = ()                        # the harness's own list is enough

# leaf name -> id: the order is part of the weights' definition
LEAF_IDS = {name: i for i, name in enumerate((
    "embed", "final_norm", "lm_head", "attn_norm", "wq", "wk", "wv",
    "bq", "bk", "bv", "wo", "mlp_norm", "w_gate", "w_up", "w_down"))}


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


def vocab(config: dict) -> int:
    return config["vocab_size"]


def program_config(config: dict):
    from repro.configs.base import ModelConfig
    d, h = config["hidden_size"], config["num_attention_heads"]
    if config["hidden_act"] != "silu":
        raise ValueError(f"hidden_act {config['hidden_act']!r}")
    return ModelConfig(
        name=config["name"], family="dense",
        num_layers=config["num_hidden_layers"], d_model=d, num_heads=h,
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config.get("head_dim", d // h),
        d_ff=config["intermediate_size"], vocab_size=config["vocab_size"],
        qkv_bias=bool(config["qkv_bias"]),
        rope_theta=float(config["rope_theta"]),
        tie_embeddings=bool(config["tie_word_embeddings"]),
        dtype=config["torch_dtype"])


# ---------------------------------------------------------------- weights

def layer_specs(s: Shapes) -> dict:
    """Per-layer leaf -> (shape, log2 half-width)."""
    d, h, g, e, f = s.d, s.heads, s.kv_heads, s.head_dim, s.ff
    out = {
        "attn_norm": ((d,), W.NORM_LOG2),
        "wq": ((d, h, e), W._log2_half(d)),
        "wk": ((d, g, e), W._log2_half(d)),
        "wv": ((d, g, e), W._log2_half(d)),
        "wo": ((h, e, d), W._log2_half(h * e)),
        "mlp_norm": ((d,), W.NORM_LOG2),
        "w_gate": ((d, f), W._log2_half(d)),
        "w_up": ((d, f), W._log2_half(d)),
        "w_down": ((f, d), W._log2_half(f)),
    }
    if s.qkv_bias:
        out.update(bq=((h, e), W.BIAS_LOG2), bk=((g, e), W.BIAS_LOG2),
                   bv=((g, e), W.BIAS_LOG2))
    return out


def global_specs(s: Shapes) -> dict:
    return {"embed": ((s.vocab, s.d), W.EMBED_LOG2),
            "final_norm": ((s.d,), W.NORM_LOG2),
            "lm_head": ((s.d, s.vocab), W._log2_half(s.d))}


def layer_weights(key, s: Shapes, layer) -> dict:
    """One layer's leaves (bf16), by canonical name."""
    return {n: W.leaf(key, LEAF_IDS[n], shp, e, layer)
            for n, (shp, e) in layer_specs(s).items()}


def global_weight(key, s: Shapes, name: str):
    shp, e = global_specs(s)[name]
    return W.leaf(key, LEAF_IDS[name], shp, e)


def program_layers(key, s: Shapes) -> dict:
    """The program's ``layers`` subtree (``models/model.Model`` layout:
    layer leaves stacked on a leading axis), to be traced inside a
    jitted call."""
    layers = jax.vmap(lambda i: layer_weights(key, s, i))(
        jnp.arange(s.layers))
    attn = {k: layers[k] for k in ("wq", "wk", "wv", "wo", "bq", "bk",
                                   "bv") if k in layers}
    return {
        "ln1": {"scale": layers["attn_norm"]},
        "attn": attn,
        "ln2": {"scale": layers["mlp_norm"]},
        "mlp": {"w_gate": layers["w_gate"], "w_up": layers["w_up"],
                "w_down": layers["w_down"]},
    }


def program_params(seed: int, config: dict) -> dict:
    """The serving program's parameter tree, made on the device in one
    jitted call."""
    s = Shapes.of(config)

    def make(key):
        return {
            "embed": global_weight(key, s, "embed"),
            "final_ln": {"scale": global_weight(key, s, "final_norm")},
            "lm_head": global_weight(key, s, "lm_head"),
            "layers": program_layers(key, s),
        }
    return jax.jit(make)(W.seed_key(seed))


# -------------------------------------------------------------- reference

@functools.partial(jax.jit, static_argnames=("s", "eps", "theta", "quant"))
def _layer(x, w, *, s: Shapes, eps: float, theta: float, quant):
    """One decoder layer over a whole (padded) sequence x (T, d)."""
    T = x.shape[0]
    f = {k: v.astype(jnp.float32) for k, v in w.items()}
    pos = jnp.arange(T)
    h = R._rms(x, 1.0 + f["attn_norm"], eps)
    q, k, v = (R._proj(h, f[n], quant) for n in ("wq", "wk", "wv"))
    if s.qkv_bias:
        q, k, v = q + f["bq"], k + f["bk"], v + f["bv"]
    q, k = R._rope(q, pos, theta), R._rope(k, pos, theta)
    rep = s.heads // s.kv_heads
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    o = R.causal_attention(q, k, v, 1.0 / np.sqrt(s.head_dim))
    o = o.reshape(T, s.heads * s.head_dim)
    x = x + R._proj(o, f["wo"].reshape(-1, s.d), quant)
    h = R._rms(x, 1.0 + f["mlp_norm"], eps)
    g = R._proj(h, f["w_gate"], quant)
    u = R._proj(h, f["w_up"], quant)
    return x + R._proj(jax.nn.silu(g) * u, f["w_down"], quant)


@functools.partial(jax.jit, static_argnames=("s",))
def _make_layer(key, layer, *, s: Shapes):
    return layer_weights(key, s, layer)


@functools.partial(jax.jit, static_argnames=("s", "name"))
def _make_global(key, *, s: Shapes, name: str):
    return global_weight(key, s, name)


def run_layers(config: dict, seed: int, x, quant=None):
    """Every decoder layer over a padded sequence's embedded rows x
    (T, d) float32, the layers' weights made one at a time."""
    s = Shapes.of(config)
    eps, theta = float(config["rms_norm_eps"]), float(config["rope_theta"])
    key = W.seed_key(seed)
    for layer in range(s.layers):
        w = _make_layer(key, jnp.int32(layer), s=s)
        x = _layer(x, w, s=s, eps=eps, theta=theta, quant=quant)
        del w
    return x


def hidden_rows(config: dict, seed: int, tokens, rows, quant=None):
    """Final-layer hidden states (before the final norm) at ``rows`` of
    the sequence ``tokens``, float32 (len(rows), d)."""
    s = Shapes.of(config)
    emb = _make_global(W.seed_key(seed), s=s, name="embed")
    x = jnp.take(emb, jnp.asarray(R.padded(tokens)),
                 axis=0).astype(jnp.float32)
    del emb
    return run_layers(config, seed, x, quant)[jnp.asarray(rows)]


def gaps(config: dict, seed: int, sequences, quant=None):
    s = Shapes.of(config)
    key = W.seed_key(seed)
    head = _make_global(key, s=s, name="lm_head")
    fnorm = _make_global(key, s=s, name="final_norm")
    return R.served_gaps(
        sequences,
        lambda seq, rows, q: hidden_rows(config, seed, seq, rows, quant=q),
        fnorm, head, float(config["rms_norm_eps"]), quant)


# ------------------------------------------------------------------ costs

def decode_tick(config: dict, contexts) -> tuple[int, int]:
    """One decode step for sequences attending ``contexts`` keys each
    (the new token's own key included): the layers, attention over the
    cache, the output head, per sequence; the weights once, each
    sequence's cached rows, its new rows and its embedding row, float32
    logits out."""
    s = Shapes.of(config)
    n = len(contexts)
    flops = n * 2 * (s.layers * s.layer_matmul_params + s.d * s.vocab)
    flops += s.layers * sum(s.attention_flops(c) for c in contexts)
    byt = s.weight_bytes() + n * (s.d * BF16 + s.vocab * F32)
    byt += s.layers * s.kv_row_bytes * sum(contexts)
    return flops, byt


def prefill_chunk(config: dict, start: int, tokens: int,
                  last: bool) -> tuple[int, int]:
    """One prompt chunk: ``tokens`` prompt tokens at positions ``start``
    onward, through every layer; the output head only for the prompt's
    last token (``last``), the one whose logits are sampled. Bytes: the
    weights, the cached rows read, the new rows written."""
    s = Shapes.of(config)
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
    """One call of the paged attention kernel (one layer): every live
    block of keys and values, float32 queries in and outputs out."""
    flops = sum(s.attention_flops(c, queries) for c in contexts)
    blocks = sum(math.ceil(c / block) for c in contexts)
    byt = blocks * block * s.kv_row_bytes
    byt += 2 * len(contexts) * queries * s.heads * s.head_dim * F32
    return flops, byt


def kernel_calls(config: dict, contexts, queries: int,
                 block: int) -> list:
    """One ``paged_attention`` call per layer, each over every key."""
    s = Shapes.of(config)
    return [(KERNEL, *paged_kernel(s, contexts, queries, block))] * s.layers
