"""Serving-engine score traces + synthetic reference workloads.

A *trace* is the sequence of attention-score computations a serving
run actually executed — one event per prefill chunk and per decode
tick per active slot, each carrying the quantized operand shapes, the
schedule's padded sweep sizes, and exact bit-sparsity tallies
(sim/skip.OperandStats). `launch/simulate.py` replays a trace through
`MacroSim` so hardware cost is *measured* on real workloads instead of
assumed.

Capture is compact by construction: a row's bit statistics depend only
on its token id (the layer-0 score operand is the quantized embedding
row — see DESIGN.md §9 for what this proxy does and doesn't capture),
so `TraceCapture` tallies each token id once into a cache and an
event aggregates per-token stats with integer sums — no per-tick
tensor snapshots, nothing on the engine's jit path.

Synthetic workloads (`reference_vit_operands`, `synthetic_workload`)
pin the paper's evaluation points: the ViT-style N=197, D=64 scores
matrix with a padded tail — shared by examples/cim_macro_sim.py and
tests/test_sim.py so the ">=55% skip / 34.1 TOPS/W" reference is
defined exactly once.
"""
from __future__ import annotations

import dataclasses
import json
from collections.abc import Sequence

import numpy as np

from repro.sim.machine import ScoreWorkload
from repro.sim.skip import OperandStats, merge_stats, operand_stats

TRACE_VERSION = 1


# ------------------------------------------------------------------ trace

@dataclasses.dataclass(frozen=True)
class TraceMeta:
    d: int                       # score operand feature dim (d_model)
    heads: int
    layers: int                  # attention layers the event repeats over
    bits: int = 8
    tile_d: int = 64
    arch: str = "?"
    decode_schedule: str = "?"
    block_size: int = 0
    max_len: int = 0


@dataclasses.dataclass(frozen=True)
class TraceEvent:
    kind: str                    # prefill | decode
    stats_q: OperandStats
    stats_kv: OperandStats
    n_q_sched: int
    n_kv_sched: int

    def workload(self, meta: TraceMeta) -> ScoreWorkload:
        return ScoreWorkload(stats_q=self.stats_q, stats_kv=self.stats_kv,
                             heads=meta.heads, layers=meta.layers,
                             n_q_sched=self.n_q_sched,
                             n_kv_sched=self.n_kv_sched,
                             shared=True, kind=self.kind)


@dataclasses.dataclass
class Trace:
    meta: TraceMeta
    events: list[TraceEvent] = dataclasses.field(default_factory=list)

    def workloads(self) -> list[ScoreWorkload]:
        return [e.workload(self.meta) for e in self.events]

    # ------------------------------------------------------ persistence
    def to_dict(self) -> dict:
        return {"version": TRACE_VERSION,
                "meta": dataclasses.asdict(self.meta),
                "events": [{"kind": e.kind,
                            "n_q_sched": e.n_q_sched,
                            "n_kv_sched": e.n_kv_sched,
                            "q": e.stats_q.to_dict(),
                            "kv": e.stats_kv.to_dict()}
                           for e in self.events]}

    def save(self, path: str):
        with open(path, "w") as f:
            json.dump(self.to_dict(), f)

    @classmethod
    def from_dict(cls, d: dict) -> "Trace":
        if d.get("version") != TRACE_VERSION:
            raise ValueError(f"unsupported trace version {d.get('version')!r}")
        meta = TraceMeta(**d["meta"])

        def stats(s: dict) -> OperandStats:
            return OperandStats(rows=s["rows"], d=meta.d, bits=meta.bits,
                                tile_d=meta.tile_d, ones=s["ones"],
                                nz_rows=s["nz_rows"],
                                nz_frags=s["nz_frags"],
                                nz_planes=s["nz_planes"])

        return cls(meta=meta,
                   events=[TraceEvent(kind=e["kind"],
                                      stats_q=stats(e["q"]),
                                      stats_kv=stats(e["kv"]),
                                      n_q_sched=e["n_q_sched"],
                                      n_kv_sched=e["n_kv_sched"])
                           for e in d["events"]])

    @classmethod
    def load(cls, path: str) -> "Trace":
        with open(path) as f:
            return cls.from_dict(json.load(f))


# ---------------------------------------------------------------- capture

def _quantize_rows(x: np.ndarray, bits: int = 8) -> np.ndarray:
    """Per-row symmetric int8 — numpy twin of core/quant.quantize
    (np.round and jnp.round both round half to even)."""
    qmax = float(2 ** (bits - 1) - 1)
    amax = np.max(np.abs(x.astype(np.float32)), axis=-1, keepdims=True)
    scale = np.maximum(amax, 1e-8) / qmax
    return np.clip(np.round(x / scale), -qmax, qmax).astype(np.int8)


class TraceCapture:
    """The engine-side hook (serving/engine.py `capture_trace=True`).

    Per-token bit statistics are computed once per distinct token id
    from the quantized embedding row and cached; recording an event is
    a few integer additions per token. Nothing here touches device
    arrays during the serving loop.
    """

    def __init__(self, embed: np.ndarray, meta: TraceMeta):
        if embed.ndim != 2 or embed.shape[1] != meta.d:
            raise ValueError(f"embedding table {embed.shape} does not "
                             f"match meta.d={meta.d}")
        self.embed = np.asarray(embed, np.float32)
        self.trace = Trace(meta=meta)
        self._token_stats: dict[int, OperandStats] = {}

    @classmethod
    def for_model(cls, model, params, *, decode_schedule: str = "?",
                  block_size: int = 0, max_len: int = 0) -> "TraceCapture":
        cfg = model.cfg
        if not getattr(cfg, "num_heads", 0):
            raise ValueError(f"trace capture needs an attention score "
                             f"path; family {cfg.family!r} has none")
        meta = TraceMeta(d=cfg.d_model, heads=cfg.num_heads,
                         layers=len(cfg.attn_layer_indices),
                         arch=getattr(cfg, "name", cfg.family),
                         decode_schedule=decode_schedule,
                         block_size=block_size, max_len=max_len)
        return cls(np.asarray(params["embed"], np.float32), meta)

    # ------------------------------------------------------------ stats
    def _stats(self, tok: int) -> OperandStats:
        s = self._token_stats.get(tok)
        if s is None:
            if not 0 <= tok < self.embed.shape[0]:
                # the jitted gather would clamp silently; a trace built
                # from clamped rows would undercount with no diagnostic
                raise ValueError(f"token id {tok} outside the embedding "
                                 f"table ({self.embed.shape[0]} rows)")
            row = _quantize_rows(self.embed[tok:tok + 1],
                                 self.trace.meta.bits)
            s = operand_stats(row, tile_d=self.trace.meta.tile_d,
                              bits=self.trace.meta.bits)
            self._token_stats[tok] = s
        return s

    def stats_for_tokens(self, tokens: Sequence[int]) -> OperandStats:
        return merge_stats([self._stats(int(t)) for t in tokens])

    # ------------------------------------------------------------ record
    def record(self, kind: str, q_tokens: Sequence[int],
               kv_tokens: Sequence[int], *, n_q_sched: int = 0,
               n_kv_sched: int = 0):
        self.trace.events.append(TraceEvent(
            kind=kind,
            stats_q=self.stats_for_tokens(q_tokens),
            stats_kv=self.stats_for_tokens(kv_tokens),
            n_q_sched=max(n_q_sched, len(q_tokens)),
            n_kv_sched=max(n_kv_sched, len(kv_tokens))))

    def save(self, path: str):
        self.trace.save(path)


# ------------------------------------------------------------- synthetics

def reference_vit_operands(n: int = 197, d: int = 64, live: int = 160,
                           seed: int = 42):
    """The repo's reference ViT-style score workload (the paper's image
    recognition evaluation point): N=197 token rows on the 64-wide
    macro, rows past `live` all-zero (the padded tail the §III.C skip
    hierarchy feeds on). Returns (x float32, qx int8)."""
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, d)).astype(np.float32)
    x[live:] = 0.0
    return x, _quantize_rows(x)


def synthetic_workload(name: str, *, heads: int = 1,
                       layers: int = 1) -> ScoreWorkload:
    """Named synthetic evaluation workloads.

    vit  : N=197, D=64, 37-row padded tail (ImageNet classification)
    detr : N=725, D=64, Laplacian activation statistics + 17% padded
           tail (visual segmentation — longer token stream, sparser
           magnitudes)
    """
    if name == "vit":
        _, qx = reference_vit_operands()
    elif name == "detr":
        rng = np.random.default_rng(7)
        x = rng.laplace(0.0, 12.0, (725, 64)).clip(-127, 127)
        x[600:] = 0.0
        qx = x.astype(np.int8)
    else:
        raise ValueError(f"unknown synthetic workload {name!r}; "
                         f"known: vit, detr")
    from repro.sim.machine import workload_from_arrays
    return workload_from_arrays(qx, heads=heads, layers=layers)
