"""Registered-backend sweep: tokens/s + cache bytes/token per backend.

Runs every ScoreBackend in the registry on the same prefill-shaped score
workload (whisper-ish geometry — the paper's regime), times it, pulls
bytes/token from the backend's own accounting, and writes
``BENCH_scores.json`` for the perf trajectory.

    PYTHONPATH=src python -m benchmarks.score_backends [--json PATH]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import util
from repro.configs.base import get_arch
from repro.core import score_backend as sb
from repro.core.score_backend import ScoreWeights

# whisper-tiny decoder geometry: absolute pos-emb, the fold's home turf
N, D, H, Hkv, DH = 256, 384, 6, 6, 64
REPEATS = 10      # timed samples; min is reported
INNER = 4         # calls per sample (amortizes dispatch overhead)


def _workload(rng):
    f = lambda *s: jnp.asarray(rng.standard_normal(s) * 0.1, jnp.float32)
    sw = ScoreWeights(wq=f(D, H, DH), wk=f(D, Hkv, DH))
    x = f(N, D)
    return sw, x


def _time_backend(be, sw, x) -> float:
    """Min seconds per score call over REPEATS samples (jitted,
    post-warmup; each sample times INNER back-to-back calls). Min-of-k
    because the regression gate normalizes every row by 'standard' —
    a scheduler hiccup in the denominator would shift every ratio."""
    folded = be.fold(sw)
    fn = jax.jit(lambda a, b: be.scores(a, b, folded, scale=DH ** -0.5))
    fn(x, x).block_until_ready()                     # compile
    fn(x, x).block_until_ready()                     # warm caches
    ts = []
    for _ in range(REPEATS):
        t0 = time.perf_counter()
        for _ in range(INNER):
            out = fn(x, x)
        out.block_until_ready()
        ts.append((time.perf_counter() - t0) / INNER)
    return float(min(ts))


def sweep() -> dict:
    cfg = get_arch("whisper-tiny")
    rng = np.random.default_rng(0)
    sw, x = _workload(rng)
    rows = {}
    for name in sb.list_backends():
        be = sb.get_backend(name)
        if not (be.max_d_aug is None or D + 1 <= be.max_d_aug):
            continue
        sec = _time_backend(be, sw, x)
        plan_cfg = dataclasses.replace(cfg, score_mode=name,
                                       cache_mode=None)
        plan = sb.plan(plan_cfg)
        rows[name] = {
            "tokens_per_s": N / sec if sec > 0 else 0.0,
            "seconds_per_call": sec,
            "bytes_per_token_layer": be.memory_bytes_per_token(
                cfg, cache_mode=plan.cache_mode),
            "cache_mode": plan.cache_mode,
            "quantized": be.quantized,
            "supports_blockwise": be.supports_blockwise,
        }
    return {"workload": {"n_tokens": N, "d_model": D, "heads": H,
                         "device": jax.default_backend()},
            "backends": rows}


def run(report):
    report.section("ScoreBackend sweep (tokens/s + bytes/token)")
    out = sweep()
    report.row(f"{'backend':18s} {'tok/s':>12s} {'B/token/layer':>14s} "
               f"{'cache':>6s}")
    for name, r in sorted(out["backends"].items()):
        report.row(f"{name:18s} {r['tokens_per_s']:12.0f} "
                   f"{r['bytes_per_token_layer']:14d} "
                   f"{r['cache_mode']:>6s}")
    with open("BENCH_scores.json", "w") as f:
        json.dump(out, f, indent=2, sort_keys=True)
    report.row("wrote BENCH_scores.json")
    names = set(out["backends"])
    report.check("all registry backends swept (pallas included)",
                 {"standard", "wqk", "wqk_int8", "wqk_int8_pallas",
                  "factored"} <= names)
    x_backends = [r for r in out["backends"].values()
                  if r["cache_mode"] in ("x", "xv")]
    kv = out["backends"]["standard"]["bytes_per_token_layer"]
    report.check("x-cache backends beat kv bytes/token on whisper "
                 "geometry (D < 2*Hkv*dh)",
                 all(r["bytes_per_token_layer"] < kv or
                     r["cache_mode"] == "xv" for r in x_backends)
                 and any(r["bytes_per_token_layer"] < kv
                         for r in x_backends))


def main():
    util.enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default="BENCH_scores.json")
    args = ap.parse_args()
    out = sweep()
    with open(args.json, "w") as f:
        json.dump(out, f, indent=2, sort_keys=True)
    for name, r in sorted(out["backends"].items()):
        print(f"{name:18s} {r['tokens_per_s']:12.0f} tok/s "
              f"{r['bytes_per_token_layer']:6d} B/token/layer "
              f"[{r['cache_mode']}]")
    print(f"wrote {args.json}")


if __name__ == "__main__":
    main()
