"""Serving-load benchmark: paged vs dense engine at an EQUAL HBM budget.

The paged allocator's claim is capacity, not FLOPs: at the same
decode-cache HBM budget the dense pool admits ``budget // (max_len *
bytes/token)`` concurrent requests (worst-case length reserved for
everyone), while the paged engine admits whatever *actually fits* in
``budget // bytes/block`` blocks. With mixed prompt lengths that is the
difference between a handful of slots and a full batch.

Runs the same mixed-length request set through both engines for every
decode-cache layout (kv / xv / x — standard scores vs the paper's
X-cache dataflow), records sustained tokens/s + peak admitted
concurrency, verifies paged-vs-dense per-token logits parity, and
writes ``BENCH_serving.json``.

    PYTHONPATH=src python -m benchmarks.serving_load [--json PATH]
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
from repro.configs.base import get_arch, reduced
from repro.models.model import build_model
from repro.serving import kvcache
from repro.serving.engine import Engine, Request
from repro.serving.paged import blocks_for

MAX_LEN = 128
BLOCK = 8
MAX_NEW = 8
N_REQUESTS = 24
PROMPT_LENS = (4, 9, 17, 26, 33, 40)       # mixed: the paged regime
DENSE_SLOT_EQUIV = 4                       # HBM = 4 worst-case sequences

# one config per decode-cache layout
LAYOUTS = {
    "kv": {"score_mode": "standard"},
    "xv": {"score_mode": "wqk", "cache_mode": "xv"},
    "x":  {"score_mode": "wqk", "cache_mode": "x"},
}


def _model(over):
    cfg = reduced(get_arch("qwen2.5-14b"), num_layers=2, **over)
    cfg = dataclasses.replace(cfg, dtype="float32")
    model = build_model(cfg)
    return model, model.init(jax.random.PRNGKey(0))


def _requests(n=N_REQUESTS, seed=0):
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        plen = PROMPT_LENS[i % len(PROMPT_LENS)]
        toks = [1] + rng.integers(3, 500, plen - 1).tolist()
        out.append(Request(rid=i, tokens=toks, max_new_tokens=MAX_NEW,
                           eos_id=None))
    return out


def _run_engine(eng) -> dict:
    reqs = _requests()
    t0 = time.perf_counter()
    eng.run(reqs)
    dt = time.perf_counter() - t0
    toks = sum(len(r.output) for r in reqs)
    assert all(r.done for r in reqs)
    reasons = {}
    for r in reqs:
        reasons[str(r.finish_reason)] = reasons.get(str(r.finish_reason),
                                                    0) + 1
    return {"tokens": toks, "seconds": dt,
            "tokens_per_s": toks / dt if dt > 0 else 0.0,
            "ticks": eng.ticks, "peak_concurrency": eng.peak_active,
            "finish_reasons": reasons,
            "outputs": [r.output for r in reqs]}


def paged_vs_dense_logits(model, params, prompt, *, max_len, block_size,
                          chunk, steps, schedule="gather"):
    """Greedy per-token logits from the dense prefill+decode path vs the
    paged chunked-prefill+decode graph on the same prompt. Returns
    (ref, got): lists of numpy (vocab,) logit rows — the admission
    logit plus ``steps`` decode steps each. Shared by the CI serving
    acceptance check and tests/test_paged.py so the two parity
    harnesses cannot drift apart.

    schedule: 'gather' runs the dense-view oracle schedule; 'stream'
    passes per-step used-block counts so the block-streamed path (the
    serving default) is what gets checked against the dense reference.
    """
    batch = {"tokens": jnp.asarray([prompt], jnp.int32),
             "lengths": jnp.asarray([len(prompt)], jnp.int32)}
    logits, cache = model.prefill(params, batch, max_len)
    ref = [np.asarray(logits[0])]
    tok, pos = int(jnp.argmax(logits, -1)[0]), len(prompt)
    for _ in range(steps):
        logits, cache = model.decode_step(
            params, cache, jnp.asarray([tok], jnp.int32),
            jnp.asarray([pos], jnp.int32))
        ref.append(np.asarray(logits[0]))
        tok, pos = int(jnp.argmax(logits, -1)[0]), pos + 1

    nbk = blocks_for(max_len, block_size)
    pool = model.init_paged_cache(num_blocks=nbk + 1,
                                  block_size=block_size)
    nres = blocks_for(len(prompt) + steps + 1, block_size)
    tables = np.zeros((1, nbk), np.int32)
    tables[0, :nres] = range(1, 1 + nres)
    tables = jnp.asarray(tables)

    def used(last_pos):
        if schedule != "stream":
            return None
        return jnp.asarray([min(last_pos // block_size + 1, nbk)],
                           np.int32)

    for c0 in range(0, len(prompt), chunk):
        buf = np.zeros((1, chunk), np.int32)
        piece = prompt[c0:c0 + chunk]
        buf[0, :len(piece)] = piece
        lg, pool = model.decode_paged(params, pool, tables,
                                      jnp.asarray(buf),
                                      jnp.asarray([c0], np.int32),
                                      used(c0 + chunk - 1))
    got = [np.asarray(lg[0, len(prompt) - 1 - c0])]
    tok, pos = int(np.argmax(got[-1])), len(prompt)
    for _ in range(steps):
        lg, pool = model.decode_paged(
            params, pool, tables, jnp.asarray([[tok]], jnp.int32),
            jnp.asarray([pos], np.int32), used(pos))
        got.append(np.asarray(lg[0, 0]))
        tok, pos = int(np.argmax(got[-1])), pos + 1
    return ref, got


def _logits_parity(model, params, schedule="gather") -> float:
    """Max |dense - paged| per-token logit difference on a chunk-crossing
    prompt (the acceptance check: paged must be a pure layout change —
    and, for schedule='stream', the block-streamed early-exit schedule
    a pure scheduling change)."""
    prompt = [1] + list(range(5, 22))
    ref, got = paged_vs_dense_logits(model, params, prompt,
                                     max_len=MAX_LEN, block_size=BLOCK,
                                     chunk=2 * BLOCK, steps=MAX_NEW - 1,
                                     schedule=schedule)
    return max(float(np.max(np.abs(a - b))) for a, b in zip(ref, got, strict=True))


def bench_layout(name: str, over: dict) -> dict:
    model, params = _model(over)
    cfg = model.cfg
    budget = kvcache.budget_for(cfg)
    pb = kvcache.paged_budget_for(cfg, BLOCK)
    hbm = DENSE_SLOT_EQUIV * MAX_LEN * budget.bytes_per_token

    dense_slots = max(1, int(budget.max_tokens(hbm)) // MAX_LEN)
    dense = Engine(model, params, max_slots=dense_slots, max_len=MAX_LEN,
                   paged=False)
    d = _run_engine(dense)

    num_blocks = pb.max_blocks(hbm)
    pagede = Engine(model, params, max_slots=16, max_len=MAX_LEN,
                    paged=True, block_size=BLOCK, num_blocks=num_blocks,
                    prefill_chunk=2 * BLOCK)
    p = _run_engine(pagede)

    outputs_equal = d.pop("outputs") == p.pop("outputs")
    diff = _logits_parity(model, params)
    sdiff = _logits_parity(model, params, schedule="stream")
    return {
        "cache_mode": pb.mode,
        "decode_schedule": pagede.decode_schedule,
        "bytes_per_token": budget.bytes_per_token,
        "bytes_per_block": pb.bytes_per_block,
        "hbm_budget_bytes": hbm,
        "dense": {**d, "slots": dense_slots},
        "paged": {**p, "num_blocks": num_blocks,
                  "block_size": BLOCK},
        "admitted_ratio": (p["peak_concurrency"]
                           / max(d["peak_concurrency"], 1)),
        "outputs_equal": outputs_equal,
        "logits_max_abs_diff": diff,
        "stream_logits_max_abs_diff": sdiff,
        "logits_ok": diff < 1e-4 and sdiff < 1e-4,
    }


# ---------------------------------------------------- decode-tick latency

# Geometry note: on CPU the while-loop stream pays a per-block dispatch
# overhead, so the block size is larger than the engine default (fewer,
# fatter blocks) and max_len is large enough that the gather schedule's
# O(max_len) work dominates the tick — the regime the optimization
# targets (big context reservation, short live sequences).
TICK_MAX_LEN = 2048       # large context reservation ...
TICK_POS = TICK_MAX_LEN // 8   # ... short live sequences: the win regime
TICK_BLOCK = 64
TICK_BATCH = 8
TICK_REPS = 10


def _time_tick(fn, *args) -> float:
    """min-of-N seconds for one jitted decode tick (min: the regression
    gate normalizes by this row, so the denominator must not flake)."""
    fn(*args)[0].block_until_ready()               # compile + warm
    best = float("inf")
    for _ in range(TICK_REPS):
        t0 = time.perf_counter()
        fn(*args)[0].block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return best


def bench_decode_tick() -> dict:
    """Per-tick decode latency, short-sequences-under-large-``max_len``:
    every slot sits at pos = max_len/8, so the gather schedule still
    scores all ``max_len`` positions while the streamed schedule stops
    at the used blocks — the length-proportionality claim, measured.
    A second streamed row near max_len shows cost growing with used
    length (and converging toward gather's constant)."""
    model, params = _model({"score_mode": "standard"})
    nbk = blocks_for(TICK_MAX_LEN, TICK_BLOCK)
    pool = model.init_paged_cache(num_blocks=TICK_BATCH * nbk + 1,
                                  block_size=TICK_BLOCK)
    tables = jnp.asarray(
        1 + np.arange(TICK_BATCH * nbk, dtype=np.int32).reshape(
            TICK_BATCH, nbk))
    toks = jnp.asarray(
        np.random.default_rng(0).integers(3, 500, (TICK_BATCH, 1)),
        jnp.int32)
    fn = jax.jit(model.decode_paged)

    def tick_seconds(pos_scalar, schedule):
        pos = jnp.full((TICK_BATCH,), pos_scalar, jnp.int32)
        used = None
        if schedule == "stream":
            used = jnp.full((TICK_BATCH,),
                            min(pos_scalar // TICK_BLOCK + 1, nbk),
                            jnp.int32)
        return _time_tick(fn, params, pool, tables, toks, pos, used)

    hi = TICK_MAX_LEN - 2
    rows = {
        "gather": {"seconds_per_tick": tick_seconds(TICK_POS, "gather"),
                   "pos": TICK_POS},
        "stream": {"seconds_per_tick": tick_seconds(TICK_POS, "stream"),
                   "pos": TICK_POS},
        "stream_full": {"seconds_per_tick": tick_seconds(hi, "stream"),
                        "pos": hi},
    }
    rows["speedup_at_pos"] = (rows["gather"]["seconds_per_tick"]
                              / rows["stream"]["seconds_per_tick"])
    rows["workload"] = {"max_len": TICK_MAX_LEN, "block_size": TICK_BLOCK,
                        "batch": TICK_BATCH,
                        "device": jax.default_backend()}
    return rows


def sweep() -> dict:
    rows = {name: bench_layout(name, over)
            for name, over in LAYOUTS.items()}
    return {"workload": {"requests": N_REQUESTS,
                         "prompt_lens": list(PROMPT_LENS),
                         "max_new": MAX_NEW, "max_len": MAX_LEN,
                         "block_size": BLOCK,
                         "device": jax.default_backend()},
            "layouts": rows,
            "decode_tick": bench_decode_tick()}


def run(report):
    report.section("Serving load: paged vs dense at equal HBM budget")
    out = sweep()
    report.row(f"{'layout':6s} {'dense tok/s':>12s} {'paged tok/s':>12s} "
               f"{'admit x':>8s} {'|dlogits|':>10s}")
    for name, r in out["layouts"].items():
        report.row(f"{name:6s} {r['dense']['tokens_per_s']:12.1f} "
                   f"{r['paged']['tokens_per_s']:12.1f} "
                   f"{r['admitted_ratio']:8.1f} "
                   f"{r['logits_max_abs_diff']:10.2e}")
    with open("BENCH_serving.json", "w") as f:
        json.dump(out, f, indent=2, sort_keys=True)
    report.row("wrote BENCH_serving.json")
    dt = out["decode_tick"]
    report.row(f"decode tick @pos={dt['stream']['pos']}/"
               f"{dt['workload']['max_len']}: "
               f"gather {dt['gather']['seconds_per_tick']*1e3:.2f} ms, "
               f"stream {dt['stream']['seconds_per_tick']*1e3:.2f} ms "
               f"({dt['speedup_at_pos']:.1f}x); stream @pos="
               f"{dt['stream_full']['pos']}: "
               f"{dt['stream_full']['seconds_per_tick']*1e3:.2f} ms")
    report.check("paged admits >= 2x dense concurrency at equal HBM",
                 all(r["admitted_ratio"] >= 2.0
                     for r in out["layouts"].values()))
    report.check("paged outputs == dense outputs (greedy)",
                 all(r["outputs_equal"] for r in out["layouts"].values()))
    report.check("per-token logits parity (fp tolerance, both schedules)",
                 all(r["logits_ok"] for r in out["layouts"].values()))
    report.check("streamed tick >= 2x faster than gather at pos=max_len/8",
                 dt["speedup_at_pos"] >= 2.0)


def main():
    util.enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default="BENCH_serving.json")
    args = ap.parse_args()
    out = sweep()
    with open(args.json, "w") as f:
        json.dump(out, f, indent=2, sort_keys=True)
    ok = True
    for name, r in out["layouts"].items():
        print(f"{name:4s} dense {r['dense']['tokens_per_s']:8.1f} tok/s "
              f"@{r['dense']['peak_concurrency']} concurrent | "
              f"paged {r['paged']['tokens_per_s']:8.1f} tok/s "
              f"@{r['paged']['peak_concurrency']} concurrent "
              f"({r['admitted_ratio']:.1f}x) | "
              f"|dlogits| {r['logits_max_abs_diff']:.2e}")
        ok &= r["admitted_ratio"] >= 2.0 and r["outputs_equal"] \
            and r["logits_ok"]
    dt = out["decode_tick"]
    print(f"decode tick @pos={dt['stream']['pos']}/"
          f"{dt['workload']['max_len']}: "
          f"gather {dt['gather']['seconds_per_tick']*1e3:8.2f} ms | "
          f"stream {dt['stream']['seconds_per_tick']*1e3:8.2f} ms "
          f"({dt['speedup_at_pos']:.1f}x) | stream @pos="
          f"{dt['stream_full']['pos']}: "
          f"{dt['stream_full']['seconds_per_tick']*1e3:8.2f} ms")
    ok &= dt["speedup_at_pos"] >= 2.0
    print(f"wrote {args.json}")
    if not ok:
        raise SystemExit("serving-load acceptance checks FAILED")


if __name__ == "__main__":
    main()
