"""Async serving benchmark: Poisson arrivals through the streaming
front end vs the batch-sync engine, plus the radix prefix cache on a
shared-system-prompt trace.

Three claims, each a structural (machine-speed-independent) gate:

* **Continuous admission beats batch collection.** The batch-sync
  baseline is ``Engine.run`` on the full request set — it cannot start
  until the batch is assembled, so every request's time-to-first-token
  (measured from its own Poisson arrival) pays the collection wait.
  The async front end admits each request the tick it arrives. At
  equal load, async p99 TTFT must be <= batch-sync p99 TTFT.

* **Priorities + preemption protect the short-request tail.** On a
  mixed trace (long-prefill low-priority jobs hogging both slots,
  short high-priority jobs arriving behind them), the FIFO scheduler
  head-blocks the shorts for a long job's full prefill+decode; the SLO
  scheduler preempts a long job (evict-to-queue, lossless resume) and
  serves the shorts immediately. High-priority p99 TTFT under FIFO
  must be >= under SLO.

* **The radix cache hits across *historical* requests.** Sixteen
  requests share a 4-block system prompt but arrive strictly
  sequentially — each finishes (blocks freed) before the next is
  submitted, so the engine's live-donor sharing can never fire; only
  the radix tree's pinned blocks can. Hit rate must be >= 0.5.

Greedy outputs through the async path are also checked bit-identical
to ``Engine.run`` (token streams concatenate to the sync result).

Writes ``BENCH_async.json`` (gated by ``check_regression`` FLOORS).

    PYTHONPATH=src python -m benchmarks.serving_async [--json PATH]
"""
from __future__ import annotations

import argparse
import asyncio
import json
import time

import numpy as np

from benchmarks.serving_load import _model
from repro import util
from repro.serving.engine import Engine, Request
from repro.serving.frontend import AsyncEngine, FIFOScheduler, SLOScheduler

MAX_LEN = 128
BLOCK = 8
SLOTS = 2

# Poisson (exponential-gap) arrival process for the latency comparison
N_POISSON = 10
MEAN_GAP_S = 0.02
POISSON_PLENS = (4, 9, 17, 26)
POISSON_MAX_NEW = 6

# mixed SLO trace: long hogs first, short urgent requests behind them
N_LONG, LONG_PLEN, LONG_MAX_NEW = 2, 40, 24
N_SHORT, SHORT_PLEN, SHORT_MAX_NEW = 6, 4, 3

# shared-system-prompt trace for the radix cache
RADIX_PREFIX_BLOCKS = 4                  # 32-token system prompt
N_RADIX = 16


def _engine(model, params, **over):
    kw = dict(max_slots=SLOTS, max_len=MAX_LEN, paged=True,
              block_size=BLOCK, prefill_chunk=2 * BLOCK)
    kw.update(over)
    return Engine(model, params, **kw)


def _warm(eng):
    """Compile every graph shape the measured trace will touch (prefill
    buckets + decode tick) so the latency rows see steady-state serving,
    not XLA compile time."""
    rng = np.random.default_rng(99)
    reqs = [Request(rid=10_000 + i,
                    tokens=[1] + rng.integers(3, 500, p - 1).tolist(),
                    max_new_tokens=2)
            for i, p in enumerate((3, 9, 17, 26, 33, LONG_PLEN))]
    eng.run(reqs)
    if eng.radix is not None:
        eng.radix.clear()
        eng.radix.reset_stats()
    eng.preemptions = 0


def _poisson_reqs(seed=0):
    rng = np.random.default_rng(seed)
    gaps = rng.exponential(MEAN_GAP_S, N_POISSON)
    out, t = [], 0.0
    for i in range(N_POISSON):
        t += gaps[i]
        plen = POISSON_PLENS[i % len(POISSON_PLENS)]
        toks = [1] + rng.integers(3, 500, plen - 1).tolist()
        out.append((t, Request(rid=i, tokens=toks,
                               max_new_tokens=POISSON_MAX_NEW)))
    return out


def _pct(xs, q):
    return float(np.percentile(xs, q)) if xs else 0.0


# ------------------------------------------------- batch-sync baseline

def bench_sync(model, params) -> dict:
    """Engine.run on the collected batch; per-request TTFT measured
    from its Poisson arrival (the batch cannot start before the last
    arrival — that wait is the point)."""
    eng = _engine(model, params)
    _warm(eng)
    arrivals = _poisson_reqs()
    first_tok = {}
    eng.on_token = (lambda req, tok:
                    first_tok.setdefault(req.rid, time.perf_counter()))
    t_start = time.perf_counter()      # batch assembled at last arrival
    eng.run([r for _, r in arrivals])
    dt = time.perf_counter() - t_start
    last = max(t for t, _ in arrivals)
    ttft = [first_tok[r.rid] - (t_start - (last - t_off))
            for t_off, r in arrivals]
    toks = sum(len(r.output) for _, r in arrivals)
    return {"p50_ttft_s": _pct(ttft, 50), "p99_ttft_s": _pct(ttft, 99),
            "tokens_per_s": toks / dt if dt > 0 else 0.0,
            "outputs": [r.output for _, r in arrivals]}


# --------------------------------------------------- async (streaming)

async def _replay(srv, arrivals, *, priorities=None):
    t0 = time.perf_counter()
    for t_off, req in arrivals:
        delay = t0 + t_off - time.perf_counter()
        if delay > 0:
            await asyncio.sleep(delay)
        srv.submit(req, priority=(priorities or {}).get(req.rid, 0))
    await srv.drain()
    return time.perf_counter() - t0


def bench_async(model, params) -> dict:
    eng = _engine(model, params, radix_cache=True)
    _warm(eng)
    arrivals = _poisson_reqs()

    async def go():
        async with AsyncEngine(eng) as srv:
            dt = await _replay(srv, arrivals)
            return srv.metrics.snapshot(eng), dt

    snap, dt = asyncio.run(go())
    toks = sum(len(r.output) for _, r in arrivals)
    return {"p50_ttft_s": snap["ttft_s"]["p50"],
            "p99_ttft_s": snap["ttft_s"]["p99"],
            "tokens_per_s": toks / dt if dt > 0 else 0.0,
            "preemptions": snap["requests"]["preemptions"],
            "outputs": [r.output for _, r in arrivals]}


# ------------------------------------------- SLO vs FIFO (mixed trace)

def _mixed_arrivals(seed=1):
    """Two slot-hogging long jobs at t=0, short urgent jobs right
    behind: the head-of-line regime preemption exists for."""
    rng = np.random.default_rng(seed)
    arrivals, prios = [], {}
    for i in range(N_LONG):
        toks = [1] + rng.integers(3, 500, LONG_PLEN - 1).tolist()
        arrivals.append((0.0, Request(rid=i, tokens=toks,
                                      max_new_tokens=LONG_MAX_NEW)))
        prios[i] = 0
    for j in range(N_SHORT):
        rid = N_LONG + j
        toks = [1] + rng.integers(3, 500, SHORT_PLEN - 1).tolist()
        arrivals.append((0.02 + 0.01 * j,
                         Request(rid=rid, tokens=toks,
                                 max_new_tokens=SHORT_MAX_NEW)))
        prios[rid] = 5
    return arrivals, prios


def bench_slo(model, params) -> dict:
    rows = {}
    for name, mk_sched in (("fifo", FIFOScheduler),
                           ("slo", SLOScheduler)):
        eng = _engine(model, params)
        _warm(eng)
        arrivals, prios = _mixed_arrivals()

        async def go():
            async with AsyncEngine(eng, scheduler=mk_sched()) as srv:
                await _replay(srv, arrivals, priorities=prios)
                return srv.metrics.snapshot(eng)

        snap = asyncio.run(go())
        hi = [m for m in snap["requests_detail"]
              if m["rid"] >= N_LONG and m["ttft_s"] is not None]
        ttft = [m["ttft_s"] for m in hi]
        rows[name] = {"p50_ttft_hi_s": _pct(ttft, 50),
                      "p99_ttft_hi_s": _pct(ttft, 99),
                      "preemptions": snap["requests"]["preemptions"]}
        assert all(r.done for _, r in arrivals)
    rows["gate"] = {
        "fifo_over_slo_p99_hi": (rows["fifo"]["p99_ttft_hi_s"]
                                 / max(rows["slo"]["p99_ttft_hi_s"],
                                       1e-9)),
        "slo_preempted": rows["slo"]["preemptions"] >= 1,
    }
    return rows


# -------------------------------------- radix cache (historical trace)

def bench_radix(model, params) -> dict:
    """Strictly sequential shared-prefix trace: every request finishes
    before the next arrives, so only the radix tree (pinned historical
    blocks) can serve the prefix — live-donor sharing never applies."""
    eng = _engine(model, params, radix_cache=True)
    _warm(eng)
    rng = np.random.default_rng(2)
    prefix = [1] + rng.integers(3, 500,
                                RADIX_PREFIX_BLOCKS * BLOCK - 1).tolist()

    async def go():
        async with AsyncEngine(eng) as srv:
            for i in range(N_RADIX):
                tail = rng.integers(3, 500, 3).tolist()
                s = srv.submit(Request(rid=i, tokens=prefix + tail,
                                       max_new_tokens=4))
                await s.collect()          # finished before the next
        return srv.metrics.snapshot(eng)

    snap = asyncio.run(go())
    return dict(snap["radix"])


# ------------------------------------------------------------ assembly

def sweep() -> dict:
    model, params = _model({"score_mode": "standard"})
    sync = bench_sync(model, params)
    asy = bench_async(model, params)
    outputs_equal = sync.pop("outputs") == asy.pop("outputs")
    slo = bench_slo(model, params)
    radix = bench_radix(model, params)
    return {
        "workload": {"poisson_requests": N_POISSON,
                     "mean_gap_s": MEAN_GAP_S, "slots": SLOTS,
                     "max_len": MAX_LEN, "block_size": BLOCK,
                     "radix_requests": N_RADIX,
                     "radix_prefix_blocks": RADIX_PREFIX_BLOCKS},
        "async": {
            "sync": sync,
            "stream": asy,
            "latency": {"sync_over_async_p99":
                        sync["p99_ttft_s"] / max(asy["p99_ttft_s"],
                                                 1e-9)},
            "slo": slo["gate"] | {"fifo": slo["fifo"],
                                  "slo": slo["slo"]},
            "radix": radix,
            "parity": {"outputs_equal": outputs_equal},
        },
    }


def run(report):
    report.section("Async serving: streaming vs batch-sync, SLO, radix")
    out = sweep()
    a = out["async"]
    report.row(f"{'mode':8s} {'p50 TTFT':>10s} {'p99 TTFT':>10s} "
               f"{'tok/s':>8s}")
    for name in ("sync", "stream"):
        r = a[name]
        report.row(f"{name:8s} {r['p50_ttft_s']*1e3:8.1f} ms "
                   f"{r['p99_ttft_s']*1e3:8.1f} ms "
                   f"{r['tokens_per_s']:8.1f}")
    report.row(f"SLO trace: hi-prio p99 TTFT fifo "
               f"{a['slo']['fifo']['p99_ttft_hi_s']*1e3:.1f} ms vs slo "
               f"{a['slo']['slo']['p99_ttft_hi_s']*1e3:.1f} ms "
               f"({a['slo']['fifo_over_slo_p99_hi']:.1f}x; "
               f"{a['slo']['slo']['preemptions']} preemptions)")
    report.row(f"radix: hit rate {a['radix']['hit_rate']:.2f} over "
               f"{a['radix']['lookup_blocks']} offered blocks")
    with open("BENCH_async.json", "w") as f:
        json.dump(out, f, indent=2, sort_keys=True)
    report.row("wrote BENCH_async.json")
    report.check("async p99 TTFT <= batch-sync at equal load",
                 a["latency"]["sync_over_async_p99"] >= 1.0)
    report.check("SLO scheduler beats FIFO on hi-prio p99 TTFT",
                 a["slo"]["fifo_over_slo_p99_hi"] >= 1.0
                 and a["slo"]["slo_preempted"])
    report.check("radix hit rate >= 0.5 on shared-prefix trace",
                 a["radix"]["hit_rate"] >= 0.5)
    report.check("async greedy outputs == batch-sync outputs",
                 a["parity"]["outputs_equal"])


def main():
    util.enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default="BENCH_async.json")
    args = ap.parse_args()
    out = sweep()
    with open(args.json, "w") as f:
        json.dump(out, f, indent=2, sort_keys=True)
    a = out["async"]
    for name in ("sync", "stream"):
        r = a[name]
        print(f"{name:8s} p50 TTFT {r['p50_ttft_s']*1e3:8.1f} ms | "
              f"p99 TTFT {r['p99_ttft_s']*1e3:8.1f} ms | "
              f"{r['tokens_per_s']:8.1f} tok/s")
    print(f"slo      fifo/slo hi-prio p99 "
          f"{a['slo']['fifo_over_slo_p99_hi']:8.1f}x | "
          f"preemptions {a['slo']['slo']['preemptions']}")
    print(f"radix    hit rate {a['radix']['hit_rate']:.2f} "
          f"({a['radix']['hit_blocks']}/{a['radix']['lookup_blocks']} "
          f"blocks)")
    ok = (a["latency"]["sync_over_async_p99"] >= 1.0
          and a["slo"]["fifo_over_slo_p99_hi"] >= 1.0
          and a["slo"]["slo_preempted"]
          and a["radix"]["hit_rate"] >= 0.5
          and a["parity"]["outputs_equal"])
    print(f"wrote {args.json}")
    if not ok:
        raise SystemExit("async-serving acceptance checks FAILED")


if __name__ == "__main__":
    main()
