"""Serving launcher: continuous-batching engine over a selected arch.

    PYTHONPATH=src python -m repro.launch.serve --arch whisper-tiny \
        --reduced --requests 8 --max-new 16

Real deployments restore params from --ckpt; without one, randomly
initialized weights serve synthetic traffic (throughput/latency path
identical).

Async streaming mode (``--stream``) routes the same requests through
the thread-pumped asyncio front end (``serving.frontend``): tokens
stream per tick, admission/preemption run under the SLO scheduler, and
the run ends with a ``ServingMetrics`` snapshot (TTFT / inter-token /
queue-wait percentiles, preemption counts, radix hit rate).
``--arrival-trace`` replays a JSON arrival schedule instead of the
synthetic all-at-once batch; ``--slo-ttft-ms`` attaches a deadline to
every request.
"""
from __future__ import annotations

import argparse
import asyncio
import dataclasses
import json
import time

import jax
import numpy as np

from repro import util
from repro.configs.base import get_arch, reduced as reduce_cfg
from repro.core import score_backend
from repro.models import frontends
from repro.models.model import build_model
from repro.serving import kvcache
from repro.serving.engine import Engine, Request
from repro.train import checkpoint as ckpt_lib


def parse_bytes(s: str) -> int:
    """'512MB', '1.5GiB', '2g', or a raw byte count."""
    t = s.strip().lower().rstrip("ib")
    for suf, mul in (("k", 1 << 10), ("m", 1 << 20), ("g", 1 << 30),
                     ("t", 1 << 40)):
        if t.endswith(suf):
            return int(float(t[:-1]) * mul)
    return int(float(t))


async def _stream_serve(eng, arrivals, args):
    """Replay ``arrivals`` ((t_offset, Request, priority) sorted or
    not) through the async front end on the wall clock; returns the
    metrics snapshot."""
    from repro.serving.frontend import (AsyncEngine, FIFOScheduler,
                                        SLOScheduler)
    sched = (FIFOScheduler() if args.scheduler == "fifo"
             else SLOScheduler())
    async with AsyncEngine(eng, scheduler=sched) as srv:
        t0 = time.monotonic()
        for t_off, req, prio in sorted(arrivals, key=lambda a: a[0]):
            delay = t0 + t_off - time.monotonic()
            if delay > 0:
                await asyncio.sleep(delay)
            stream = srv.submit(req, priority=prio,
                                slo_ttft_ms=args.slo_ttft_ms)
            if len(arrivals) == 1:
                async for tok in stream:
                    print(f"[serve] rid={req.rid} tok={tok}")
        await srv.drain()
        return srv.metrics.snapshot(eng)


def main():
    util.enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--score-backend", default=None,
                    help="registered ScoreBackend name (overrides the "
                         "arch's score_mode); see score_backend.list_backends")
    ap.add_argument("--paged", dest="paged", default=None,
                    action="store_true",
                    help="paged block-table cache (default: auto — on for "
                         "families the paged engine supports)")
    ap.add_argument("--dense", dest="paged", action="store_false",
                    help="force the dense [slots, max_len] cache pool")
    ap.add_argument("--block-size", type=int, default=16,
                    help="tokens per cache block (paged mode)")
    ap.add_argument("--hbm-budget", default=None,
                    help="decode-cache HBM budget, e.g. '512MB' or '4GiB'; "
                         "paged mode sizes the block pool from it "
                         "(PagedCacheBudget.max_blocks)")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="chunked-prefill chunk size (default 4x block)")
    ap.add_argument("--no-prefix-sharing", action="store_true",
                    help="disable copy-on-write prompt-prefix block sharing")
    ap.add_argument("--decode-schedule", default="auto",
                    choices=("auto", "stream", "gather"),
                    help="paged decode schedule: 'stream' = block-"
                         "streamed online softmax with used-length early "
                         "exit (tick cost ~ actual length); 'gather' = "
                         "dense logical view (parity oracle); 'auto' "
                         "follows the score planner")
    ap.add_argument("--mesh", default=None, metavar="DxM",
                    help="tensor-parallel serving mesh, e.g. '1x4' "
                         "(data x model axes). Params shard with the "
                         "training rules and the paged pool shards "
                         "head-wise over the model axis; --hbm-budget "
                         "then reads as a PER-DEVICE budget. Needs "
                         "DxM visible devices — on CPU set XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N "
                         "before launching")
    ap.add_argument("--router", action="store_true",
                    help="data-parallel replica routing: one engine per "
                         "data-axis index of --mesh (weights replicated "
                         "per replica, sharded over each replica's model "
                         "axis); requests spread under --router-policy. "
                         "Requires --mesh with data>=1 and paged mode")
    ap.add_argument("--router-policy", default="least_loaded",
                    choices=("least_loaded", "radix_affinity",
                             "round_robin"),
                    help="--router placement policy (see "
                         "serving.router.policies)")
    ap.add_argument("--disaggregate", action="store_true",
                    help="--router: split every replica into a prefill "
                         "worker and a decode worker with paged-block "
                         "handoff — a long prompt costs decode at most "
                         "one chunk of interference per router step")
    ap.add_argument("--prefill-slots", type=int, default=2,
                    help="--disaggregate: concurrent prefill-worker "
                         "slots per replica")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature for all requests "
                         "(0 = greedy; >0 = categorical, seeded)")
    ap.add_argument("--stream", action="store_true",
                    help="serve through the async front end "
                         "(serving.frontend.AsyncEngine): per-tick "
                         "token streaming, SLO-aware admission/"
                         "preemption, metrics snapshot at exit")
    ap.add_argument("--scheduler", default="slo",
                    choices=("slo", "fifo"),
                    help="--stream scheduling policy: 'slo' = priority/"
                         "deadline with evict-to-queue preemption; "
                         "'fifo' = head-of-queue arrival order")
    ap.add_argument("--radix-cache", action="store_true",
                    help="radix-tree prefix cache over historical "
                         "requests (paged mode; pinned refcounted "
                         "blocks, LRU-evicted under pressure)")
    ap.add_argument("--arrival-trace", default=None, metavar="PATH",
                    help="JSON arrival schedule for --stream: a list of "
                         "{'t': sec_offset, 'prompt_len'|'tokens', "
                         "'max_new', 'priority'} objects replayed on "
                         "the wall clock instead of the synthetic "
                         "all-at-once batch")
    ap.add_argument("--slo-ttft-ms", type=float, default=None,
                    help="--stream: attach a time-to-first-token "
                         "deadline (ms from arrival) to every request "
                         "without an explicit one in the trace")
    ap.add_argument("--sim-trace", default=None, metavar="PATH",
                    help="capture the quantized score-path workload "
                         "(shapes + bit sparsity per prefill chunk / "
                         "decode tick) and write it to PATH for replay "
                         "through the CIM macro simulator: "
                         "python -m repro.launch.simulate --trace PATH")
    args = ap.parse_args()

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = reduce_cfg(cfg)
    if args.score_backend:
        score_backend.get_backend(args.score_backend)   # validate early
        cfg = dataclasses.replace(cfg, score_mode=args.score_backend)
    if not cfg.num_heads and cfg.family == "ssm":
        pass                                  # ssm decode is O(1)/token
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    if args.ckpt:
        step = ckpt_lib.latest_step(args.ckpt)
        if step is not None:
            (params, _), _ = ckpt_lib.restore(args.ckpt, step,
                                              (params, None))
            print(f"[serve] restored step {step}")

    hbm = parse_bytes(args.hbm_budget) if args.hbm_budget else None
    mesh = None
    if args.mesh:
        from repro.launch.mesh import parse_mesh
        mesh = parse_mesh(args.mesh)
        print(f"[serve] mesh {args.mesh}: data={mesh.shape['data']} x "
              f"model={mesh.shape['model']} over "
              f"{mesh.devices.size} device(s)")
    engine_kw = dict(max_slots=args.slots, max_len=args.max_len,
                     paged=args.paged, block_size=args.block_size,
                     hbm_bytes=hbm, prefill_chunk=args.prefill_chunk,
                     prefix_sharing=not args.no_prefix_sharing,
                     decode_schedule=args.decode_schedule,
                     radix_cache=args.radix_cache)
    if args.router:
        if mesh is None:
            ap.error("--router requires --mesh DxM (data axis = "
                     "replica count)")
        if args.sim_trace:
            ap.error("--sim-trace captures a single engine; drop "
                     "--router")
        from repro.serving.router import ReplicaRouter
        eng = ReplicaRouter.for_mesh(
            model, params, mesh, policy=args.router_policy,
            disaggregate=args.disaggregate,
            prefill_slots=args.prefill_slots, **engine_kw)
        e0 = eng.engines[0]
        print(f"[serve] router: {len(eng.replicas)} "
              f"{'disaggregated' if args.disaggregate else 'fused'} "
              f"replica(s), policy {args.router_policy!r}; "
              f"{eng.allocator.num_usable} usable blocks fleet-wide "
              f"x {e0.block_size} tokens; chunked prefill "
              f"C={e0.prefill_chunk}")
    else:
        eng = Engine(model, params, mesh=mesh,
                     capture_trace=args.sim_trace is not None,
                     **engine_kw)
    if not args.router and eng.plan is not None:
        budget = kvcache.budget_for(cfg)
        dev = jax.devices()[0]
        limit = (dev.memory_stats() or {}).get("bytes_limit")
        fits = (f"{budget.max_tokens(limit):,} tokens in the {limit:,} B "
                f"of one {dev.device_kind}" if limit else
                f"{dev.device_kind} reports no memory limit")
        print(f"[serve] score backend {eng.plan.backend.name!r} "
              f"({'blockwise' if eng.plan.blockwise else 'quadratic'}); "
              f"cache mode {budget.mode!r}; "
              f"{budget.bytes_per_token} B/token; {fits}")
        print(f"[serve] plan: {eng.plan.reason}")
    if not args.router and eng.paged:
        pb = kvcache.paged_budget_for(cfg, args.block_size)
        print(f"[serve] paged cache: {eng.allocator.num_usable} usable "
              f"blocks x {args.block_size} tokens "
              f"({pb.bytes_per_block} B/block); chunked prefill "
              f"C={eng.prefill_chunk}; prefix sharing "
              f"{'on' if eng.prefix_sharing else 'off'}; decode "
              f"schedule {eng.decode_schedule!r}")
        if mesh is not None:
            print(f"[serve] pool "
                  f"{'head-sharded' if eng.pool_sharded else 'replicated'}"
                  f" on the model axis; "
                  f"{eng.pool_bytes_per_device():,} B/device")
    elif not args.router:
        print("[serve] dense cache pool "
              f"[{args.slots} slots x {args.max_len} tokens]")
    rng = np.random.default_rng(0)

    def _synth_tokens(plen=None):
        plen = plen if plen is not None else int(rng.integers(2, 9))
        return [1] + rng.integers(3, cfg.vocab_size,
                                  max(plen - 1, 1)).tolist()

    arrivals = []                       # (t_offset, Request, priority)
    if args.arrival_trace:
        with open(args.arrival_trace) as f:
            trace = json.load(f)
        for i, ev in enumerate(trace):
            toks = (list(ev["tokens"]) if "tokens" in ev
                    else _synth_tokens(ev.get("prompt_len")))
            r = Request(rid=i, tokens=toks,
                        max_new_tokens=ev.get("max_new", args.max_new),
                        eos_id=None, temperature=args.temperature)
            arrivals.append((float(ev.get("t", 0.0)), r,
                             int(ev.get("priority", 0))))
    else:
        for i in range(args.requests):
            r = Request(rid=i, tokens=_synth_tokens(),
                        max_new_tokens=args.max_new, eos_id=None,
                        temperature=args.temperature)
            if cfg.enc_dec:
                r.tokens = [1]
                r.enc_embeds = frontends.audio_frames(1, 64, cfg.d_model,
                                                      seed=i)
            arrivals.append((0.0, r, 0))
    reqs = [r for _, r, _ in arrivals]

    t0 = time.time()
    if args.stream:
        snap = asyncio.run(_stream_serve(eng, arrivals, args))
    else:
        eng.run(reqs)
    dt = time.time() - t0
    tok = sum(len(r.output) for r in reqs)
    reasons = {}
    for r in reqs:
        reasons[r.finish_reason] = reasons.get(r.finish_reason, 0) + 1
    print(f"[serve] {len(reqs)} reqs, {tok} tokens, {eng.ticks} ticks, "
          f"{dt:.1f}s ({tok/dt:.1f} tok/s); finish reasons: "
          + ", ".join(f"{k}={v}" for k, v in sorted(reasons.items(),
                                                    key=lambda kv: str(kv[0]))))
    if args.stream:
        print("[serve] metrics: " + json.dumps(snap, indent=2,
                                               sort_keys=True))
    if args.sim_trace:
        eng.trace.save(args.sim_trace)
        print(f"[serve] wrote {len(eng.trace.trace.events)} score-trace "
              f"events to {args.sim_trace}; replay with: python -m "
              f"repro.launch.simulate --trace {args.sim_trace}")


if __name__ == "__main__":
    main()
