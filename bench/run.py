"""Run one cell of the serving benchmark once, on the chip it runs on.

    python3 bench/run.py --workload qwen14b.decode --seed 7 \
        --seconds 45 --trace 0

Set-up makes the weights from the seed, builds the engine as
``launch/serve.py`` does, compiles every shape the window uses, and
sends the cell's traffic for the mix's ramp; then the window measures
for ``--seconds``. After it, the served tokens of a sample of finished
requests are checked against the plain float32 reference. The last line
of standard output is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``); with ``--trace 1`` its metrics
are the cell's per-layer metrics, read from a profiler trace of part of
the window. Exits non-zero without that line when JAX finds no TPU or
fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

T0 = time.monotonic()          # set-up is timed from the process start

import argparse  # noqa: E402
import asyncio  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import check, costs, generate, spec  # noqa: E402
from bench import program as prog  # noqa: E402
from bench.stats import percentile  # noqa: E402

clock = prog.clock
CACHE_DIR = ".jax_cache"       # JAX's compilation cache, in the checkout
RUN_DIR = ".bench_run"         # traces, in the checkout
TRACE_SECONDS = 4.0


@dataclasses.dataclass
class Sent:
    """One request as the benchmark sent and received it."""
    rid: int
    prompt: list
    max_new: int
    due: float                  # open loop: when it was due; closed: sent
    sent: float | None = None
    times: list = dataclasses.field(default_factory=list)
    output: list = dataclasses.field(default_factory=list)
    finish: str | None = None
    failed: bool = False


@dataclasses.dataclass
class Run:
    """What a run recorded; per-layer metric readers take this."""
    cell: spec.Cell
    peaks: dict | None
    max_slots: int
    block_size: int
    window: tuple               # (open, close) on ``clock``
    sent: list
    ticks: list                 # program.Tick
    admissions: list            # program.Admission
    trace: object = None        # trace.Summary of the traced part

    def in_window(self) -> list:
        w0, w1 = self.window
        return [s for s in self.sent if w0 <= s.due < w1]


async def _drive(engine, probe, cell, planned, seconds, tracer) -> tuple:
    """Send the traffic, open and close the window; returns
    (sent, window, lateness)."""
    from repro.serving.frontend import AsyncEngine
    mix = cell.traffic
    srv = AsyncEngine(engine)
    srv.start()
    sent: list[Sent] = []
    tasks = []
    lateness = []
    origin = clock() + 0.05
    w0 = origin + mix["ramp_s"]
    w1 = w0 + seconds

    async def consume(stream, s: Sent):
        async for tok in stream:
            s.times.append(clock())
        s.output = list(stream.request.output)
        s.finish = stream.request.finish_reason

    def submit(p, due, tokens):
        """Send one request; its consuming task, or None if refused."""
        s = Sent(rid=p.rid, prompt=tokens, max_new=p.max_new, due=due)
        sent.append(s)
        s.sent = clock()
        try:
            stream = srv.submit(prog.request(p.rid, tokens, p.max_new))
        except ValueError as e:         # refused: counts as failed
            print(f"[bench] request {p.rid} refused: {e}",
                  file=sys.stderr)
            s.failed = True
            return None
        tasks.append(asyncio.ensure_future(consume(stream, s)))
        return tasks[-1]

    async def traced():
        await asyncio.sleep(max(0.0, w0 + (seconds - TRACE_SECONDS) / 2
                                - clock()))
        await asyncio.to_thread(tracer.start)
        with tracer.span():
            await asyncio.sleep(TRACE_SECONDS)
        await asyncio.to_thread(tracer.stop)

    trace_task = asyncio.ensure_future(traced()) if tracer else None
    if mix["loop"] == "open":
        prompts = [p.tokens() for p in planned]
        for p, toks in zip(planned, prompts, strict=True):
            due = origin + p.due
            if due >= w1:
                break
            if due > clock():
                await asyncio.sleep(due - clock())
            submit(p, due, toks)
            lateness.append(clock() - due)
    else:
        queues = {}
        for p in planned:
            queues.setdefault(p.client, []).append(p)

        async def client(mine):
            for p in mine:
                if clock() >= w1:
                    return
                task = submit(p, clock(), p.tokens())
                if task is None:
                    return
                await task
        clients = [asyncio.ensure_future(client(m)) for m in
                   queues.values()]
    if clock() < w1:
        await asyncio.sleep(w1 - clock())
    if trace_task is not None:
        await trace_task
    probe.stop.set()
    srv._stop.set()
    await asyncio.to_thread(srv._thread.join)
    if mix["loop"] == "closed":
        for c in clients:
            c.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)
    err = srv._error
    if err is not None and not isinstance(err, prog.WindowClosed):
        raise err
    return sent, (w0, w1), lateness


def occupancy(run: Run, ramp_s: float, step: float = 5.0) -> list:
    """Mean active slots per tick in each ``step`` seconds from the
    start of the traffic's ramp to the window's close: level from the
    window's open when the ramp was long enough."""
    w0, w1 = run.window
    t0 = w0 - ramp_s
    out = []
    while t0 < w1 - 1e-9:
        n = [len(t.contexts) for t in run.ticks if t0 <= t.start < t0 + step]
        out.append(round(sum(n) / len(n), 2) if n else 0)
        t0 += step
    return out


def end_to_end(run: Run, setup_s: float) -> dict:
    """The cell's end-to-end metrics from one run's records."""
    w0, w1 = run.window
    seconds = w1 - w0
    due = run.in_window()
    ttft = []
    for s in due:
        first = s.times[0] if s.times else w1
        ttft.append(min(first, w1) - s.due)
    gaps, tokens = [], 0
    for s in run.sent:
        for a, b in zip(s.times, s.times[1:], strict=False):
            if w0 <= b < w1:
                gaps.append(b - a)
        tokens += sum(w0 <= t < w1 for t in s.times)
    vals = {
        "ttft_p90_ms": _ms(percentile(ttft, 90)),
        "itl_p50_ms": _ms(percentile(gaps, 50)),
        "itl_p99_ms": _ms(percentile(gaps, 99)),
        "output_tok_s": tokens / seconds,
        "setup_s": setup_s,
    }
    print(f"[bench] window {seconds:.3f} s: {len(due)} requests due, "
          f"{len(ttft)} first-token waits, {len(gaps)} gaps, {tokens} "
          f"tokens", file=sys.stderr)
    return vals


def _ms(x):
    return None if x is None else x * 1e3


def _device_info(devices, chips: int) -> dict:
    used = devices[:chips]
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                for d in used)
    return {"platform": used[0].platform, "kind": used[0].device_kind,
            "count": len(used), "memory_peak_bytes": int(peak)}


def open_chip(cell, root: str, require_chip: bool = True):
    """Import the program and JAX; (devices, peaks), or None when the
    checkout has no program, JAX finds no TPU or fewer chips than the
    cell asks for, or the peaks table does not know the device. With a
    chip, JAX's compilation cache goes inside the checkout.
    ``require_chip=False`` (tests) skips the look and the cache."""
    try:
        prog.import_program(root)
    except FileNotFoundError as e:
        print(f"bench: {e}", file=sys.stderr)
        return None
    import jax
    devices = jax.devices()
    if not require_chip:
        return devices, None
    if devices[0].platform != "tpu":
        print(f"bench: JAX finds no TPU (platform "
              f"{devices[0].platform!r}); nothing measured",
              file=sys.stderr)
        return None
    if len(devices) < cell.chips:
        print(f"bench: cell {cell.name} needs {cell.chips} chips, JAX "
              f"finds {len(devices)}", file=sys.stderr)
        return None
    try:
        peaks = costs.load_peaks(devices[0].device_kind)
    except KeyError as e:
        print(f"bench: {e}", file=sys.stderr)
        return None
    jax.config.update("jax_compilation_cache_dir",
                      os.path.join(root, CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return devices, peaks


def make_weights(cell, seed: int):
    import jax
    params = cell.arch.program_params(seed, cell.config)
    jax.block_until_ready(params)
    return params


def serve(cell, params, seed: int, seconds: float, *, peaks=None,
          trace_dir: str | None = None, faults=None) -> Run:
    """Build the engine over ``params``, warm it up, send the cell's
    traffic and measure one window; the engine is gone on return."""
    engine = prog.build_engine(cell, params)
    t = clock()
    prog.warm_up(engine)
    t_warm = clock() - t
    if faults is not None:
        faults(engine)
    probe = prog.Probe(engine)
    planned = generate.plan(cell.traffic, seed,
                            cell.arch.vocab(cell.config), seconds)
    tracer = None
    if trace_dir is not None:
        from bench import trace
        tracer = trace.Capture(trace_dir)
    compiles = check.CompileCounter()
    sent, window, lateness = asyncio.run(_drive(
        engine, probe, cell, planned, seconds, tracer))
    run = Run(cell=cell, peaks=peaks,
              max_slots=engine.max_slots, block_size=engine.block_size,
              window=window, sent=sent, ticks=probe.ticks,
              admissions=probe.admissions)
    print(f"[bench] engine warm-up {t_warm:.3f} s, ramp "
          f"{cell.traffic['ramp_s']} s; {compiles.stop()} compilations "
          f"inside the window", file=sys.stderr)
    print(f"[bench] active slots by 5 s from the ramp's start (window "
          f"opens after {cell.traffic['ramp_s']} s): "
          f"{occupancy(run, cell.traffic['ramp_s'])}", file=sys.stderr)
    if lateness:
        print(f"[bench] generator lateness: p50 "
              f"{_ms(percentile(lateness, 50)):.3f} ms, p99 "
              f"{_ms(percentile(lateness, 99)):.3f} ms, max "
              f"{_ms(max(lateness)):.3f} ms over {len(lateness)} sends",
              file=sys.stderr)
    if tracer is not None:
        run.trace = t = tracer.reduce(run)
        shutil.rmtree(trace_dir, ignore_errors=True)
        print(f"[trace] {t.window_s} s traced: {len(t.tick_device_s)} "
              f"ticks, {t.chunk_count} prefill chunks" + "".join(
                  f"; kernel {name} {k.device_s} s on the device against "
                  f"{k.min_s} s of least time, {k.bound}-bound"
                  for name, k in t.kernels.items()), file=sys.stderr)
    return run


def main(argv=None, *, root: str = ROOT, require_chip: bool = True,
         faults=None) -> int:
    """One run. ``require_chip=False`` and ``faults`` exist for the
    tests: the first skips the look for a TPU (and the compilation
    cache), the second breaks the engine under the run
    (``faults(engine)``)."""
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = spec.load_cell(root, args.workload)
    chip = open_chip(cell, root, require_chip)
    if chip is None:
        return 1
    devices, peaks = chip
    t = clock()
    params = make_weights(cell, args.seed)
    print(f"[bench] weights from seed {args.seed} in {clock() - t:.3f} s",
          file=sys.stderr)
    run = serve(cell, params, args.seed, args.seconds, peaks=peaks,
                trace_dir=(os.path.join(root, RUN_DIR, "trace")
                           if args.trace else None), faults=faults)
    del params
    setup_s = run.window[0] - T0
    print(f"[bench] set-up {setup_s:.3f} s", file=sys.stderr)
    device = _device_info(devices, cell.chips)
    if args.trace:
        if run.trace.window_s:
            device["busy_s"] = run.trace.busy_s
            device["window_s"] = run.trace.window_s
        metrics = {}
        for m in cell.per_layer:
            v = spec.metric_reader(root, m["name"])(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        vals = end_to_end(run, setup_s)
        metrics = {m["name"]: {"value": vals[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end
                   if vals.get(m["name"]) is not None}

    gc.collect()
    verdict = check.judge(cell, args.seed, run)
    due = run.in_window()
    out = {"correct": verdict["correct"], "attempted": len(due),
           "failed": sum(s.failed for s in due),
           "metrics": metrics, "device": device}
    if args.trace:
        out["breakdown"] = run.trace.breakdown
    out["limits"] = verdict["limits"]
    for line in verdict["lines"]:
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
