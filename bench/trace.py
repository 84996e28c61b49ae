"""A profiler trace of part of the window, and its reduction.

The benchmark's own process holds the chip, so it takes the trace
(``jax.profiler``) around a few seconds of the window, inside its one
host span ``bench.capture``. Everything else the reduction reads is
named by the serving program:

  * host spans, one per phase, with their attributes (``SPANS``, the
    program's list ``repro.serving.spans.NAMES``, checked equal by a
    test; and any span the cell's architecture module names in its
    ``SPANS``): ``engine.tick`` [``index``, ``active``, ``kv_blocks``],
    ``engine.admit`` [``rid``] with its ``admit.chunk`` [``rid``,
    ``c0``], and their phases;
  * device programs by module name (``jit_decode_tick(...)``,
    ``jit_prefill_chunk(...)``);
  * device ops under their HLO names; a Pallas kernel's ops carry the
    kernel's ``name=``: ``%paged_attention.<n>`` is the kernel
    ``paged_attention``.

``extract`` reads the ``.xplane.pb`` once into plain tuples;
``summarize`` needs only those tuples and the run's records, so a test
can give it events made by hand:

  * the window: the ``bench.capture`` span; busy is the union of the
    device's op intervals clipped to it, idle share 1 - busy / window;
  * ``spans``: every kept program span that lies in the window, with
    its attributes, the device's busy time inside it, and the programs
    that start inside it and their device time;
  * the engine's calls: each ``engine.tick`` span in the window, and
    each ``engine.admit`` span in it that ran an ``admit.chunk`` (one
    that ran none admitted nothing). The engine waits for the device at
    the end of both, so the programs that start inside a call are its
    work. A tick finds the probe's record of its contexts by the
    engine's tick index (the span's ``index``); an admission finds the
    record of its chunks by ``rid`` and the engine's tick count, which
    is the ``index`` of the next tick;
  * ``kernels``: for each kernel the architecture module's
    ``kernel_calls`` names, its ops' device time inside those calls,
    and the least time of those calls' work (``roofline`` reads it);
  * ``idle_by_span``: the window's idle time by the innermost program
    span over it, or ``OUTSIDE``.

A trace with no capture span or no device op has no window and reads
nothing; a program span that is renamed or dropped leaves the metrics
that key on it with nothing to read.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
import shutil

from bench import costs

CAPTURE = "bench.capture"         # host span of the traced window
TICK, ADMIT, ADMIT_CHUNK = "engine.tick", "engine.admit", "admit.chunk"
SPANS = ("pump.schedule", "pump.wait", TICK, "tick.upload", "tick.step",
         "tick.sample", "tick.emit", ADMIT, "admit.reserve", ADMIT_CHUNK,
         "admit.sample")
OUTSIDE = "outside program spans"
TOP = 10


@dataclasses.dataclass
class Span:
    name: str
    start_s: float                # from the window's open
    dur_s: float
    attrs: dict
    busy_s: float                 # device busy time inside the span
    programs: int                 # device programs that start inside
    program_s: float              # their device time


@dataclasses.dataclass
class Kernel:
    device_s: float               # its ops' time in the traced calls
    min_s: float                  # least time for those calls' work
    bound: str                    # which bound dominates min_s


@dataclasses.dataclass
class Summary:
    window_s: float | None
    busy_s: float
    spans: list                   # Span, by start
    kernels: dict                 # kernel name -> Kernel
    idle_by_span: dict            # span name -> idle seconds
    tick_device_s: list           # device time of each traced tick
    chunk_device_s: float         # all traced chunks' device time
    chunk_count: int
    step_device_s: float          # ticks + chunks
    step_flops: float
    breakdown: dict


def roofline(run, kernel: str) -> float | None:
    """Percent of its roofline that ``kernel`` reached over the traced
    engine calls: the least time of the calls' work over its device
    time; None when the trace holds no call of it."""
    k = run.trace.kernels.get(kernel) if run.trace else None
    if k is None or not k.device_s or not k.min_s:
        return None
    return 100.0 * k.min_s / k.device_s


def admissions(spans) -> list:
    """The ``engine.admit`` spans in which an ``admit.chunk`` starts."""
    chunks = sorted(s.start_s for s in spans if s.name == ADMIT_CHUNK)
    return [s for s in spans if s.name == ADMIT and bisect.bisect_right(
        chunks, s.start_s + s.dur_s) > bisect.bisect_left(chunks, s.start_s)]


class Capture:
    def __init__(self, outdir: str):
        self.outdir = outdir

    def start(self):
        import jax
        shutil.rmtree(self.outdir, ignore_errors=True)
        os.makedirs(self.outdir, exist_ok=True)
        jax.profiler.start_trace(self.outdir)

    def span(self):
        """The traced window on the host's timeline: entered once the
        profiler runs, left before it stops."""
        import jax
        return jax.profiler.TraceAnnotation(CAPTURE)

    def stop(self):
        import jax
        jax.profiler.stop_trace()

    def reduce(self, run) -> Summary:
        path = sorted(glob.glob(os.path.join(
            self.outdir, "**", "*.xplane.pb"), recursive=True))[-1]
        return summarize(extract(path, SPANS + run.cell.arch.SPANS), run)


def _device_planes(pd):
    return [p for p in pd.planes if p.name.startswith("/device:TPU:")
            or p.name.startswith("/device:CPU:")]


def extract(path: str, names=SPANS) -> dict:
    """Plain tuples from a trace: the first device's ops and programs
    (name, start_ns, duration_ns), an op under its HLO name cut before
    " = " (``%fusion.12``); and the host spans (name, start_ns,
    duration_ns, {attribute: value}) named in ``names``, and the
    capture span."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    keep = {*names, CAPTURE}
    ops, modules, spans = [], [], []
    dev = _device_planes(pd)
    if dev:
        for line in dev[0].lines:
            if line.name.lower() == "xla ops":
                ops += [(e.name.split(" = ")[0], e.start_ns, e.duration_ns)
                        for e in line.events]
            elif line.name.lower() == "xla modules":
                modules += [(e.name, e.start_ns, e.duration_ns)
                            for e in line.events]
    for p in pd.planes:
        if p.name.startswith("/host:"):
            for line in p.lines:
                spans += [(e.name, e.start_ns, e.duration_ns, dict(e.stats))
                          for e in line.events if e.name in keep]
    return {"ops": ops, "modules": modules, "spans": spans}


def kernel_of(op: str) -> str:
    """The kernel an op belongs to, by its HLO name:
    ``%paged_attention.9`` -> ``paged_attention``."""
    return re.sub(r"\.\d+$", "", op.lstrip("%"))


class _Busy:
    """Busy time inside any interval, from the union of busy ones."""

    def __init__(self, intervals):
        self.starts, self.ends, self.before = [], [], [0]
        for a, b in sorted(intervals):
            if self.ends and a <= self.ends[-1]:
                self.ends[-1] = max(self.ends[-1], b)
                continue
            if self.starts:
                self.before.append(self.before[-1] + self.ends[-1]
                                   - self.starts[-1])
            self.starts.append(a)
            self.ends.append(b)

    def _upto(self, t) -> int:
        i = bisect.bisect_right(self.starts, t) - 1
        if i < 0:
            return 0
        return self.before[i] + min(t, self.ends[i]) - self.starts[i]

    def inside(self, a, b) -> int:
        return self._upto(b) - self._upto(a)


def _innermost(spans, t0, t1) -> list:
    """[t0, t1) cut into (a, b, name): the innermost span over each
    piece, None where there is none. The spans nest, as one thread's
    do."""
    out, stack, cur = [], [], t0

    def upto(t, name):
        nonlocal cur
        t = min(max(t, t0), t1)
        if t > cur:
            out.append((cur, t, name))
            cur = t

    def pop():
        name, end = stack.pop()
        upto(end, name)

    for name, s, d, *_ in sorted(spans, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][1] <= s:
            pop()
        upto(s, stack[-1][0] if stack else None)
        stack.append((name, s + d))
    while stack:
        pop()
    upto(t1, None)
    return out


def _top(ns: dict) -> list:
    """The largest ``TOP`` entries, in seconds."""
    return [[k, v / 1e9] for k, v in
            sorted(ns.items(), key=lambda kv: -kv[1])[:TOP]]


def summarize(ev: dict, run) -> Summary:
    arch, config = run.cell.arch, run.cell.config
    ops = [tuple(o) for o in ev["ops"]]
    mods = sorted((tuple(m) for m in ev["modules"]), key=lambda m: m[1])
    cap = [x for x in ev["spans"] if x[0] == CAPTURE]
    program = sorted((tuple(x) for x in ev["spans"] if x[0] != CAPTURE),
                     key=lambda x: x[1])
    if not cap or not ops:
        return Summary(window_s=None, busy_s=0.0, spans=[], kernels={},
                       idle_by_span={}, tick_device_s=[], chunk_device_s=0.0,
                       chunk_count=0, step_device_s=0.0, step_flops=0.0,
                       breakdown={"device_ops": [], "idle_gaps": []})
    t0, t1 = cap[0][1], cap[0][1] + cap[0][2]
    busy = _Busy((max(s, t0), min(s + d, t1)) for _, s, d in ops
                 if s + d > t0 and s < t1)
    starts = [m[1] for m in mods]
    before = [0]                        # programs' device time so far
    for m in mods:
        before.append(before[-1] + m[2])

    def span(name, s, d, attrs) -> Span:
        i = bisect.bisect_left(starts, s)
        j = bisect.bisect_right(starts, s + d)
        return Span(name, (s - t0) / 1e9, d / 1e9, attrs,
                    busy.inside(s, s + d) / 1e9, j - i,
                    (before[j] - before[i]) / 1e9)
    inside = [x for x in program if t0 <= x[1] and x[1] + x[2] <= t1]
    spans = [span(*x) for x in inside]

    # the engine's calls in the window, and the probe's records of them
    raw = {id(s): x for x, s in zip(inside, spans, strict=True)}
    index = [(x[1], x[3]["index"]) for x in program if x[0] == TICK]
    ticks = [s for s in spans if s.name == TICK]
    # an admission's record is found by the ticks around it: with none
    # in the trace, none is
    admits = admissions(spans) if index else []
    calls = sorted((raw[id(s)][1], raw[id(s)][1] + raw[id(s)][2])
                   for s in ticks + admits)
    tick_rec = {t.index: t for t in run.ticks if t.contexts}
    admit_rec = {(a.rid, a.tick): a for a in run.admissions}
    bs = run.block_size
    step_flops, work = 0.0, []          # work: (kernel, FLOPs, bytes)
    for s in ticks:
        ctx = tick_rec[s.attrs["index"]].contexts
        step_flops += arch.decode_tick(config, ctx)[0]
        work += arch.kernel_calls(config, ctx, 1, bs)
    chunk_count = 0
    for s in admits:
        # the engine's tick count as it admitted: the next tick's index
        k = bisect.bisect_left(index, (raw[id(s)][1],))
        n_ticks = index[k][1] if k < len(index) else index[-1][1] + 1
        a = admit_rec[(s.attrs["rid"], n_ticks)]
        n_prompt = sum(n for _, n in a.chunks) + (a.chunks[0][0]
                                                 if a.chunks else 0)
        for c0, n in a.chunks:
            step_flops += arch.prefill_chunk(config, c0, n,
                                             c0 + n == n_prompt)[0]
            work += arch.kernel_calls(config, [c0 + n], n, bs)
            chunk_count += 1

    least: dict[str, dict] = {}
    for name, f, b in work:
        t, which = costs.min_seconds(f, b, run.peaks)
        by = least.setdefault(name, {"memory": 0.0, "compute": 0.0})
        by[which] += t
    kernels = {name: Kernel(0.0, by["memory"] + by["compute"],
                            max(by, key=by.get))
               for name, by in least.items()}
    call_starts = [a for a, _ in calls]
    kernel_ns = dict.fromkeys(kernels, 0)
    for op, s, d in ops:
        name = kernel_of(op)
        i = bisect.bisect_right(call_starts, s) - 1
        if name in kernel_ns and i >= 0 and s <= calls[i][1]:
            kernel_ns[name] += d
    for name, ns in kernel_ns.items():
        kernels[name].device_s = ns / 1e9

    idle: dict[str, int] = {}           # ns
    for a, b, name in _innermost(program, t0, t1):
        gap = b - a - busy.inside(a, b)
        if gap:
            idle[name or OUTSIDE] = idle.get(name or OUTSIDE, 0) + gap
    by_op: dict[str, int] = {}
    for name, _, d in ops:
        by_op[name] = by_op.get(name, 0) + d

    tick_s = [s.program_s for s in ticks]
    chunk_s = sum(s.program_s for s in admits)
    return Summary(
        window_s=(t1 - t0) / 1e9, busy_s=busy.inside(t0, t1) / 1e9,
        spans=spans, kernels=kernels,
        idle_by_span={k: v / 1e9 for k, v in idle.items()},
        tick_device_s=tick_s, chunk_device_s=chunk_s,
        chunk_count=chunk_count, step_device_s=sum(tick_s) + chunk_s,
        step_flops=step_flops,
        breakdown={"device_ops": _top(by_op), "idle_gaps": _top(idle)})
