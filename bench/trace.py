"""A profiler trace of part of the window, and its reduction.

The benchmark's own process holds the chip, so it takes the trace
(``jax.profiler``) around a few seconds of the window, while
``program.Probe`` names each engine call it wraps (``bench.tick``,
``bench.admit``, with the call's index) on the host's timeline.

``extract`` reads the ``.xplane.pb`` into plain tuples: the device's
operations, the device's program (module) executions, the benchmark's
host spans. ``summarize`` needs only those tuples and the run's
records, so a test can give it events made by hand:

  * the window: the benchmark's ``bench.capture`` host span, entered
    once the profiler runs and left before it stops;
  * busy: the union of the device's operation intervals, clipped to the
    window; idle share is 1 - busy / window;
  * each program execution that starts inside a ``bench.tick`` span is
    that tick's program, inside a ``bench.admit`` span a prefill
    chunk's (the engine waits for the device at the end of both calls,
    so their device work runs inside them);
  * the paged kernel's operations are found by ``KERNEL`` in their
    name, and attributed to spans the same way, so each kernel call's
    work comes from the probe's record of that tick or admission.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import shutil

from bench import costs

KERNEL = "paged_attention"        # the paged attention kernel's ops
# the Pallas kernel is the only custom call of the served programs; its
# events are recognised by these marks in their name or statistics
_KERNEL_MARKS = ("tpu_custom_call", "custom-call", "custom_call",
                 "_kernel", "paged")
SPAN_PREFIX = "bench."
CAPTURE = "bench.capture"         # host span of the traced window
TOP = 10


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float
    tick_device_s: list           # device time of each traced tick
    chunk_device_s: float         # all traced chunks' device time
    chunk_count: int
    kernel_device_s: float
    kernel_min_s: float           # least time for the kernel's work
    kernel_bound: str             # which bound dominates kernel_min_s
    step_device_s: float          # ticks + chunks
    step_flops: float
    breakdown: dict


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
        return summarize(extract(path), run)


def _device_planes(pd):
    return [p for p in pd.planes if p.name.startswith("/device:TPU:")
            or p.name.startswith("/device:CPU:")]


def extract(path: str) -> dict:
    """Plain tuples (name, start_ns, duration_ns[, index]) from a
    trace: the first device's operations and programs, and the host
    spans of the benchmark."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops, modules, spans = [], [], []
    dev = _device_planes(pd)
    if dev:
        for line in dev[0].lines:
            if line.name.lower() == "xla ops":
                # an op's name is its HLO instruction; keep the part
                # before " = " (e.g. "%fusion.12")
                ops += [(KERNEL if _is_kernel(e) else e.name.split(" = ")[0],
                         e.start_ns, e.duration_ns) for e in line.events]
            elif line.name.lower() == "xla modules":
                modules += [(e.name, e.start_ns, e.duration_ns)
                            for e in line.events]
    for p in pd.planes:
        if not p.name.startswith("/host:"):
            continue
        for line in p.lines:
            for e in line.events:
                if e.name.startswith(SPAN_PREFIX):
                    idx = next((int(v) for k, v in e.stats
                                if k == "index"), -1)
                    spans.append((e.name, e.start_ns, e.duration_ns, idx))
    return {"ops": ops, "modules": modules, "spans": spans}


def _is_kernel(e) -> bool:
    text = e.name.lower()
    if any(m in text for m in _KERNEL_MARKS):
        return True
    return any(m in str(v).lower() for k, v in e.stats
               if k in ("hlo_category", "long_name", "tf_op")
               for m in _KERNEL_MARKS)


def _union(intervals) -> float:
    total, end = 0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def _owner(spans, t):
    """The span (sorted by start) that contains time ``t``, or None."""
    lo, hi = 0, len(spans)
    while lo < hi:
        mid = (lo + hi) // 2
        if spans[mid][1] <= t:
            lo = mid + 1
        else:
            hi = mid
    if lo and spans[lo - 1][1] <= t <= spans[lo - 1][1] + spans[lo - 1][2]:
        return spans[lo - 1]
    return None


def summarize(ev: dict, run) -> Summary:
    arch, config = run.cell.arch, run.cell.config
    ops = [tuple(o) for o in ev["ops"]]
    mods = [tuple(m) for m in ev["modules"]]
    spans = sorted((tuple(x) for x in ev["spans"] if x[0] != CAPTURE),
                   key=lambda x: x[1])
    capture = [x for x in ev["spans"] if x[0] == CAPTURE]
    busy_iv = [(o[1], o[1] + o[2]) for o in ops] or \
        [(m[1], m[1] + m[2]) for m in mods]
    # the window is the capture's own span, so idle time at its edges
    # counts; with no span or no device event there is no window
    t0, t1 = (capture[0][1], capture[0][1] + capture[0][2]) if capture \
        else (0, 0)
    window_ns = t1 - t0 if busy_iv else 0
    busy_iv = [(max(a, t0), min(b, t1)) for a, b in busy_iv
               if b > t0 and a < t1]
    busy_ns = _union(busy_iv)

    tick_dev: dict[int, int] = {}
    admit_dev: dict[int, int] = {}
    for name, start, dur in mods:
        sp = _owner(spans, start)
        if sp is None:
            continue
        if sp[0] == "bench.tick":
            tick_dev[sp[3]] = tick_dev.get(sp[3], 0) + dur
        elif sp[0] == "bench.admit":
            admit_dev[sp[3]] = admit_dev.get(sp[3], 0) + dur

    peaks = run.peaks
    kern_ns, kern_min, kern_mem, kern_cmp = 0, 0.0, 0.0, 0.0
    for name, start, dur in ops:
        if name != KERNEL:
            continue
        sp = _owner(spans, start)
        if sp is not None and sp[3] in (
                tick_dev if sp[0] == "bench.tick" else admit_dev):
            kern_ns += dur
    bs = run.block_size
    kern_work = []                     # (flops, bytes) of traced calls
    step_flops = 0.0
    for i in tick_dev:
        ctx = run.ticks[i].contexts
        f, _ = arch.decode_tick(config, ctx)
        step_flops += f
        kern_work += arch.kernel_calls(config, ctx, 1, bs)
    chunk_count = 0
    for j in admit_dev:
        a = run.admissions[j]
        n_prompt = sum(n for _, n in a.chunks) + (a.chunks[0][0]
                                                 if a.chunks else 0)
        for c0, n in a.chunks:
            f, _ = arch.prefill_chunk(config, c0, n, c0 + n == n_prompt)
            step_flops += f
            kern_work += arch.kernel_calls(config, [c0 + n], n, bs)
            chunk_count += 1
    bound = "none"
    if peaks is not None and kern_work:
        for f, b in kern_work:
            t, which = costs.min_seconds(f, b, peaks)
            kern_min += t
            if which == "memory":
                kern_mem += t
            else:
                kern_cmp += t
        bound = "memory" if kern_mem >= kern_cmp else "compute"

    # device time by operation name, and idle gaps by host activity
    by_op: dict[str, int] = {}
    for name, _, dur in ops:
        by_op[name] = by_op.get(name, 0) + dur
    idle: dict[str, int] = {}
    gaps, prev_end = [], t0
    for a, b in sorted(busy_iv):
        if a > prev_end:
            gaps.append((prev_end, a))
        prev_end = max(prev_end, b)
    if t1 > prev_end:
        gaps.append((prev_end, t1))
    starts = [x[1] for x in spans]
    for a, b in gaps:
        # split each idle gap by the host span (if any) covering it
        i = max(bisect.bisect_right(starts, a) - 1, 0)
        covered = 0
        while i < len(spans) and spans[i][1] < b:
            lo, hi = max(a, spans[i][1]), min(b, spans[i][1] + spans[i][2])
            if hi > lo:
                label = "host in " + spans[i][0][len(SPAN_PREFIX):]
                idle[label] = idle.get(label, 0) + hi - lo
                covered += hi - lo
            i += 1
        if b - a > covered:
            idle["host between engine calls"] = \
                idle.get("host between engine calls", 0) + b - a - covered

    def top(d):
        return [[k, v / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]

    tick_s = [v / 1e9 for _, v in sorted(tick_dev.items())]
    chunk_s = sum(admit_dev.values()) / 1e9
    return Summary(
        window_s=window_ns / 1e9 or None, busy_s=busy_ns / 1e9,
        tick_device_s=tick_s, chunk_device_s=chunk_s,
        chunk_count=chunk_count, kernel_device_s=kern_ns / 1e9,
        kernel_min_s=kern_min, kernel_bound=bound,
        step_device_s=sum(tick_s) + chunk_s, step_flops=step_flops,
        breakdown={"device_ops": top(by_op), "idle_gaps": top(idle)})
