"""The serving program's own names in a trace, and their reduction.

``trace.py`` finds the engine's calls by the benchmark's spans around
them (``bench.tick``, ``bench.admit``) and the paged kernel by marks in
its op text. The program names these itself:

  * host spans (``SPANS``), one per phase: the pump's
    ``pump.schedule`` and ``pump.wait``; ``engine.tick`` with its
    ``tick.upload``, ``tick.step``, ``tick.sample`` and ``tick.emit``;
    ``engine.admit`` with ``admit.reserve``, ``admit.chunk`` and
    ``admit.sample``;
  * device programs ``jit_decode_tick(...)`` and
    ``jit_prefill_chunk(...)``, and the Pallas kernel's op
    ``%paged_attention.<n>``.

The names are the benchmark's, here, as ``trace.py``'s are: a program
that renames or drops a span leaves the reading of it missing, and the
program's own list (``repro.serving.spans.NAMES``) is checked against
``SPANS`` by a test. ``extract`` adds the program's spans and the ops'
own names to ``trace.extract``'s tuples; ``reduce`` needs only those
tuples, so a test can give it events made by hand. A trace of a program
without these names raises nothing: its readings are None, and
``missing`` names the spans the reduction found none of.

Read from them:

  * ``tick_host_ms`` / ``admit_host_ms``: the device's idle time inside
    each ``engine.tick`` / ``engine.admit`` span that lies in the
    window, mean per span (an ``engine.admit`` that ran no
    ``admit.chunk`` admitted nothing, and is not counted);
  * ``tick_programs``: device programs (module executions) that start
    inside each such ``engine.tick``, mean per tick;
  * ``idle_by_span``: the window's idle time by the innermost program
    span over it, or ``OUTSIDE``; the full set sums to window - busy,
    and the top ``trace.TOP`` are kept.

And from the probe's records, as ``bench/metrics`` readers take them:

  * ``kv_live_block_share``: the active slots' live kv blocks over the
    block-table cells the decode tick's kernel is launched over.

``run.py`` does not call this module yet (``PERF.md``, Open questions).
"""
from __future__ import annotations

import bisect
import math

from bench import trace
from bench.program import engine_options

TICK, ADMIT, ADMIT_CHUNK = "engine.tick", "engine.admit", "admit.chunk"
SPANS = ("pump.schedule", "pump.wait", TICK, "tick.upload", "tick.step",
         "tick.sample", "tick.emit", ADMIT, "admit.reserve", ADMIT_CHUNK,
         "admit.sample")
TICK_PROGRAM, CHUNK_PROGRAM = "jit_decode_tick", "jit_prefill_chunk"
KERNEL_OP = "%paged_attention"
OUTSIDE = "outside program spans"
READINGS = ("tick_host_ms", "admit_host_ms", "tick_programs",
            "idle_by_span")


def kv_live_block_share(run) -> float | None:
    """Percent of the block-table cells a decode tick's paged kernel is
    launched over (every slot's row, ``max_len / block_size`` columns)
    that held a live block of an active slot, over the ticks that
    started in the window."""
    w0, w1 = run.window
    ticks = [t for t in run.ticks if w0 <= t.start < w1]
    if not ticks:
        return None
    bs = run.block_size
    width = -(-engine_options(run.cell.config, run.cell.traffic)["max_len"]
              // bs)
    live = sum(math.ceil(c / bs) for t in ticks for c in t.contexts)
    return 100.0 * live / (len(ticks) * run.max_slots * width)


def extract(path: str) -> dict:
    """``trace.extract``'s tuples, plus ``program``: the program's host
    spans (name, start_ns, duration_ns, {stat: value}), and
    ``op_names``: the device's ops (name, start_ns, duration_ns) under
    their own names, cut before " = "."""
    from jax.profiler import ProfileData
    ev = trace.extract(path)
    pd = ProfileData.from_file(path)
    names = set(SPANS)
    ops, program = [], []
    dev = trace._device_planes(pd)
    if dev:
        for line in dev[0].lines:
            if line.name.lower() == "xla ops":
                ops += [(e.name.split(" = ")[0], e.start_ns, e.duration_ns)
                        for e in line.events]
    for p in pd.planes:
        if not p.name.startswith("/host:"):
            continue
        for line in p.lines:
            program += [(e.name, e.start_ns, e.duration_ns, dict(e.stats))
                        for e in line.events if e.name in names]
    ev["program"] = sorted(program, key=lambda x: x[1])
    ev["op_names"] = ops
    return ev


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


def _window(ev: dict):
    """(start, end) of the traced window, or None when the trace has no
    capture span or no device op."""
    cap = [x for x in ev["spans"] if x[0] == trace.CAPTURE]
    if not cap or not ev["ops"]:
        return None
    return cap[0][1], cap[0][1] + cap[0][2]


def idle_by_span(ev: dict) -> dict:
    """Device idle ns in the window by innermost program span (all of
    them, ``OUTSIDE`` included); empty when the trace has no window."""
    window = _window(ev)
    if window is None:
        return {}
    t0, t1 = window
    busy = _Busy((max(s, t0), min(s + d, t1)) for _, s, d in ev["ops"]
                 if s + d > t0 and s < t1)
    idle: dict[str, int] = {}
    for a, b, name in _innermost(ev["program"], t0, t1):
        gap = b - a - busy.inside(a, b)
        if gap:
            idle[name or OUTSIDE] = idle.get(name or OUTSIDE, 0) + gap
    return idle


def reduce(ev: dict) -> dict:
    """The program's spans read into ``tick_host_ms``,
    ``admit_host_ms``, ``tick_programs`` and ``idle_by_span`` (each
    None when there is nothing to read), with the number of traced
    ticks and admissions they rest on, and ``missing``: the spans they
    key on that the trace holds none of."""
    seen = {x[0] for x in ev["program"]}
    out = dict.fromkeys(READINGS)
    out.update(traced_ticks=0, traced_admissions=0, missing=[
        n for n in (TICK, ADMIT, ADMIT_CHUNK) if n not in seen])
    window = _window(ev)
    if window is None:
        return out
    t0, t1 = window
    busy = _Busy((s, s + d) for _, s, d in ev["ops"])
    starts = sorted(m[1] for m in ev["modules"])

    def inside(name):
        return [(s, s + d) for n, s, d, _ in ev["program"]
                if n == name and t0 <= s and s + d <= t1]
    ticks = inside(TICK)
    # an admission span that ran no chunk admitted nothing: the request
    # did not fit and stays queued
    chunks = sorted(s for n, s, _, _ in ev["program"] if n == ADMIT_CHUNK)
    admits = [(a, b) for a, b in inside(ADMIT) if bisect.bisect_right(
        chunks, b) > bisect.bisect_left(chunks, a)]
    out.update(traced_ticks=len(ticks), traced_admissions=len(admits))
    for key, calls in (("tick_host_ms", ticks), ("admit_host_ms", admits)):
        if calls:
            out[key] = sum(b - a - busy.inside(a, b) for a, b in calls) \
                / len(calls) / 1e6
    if ticks:
        out["tick_programs"] = sum(
            bisect.bisect_right(starts, b) - bisect.bisect_left(starts, a)
            for a, b in ticks) / len(ticks)
    idle = idle_by_span(ev)
    if ev["program"] and idle:
        out["idle_by_span"] = [
            [k, v / 1e9] for k, v in
            sorted(idle.items(), key=lambda kv: -kv[1])[:trace.TOP]]
    return out
