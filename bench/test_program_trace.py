"""The reduction of the program's own names in a trace (``trace.py``):
on events made by hand, on a tiny run of the engine, and on a trace
recorded on one TPU v5e."""
from __future__ import annotations

import glob
import json
import math
import os
import types

import pytest

from bench import run, spec, trace
from bench.test_trace import RECORDED, _recorded

MS = 1_000_000
REPO = os.path.dirname(spec.BENCH_DIR)
HOST = ("tick_host_ms", "admit_host_ms", "tick_programs")


def _ev():
    """One admission of two chunks inside a scheduler step, one that
    fails, one tick, one wait, in a 100 ms window; device work as its
    programs."""
    program = [
        ("pump.schedule", 0, 30 * MS, {}),
        ("engine.admit", 2 * MS, 26 * MS, {"rid": 7}),
        ("admit.reserve", 2 * MS, 2 * MS, {"rid": 7}),
        ("admit.chunk", 4 * MS, 2 * MS, {"rid": 7, "c0": 0}),
        ("admit.chunk", 6 * MS, 2 * MS, {"rid": 7, "c0": 64}),
        ("admit.sample", 20 * MS, 7 * MS, {"rid": 7}),
        ("pump.schedule", 31 * MS, 2 * MS, {}),       # rid 8 does not fit
        ("engine.admit", 31 * MS + MS // 2, MS, {"rid": 8}),
        ("admit.reserve", 31 * MS + MS // 2, 9 * MS // 10, {"rid": 8}),
        ("engine.tick", 40 * MS, 30 * MS, {"index": 0, "active": 2}),
        ("tick.upload", 40 * MS, 2 * MS, {}),
        ("tick.step", 42 * MS, 3 * MS, {}),
        ("tick.sample", 45 * MS, 21 * MS, {}),
        ("tick.emit", 66 * MS, 4 * MS, {}),
        ("pump.wait", 80 * MS, 10 * MS, {}),
    ]
    modules = [("jit_prefill_chunk(1)", 5 * MS, 10 * MS),
               ("jit_prefill_chunk(1)", 15 * MS, 10 * MS),
               ("jit__argmax(2)", 26 * MS, MS // 2),
               ("jit_broadcast_in_dim(3)", 44 * MS, MS // 5),
               ("jit_decode_tick(4)", 45 * MS, 15 * MS),
               ("jit_dynamic_slice(5)", 60 * MS + MS // 2, MS // 2),
               ("jit_squeeze(6)", 61 * MS + MS // 5, 3 * MS // 10),
               ("jit__argmax(2)", 62 * MS, MS)]
    return {"ops": [("%" + n.split("(")[0], s, d) for n, s, d in modules],
            "modules": modules,
            "spans": program + [(trace.CAPTURE, 0, 100 * MS, {})]}


def _hand_run():
    from bench.program import Admission, Tick
    from bench.test_trace import _run
    cell = spec.load_cell(REPO, "qwen14b.decode")
    return _run(cell.config, [Tick(0.04, 0.07, [100, 200], 0)],
                [Admission(7, 0.002, 0.028, [(0, 64), (64, 36)], 0)],
                peaks={"bf16_flop_s": 197e12, "hbm_byte_s": 819e9})


def _read(ev, r) -> dict:
    r.trace = trace.summarize(ev, r)
    return {m: spec.metric_reader(REPO, m)(r) for m in HOST}


def test_innermost_span_cuts_the_window():
    spans = [("a", 0, 10, {}), ("b", 2, 3, {}), ("c", 6, 2, {}),
             ("d", 12, 4, {})]
    assert trace._innermost(spans, 0, 14) == [
        (0, 2, "a"), (2, 5, "b"), (5, 6, "a"), (6, 8, "c"), (8, 10, "a"),
        (10, 12, None), (12, 14, "d")]


def test_busy_inside_intervals():
    b = trace._Busy([(0, 10), (5, 20), (30, 40)])
    assert b.inside(0, 100) == 30
    assert b.inside(15, 35) == 10
    assert b.inside(20, 30) == 0
    assert b.inside(-5, 3) == 3


def test_reduction_of_hand_made_events():
    ev = _ev()
    r = _hand_run()
    got = _read(ev, r)
    t = r.trace
    idle = t.idle_by_span
    busy = 20 + 0.5 + 0.2 + 15 + 0.5 + 0.3 + 1
    assert sum(idle.values()) == pytest.approx((100 - busy) / 1e3)
    want = {"pump.schedule": 5, "engine.admit": 1.1, "admit.reserve": 2.9,
            "admit.chunk": 1, "admit.sample": 1.5, "tick.upload": 2,
            "tick.step": 2.8, "tick.sample": 4.2, "tick.emit": 4,
            "pump.wait": 10, trace.OUTSIDE: 28}
    assert idle == pytest.approx({k: v / 1e3 for k, v in want.items()})
    assert got["tick_host_ms"] == pytest.approx(2 + 2.8 + 4.2 + 4)
    assert got["admit_host_ms"] == pytest.approx(5.5)
    assert got["tick_programs"] == 5
    assert len(t.tick_device_s) == 1 and t.chunk_count == 2
    top = t.breakdown["idle_gaps"]          # seconds, the largest first
    assert len(top) == trace.TOP < len(want)
    assert [v for _, v in top] == sorted((v for _, v in top), reverse=True)
    assert dict(top) == pytest.approx({k: want[k] / 1e3 for k, _ in top})
    # the tick's and the chunks' programs, by name, inside their spans
    (_, t0, td, _), = [x for x in ev["spans"] if x[0] == trace.TICK]
    (_, a0, ad, _), _ = [x for x in ev["spans"] if x[0] == trace.ADMIT]
    assert [n for n, s, _ in ev["modules"] if t0 <= s <= t0 + td
            and n.startswith("jit_decode_tick")] == ["jit_decode_tick(4)"]
    assert sum(n.startswith("jit_prefill_chunk") for n, s, _ in ev["modules"]
               if a0 <= s <= a0 + ad) == 2


def test_no_window_or_no_program_spans_read_nothing():
    ev = _ev()
    nothing = dict.fromkeys(HOST)
    r = _hand_run()
    assert _read(dict(ev, spans=[x for x in ev["spans"]
                                 if x[0] != trace.CAPTURE]), r) == nothing
    assert r.trace.window_s is None and r.trace.spans == []
    assert _read(dict(ev, ops=[]), r) == nothing
    assert _read(dict(ev, spans=[x for x in ev["spans"]
                                 if x[0] == trace.CAPTURE]), r) == nothing
    assert r.trace.idle_by_span == pytest.approx(
        {trace.OUTSIDE: 0.1 - r.trace.busy_s})
    assert r.trace.tick_device_s == [] and r.trace.kernels == {}


def test_a_renamed_span_reads_as_missing():
    """A program that stops writing ``engine.tick`` under that name
    leaves the tick's readings missing."""
    ev = _ev()
    ev["spans"] = [("step.tick" if x[0] == trace.TICK else x[0], *x[1:])
                   for x in ev["spans"]]
    r = _hand_run()
    got = _read(ev, r)
    assert got["tick_host_ms"] is got["tick_programs"] is None
    assert r.trace.tick_device_s == []
    assert got["admit_host_ms"] == pytest.approx(5.5)


def test_span_names_are_the_programs():
    """The benchmark keeps its own list of the program's spans; this is
    where the two must agree."""
    from repro.serving import spans
    assert trace.SPANS == spans.NAMES
    assert (trace.TICK, trace.ADMIT, trace.ADMIT_CHUNK) == (
        spans.TICK, spans.ADMIT, spans.ADMIT_CHUNK)


def test_counters_over_a_tiny_run_match_the_probe(tiny_root):
    """The engine's counters, from the probe's start to the run's end,
    against what the probe recorded of every tick it wrapped, and the
    engine's tick index that each record keeps."""
    cell = spec.load_cell(tiny_root, "tiny.open")
    assert run.open_chip(cell, tiny_root, require_chip=False)
    seen = {}

    def counters(engine):
        return {"ticks": engine.ticks, "slot_ticks": engine.slot_ticks}

    def observe(engine):
        seen["engine"], seen["open"] = engine, counters(engine)

    r = run.serve(cell, run.make_weights(cell, 41), 41, 2.0,
                  faults=observe)
    eng = seen["engine"]
    d = {k: v - seen["open"][k] for k, v in counters(eng).items()}
    ticks = [t for t in r.ticks if t.contexts]
    assert d["ticks"] == len(ticks) > 0
    assert d["slot_ticks"] == sum(len(t.contexts) for t in ticks)
    first = seen["open"]["ticks"]
    assert [t.index for t in ticks] == list(range(first, eng.ticks))
    assert all(first <= a.tick <= eng.ticks for a in r.admissions)
    assert [a.tick for a in r.admissions] == sorted(
        a.tick for a in r.admissions)


def test_a_traced_tiny_run_finds_every_record(tiny_root, tmp_path):
    """The program's own spans, as this host's profiler records them
    over a tiny engine's run, against the probe's records: every tick
    and every admission in the window finds its record, and the work
    read from them is every record's. (The host has no device ops: one
    op over the window stands in, so that the trace has a window.)"""
    from bench import costs
    from bench import program as prog
    cell = spec.load_cell(tiny_root, "tiny.open")
    assert run.open_chip(cell, tiny_root, require_chip=False)
    engine = prog.build_engine(cell, run.make_weights(cell, 43))
    prog.warm_up(engine)
    probe = prog.Probe(engine)
    cap = trace.Capture(str(tmp_path / "trace"))
    cap.start()
    try:
        with cap.span():
            engine.run([prog.request(i, [5 + i] * (20 + 37 * i), 3 + i)
                        for i in range(7)])
    finally:
        cap.stop()
    ev = trace.extract(glob.glob(str(tmp_path / "trace" / "**" /
                                     "*.xplane.pb"), recursive=True)[0])
    (_, c0, cd, _), = [x for x in ev["spans"] if x[0] == trace.CAPTURE]
    ev["ops"] = [("%fusion.1", c0, cd)]
    r = types.SimpleNamespace(
        cell=cell, ticks=probe.ticks, admissions=probe.admissions,
        block_size=engine.block_size,
        peaks=costs.load_peaks("TPU v5 lite"))
    t = trace.summarize(ev, r)
    ticks = [x for x in probe.ticks if x.contexts]
    assert len(t.tick_device_s) == len(ticks) > len(probe.admissions) == 7
    assert len(trace.admissions(t.spans)) == 7
    assert t.chunk_count == sum(len(a.chunks) for a in probe.admissions) \
        == sum(x[0] == trace.ADMIT_CHUNK for x in ev["spans"]) > 7
    arch, cfg = cell.arch, cell.config
    assert t.step_flops == pytest.approx(
        sum(arch.decode_tick(cfg, x.contexts)[0] for x in ticks)
        + sum(arch.prefill_chunk(cfg, c, n, c + n == 20 + 37 * a.rid)[0]
              for a in probe.admissions for c, n in a.chunks))


# what the harness read from the recorded trace before this reduction:
# it found ticks and chunks by the probe's ``bench.tick`` /
# ``bench.admit`` spans (still in the recording) and the kernel by
# marks in its ops' text, which also caught the ops that take the
# kernel's output; ``program_trace`` read the three host metrics
PARENT = {"window_s": 0.501740041, "busy_s": 0.42754188,
          "tick_device_s": [0.036892317, 0.037501561, 0.037670097,
                            0.037700716],
          "chunk_device_s": 0.274682296, "chunk_count": 18,
          "kernel_device_s": 0.118948517,
          "kernel_min_s": 0.004499178432234435,
          "step_flops": 5462479339520.0}
PARENT_METRICS = {"decode_step_ms": 37.44117275,
                  "prefill_chunk_ms": 15.260127555555556,
                  "paged_attn_roofline": 3.782458617987171,
                  "device_idle_share": 14.788168162165881,
                  "step_mfu": 6.532811486397969,
                  "tick_host_ms": 10.50260075,
                  "admit_host_ms": 6.993034666666667,
                  "tick_programs": 5.0}


@pytest.fixture(scope="module")
def spans_trace():
    with open(RECORDED + ".run.json") as f:
        rec = json.load(f)
    ev, r = _recorded()
    return ev, r, rec


def test_first_recorded_trace_reduces_as_before(spans_trace):
    """Every reading is the parent's but the kernel's: its device time
    is now the ops named for it alone, so its roofline share reads
    higher by the share the consumers had of the old time."""
    ev, r, _ = spans_trace
    r.trace = t = trace.summarize(ev, r)
    k = t.kernels["paged_attention"]
    assert (t.window_s, t.busy_s, t.chunk_device_s, t.chunk_count,
            k.min_s, t.step_flops) == pytest.approx(
        tuple(PARENT[n] for n in ("window_s", "busy_s", "chunk_device_s",
                                  "chunk_count", "kernel_min_s",
                                  "step_flops")))
    assert t.tick_device_s == pytest.approx(PARENT["tick_device_s"])
    read = {m: spec.metric_reader(REPO, m)(r) for m in PARENT_METRICS}
    moved = PARENT_METRICS["paged_attn_roofline"] \
        * PARENT["kernel_device_s"] / k.device_s
    assert read == pytest.approx(dict(PARENT_METRICS,
                                      paged_attn_roofline=moved))
    assert moved == pytest.approx(4.144235518013627)


def test_first_recorded_trace_has_no_program_names(spans_trace):
    """A trace without the program's names (the recording with them
    taken out, as the first recording, now gone, was) reads the window
    and nothing of the engine's calls."""
    ev, r, _ = spans_trace
    bare = dict(ev, spans=[x for x in ev["spans"] if x[0] == trace.CAPTURE])
    r.trace = t = trace.summarize(bare, r)
    assert (t.window_s, t.busy_s) == pytest.approx(
        (PARENT["window_s"], PARENT["busy_s"]))
    assert t.tick_device_s == [] and t.chunk_count == 0
    assert t.kernels == {} and t.spans == []
    assert list(t.idle_by_span) == [trace.OUTSIDE]
    for m in ("decode_step_ms", "prefill_chunk_ms", "paged_attn_roofline",
              "step_mfu") + HOST:
        assert spec.metric_reader(REPO, m)(r) is None
    assert spec.metric_reader(REPO, "device_idle_share")(r) == \
        pytest.approx(PARENT_METRICS["device_idle_share"])


def _inside(ev, span, prefix=""):
    """Module executions starting inside ``span``, by name prefix."""
    _, s, d, *_ = span
    return [m for m in ev["modules"]
            if s <= m[1] <= s + d and m[0].startswith(prefix)]


def test_recorded_ticks_hold_five_programs_one_named_for_the_tick(
        spans_trace):
    ev, r, _ = spans_trace
    r.trace = t = trace.summarize(ev, r)
    ticks = [x for x in ev["spans"] if x[0] == trace.TICK]
    assert len([s for s in t.spans if s.name == trace.TICK]) \
        == len(ticks) > 0
    assert trace.admissions(t.spans)
    assert spec.metric_reader(REPO, "tick_programs")(r) == sum(
        len(_inside(ev, x)) for x in ticks) / len(ticks) == 5.0
    for x in ticks:
        assert len(_inside(ev, x, "jit_decode_tick")) == 1


def test_recorded_programs_by_name_are_the_harness_attribution(
        spans_trace):
    """Per tick, the ``jit_decode_tick`` program is the device time the
    harness gives its ``engine.tick`` span but for the four small eager
    programs around it; per admission, one ``jit_prefill_chunk`` per
    chunk the harness counts."""
    ev, r, _ = spans_trace
    t = trace.summarize(ev, r)
    ticks = sorted((x for x in ev["spans"] if x[0] == trace.TICK),
                   key=lambda x: x[1])
    named = [sum(m[2] for m in _inside(ev, x, "jit_decode_tick")) / 1e9
             for x in ticks]
    assert len(named) == len(t.tick_device_s) > 0
    for got, attributed in zip(named, t.tick_device_s, strict=True):
        assert 0.99 * attributed < got <= attributed
    admits = [x for x in ev["spans"] if x[0] == trace.ADMIT]
    assert sum(len(_inside(ev, x, "jit_prefill_chunk")) for x in admits) \
        == t.chunk_count > 0


def test_recorded_ticks_by_engine_spans_are_the_probes_attribution(
        spans_trace):
    """The recording also holds the probe's old ``bench.tick`` spans:
    the ticks found by ``engine.tick`` are the same four, each with the
    same device time, and each ``engine.tick`` lies inside its
    ``bench.tick``, whose probe record it finds by the engine's index."""
    ev, r, _ = spans_trace
    t = trace.summarize(ev, r)
    assert t.tick_device_s == PARENT["tick_device_s"]
    old = trace.extract(RECORDED + ".xplane.pb", ("bench.tick",))
    bench = sorted((x for x in old["spans"] if x[0] == "bench.tick"),
                   key=lambda x: x[1])
    engine = sorted((x for x in ev["spans"] if x[0] == trace.TICK),
                    key=lambda x: x[1])
    assert len(bench) == len(engine) == 4
    for b, e in zip(bench, engine, strict=True):
        assert b[1] <= e[1] and e[1] + e[2] <= b[1] + b[2]
        # the probe's index was its list's; the engine's is its count
        assert r.ticks[b[3]["index"]].index == e[3]["index"]


def test_recorded_kernel_by_name_is_the_marks_less_its_consumers(
        spans_trace):
    """The kernel's device time in the traced calls is its named ops'
    alone; the marks the parent harness searched op text for also
    caught each op that takes the kernel's output as an operand, and
    1-ns ``custom-call`` allocations: together they were the parent's
    kernel time."""
    from jax.profiler import ProfileData
    ev, r, _ = spans_trace
    t = trace.summarize(ev, r)
    calls = sorted((x[1], x[1] + x[2]) for x in ev["spans"]
                   if x[0] == trace.TICK or (x[0] == trace.ADMIT and any(
                       x[1] <= c[1] <= x[1] + x[2] for c in ev["spans"]
                       if c[0] == trace.ADMIT_CHUNK)))
    pd = ProfileData.from_file(RECORDED + ".xplane.pb")
    ops, = [ln for ln in trace._device_planes(pd)[0].lines
            if ln.name.lower() == "xla ops"]
    named = consumers = allocations = 0
    for e in ops.events:
        if not any(a <= e.start_ns <= b for a, b in calls):
            continue
        head, _, rest = e.name.partition(" = ")
        if trace.kernel_of(head) == "paged_attention":
            named += e.duration_ns
        elif "%paged_attention." in rest:
            consumers += e.duration_ns
        elif head.startswith("%custom-call") and e.duration_ns < 100:
            allocations += e.duration_ns
    assert t.kernels["paged_attention"].device_s == pytest.approx(
        named / 1e9)
    assert consumers > 0
    assert (named + consumers + allocations) / 1e9 == pytest.approx(
        PARENT["kernel_device_s"], abs=1e-6)


def test_recorded_counters_agree_with_the_trace(spans_trace):
    """Between the profiler's start and stop the engine counted the
    ticks the trace holds, their active slots, and the live blocks the
    probe's records give for those ticks, each found by the engine's
    tick index on its ``engine.tick`` span."""
    ev, r, rec = spans_trace
    o, c = rec["counters"]["open"], rec["counters"]["close"]
    ticks = [x for x in ev["spans"] if x[0] == trace.TICK]
    assert c["ticks"] - o["ticks"] == len(ticks)
    assert c["slot_ticks"] - o["slot_ticks"] == sum(
        x[3]["active"] for x in ticks)
    by_index = {t.index: t for t in r.ticks}
    assert all(len(by_index[x[3]["index"]].contexts) == x[3]["active"]
               for x in ticks)
    assert c["kv_blocks_live"] - o["kv_blocks_live"] == sum(
        math.ceil(n / r.block_size) for x in ticks
        for n in by_index[x[3]["index"]].contexts)
