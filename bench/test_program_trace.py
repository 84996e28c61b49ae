"""The reduction of the program's own names (``program_trace``): on
events made by hand, on a tiny run of the engine, and on traces
recorded on one TPU v5e."""
from __future__ import annotations

import json
import math
import os
import types

import pytest

from bench import costs, run, spec, trace
from bench import program_trace as pt
from bench.program import Admission, Tick, engine_options
from bench.test_trace import RECORDED, _recorded, _run

MS = 1_000_000


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
    return {"ops": [(n.split("(")[0], s, d) for n, s, d in modules],
            "modules": modules, "program": program,
            "spans": [(trace.CAPTURE, 0, 100 * MS, -1)]}


def test_innermost_span_cuts_the_window():
    spans = [("a", 0, 10, {}), ("b", 2, 3, {}), ("c", 6, 2, {}),
             ("d", 12, 4, {})]
    assert pt._innermost(spans, 0, 14) == [
        (0, 2, "a"), (2, 5, "b"), (5, 6, "a"), (6, 8, "c"), (8, 10, "a"),
        (10, 12, None), (12, 14, "d")]


def test_busy_inside_intervals():
    b = pt._Busy([(0, 10), (5, 20), (30, 40)])
    assert b.inside(0, 100) == 30
    assert b.inside(15, 35) == 10
    assert b.inside(20, 30) == 0
    assert b.inside(-5, 3) == 3


def test_reduction_of_hand_made_events():
    ev = _ev()
    idle = pt.idle_by_span(ev)
    busy = 20 + 0.5 + 0.2 + 15 + 0.5 + 0.3 + 1
    assert sum(idle.values()) == pytest.approx((100 - busy) * MS)
    want = {"pump.schedule": 5, "engine.admit": 1.1, "admit.reserve": 2.9,
            "admit.chunk": 1, "admit.sample": 1.5, "tick.upload": 2,
            "tick.step": 2.8, "tick.sample": 4.2, "tick.emit": 4,
            "pump.wait": 10, pt.OUTSIDE: 28}
    assert idle == pytest.approx({k: v * MS for k, v in want.items()})
    r = pt.reduce(ev)
    assert r["traced_ticks"] == r["traced_admissions"] == 1
    assert r["tick_host_ms"] == pytest.approx(2 + 2.8 + 4.2 + 4)
    assert r["admit_host_ms"] == pytest.approx(5.5)
    assert r["tick_programs"] == 5
    top = r["idle_by_span"]                 # seconds, the largest first
    assert len(top) == trace.TOP < len(want)
    assert [v for _, v in top] == sorted((v for _, v in top), reverse=True)
    assert dict(top) == pytest.approx({k: want[k] / 1e3 for k, _ in top})
    # the tick's and the chunks' programs, by name, inside their spans
    (_, t0, td, _), = [x for x in ev["program"] if x[0] == pt.TICK]
    (_, a0, ad, _), _ = [x for x in ev["program"] if x[0] == pt.ADMIT]
    assert [n for n, s, _ in ev["modules"] if t0 <= s <= t0 + td
            and n.startswith(pt.TICK_PROGRAM)] == ["jit_decode_tick(4)"]
    assert sum(n.startswith(pt.CHUNK_PROGRAM) for n, s, _ in ev["modules"]
               if a0 <= s <= a0 + ad) == 2


def test_no_window_or_no_program_spans_read_nothing():
    ev = _ev()
    nothing = dict.fromkeys(pt.READINGS)
    r = pt.reduce(dict(ev, spans=[]))
    assert r == {**nothing, "traced_ticks": 0, "traced_admissions": 0,
                 "missing": []}
    r = pt.reduce(dict(ev, program=[]))
    assert r == {**nothing, "traced_ticks": 0, "traced_admissions": 0,
                 "missing": [pt.TICK, pt.ADMIT, pt.ADMIT_CHUNK]}


def test_a_renamed_span_reads_as_missing():
    """A program that stops writing ``engine.tick`` under that name
    leaves the tick's readings missing, and says which span."""
    ev = _ev()
    ev["program"] = [("step.tick" if x[0] == pt.TICK else x[0], *x[1:])
                     for x in ev["program"]]
    r = pt.reduce(ev)
    assert r["missing"] == [pt.TICK]
    assert r["tick_host_ms"] is r["tick_programs"] is None
    assert r["traced_ticks"] == 0
    assert r["admit_host_ms"] == pytest.approx(5.5)


def test_span_names_are_the_programs():
    """The benchmark keeps its own list of the program's spans; this is
    where the two must agree."""
    from repro.serving import spans
    assert pt.SPANS == spans.NAMES
    assert (pt.TICK, pt.ADMIT, pt.ADMIT_CHUNK) == (
        spans.TICK, spans.ADMIT, spans.ADMIT_CHUNK)


def _ticks_run(ticks, window=(0.0, 10.0), max_slots=4, max_len=256,
               block=16):
    cell = types.SimpleNamespace(config={"engine": {"max_len": max_len}},
                                 traffic={})
    return types.SimpleNamespace(cell=cell, ticks=ticks, window=window,
                                 max_slots=max_slots, block_size=block)


def test_kv_live_block_share_of_hand_made_ticks():
    # 16 columns of 16 tokens, 4 rows: 64 cells per tick; live blocks
    # 1 + 2 + 16 in the first tick, 3 in the second, and the third
    # starts after the window
    ticks = [Tick(1.0, 1.1, [1, 17, 256]), Tick(2.0, 2.1, [33]),
             Tick(10.0, 10.1, [256] * 4)]
    r = _ticks_run(ticks)
    assert pt.kv_live_block_share(r) == pytest.approx(100 * 22 / 128)
    assert pt.kv_live_block_share(_ticks_run(ticks[2:])) is None
    # a traffic mix's engine options override the configuration's
    r.cell.traffic = {"engine": {"max_len": 512}}
    assert pt.kv_live_block_share(r) == pytest.approx(100 * 22 / 256)


def test_counters_over_a_tiny_run_match_the_probe(tiny_root):
    """The engine's counters, from the probe's start to the run's end,
    against what the probe recorded of every tick it wrapped; and the
    live-block share over those records against the block tables the
    engine launches its kernel over."""
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
    assert eng.tables.shape == (r.max_slots, eng.blocks_per_seq)
    inside = [t for t in ticks if r.window[0] <= t.start < r.window[1]]
    live = sum(math.ceil(c / r.block_size) for t in inside
               for c in t.contexts)
    assert pt.kv_live_block_share(r) == pytest.approx(
        100 * live / (len(inside) * eng.tables.size))
    assert 0 < pt.kv_live_block_share(r) <= 100


# the trace recorded before the program named its spans: the harness's
# reduction of it, pinned, and nothing of the program's read from it
def test_first_recorded_trace_reduces_as_before():
    ev, r = _recorded()
    r.trace = t = trace.summarize(ev, r)
    read = {m: spec.metric_reader(os.path.dirname(spec.BENCH_DIR), m)(r)
            for m in ("decode_step_ms", "prefill_chunk_ms",
                      "paged_attn_roofline", "device_idle_share",
                      "step_mfu")}
    assert (t.window_s, t.busy_s) == pytest.approx((0.50174001,
                                                    0.426649084))
    assert read == pytest.approx({
        "decode_step_ms": 25.364649999999997,
        "prefill_chunk_ms": 15.674166181818181,
        "paged_attn_roofline": 2.8108127261749227,
        "device_idle_share": 14.96610286271569,
        "step_mfu": 4.649312073868518})


def test_first_recorded_trace_has_no_program_names():
    ev = pt.extract(RECORDED + ".xplane.pb")
    assert ev["program"] == []
    r = pt.reduce(ev)
    assert r["missing"] == [pt.TICK, pt.ADMIT, pt.ADMIT_CHUNK]
    assert all(r[k] is None for k in pt.READINGS)
    assert not any(n.startswith(pt.KERNEL_OP) for n, _, _ in ev["op_names"])


# a trace recorded on one TPU v5e after the program named its phases,
# with the run's records and the engine's counters read as the profiler
# started and stopped
SPANS = os.path.join(spec.BENCH_DIR, "traces", "qwen14b.decode.spans")


@pytest.fixture(scope="module")
def spans_trace():
    with open(SPANS + ".run.json") as f:
        rec = json.load(f)
    cell = spec.load_cell(os.path.dirname(spec.BENCH_DIR), "qwen14b.decode")
    r = _run(cell.config, [Tick(**t) for t in rec["ticks"]],
             [Admission(**a) for a in rec["admissions"]],
             block=rec["block_size"], peaks=costs.load_peaks("TPU v5 lite"))
    r.table_blocks = engine_options(cell.config, cell.traffic)["max_len"] \
        // rec["block_size"]
    return pt.extract(SPANS + ".xplane.pb"), r, rec


def _inside(ev, span, prefix=""):
    """Module executions starting inside ``span``, by name prefix."""
    _, s, d, *_ = span
    return [m for m in ev["modules"]
            if s <= m[1] <= s + d and m[0].startswith(prefix)]


def test_recorded_ticks_hold_five_programs_one_named_for_the_tick(
        spans_trace):
    ev, _, _ = spans_trace
    r = pt.reduce(ev)
    ticks = [x for x in ev["program"] if x[0] == pt.TICK]
    assert r["traced_ticks"] == len(ticks) > 0
    assert r["traced_admissions"] > 0
    assert r["tick_programs"] == sum(len(_inside(ev, t)) for t in ticks) \
        / len(ticks) == 5.0
    for t in ticks:
        assert len(_inside(ev, t, pt.TICK_PROGRAM)) == 1


def test_recorded_programs_by_name_are_the_harness_attribution(
        spans_trace):
    """Per tick, the ``jit_decode_tick`` program is the device time the
    harness gives its ``bench.tick`` span but for the four small eager
    programs around it; per admission, one ``jit_prefill_chunk`` per
    chunk the harness counts."""
    ev, r, _ = spans_trace
    t = trace.summarize(ev, r)
    ticks = sorted((x for x in ev["spans"] if x[0] == "bench.tick"),
                   key=lambda x: x[3])
    named = [sum(m[2] for m in _inside(ev, x, pt.TICK_PROGRAM)) / 1e9
             for x in ticks]
    assert len(named) == len(t.tick_device_s) > 0
    for got, attributed in zip(named, t.tick_device_s, strict=True):
        assert 0.99 * attributed < got <= attributed
    admits = [x for x in ev["spans"] if x[0] == "bench.admit"]
    assert sum(len(_inside(ev, x, pt.CHUNK_PROGRAM)) for x in admits) \
        == t.chunk_count > 0


def test_recorded_kernel_by_name_is_the_marks_less_its_consumers(
        spans_trace):
    """Every op named ``paged_attention`` is one the marks find; the
    marks also find each op that takes the kernel's output as an
    operand, and 1-ns ``custom-call`` allocations."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(SPANS + ".xplane.pb")
    ops, = [ln for ln in trace._device_planes(pd)[0].lines
            if ln.name.lower() == "xla ops"]
    by_name = by_marks = consumers = 0
    for e in ops.events:
        head, _, rest = e.name.partition(" = ")
        named, marked = head.startswith(pt.KERNEL_OP), trace._is_kernel(e)
        assert marked or not named
        by_name += named * e.duration_ns
        by_marks += marked * e.duration_ns
        if marked and not named:
            assert pt.KERNEL_OP in rest or (
                head.startswith("%custom-call") and e.duration_ns < 100)
            consumers += pt.KERNEL_OP in rest and e.duration_ns
    assert by_name > 0
    assert by_name + consumers == pytest.approx(by_marks, abs=1e3)


def test_recorded_counters_agree_with_the_trace(spans_trace):
    """Between the profiler's start and stop the engine counted the
    ticks the trace holds, their active slots, and the live blocks the
    probe's records give for those ticks (the recording's engine also
    counted live blocks and launched cells); the share read from the
    probe's records of those ticks is the share those counts give."""
    ev, r, rec = spans_trace
    o, c = rec["counters"]["open"], rec["counters"]["close"]
    ticks = [x for x in ev["program"] if x[0] == pt.TICK]
    assert c["ticks"] - o["ticks"] == len(ticks)
    assert c["slot_ticks"] - o["slot_ticks"] == sum(
        x[3]["active"] for x in ticks)
    index = [x[3] for x in ev["spans"] if x[0] == "bench.tick"]
    assert c["kv_blocks_live"] - o["kv_blocks_live"] == sum(
        math.ceil(n / r.block_size) for i in index
        for n in r.ticks[i].contexts)
    assert c["kv_blocks_grid"] - o["kv_blocks_grid"] == len(ticks) \
        * rec["max_slots"] * r.table_blocks
    traced = [r.ticks[i] for i in index]
    share = pt.kv_live_block_share(_ticks_run(
        traced, (traced[0].start, traced[-1].end), rec["max_slots"],
        r.table_blocks * r.block_size, r.block_size))
    assert share == pytest.approx(100 * (c["kv_blocks_live"]
                                         - o["kv_blocks_live"])
                                  / (c["kv_blocks_grid"]
                                     - o["kv_blocks_grid"]))
