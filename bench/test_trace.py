"""The trace reduction, on events made by hand and on a trace recorded
on one TPU v5e."""
from __future__ import annotations

import json
import os
import types

import pytest

from bench import costs, trace
from bench.program import Admission, Tick
from bench.spec import BENCH_DIR, load_arch, load_cell

MS = 1_000_000


def _run(config, ticks, admissions, block=16, peaks=None):
    cell = types.SimpleNamespace(
        config=config, arch=load_arch(os.path.dirname(BENCH_DIR), config))
    return types.SimpleNamespace(cell=cell, ticks=ticks,
                                 admissions=admissions, block_size=block,
                                 peaks=peaks)


def test_union_and_owner():
    assert trace._union([(0, 10), (5, 20), (30, 40), (35, 36)]) == 30
    spans = [("bench.tick", 0, 10, 0), ("bench.admit", 20, 10, 0)]
    assert trace._owner(spans, 5)[0] == "bench.tick"
    assert trace._owner(spans, 15) is None
    assert trace._owner(spans, 30)[0] == "bench.admit"


def test_summary_of_hand_made_events():
    cell = load_cell(os.path.dirname(BENCH_DIR), "qwen14b.decode")
    cfg, arch = cell.config, cell.arch
    peaks = costs.load_peaks("TPU v5 lite")
    # two ticks, one admission of two chunks, over 100 ms
    spans = [["bench.tick", 0, 20 * MS, 0], ["bench.admit", 30 * MS,
                                             40 * MS, 0],
             ["bench.tick", 80 * MS, 20 * MS, 1],
             [trace.CAPTURE, -5 * MS, 110 * MS, -1]]
    mods = [["jit_decode_paged", 2 * MS, 15 * MS],
            ["jit_decode_paged", 32 * MS, 15 * MS],
            ["jit_decode_paged", 50 * MS, 15 * MS],
            ["jit_decode_paged", 82 * MS, 15 * MS]]
    ops = [["fusion", 2 * MS, 10 * MS], ["paged_kernel", 12 * MS, 5 * MS],
           ["fusion", 32 * MS, 30 * MS], ["paged_kernel", 62 * MS, 3 * MS],
           ["fusion", 82 * MS, 15 * MS]]
    for o in ops:
        o[0] = o[0].replace("paged_kernel", trace.KERNEL)
    ticks = [Tick(0.0, 0.02, [100, 200]), Tick(0.08, 0.1, [101, 201])]
    adm = [Admission(7, 0.03, 0.07, [(0, 64), (64, 36)])]
    r = _run(cfg, ticks, adm, peaks=peaks)
    t = trace.summarize({"ops": ops, "modules": mods, "spans": spans}, r)
    assert t.window_s == pytest.approx(0.11)
    assert t.busy_s == pytest.approx(0.015 + 0.033 + 0.015)
    assert t.tick_device_s == pytest.approx([0.015, 0.015])
    assert t.chunk_device_s == pytest.approx(0.030)
    assert t.chunk_count == 2
    assert t.kernel_device_s == pytest.approx(0.008)
    work = arch.kernel_calls(cfg, [100, 200], 1, 16) \
        + arch.kernel_calls(cfg, [101, 201], 1, 16) \
        + arch.kernel_calls(cfg, [64], 64, 16) \
        + arch.kernel_calls(cfg, [100], 36, 16)
    assert len(work) == 4 * cfg["num_hidden_layers"]
    want = sum(costs.min_seconds(f, b, peaks)[0] for f, b in work)
    assert t.kernel_min_s == pytest.approx(want)
    flops = arch.decode_tick(cfg, [100, 200])[0] \
        + arch.decode_tick(cfg, [101, 201])[0] \
        + arch.prefill_chunk(cfg, 0, 64, False)[0] \
        + arch.prefill_chunk(cfg, 64, 36, True)[0]
    assert t.step_flops == pytest.approx(flops)
    assert t.step_device_s == pytest.approx(0.06)
    idle = dict(t.breakdown["idle_gaps"])
    assert sum(idle.values()) == pytest.approx(0.11 - t.busy_s)
    assert idle["host in tick"] == pytest.approx(0.002 + 0.003 + 0.002
                                                 + 0.003)
    assert t.breakdown["device_ops"][0] == ["fusion", pytest.approx(0.055)]


# a trace of the window recorded on one TPU v5e (qwen14b.decode), with
# the run's records of its engine calls
RECORDED = os.path.join(BENCH_DIR, "traces", "qwen14b.decode")


def _recorded():
    with open(RECORDED + ".run.json") as f:
        rec = json.load(f)
    cfg = load_cell(os.path.dirname(BENCH_DIR), "qwen14b.decode").config
    run = _run(cfg, [Tick(**t) for t in rec["ticks"]],
               [Admission(**a) for a in rec["admissions"]],
               block=rec["block_size"],
               peaks=costs.load_peaks("TPU v5 lite"))
    return trace.extract(RECORDED + ".xplane.pb"), run


def test_recorded_trace_has_the_device_and_the_spans():
    ev, _ = _recorded()
    names = {x[0] for x in ev["spans"]}
    assert {trace.CAPTURE, "bench.tick"} <= names
    assert ev["modules"] and ev["ops"]
    assert any(o[0] == trace.KERNEL for o in ev["ops"])


def test_recorded_trace_reduces_to_what_the_events_say():
    """Busy time, per-tick program time and kernel time, each counted
    again here the plain way from the extracted events."""
    ev, run = _recorded()
    t = trace.summarize(ev, run)
    cap = next(x for x in ev["spans"] if x[0] == trace.CAPTURE)
    t0, t1 = cap[1], cap[1] + cap[2]
    assert t.window_s == pytest.approx((t1 - t0) / 1e9)
    # busy: walk the device's op intervals in order of start
    busy, end = 0, t0
    for a, b in sorted((o[1], o[1] + o[2]) for o in ev["ops"]):
        a, b = max(a, end), min(b, t1)
        if b > a:
            busy += b - a
            end = b
    assert t.busy_s == pytest.approx(busy / 1e9)
    assert 0 < t.busy_s < t.window_s
    # per tick: the programs that start inside each tick span
    ticks = [x for x in ev["spans"] if x[0] == "bench.tick"]
    want = {}
    for _, s, d, i in ticks:
        got = sum(m[2] for m in ev["modules"] if s <= m[1] <= s + d)
        if got:
            want[i] = got / 1e9
    assert t.tick_device_s == pytest.approx([want[i] for i in sorted(want)])
    # kernel: its ops inside the tick and admission spans that ran one
    calls = [x for x in ev["spans"] if x[0] in ("bench.tick", "bench.admit")]
    k = sum(o[2] for o in ev["ops"] if o[0] == trace.KERNEL and any(
        s <= o[1] <= s + d and any(s <= m[1] <= s + d for m in ev["modules"])
        for _, s, d, _ in calls))
    assert t.kernel_device_s == pytest.approx(k / 1e9)
    assert 0 < t.kernel_device_s < t.step_device_s
    assert 0 < t.kernel_min_s < t.kernel_device_s
