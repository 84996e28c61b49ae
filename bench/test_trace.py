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


def _run(config, ticks, admissions, block=16, peaks=None, arch=None):
    cell = types.SimpleNamespace(
        config=config,
        arch=arch or load_arch(os.path.dirname(BENCH_DIR), config))
    return types.SimpleNamespace(cell=cell, ticks=ticks,
                                 admissions=admissions, block_size=block,
                                 peaks=peaks, trace=None)


def test_kernel_of_op_names():
    assert trace.kernel_of("%paged_attention.9") == "paged_attention"
    assert trace.kernel_of("%paged_attention") == "paged_attention"
    assert trace.kernel_of("%expert_matmul.12") == "expert_matmul"
    assert trace.kernel_of("%fusion.3") == "fusion"
    assert trace.kernel_of("%bitcast_add_fusion.6") == "bitcast_add_fusion"


def _calls():
    """Two ticks and one admission of two chunks over a 110-ms window:
    the program's spans, programs and ops, and the probe's records."""
    spans = [["engine.tick", 0, 20 * MS, {"index": 0, "active": 2}],
             ["engine.admit", 30 * MS, 40 * MS, {"rid": 7}],
             ["admit.chunk", 31 * MS, 2 * MS, {"rid": 7, "c0": 0}],
             ["admit.chunk", 49 * MS, 2 * MS, {"rid": 7, "c0": 64}],
             ["engine.tick", 80 * MS, 20 * MS, {"index": 1, "active": 2}],
             [trace.CAPTURE, -5 * MS, 110 * MS, {}]]
    mods = [["jit_decode_tick(1)", 2 * MS, 15 * MS],
            ["jit_prefill_chunk(2)", 32 * MS, 15 * MS],
            ["jit_prefill_chunk(2)", 50 * MS, 15 * MS],
            ["jit_decode_tick(1)", 82 * MS, 15 * MS]]
    ticks = [Tick(0.0, 0.02, [100, 200], 0), Tick(0.08, 0.1, [101, 201], 1)]
    adm = [Admission(7, 0.03, 0.07, [(0, 64), (64, 36)], 1)]
    return spans, mods, ticks, adm


def test_summary_of_hand_made_events():
    cell = load_cell(os.path.dirname(BENCH_DIR), "qwen14b.decode")
    cfg, arch = cell.config, cell.arch
    peaks = costs.load_peaks("TPU v5 lite")
    spans, mods, ticks, adm = _calls()
    ops = [["%fusion.1", 2 * MS, 10 * MS],
           ["%paged_attention.3", 12 * MS, 5 * MS],
           ["%fusion.2", 32 * MS, 30 * MS],
           ["%paged_attention.4", 62 * MS, 3 * MS],
           ["%fusion.1", 82 * MS, 15 * MS]]
    r = _run(cfg, ticks, adm, peaks=peaks)
    t = trace.summarize({"ops": ops, "modules": mods, "spans": spans}, r)
    assert t.window_s == pytest.approx(0.11)
    assert t.busy_s == pytest.approx(0.015 + 0.033 + 0.015)
    assert t.tick_device_s == pytest.approx([0.015, 0.015])
    assert t.chunk_device_s == pytest.approx(0.030)
    assert t.chunk_count == 2
    k = t.kernels["paged_attention"]
    assert list(t.kernels) == ["paged_attention"]
    assert k.device_s == pytest.approx(0.008)
    work = arch.kernel_calls(cfg, [100, 200], 1, 16) \
        + arch.kernel_calls(cfg, [101, 201], 1, 16) \
        + arch.kernel_calls(cfg, [64], 64, 16) \
        + arch.kernel_calls(cfg, [100], 36, 16)
    assert len(work) == 4 * cfg["num_hidden_layers"]
    assert {w[0] for w in work} == {"paged_attention"}
    want = sum(costs.min_seconds(f, b, peaks)[0] for _, f, b in work)
    assert k.min_s == pytest.approx(want)
    assert k.bound == "memory"
    flops = arch.decode_tick(cfg, [100, 200])[0] \
        + arch.decode_tick(cfg, [101, 201])[0] \
        + arch.prefill_chunk(cfg, 0, 64, False)[0] \
        + arch.prefill_chunk(cfg, 64, 36, True)[0]
    assert t.step_flops == pytest.approx(flops)
    assert t.step_device_s == pytest.approx(0.06)
    idle = dict(t.breakdown["idle_gaps"])
    assert sum(idle.values()) == pytest.approx(0.11 - t.busy_s)
    assert idle == pytest.approx({"engine.tick": 0.010, "engine.admit": 0.006,
                                  "admit.chunk": 0.001, trace.OUTSIDE: 0.030})
    assert t.idle_by_span == pytest.approx(idle)
    assert t.breakdown["device_ops"][0] == ["%fusion.2",
                                            pytest.approx(0.030)]
    assert [s.name for s in t.spans] == [x[0] for x in spans[:-1]]
    assert [s.programs for s in t.spans] == [1, 2, 1, 1, 1]
    assert t.spans[0].attrs == {"index": 0, "active": 2}
    assert trace.admissions(t.spans) == [t.spans[1]]


def test_two_named_kernels_are_read_by_name_and_a_consumer_is_not():
    """A second Pallas kernel is counted apart from the first, each by
    its own name, against its own calls' work; an op that takes a
    kernel's output, and a kernel no call names, count for neither."""
    cfg = load_cell(os.path.dirname(BENCH_DIR), "qwen14b.decode").config
    peaks = costs.load_peaks("TPU v5 lite")

    def kernel_calls(config, contexts, queries, block):
        n = len(contexts) * queries
        return [("paged_attention", 1e6 * n, 1e8), ("expert_matmul",
                                                    4e11 * n, 1e6)] * 2
    arch = types.SimpleNamespace(decode_tick=lambda c, ctx: (1.0, 1.0),
                                 prefill_chunk=lambda *a: (1.0, 1.0),
                                 kernel_calls=kernel_calls, SPANS=())
    spans, mods, ticks, adm = _calls()
    ops = [["%paged_attention.3", 2 * MS, 4 * MS],
           ["%fusion.7", 6 * MS, 2 * MS],          # the kernel's consumer
           ["%expert_matmul.1", 8 * MS, 6 * MS],
           ["%other_kernel.2", 14 * MS, 1 * MS],   # named by no call
           ["%paged_attention.4", 32 * MS, 3 * MS],
           ["%expert_matmul.2", 40 * MS, 10 * MS],
           ["%expert_matmul.1", 72 * MS, 5 * MS],  # outside every call
           ["%paged_attention.3", 82 * MS, 4 * MS],
           ["%expert_matmul.1", 86 * MS, 7 * MS]]
    r = _run(cfg, ticks, adm, peaks=peaks, arch=arch)
    r.trace = t = trace.summarize(
        {"ops": ops, "modules": mods, "spans": spans}, r)
    assert set(t.kernels) == {"paged_attention", "expert_matmul"}
    pa, em = t.kernels["paged_attention"], t.kernels["expert_matmul"]
    assert pa.device_s == pytest.approx(0.011)
    assert em.device_s == pytest.approx(0.023)
    # two calls of each kernel in each of the two ticks and two chunks
    # (2 + 2 sequences in the ticks, 64 + 36 tokens in the chunks)
    assert pa.min_s == pytest.approx(8 * 1e8 / 819e9)
    assert pa.bound == "memory"
    assert em.min_s == pytest.approx(2 * (2 + 2 + 64 + 36) * 4e11 / 197e12)
    assert em.bound == "compute"
    assert trace.roofline(r, "expert_matmul") == pytest.approx(
        100 * em.min_s / 0.023)
    assert trace.roofline(r, "paged_attention") == pytest.approx(
        100 * pa.min_s / 0.011)
    assert trace.roofline(r, "other_kernel") is None


# a trace of the window recorded on one TPU v5e (qwen14b.decode), after
# the program named its phases, with the run's records of its engine
# calls and the engine's counters read as the profiler started and
# stopped
RECORDED = os.path.join(BENCH_DIR, "traces", "qwen14b.decode.spans")


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
    assert {trace.CAPTURE, trace.TICK, trace.ADMIT, trace.ADMIT_CHUNK} \
        <= names <= {trace.CAPTURE, *trace.SPANS}
    assert ev["modules"] and ev["ops"]
    assert any(trace.kernel_of(o[0]) == "paged_attention"
               for o in ev["ops"])
    tick = next(x for x in ev["spans"] if x[0] == trace.TICK)
    assert set(tick[3]) == {"index", "active"}


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
    ticks = sorted((x for x in ev["spans"] if x[0] == trace.TICK
                    and t0 <= x[1] and x[1] + x[2] <= t1),
                   key=lambda x: x[1])
    want = [sum(m[2] for m in ev["modules"] if s <= m[1] <= s + d) / 1e9
            for _, s, d, _ in ticks]
    assert t.tick_device_s == pytest.approx(want)
    # kernel: its ops inside the tick and admission spans that ran one
    chunks = [x[1] for x in ev["spans"] if x[0] == trace.ADMIT_CHUNK]
    calls = ticks + [x for x in ev["spans"] if x[0] == trace.ADMIT
                     and any(x[1] <= c <= x[1] + x[2] for c in chunks)]
    k = sum(o[2] for o in ev["ops"]
            if o[0].startswith("%paged_attention.") and any(
                s <= o[1] <= s + d for _, s, d, _ in calls))
    kern = t.kernels["paged_attention"]
    assert kern.device_s == pytest.approx(k / 1e9)
    assert 0 < kern.device_s < t.step_device_s
    assert 0 < kern.min_s < kern.device_s
