"""The serving program's profiler spans: a tiny run through the async
front end with the profiler on names every phase, nested as
``repro.serving.spans`` says, with the request on admission spans and
the paged kernel's launched kv blocks on each tick."""
import asyncio
import dataclasses
import glob
import os

import jax
import numpy as np
import pytest

from repro.configs.base import get_arch, reduced
from repro.models.model import build_model
from repro.serving import spans
from repro.serving.engine import Engine, Request
from repro.serving.frontend import AsyncEngine

# each span's parent; None: opened by the pump outside any other
PARENT = {
    spans.PUMP_SCHEDULE: None, spans.PUMP_WAIT: None, spans.TICK: None,
    spans.TICK_UPLOAD: spans.TICK, spans.TICK_STEP: spans.TICK,
    spans.TICK_SAMPLE: spans.TICK, spans.TICK_EMIT: spans.TICK,
    spans.ADMIT: spans.PUMP_SCHEDULE, spans.ADMIT_RESERVE: spans.ADMIT,
    spans.ADMIT_CHUNK: spans.ADMIT, spans.ADMIT_SAMPLE: spans.ADMIT,
}
STATS = {spans.TICK: {"index", "active", "kv_blocks", "kv_steps"},
         spans.ADMIT: {"rid"},
         spans.ADMIT_RESERVE: {"rid"}, spans.ADMIT_CHUNK: {"rid", "c0"},
         spans.ADMIT_SAMPLE: {"rid"}}


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """(spans by name, requests, each traced tick's blocks_used):
    spans as (start, end, stats, line)."""
    cfg = dataclasses.replace(
        reduced(get_arch("qwen2.5-14b"), num_layers=2), dtype="float32")
    model = build_model(cfg)
    eng = Engine(model, model.init(jax.random.PRNGKey(0)), max_slots=2,
                 max_len=64, paged=True, block_size=8, prefill_chunk=16)
    reqs = [Request(rid=10 + i, tokens=[1] + list(range(3, 3 + n)),
                    max_new_tokens=4) for i, n in enumerate((5, 20, 9))]
    eng.run([Request(rid=-1, tokens=[1] * 20, max_new_tokens=2)])
    launched, step = [], eng._decode_paged

    def spy(params, pool, tables, tokens, pos, used, tick):
        if tick:                         # the blocks_used the tick runs
            launched.append(np.asarray(used))
        return step(params, pool, tables, tokens, pos, used, tick)
    eng._decode_paged = spy

    async def serve():
        async with AsyncEngine(eng) as srv:
            for s in [srv.submit(r) for r in reqs]:
                await s.collect()

    out = str(tmp_path_factory.mktemp("trace"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(out, profiler_options=opts)
    try:
        asyncio.run(serve())
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(out, "**", "*.xplane.pb"),
                      recursive=True)
    found: dict = {}
    for p in jax.profiler.ProfileData.from_file(path).planes:
        for i, line in enumerate(p.lines):
            for e in line.events:
                if e.name in spans.NAMES:
                    found.setdefault(e.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns,
                         dict(e.stats), (p.name, i)))
    return found, reqs, launched


def test_every_span_is_written(traced):
    found, *_ = traced
    assert set(found) == set(spans.NAMES)


@pytest.mark.parametrize("name", [n for n in spans.NAMES if PARENT[n]])
def test_span_nests_in_its_parent(traced, name):
    found, *_ = traced
    for a, b, _, line in found[name]:
        assert any(pa <= a and b <= pb and pline == line
                   for pa, pb, _, pline in found[PARENT[name]]), (name, a)


@pytest.mark.parametrize("name", sorted(STATS))
def test_span_carries_its_stats(traced, name):
    found, reqs, _ = traced
    rids = {r.rid for r in reqs}
    for *_, stats, _ in found[name]:
        assert STATS[name] <= set(stats)
        if "rid" in stats:
            assert stats["rid"] in rids
    if name == spans.ADMIT:
        assert {s["rid"] for *_, s, _ in found[name]} == rids


def test_tick_spans_count_the_ticks(traced):
    found, *_ = traced
    ticks = found[spans.TICK]
    index = [s["index"] for *_, s, _ in ticks]
    assert index == list(range(index[0], index[0] + len(ticks)))
    assert all(1 <= s["active"] <= 2 for *_, s, _ in ticks)
    # a tick's four phases, once each, in order
    for a, b, _, line in ticks:
        inner = sorted((x[0], n) for n in (spans.TICK_UPLOAD,
                                           spans.TICK_STEP,
                                           spans.TICK_SAMPLE,
                                           spans.TICK_EMIT)
                       for x in found[n] if a <= x[0] <= b and x[3] == line)
        assert [n for _, n in inner] == [spans.TICK_UPLOAD, spans.TICK_STEP,
                                         spans.TICK_SAMPLE, spans.TICK_EMIT]


def test_tick_span_counts_the_launched_kv_blocks(traced):
    """``kv_blocks`` on each tick is the sum of the clipped
    ``blocks_used`` that tick hands the paged kernel: its live
    blocks."""
    found, _, launched = traced
    kv = [s["kv_blocks"] for *_, s, _ in found[spans.TICK]]
    assert kv == [int(u.sum()) for u in launched] and len(kv) >= 4
    assert all(2 <= k <= 2 * 8 for k in kv)    # 2 slots, 8-block rows


def test_tick_span_counts_the_kernel_grid_steps(traced):
    """``kv_steps`` on each tick is the paged kernel's grid: each
    slot's live blocks in steps of K. The 8-block rows of 8-token
    blocks take K = min(8, 128 // 8) = 8, so a step a slot."""
    from repro.kernels.paged_attention import kernel as pkernel
    found, _, launched = traced
    K = min(8, pkernel.LANES // 8)
    steps = [s["kv_steps"] for *_, s, _ in found[spans.TICK]]
    assert steps == [int((-(-u // K)).sum()) for u in launched]
    assert all(s == len(u) for s, u in zip(steps, launched))


def test_tick_and_chunk_compile_once_each_under_their_names():
    """An engine compiles its tick and its chunk once each, under the
    names a trace shows, and nothing under the graph's own name: as
    many programs as when both ran as ``decode_paged``."""
    cfg = dataclasses.replace(
        reduced(get_arch("qwen2.5-14b"), num_layers=1), dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    names, on = [], [True]

    def listen(event, duration, fun_name=None, **kw):
        if on[0] and event == "/jax/core/compile/backend_compile_duration":
            names.append(fun_name)

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        Engine(model, params, max_slots=2, max_len=64, paged=True,
               block_size=8, prefill_chunk=16).run(
            [Request(rid=0, tokens=[1] * 20, max_new_tokens=3)])
    finally:
        on[0] = False
    assert names.count("jit(decode_tick)") == 1
    assert names.count("jit(prefill_chunk)") == 1
    assert "jit(decode_paged)" not in names
