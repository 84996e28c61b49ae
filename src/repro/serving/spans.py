"""Names of the serving program's profiler spans.

Each span is a ``jax.profiler.TraceAnnotation``: with a profiler session
active it lands on the host plane of the trace, on the clock the
profiler aligns with the device planes, so the device time (and the
device's idle time) inside each host phase can be read off one trace.
With no session active an annotation costs about a microsecond, so the
spans are always on.

Nesting, all on the pump thread (``rid`` ties a request's spans
together):

  pump.schedule      scheduler.step: admission and preemption
  pump.wait          blocked on the inbox, or the idle sleep
  engine.tick        [index, active, kv_blocks, kv_steps] one decode
                     tick; streamed paged engines: kv_blocks, the live
                     kv blocks per layer, at least one per slot;
                     kv_steps, the grid steps its paged kernel launches
                     per layer, each slot's blocks in steps of K, so
                     kv_blocks / (K * kv_steps) is the live share of
                     the blocks it fetches
    tick.upload      last tokens, positions, live blocks (and the block
                     tables when they changed) to the device
    tick.step        dispatch of the tick program (returns before the
                     device finishes)
    tick.sample      logits slice, argmax or categorical draw, and the
                     copy to the host, which waits for the device
    tick.emit        per-slot bookkeeping, on_token / on_finish,
                     evictions
  engine.admit       [rid] one admission
    admit.reserve    [rid] prefix match, block allocation, the table
                     row's upload
    admit.chunk      [rid, c0] one prefill chunk: buffer, uploads,
                     dispatch
    admit.sample     [rid] the admission token's draw

``admit.reserve`` and ``admit.chunk`` are opened where the work is
(``Engine.begin_prefill``, ``PrefillJob.step``), so a disaggregated
prefill worker's chunks are named too, outside any ``engine.admit``.
"""
from __future__ import annotations

import jax

PUMP_SCHEDULE = "pump.schedule"
PUMP_WAIT = "pump.wait"
TICK = "engine.tick"
TICK_UPLOAD = "tick.upload"
TICK_STEP = "tick.step"
TICK_SAMPLE = "tick.sample"
TICK_EMIT = "tick.emit"
ADMIT = "engine.admit"
ADMIT_RESERVE = "admit.reserve"
ADMIT_CHUNK = "admit.chunk"
ADMIT_SAMPLE = "admit.sample"

NAMES = (PUMP_SCHEDULE, PUMP_WAIT, TICK, TICK_UPLOAD, TICK_STEP,
         TICK_SAMPLE, TICK_EMIT, ADMIT, ADMIT_RESERVE, ADMIT_CHUNK,
         ADMIT_SAMPLE)

span = jax.profiler.TraceAnnotation
