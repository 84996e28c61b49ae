"""Asyncio serving front end: streaming tokens over the blocking engine.

``Engine.tick()`` is a blocking jitted step — the right concurrency
model is a **thread pump**: one daemon thread owns the engine and loops
``drain submissions -> scheduler.step -> tick``, while the asyncio side
only ever touches thread-safe handoffs. Tokens cross back via
``loop.call_soon_threadsafe`` into per-request ``asyncio.Queue``s, so
``submit(req)`` returns an async iterator that yields tokens the tick
that produced them — admission and eviction decisions happen *every
tick* under whatever load is queued, not once per ``run()`` call.

    eng = Engine(model, params, paged=True, radix_cache=True)
    async with AsyncEngine(eng, scheduler=SLOScheduler()) as srv:
        stream = srv.submit(Request(rid=0, tokens=prompt),
                            priority=1, slo_ttft_ms=50)
        async for tok in stream:
            ...                       # arrives as decoded, not at end
    print(srv.metrics.snapshot(eng))

Ordering guarantee: everything that mutates the engine (admission,
preemption, tick, radix eviction) runs on the pump thread, so the
engine needs no locks and the sync ``Engine`` API stays single-threaded.
Greedy outputs are bit-identical to ``Engine.run()`` on the same
request set — per-slot logits are independent of co-scheduling, prefix
forks are bit-equal rows, and preemption resume replays the identical
graph (tested in tests/test_frontend.py). With the per-slot rid-keyed
sampler, ``temperature > 0`` streams are reproducible under async
admission reordering too.
"""
from __future__ import annotations

import asyncio
import queue
import threading
import time

from repro.serving import spans
from repro.serving.engine import Engine, Request
from repro.serving.frontend.metrics import ServingMetrics
from repro.serving.frontend.scheduler import SLOScheduler, Ticket

_DONE = object()                       # stream sentinel


class TokenStream:
    """Async iterator over one request's tokens as the engine emits
    them. ``request`` exposes the underlying ``Request`` (output,
    finish_reason) once exhausted."""

    def __init__(self, req: Request, q: asyncio.Queue):
        self.request = req
        self._q = q

    def __aiter__(self):
        return self

    async def __anext__(self) -> int:
        item = await self._q.get()
        if item is _DONE:
            raise StopAsyncIteration
        return item

    async def collect(self) -> list[int]:
        """Drain the stream to a list (== ``request.output``)."""
        return [tok async for tok in self]


class AsyncEngine:
    """Thread-pumped asyncio front end over a (sync) ``Engine``.

    ``scheduler`` defaults to ``SLOScheduler``; pass ``FIFOScheduler()``
    for the non-preemptive baseline. It stamps admissions on its own
    clock, which must be ``clock``. ``idle_wait`` is how long the pump
    blocks on the submission queue when no slot is active (it never
    busy-spins an idle engine).
    """

    def __init__(self, engine: Engine, scheduler=None, *,
                 clock=time.monotonic, idle_wait: float = 0.002):
        self.engine = engine
        if scheduler is None:
            scheduler = SLOScheduler(clock=clock)
        elif scheduler.clock is not clock:
            raise ValueError("the scheduler's clock must be the front "
                             "end's: both stamp a request's metrics")
        self.scheduler = scheduler
        self.metrics = ServingMetrics(clock=clock)
        self.idle_wait = idle_wait
        self._inbox: queue.SimpleQueue = queue.SimpleQueue()
        self._streams: dict[int, asyncio.Queue] = {}
        self._outstanding = 0          # submitted, not yet finished
        self._seq = 0
        self._loop: asyncio.AbstractEventLoop | None = None
        self._thread: threading.Thread | None = None
        self._stop = threading.Event()
        self._error: BaseException | None = None
        engine.on_token = self._on_token
        engine.on_finish = self._on_finish

    # -------------------------------------------------------- lifecycle
    async def __aenter__(self):
        self.start()
        return self

    async def __aexit__(self, *exc):
        await self.close()

    def start(self):
        if self._thread is not None:
            return
        self._loop = asyncio.get_running_loop()
        self._stop.clear()
        self._thread = threading.Thread(target=self._pump,
                                        name="serving-pump", daemon=True)
        self._thread.start()

    async def drain(self):
        """Wait until every submitted request has finished streaming."""
        while self._outstanding > 0 or not self._inbox.empty():
            self._raise_pump_error()
            await asyncio.sleep(0.002)
        self._raise_pump_error()

    async def close(self):
        """Finish in-flight work, then stop the pump thread."""
        await self.drain()
        self._stop.set()
        if self._thread is not None:
            await asyncio.to_thread(self._thread.join)
            self._thread = None
        self._raise_pump_error()

    def _raise_pump_error(self):
        if self._error is not None:
            err, self._error = self._error, None
            raise err

    # ------------------------------------------------------- submission
    def submit(self, req: Request, *, priority: int = 0,
               slo_ttft_ms: float | None = None) -> TokenStream:
        """Queue ``req`` and return its token stream. Must be called
        from the event loop thread (it owns the stream's queue). A
        request the engine could *never* serve raises here, not on the
        pump thread."""
        if self._thread is None:
            self.start()
        self.engine.check_servable(req)
        now = self.metrics.clock()
        self._seq += 1
        ticket = Ticket(
            req=req, priority=priority,
            deadline=(now + slo_ttft_ms / 1e3
                      if slo_ttft_ms is not None else None),
            arrival=now, seq=self._seq)
        q: asyncio.Queue = asyncio.Queue()
        self._streams[req.rid] = q
        self._outstanding += 1
        self.metrics.submitted(req.rid)
        self._inbox.put(ticket)
        return TokenStream(req, q)

    # ---------------------------------------------------- pump (thread)
    def _push(self, rid: int, item):
        """Thread-safe delivery into the request's asyncio queue."""
        q = self._streams.get(rid)
        if q is not None and self._loop is not None:
            self._loop.call_soon_threadsafe(q.put_nowait, item)

    def _on_token(self, req: Request, tok: int):
        self.metrics.token(req.rid)
        self._push(req.rid, tok)

    def _on_finish(self, req: Request):
        self.metrics.finished(req.rid, req.finish_reason)
        self.scheduler.note_finished(req)
        self._push(req.rid, _DONE)
        self._streams.pop(req.rid, None)   # _DONE already queued
        self._outstanding -= 1

    def _drain_inbox(self) -> int:
        n = 0
        while True:
            try:
                ticket = self._inbox.get_nowait()
            except queue.Empty:
                return n
            self.scheduler.submit(ticket)
            n += 1

    def _pump(self):
        eng = self.engine
        try:
            while True:
                self._drain_inbox()
                with spans.span(spans.PUMP_SCHEDULE):
                    rep = self.scheduler.step(eng)
                for t in rep.admitted:
                    self.metrics.admitted(t.req.rid, t.admit_started)
                for t in rep.preempted:
                    self.metrics.preempted(t.req.rid)
                if any(r is not None for r in eng.slot_req):
                    eng.tick()
                    continue
                if len(self.scheduler):
                    # queued but unadmittable with an idle engine — a
                    # transient (e.g. radix eviction lands next step);
                    # the sleep keeps a pathological state from pegging
                    # a core
                    with spans.span(spans.PUMP_WAIT):
                        time.sleep(self.idle_wait)
                    continue
                if self._stop.is_set() and self._inbox.empty():
                    return
                try:                    # idle: block for new work
                    with spans.span(spans.PUMP_WAIT):
                        ticket = self._inbox.get(timeout=self.idle_wait)
                except queue.Empty:
                    continue
                self.scheduler.submit(ticket)
        except BaseException as e:      # surfaced on the asyncio side
            self._error = e
            # fail every open stream so consumers don't hang
            for rid in list(self._streams):
                self._push(rid, _DONE)
