"""SLO-aware admission scheduling over the continuous-batching engine.

The engine knows how to admit, tick and evict; it has no opinion about
*which* pending request deserves the next free slot or whether a
running request should give its blocks up. That policy lives here.

Two policies, one protocol (``submit(ticket)`` + ``step(engine)``):

``FIFOScheduler`` — strict arrival order, head-of-line admission only,
no preemption. This is the batch-sync ``Engine.run()`` behavior lifted
into the tick loop, kept as the benchmark baseline.

``SLOScheduler`` — every tick it (1) orders the pending queue by
``(-priority, deadline, arrival)``; (2) scans up to ``scan_limit``
tickets and admits *any* that fit right now (a blocked head never
starves a smaller request behind it); (3) if the most urgent ticket is
still blocked on resources and a strictly lower-priority request is
running, preempts the victim — ``Engine.preempt`` evicts it to the
queue (lossless: the refcounted allocator keeps forked prefix blocks
alive, and greedy resume is bit-identical, see DESIGN.md §13) — and
retries the urgent admission immediately. At most
``max_preemptions_per_step`` victims per tick bounds thrash.

Deadlines order admission (earliest first within a priority class);
preemption triggers on *strict priority* only — a deadline can say
"serve me sooner", not "throw someone else out".
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque

from repro.serving.engine import Engine, Request


@dataclasses.dataclass
class Ticket:
    """One scheduled request: the engine ``Request`` plus the policy
    fields the scheduler trades on. ``deadline`` is absolute seconds on
    the front end's clock (e.g. ``arrival + slo_ttft``); None = no SLO.
    Higher ``priority`` = more urgent."""
    req: Request
    priority: int = 0
    deadline: float | None = None
    arrival: float = 0.0
    seq: int = 0                    # submission order tiebreak
    preemptions: int = 0
    admit_started: float | None = None  # latest admission's start


@dataclasses.dataclass
class StepReport:
    """What one scheduler step did (the front end feeds metrics and
    stream bookkeeping from this)."""
    admitted: list[Ticket] = dataclasses.field(default_factory=list)
    preempted: list[Ticket] = dataclasses.field(default_factory=list)


class FIFOScheduler:
    """Arrival order, head-only, non-preemptive — the sync baseline.

    ``clock`` stamps when each admission starts (``Ticket.
    admit_started``); injectable for deterministic tests.
    """

    preemptive = False

    def __init__(self, *, clock=time.monotonic):
        self.pending: deque[Ticket] = deque()
        self.running: dict[int, Ticket] = {}
        self.clock = clock

    def submit(self, ticket: Ticket):
        self.pending.append(ticket)

    def __len__(self):
        return len(self.pending)

    def _admit(self, engine: Engine, t: Ticket, rep: StepReport) -> bool:
        """Try to admit ``t`` now; on success stamp when the admission
        started and report it."""
        started = self.clock()
        if not engine.admit(t.req):
            return False
        t.admit_started = started
        rep.admitted.append(t)
        if not t.req.done:             # admission itself may finish it
            self.running[t.req.rid] = t
        return True

    def note_finished(self, req: Request):
        self.running.pop(req.rid, None)

    def step(self, engine: Engine) -> StepReport:
        rep = StepReport()
        while self.pending and engine._free_slot() is not None:
            if not self._admit(engine, self.pending[0], rep):
                break                   # head blocked: FIFO waits
            self.pending.popleft()
        return rep


class SLOScheduler(FIFOScheduler):
    """Priority + deadline ordering, queue-scan admission, preemption.

    ``clock`` is injectable for deterministic tests.
    """

    preemptive = True

    def __init__(self, *, scan_limit: int = 8,
                 max_preemptions_per_step: int = 1,
                 clock=time.monotonic):
        super().__init__(clock=clock)
        self.scan_limit = scan_limit
        self.max_preemptions_per_step = max_preemptions_per_step

    @staticmethod
    def _key(t: Ticket):
        return (-t.priority,
                t.deadline if t.deadline is not None else float("inf"),
                t.seq)

    def step(self, engine: Engine) -> StepReport:
        rep = StepReport()
        # self-heal: finished requests leave running even when nobody
        # wired note_finished (direct scheduler use in tests/benches)
        self.running = {rid: t for rid, t in self.running.items()
                        if not t.req.done and t.req in engine.slot_req}
        order = sorted(self.pending, key=self._key)
        self.pending = deque(order)

        # (2) scan admission: any of the first scan_limit that fits now
        scanned, i = 0, 0
        pend = self.pending
        while i < len(pend) and scanned < self.scan_limit \
                and engine._free_slot() is not None:
            if self._admit(engine, pend[i], rep):
                del pend[i]
            else:
                i += 1
                scanned += 1

        # (3) preemption: urgent still blocked + strictly lower-priority
        # victim running -> evict-to-queue, retry urgent immediately
        for _ in range(self.max_preemptions_per_step):
            if not pend:
                break
            urgent = pend[0]
            victims = [t for t in self.running.values()
                       if t.priority < urgent.priority]
            if not victims:
                break
            # lowest priority first; among equals the newest arrival
            # (least decode progress to redo on resume)
            victim = min(victims, key=lambda t: (t.priority, -t.seq))
            slot = engine.slot_req.index(victim.req)
            engine.preempt(slot)
            del self.running[victim.req.rid]
            victim.preemptions += 1
            pend.append(victim)
            rep.preempted.append(victim)
            if self._admit(engine, urgent, rep):
                pend.remove(urgent)
        return rep
