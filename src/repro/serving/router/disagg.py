"""Disaggregated prefill/decode replica: two engines, one block handoff.

The fused engine runs every prefill chunk inside ``admit`` — a long
prompt admitted between ticks stalls every co-resident decode by the
full chunk loop. Disaggregation splits the replica into:

  * a **prefill worker**: an ``Engine(prefill_only=True)`` that only
    builds cache blocks. Admission reserves prompt blocks alone (the
    decode budget is reserved at adoption) and ``PrefillJob.step()``
    advances ONE chunk per router step, interleaved with the decode
    worker's ticks — the interference a decode tick sees is bounded by
    one chunk, not one prompt.
  * a **decode worker**: a normal engine that never prefills. It
    ``adopt``s finished prefills — chunked prefill already emits pool
    blocks in exactly the layout decode consumes, so the handoff is
    ``paged.export_blocks`` (bit-copy of the written blocks) plus a
    table splice, and the continuation is bit-identical to the fused
    engine (tests/test_router.py).

Backpressure instead of floating state: a completed prefill stays
resident on the prefill worker (slot + blocks held) until
``decode.can_adopt`` says the decode side has a slot AND the full
decode-budget blocks — only then does ``export_sequence`` release it.
Nothing is ever in neither engine, so a crash/preemption at any step
finds every request owned by exactly one allocator.

Preemption covers all three residencies: decode slots and completed
prefill slots evict-to-queue through the normal ``Engine.preempt``
(resume replays bit-identically, on any replica); an in-flight
``PrefillJob`` is cancelled — its blocks return and the request
re-prefills from scratch on re-admission.
"""
from __future__ import annotations

import time

from repro.serving import paged as paged_lib
from repro.serving.engine import Engine, Request


class DisaggReplica:
    """One prefill worker + one decode worker behind the replica
    interface (see ``router.FusedReplica`` for the fused twin)."""

    def __init__(self, prefill: Engine, decode: Engine):
        if not prefill.prefill_only:
            raise ValueError("prefill worker must be built with "
                             "prefill_only=True")
        if decode.prefill_only or not decode.paged:
            raise ValueError("decode worker must be a normal paged engine")
        if prefill.block_size != decode.block_size:
            raise ValueError(
                f"block_size mismatch: prefill {prefill.block_size} vs "
                f"decode {decode.block_size} — handoff moves whole blocks")
        self.prefill = prefill
        self.decode = decode
        self.handoffs = 0              # sequences migrated prefill→decode
        self.busy_s = 0.0

    @property
    def engines(self) -> list[Engine]:
        return [self.prefill, self.decode]

    # -------------------------------------------------------- admission
    def admit(self, req: Request) -> bool:
        """Start (not run) the prefill: reserve a prefill-worker slot
        and prompt blocks; chunks advance one per ``step()``."""
        t0 = time.perf_counter()
        job = self.prefill.begin_prefill(req)
        self.busy_s += time.perf_counter() - t0
        return job is not None

    def check_servable(self, req: Request) -> None:
        # both halves must be able to hold the request at all
        self.prefill.check_servable(req)
        self.decode.check_servable(req)

    def has_free_slot(self) -> bool:
        return self.prefill._free_slot() is not None

    # ------------------------------------------------------------- step
    def _jobs(self):
        pre = self.prefill
        return [pre._prefilling[s] for s in sorted(pre._prefilling)]

    def step(self) -> None:
        """One router step: advance the oldest in-flight prefill by ONE
        chunk (completing jobs run the admission epilogue on the
        prefill worker), hand off every completed sequence the decode
        side can take right now, then one decode tick."""
        t0 = time.perf_counter()
        pre, dec = self.prefill, self.decode
        jobs = self._jobs()
        if jobs:
            job = jobs[0]
            if job.step():
                pre._post_admit(job.req, job.slot, job.resume)
        for slot, req in enumerate(pre.slot_req):
            if req is None:
                continue
            # capacity probe BEFORE export so the sequence never
            # leaves the prefill worker without a confirmed home
            probe = paged_lib.SequenceHandoff(
                req=req, blob=None,
                n_blocks=paged_lib.blocks_for(int(pre.pos[slot]),
                                              pre.block_size),
                pos=int(pre.pos[slot]), last_tok=int(pre.last_tok[slot]),
                block_size=pre.block_size)
            if not dec.can_adopt(probe):
                continue
            handoff = pre.export_sequence(slot)
            if dec.adopt_sequence(handoff) is None:
                raise RuntimeError(
                    "adopt_sequence failed after can_adopt — decode "
                    "worker state changed mid-step")
            self.handoffs += 1
        if any(r is not None for r in dec.slot_req):
            dec.tick()
        self.busy_s += time.perf_counter() - t0

    # ------------------------------------------------------- residency
    def slots(self) -> list[Request | None]:
        """Stable flattened residency: decode slots, completed prefill
        slots, then in-flight jobs (slot order) — ``preempt_at``
        decodes indices against this exact layout."""
        return (list(self.decode.slot_req) + list(self.prefill.slot_req)
                + [j.req for j in self._jobs()])

    def preempt_at(self, idx: int) -> Request:
        nd = len(self.decode.slot_req)
        if idx < nd:
            return self.decode.preempt(idx)
        idx -= nd
        npre = len(self.prefill.slot_req)
        if idx < npre:
            # completed-awaiting-adoption: ordinary evict-to-queue (the
            # admission token already in req.output makes it a resume)
            return self.prefill.preempt(idx)
        idx -= npre
        jobs = self._jobs()
        if idx >= len(jobs):
            raise ValueError(f"replica slot {idx} out of range")
        job = jobs[idx]
        req = job.req
        job.cancel()
        req.finish_reason = "preempted"
        self.prefill.preemptions += 1
        return req

    # ----------------------------------------------------------- gauges
    def free_blocks(self) -> int:
        return (self.decode.allocator.num_free
                + self.prefill.allocator.num_free)

    def active(self) -> int:
        return sum(r is not None for r in self.slots())

    def peek_prefix(self, tokens) -> int:
        radix = self.prefill.radix
        return 0 if radix is None else radix.peek(tokens)
