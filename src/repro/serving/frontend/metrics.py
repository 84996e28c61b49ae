"""Serving metrics: per-request latency accounting + engine gauges.

The async front end is only worth having if its latency story is
measurable: TTFT (time to first token — the SLO the scheduler trades
on), inter-token latency, queue wait, and preemption counts per
request, plus engine-level gauges read off the engine's own counters
at snapshot time (ticks, active slots, free blocks, radix-cache
residency/hit rate). Everything is plain host floats fed by the
engine's ``on_token``/``on_finish`` hooks and the scheduler's step
report — the jitted serving path is untouched.

``snapshot()`` exports one JSON-able dict (``launch/serve.py`` prints
it).
"""
from __future__ import annotations

import dataclasses
import time


@dataclasses.dataclass
class RequestMetrics:
    """Wall-clock milestones of one request (absolute seconds on the
    injected clock; derived durations via the properties)."""
    rid: int
    submitted: float
    admitted: float | None = None      # start of the first admission
    first_token: float | None = None
    finished: float | None = None
    finish_reason: str | None = None
    tokens: int = 0
    preemptions: int = 0
    token_times: list[float] = dataclasses.field(default_factory=list)

    @property
    def ttft(self) -> float | None:
        """Submit -> first streamed token (the SLO quantity)."""
        if self.first_token is None:
            return None
        return self.first_token - self.submitted

    @property
    def queue_wait(self) -> float | None:
        """Submit -> start of the first admission (pure scheduler
        delay: neither its own prefill nor that of a request admitted
        before it in the same scheduler step)."""
        if self.admitted is None:
            return None
        return self.admitted - self.submitted

    @property
    def inter_token(self) -> list[float]:
        """Gaps between consecutive streamed tokens (preemption gaps
        included — that is the latency the client actually sees)."""
        tt = self.token_times
        return [b - a for a, b in zip(tt, tt[1:], strict=False)]


def _percentile(values: list[float], q: float) -> float | None:
    """Nearest-rank percentile (q in [0, 100]); None on empty input.
    Stdlib-only, so this module imports without jax/numpy."""
    if not values:
        return None
    v = sorted(values)
    idx = min(len(v) - 1, max(0, round(q / 100.0 * (len(v) - 1))))
    return v[idx]


class ServingMetrics:
    """Aggregator: one ``RequestMetrics`` per rid; engine gauges come
    from the engine passed to ``snapshot``.

    ``clock`` is injectable for deterministic tests; production uses
    ``time.monotonic``.
    """

    def __init__(self, clock=time.monotonic):
        self.clock = clock
        self.requests: dict[int, RequestMetrics] = {}
        self.preemptions = 0           # engine-wide counter

    # ----------------------------------------------------------- events
    def submitted(self, rid: int) -> RequestMetrics:
        m = RequestMetrics(rid=rid, submitted=self.clock())
        self.requests[rid] = m
        return m

    def admitted(self, rid: int, started: float | None = None):
        """``rid`` was admitted; its admission started at ``started``
        (default: now)."""
        m = self.requests.get(rid)
        if m is not None and m.admitted is None:
            m.admitted = self.clock() if started is None else started

    def token(self, rid: int):
        m = self.requests.get(rid)
        if m is None:
            return
        now = self.clock()
        if m.first_token is None:
            m.first_token = now
        m.tokens += 1
        m.token_times.append(now)

    def preempted(self, rid: int):
        self.preemptions += 1
        m = self.requests.get(rid)
        if m is not None:
            m.preemptions += 1

    def finished(self, rid: int, reason: str | None):
        m = self.requests.get(rid)
        if m is not None:
            m.finished = self.clock()
            m.finish_reason = reason

    # ---------------------------------------------------------- exports
    def snapshot(self, engine=None) -> dict:
        """One JSON-able dict: latency percentiles (seconds), totals,
        and the engine's gauges (plus radix stats when the engine has
        the cache attached). The tick gauges are the engine's totals
        since its construction: ``active_mean`` is active slots per
        tick, ``active_max`` the most slots ever active at once."""
        done = [m for m in self.requests.values()
                if m.finished is not None]
        ttfts = [m.ttft for m in done if m.ttft is not None]
        waits = [m.queue_wait for m in done if m.queue_wait is not None]
        itls = [g for m in done for g in m.inter_token]
        out = {
            "requests": {
                "submitted": len(self.requests),
                "finished": len(done),
                "preemptions": self.preemptions,
                "tokens": sum(m.tokens for m in self.requests.values()),
            },
            "ttft_s": {
                "p50": _percentile(ttfts, 50),
                "p99": _percentile(ttfts, 99),
                "max": max(ttfts) if ttfts else None,
            },
            "inter_token_s": {
                "p50": _percentile(itls, 50),
                "p99": _percentile(itls, 99),
            },
            "queue_wait_s": {
                "p50": _percentile(waits, 50),
                "p99": _percentile(waits, 99),
            },
            "requests_detail": [
                {"rid": m.rid, "ttft_s": m.ttft,
                 "queue_wait_s": m.queue_wait, "tokens": m.tokens,
                 "preemptions": m.preemptions,
                 "finish_reason": m.finish_reason}
                for m in sorted(self.requests.values(),
                                key=lambda m: m.rid)],
            "gauges": _gauges(engine),
        }
        if engine is not None and getattr(engine, "radix", None) is not None:
            out["radix"] = engine.radix.stats()
        return out


def _gauges(engine) -> dict:
    ticks = engine.ticks if engine is not None else 0
    paged = engine is not None and engine.paged
    return {
        "ticks": ticks,
        "active_mean": engine.slot_ticks / ticks if ticks else 0.0,
        "active_max": engine.peak_active if engine is not None else 0,
        "free_blocks": engine.allocator.num_free if paged else None,
        "pinned_blocks": engine.allocator.num_pinned if paged else None,
    }
