"""Data-parallel replica router: N independent engines, one front end.

PR 5 made one engine mesh-native over a ("data", "model") mesh's
*model* axis; this module is the data axis. ``replica_submeshes``
splits a DxM serving mesh into D disjoint (1, M) TP groups, each group
runs its own ``Engine`` (weights replicated per replica — the paper's
weight-stationary story, D times over), and ``ReplicaRouter`` spreads
requests across them under a pluggable placement policy
(``policies.py``).

The router deliberately *is* an engine to its callers: it exposes the
``Engine`` surface the async front end and the SLO scheduler consume —
``slot_req`` (flattened across replicas), ``admit`` / ``admit_from`` /
``tick`` / ``preempt`` / ``check_servable`` / ``_free_slot``,
``on_token`` / ``on_finish`` hooks, and the ``paged`` / ``allocator``
/ ``radix`` gauges — so ``AsyncEngine(router)`` streams tokens over a
whole replica fleet with zero front-end changes. Greedy outputs are
bit-identical to a single-engine oracle on the same request set
regardless of placement: per-slot sampling is keyed by (seed, rid,
token index) and cache rows depend only on their token prefix, so
*which* replica serves a request can never change its tokens (tested
in tests/test_router.py).

Each replica is either **fused** (``FusedReplica``: one engine does
prefill and decode, admission runs every prefill chunk inline — the
PR 2–8 behavior) or **disaggregated** (``disagg.DisaggReplica``: a
prefill worker and a decode worker with paged-block handoff, so a long
prompt never stalls a decode tick).

Wall-clock accounting: replicas occupy disjoint device groups, so a
deployment runs them concurrently; a single host process necessarily
steps them in sequence. The router therefore tracks both
``serial_time`` (what this process spent) and ``modeled_time``
(sum over router steps of the slowest replica's busy time that step —
the deployment's critical path).
"""
from __future__ import annotations

import time
from collections.abc import Callable, Sequence

from repro.serving.engine import Engine, Request
from repro.serving.router.policies import make_policy


class FusedReplica:
    """One fused engine behind the replica interface: admission runs
    chunked prefill inline (blocking this replica, exactly the single-
    engine behavior), ``step()`` is one decode tick."""

    def __init__(self, engine: Engine):
        if not engine.paged:
            raise ValueError("the replica router requires paged engines "
                             "(handoff and capacity signals are blocks)")
        self.engine = engine
        self.busy_s = 0.0              # admit + step seconds, cumulative

    @property
    def engines(self) -> list[Engine]:
        return [self.engine]

    def admit(self, req: Request) -> bool:
        t0 = time.perf_counter()
        ok = self.engine.admit(req)
        self.busy_s += time.perf_counter() - t0
        return ok

    def step(self) -> None:
        t0 = time.perf_counter()
        if any(r is not None for r in self.engine.slot_req):
            self.engine.tick()
        self.busy_s += time.perf_counter() - t0

    def slots(self) -> list[Request | None]:
        return list(self.engine.slot_req)

    def preempt_at(self, idx: int) -> Request:
        return self.engine.preempt(idx)

    def has_free_slot(self) -> bool:
        return self.engine._free_slot() is not None

    def free_blocks(self) -> int:
        return self.engine.allocator.num_free

    def active(self) -> int:
        return sum(r is not None for r in self.engine.slot_req)

    def peek_prefix(self, tokens) -> int:
        radix = self.engine.radix
        return 0 if radix is None else radix.peek(tokens)

    def check_servable(self, req: Request) -> None:
        self.engine.check_servable(req)


class _AllocatorView:
    """Aggregate block gauges over every replica allocator — what
    ``ServingMetrics.snapshot`` reads off the router."""

    def __init__(self, allocators: Sequence):
        self._allocs = list(allocators)

    @property
    def num_free(self) -> int:
        return sum(a.num_free for a in self._allocs)

    @property
    def num_usable(self) -> int:
        return sum(a.num_usable for a in self._allocs)

    @property
    def num_live(self) -> int:
        return sum(a.num_live for a in self._allocs)

    @property
    def num_pinned(self) -> int:
        return sum(a.num_pinned for a in self._allocs)


class _RadixView:
    """Merged radix-cache stats across replicas (counters sum; the
    aggregate hit rate re-derives from the summed counters)."""

    def __init__(self, caches: Sequence):
        self._caches = list(caches)

    def stats(self) -> dict:
        out: dict = {}
        for c in self._caches:
            for k, v in c.stats().items():
                if k != "hit_rate":
                    out[k] = out.get(k, 0) + v
        out["hit_rate"] = (out["hit_blocks"] / out["lookup_blocks"]
                          if out.get("lookup_blocks") else 0.0)
        return out


class ReplicaRouter:
    """Engine-shaped front over N replicas (see module docstring)."""

    def __init__(self, replicas: Sequence, *, policy="least_loaded",
                 admit_scan: int = 8):
        if not replicas:
            raise ValueError("need at least one replica")
        self.replicas = list(replicas)
        self.policy = make_policy(policy)
        self.admit_scan = admit_scan
        self.paged = True
        self.on_token: Callable | None = None
        self.on_finish: Callable | None = None
        # every engine's hooks forward to the router's current hooks
        # (read at call time: AsyncEngine installs its handlers on the
        # router AFTER construction)
        for rep in self.replicas:
            for eng in rep.engines:
                eng.on_token = self._fwd_token
                eng.on_finish = self._fwd_finish
        self.ticks = 0                  # router steps
        self.peak_active = 0            # most slots active at once
        self.serial_time = 0.0          # sum of replica busy seconds
        self.modeled_time = 0.0         # sum of per-step max busy
        self._busy_prev = [rep.busy_s for rep in self.replicas]

    # ----------------------------------------------------- construction
    @classmethod
    def for_mesh(cls, model, params, mesh, *, policy="least_loaded",
                 disaggregate: bool = False, prefill_slots: int = 2,
                 admit_scan: int = 8, **engine_kw) -> "ReplicaRouter":
        """Build one replica per data-axis index of a ("data", "model")
        mesh: each gets its own (1, M) submesh over disjoint devices
        (weights replicate across replicas, shard over each replica's
        model axis). ``disaggregate=True`` splits every replica into a
        ``prefill_slots``-slot prefill worker and a decode worker
        (``engine_kw`` sizes the decode side)."""
        import jax

        from repro.launch.mesh import replica_submeshes
        from repro.serving.router.disagg import DisaggReplica
        from repro.sharding import specs

        replicas: list = []
        for sub in replica_submeshes(mesh):
            # one copy of the weights per replica: its prefill and decode
            # workers read the same arrays (Engine's own device_put of an
            # array already laid out on ``sub`` is a no-op)
            params_sub = jax.device_put(
                params, specs.param_shardings(params, sub))
            if disaggregate:
                pre_kw = dict(engine_kw)
                # the prefill worker only ever holds prompt blocks, and
                # its radix cache is where recurring prefixes pay off;
                # the decode side frees its copy of both
                pre_kw.update(max_slots=prefill_slots, prefill_only=True)
                dec_kw = dict(engine_kw)
                dec_kw.pop("radix_cache", None)
                pre = Engine(model, params_sub, mesh=sub, **pre_kw)
                dec = Engine(model, params_sub, mesh=sub, **dec_kw)
                replicas.append(DisaggReplica(pre, dec))
            else:
                replicas.append(FusedReplica(
                    Engine(model, params_sub, mesh=sub, **engine_kw)))
        return cls(replicas, policy=policy, admit_scan=admit_scan)

    # ----------------------------------------------------------- hooks
    def _fwd_token(self, req: Request, tok: int):
        if self.on_token:
            self.on_token(req, tok)

    def _fwd_finish(self, req: Request):
        if self.on_finish:
            self.on_finish(req)

    # ---------------------------------------------------- engine surface
    @property
    def slot_req(self) -> list[Request | None]:
        """Every resident request across replicas, flattened in a
        stable per-replica order — schedulers index into this and hand
        the index straight to ``preempt``, so both sides derive it from
        the same ``slots()`` layout."""
        return [r for rep in self.replicas for r in rep.slots()]

    def check_servable(self, req: Request) -> None:
        # replicas are homogeneous: replica 0 speaks for the fleet
        self.replicas[0].check_servable(req)

    def _free_slot(self) -> int | None:
        for i, rep in enumerate(self.replicas):
            if rep.has_free_slot():
                return i
        return None

    def admit(self, req: Request) -> bool:
        """Place ``req`` on the best replica that will take it now
        (policy ranking, first success wins)."""
        self.check_servable(req)
        for idx in self.policy.rank(self, req):
            if self.replicas[idx].admit(req):
                self._note_active()
                return True
        return False

    def _note_active(self):
        self.peak_active = max(self.peak_active,
                               sum(r is not None for r in self.slot_req))

    def admit_from(self, pending: list[Request]) -> int:
        """Engine-compatible bounded head-of-line scan over
        ``pending`` (see ``Engine.admit_from``)."""
        admitted = 0
        progress = True
        while progress and pending and self._free_slot() is not None:
            progress = False
            for i, r in enumerate(pending[:self.admit_scan]):
                if self.admit(r):
                    pending.pop(i)
                    admitted += 1
                    progress = True
                    break
        return admitted

    def preempt(self, slot: int) -> Request:
        """Preempt the request at flattened-``slot_req`` index
        ``slot`` (evict-to-queue, resumable on ANY replica — cache
        rows rebuild bit-equal from the token prefix wherever the
        re-admission lands)."""
        for rep in self.replicas:
            n = len(rep.slots())
            if slot < n:
                return rep.preempt_at(slot)
            slot -= n
        raise ValueError(f"slot {slot} out of range")

    def tick(self) -> None:
        """One router step: every replica advances (prefill chunk,
        handoffs, decode tick). Updates the serial/modeled wall-time
        split described in the module docstring."""
        for rep in self.replicas:
            rep.step()
        self._note_active()
        deltas = []
        for i, rep in enumerate(self.replicas):
            deltas.append(rep.busy_s - self._busy_prev[i])
            self._busy_prev[i] = rep.busy_s
        self.ticks += 1
        self.serial_time += sum(deltas)
        self.modeled_time += max(deltas)

    def run(self, requests: list[Request], max_ticks: int = 10_000
            ) -> list[Request]:
        """Continuous batching across the fleet (``Engine.run``
        semantics: admit whatever fits as slots free, tick until
        done)."""
        pending = list(requests)
        for _ in range(max_ticks):
            self.admit_from(pending)
            if not pending and all(r is None for r in self.slot_req):
                break
            self.tick()
        return requests

    # ----------------------------------------------------------- gauges
    @property
    def engines(self) -> list[Engine]:
        return [e for rep in self.replicas for e in rep.engines]

    @property
    def allocator(self) -> _AllocatorView:
        return _AllocatorView([e.allocator for e in self.engines])

    @property
    def radix(self) -> _RadixView | None:
        caches = [e.radix for e in self.engines if e.radix is not None]
        return _RadixView(caches) if caches else None

    @property
    def preemptions(self) -> int:
        return sum(e.preemptions for e in self.engines)

    @property
    def slot_ticks(self) -> int:
        """Active slots summed over every engine's ticks: over router
        steps (``ticks``), the fleet's mean active slots per step."""
        return sum(e.slot_ticks for e in self.engines)

    def pool_bytes_per_device(self) -> int:
        return max(e.pool_bytes_per_device() for e in self.engines)
