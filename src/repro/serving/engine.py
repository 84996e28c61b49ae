"""Batched serving engine with continuous batching — dense or paged cache.

A fixed set of ``max_slots`` decode slots; requests are admitted into
free slots, every engine tick runs ONE jitted decode step for all active
slots, finished sequences (EOS or max_new_tokens) free their slot
immediately — classic continuous batching (Orca/vLLM style), expressed
with static-shape graphs so the TPU never recompiles.

Two cache regimes:

**Dense** (training-style pool, and the fallback for families the paged
cache does not cover yet): a ``[max_slots, max_len]`` cache; prefill
uses a per-request graph over bucketed prompt lengths and copies the
filled rows into the pool at the slot index.

**Paged** (default where supported): the cache is a pool of fixed-size
token blocks (``serving/paged.py``) and each sequence holds a block
table. Admission *asks the allocator* — it reserves
``ceil((plen + max_new)/block_size)`` blocks (minus any prompt-prefix
blocks forked copy-on-write from an active sequence with the same
prompt prefix) and the request stays queued when the pool can't serve
it. Prompts stream through **chunked prefill**: fixed-size chunks
through the same ``model.decode_paged`` graph that serves decode ticks,
so the engine compiles exactly two shapes — ``(1, chunk)`` and
``(max_slots, 1)`` — instead of one prefill graph per prompt-length
bucket. Eviction frees blocks back to the allocator.

Paged decode runs one of two schedules (``decode_schedule``): the
default **stream** schedule passes per-slot used lengths
(``ceil((pos+1)/block_size)``) into the decode graph, which streams
physical blocks through online softmax and early-exits past the
longest live sequence — tick cost scales with actual sequence length,
not ``max_len``. **gather** forces the dense logical-view path (the
parity oracle).

Sampling is greedy at ``Request.temperature == 0`` and categorical at
``temperature > 0``. Categorical draws are keyed **per slot** by
``(engine seed, rid, token index)`` — a request samples the same
tokens solo, batched, or resumed after preemption, so outputs stay
reproducible under async admission reordering. Every finished request
records ``finish_reason``: ``"eos"`` (sampled its eos_id), ``"length"``
(max_new_tokens reached), or ``"truncated"`` (hit the ``max_len - 1``
context wall with budget left).

**Serving front end** (``serving/frontend/``): the engine stays a
blocking tick machine; the asyncio layer (``AsyncEngine``) pumps it
from a thread, the SLO scheduler drives ``admit``/``preempt`` every
tick, and ``radix_cache=True`` attaches the radix-tree prefix cache so
prompt prefixes are forked from *historical* requests, not just
co-resident ones (LRU-evicted when admission needs the blocks back).
``preempt(slot)`` evicts a running request back to the queue marked
``finish_reason="preempted"``; re-``admit`` detects prior output and
resumes losslessly — the rebuilt cache rows are bit-equal because each
row depends only on its token prefix. ``on_token``/``on_finish`` hooks
fire host-side per appended token / finished request (None by default:
the sync path is unchanged).

**Profiler names** (``serving/spans.py``): the tick and the chunk run
as two jitted programs over the one ``decode_paged`` graph, named
``decode_tick`` and ``prefill_chunk`` in a trace; each host phase of a
tick and of an admission is a named span; and the engine keeps
monotonic counters (``ticks``, ``slot_ticks``, ``peak_active``) that
readers difference over a window.

``capture_trace=True`` attaches a ``repro.sim`` score-trace hook: every
prefill chunk and decode tick records its quantized score-operand
shapes (logical + schedule-padded) and exact bit-sparsity tallies into
``engine.trace`` for replay through the cycle-level CIM macro
simulator (``launch/simulate.py``). The hook is pure host-side integer
bookkeeping behind an ``if`` — the jitted serving path is untouched.

**Tensor-parallel serving** (``mesh=``): pass a ``("data", "model")``
mesh (``launch/mesh.parse_mesh("1x4")``) and the engine goes
mesh-native — exactly the paper's scale-out story (weights stay
resident per macro; only raw inputs stream):

  * params shard with the training rules (``sharding/specs.spec_for``:
    heads over "model" for wq/wk/wv, the folded W_QK per head);
  * the paged block pool shards head-wise over "model"
    (``specs.paged_pool_shardings``) — each device holds only its
    head-slice of every block, so a pod's aggregate HBM backs the pool
    while ``hbm_bytes`` is read as a PER-DEVICE budget
    (``PagedCacheBudget.max_blocks(hbm, mesh)``);
  * block tables, ``blocks_used``, tokens and positions replicate, so
    the allocator, copy-on-write prefix sharing and eviction run
    unchanged host-side;
  * prefill chunks and decode ticks run the same jitted graphs under
    ``NamedSharding``; the paged kernel runs per device under
    ``shard_map`` on its head-slice (``models/attention._stream_kv``)
    and per-head partials stay on their shard, so the only TP
    collective per attention layer is the one combine at the wo
    projection. On a TPU mesh, X-stream plans are refused
    (``check_mesh_plan``): their pool rows split D, which the kernel
    contracts over.

Backends whose score path cannot split by head (``plan.shards_heads``
False, e.g. ``factored``'s shared K projection) fall back to a
replicated pool with a warning instead of crashing. ``mesh=None`` (the
default) touches none of this — outputs are bit-identical to the
single-device engine; a degenerate 1x1 mesh runs the mesh code path
with identical numerics.
"""
from __future__ import annotations

import dataclasses
import warnings
from collections.abc import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.serving import paged as paged_lib
from repro.serving import spans
from repro.sharding import act


@dataclasses.dataclass
class Request:
    rid: int
    tokens: list[int]                      # prompt
    max_new_tokens: int = 32
    temperature: float = 0.0               # 0 => greedy
    eos_id: int | None = 2
    # engine-filled:
    output: list[int] = dataclasses.field(default_factory=list)
    done: bool = False
    finish_reason: str | None = None    # eos | length | truncated


def _bucket(n: int) -> int:
    b = 8
    while b < n:
        b <<= 1
    return b


class NonDividingShardWarning(UserWarning):
    """A pool leaf's head axis does not divide the model axis: the
    layout fell back to head-dim sharding or replication, and the
    streamed decode gather re-materializes those leaves every tick
    (fallback-correct, but with extra collectives — the PR 5 known
    issue). Structured so callers/tests can filter on the category and
    inspect the offending layout."""

    def __init__(self, message: str, *, model_size: int,
                 shapes: tuple[tuple[int, ...], ...]):
        super().__init__(message)
        self.model_size = model_size
        self.shapes = shapes


# one warning per distinct (model-axis extent, offending leaf shapes) —
# every engine built on the same fallback layout after the first stays
# quiet, so sweeps/tests don't drown in repeats
_NONDIV_WARNED: set = set()


def check_mesh_plan(plan, decode_schedule: str, platform: str) -> None:
    """Refuse a plan that a mesh with a sharded pool cannot compile. An
    X-stream layout shards its pool rows over D
    (``sharding/specs.paged_pool_spec``), and on a TPU the streamed
    schedule runs the Pallas paged kernel, which contracts over D: a
    kernel cannot run on a split D, and GSPMD cannot partition it. The
    CPU runs the jnp stream, which GSPMD partitions."""
    if (platform == "tpu" and decode_schedule == "stream"
            and plan.backend.uses_x_cache):
        raise ValueError(
            f"score backend {plan.backend.name!r} streams the "
            f"{plan.cache_mode!r} X-cache, whose pool rows split D over "
            f"the mesh's model axis; the Pallas paged kernel contracts "
            f"over D and cannot run on a split D. Serve this plan on "
            f"one chip, or with decode_schedule='gather'.")


@dataclasses.dataclass
class PrefillJob:
    """One request's chunked prefill, advanced one chunk per ``step()``.

    ``Engine.begin_prefill`` reserves the slot and blocks up front and
    returns the job; the fused ``admit`` path drives it to completion
    synchronously (bit-identical to the old inline loop), while the
    disaggregated prefill worker (``serving/router/disagg.py``) advances
    one chunk per router step so a long prompt never stalls a
    co-resident decode tick. While in flight the slot is *held* —
    ``slot_req`` stays None (ticks skip it) but ``_free_slot`` won't
    hand it out. ``step()`` returns True once the slot is live: the
    admission token is sampled (fresh) or carried (resume) and
    ``slot_req``/``pos``/``last_tok`` are set.
    """
    engine: "Engine"
    req: Request
    slot: int
    ctx: list[int]
    resume: bool
    c0: int                      # next chunk offset (block-aligned)
    plen: int
    trow: object                 # device copy of this slot's table row
    logits: object = None        # last chunk's logits (admission sample)
    last_c0: int = 0
    done: bool = False

    def chunks_left(self) -> int:
        if self.done or self.c0 >= self.plen:
            return 0
        return -(-(self.plen - self.c0) // self.engine.prefill_chunk)

    def step(self) -> bool:
        """Run one prefill chunk; the final chunk also finalizes the
        slot (a fully-cached resume finalizes with no chunk at all).
        Returns True when the job is done."""
        if self.done:
            return True
        eng = self.engine
        if self.c0 < self.plen:
            C = eng.prefill_chunk
            c0 = self.c0
            chunk = self.ctx[c0:c0 + C]
            with spans.span(spans.ADMIT_CHUNK, rid=self.req.rid, c0=c0):
                buf = np.zeros((1, C), np.int32)
                buf[0, :len(chunk)] = chunk
                with eng._mesh_ctx():
                    self.logits, eng.pool = eng._decode_paged(
                        eng.params, eng.pool, self.trow, eng._dev(buf),
                        eng._dev(np.asarray([c0], np.int32)),
                        eng._blocks_used(np.asarray([c0 + C - 1])),
                        False)
            if eng.trace is not None:
                # queries: this chunk; keys: every position the graph
                # scores it against (the schedule covers the padded
                # chunk end c0+C-1, exactly what _blocks_used saw)
                eng.trace.record(
                    "prefill", chunk, self.ctx[:c0 + len(chunk)],
                    n_q_sched=C, n_kv_sched=eng._sched_rows(c0 + C - 1))
            self.last_c0 = c0
            self.c0 = c0 + C
            if self.c0 < self.plen:
                return False
        self._finalize()
        return True

    def _finalize(self):
        eng, req = self.engine, self.req
        if self.resume:
            # a fully-cached resume context (no chunks run) is legal:
            # no admission sample is drawn, so no logits needed
            tok = req.output[-1]
        else:
            assert self.logits is not None   # cap guarantees >= 1 chunk
            with spans.span(spans.ADMIT_SAMPLE, rid=req.rid):
                tok = int(eng._sample(
                    self.logits[:, self.plen - 1 - self.last_c0], [req])[0])
            req.output.append(tok)
            if eng.on_token:
                eng.on_token(req, tok)
        del eng._prefilling[self.slot]
        eng.slot_req[self.slot] = req
        eng.pos[self.slot] = self.plen
        eng.last_tok[self.slot] = tok
        self.done = True

    def cancel(self):
        """Abandon an in-flight job: release its blocks and slot. The
        request keeps whatever output it had (none for fresh
        admissions), so a later re-admission replays the identical
        prefill from scratch."""
        if self.done:
            raise ValueError("job already finalized; preempt the slot")
        eng = self.engine
        del eng._prefilling[self.slot]
        eng.allocator.free(eng.seq_blocks[self.slot].ids)
        eng.seq_blocks[self.slot] = None
        eng.tables[self.slot, :] = 0
        eng._tables_dev = None
        self.done = True


class Engine:
    def __init__(self, model, params, *, max_slots: int = 8,
                 max_len: int = 512, rng_seed: int = 0,
                 paged: bool | None = None, block_size: int = 16,
                 num_blocks: int | None = None,
                 hbm_bytes: int | None = None,
                 prefill_chunk: int | None = None,
                 prefix_sharing: bool = True,
                 radix_cache: bool = False,
                 admit_scan: int = 8,
                 decode_schedule: str = "auto",
                 mesh=None,
                 prefill_only: bool = False,
                 capture_trace: bool = False):
        self.model, self.params = model, params
        self.max_slots, self.max_len = max_slots, max_len
        cfg = model.cfg
        # the resolved score plan for this deployment: which backend
        # evaluates S, its schedule, and the cache layout it dictates
        self.plan = None
        if getattr(cfg, "num_heads", 0):
            from repro.core import score_backend as sb
            self.plan = sb.plan(cfg, seq_len=max_len)

        # tensor-parallel serving mesh: params shard with the training
        # rules; everything the host-side scheduler touches replicates
        self.mesh = mesh
        self._rep = None
        self._shard_pool = False
        if mesh is not None:
            from repro.sharding import specs
            self._rep = NamedSharding(mesh, P())
            self._shard_pool = ("model" in mesh.axis_names
                                and mesh.shape["model"] > 1)
            if self._shard_pool and self.plan is not None \
                    and not self.plan.shards_heads:
                warnings.warn(
                    f"score backend {self.plan.backend.name!r} cannot "
                    f"shard heads (shared K-side projection); the paged "
                    f"pool stays replicated on the "
                    f"{mesh.shape['model']}-way model axis",
                    stacklevel=2)
                self._shard_pool = False
            self.params = jax.device_put(
                params, specs.param_shardings(params, mesh))
        if paged and not model.supports_paged():
            raise ValueError(
                f"paged cache unsupported for family {cfg.family!r}")
        self.paged = model.supports_paged() if paged is None else bool(paged)
        # prefill worker mode (serving/router/disagg.py): this engine
        # only builds cache blocks — admission reserves prompt blocks
        # alone (the decode budget is reserved by the adopting decode
        # engine), and tick() is forbidden
        self.prefill_only = bool(prefill_only)
        if self.prefill_only and not self.paged:
            raise ValueError("prefill_only=True requires the paged cache "
                             "(handoff moves pool blocks)")
        # slots held by in-flight PrefillJobs: slot_req is still None
        # (ticks skip them) but _free_slot won't hand them out
        self._prefilling: dict[int, PrefillJob] = {}
        if radix_cache and not self.paged:
            raise ValueError("radix_cache=True requires the paged cache "
                             "(block ids are what the tree stores)")
        if decode_schedule not in ("auto", "stream", "gather"):
            raise ValueError(
                f"decode_schedule={decode_schedule!r}; expected "
                f"'auto' | 'stream' | 'gather'")
        if decode_schedule == "stream":
            if not self.paged:
                raise ValueError("decode_schedule='stream' requires the "
                                 "paged cache")
            if self.plan is None \
                    or not self.plan.backend.supports_block_stream:
                raise ValueError(
                    f"decode_schedule='stream' but backend "
                    f"{self.plan.backend.name if self.plan else None!r} "
                    f"does not support block streaming")

        self.pos = np.zeros(max_slots, np.int32)          # next position
        self.last_tok = np.zeros(max_slots, np.int32)
        self.slot_req: list[Request | None] = [None] * max_slots
        # sampling base key: per-slot draws fold in (rid, token index)
        # so a request's sampled tokens never depend on co-scheduling
        self._base_key = jax.random.PRNGKey(rng_seed)
        # monotonic totals since construction: decode ticks, active
        # slots summed over ticks, the most slots ever active at once
        self.ticks = 0
        self.slot_ticks = 0
        self.peak_active = 0
        self.preemptions = 0
        # how deep Engine.run / the schedulers scan the pending queue
        # when the head doesn't fit (head-of-line fix; bounded so a
        # huge queue never turns admission into an O(queue) stall)
        self.admit_scan = admit_scan
        # front-end hooks (serving/frontend): called host-side whenever
        # a token is appended to a request / a request finishes. None
        # (the default) keeps the sync engine entirely unchanged.
        self.on_token: Callable | None = None
        self.on_finish: Callable | None = None
        self.radix = None

        if self.paged:
            self.block_size = block_size
            self.blocks_per_seq = paged_lib.blocks_for(max_len, block_size)
            if num_blocks is None:
                if hbm_bytes is not None:
                    # per-DEVICE budget: a sharded pool buys shard-factor
                    # times the blocks at the same bytes per device
                    from repro.serving.kvcache import paged_budget_for
                    num_blocks = paged_budget_for(
                        cfg, block_size).max_blocks(
                            hbm_bytes, mesh if self._shard_pool else None)
                else:
                    # default: dense-pool-equivalent capacity (+ null)
                    num_blocks = max_slots * self.blocks_per_seq + 1
            self.allocator = paged_lib.BlockAllocator(num_blocks, block_size)
            self.prefill_chunk = prefill_chunk or 4 * block_size
            self.prefix_sharing = prefix_sharing
            if radix_cache:
                from repro.serving.frontend.radix_cache import RadixCache
                self.radix = RadixCache(self.allocator, block_size)
            # 'auto' follows the planner (cfg.decode_schedule override
            # included); explicit 'stream'/'gather' wins — streaming is
            # engaged by actually passing blocks_used into the graph,
            # so the override is real either way
            planned = self.plan.decode_schedule if self.plan else "gather"
            self.decode_schedule = planned if decode_schedule == "auto" \
                else decode_schedule
            if self._shard_pool:
                check_mesh_plan(self.plan, self.decode_schedule,
                                mesh.devices.flat[0].platform)
            self.pool = model.init_paged_cache(
                num_blocks, block_size,
                mesh=mesh if self._shard_pool else None)
            if self._shard_pool:
                from repro.sharding import specs
                msz = mesh.shape["model"]
                bad = specs.nondividing_pool_leaves(self.pool, msz)
                if bad:
                    key = (msz, tuple(bad))
                    if key not in _NONDIV_WARNED:
                        _NONDIV_WARNED.add(key)
                        warnings.warn(NonDividingShardWarning(
                            f"paged pool leaves {bad} cannot shard "
                            f"their head axis over the {msz}-way model "
                            f"axis; they fall back to head-dim sharding "
                            f"or replication. Decode stays correct, but "
                            f"the streamed gather re-materializes these "
                            f"leaves per tick (extra collectives).",
                            model_size=msz, shapes=tuple(bad)),
                            stacklevel=2)
            if mesh is not None and not self._shard_pool:
                self.pool = jax.device_put(self.pool, self._rep)
            self.tables = np.zeros((max_slots, self.blocks_per_seq),
                                   np.int32)
            self._tables_dev = None        # device copy, refreshed lazily
            self.seq_blocks: list[paged_lib.SeqBlocks | None] = \
                [None] * max_slots
            # one graph, two programs named for a trace (jit_decode_tick,
            # jit_prefill_chunk), one shape each. Per-engine wrappers,
            # NOT bound methods: jax's trace cache keys on function
            # identity and bakes this mesh's sharding constraints into
            # the jaxpr — two replicas jitting one function would share
            # one trace and cross-wire their device groups. Engines
            # without a mesh lose nothing by it: jax traces and compiles
            # again for each engine even through one bound method.
            def decode_tick(*a):
                return model.decode_paged(*a)

            def prefill_chunk(*a):
                return model.decode_paged(*a)
            jit_kw = {}
            if mesh is not None:
                # pin the outputs: logits replicate (host samples them),
                # the pool keeps its shard layout across ticks
                pool_sh = jax.tree_util.tree_map(lambda l: l.sharding,
                                                 self.pool)
                jit_kw["out_shardings"] = (self._rep, pool_sh)
            self._decode_tick = jax.jit(decode_tick, **jit_kw)
            self._prefill_chunk = jax.jit(prefill_chunk, **jit_kw)
        else:
            self.decode_schedule = "gather"      # dense pool: no paging
            self.cache = model.init_cache(max_slots, max_len)
            if mesh is not None:
                self.cache = jax.device_put(self.cache, self._rep)
            if mesh is None:
                self._decode = jax.jit(model.decode_step)
            else:
                def _decode_step_fn(*a):   # same trace-isolation story
                    return model.decode_step(*a)
                self._decode = jax.jit(_decode_step_fn)
            self._prefills: dict[int, Callable] = {}

        # score-trace capture for the hardware simulator (repro.sim):
        # records quantized score-path operand shapes + exact bit
        # sparsity per prefill chunk / decode tick. None (the default)
        # keeps the serving loop entirely untouched.
        self.trace = None
        if capture_trace:
            from repro.sim.trace import TraceCapture
            self.trace = TraceCapture.for_model(
                model, params, decode_schedule=self.decode_schedule,
                block_size=self.block_size if self.paged else 0,
                max_len=max_len)

    # ------------------------------------------------------------- mesh
    def _dev(self, arr):
        """Host operand upload: replicated across the mesh (tables,
        tokens, positions, blocks_used — everything the host scheduler
        owns), a plain device array otherwise."""
        a = jnp.asarray(arr)
        return a if self.mesh is None else jax.device_put(a, self._rep)

    def _mesh_ctx(self):
        """Install the serving mesh for trace time so the activation
        constraints (sharding/act) see it; identity when mesh=None."""
        return act.use_mesh(self.mesh)

    @property
    def pool_sharded(self) -> bool:
        """Whether the decode-cache pool is split over the mesh's
        "model" axis (False for mesh=None, 1x1 meshes, and the
        replicated fallback of head-unsplittable backends)."""
        return self._shard_pool

    def pool_bytes_per_device(self) -> int:
        """Decode-cache bytes held by one device — num_blocks' worth
        split by the pool-shard factor when the pool is head-sharded."""
        src = self.pool if self.paged else self.cache
        return paged_lib.pool_device_bytes(src)

    # ---------------------------------------------------------- admission
    def _free_slot(self) -> int | None:
        for i, r in enumerate(self.slot_req):
            if r is None and i not in self._prefilling:
                return i
        return None

    def _note_active(self):
        self.peak_active = max(self.peak_active,
                               sum(r is not None for r in self.slot_req))

    def check_servable(self, req: Request) -> None:
        """Raise for a request the engine could NEVER serve (prompt too
        long for the context, or more blocks than the whole pool) —
        admission failures for *transient* reasons return False from
        ``admit`` instead. Front ends call this at submit time so the
        error surfaces to the submitter, not the pump thread."""
        ctx_len = len(req.tokens) + max(len(req.output) - 1, 0)
        if ctx_len >= self.max_len:
            raise ValueError(
                f"request {req.rid}: prompt length {ctx_len} >= "
                f"max_len {self.max_len} — can never be served; raise "
                f"--max-len or truncate the prompt")
        if self.paged:
            # a prefill-only worker reserves prompt blocks alone; the
            # decode budget is the adopting engine's problem
            need = ctx_len if self.prefill_only else \
                min(len(req.tokens) + req.max_new_tokens, self.max_len)
            n_res = min(paged_lib.blocks_for(need, self.block_size),
                        self.blocks_per_seq)
            if n_res > self.allocator.num_usable:
                raise ValueError(
                    f"request {req.rid}: needs {n_res} blocks, pool has "
                    f"{self.allocator.num_usable} — raise --hbm-budget "
                    f"or lower max_len/max_new_tokens")

    def admit(self, req: Request) -> bool:
        """Prefill ``req`` into a free slot; False if the slot pool (or,
        paged, the block allocator) cannot serve it right now. A prompt
        that can never fit (plen >= max_len) raises instead of silently
        truncating into garbage.

        A request with prior ``output`` (preempted mid-decode, see
        ``preempt``) is **resumed**: the cache context — prompt plus
        every generated token except the last — is rebuilt (forked from
        the radix cache where possible, recomputed otherwise; cache
        rows depend only on their prefix, so either way they are
        bit-equal to the pre-preemption rows) and decoding continues
        from the last sampled token without drawing a fresh admission
        sample."""
        with spans.span(spans.ADMIT, rid=req.rid):
            self.check_servable(req)
            resume = bool(req.output)
            # cache context: every token whose row must exist before the
            # next decode tick feeds req.output[-1] (fresh: the prompt)
            ctx = req.tokens + req.output[:-1] if resume else req.tokens
            if self.paged:
                job = self.begin_prefill(req)
                if job is None:
                    return False
                while not job.step():       # fused: drive every chunk now
                    pass
                slot = job.slot
            else:
                slot = self._admit_dense(req, ctx, resume)
                if slot is None:
                    return False
            return self._post_admit(req, slot, resume)

    def _post_admit(self, req: Request, slot: int, resume: bool) -> bool:
        """Admission epilogue once the slot is live: clear a resume's
        "preempted" marker, or finish the request outright when the
        admission-sampled token already completes it (max_new_tokens <=
        1, or EOS straight out of prefill) instead of letting a tick
        append a second token."""
        if resume:
            req.finish_reason = None        # clears "preempted"
            self._note_active()
            return True
        tok = req.output[-1]
        if req.eos_id is not None and tok == req.eos_id:
            req.done, req.finish_reason = True, "eos"
            self._evict(slot)
        elif len(req.output) >= req.max_new_tokens:
            req.done, req.finish_reason = True, "length"
            self._evict(slot)
        else:
            # a cancelled PrefillJob leaves "preempted" on an output-less
            # request; clear it or the next tick reads it as a finish
            req.finish_reason = None
            self._note_active()
        if req.done and self.on_finish:
            self.on_finish(req)
        return True

    def admit_from(self, pending: list[Request]) -> int:
        """Admit every request that fits *now* from the first
        ``admit_scan`` entries of ``pending`` (popping admitted ones;
        arrival order otherwise preserved). A blocked head no longer
        starves smaller requests behind it. Returns admitted count."""
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

    # ---------------------------------------------------- dense admission
    def _prefill_fn(self, plen: int):
        if plen not in self._prefills:
            self._prefills[plen] = jax.jit(
                lambda p, b: self.model.prefill(p, b, self.max_len))
        return self._prefills[plen]

    def _admit_dense(self, req: Request, ctx: list[int],
                     resume: bool) -> int | None:
        slot = self._free_slot()
        if slot is None:
            return None
        plen = len(ctx)
        b = _bucket(plen)
        toks = np.zeros((1, b), np.int32)
        toks[0, :plen] = ctx
        batch = {"tokens": self._dev(toks),
                 "lengths": self._dev(np.asarray([plen], np.int32))}
        cfg = self.model.cfg
        if cfg.enc_dec:
            # audio request: tokens are the decoder prompt; encoder side
            # comes from the stub frontend embeddings attached to req
            batch["enc_embeds"] = self._dev(req.enc_embeds)  # type: ignore
        with self._mesh_ctx():
            logits, cache1 = self._prefill_fn(b)(self.params, batch)
        if self.trace is not None:
            # dense prefill sweeps the full bucketed self-attention
            self.trace.record("prefill", ctx, ctx,
                              n_q_sched=b, n_kv_sched=b)
        self._copy_slot(cache1, slot)
        if resume:
            tok = req.output[-1]          # continue, don't resample
        else:
            tok = int(self._sample(logits, [req])[0])
            req.output.append(tok)
            if self.on_token:
                self.on_token(req, tok)
        self.slot_req[slot] = req
        self.pos[slot] = plen
        self.last_tok[slot] = tok
        return slot

    def _copy_slot(self, cache1, slot: int):
        """Copy batch-row 0 of a single-request cache into pool slot."""
        def one(pool, single):
            if pool.ndim <= 1:
                return pool.at[slot].set(single[0])
            # leaves are (L, B, ...) stacked or (B, ...) for enc_len etc.
            if pool.shape[0] == single.shape[0] and pool.ndim >= 2 \
                    and single.ndim == pool.ndim:
                return pool.at[:, slot].set(single[:, 0])
            return pool.at[slot].set(single[0])
        self.cache = jax.tree_util.tree_map(one, self.cache, cache1)

    # ---------------------------------------------------- paged admission
    def _find_prefix_donor(self, tokens: list[int]):
        """Longest shareable prefix (whole blocks) of ``tokens`` among
        active sequences. Cache rows at position p depend only on
        tokens 0..p, so equal prefixes mean bit-equal rows — the
        borrower forks those blocks instead of recomputing them."""
        best_n, best_slot = 0, None
        for s, r in enumerate(self.slot_req):
            if r is None:
                continue
            n = paged_lib.shared_prefix_blocks(tokens, r.tokens,
                                               self.block_size)
            n = min(n, len(self.seq_blocks[s].ids))
            if n > best_n:
                best_n, best_slot = n, s
        return best_n, best_slot

    def begin_prefill(self, req: Request) -> PrefillJob | None:
        """Reserve a slot and blocks for ``req`` and return a
        ``PrefillJob`` that advances its chunked prefill one chunk per
        ``step()`` call (None when no slot/blocks are available right
        now — the request stays queued). The fused ``admit`` drives the
        job to completion inline; the disaggregated prefill worker
        interleaves ``step()`` with its decode sibling's ticks. Callers
        other than ``admit`` must invoke ``_post_admit`` (or export the
        sequence) once the job reports done."""
        if not self.paged:
            raise ValueError("begin_prefill requires the paged cache")
        with spans.span(spans.ADMIT_RESERVE, rid=req.rid):
            return self._reserve(req)

    def _reserve(self, req: Request) -> PrefillJob | None:
        self.check_servable(req)
        resume = bool(req.output)
        ctx = req.tokens + req.output[:-1] if resume else req.tokens
        slot = self._free_slot()
        if slot is None:
            return None
        plen = len(ctx)
        BS = self.block_size
        # total reservation is arrival-invariant: resume re-reserves
        # exactly what the fresh admission did (prompt + full budget).
        # A prefill-only worker reserves just the prompt's blocks — the
        # adopting decode engine reserves the full budget at handoff.
        need_tokens = plen if self.prefill_only else \
            min(len(req.tokens) + req.max_new_tokens, self.max_len)
        n_res = min(paged_lib.blocks_for(need_tokens, BS),
                    self.blocks_per_seq)

        # prefix donors, best of both: a live co-scheduled sequence
        # (fork its blocks) or the radix cache of historical prefixes.
        # Cap so a fresh admission still prefills >= its final prompt
        # token itself (the admission logits must be its own forward
        # pass); harmless for resume (no admission sample drawn).
        n_shared, donor = 0, None
        radix_ids: list[int] = []
        if self.prefix_sharing:
            n_shared, donor = self._find_prefix_donor(ctx)
            n_shared = min(n_shared, n_res)
        if self.radix is not None:
            # resume may fork every full ctx block (no admission
            # logits needed); fresh admissions keep one token back
            cap = min((plen if resume else max(plen - 1, 0)) // BS,
                      n_res)
            radix_ids = self.radix.match(ctx, max_blocks=cap)
            if len(radix_ids) <= n_shared:
                radix_ids = []             # live donor wins ties
        if radix_ids:
            ids_shared = self.allocator.fork(radix_ids)
        elif n_shared:
            ids_shared = self.allocator.fork(
                self.seq_blocks[donor].ids[:n_shared])
        else:
            ids_shared = []
        n_fresh = n_res - len(ids_shared)
        if n_fresh > self.allocator.num_free:
            # LRU-evict historical prefixes before giving up: cached
            # blocks are strictly less valuable than a live admission
            if self.radix is not None:
                self.radix.evict(n_fresh - self.allocator.num_free)
            if n_fresh > self.allocator.num_free:
                self.allocator.free(ids_shared)
                return None                # exhausted: stay queued
        fresh = self.allocator.alloc(n_fresh)
        ids = ids_shared + fresh
        self.seq_blocks[slot] = paged_lib.SeqBlocks(ids, len(ids_shared))
        self.tables[slot, :] = 0
        self.tables[slot, :len(ids)] = ids
        self._tables_dev = None

        # chunked prefill streams the (unshared part of the) context in
        # fixed-size chunks through the shared decode graph — one chunk
        # per PrefillJob.step(). Writes at block-aligned ``start``
        # onward touch only exclusively-owned blocks; padding past the
        # table lands in the null block.
        trow = self._dev(self.tables[slot:slot + 1])
        start = len(ids_shared) * BS
        job = PrefillJob(engine=self, req=req, slot=slot, ctx=ctx,
                         resume=resume, c0=start, plen=plen, trow=trow)
        self._prefilling[slot] = job
        return job

    # ----------------------------------------------------------- handoff
    def _handoff_blocks(self, req: Request) -> int:
        """Blocks a full (fused-equivalent) reservation for ``req``
        takes — what ``adopt_sequence`` allocates so migration keeps
        admission arrival-invariant."""
        need = min(len(req.tokens) + req.max_new_tokens, self.max_len)
        return min(paged_lib.blocks_for(need, self.block_size),
                   self.blocks_per_seq)

    def export_sequence(self, slot: int) -> paged_lib.SequenceHandoff:
        """Package the live sequence in ``slot`` for adoption by
        another engine (disaggregated prefill→decode handoff, or
        cross-replica migration): a bit-copy of its written blocks plus
        the scalar decode state, then a normal eviction — with the
        radix cache attached the written prefix stays pinned on THIS
        engine for future local admissions to fork."""
        req = self.slot_req[slot]
        if not self.paged or req is None:
            raise ValueError(f"slot {slot} holds no exportable sequence")
        pos = int(self.pos[slot])
        ids = self.seq_blocks[slot].ids
        # rows 0..pos-1 are written; later reserved blocks carry nothing
        n_blk = min(paged_lib.blocks_for(pos, self.block_size), len(ids))
        blob = paged_lib.export_blocks(self.pool, ids[:n_blk])
        h = paged_lib.SequenceHandoff(
            req=req, blob=blob, n_blocks=n_blk, pos=pos,
            last_tok=int(self.last_tok[slot]), block_size=self.block_size)
        self._evict(slot)
        return h

    def can_adopt(self, handoff: paged_lib.SequenceHandoff) -> bool:
        """Whether ``adopt_sequence`` would succeed right now (a free
        slot plus the full decode-budget blocks, LRU-evicting radix
        prefixes if that's what it takes). The disagg worker checks
        this BEFORE exporting so a sequence is never left floating
        between engines."""
        if not self.paged or self._free_slot() is None:
            return False
        n_res = max(self._handoff_blocks(handoff.req), handoff.n_blocks)
        short = n_res - self.allocator.num_free
        if short > 0 and self.radix is not None:
            self.radix.evict(short)
        return n_res <= self.allocator.num_free

    def adopt_sequence(self, handoff: paged_lib.SequenceHandoff
                       ) -> int | None:
        """Install an exported sequence: reserve the full decode budget
        (exactly what a fused admission would have reserved), bit-copy
        the blob into fresh exclusively-owned blocks, splice the block
        table, and continue decoding from the carried token. Returns
        the slot, or None when a slot or blocks are unavailable (the
        handoff is untouched — the caller retries)."""
        if not self.paged:
            raise ValueError("adopt_sequence requires the paged cache")
        if handoff.block_size != self.block_size:
            raise ValueError(
                f"handoff block_size {handoff.block_size} != engine "
                f"block_size {self.block_size} — replicas must share "
                f"the pool geometry")
        slot = self._free_slot()
        if slot is None:
            return None
        n_res = max(self._handoff_blocks(handoff.req), handoff.n_blocks)
        short = n_res - self.allocator.num_free
        if short > 0 and self.radix is not None:
            self.radix.evict(short)
        ids = self.allocator.alloc(n_res)
        if ids is None:
            return None
        blob = handoff.blob
        if self._shard_pool:
            # re-lay the blob onto THIS engine's mesh (cross-replica
            # migration moves between disjoint device groups)
            from repro.sharding import specs
            blob = jax.device_put(
                blob, specs.handoff_shardings(blob, self.mesh))
        else:
            blob = jax.tree_util.tree_map(
                lambda b, leaf: jax.device_put(b, leaf.sharding),
                blob, self.pool)
        self.pool = paged_lib.adopt_blocks(
            self.pool, ids[:handoff.n_blocks], blob)
        self.seq_blocks[slot] = paged_lib.SeqBlocks(ids, 0)
        self.tables[slot, :] = 0
        self.tables[slot, :len(ids)] = ids
        self._tables_dev = None
        req = handoff.req
        req.finish_reason = None           # clears a migration's marker
        self.slot_req[slot] = req
        self.pos[slot] = handoff.pos
        self.last_tok[slot] = handoff.last_tok
        self._note_active()
        return slot

    def _evict(self, slot: int):
        """Free the slot (paged: return blocks to the allocator). With
        the radix cache attached, the sequence's fully-written whole
        blocks are first inserted (pinned) into the tree, so the prefix
        outlives the request for future admissions to fork."""
        req = self.slot_req[slot]
        if self.radix is not None and req is not None \
                and self.seq_blocks[slot] is not None:
            # positions written so far: the prompt plus every generated
            # token except the last (sampled but never fed back)
            written = req.tokens + req.output[:-1] if req.output \
                else req.tokens
            ids = self.seq_blocks[slot].ids
            n_full = min(len(written) // self.block_size, len(ids))
            if n_full:
                self.radix.insert(written[:n_full * self.block_size],
                                  ids[:n_full])
        self.slot_req[slot] = None
        self.pos[slot] = 0
        self.last_tok[slot] = 0
        if self.paged and self.seq_blocks[slot] is not None:
            self.allocator.free(self.seq_blocks[slot].ids)
            self.seq_blocks[slot] = None
            self.tables[slot, :] = 0
            self._tables_dev = None

    def preempt(self, slot: int) -> Request:
        """Evict-to-queue: release the slot's blocks (radix keeps the
        written prefix pinned when attached) and hand the request back
        to the scheduler marked ``finish_reason="preempted"`` —
        re-``admit`` resumes it losslessly (greedy continuation is
        bit-identical: cache rows are rebuilt from the same prefix,
        forked or recomputed)."""
        req = self.slot_req[slot]
        if req is None or req.done:
            raise ValueError(f"slot {slot} holds no preemptible request")
        req.finish_reason = "preempted"
        self.preemptions += 1
        self._evict(slot)
        return req

    # -------------------------------------------------------------- tick
    def _sched_rows(self, last_pos: int) -> int:
        """KV rows the decode graph actually sweeps for a sequence whose
        last written position is ``last_pos`` — what the hardware trace
        records as the scheduled operand height (rows past the logical
        length are zero: pure zero-skip food for the simulator)."""
        if not self.paged:
            return self.max_len                   # dense logical view
        if self.decode_schedule == "stream":
            used = min(last_pos // self.block_size + 1,
                       self.blocks_per_seq)
            return used * self.block_size         # early-exit bound
        return self.blocks_per_seq * self.block_size

    def _live_blocks(self, last_pos: np.ndarray):
        """Per-slot live block counts covering every position up to
        ``last_pos``, on the host and clipped to [1, blocks_per_seq] —
        the streamed schedule's early-exit bound, and the paged
        kernel's grid steps per slot. None on the gather path (the
        graph then materializes the full view)."""
        if self.decode_schedule != "stream":
            return None
        used = last_pos // self.block_size + 1
        return np.clip(used, 1, self.blocks_per_seq).astype(np.int32)

    def _blocks_used(self, last_pos: np.ndarray):
        """``_live_blocks`` on the device."""
        used = self._live_blocks(last_pos)
        return None if used is None else self._dev(used)

    def _decode_paged(self, params, pool, tables, tokens, pos, used,
                      tick: bool):
        """The paged step through ``decode_tick`` (``tick``) or
        ``prefill_chunk``. Ticks and chunks both call this, so one
        wrapper around it sees every step."""
        step = self._decode_tick if tick else self._prefill_chunk
        return step(params, pool, tables, tokens, pos, used)

    def _sample(self, logits, reqs) -> np.ndarray:
        """Next token per row: greedy where the row's temperature is 0,
        else categorical over ``logits / temp``. The categorical key is
        **per slot**: ``fold_in(fold_in(base, rid), token_index)`` — a
        request's sampled tokens depend only on (engine seed, rid, how
        many tokens it has sampled), never on which other requests are
        co-scheduled or in what order admission happened. Solo ==
        batched == resumed-after-preemption, reproducibly."""
        greedy = jnp.argmax(logits, axis=-1)
        t = np.asarray([0.0 if r is None else r.temperature
                        for r in reqs], np.float32)
        if not (t > 0).any():
            return np.asarray(greedy, np.int32)
        keys = jnp.stack([
            jax.random.fold_in(jax.random.fold_in(self._base_key, r.rid),
                               len(r.output))
            if r is not None and r.temperature > 0 else self._base_key
            for r in reqs])
        tj = jnp.asarray(t)
        safe = jnp.where(tj > 0, tj, 1.0)[:, None]
        drawn = jax.vmap(jax.random.categorical)(keys, logits / safe)
        return np.asarray(jnp.where(tj > 0, drawn, greedy), np.int32)

    def tick(self):
        """One decode step for all slots (inactive slots decode garbage
        into their own row / the null block; masked on readout)."""
        if self.prefill_only:
            raise RuntimeError(
                "prefill-only worker cannot tick; export its sequences "
                "to a decode engine (serving/router/disagg.py)")
        if self._prefilling:
            # an in-flight job's table row is live — a tick would
            # scatter garbage into its first block. Fused admission
            # completes jobs inline; interleaving belongs to a separate
            # prefill worker, never to one engine.
            raise RuntimeError(
                f"tick with in-flight prefill jobs in slots "
                f"{sorted(self._prefilling)}; drive them to completion "
                f"(or cancel) first")
        active = np.asarray([r is not None for r in self.slot_req])
        if not active.any():
            return
        used = self._live_blocks(self.pos) if self.paged else None
        # kv_blocks: the grid steps the tick's paged kernel launches
        # per layer, the sum of its clipped blocks_used
        stats = {} if used is None else {"kv_blocks": int(used.sum())}
        with spans.span(spans.TICK, index=self.ticks,
                        active=int(active.sum()), **stats):
            self._tick(active, used)

    def _tick(self, active: np.ndarray, used):
        if self.trace is not None:
            for s, req in enumerate(self.slot_req):
                if req is None:
                    continue
                toks_all = req.tokens + req.output   # positions 0..pos
                self.trace.record(
                    "decode", toks_all[-1:], toks_all,
                    n_kv_sched=self._sched_rows(int(self.pos[s])))
        with spans.span(spans.TICK_UPLOAD):
            toks = self._dev(self.last_tok)
            pos = self._dev(self.pos)
            if self.paged:
                # tables only change at admit/evict — reuse the device
                # copy across decode ticks instead of re-uploading
                if self._tables_dev is None:
                    self._tables_dev = self._dev(self.tables)
                if used is not None:
                    used = self._dev(used)
        with spans.span(spans.TICK_STEP):
            with self._mesh_ctx():
                if self.paged:
                    logits, self.pool = self._decode_paged(
                        self.params, self.pool, self._tables_dev,
                        toks[:, None], pos, used, True)
                else:
                    logits, self.cache = self._decode(
                        self.params, self.cache, toks, pos)
        with spans.span(spans.TICK_SAMPLE):
            if self.paged:
                logits = logits[:, 0]
            nxt = self._sample(logits, self.slot_req)
        self.ticks += 1
        self.slot_ticks += int(active.sum())
        with spans.span(spans.TICK_EMIT):
            for s, req in enumerate(self.slot_req):
                if req is None:
                    continue
                self.pos[s] += 1
                tok = int(nxt[s])
                req.output.append(tok)
                self.last_tok[s] = tok
                if self.on_token:
                    self.on_token(req, tok)
                if req.eos_id is not None and tok == req.eos_id:
                    req.finish_reason = "eos"
                elif len(req.output) >= req.max_new_tokens:
                    req.finish_reason = "length"
                elif self.pos[s] >= self.max_len - 1:
                    # context wall: out of cache positions with new-token
                    # budget left — distinguishable from natural
                    # completion
                    req.finish_reason = "truncated"
                if req.finish_reason is not None:
                    req.done = True
                    self._evict(s)
                    if self.on_finish:
                        self.on_finish(req)

    # --------------------------------------------------------------- run
    def run(self, requests: list[Request], max_ticks: int = 10_000
            ) -> list[Request]:
        """Continuous batching: admit whatever fits when slots free
        (``admit_from`` scans past a blocked head), tick until done."""
        pending = list(requests)
        for _ in range(max_ticks):
            self.admit_from(pending)
            if not pending and all(r is None for r in self.slot_req):
                break
            self.tick()
        return requests
