"""The benchmark's one point of contact with the serving program.

It builds what ``launch/serve.py`` builds (``Engine`` over the paged
pool with the default plan, the async front end over it) from a
configuration file and the model configuration that the cell's
architecture module gives, hands it the benchmark's weights, and wraps
two methods of the engine instance, ``tick`` and ``admit``, to record
when each ran and the engine's tick count then (which the program's
``engine.tick`` span carries as its ``index``, so a trace finds each
record), and to stop the engine when the window closes. Nothing else of
the program is read.
"""
from __future__ import annotations

import dataclasses
import os
import sys
import threading
import time

clock = time.monotonic


class WindowClosed(Exception):
    """Raised in the engine's pump thread to stop it at the window's
    end, without draining the requests still in flight."""


def import_program(root: str) -> None:
    src = os.path.join(root, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise FileNotFoundError(f"no serving program under {src}")
    if src not in sys.path:
        sys.path.insert(0, src)


def engine_options(config: dict, traffic: dict) -> dict:
    return {**config["engine"], **traffic.get("engine", {})}


def build_engine(cell, params):
    """The engine over ``params`` for the cell's configuration, as its
    architecture module gives it to the program."""
    from repro.models.model import build_model
    from repro.serving.engine import Engine
    opts = engine_options(cell.config, cell.traffic)
    model = build_model(cell.arch.program_config(cell.config))
    return Engine(model, params,
                  max_slots=opts["max_slots"], max_len=opts["max_len"],
                  block_size=opts["block_size"],
                  hbm_bytes=opts["hbm_bytes"],
                  radix_cache=bool(opts.get("radix_cache", False)))


def request(rid: int, tokens: list, max_new: int):
    from repro.serving.engine import Request
    return Request(rid=rid, tokens=tokens, max_new_tokens=max_new,
                   eos_id=None)


def warm_up(engine) -> None:
    """Compile every shape the window uses: the prefill chunk (first
    and later chunks), the decode tick and the sampling of both."""
    c = engine.prefill_chunk
    engine.run([request(-1, [3] * (c + 6), 3), request(-2, [4] * 5, 3)])


@dataclasses.dataclass
class Tick:
    start: float
    end: float
    contexts: list          # keys each active sequence attends
    index: int              # the engine's tick count before it


@dataclasses.dataclass
class Admission:
    rid: int
    start: float
    end: float
    chunks: list            # (first position, tokens) of each chunk run
    tick: int               # the engine's tick count as it admitted


class Probe:
    """Wraps ``tick`` and ``admit`` of one engine instance."""

    def __init__(self, engine):
        self.engine = engine
        self.stop = threading.Event()
        self.ticks: list[Tick] = []
        self.admissions: list[Admission] = []
        self._tick, self._admit = engine.tick, engine.admit
        engine.tick, engine.admit = self.tick, self.admit

    def tick(self):
        if self.stop.is_set():
            raise WindowClosed
        eng = self.engine
        ctx = [int(p) + 1 for p, r in zip(eng.pos, eng.slot_req,
                                          strict=True) if r is not None]
        t, index = clock(), eng.ticks
        self._tick()
        self.ticks.append(Tick(t, clock(), ctx, index))

    def admit(self, req) -> bool:
        if self.stop.is_set():
            raise WindowClosed
        t, tick = clock(), self.engine.ticks
        ok = self._admit(req)
        if ok:
            self.admissions.append(Admission(
                req.rid, t, clock(), self._chunks(req), tick))
        return ok

    def _chunks(self, req) -> list:
        eng = self.engine
        shared = 0
        if req in eng.slot_req:
            shared = eng.seq_blocks[eng.slot_req.index(req)].num_shared
        c, n = eng.prefill_chunk, len(req.tokens)
        return [(c0, min(c, n - c0))
                for c0 in range(shared * eng.block_size, n, c)]
