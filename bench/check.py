"""What decides ``correct``, and the count of compilations in the window.

Once the window has closed and the program's state is freed, a sample
of the requests the engine finished, drawn from the seed and with the
longest of them in it, is replayed through the plain float32 reference
of the cell's architecture (its module's ``gaps``), teacher-forced on
the served tokens. Compared, each against the limit in the
configuration's ``correctness``:

  logit_gap      the widest gap, over every sampled served token, of
                 the reference's logit of that token below the
                 reference's best, in units of the row's standard
                 deviation (greedy decoding serves the top logit, so a
                 sound engine reads only rounding near ties)
  wrong_length   finished requests whose output is not as long as they
                 asked (exact: 0)
  sampled_tokens served tokens compared; at least ``min_tokens``
"""
from __future__ import annotations

import numpy as np


class CompileCounter:
    """Counts executables compiled or loaded from the compilation
    cache from its creation until ``stop()``."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.n = 0
        self.on = True
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, duration, **kw):
        if self.on and name == self.EVENT:
            self.n += 1

    def stop(self) -> int:
        self.on = False
        return self.n


def finished(run) -> list:
    return [s for s in run.sent if s.finish is not None]


def sample(run, seed: int, tokens: int, max_requests: int) -> list:
    """Finished requests: the one with the most served tokens, then
    others in an order drawn from the seed, until ``tokens`` served
    tokens or ``max_requests`` requests."""
    done = sorted(finished(run), key=lambda s: (-len(s.output),
                                                -len(s.prompt), s.rid))
    if not done:
        return []
    rest = done[1:]
    order = np.random.default_rng(seed).permutation(len(rest))
    out = [done[0]]
    for i in order:
        if sum(len(s.output) for s in out) >= tokens \
                or len(out) >= max_requests:
            break
        out.append(rest[i])
    return out


def judge(cell, seed: int, run, gap_fn=None) -> dict:
    """The verdict: ``correct``, each compared number with its limit
    (``limits``) and the lines that print them."""
    if gap_fn is None:
        gap_fn = cell.arch.gaps
    lim = cell.config["correctness"]
    picked = sample(run, seed, lim["sample_tokens"],
                    lim["sample_max_requests"])
    wrong = sum(s.finish != "length" or len(s.output) != s.max_new
                for s in finished(run))
    gaps = gap_fn(cell.config, seed, [(s.prompt, s.output)
                                      for s in picked])
    n = int(gaps.size)
    widest = float(gaps.max()) if n else None
    limits = {
        "logit_gap": {"value": widest, "limit": lim["logit_gap"],
                      "le": True},
        "wrong_length": {"value": wrong, "limit": 0, "le": True},
        "sampled_tokens": {"value": n, "limit": lim["min_tokens"],
                           "le": False},
    }
    ok = True
    lines = [f"[check] {len(picked)} of {len(finished(run))} finished "
             f"requests compared with the float32 reference"]
    for name, c in limits.items():
        if c["limit"] is None or c["value"] is None:
            good = False
        elif c["le"]:
            good = c["value"] <= c["limit"]
        else:
            good = c["value"] >= c["limit"]
        ok &= good
        rel = "<=" if c["le"] else ">="
        lines.append(f"[check] {name} {c['value']} (limit {rel} "
                     f"{c['limit']}): {'ok' if good else 'FAILED'}")
    lines.append(f"[check] correct: {ok}")
    return {"correct": ok, "lines": lines,
            "limits": {k: {"value": v["value"], "limit": v["limit"]}
                       for k, v in limits.items()}}
