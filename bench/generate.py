"""The one traffic generator: a traffic mix's file in, requests out.

Everything is drawn from the run's seed, and the program receives only
the generated token ids and output lengths. A mix file says:

  loop       "open" (arrivals on a schedule, at ``rate_rps`` on
             average) or "closed" (``clients`` callers, each sending its
             next request when its last one completes)
  ramp_s     seconds of traffic before the measured window opens
  start_in_flight
             open loop: requests already being served when the ramp
             starts, all due at its start (the rate times the mean time
             a request is served, as measured); closed loop: one per
             client. Their output lengths follow the residual of the
             output distribution, as requests caught mid-answer do, so
             the batch starts near its steady occupancy
  prompt     the length distribution of a request's own prompt tokens
  output     the length distribution of the tokens it asks for
  prefix     optional: one of ``count`` shared prefixes (lengths from
             "length", picked with Zipf exponent "zipf") is prepended
             to each prompt
  engine     deployment options this mix needs (``max_len``,
             ``radix_cache``), laid over the configuration's

A length distribution is {"dist": "lognormal", "median", "sigma",
"min", "max"} or {"dist": "uniform", "min", "max"}.

So that two seeds give the same work in another order, every block of
``BLOCK`` consecutive requests takes its lengths, prefix picks
and gaps between arrivals from the quantiles ``(k + 0.5) / BLOCK`` of
their distributions, shuffled by the seed; the token ids are drawn from
the seed.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np

BLOCK = 64
FIRST_ID = 3          # ids below are left to special tokens


@dataclasses.dataclass
class Planned:
    rid: int
    prompt_len: int     # own tokens, after any shared prefix
    max_new: int        # output tokens the request asks for
    seed: int
    vocab: int
    prefix: list = dataclasses.field(default_factory=list)
    due: float | None = None      # open loop: seconds after the origin
    client: int | None = None     # closed loop: which caller sends it

    def tokens(self) -> list:
        """The prompt's ids: the shared prefix, then ids drawn from
        (seed, rid), so a request's prompt does not depend on how many
        requests a run sends."""
        rng = np.random.default_rng([self.seed, self.rid])
        return self.prefix + rng.integers(
            FIRST_ID, self.vocab, self.prompt_len).tolist()


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(dist: dict, n: int) -> np.ndarray:
    """The ``n`` quantile lengths of ``dist``, in order, clipped."""
    u = _quantiles(n)
    if dist["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in u])
        v = dist["median"] * np.exp(dist["sigma"] * z)
    elif dist["dist"] == "uniform":
        v = dist["min"] + u * (dist["max"] - dist["min"])
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(np.rint(v), dist["min"], dist["max"]).astype(np.int64)


def residual_lengths(dist: dict, n: int) -> np.ndarray:
    """The ``n`` quantile lengths of what is left of an answer of length
    ``dist`` at a moment drawn uniformly from its time being served:
    P(R > x) = E[(L - x)+] / E[L], from the quantile grid of L."""
    L = np.sort(lengths(dist, 4096)).astype(np.float64)
    x = np.arange(int(L[-1]) + 1)
    tail = np.cumsum(L[::-1])[::-1]             # sum of L[i:]
    i = np.searchsorted(L, x, side="right")     # first L > x
    above = np.append(tail, 0.0)[i] - x * (len(L) - i)
    cdf = 1.0 - above / L.sum()                 # P(R <= x)
    return np.maximum(np.searchsorted(cdf, _quantiles(n)), 1)


def _counts(weights, n: int) -> np.ndarray:
    """Largest-remainder split of ``n`` items by ``weights``."""
    w = np.asarray(weights, np.float64)
    exact = w / w.sum() * n
    c = np.floor(exact).astype(np.int64)
    for i in np.argsort(-(exact - c), kind="stable")[:n - c.sum()]:
        c[i] += 1
    return c


def _picks(weights, n: int, rng) -> np.ndarray:
    """``n`` indices in proportion to ``weights``, shuffled."""
    c = _counts(weights, n)
    return rng.permutation(np.repeat(np.arange(len(c)), c))


def _gaps(n: int, rate: float, rng) -> np.ndarray:
    """Exponential gaps between arrivals at mean ``1 / rate``: the
    quantile set of each block, shuffled."""
    out = []
    for b in range(0, n, BLOCK):
        k = min(BLOCK, n - b)
        out.append(rng.permutation(-np.log1p(-_quantiles(k)) / rate))
    return np.concatenate(out)


def _block_draw(dist: dict, n: int, rng) -> np.ndarray:
    out = [np.zeros(0, np.int64)]
    for b in range(0, n, BLOCK):
        out.append(rng.permutation(lengths(dist, min(BLOCK, n - b))))
    return np.concatenate(out)


def plan(mix: dict, seed: int, vocab: int, seconds: float) -> list:
    """The requests of one run: ``ramp_s`` of traffic before a window
    of ``seconds``, with margin, as ``Planned`` entries in send order
    (open loop: by due time; closed loop: each client's in turn)."""
    rng = np.random.default_rng(seed)
    horizon = mix["ramp_s"] + seconds
    if mix["loop"] == "open":
        n0 = int(mix.get("start_in_flight", 0))
        n = n0 + math.ceil(mix["rate_rps"] * horizon * 1.2) + BLOCK
    elif mix["loop"] == "closed":
        n0 = mix["clients"]
        # more than any client can finish: a request is at least one
        # prefill chunk and its output, several ms a token
        n = mix["clients"] * (math.ceil(horizon) * 4 + 8)
    else:
        raise ValueError(f"unknown loop {mix['loop']!r}")

    # the requests in flight at the start, then the rest, each its own
    # set of quantiles
    prompt = np.concatenate([_block_draw(mix["prompt"], n0, rng),
                             _block_draw(mix["prompt"], n - n0, rng)])
    out = np.concatenate([
        rng.permutation(residual_lengths(mix["output"], n0)),
        _block_draw(mix["output"], n - n0, rng)])
    prefixes, prefix_of = [], np.full(n, -1)
    if mix.get("prefix"):
        p = mix["prefix"]
        plen = rng.permutation(lengths(p["length"], p["count"]))
        prefixes = [rng.integers(FIRST_ID, vocab, int(m)).tolist()
                    for m in plen]
        zipf = 1.0 / np.arange(1, p["count"] + 1) ** p["zipf"]
        prefix_of = np.concatenate([
            _picks(zipf, min(BLOCK, n - b), rng)
            for b in range(0, n, BLOCK)])

    reqs = [Planned(rid=i, prompt_len=int(prompt[i]),
                    max_new=int(out[i]), seed=seed, vocab=vocab,
                    prefix=(prefixes[int(prefix_of[i])]
                            if prefix_of[i] >= 0 else []))
            for i in range(n)]

    if mix["loop"] == "open":
        t = np.concatenate([np.zeros(n0),
                            np.cumsum(_gaps(n - n0, mix["rate_rps"], rng))])
        for r, due in zip(reqs, t, strict=True):
            r.due = float(due)
    else:
        for r in reqs:
            r.client = r.rid % mix["clients"]
    return reqs
