"""Find the knee of an open-loop cell once, on the chip.

    python3 bench/sweep.py --workload qwen14b.decode --seed 5 \
        --seconds 30 --rates 2,2.5,3,3.5,4

One process, one set of weights; for each offered rate a fresh engine
serves the cell's mix at that rate for its ramp and a window. The knee
is the highest rate at which admissions keep up with arrivals: the
queue of requests sent and not yet admitted is no longer at the
window's end than its longest in the window's first half. One JSON
line per rate, with the queue and the batch's occupancy over time.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from bench import run, spec  # noqa: E402


def queue_at(r, t: float) -> int:
    """Requests sent by ``t`` whose admission had not started."""
    started = {}
    for a in r.admissions:
        started.setdefault(a.rid, a.start)
    return sum(1 for s in r.sent if s.sent is not None and s.sent <= t
               and started.get(s.rid, float("inf")) > t)


def service_s(r) -> float | None:
    """Mean seconds from the start of a request's admission to its last
    token, over the requests that finished: with the rate, the number
    of requests in flight (Little's law)."""
    start = {}
    for a in r.admissions:
        start.setdefault(a.rid, a.start)
    d = [s.times[-1] - start[s.rid] for s in r.sent
         if s.finish is not None and s.times and s.rid in start]
    return sum(d) / len(d) if d else None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--ramp", type=float, default=None,
                    help="seconds of ramp, in place of the mix's")
    args = ap.parse_args(argv)
    cell = spec.load_cell(ROOT, args.workload)
    chip = run.open_chip(cell, ROOT)
    if chip is None:
        return 1
    params = run.make_weights(cell, args.seed)
    for rate in (float(x) for x in args.rates.split(",")):
        mix = {**cell.traffic, "rate_rps": rate}
        if args.ramp is not None:
            mix["ramp_s"] = args.ramp
        c = dataclasses.replace(cell, traffic=mix)
        r = run.serve(c, params, args.seed, args.seconds, peaks=chip[1])
        w0, w1 = r.window
        steps = [w0 + (w1 - w0) * k / 20 for k in range(21)]
        q = [queue_at(r, t) for t in steps]
        e2e = run.end_to_end(r, 0.0)
        rec = {"workload": cell.name, "rate_rps": rate,
               "due": len(r.in_window()), "queue": q,
               "keeps_up": q[-1] <= max(q[:11]),
               "occupancy": run.occupancy(r, c.traffic["ramp_s"]),
               "service_s": service_s(r),
               **{k: e2e[k] for k in ("ttft_p90_ms", "itl_p50_ms",
                                      "itl_p99_ms", "output_tok_s")}}
        print(json.dumps(rec), flush=True)
        del r
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
