"""Readings for a cell's correctness limits, on the chip.

    python3 bench/calibrate.py --workload qwen14b.decode \
        --seeds 11,12,13 --seconds 20 [--control]

For each seed, in one process: weights from the seed, a window of the
cell's traffic at its own load, then the same sample of finished
requests that a benchmark run compares (``check.sample``), replayed
through the float32 reference of the cell's architecture: the widest
gap of a served token below the reference's best. With ``--control``
the reference in float8 is put in the program's place on the same
prompts and tokens, and the gap of its own first choices is read the
same way.
One JSON line per seed on standard output.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from bench import check, run, spec  # noqa: E402


def _stats(g) -> dict:
    import numpy as np
    if not g.size:
        return {"n": 0}
    return {"n": int(g.size), "max": float(g.max()),
            "p99": float(np.quantile(g, 0.99)), "mean": float(g.mean()),
            "nonzero": int((g > 0).sum())}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)
    cell = spec.load_cell(ROOT, args.workload)
    chip = run.open_chip(cell, ROOT)
    if chip is None:
        return 1
    lim = cell.config["correctness"]
    for seed in (int(x) for x in args.seeds.split(",")):
        params = run.make_weights(cell, seed)
        r = run.serve(cell, params, seed, args.seconds, peaks=chip[1])
        del params
        gc.collect()
        picked = check.sample(r, seed, lim["sample_tokens"],
                              lim["sample_max_requests"])
        seqs = [(s.prompt, s.output) for s in picked]
        rec = {"workload": cell.name, "seed": seed,
               "requests": len(picked),
               "finished": len(check.finished(r)),
               "program": _stats(cell.arch.gaps(cell.config, seed, seqs))}
        if args.control:
            rec["control_fp8"] = _stats(cell.arch.gaps(
                cell.config, seed, seqs, quant="fp8"))
        print(json.dumps(rec), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
