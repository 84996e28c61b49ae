"""Tensor-parallel sharded serving benchmark: per-device HBM and
admitted concurrency, 1-way vs 4-way, with output parity.

The mesh-native engine's claim mirrors the paper's scale-out story
(weights stay resident per macro, only raw inputs stream): head-shard
the paged block pool over the "model" axis and each device holds only
its slice, so at FIXED concurrency the per-device decode-cache HBM
drops by the pool-shard factor — equivalently, at EQUAL per-device HBM
the mesh admits shard-factor times the concurrent sequences. Both are
measured here, against the single-device engine as the parity oracle
(greedy outputs must be identical, per-token logits within float
tolerance).

Writes ``BENCH_sharded.json`` with a ``sharded`` section gated by
baseline-free floors in ``benchmarks/check_regression.py`` (>=2x
per-device HBM reduction at 4-way, parity flags true).

    PYTHONPATH=src python -m benchmarks.serving_sharded [--json PATH]

Needs >= 4 visible devices; on CPU this module forces
``--xla_force_host_platform_device_count=4`` BEFORE importing jax (so
run it as its own process, not from an aggregator that already
initialized jax).
"""
from __future__ import annotations

import os

# Standalone runs (python -m benchmarks.serving_sharded) force the host
# devices BEFORE the jax import below. Guarded on __main__ so merely
# importing this module (benchmarks.run's aggregator) cannot leak a
# 4-device topology into sibling benchmarks — the aggregator's run()
# hook spawns a subprocess instead.
if __name__ == "__main__" and \
        "--xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    # ra: allow[RA103] __main__-guarded, precedes the jax import below;
    # importing the module (benchmarks.run) never reaches this branch
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4"
                               ).strip()

import argparse
import dataclasses
import json
import warnings

import jax
import numpy as np

from repro import util
from repro.configs.base import get_arch, reduced
from repro.launch.mesh import make_mesh
from repro.models.model import build_model
from repro.serving import kvcache
from repro.serving.engine import Engine, Request

MAX_LEN = 128
BLOCK = 8
MAX_NEW = 8
N_REQUESTS = 16
PROMPT_LENS = (4, 9, 17, 26, 33, 40)
TP = 4


class _CapturingEngine(Engine):
    """Engine that logs every sampling call's active-slot logits, so two
    engines fed the same request stream can be compared token-for-token
    (inactive decode rows are garbage by design and excluded)."""

    def __init__(self, *a, **k):
        super().__init__(*a, **k)
        self.logit_log = []

    def _sample(self, logits, temps):
        arr = np.asarray(logits, np.float32)
        if arr.shape[0] == self.max_slots:
            mask = np.array([r is not None for r in self.slot_req])
            arr = arr[mask]
        self.logit_log.append(arr)
        return super()._sample(logits, temps)


def _model():
    # num_heads/num_kv_heads chosen to divide the 4-way model axis so
    # the kv pool head-shards fully (the reduced default Hkv=2 would
    # drop to replication — elasticity, but not what we benchmark)
    cfg = reduced(get_arch("qwen2.5-14b"), num_layers=2, num_heads=8,
                  num_kv_heads=8, score_mode="standard")
    cfg = dataclasses.replace(cfg, dtype="float32")
    model = build_model(cfg)
    return model, model.init(jax.random.PRNGKey(0))


def _requests(n=N_REQUESTS, seed=0):
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        plen = PROMPT_LENS[i % len(PROMPT_LENS)]
        toks = [1] + rng.integers(3, 500, plen - 1).tolist()
        out.append(Request(rid=i, tokens=toks, max_new_tokens=MAX_NEW,
                           eos_id=None))
    return out


def run_pair(model, params, mesh, *, num_blocks=None, hbm_bytes=None,
             max_slots=8):
    """The sharded engine and the single-device oracle on the same
    request stream; returns both engines plus parity verdicts."""
    def one(m):
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            e = _CapturingEngine(model, params, max_slots=max_slots,
                                 max_len=MAX_LEN, block_size=BLOCK,
                                 num_blocks=num_blocks,
                                 hbm_bytes=hbm_bytes, mesh=m)
        reqs = _requests()
        e.run(reqs)
        return e, [r.output for r in reqs]

    ref, ref_out = one(None)
    got, got_out = one(mesh)
    outputs_equal = ref_out == got_out
    ldiff = 0.0
    logits_ok = len(ref.logit_log) == len(got.logit_log)
    if logits_ok:
        for a, b in zip(ref.logit_log, got.logit_log, strict=True):
            if a.shape != b.shape:
                logits_ok = False
                break
            ldiff = max(ldiff, float(np.max(np.abs(a - b))))
        logits_ok = logits_ok and ldiff < 1e-4
    return ref, got, outputs_equal, logits_ok, ldiff


def sweep() -> dict:
    model, params = _model()
    cfg = model.cfg
    mesh = make_mesh((1, TP), ("data", "model"))

    # fixed concurrency: identical pools on both engines; the sharded
    # one holds 1/TP of every block per device
    nbk = 8 * (MAX_LEN // BLOCK) + 1
    ref, got, out_eq, logits_ok, ldiff = run_pair(
        model, params, mesh, num_blocks=nbk)
    b1 = ref.pool_bytes_per_device()
    b4 = got.pool_bytes_per_device()

    # equal per-device HBM: the mesh engine's budget buys ~TP x blocks,
    # so it admits ~TP x the concurrent sequences. The budget is sized
    # scarce (one worst-case sequence's blocks) so admission, not the
    # slot count, is the binding constraint at 1-way.
    pb = kvcache.paged_budget_for(cfg, BLOCK)
    hbm = pb.bytes_per_block * (MAX_LEN // BLOCK)
    ref2, got2, out_eq2, _, _ = run_pair(model, params, mesh,
                                         hbm_bytes=hbm, max_slots=16)
    admit_ratio = got2.peak_active / max(ref2.peak_active, 1)

    return {"sharded": {
        "scale": {
            "tp": TP,
            "per_device_pool_bytes_tp1": b1,
            "per_device_pool_bytes_tp4": b4,
            "per_device_hbm_reduction_4way": b1 / max(b4, 1),
            "outputs_equal": bool(out_eq and out_eq2),
            "logits_ok": bool(logits_ok),
            "logits_max_abs_diff": ldiff,
            "admitted_ratio_equal_hbm": admit_ratio,
            "peak_concurrency_tp1": ref2.peak_active,
            "peak_concurrency_tp4": got2.peak_active,
        },
        "workload": {"requests": N_REQUESTS,
                     "prompt_lens": list(PROMPT_LENS),
                     "max_new": MAX_NEW, "max_len": MAX_LEN,
                     "block_size": BLOCK,
                     "hbm_budget_bytes_per_device": hbm,
                     "device": jax.default_backend(),
                     "devices": len(jax.devices())},
    }}


def run(report):
    """Aggregator hook (benchmarks.run): the sweep needs >= TP devices
    forced BEFORE jax initializes, so it always runs as a subprocess —
    the aggregator process already holds a 1-device jax."""
    import subprocess
    import sys
    report.section("Sharded serving: 1-way vs 4-way tensor parallelism")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={TP}")
    env.setdefault("PYTHONPATH", "src")
    r = subprocess.run([sys.executable, "-m", "benchmarks.serving_sharded"],
                       capture_output=True, text=True, env=env)
    for line in r.stdout.strip().splitlines():
        report.row(line)
    if r.returncode != 0 and r.stderr:
        report.row(r.stderr.strip().splitlines()[-1])
    report.check("sharded serving: >=2x per-device HBM + parity "
                 "(subprocess)", r.returncode == 0)


def main():
    util.enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default="BENCH_sharded.json")
    args = ap.parse_args()
    if len(jax.devices()) < TP:
        raise SystemExit(
            f"serving_sharded needs >= {TP} devices, found "
            f"{len(jax.devices())} — run as its own process so the "
            f"forced-host-device flag lands before jax init")
    out = sweep()
    s = out["sharded"]["scale"]
    print(f"fixed concurrency: {s['per_device_pool_bytes_tp1']:,} B/dev "
          f"(1-way) -> {s['per_device_pool_bytes_tp4']:,} B/dev "
          f"({TP}-way) = {s['per_device_hbm_reduction_4way']:.1f}x "
          f"reduction")
    print(f"equal per-device HBM: peak concurrency "
          f"{s['peak_concurrency_tp1']} -> {s['peak_concurrency_tp4']} "
          f"({s['admitted_ratio_equal_hbm']:.1f}x admitted)")
    print(f"parity: outputs_equal={s['outputs_equal']} "
          f"logits_ok={s['logits_ok']} "
          f"(|dlogits| {s['logits_max_abs_diff']:.2e})")
    with open(args.json, "w") as f:
        json.dump(out, f, indent=2, sort_keys=True)
    print(f"wrote {args.json}")
    if not (s["per_device_hbm_reduction_4way"] >= 2.0
            and s["outputs_equal"] and s["logits_ok"]
            and s["admitted_ratio_equal_hbm"] >= 3.0):
        raise SystemExit("sharded-serving acceptance checks FAILED")


if __name__ == "__main__":
    main()
