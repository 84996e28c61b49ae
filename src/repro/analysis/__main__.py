"""``python -m repro.analysis`` — run the four static-analysis layers.

Order: lint (pure AST, milliseconds) -> contracts (imports jax, no
devices) -> kernelcheck (symbolic kernel verifier, eval_shape only) ->
invariants (subprocess with forced host devices, so the meshed checks
see a real 1x4 mesh without mutating THIS process's XLA_FLAGS — same
idiom as tests/conftest.forced_devices_env).

``--only LAYER`` (repeatable) restricts the run; ``--list`` prints the
layer names. The summary line reports PASS/FAIL per executed layer and
the exit code is 0 iff every executed layer passed.
"""
from __future__ import annotations

import argparse
import os
import subprocess
import sys

LAYERS = ("lint", "contracts", "kernelcheck", "invariants")

_DESCRIPTIONS = {
    "lint": "pure-AST JAX/Pallas footgun lint (RA101-RA108), no jax import",
    "contracts": "Pallas/budget contract checker (VMEM mirrors, grid math, "
                 "paged-cache accounting vs layout rule)",
    "kernelcheck": "symbolic kernel verifier: index-map bounds, write-once "
                   "coverage, VMEM pipeline fit, quantization plumbing",
    "invariants": "jaxpr/HLO invariants in a forced-device subprocess "
                  "(collective signatures, graph stability, shardings)",
}


def _run_lint() -> bool:
    from repro.analysis import lint
    findings = lint.check_paths()
    for f in findings:
        print(f)
    print(f"[lint] {len(findings)} finding(s)")
    return not findings


def _run_contracts() -> bool:
    from repro.analysis import contracts
    violations = contracts.run_all()
    for v in violations:
        print(f"VIOLATION: {v}")
    return not violations


def _run_kernelcheck() -> bool:
    from repro.analysis import kernelcheck
    violations = kernelcheck.run_all()
    for v in violations:
        print(f"VIOLATION: {v}")
    return not violations


def _run_invariants() -> bool:
    from repro.analysis import invariants
    n = invariants.MESH_SHAPE[0] * invariants.MESH_SHAPE[1]
    env = dict(os.environ, JAX_PLATFORMS="cpu")     # forced CPU devices
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={n} "
                        + env.get("XLA_FLAGS", "")).strip()
    env.setdefault("PYTHONPATH", os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
    proc = subprocess.run(
        [sys.executable, "-m", "repro.analysis.invariants"], env=env)
    return proc.returncode == 0


_RUNNERS = {
    "lint": _run_lint,
    "contracts": _run_contracts,
    "kernelcheck": _run_kernelcheck,
    "invariants": _run_invariants,
}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m repro.analysis",
        description="Run the repo's static-analysis layers.")
    parser.add_argument(
        "--only", action="append", choices=LAYERS, metavar="LAYER",
        help="run only this layer (repeatable); default: all layers in "
             f"order {', '.join(LAYERS)}")
    parser.add_argument(
        "--list", action="store_true",
        help="list the available layers and exit")
    args = parser.parse_args(argv)

    if args.list:
        for name in LAYERS:
            print(f"{name:12s} {_DESCRIPTIONS[name]}")
        return 0

    selected = [n for n in LAYERS if not args.only or n in args.only]
    results: dict[str, bool] = {}
    for name in selected:
        print(f"=== repro.analysis: {name} ===")
        results[name] = _RUNNERS[name]()

    status = " ".join(
        f"{n}={'PASS' if ok else 'FAIL'}" for n, ok in results.items())
    if all(results.values()):
        print(f"repro.analysis: {status} -> OK")
        return 0
    print(f"repro.analysis: {status} -> FAILED")
    return 1


if __name__ == "__main__":
    raise SystemExit(main())
