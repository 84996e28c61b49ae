"""Benchmark aggregator: `PYTHONPATH=src python -m benchmarks.run`.

One module per paper table/figure + the framework-side roofline report.
Exit code 1 if any reproduction check fails.

This process never imports JAX: each module runs in a child of its own
(``python -m benchmarks.run --module NAME``), so the child that touches
a device is the only process holding it. Children that force host
devices (the sharded and router sweeps) run on the CPU platform.
"""
from __future__ import annotations

import argparse
import subprocess
import sys

MODULES = ("table1_macro", "fig6_cpu_gpu", "fig7_memory", "zeroskip_bench",
           "wqk_vs_standard", "score_backends", "serving_load",
           "serving_async", "serving_sharded", "sim_trace", "roofline")


class Report:
    def __init__(self):
        self.checks = []

    def section(self, s):
        print(f"\n== {s} ==")

    def row(self, s):
        print(f"   {s}")

    def check(self, name, ok):
        self.checks.append((name, bool(ok)))
        print(f"   [{'PASS' if ok else 'FAIL'}] {name}")


def run_module(name: str) -> int:
    """Child side: run one module's checks; exit 1 on any failure."""
    import importlib

    from repro import util
    util.enable_compile_cache()
    report = Report()
    importlib.import_module(f"benchmarks.{name}").run(report)
    n_fail = sum(1 for _, ok in report.checks if not ok)
    print(f"   -- {name}: {len(report.checks)} checks, {n_fail} failures",
          flush=True)
    return 1 if n_fail else 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--module", choices=MODULES,
                    help="run one module in this process")
    args = ap.parse_args(argv)
    if args.module:
        return run_module(args.module)
    failed = []
    for name in MODULES:
        r = subprocess.run([sys.executable, "-m", "benchmarks.run",
                            "--module", name])
        if r.returncode:
            failed.append(name)
    print(f"\n{'='*60}\n{len(MODULES)} modules, "
          f"{len(failed)} with failures{': ' if failed else ''}"
          f"{', '.join(failed)}")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
