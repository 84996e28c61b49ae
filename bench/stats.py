"""Percentile arithmetic of the benchmark.

``percentile`` is the rounded-rank rule of the serving front end's
metrics module (``serving/frontend/metrics._percentile``), copied here
so that a change to the program cannot change how the benchmark ranks
its samples.
"""
from __future__ import annotations


def percentile(values, q: float) -> float | None:
    """The sample at rank ``round(q / 100 * (n - 1))`` of the sorted
    values (q in [0, 100]); None for no values."""
    if not values:
        return None
    v = sorted(values)
    idx = min(len(v) - 1, max(0, round(q / 100.0 * (len(v) - 1))))
    return v[idx]
