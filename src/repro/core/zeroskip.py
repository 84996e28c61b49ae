"""Zero-value bit-skipping statistics and cycle model (paper §III.C).

The macro's input buffer skips any bit-pair where x_ii'(i*) AND x_jj'(j*)
is zero — the word line never fires, saving the add cycle and its energy.
A systolic MXU cannot skip data-dependently, so on TPU this lives as:

  (a) a faithful *cycle/energy model*: given real input tensors, count the
      exact number of fired vs skipped word-line events the macro would see
      (reproduces the paper's ">=55% reduction" claim in
      tests/test_zeroskip_energy.py), and
  (b) the TPU-friendly analogue — token-level padding skip via sequence
      packing (data/pipeline.py) — which removes whole all-zero rows, the
      dominant source of zero bits the paper cites.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.bitserial import to_bitplanes

# One-bit counts accumulate in int32 on device; the total over an
# operand is bounded by N * D * bits, so the sum is exact iff that
# product stays below 2^31. Asserted in skip_stats; bigger workloads
# (real serving traces easily reach N * D * bits >= 2^31) go through
# skip_stats_chunked, which slices rows under the bound and combines
# the exact per-chunk counts as Python ints.
_INT32_EVENT_BOUND = 2 ** 31


class SkipStats(NamedTuple):
    """Counts are exact: per-row 1-bit tallies accumulate in int32
    (bound asserted), the final sums and product are Python ints
    (arbitrary precision — no 2^24 f32 or 2^53 f64 rounding, however
    large the workload). Only the derived *ratios* are float64. Not
    jit-traceable (by design: exactness requires leaving the f32
    accumulator domain)."""
    total_events: int           # word-line events without skipping
    fired_events: int           # events where both gating bits are 1
    bit_density_a: np.ndarray   # fraction of 1-bits in xa planes (f64)
    bit_density_b: np.ndarray

    @property
    def skip_fraction(self):
        return 1.0 - self.fired_events / max(self.total_events, 1)


@partial(jax.jit, static_argnames=("bits",))
def _ones_kernel(x: jax.Array, bits: int) -> jax.Array:
    planes = to_bitplanes(x, bits)                    # (N, D, K) uint8
    return jnp.sum(planes, dtype=jnp.int32)


def _ones_sum(x: jax.Array, bits: int) -> int:
    """Exact total 1-bit count of one operand (N, D) as a Python int.
    Caller guarantees N * D * bits < 2^31 (int32 accumulation bound)."""
    return int(_ones_kernel(jnp.asarray(x), bits))


def skip_stats(xa: jax.Array, xb: jax.Array, bits: int = 8) -> SkipStats:
    """Exact count of fired word-line events for scores over (xa, xb).

    A word-line event exists for every (i, j, i', j', i*, j*) tuple; it
    fires iff xa[i, i'](i*) & xb[j, j'](j*). Because the AND factorizes,
    fired = (sum of 1-bits over xa rows) x (sum of 1-bits over xb rows)
    summed over (i,j) pairs — computed exactly without materializing the
    6-D event tensor.

    xa (Na, D) int8, xb (Nb, D) int8. Workloads past the int32 event
    bound (N * D * bits >= 2^31) must go through skip_stats_chunked.
    """
    Na, D = xa.shape[-2], xa.shape[-1]
    Nb = xb.shape[-2]
    for n, name in ((Na, "xa"), (Nb, "xb")):
        if n * D * bits >= _INT32_EVENT_BOUND:
            raise ValueError(
                f"{name}: {n} x {D} x {bits} one-bit events can exceed "
                f"int32 — use skip_stats_chunked, which combines exact "
                f"per-chunk counts as Python ints")
    sa = _ones_sum(xa, bits)
    sb = _ones_sum(xb, bits)
    return SkipStats(Na * Nb * D * D * bits * bits,   # exact Python ints
                     sa * sb,
                     np.float64(sa) / (Na * D * bits),
                     np.float64(sb) / (Nb * D * bits))


def skip_stats_chunked(xa: jax.Array, xb: jax.Array, bits: int = 8,
                       chunk: int = 4096) -> SkipStats:
    """skip_stats for workloads of ANY size: rows are processed in
    chunks that individually respect the int32 accumulation bound and
    the exact per-chunk 1-bit counts combine as Python ints (the
    factorized fired count only needs each operand's total — sums over
    row chunks are associative with no rounding at any size).

    ``chunk`` rows per slice; it is clamped down automatically if
    ``chunk * D * bits`` itself would exceed the bound. Bit-identical
    to skip_stats wherever both are defined.

    This is the jnp-side API for exact counts at any size; the macro
    simulator's trace capture keeps its own host-side tally at finer
    granularity (``repro.sim.skip.operand_stats`` — per-row/per-plane,
    int64 numpy). tests/test_sim.py pins the two implementations to
    identical fired/total counts so they cannot drift apart.
    """
    D = xa.shape[-1]
    if xb.shape[-1] != D:
        raise ValueError(f"operand widths differ: {D} vs {xb.shape[-1]}")
    max_rows = (_INT32_EVENT_BOUND - 1) // max(D * bits, 1)
    if max_rows < 1:
        raise ValueError(f"one row of {D} x {bits} bits already exceeds "
                         f"the int32 event bound")
    chunk = max(1, min(chunk, max_rows))

    def total_ones(x) -> int:
        return sum(_ones_sum(x[r:r + chunk], bits)
                   for r in range(0, x.shape[-2], chunk))

    Na, Nb = xa.shape[-2], xb.shape[-2]
    sa = total_ones(xa)
    sb = sa if xb is xa else total_ones(xb)
    return SkipStats(Na * Nb * D * D * bits * bits,
                     sa * sb,
                     np.float64(sa) / (Na * D * bits),
                     np.float64(sb) / (Nb * D * bits))


def cycles_with_skip(stats: SkipStats, lanes: int = 64) -> float:
    """Macro cycles with zero-skip: only fired events consume add cycles;
    `lanes` parallel adder columns (64 in the paper's 64x64 array)."""
    return stats.fired_events / lanes


def cycles_without_skip(stats: SkipStats, lanes: int = 64) -> float:
    return stats.total_events / lanes
