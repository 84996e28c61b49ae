"""Small shared utilities / runtime flags."""
from __future__ import annotations

import os

_UNROLL_ENV = "REPRO_UNROLL_SCANS"
# <checkout>/.jax_cache (gitignored): fixed, because the cache directory
# is part of what a later run must find again
_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; entry points call this
    first thing in ``main`` (never at import). Where
    ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
    sets nothing; otherwise the cache goes to ``<checkout>/.jax_cache``.
    Returns the directory in use. libtpu's own flags are left alone:
    nothing here reads or writes ``LIBTPU_INIT_ARGS``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", _CACHE_DIR)
    return _CACHE_DIR


def scan_unroll():
    """Read at trace time: when truthy, layer/chunk/block scans fully
    unroll. The dry-run uses this for its cost-analysis pass because XLA's
    ``cost_analysis()`` counts a while-loop body ONCE regardless of trip
    count (verified experimentally) — unrolled lowering restores exact
    FLOP/byte/collective totals. Normal runs keep rolled scans (small HLO,
    fast SPMD compiles, sequential-reuse buffers)."""
    v = os.environ.get(_UNROLL_ENV, "0")
    try:
        n = int(v)
    except ValueError:
        return False
    return True if n == 1 else (n if n > 1 else False)


def ffn_seq_shard() -> bool:
    """§Perf hillclimb A toggle: sequence-sharded FFN intermediates."""
    return os.environ.get("REPRO_FFN_SEQ_SHARD", "0") == "1"
