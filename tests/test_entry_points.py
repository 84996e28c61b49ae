"""Process rules of the entry points: where the compile cache lives, and
that the chip smoke run refuses a host without the chip."""
import os
import subprocess
import sys

import jax

from repro import util

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_compile_cache_follows_env_then_the_checkout(monkeypatch):
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert util.enable_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == was  # untouched
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = util.enable_compile_cache()
        assert path == os.path.join(ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        with open(os.path.join(ROOT, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_chip_smoke_refuses_a_cpu_host():
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=ROOT,
                       capture_output=True, text=True, timeout=120,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "no TPU" in r.stderr


def test_chip_smoke_parity_verdicts():
    """The teacher-forced comparison: logits within LOGIT_BOUND x rms,
    and a different greedy choice only where it is a near-tie."""
    import importlib.util

    import numpy as np
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    cs = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cs)

    rng = np.random.default_rng(0)
    row = rng.standard_normal(64).astype(np.float32)
    top, second = np.argsort(row)[-1], np.argsort(row)[-2]
    bound = cs.LOGIT_BOUND * float(np.sqrt(np.mean(row * row)))

    def tapes(got_row):
        ref, got = cs.Tape(), cs.Tape()
        ref.rows[(0, 0)], ref.tokens[(0, 0)] = row, int(np.argmax(row))
        got.rows[(0, 0)] = got_row
        got.tokens[(0, 0)] = int(np.argmax(got_row))
        return cs.compare(ref, got)

    assert tapes(row.copy())["ok"]
    off = row.copy()
    off[3] += 2 * bound
    assert not tapes(off)["ok"]
    near = row.copy()
    near[second] = near[top] + bound / 4       # flips argmax, within bound
    res = tapes(near)
    assert res["greedy_near_tie"] == 1 and res["greedy_wrong"] == 0
    far = row.copy()
    far[second] = far[top] + 0.9 * bound
    far[top] -= 0.9 * bound                    # decisive flip
    assert tapes(far)["greedy_wrong"] == 1 and not tapes(far)["ok"]
    ref = cs.Tape()
    ref.rows[(0, 0)], ref.tokens[(0, 0)] = row, int(np.argmax(row))
    assert not cs.compare(ref, cs.Tape())["ok"]   # unmatched rows
