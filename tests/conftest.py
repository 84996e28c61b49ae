"""Shared fixtures. NOTE: no XLA_FLAGS here — tests must see the real
1-device CPU host (the 512-device override belongs ONLY to dryrun.py).

Tests that need a multi-device view spawn a SUBPROCESS with the env
built by ``forced_devices_env`` below; the autouse guard fails any test
that mutates XLA_FLAGS in-process, because under pytest-xdist the
sibling tests sharing that worker would silently inherit (or silently
miss — jax is already initialized) the override.
"""
import os

import jax
import numpy as np
import pytest


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "nightly: slow full-matrix sweeps (24-combo sharded parity, "
        "all-arch serving smoke) run by the scheduled workflow: "
        "pytest -m nightly")


def pytest_collection_modifyitems(config, items):
    """Nightly-marked tests are skipped from plain runs (tier-1 must
    stay fast); any explicit ``-m`` expression takes over selection."""
    if config.option.markexpr:
        return
    skip = pytest.mark.skip(reason="nightly-only: run with -m nightly")
    for item in items:
        if "nightly" in item.keywords:
            item.add_marker(skip)


def forced_devices_env(num_devices=None):
    """Subprocess env for tests that force a host device count. The
    override must be set BEFORE the child's jax import and must never
    touch this (possibly xdist-worker) process's environment. The child
    is pinned to the CPU platform: forced host devices are CPU devices,
    and a child that probed for an accelerator would wait on (or take)
    a chip another process holds."""
    env = {"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
           "JAX_PLATFORMS": "cpu"}
    if num_devices:
        env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                            f"{num_devices}")
    return env


@pytest.fixture(autouse=True)
def _xla_flags_stay_put():
    """Guard: in-process XLA_FLAGS mutation breaks xdist workers."""
    before = os.environ.get("XLA_FLAGS")
    yield
    assert os.environ.get("XLA_FLAGS") == before, (
        "test mutated XLA_FLAGS in-process; use "
        "conftest.forced_devices_env + a subprocess instead")


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(1234)


@pytest.fixture(autouse=True)
def _x64_off():
    # the framework is bf16/f32 throughout; keep tests in default mode
    yield


def assert_trees_close(a, b, rtol=1e-5, atol=1e-5):
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb, strict=True):
        np.testing.assert_allclose(np.asarray(x, np.float32),
                                   np.asarray(y, np.float32),
                                   rtol=rtol, atol=atol)
