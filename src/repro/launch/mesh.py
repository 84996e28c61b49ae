"""Production mesh construction.

Defined as functions (never module-level constants) so importing this
module never touches jax device state. The dry-run launcher sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 BEFORE importing jax.
"""
from __future__ import annotations

import jax


def make_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(
        jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


_MESH_AXES = ("data", "model")


def _parse_mesh_spec(spec: str) -> tuple[int, int]:
    """Parse 'DxM' or 'data=D,model=M' (either separator) into (D, M),
    validating names and values — every bad spec gets a targeted error
    here instead of an opaque mesh-construction failure deep in jax."""
    s = spec.lower().replace("×", "x").strip()
    hint = "expected 'DxM' (e.g. '2x4') or 'data=D,model=M'"
    if "=" in s:
        sizes: dict[str, int] = {}
        for part in (p for p in s.replace(",", "x").split("x") if p):
            name, _, val = part.partition("=")
            name, val = name.strip(), val.strip()
            if name not in _MESH_AXES:
                raise ValueError(
                    f"mesh spec {spec!r}: unknown axis {name!r}; serving "
                    f"meshes have axes {_MESH_AXES} — {hint}")
            if name in sizes:
                raise ValueError(
                    f"mesh spec {spec!r}: axis {name!r} given twice")
            if not val.isdigit():
                raise ValueError(
                    f"mesh spec {spec!r}: axis {name!r} needs an integer "
                    f"size, got {val!r} — {hint}")
            sizes[name] = int(val)
        return sizes.get("data", 1), sizes.get("model", 1)
    parts = s.split("x")
    if len(parts) != 2 or not all(p.strip().isdigit() for p in parts):
        raise ValueError(f"mesh spec {spec!r}: expected 'DxM', e.g. '1x4' "
                         f"(or named axes: 'data=D,model=M')")
    return int(parts[0]), int(parts[1])


def parse_mesh(spec: str):
    """Serving-mesh spec -> a ("data", "model") mesh.

    Accepts bare ``'DxM'`` ('1x4' = 4-way tensor parallelism; '1x1' =
    the degenerate host mesh, numerically identical to mesh=None) or
    named axes in either order (``'data=2,model=4'``). The data axis is
    the replica-router axis: ``replica_submeshes`` splits a DxM mesh
    into D independent (1, M) TP groups.

    Raises with the XLA_FLAGS recipe when the host exposes fewer
    devices than the spec needs, and rejects specs whose size does not
    divide the visible device count — jax versions differ on whether a
    non-dividing ``make_mesh`` fails loudly, slices silently, or dies
    deep in mesh construction, so the contract is enforced here.
    """
    d, m = _parse_mesh_spec(spec)
    if d < 1 or m < 1:
        raise ValueError(f"mesh spec {spec!r}: axes must be >= 1")
    have = len(jax.devices())
    if d * m > have:
        raise ValueError(
            f"mesh {spec} needs {d * m} devices but only {have} visible; "
            f"set XLA_FLAGS=--xla_force_host_platform_device_count={d * m} "
            f"before launching (must precede jax import)")
    if have % (d * m) != 0:
        raise ValueError(
            f"mesh {spec} ({d * m} devices) does not divide the {have} "
            f"visible devices — {have - have // (d * m) * (d * m)} would "
            f"sit idle. Use a spec whose size divides {have} (e.g. "
            f"'{1 if have % 2 else 2}x{have if have % 2 else have // 2}') "
            f"or force a matching device count via XLA_FLAGS")
    return make_mesh((d, m), _MESH_AXES)


def replica_submeshes(mesh) -> list:
    """Split a ("data", "model") serving mesh into its data-parallel
    replica groups: one (1, M) mesh per data-axis index, over disjoint
    devices. Each submesh drives an independent TP ``Engine`` (weights
    replicate per replica, pool/params shard over its own "model"
    axis); the replica router spreads requests across them."""
    import numpy as np

    names = tuple(mesh.axis_names)
    if names != _MESH_AXES:
        raise ValueError(
            f"replica_submeshes needs a ('data', 'model') mesh, "
            f"got axes {names}")
    devs = np.asarray(mesh.devices)
    return [jax.sharding.Mesh(devs[i:i + 1], _MESH_AXES)
            for i in range(devs.shape[0])]


def make_host_mesh():
    """Degenerate 1x1 mesh on the local device (smoke tests, examples)."""
    return make_mesh((1, 1), ("data", "model"))


def batch_axes(mesh) -> tuple:
    """Mesh axes the global batch shards over."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def fsdp_axes(mesh) -> tuple:
    """Axes parameters shard over FSDP-style (within-pod only: cross-pod
    parameter gathers would traverse the slow inter-pod links every layer;
    pods stay pure DP with one gradient all-reduce per step)."""
    return ("data",)
