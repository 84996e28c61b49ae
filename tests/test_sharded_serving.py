"""Tensor-parallel sharded serving: mesh-aware cache budgeting (pure
accounting, no devices), admission scaling at equal per-device HBM, and
the multi-device oracle parity sweep (subprocess with 8 forced CPU
devices; env from conftest.forced_devices_env).

The oracle sweep is the acceptance check for the mesh-native engine:
on 1x4 and 1x8 meshes, greedy tokens must be IDENTICAL to the
single-device engine and every sampling call's active-slot logits must
match to float tolerance, across {kv, xv, x} x {float, int8} x
{stream, gather}, with admission/eviction/prefix-fork exercised
mid-run (more requests than slots, shared prompt prefixes, a scarce
block pool). A degenerate 1x1 mesh must reproduce mesh=None exactly;
the head-unsplittable ``factored`` backend must fall back to a
replicated pool with a warning, not crash.
"""
import dataclasses
import subprocess
import sys

import numpy as np
import pytest

from conftest import forced_devices_env
from repro.configs.base import get_arch, reduced
from repro.core import score_backend as sb
from repro.serving import kvcache


def _cfg(**over):
    base = dict(num_layers=2, num_heads=8, num_kv_heads=8)
    base.update(over)
    cfg = reduced(get_arch("qwen2.5-14b"), **base)
    return dataclasses.replace(cfg, dtype="float32")


# --------------------------------------------------- budget accounting

def test_max_blocks_scales_with_pool_shards():
    """kv pool rows split by the head axis: the same per-device HBM
    buys shard-factor times the blocks at 1/4/8-way."""
    cfg = _cfg(score_mode="standard")
    pb = kvcache.paged_budget_for(cfg, block_size=8)
    hbm = 1 << 20
    n1 = pb.max_blocks(hbm)
    assert pb.max_blocks(hbm, 1) == n1          # int shard count
    assert pb.max_blocks(hbm, 4) == 4 * n1
    assert pb.max_blocks(hbm, 8) == 8 * n1
    assert pb.per_device_bytes_per_block(4) * 4 \
        == pb.per_device_bytes_per_block()


def test_max_blocks_head_dim_fallback_and_replication():
    """Hkv=2 on a 4-way axis head-shards via the head-DIM fallback
    (dh=32 divides — same rule as specs.paged_pool_shardings / wk's
    spec_for fallback); a shard count dividing neither dim must NOT
    promise extra blocks."""
    cfg = _cfg(num_kv_heads=2, score_mode="standard")
    pb = kvcache.paged_budget_for(cfg, block_size=8)
    hbm = 1 << 20
    assert pb.max_blocks(hbm, 4) == 4 * pb.max_blocks(hbm)  # dh fallback
    assert pb.max_blocks(hbm, 2) == 2 * pb.max_blocks(hbm)  # Hkv divides
    # 5 divides neither Hkv=2 nor dh=32: replicated, no phantom blocks
    assert pb.max_blocks(hbm, 5) == pb.max_blocks(hbm)


def test_max_blocks_xv_layout_partial_sharding():
    """xv pool: X rows split over D, V rows over (Hkv, dh) — a shard
    count dividing D but neither head dim shards only the X component."""
    cfg = _cfg(num_kv_heads=2, head_dim=12, score_mode="wqk",
               cache_mode="xv")
    pb = kvcache.paged_budget_for(cfg, block_size=8)
    D, Hkv, dh = cfg.d_model, cfg.num_kv_heads, cfg.head_dim
    per1 = pb.per_device_bytes_per_block()
    per8 = pb.per_device_bytes_per_block(8)  # 8 | D=128; 8 !| {2, 12}
    dtype_bytes = pb.dtype_bytes
    expect8 = (D * dtype_bytes // 8 + Hkv * dh * dtype_bytes) \
        * pb.layers * pb.block_size
    assert per1 == (D + Hkv * dh) * dtype_bytes * pb.layers * pb.block_size
    assert per8 == expect8
    assert per8 < per1


def test_max_blocks_accepts_mesh_or_none():
    cfg = _cfg(score_mode="standard")
    pb = kvcache.paged_budget_for(cfg, block_size=8)
    assert pb.pool_shards(None) == 1
    assert pb.pool_shards(4) == 4

    class _FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 1, "model": 4}
    assert pb.pool_shards(_FakeMesh()) == 4
    assert pb.max_blocks(1 << 20, _FakeMesh()) \
        == pb.max_blocks(1 << 20, 4)


def test_shards_heads_capability_in_plan():
    """The planner surfaces the backend's head-sharding capability; the
    factored rank-dh path (shared K projection) cannot split."""
    assert sb.plan(_cfg(score_mode="standard")).shards_heads
    assert sb.plan(_cfg(score_mode="wqk")).shards_heads
    assert not sb.plan(_cfg(score_mode="factored")).shards_heads


def test_tpu_mesh_refuses_x_stream_plans():
    """A sharded X pool splits D over the model axis; the Pallas paged
    kernel contracts over D, so a TPU mesh refuses the streamed X plan
    instead of compiling something else. kv plans, the gather schedule
    and CPU meshes (jnp stream under GSPMD) pass."""
    from repro.serving.engine import check_mesh_plan
    x = sb.plan(_cfg(score_mode="wqk_int8", cache_quant="int8"))
    with pytest.raises(ValueError, match="split D"):
        check_mesh_plan(x, "stream", "tpu")
    check_mesh_plan(x, "gather", "tpu")
    check_mesh_plan(x, "stream", "cpu")
    check_mesh_plan(sb.plan(_cfg(score_mode="standard")), "stream", "tpu")


# ---------------------------------------------- admission at equal HBM

def test_admission_scales_with_per_device_budget():
    """A 4-way pool shard means 4x the blocks per device-budget —
    the engine admits ~4x the concurrent sequences. (Host-side: the
    allocator is sized from the per-device accounting; the real-mesh
    engine path is exercised by the subprocess sweep below.)"""
    import jax
    from repro.models.model import build_model
    from repro.serving.engine import Engine, Request

    cfg = _cfg(score_mode="standard")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    pb = kvcache.paged_budget_for(cfg, block_size=8)
    max_len = 64
    hbm = pb.bytes_per_block * (max_len // 8)   # one worst-case seq

    def peak(shards):
        eng = Engine(model, params, max_slots=16, max_len=max_len,
                     block_size=8,
                     num_blocks=pb.max_blocks(hbm, shards))
        rng = np.random.default_rng(0)
        reqs = [Request(rid=i,
                        tokens=[1] + rng.integers(3, 500, 10).tolist(),
                        max_new_tokens=4, eos_id=None)
                for i in range(16)]
        eng.run(reqs)
        assert all(r.done for r in reqs)
        return eng.peak_active

    p1, p4 = peak(1), peak(4)
    assert p4 >= 3 * p1, (p1, p4)


# ------------------------------------------------- oracle parity sweep

_SWEEP_SCRIPT = r"""
import dataclasses, json, sys, warnings
import jax, numpy as np
from repro.configs.base import get_arch, reduced
from repro.models.model import build_model
from repro.serving.engine import Engine, Request
from repro.launch.mesh import make_mesh
assert len(jax.devices()) == 8, jax.devices()


# ONE definition of what "parity" compares: every sampling call's
# active-slot logits (inactive decode rows are garbage by design)
class CapEngine(Engine):
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


def build(score_mode, cache_mode=None, cache_quant=None):
    over = dict(num_layers=2, num_heads=8, num_kv_heads=8,
                score_mode=score_mode)
    if cache_mode:
        over["cache_mode"] = cache_mode
    if cache_quant:
        over["cache_quant"] = cache_quant
    cfg = dataclasses.replace(reduced(get_arch("qwen2.5-14b"), **over),
                              dtype="float32")
    model = build_model(cfg)
    return model, model.init(jax.random.PRNGKey(0))


def requests():
    # more requests than slots + shared prompt prefixes + scarce pool:
    # admission queues, prefix blocks fork copy-on-write, finished
    # sequences evict and their blocks get reused mid-run
    rng = np.random.default_rng(0)
    shared = [1] + rng.integers(3, 500, 17).tolist()
    out = []
    for i in range(7):
        if i % 2 == 0:
            toks = shared[: 10 + 2 * i] \
                + rng.integers(3, 500, 3).tolist()
        else:
            toks = [1] + rng.integers(3, 500, 4 + 3 * i).tolist()
        out.append(Request(rid=i, tokens=toks, max_new_tokens=4 + i % 3,
                           eos_id=None))
    return out


def run(model, params, mesh, sched):
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        e = CapEngine(model, params, max_slots=3, max_len=64,
                      block_size=8, num_blocks=24, mesh=mesh,
                      decode_schedule=sched)
    reqs = requests()
    e.run(reqs)
    assert all(r.done for r in reqs)
    return e, [r.output for r in reqs]


def parity(label, model, params, mesh, sched, exact=False, atol=1e-4):
    ref, ref_out = run(model, params, None, sched)
    got, got_out = run(model, params, mesh, sched)
    assert ref_out == got_out, (label, ref_out, got_out)
    assert len(ref.logit_log) == len(got.logit_log), label
    for a, b in zip(ref.logit_log, got.logit_log):
        assert a.shape == b.shape, label
        if exact:
            np.testing.assert_array_equal(a, b, err_msg=label)
        else:
            np.testing.assert_allclose(a, b, atol=atol, err_msg=label)
    if label.startswith("kv-") and mesh.shape["model"] >= 4:
        # each device holds only its head slice of the kv pool
        assert got.pool_sharded, label
        assert ref.pool_bytes_per_device() \
            >= 2 * got.pool_bytes_per_device(), label
    print(f"  {label}: ok")


def mesh_of(spec):
    d, m = spec.split("x")
    return make_mesh((int(d), int(m)), ("data", "model"))


# combos arrive as JSON argv so the tier-1 run and the nightly full
# matrix share ONE script (and one definition of parity)
payload = json.loads(sys.argv[1])
for label, score_mode, cache_mode, cache_quant, spec, sched, atol \
        in payload["combos"]:
    model, params = build(score_mode, cache_mode, cache_quant)
    parity(label, model, params, mesh_of(spec), sched, atol=atol)

if payload.get("extras"):
    # degenerate 1x1 mesh == mesh=None, bit-for-bit
    model, params = build("standard")
    parity("kv-float-stream-1x1-exact", model, params, mesh_of("1x1"),
           "stream", exact=True)

    # factored cannot split heads: replicated-pool fallback + warning
    model, params = build("factored")
    mesh4 = mesh_of("1x4")
    with warnings.catch_warnings(record=True) as wlog:
        warnings.simplefilter("always")
        e = Engine(model, params, max_slots=3, max_len=64, block_size=8,
                   num_blocks=24, mesh=mesh4)
    assert any("cannot shard heads" in str(w.message) for w in wlog), \
        [str(w.message) for w in wlog]
    assert not e.pool_sharded
    reqs = requests()
    e.run(reqs)
    ref = Engine(model, params, max_slots=3, max_len=64, block_size=8,
                 num_blocks=24)
    ref_reqs = requests()
    ref.run(ref_reqs)
    assert [r.output for r in reqs] == [r.output for r in ref_reqs]
print("SHARDED_SWEEP_OK")
"""

# int8 rows tolerate a quantization step of drift: an epsilon-level
# reduction-reorder difference on a value sitting at a rounding
# boundary flips one int8 code (~row_max/127) — greedy tokens must
# still match exactly. Combo rows: [label, score_mode, cache_mode,
# cache_quant, mesh, schedule, atol].
TIER1_COMBOS = [
    ["kv-float-stream-1x4", "standard", None, None, "1x4", "stream", 1e-4],
    ["kv-float-gather-1x4", "standard", None, None, "1x4", "gather", 1e-4],
    ["kv-int8-stream-1x4", "standard", None, "int8", "1x4", "stream", 5e-3],
    ["xv-float-stream-1x4", "wqk", "xv", None, "1x4", "stream", 1e-4],
    ["xv-int8-gather-1x4", "wqk", "xv", "int8", "1x4", "gather", 5e-3],
    ["x-float-gather-1x4", "wqk", "x", None, "1x4", "gather", 1e-4],
    ["x-int8-stream-1x4", "wqk", "x", "int8", "1x4", "stream", 5e-3],
    ["kv-float-stream-1x8", "standard", None, None, "1x8", "stream", 1e-4],
]


def _full_matrix():
    """The nightly sweep: every {layout} x {quant} x {schedule} on both
    mesh widths — 24 combos (tier-1 runs the 8-row diagonal above)."""
    combos = []
    for spec in ("1x4", "1x8"):
        for lname, smode, cmode in (("kv", "standard", None),
                                    ("xv", "wqk", "xv"),
                                    ("x", "wqk", "x")):
            for quant in (None, "int8"):
                for sched in ("stream", "gather"):
                    combos.append(
                        [f"{lname}-{quant or 'float'}-{sched}-{spec}",
                         smode, cmode, quant, spec, sched,
                         5e-3 if quant else 1e-4])
    return combos


def _run_sweep(combos, extras, timeout):
    import json
    r = subprocess.run(
        [sys.executable, "-c", _SWEEP_SCRIPT,
         json.dumps({"combos": combos, "extras": extras})],
        capture_output=True, text=True, timeout=timeout,
        env=forced_devices_env(8))
    assert "SHARDED_SWEEP_OK" in r.stdout, r.stdout + r.stderr


def test_sharded_engine_matches_oracle_subprocess():
    """1x4 + 1x8 meshes across layouts/quant/schedules == the
    single-device engine, token-for-token and logit-for-logit; a
    head-sharded kv pool holds >= 2x fewer bytes per device."""
    _run_sweep(TIER1_COMBOS, extras=True, timeout=1800)


@pytest.mark.nightly
def test_sharded_engine_full_matrix_nightly():
    """The exhaustive 24-combo cross product (scheduled workflow only —
    see .github/workflows/nightly.yml)."""
    _run_sweep(_full_matrix(), extras=False, timeout=3600)


def test_parse_mesh_validates():
    from repro.launch.mesh import parse_mesh
    with pytest.raises(ValueError, match="expected 'DxM'"):
        parse_mesh("4")
    with pytest.raises(ValueError, match="device"):
        parse_mesh("64x64")             # far beyond any visible host
    m = parse_mesh("1x1")
    assert m.axis_names == ("data", "model")

