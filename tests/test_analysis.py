"""The static-analysis subsystem (repro.analysis) is itself under test:
every lint rule fires on a planted-bad fixture and stays silent on its
good twin; the contract checker rejects perturbed accounting/
divisibility rules; the symbolic kernel verifier (kernelcheck) proves
clean on every planner-reachable workload AND fails on each planted
violation class (OOB index map, write-twice, hole, oversized scratch,
unguarded gather, dropped scale ref); the invariant checker proves the
one-TP-collective claim on a forced 1x4 mesh AND flags a planted extra
collective (subprocess with forced host devices, conftest-style)."""
import subprocess
import sys
import textwrap

from conftest import forced_devices_env

from repro.analysis import contracts, lint


def codes(src, path="src/repro/somemod.py"):
    return [f.code for f in lint.check_source(textwrap.dedent(src), path)]


# ------------------------------------------------------------ lint rules

def test_ra101_tracer_branch_fires_and_good_twin_silent():
    bad = """
        import jax.numpy as jnp
        def f(x):
            if jnp.all(x > 0):
                return x
            return -x
    """
    good = """
        import jax.numpy as jnp
        def f(x):
            if bool(jnp.all(x > 0)):
                return x
            return -x
    """
    assert "RA101" in codes(bad)
    assert codes(good) == []


def test_ra101_covers_while_ternary_assert():
    assert "RA101" in codes("""
        import jax.numpy as jnp
        def f(x):
            while jnp.any(x):
                x = x - 1
            return x
    """)
    assert "RA101" in codes("""
        import jax.numpy as jnp
        def f(x):
            return 1 if jnp.max(x) > 0 else 0
    """)
    # float()-wrapped comparison is the documented remedy: silent
    assert codes("""
        import jax.numpy as jnp
        def f(x):
            assert float(jnp.max(x)) > 0
            return x
    """) == []


def test_ra102_host_sync_in_jit_target():
    bad = """
        import jax
        def step(x):
            return x.item() + 1
        run = jax.jit(step)
    """
    good = """
        import jax
        def step(x):
            return x + 1
        run = jax.jit(step)
        def report(x):
            return x.item()          # not a jit target: fine
    """
    assert "RA102" in codes(bad)
    assert codes(good) == []


def test_ra103_xla_env_mutation():
    bad = 'import os\nos.environ["XLA_FLAGS"] = "--foo"\n'
    good = 'import os\nos.environ["MY_FLAG"] = "--foo"\n'
    assert "RA103" in codes(bad)
    assert codes(good) == []


def test_ra103_suppression_needs_reason():
    with_reason = ('import os\n'
                   '# ra: allow[RA103] must precede the jax import\n'
                   'os.environ["XLA_FLAGS"] = "--foo"\n')
    bare = ('import os\n'
            '# ra: allow[RA103]\n'
            'os.environ["XLA_FLAGS"] = "--foo"\n')
    assert codes(with_reason) == []
    assert codes(bare) == ["RA100"]


def test_ra104_late_docstring():
    bad = 'import os\n"""I am not a docstring."""\n'
    good = '"""I am the docstring."""\nimport os\ndel os\n'
    assert "RA104" in codes(bad)
    assert codes(good) == []


def test_ra105_nonhashable_static():
    bad = """
        import jax
        def f(x, shape=[8, 8]):
            return x
        g = jax.jit(f, static_argnames="shape")
    """
    good = """
        import jax
        def f(x, shape=(8, 8)):
            return x
        g = jax.jit(f, static_argnames="shape")
    """
    bad_call = """
        import jax
        def f(x, shape=(8, 8)):
            return x
        g = jax.jit(f, static_argnames="shape")
        y = f(1, shape=[8, 8])
    """
    assert "RA105" in codes(bad)
    assert codes(good) == []
    assert "RA105" in codes(bad_call)


def test_ra106_unpinned_jit_only_in_serving():
    src = """
        import jax
        def f(x):
            return x
        def tick(x):
            return jax.jit(f)(x)
    """
    assert "RA106" in codes(src, path="src/repro/serving/engine2.py")
    # outside serving/ the rule does not apply
    assert codes(src, path="src/repro/models/model2.py") == []


def test_ra106_pinned_forms_are_silent():
    good = """
        import jax
        def f(x):
            return x
        g = jax.jit(f)                       # module-level name: pinned
        class E:
            def __init__(self):
                self._step = jax.jit(f)      # attribute: pinned
            def build(self, cache, k):
                cache[k] = jax.jit(f)        # subscript: pinned
                return jax.jit(f)            # returned: pinned by caller
    """
    bad_local = """
        import jax
        def f(x):
            return x
        def tick(x):
            h = jax.jit(f)                   # rebuilt per tick
            return h(x)
    """
    assert codes(good, path="src/repro/serving/engine2.py") == []
    assert "RA106" in codes(bad_local, path="src/repro/serving/engine2.py")


def test_lint_clean_on_this_repo():
    findings = lint.check_paths()
    assert findings == [], "\n".join(str(f) for f in findings)


# --------------------------------------------------------- contract layer

def test_contracts_clean_on_this_repo():
    assert contracts.run_all(verbose=False) == []


def test_contracts_reject_perturbed_divisibility():
    # a spec rule that shards the layer axis (extent 2) on a 4-way mesh
    # must be caught by the divisibility check
    def bad_spec(shape, msz):
        return ("model",)
    out = contracts.check_budget_vs_layout(extents=(4,), spec_fn=bad_spec)
    assert any("% 4" in v or "device_put" in v for v in out), out


def test_contracts_reject_never_sharding_spec():
    # a spec that never shards disagrees with the budget's split
    # decisions (and with per-device bytes) at every msz > 1
    out = contracts.check_budget_vs_layout(extents=(4,),
                                           spec_fn=lambda shape, msz: ())
    assert out


def test_contracts_reject_lying_budget():
    from repro.serving import kvcache

    class Lying:
        """Delegates everything but under-reports per-device bytes."""
        def __init__(self, inner):
            self._inner = inner

        def __getattr__(self, k):
            return getattr(self._inner, k)

        def per_device_bytes_per_block(self, shards):
            return self._inner.per_device_bytes_per_block(shards) - 8

    out = contracts.check_budget_vs_layout(
        budget_fn=lambda cfg, **kw: Lying(
            kvcache.paged_budget_for(cfg, **kw)))
    assert any("UNDER" in v for v in out), out


def test_contracts_reject_a_paged_step_over_vmem(monkeypatch):
    # a blocks-per-step rule that ignores VMEM: the contract's own count
    # of the served chunk's step (40 heads x 64 positions) fits at the
    # kernel's pick, and not at 64 blocks of 16 tokens
    from repro.kernels.paged_attention import kernel as pk
    chunk = dict(B=1, H=40, Hkv=8, n=64, E=128, dv=128, NB=2048, BS=16,
                 max_len=4096, int8=False)
    assert contracts.check_paged_grid([chunk]) == []
    monkeypatch.setattr(pk, "blocks_per_step", lambda *a, **kw: 64)
    out = contracts.check_paged_grid([chunk])
    assert any("a grid step of 64 blocks" in v for v in out), out


# -------------------------------------------------------- invariant layer

def test_graph_stability_and_no_host_ops_clean():
    from repro.analysis import invariants
    assert invariants.check_graph_stability() == []
    assert invariants.check_no_host_ops() == []


_MESHED_SCRIPT = """
import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.analysis import invariants
from repro.models import attention as attn

mesh = invariants._mesh()
clean = invariants.check_attention_one_collective(mesh)
assert not clean, f"clean attention flagged: {clean}"
print("CLEAN_OK")

# plant an extra collective: force h onto the model axis and back —
# GSPMD must insert a reshard (all-gather) the pinned table forbids
orig = attn.attention_decode_paged

def planted(pa, hx, pool, tables, pos, cfg, **kw):
    hx = jax.lax.with_sharding_constraint(
        hx, NamedSharding(mesh, P(None, None, "model")))
    hx = jax.lax.with_sharding_constraint(hx, NamedSharding(mesh, P()))
    return orig(pa, hx, pool, tables, pos, cfg, **kw)

attn.attention_decode_paged = planted
caught = invariants.check_attention_one_collective(mesh)
assert caught, "planted extra collective went undetected"
print("PLANTED_DETECTED", len(caught))
"""


def test_one_collective_on_forced_mesh_and_planted_violation():
    """Subprocess with 4 forced host devices (env via conftest — this
    process's jax stays single-device): the one-TP-collective claim
    holds on a real 1x4 mesh, and a planted extra collective makes the
    checker report a violation."""
    r = subprocess.run([sys.executable, "-c", _MESHED_SCRIPT],
                       capture_output=True, text=True, timeout=900,
                       env=forced_devices_env(4))
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "CLEAN_OK" in r.stdout
    assert "PLANTED_DETECTED" in r.stdout


# ------------------------------------------------- lint: RA107 / RA108

def test_ra107_branching_and_closure_in_index_map():
    bad_branch = """
        from jax.experimental import pallas as pl
        def build(nb):
            spec = pl.BlockSpec((128, 128),
                                lambda i, j: (i if i < nb else 0, 0))
    """
    bad_closure = """
        from jax.experimental import pallas as pl
        def build(nb):
            def imap(i, j):
                return (i % nb, 0)
            return pl.BlockSpec((128, 128), imap)
    """
    good = """
        from jax.experimental import pallas as pl
        def x_index_map(i, j):
            return (i, 0)
        def build():
            return pl.BlockSpec((128, 128), x_index_map)
    """
    assert "RA107" in codes(bad_branch)
    assert "RA107" in codes(bad_closure)
    assert codes(good) == []


def test_ra107_module_level_names_and_params_allowed():
    # closing over module-level constants / own parameters is fine —
    # that is exactly what the refactored kernels do (scalar-prefetch
    # refs arrive as index-map arguments).
    good = """
        import jax.numpy as jnp
        from jax.experimental import pallas as pl
        NULL_BLOCK = 0
        def block_index_map(b, j, tables_ref, used_ref, _where=jnp.where):
            return (_where(j < used_ref[b], tables_ref[b, j], NULL_BLOCK),
                    0, 0, 0)
        spec = pl.BlockSpec((1, 16, 4, 32), block_index_map)
    """
    assert codes(good) == []


def test_ra108_program_id_branch():
    bad_direct = """
        from jax.experimental import pallas as pl
        def kern(x_ref, o_ref):
            if pl.program_id(0) == 0:
                o_ref[...] = x_ref[...]
    """
    bad_via_name = """
        from jax.experimental import pallas as pl
        def kern(x_ref, o_ref):
            i = pl.program_id(0)
            if i == 0:
                o_ref[...] = x_ref[...]
    """
    good = """
        from jax.experimental import pallas as pl
        def kern(x_ref, o_ref):
            i = pl.program_id(0)
            @pl.when(i == 0)
            def _init():
                o_ref[...] = x_ref[...]
    """
    assert "RA108" in codes(bad_direct)
    assert "RA108" in codes(bad_via_name)
    assert codes(good) == []


# ------------------------------------------------------ kernelcheck layer

def _kc():
    from repro.analysis import kernelcheck
    return kernelcheck


def test_kernelcheck_clean_on_this_repo():
    """Every planner-reachable (config, layout, quantization,
    mesh-extent) combo proves clean for all four kernels."""
    out = _kc().run_all(verbose=False)
    assert out == [], "\n".join(out)


def test_kernelcheck_planted_oob_index_map():
    import dataclasses
    kc = _kc()
    spec = kc.wqk_spec(2, 256, 256, 64)
    bad = dataclasses.replace(spec, blocks=[
        dataclasses.replace(b, index_map=(lambda h, i, j: (i + 1, 0)))
        if b.name == "x_q" else b
        for b in spec.blocks])
    out = kc.check_in_bounds(bad)
    assert out, "planted OOB map not caught"
    assert "wqk_score" in out[0] and "x_q" in out[0]
    assert "grid point" in out[0]          # names the counterexample


def test_kernelcheck_planted_write_twice():
    import dataclasses
    kc = _kc()
    spec = kc.wqk_spec(2, 256, 256, 64)
    # out coords driven by axes (1, 2) while axis 0 (extent 2) iterates
    # OUTSIDE them: the same tile is written on separated grid steps.
    out_blk = next(b for b in spec.blocks if b.out)
    bad_blk = dataclasses.replace(
        out_blk, shape=(2, 2, 1), block=(1, 1, 1),
        index_map=(lambda h, i, j: (i, j, 0)))
    bad = dataclasses.replace(spec, blocks=[bad_blk])
    out = kc.check_write_once(bad)
    assert any("write-twice" in v for v in out), out


def test_kernelcheck_planted_hole():
    import dataclasses
    kc = _kc()
    spec = kc.wqk_spec(2, 256, 256, 64)
    # dim 2 pinned to block 0 while the operand has 2 blocks there
    out_blk = next(b for b in spec.blocks if b.out)
    bad_blk = dataclasses.replace(
        out_blk, index_map=(lambda h, i, j: (h, i, 0)))
    bad = dataclasses.replace(spec, blocks=[bad_blk])
    out = kc.check_write_once(bad)
    assert any("hole" in v or "never written" in v for v in out), out


def test_kernelcheck_nonaffine_falls_back_to_enumeration():
    import dataclasses
    kc = _kc()
    spec = kc.wqk_spec(2, 512, 512, 64)
    out_blk = next(b for b in spec.blocks if b.out)
    # j // 2 is not affine -> enumeration; half the dim-2 blocks are holes
    bad_blk = dataclasses.replace(
        out_blk, index_map=(lambda h, i, j: (h, i, j // 2)))
    bad = dataclasses.replace(spec, blocks=[bad_blk])
    out = kc.check_write_once(bad)
    assert any("hole" in v for v in out), out


def test_kernelcheck_planted_vmem_overflow():
    import dataclasses
    kc = _kc()
    spec = kc.flash_spec(4, 4, 1024, 1024, 128, 128)
    bad = dataclasses.replace(spec, scratch_bytes=32 << 20)
    out = kc.check_vmem(bad)
    assert out and "VMEM" in out[0] and "flash_scores" in out[0], out


def test_kernelcheck_gather_unguarded_escapes_bounds():
    """Dropping the liveness guard from the paged gather makes the
    abstract index unprovable (the raw table load is only constrained
    by int32 range), so check_in_bounds must flag it."""
    import dataclasses
    kc = _kc()

    def unguarded(i, seq_ref, start_ref, used_ref, tables_ref, *_refs):
        b = seq_ref[i]
        return (tables_ref[b, i - start_ref[b]], 0, 0, 0)  # no redirect

    spec, _ = _paged_fixture(kc)
    bad = dataclasses.replace(spec, blocks=[
        dataclasses.replace(b, abstract_eval=kc._step_eval(
            unguarded, 64, 4, 16, 8)) if b.name.startswith("k_pool")
        else b for b in spec.blocks])
    out = kc.check_in_bounds(bad)
    assert any("gather" in v and "escapes" in v for v in out), out


def test_kernelcheck_gather_off_its_own_run_escapes_bounds():
    """A logical block measured from another sequence's first step can
    be negative, so the liveness guard no longer proves the gather."""
    import dataclasses
    kc = _kc()
    import jax.numpy as jnp

    def off_run(i, seq_ref, start_ref, used_ref, tables_ref, *_refs,
                _where=jnp.where):
        b = seq_ref[i]
        j = i - start_ref[0]
        return (_where(j < used_ref[b], tables_ref[b, j], 0), 0, 0, 0)

    spec, _ = _paged_fixture(kc)
    bad = dataclasses.replace(spec, blocks=[
        dataclasses.replace(b, abstract_eval=kc._step_eval(
            off_run, 64, 4, 16, 8)) if b.name.startswith("k_pool")
        else b for b in spec.blocks])
    out = kc.check_in_bounds(bad)
    assert any("gather" in v and "escapes" in v for v in out), out


def test_kernelcheck_sub_block_past_the_padded_table_escapes_bounds():
    """A sub-block one past the step's K reads a table column beyond
    the padded width: the liveness select no longer proves the gather,
    whatever it selects."""
    import dataclasses
    import functools
    kc = _kc()
    from repro.kernels.paged_attention import kernel as k
    spec, _ = _paged_fixture(kc)
    past = functools.partial(k.block_index_map, t=8, K=8)
    bad = dataclasses.replace(spec, blocks=[
        dataclasses.replace(b, abstract_eval=kc._step_eval(
            past, 64, 4, 16, 8)) if b.name == "v_pool[7]" else b
        for b in spec.blocks])
    out = kc.check_in_bounds(bad)
    assert any("v_pool[7]" in v and "escapes" in v for v in out), out


def test_kernelcheck_paged_grid_is_the_flattened_steps():
    """The paged kernel's grid is one axis of at most B * ceil(nbk/K)
    steps of K = 128 // BS blocks, each pool operand K sub-blocks, and
    the maps that read the step arrays are evaluated abstractly."""
    kc = _kc()
    from repro.kernels.paged_attention import kernel as k
    spec, _ = _paged_fixture(kc)
    assert spec.grid == (4 * 2,)                  # nbk 16, K 8
    stepped = {b.name for b in spec.blocks if b.abstract_eval is not None}
    assert stepped == {"q", "out"} | {f"{p}[{t}]" for p in ("k_pool",
                                                            "v_pool")
                                      for t in range(8)}
    out_blk = next(b for b in spec.blocks if b.out)
    assert out_blk.index_map is k.out_index_map


def test_kernelcheck_paged_out_map_off_the_sequence_is_flagged():
    """An output map that follows the step's physical block instead of
    its sequence has no write-once proof."""
    import dataclasses
    kc = _kc()
    from repro.kernels.paged_attention import kernel as k
    spec, _ = _paged_fixture(kc)
    bad = dataclasses.replace(spec, blocks=[
        dataclasses.replace(b, abstract_eval=kc._step_eval(
            k.block_index_map, 64, 4, 16, 8)) if b.out else b
        for b in spec.blocks])
    out = kc.check_write_once(bad)
    assert any("write-once coverage not provable" in v for v in out), out


def _paged_fixture(kc, int8=False):
    import jax
    import jax.numpy as jnp
    NB, BS, Hkv, dh, H, n = 64, 16, 4, 32, 8, 1
    dt = jnp.int8 if int8 else jnp.float32
    ops = {
        "q": jax.ShapeDtypeStruct((4, H, n, dh), jnp.float32),
        "k_pool": jax.ShapeDtypeStruct((NB, BS, Hkv, dh), dt),
        "v_pool": jax.ShapeDtypeStruct((NB, BS, Hkv, dh), dt),
    }
    if int8:
        ops["k_scale"] = jax.ShapeDtypeStruct((NB, BS, Hkv, 1),
                                              jnp.float32)
        ops["v_scale"] = jax.ShapeDtypeStruct((NB, BS, Hkv, 1),
                                              jnp.float32)
    return kc.paged_spec(ops, B=4, n=n, NB=NB, BS=BS, nbk=16,
                         workload="test")


def test_kernelcheck_paged_fixture_is_clean():
    kc = _kc()
    for int8 in (False, True):
        spec, quant = _paged_fixture(kc, int8=int8)
        assert quant == [], quant
        assert kc.verify_spec(spec) == []


def test_kernelcheck_dropped_scale_ref():
    kc = _kc()
    import jax
    import jax.numpy as jnp
    from repro.kernels.paged_attention import kernel as k
    NB, BS, Hkv, dh = 64, 16, 4, 32
    q = jax.ShapeDtypeStruct((4, 8, 1, dh), jnp.float32)
    kp = jax.ShapeDtypeStruct((NB, BS, Hkv, dh), jnp.int8)
    vp = jax.ShapeDtypeStruct((NB, BS, Hkv, dh), jnp.int8)
    ks = jax.ShapeDtypeStruct((NB, BS, Hkv, 1), jnp.float32)
    vs = jax.ShapeDtypeStruct((NB, BS, Hkv, 1), jnp.float32)
    specs, flags = k.build_specs(q, kp, v_pool=vp, k_scale=ks, v_scale=vs)
    # drop the k_scale entry but leave the flag claiming it exists
    broken = [s for s in specs if s[0] != "k_scale"]
    out = kc.check_paged_quant(broken, flags)
    assert any("NO k_scale" in v for v in out), out
    assert any("has_ks" in v for v in out), out   # flag mismatch too


def test_kernelcheck_scale_with_wrong_index_map():
    kc = _kc()
    import jax
    import jax.numpy as jnp
    from repro.kernels.paged_attention import kernel as k
    NB, BS, Hkv, dh = 64, 16, 4, 32
    q = jax.ShapeDtypeStruct((4, 8, 1, dh), jnp.float32)
    kp = jax.ShapeDtypeStruct((NB, BS, Hkv, dh), jnp.int8)
    vp = jax.ShapeDtypeStruct((NB, BS, Hkv, dh), jnp.float32)
    ks = jax.ShapeDtypeStruct((NB, BS, Hkv, 1), jnp.float32)
    specs, flags = k.build_specs(q, kp, v_pool=vp, k_scale=ks)
    # re-point the scale at the (stationary) q map: rows would
    # dequantize against a different physical block
    specs = [(n_, op, blk, (k.q_index_map,)) if n_ == "k_scale"
             else (n_, op, blk, maps) for n_, op, blk, maps in specs]
    out = kc.check_paged_quant(specs, flags)
    assert any("DIFFERENT physical block" in v for v in out), out


def test_kernelcheck_wqk_step_bytes_matches_contract_bound():
    """The contracts layer's VMEM_D_LIMIT derivation now rests on the
    kernel-spec byte model: fits at the limit, fails at 2x."""
    kc = _kc()
    from repro.kernels.wqk_score.ops import VMEM_D_LIMIT
    assert kc.wqk_step_bytes(VMEM_D_LIMIT) <= kc.VMEM_BUDGET
    assert kc.wqk_step_bytes(2 * VMEM_D_LIMIT) > kc.VMEM_BUDGET


def test_nondividing_pool_leaves_classification():
    from repro.sharding import specs as sspecs
    # Hkv=4 divides msz=4 -> no fallback; msz=8 -> head-axis fallback
    # for K/V rows AND their per-row scale columns (axis 4 == 1 cannot
    # absorb the shard). Per-token X scale rows (axis3 == 1) are
    # by-design replicated, never a fallback.
    kv = [(2, 64, 16, 4, 32), (2, 64, 16, 4, 1), (2, 64, 16, 1)]
    assert sspecs.nondividing_pool_leaves(kv, 4) == []
    bad = sspecs.nondividing_pool_leaves(kv, 8)
    assert bad == [(2, 64, 16, 4, 32), (2, 64, 16, 4, 1)]
    assert sspecs.nondividing_pool_leaves(kv, 1) == []


def test_analysis_cli_list_and_only():
    env = dict(__import__("os").environ)
    r = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "--list"],
        capture_output=True, text=True, timeout=120, env=env)
    assert r.returncode == 0
    for layer in ("lint", "contracts", "kernelcheck", "invariants"):
        assert layer in r.stdout
    r = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "--only", "lint"],
        capture_output=True, text=True, timeout=300, env=env)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "lint=PASS" in r.stdout
    assert "contracts" not in r.stdout.splitlines()[-1]


def test_nondividing_shard_warning_is_structured():
    from repro.serving.engine import NonDividingShardWarning
    w = NonDividingShardWarning(
        "fallback", model_size=8, shapes=((2, 64, 16, 4, 32),))
    assert isinstance(w, UserWarning)
    assert w.model_size == 8
    assert w.shapes == ((2, 64, 16, 4, 32),)
