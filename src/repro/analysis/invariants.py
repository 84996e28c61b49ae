"""Jaxpr/HLO invariant checker: the engine's structural claims,
machine-verified from traces — no kernel ever executes.

The paper's efficiency story is structural (weight-stationary operands
never move; schedules are static), and the serving engine's claims are
the software analogues. Each is asserted here by tracing/lowering the
jitted entry points and counting ops via ``launch.hlo``'s collective
parser and ``launch.jaxpr_cost``'s sub-jaxpr walker:

  * **one-collective attention** — a single layer's paged decode
    attention (``attention_decode_paged`` + wo combine) compiled on a
    tensor-parallel mesh contains EXACTLY ONE all-reduce (the wo
    combine) and nothing else for kv layouts; X-cache layouts add only
    the by-design all-gathers that re-stream raw X rows (the paper's
    dataflow: raw inputs move, weights stay put).
  * **tick signature** — the full ``decode_paged`` tick's collective
    signature is pinned per layout, split into layer-loop-body ops
    (execute per layer) and outer ops (once per tick), via
    ``hlo.collective_counts`` + ``hlo.loop_body_names``. Growth here is
    a structural regression even when tests still pass.
  * **graph stability** — the tick lowers to byte-identical HLO across
    argument *values* (positions, tables, tokens), so ticks never
    silently recompile; and the decode tick (n=1) and prefill chunk
    (n=C) trace to the same primitive multiset — one shape-polymorphic
    graph family serves both, as the engine's two-entry cache assumes.
  * **no host ops in the tick** — no callback/infeed/outfeed
    primitives in the jaxpr, no host custom-calls in the HLO.
  * **pinned output shardings** — the engine-style jit (explicit
    ``out_shardings``) yields compiled output shardings equivalent to
    the declared ones: replicated logits, ``paged_pool_spec`` pool.

Meshed checks need forced host devices, which must be set BEFORE jax
initializes — run via ``python -m repro.analysis`` (spawns this module
in a subprocess with the right env, conftest-style) or directly::

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        PYTHONPATH=src python -m repro.analysis.invariants

Without >= 4 devices the meshed checks are skipped (reported), and the
unmeshed checks (graph stability, host ops) still run.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

MESH_SHAPE = (1, 4)              # (data, model) for the meshed checks

# jaxpr primitives that sync or round-trip through the host — never
# allowed inside a serving tick
HOST_PRIMITIVES = frozenset({
    "pure_callback", "io_callback", "debug_callback", "debug_print",
    "host_callback_call", "infeed", "outfeed",
})
# optimized-HLO markers of host round-trips (custom-call targets of the
# python callback machinery, infeed/outfeed ops)
HLO_HOST_MARKERS = ("xla_python_cpu_callback", "xla_ffi_python",
                    " infeed(", " outfeed(")

# Pinned collective signatures per (layout, quantization) combo,
# measured on the 1x4 (data, model) mesh over the reduced 2-layer
# config with jax 0.9, lowered under the installed mesh exactly as the
# engine traces its tick. A diff is a structural regression (or a
# deliberate dataflow change: update the table and DESIGN.md §11
# together).
#
# EXPECTED_LAYER: one attention layer (attention_decode_paged + wo).
# kv layouts realize the one-TP-collective claim literally: the paged
# kernel runs per device under shard_map on its head-slice of the pool,
# and the single all-reduce is the wo output combine. X-cache layouts
# shard pool rows over D, so the X-consuming contractions (score fold,
# per-row requant max, S·X^T value recompute, wo) combine partial sums
# — raw inputs move, folded weights stay put. The three all-to-alls sit
# at the [X 1] bias augmentation (concatenate): jax 0.9's partitioner
# keeps the D+1 = 129-wide rows sharded by padding them to 132 and
# re-slicing the 32-column shards into 33-column ones, where jax 0.4
# replicated the operand (one more all-reduce). This path is the
# GSPMD-partitioned jnp stream, which only CPU meshes run: a TPU mesh
# refuses X-stream plans (serving.engine.check_mesh_plan).
EXPECTED_LAYER = {
    "kv-float": {"all-reduce": 1},
    "kv-int8": {"all-reduce": 1},
    "x-int8": {"all-reduce": 3, "all-gather": 2, "all-to-all": 3},
}

# EXPECTED_TICK: the full decode_paged tick. "body" ops sit inside the
# layer scan (execute per layer); "outer" ops run once per tick (the
# unembed combine + logits replication; int8-x adds the embed-side
# quantization combines and the loop-entry reshards of the two [X 1]
# augmentations, hoisted out of the scan as all-to-alls).
EXPECTED_TICK = {
    "kv-float": {"body": {"all-reduce": 2},      # wo combine + mlp down
                 "outer": {"all-reduce": 1, "all-gather": 1}},
    "kv-int8": {"body": {"all-reduce": 2},
                "outer": {"all-reduce": 1, "all-gather": 1}},
    "x-int8": {"body": {"all-reduce": 6, "all-gather": 4,
                        "all-to-all": 4},
               "outer": {"all-reduce": 2, "all-gather": 2,
                         "all-to-all": 2}},
}


# ----------------------------------------------------------- fixtures

def _cfg(**over):
    from repro.configs.base import get_arch, reduced
    base = dict(num_layers=2, num_heads=8, num_kv_heads=8)
    base.update(over)
    extra = {k: base.pop(k) for k in list(base)
             if k in ("score_mode", "cache_quant", "pos_emb")}
    cfg = reduced(get_arch("qwen2.5-14b"), **base)
    return dataclasses.replace(cfg, dtype="float32", **extra)


_COMBOS = (
    ("kv-float", dict(score_mode="standard")),
    ("kv-int8", dict(score_mode="standard", cache_quant="int8")),
    ("x-int8", dict(score_mode="wqk_int8", cache_quant="int8",
                    pos_emb="none")),
)


def _tick_args(model, cfg, *, B=4, NB=16, BS=8, n=1, seed=0):
    """Concrete decode_paged arguments (values vary with ``seed`` so
    graph-stability lowers can differ only if values leak)."""
    r = np.random.default_rng(seed)
    nbk = NB // 2
    params = model.init(jax.random.PRNGKey(seed))
    cache = jax.eval_shape(
        lambda: model.init_paged_cache(NB, BS))
    cache = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), cache)
    tables = jnp.asarray(
        r.permutation(np.arange(1, B * nbk + 1)).reshape(B, nbk)
        % NB, jnp.int32)
    tokens = jnp.asarray(
        r.integers(0, cfg.vocab_size, (B, n)), jnp.int32)
    pos = jnp.asarray(r.integers(0, BS * 2, (B,)), jnp.int32)
    used = jnp.asarray(r.integers(1, nbk + 1, (B,)), jnp.int32)
    return params, cache, tables, tokens, pos, used


def _jaxpr_primitive_counts(closed) -> dict[str, int]:
    """Recursive primitive histogram of a ClosedJaxpr, reusing
    jaxpr_cost's sub-jaxpr discovery (scan/while/cond/pjit bodies)."""
    from repro.launch.jaxpr_cost import _sub_jaxprs
    counts: dict[str, int] = {}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            counts[eqn.primitive.name] = \
                counts.get(eqn.primitive.name, 0) + 1
            for sub, _m in _sub_jaxprs(eqn):
                walk(sub.jaxpr if hasattr(sub, "jaxpr") else sub)
    walk(closed.jaxpr)
    return counts


# ------------------------------------------------- unmeshed invariants

def check_graph_stability() -> list[str]:
    """The decode tick's lowering must be a pure function of argument
    SHAPES: lower at two different value-sets and require identical
    text (a diff means a Python value was baked into the graph — every
    tick would silently recompile). Also: the n=1 tick and the n=C
    prefill chunk must trace to the same primitive multiset — the
    single shape-polymorphic graph the engine's two-entry cache relies
    on."""
    from repro.models.model import build_model

    out = []
    cfg = _cfg()
    model = build_model(cfg)
    jitted = jax.jit(model.decode_paged)

    def canon(text: str) -> str:
        return "\n".join(ln for ln in text.splitlines()
                         if not ln.lstrip().startswith(("module @",
                                                        "#loc")))

    lowers = {}
    for n in (1, 8):
        texts = []
        for seed in (0, 1):
            args = _tick_args(model, cfg, n=n, seed=seed)
            texts.append(canon(jitted.lower(*args).as_text()))
        if texts[0] != texts[1]:
            out.append(
                f"decode_paged(n={n}) lowers differently for "
                f"different argument VALUES at identical shapes — a "
                f"Python value leaked into the graph; every tick "
                f"recompiles.")
        lowers[n] = jax.make_jaxpr(model.decode_paged)(
            *_tick_args(model, cfg, n=n, seed=0))
    tick_prims = _jaxpr_primitive_counts(lowers[1])
    chunk_prims = _jaxpr_primitive_counts(lowers[8])
    if tick_prims != chunk_prims:
        diff = {k: (tick_prims.get(k, 0), chunk_prims.get(k, 0))
                for k in set(tick_prims) | set(chunk_prims)
                if tick_prims.get(k, 0) != chunk_prims.get(k, 0)}
        out.append(
            f"decode tick (n=1) and prefill chunk (n=8) trace to "
            f"different primitive multisets {diff} — not one "
            f"shape-polymorphic graph; the engine's chunked-prefill/"
            f"decode unification is broken.")
    return out


def check_no_host_ops() -> list[str]:
    """No callback / infeed / outfeed primitives inside the tick
    jaxpr, and no host custom-calls in the compiled HLO."""
    from repro.models.model import build_model

    out = []
    cfg = _cfg()
    model = build_model(cfg)
    args = _tick_args(model, cfg)
    closed = jax.make_jaxpr(model.decode_paged)(*args)
    prims = _jaxpr_primitive_counts(closed)
    for bad in sorted(HOST_PRIMITIVES & set(prims)):
        out.append(
            f"host primitive {bad!r} inside the decode tick jaxpr "
            f"(x{prims[bad]}) — a device->host round-trip per tick.")
    hlo = jax.jit(model.decode_paged).lower(*args).compile().as_text()
    for marker in HLO_HOST_MARKERS:
        if marker in hlo:
            out.append(
                f"compiled tick HLO contains host marker {marker!r}.")
    return out


# --------------------------------------------------- meshed invariants

def _mesh():
    from repro.launch.mesh import make_mesh
    return make_mesh(MESH_SHAPE, ("data", "model"))


def _layer_pool_sharding(mesh, pool_sds):
    """Per-layer KVCache leaf shardings: the pool rule applied to the
    stacked (L, ...) shape, minus the layer axis."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.sharding import specs

    msz = mesh.shape["model"]

    def one(leaf):
        full = (1,) + tuple(leaf.shape)
        spec = list(specs.paged_pool_spec(full, msz))
        spec += [None] * (len(full) - len(spec))
        return NamedSharding(mesh, P(*spec[1:]))
    return jax.tree_util.tree_map(one, pool_sds)


def check_attention_one_collective(mesh=None) -> list[str]:
    """THE paper-level claim: one layer's paged decode attention on a
    TP mesh performs exactly one all-reduce (the wo output combine).
    kv layouts: nothing else. X-cache layouts: plus only the by-design
    all-gathers that re-stream raw X (pinned count, no other kinds)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.launch import hlo as hlo_lib
    from repro.models import attention as attn
    from repro.models.model import build_model
    from repro.sharding import act, specs

    mesh = mesh or _mesh()
    out = []
    rep = NamedSharding(mesh, P())
    for label, over in _COMBOS:
        cfg = _cfg(**over)
        model = build_model(cfg)
        params_sds = jax.eval_shape(
            lambda m=model: m.init(jax.random.PRNGKey(0)))
        attn_sds = jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape[1:], s.dtype),
            params_sds["layers"]["attn"])
        attn_sh = jax.tree_util.tree_map_with_path(
            lambda p, s: NamedSharding(
                mesh, specs.spec_for(
                    "attn/" + specs._path_str(p), s.shape, mesh)),
            attn_sds)
        B, NB, BS, n = 4, 16, 8, 1
        pool_sds = jax.eval_shape(
            lambda c=cfg: attn.init_kv_cache(c, NB, BS,
                                             jnp.dtype(c.dtype)))
        pool_sh = _layer_pool_sharding(mesh, pool_sds)
        nbk = NB // 2
        h = jax.ShapeDtypeStruct((B, n, cfg.d_model),
                                 jnp.dtype(cfg.dtype))
        tables = jax.ShapeDtypeStruct((B, nbk), jnp.int32)
        pos = jax.ShapeDtypeStruct((B,), jnp.int32)
        used = jax.ShapeDtypeStruct((B,), jnp.int32)

        def layer(pa, hx, pool, tb, ps, bu, c=cfg):
            return attn.attention_decode_paged(
                pa, hx, pool, tb, ps, c, blocks_used=bu)

        jitted = jax.jit(layer, in_shardings=(
            attn_sh, rep, pool_sh, rep, rep, rep))
        try:
            with act.use_mesh(mesh):
                text = jitted.lower(attn_sds, h, pool_sds, tables, pos,
                                    used).compile().as_text()
        except Exception as e:          # pragma: no cover - diagnostics
            out.append(f"attention[{label}]: meshed compile failed: "
                       f"{type(e).__name__}: {e}")
            continue
        total: dict[str, int] = {}
        for counts in hlo_lib.collective_counts(text).values():
            for k, v in counts.items():
                total[k] = total.get(k, 0) + v
        expected = EXPECTED_LAYER[label]
        if total != expected:
            claim = ("the one-TP-collective (wo combine) claim is "
                     "broken" if attn.cache_mode_for(cfg) == "kv" else
                     "the pinned X-streaming signature drifted")
            out.append(
                f"attention[{label}]: single-layer collectives "
                f"{total} != pinned {expected} — {claim}.")
    return out


def check_tick_signature(mesh=None) -> list[str]:
    """Pin the full decode tick's collective signature per layout,
    split into layer-loop-body vs outer ops, and verify the
    engine-style pinned output shardings on the same compile."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.launch import hlo as hlo_lib
    from repro.models.model import build_model
    from repro.sharding import act, specs

    mesh = mesh or _mesh()
    out = []
    rep = NamedSharding(mesh, P())
    for label, over in _COMBOS:
        cfg = _cfg(**over)
        model = build_model(cfg)
        mode = _cache_mode(cfg)
        expected = EXPECTED_TICK[label]
        params, cache, tables, tokens, pos, used = _tick_args(
            model, cfg)
        params_sh = specs.param_shardings(params, mesh)
        pool_sh = specs.paged_pool_shardings(cache, mesh)
        jitted = jax.jit(
            model.decode_paged,
            in_shardings=(params_sh, pool_sh, rep, rep, rep, rep),
            out_shardings=(rep, pool_sh))
        sds = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
            (params, cache, tables, tokens, pos, used))
        try:
            with act.use_mesh(mesh):
                compiled = jitted.lower(*sds).compile()
        except Exception as e:          # pragma: no cover - diagnostics
            out.append(f"tick[{label}]: meshed compile failed: "
                       f"{type(e).__name__}: {e}")
            continue
        text = compiled.as_text()
        comps = hlo_lib.collective_counts(text)
        bodies = hlo_lib.loop_body_names(text)
        got = {"body": {}, "outer": {}}
        for cname, counts in comps.items():
            where = "body" if cname in bodies else "outer"
            for k, v in counts.items():
                got[where][k] = got[where].get(k, 0) + v
        for where in ("body", "outer"):
            if got[where] != expected[where]:
                out.append(
                    f"tick[{label}] ({mode} layout): {where} "
                    f"collectives {got[where]} != pinned "
                    f"{expected[where]} — structural regression (or "
                    f"update EXPECTED_TICK + DESIGN.md §11 together).")
        out.extend(_check_output_shardings(compiled, pool_sh, label))
    return out


def _cache_mode(cfg) -> str:
    from repro.models.attention import cache_mode_for
    return cache_mode_for(cfg)


def _check_output_shardings(compiled, pool_sh, label) -> list[str]:
    """Engine parity: compiled outputs must carry exactly the declared
    shardings — replicated logits, pool-spec'd cache."""
    out = []
    try:
        logits_sh, cache_sh = compiled.output_shardings
    except Exception as e:              # pragma: no cover - diagnostics
        return [f"tick[{label}]: output_shardings unavailable: {e}"]
    if not _is_replicated(logits_sh):
        out.append(f"tick[{label}]: logits sharding {logits_sh} is "
                   f"not replicated — the host-side sampler would "
                   f"gather per token.")
    declared = jax.tree_util.tree_leaves(
        pool_sh, is_leaf=lambda x: hasattr(x, "spec"))
    got = jax.tree_util.tree_leaves(
        cache_sh, is_leaf=lambda x: hasattr(x, "spec"))
    if len(declared) != len(got):
        return out + [f"tick[{label}]: cache sharding tree mismatch."]
    for d, g in zip(declared, got, strict=True):
        if hasattr(g, "spec") and g.spec != d.spec:
            out.append(
                f"tick[{label}]: cache output sharding {g.spec} != "
                f"declared {d.spec} — the pool silently re-lays-out "
                f"every tick.")
    return out


def _is_replicated(sh) -> bool:
    spec = getattr(sh, "spec", None)
    if spec is None:
        return getattr(sh, "is_fully_replicated", False)
    return all(ax is None for ax in spec)


# --------------------------------------------------------------- driver

def run_all(verbose: bool = True) -> list[str]:
    checks = [("graph-stability", check_graph_stability),
              ("no-host-ops", check_no_host_ops)]
    n_dev = len(jax.devices())
    meshed = n_dev >= MESH_SHAPE[0] * MESH_SHAPE[1]
    if meshed:
        checks += [("attention-one-collective",
                    check_attention_one_collective),
                   ("tick-signature", check_tick_signature)]
    violations = []
    for name, fn in checks:
        got = fn()
        if verbose:
            print(f"[invariants] {name}: "
                  f"{'OK' if not got else f'{len(got)} violation(s)'}")
        violations.extend(got)
    if not meshed and verbose:
        print(f"[invariants] meshed checks SKIPPED ({n_dev} device(s); "
              f"need {MESH_SHAPE[0] * MESH_SHAPE[1]} — run via "
              f"python -m repro.analysis).")
    return violations


def main() -> int:
    violations = run_all()
    for v in violations:
        print(f"VIOLATION: {v}")
    return 1 if violations else 0


if __name__ == "__main__":
    raise SystemExit(main())
