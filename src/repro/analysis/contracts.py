"""Static Pallas/budget contract checks — the accounting-vs-layout and
grid-math claims, verified without running a kernel or building a mesh.

Three families (each returns a list of violation strings; empty = pass):

  * ``check_vmem_limits``  — the VMEM-residency regime: the duplicated
    ``VMEM_D_LIMIT`` constants (core/score_backend.py mirrors
    kernels/wqk_score/ops.py so the planner never imports Pallas) must
    be equal, and the limit itself must be *derivable* from the 16 MiB
    VMEM budget — one head's int8 W_QK tile plus streaming X tiles and
    the int32 output tile must fit at D = VMEM_D_LIMIT (and must NOT
    fit at 2·D, else the limit is needlessly conservative).
  * ``check_wqk_grid`` / ``check_paged_grid`` — BlockSpec/grid math
    re-derived from the kernel wrappers' own static shape arithmetic:
    block shapes divide (padded) operand shapes, the grid covers the
    logical iteration space exactly, scratch + resident blocks fit
    VMEM, and the paged kernel's null-block redirect target is the
    allocator's reserved ``NULL_BLOCK``.
  * ``check_budget_vs_layout`` — ``PagedCacheBudget`` accounting vs
    ``specs.paged_pool_spec`` for every (layout, quantization,
    mesh-extent) combination: the budget's per-component split decision
    must agree with the PartitionSpec rule on the real pool leaf shapes
    (obtained via ``jax.eval_shape`` on ``attention.init_kv_cache`` —
    no hardcoded shape formulas to drift), and the per-device
    bytes-per-block must match exactly for float pools / bound from
    above for int8 pools (the budget's dtype_bytes=2 planning default
    intentionally overestimates int8 rows; an underestimate would
    overcommit HBM and is a violation).

``budget_fn`` / ``spec_fn`` are injectable so tests can plant a
perturbed divisibility rule and prove the checker rejects it.

CLI: ``python -m repro.analysis.contracts``.
"""
from __future__ import annotations

from collections.abc import Sequence

# one budget constant across the static layers: the kernel verifier's
# per-grid-step byte model (repro.analysis.kernelcheck) and these
# contracts must agree on what "fits VMEM" means
from repro.analysis.kernelcheck import VMEM_BUDGET, wqk_step_bytes

_EXTENTS = (1, 2, 4, 8, 16)     # model-axis extents to sweep


# ------------------------------------------------------------ vmem limit

def check_vmem_limits() -> list[str]:
    from repro.core import score_backend as sb
    from repro.kernels.wqk_score import kernel as wqk_kernel
    from repro.kernels.wqk_score import ops as wqk_ops

    out = []
    if sb.VMEM_D_LIMIT != wqk_ops.VMEM_D_LIMIT:
        out.append(
            f"VMEM_D_LIMIT mirror drift: core/score_backend.py has "
            f"{sb.VMEM_D_LIMIT}, kernels/wqk_score/ops.py has "
            f"{wqk_ops.VMEM_D_LIMIT} — the planner's VMEM-residency "
            f"decision no longer matches the kernel's actual limit.")

    # the per-grid-step account comes from the kernel verifier's
    # double-buffer-aware model over the kernel's REAL BlockSpecs
    # (kernelcheck.spec_step_bytes), not a hand-maintained formula
    bn, bm = wqk_kernel.DEFAULT_BLOCK_N, wqk_kernel.DEFAULT_BLOCK_M

    def footprint(d: int) -> int:
        return wqk_step_bytes(d, block_n=bn, block_m=bm)

    d = wqk_ops.VMEM_D_LIMIT
    if footprint(d) > VMEM_BUDGET:
        out.append(
            f"VMEM_D_LIMIT={d} does not fit the {VMEM_BUDGET >> 20} MiB "
            f"budget: W_QK + tiles need {footprint(d)} bytes.")
    if footprint(2 * d) <= VMEM_BUDGET:
        out.append(
            f"VMEM_D_LIMIT={d} is needlessly conservative: "
            f"D={2 * d} would still fit ({footprint(2 * d)} bytes "
            f"<= {VMEM_BUDGET}).")
    for name in sb.list_backends():
        be = sb.get_backend(name)
        lim = be.max_d_aug
        if lim is not None and lim > wqk_ops.VMEM_D_LIMIT \
                and "pallas" in be.name:
            out.append(
                f"backend {be.name!r} advertises max_d_aug={lim} above "
                f"the kernel's VMEM_D_LIMIT={wqk_ops.VMEM_D_LIMIT}.")
    return out


# --------------------------------------------------------- wqk grid math

def check_wqk_grid(shapes: Sequence | None = None) -> list[str]:
    """Re-derive ops.scores' pad-then-tile arithmetic for representative
    (N, M, H, D) workloads: padded extents divide the block sizes, the
    grid covers exactly the padded score matrix, and one grid step's
    resident blocks fit VMEM."""
    from repro.kernels.wqk_score import kernel as wqk_kernel

    bn, bm = wqk_kernel.DEFAULT_BLOCK_N, wqk_kernel.DEFAULT_BLOCK_M
    shapes = shapes or ((1, 17, 8, 64), (128, 128, 8, 385),
                        (200, 333, 4, 1024), (4096, 4096, 2, 2048))
    out = []
    for N, M, H, D in shapes:
        Np, Mp = N + (-N) % bn, M + (-M) % bm     # ops._pad_to
        if Np % bn or Mp % bm:
            out.append(f"wqk pad math broken for N={N},M={M}: padded "
                       f"({Np},{Mp}) not block multiples ({bn},{bm}).")
        grid = (H, Np // bn, Mp // bm)
        if grid[1] * bn != Np or grid[2] * bm != Mp:
            out.append(f"wqk grid {grid} does not cover padded "
                       f"({Np},{Mp}) exactly.")
        if bn % 8 or bm % 8:
            out.append(f"wqk block sizes ({bn},{bm}) not sublane-"
                       f"aligned (8) for int8.")
        resident = wqk_step_bytes(D, block_n=bn, block_m=bm, heads=H)
        if resident > VMEM_BUDGET:
            out.append(f"wqk grid step for D={D} needs {resident} "
                       f"bytes VMEM > {VMEM_BUDGET}.")
    return out


# ------------------------------------------------------- paged grid math

def check_paged_grid(workloads: Sequence[dict] | None = None
                     ) -> list[str]:
    """BlockSpec divisibility + VMEM footprint for the paged-attention
    kernel at the blocks per step its own rule picks, counted by hand
    here (the kernel's ``step_vmem`` is what that rule reads)."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.paged_attention import kernel as pk
    from repro.serving import paged

    out = []
    if paged.NULL_BLOCK != 0:
        out.append(
            f"paged.NULL_BLOCK={paged.NULL_BLOCK} but the kernel's "
            f"index map redirects dead blocks to physical block 0 "
            f"(kernels/paged_attention/kernel.block_index_map) — the "
            f"redirect would fetch a LIVE block.")

    workloads = workloads or (
        # B, H, Hkv, n, E, dv, NB, BS, max_len, int8
        dict(B=8, H=8, Hkv=8, n=1, E=64, dv=64, NB=64, BS=16,
             max_len=512, int8=False),
        dict(B=4, H=8, Hkv=4, n=32, E=65, dv=64, NB=128, BS=16,
             max_len=1024, int8=True),
        dict(B=16, H=40, Hkv=8, n=1, E=128, dv=128, NB=512, BS=32,
             max_len=8192, int8=False),
        # qwen2.5-14b served: the decode tick and the 64-token chunk
        dict(B=32, H=40, Hkv=8, n=1, E=128, dv=128, NB=2048, BS=16,
             max_len=4096, int8=False),
        dict(B=1, H=40, Hkv=8, n=64, E=128, dv=128, NB=2048, BS=16,
             max_len=4096, int8=False),
    )
    for w in workloads:
        B, H, Hkv, n = w["B"], w["H"], w["Hkv"], w["n"]
        E, dv, NB, BS = w["E"], w["dv"], w["NB"], w["BS"]
        nbk = -(-w["max_len"] // BS)              # paged.blocks_for
        tag = f"paged[{w}]"
        if nbk * BS < w["max_len"]:
            out.append(f"{tag}: {nbk} blocks of {BS} don't cover "
                       f"max_len={w['max_len']}.")
        if H % Hkv:
            out.append(f"{tag}: H={H} not a multiple of Hkv={Hkv} — "
                       f"GQA head grouping breaks.")
        if nbk > NB:
            out.append(f"{tag}: logical blocks/seq nbk={nbk} exceeds "
                       f"physical pool NB={NB}; even one sequence "
                       f"cannot be resident.")
        # K, the blocks one grid step covers, is the kernel's own pick
        # for the pool the planner serves (bf16, or int8 with float32
        # row scales); the rest is this check's own arithmetic: block
        # shapes vs operand shapes (leading block-id dim indexes one
        # pool entry; trailing dims must match the pool exactly —
        # BlockSpec tiles of extent==dim always divide), and one grid
        # step's VMEM, unpadded, so a lower bound of what it holds
        pool_dt = jnp.int8 if w["int8"] else jnp.bfloat16
        kbytes = jnp.dtype(pool_dt).itemsize
        sds = jax.ShapeDtypeStruct
        sc = sds((NB, BS, Hkv, 1), jnp.float32) if w["int8"] else None
        K = pk.blocks_per_step(
            sds((B, H, n, E), jnp.float32),
            sds((NB, BS, Hkv, E), pool_dt), nbk,
            v_pool=sds((NB, BS, Hkv, dv), pool_dt), k_scale=sc,
            v_scale=sc)
        S = K * BS                                # tokens a step covers
        blocks = [("q", (1, H, n, E), (B, H, n, E), 4, 1),
                  ("k", (1, BS, Hkv, E), (NB, BS, Hkv, E), kbytes, K),
                  ("v", (1, BS, Hkv, dv), (NB, BS, Hkv, dv), kbytes, K),
                  ("o", (1, H, n, dv), (B, H, n, dv), 4, 1)]
        if w["int8"]:
            blocks += [(name, (1, BS, Hkv, 1), (NB, BS, Hkv, 1), 4, K)
                       for name in ("ks", "vs")]
        resident = 0
        for name, blk, full, nbytes, count in blocks:
            for bdim, fdim in zip(blk, full, strict=True):
                if fdim % bdim:
                    out.append(f"{tag}: {name} block dim {bdim} does "
                               f"not divide operand dim {fdim}.")
            sz = nbytes * count
            for bdim in blk:
                sz *= bdim
            resident += 2 * sz                    # double-buffered
        # running max and sum; the step's keys and values in float32,
        # its scores and probabilities
        body = (2 * H * n + S * Hkv * (E + dv) + 2 * H * n * S) * 4
        if resident + body > VMEM_BUDGET:
            out.append(f"{tag}: a grid step of {K} blocks holds "
                       f"{resident} bytes of blocks and {body} of "
                       f"values, over the VMEM budget {VMEM_BUDGET}.")
    return out


# --------------------------------------------------- budget vs pool spec

def _default_cfgs():
    """(label, cfg) pairs spanning the layout × quantization matrix:
    kv float, kv int8, x/xv via the wqk family. Reduced so eval_shape
    stays tiny; bfloat16 so the budget's dtype_bytes matches itemsize."""
    import dataclasses as dc

    from repro.configs.base import get_arch, reduced

    base = reduced(get_arch("qwen2.5-14b"), num_layers=2, num_heads=8,
                   num_kv_heads=4)
    out = [("kv-float", base)]
    out.append(("kv-int8", dc.replace(base, cache_quant="int8")))
    wqk = dc.replace(base, score_mode="wqk_int8", pos_emb="none")
    out.append(("x-family-float", wqk))
    out.append(("x-family-int8", dc.replace(wqk, cache_quant="int8")))
    return out


def check_budget_vs_layout(cfgs=None, extents: Sequence[int] = _EXTENTS,
                           budget_fn=None, spec_fn=None,
                           block_size: int = 16) -> list[str]:
    import jax
    import jax.numpy as jnp

    from repro.models import attention as attn
    from repro.serving import kvcache
    from repro.sharding import specs

    budget_fn = budget_fn or kvcache.paged_budget_for
    spec_fn = spec_fn or specs.paged_pool_spec
    cfgs = cfgs if cfgs is not None else _default_cfgs()
    out = []
    for label, cfg in cfgs:
        dt = jnp.dtype(cfg.dtype)
        bud = budget_fn(cfg, block_size=block_size,
                        dtype_bytes=dt.itemsize)
        is_int8 = getattr(cfg, "cache_quant", None) == "int8"
        # real single-layer pool leaf shapes, no allocation
        leaves = jax.eval_shape(
            lambda: attn.init_kv_cache(cfg, 1, block_size, dt))
        leaves = [leaf for leaf in leaves if leaf is not None]
        L = bud.layers
        for msz in extents:
            actual = 0
            for leaf in leaves:
                full = (L,) + leaf.shape            # pool stacks layers
                spec = tuple(spec_fn(full, msz))
                n = 1
                for i, d in enumerate(full):
                    if i < len(spec) and spec[i] == "model":
                        if d % msz:
                            out.append(
                                f"{label}@model={msz}: spec shards "
                                f"axis {i} of {full} but {d} % {msz} "
                                f"!= 0 — device_put would raise.")
                        d //= msz
                    n *= d
                actual += n * leaf.dtype.itemsize
            budgeted = bud.per_device_bytes_per_block(msz)
            if budgeted != actual:
                kind = ("UNDERestimates (max_blocks would overcommit "
                        "HBM)" if budgeted < actual else "overestimates")
                out.append(
                    f"{label}@model={msz}: budget says {budgeted} "
                    f"bytes/block/device but the pool layout gives "
                    f"{actual} — accounting {kind}; drifted from "
                    f"specs.paged_pool_spec "
                    f"(int8={is_int8}).")
            # structural agreement: each budget component's split
            # decision must match the spec rule on a synthetic leaf
            # carrying that component's candidate extents ("model" on a
            # 1-extent mesh axis is numerically no split)
            for row_bytes, exts in bud.components:
                b_split = msz > 1 and any(
                    e and e % msz == 0 for e in exts)
                synth = (L, 1, block_size) + tuple(exts)
                s_split = msz > 1 \
                    and "model" in tuple(spec_fn(synth, msz))
                if b_split != s_split:
                    out.append(
                        f"{label}@model={msz}: component "
                        f"{(row_bytes, exts)} split={b_split} in the "
                        f"budget but {s_split} under the pool spec "
                        f"rule — divisibility rules drifted.")
    return out


# --------------------------------------------------------------- driver

def run_all(verbose: bool = True) -> list[str]:
    checks = (("vmem-limits", check_vmem_limits),
              ("wqk-grid", check_wqk_grid),
              ("paged-grid", check_paged_grid),
              ("budget-vs-layout", check_budget_vs_layout))
    violations = []
    for name, fn in checks:
        got = fn()
        if verbose:
            print(f"[contracts] {name}: "
                  f"{'OK' if not got else f'{len(got)} violation(s)'}")
        violations.extend(got)
    return violations


def main() -> int:
    violations = run_all()
    for v in violations:
        print(f"VIOLATION: {v}")
    return 1 if violations else 0


if __name__ == "__main__":
    raise SystemExit(main())
