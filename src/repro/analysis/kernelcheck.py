"""kernelcheck — symbolic verifier for the Pallas kernels (layer 4).

Evaluates each kernel's BlockSpec index maps over the abstract domain in
``repro.analysis.absdomain`` and proves, per kernel and per
planner-reachable (config, layout, quantization, mesh-extent) workload:

  1. **in-bounds access** — every index map's block coordinates land
     inside the operand's block grid for ALL grid points; the
     ``paged_attention`` maps read its scalar-prefetch step arrays and
     block table, modeled symbolically: the step's sequence in
     ``[0, B)``, the logical block of each of its K sub-blocks inside
     the table as the wrapper pads it, and the table gather (live
     entries in ``[0, num_blocks)``, the ``j >= blocks_used[b]`` →
     null-block-0 redirect recognized explicitly, everything else
     degrading to full int32 and failing).
  2. **write-once coverage** — output BlockSpecs tile the output exactly
     once: no overlapping/revisiting writes across separated grid steps,
     no unwritten holes. Affine maps are decided in closed form (each
     output coordinate a distinct grid axis with unit coefficient, and
     every ignored grid axis iterating INSIDE the varying ones, so
     revisits are consecutive — TPU grids are sequential, last axis
     fastest); a map through a sequence-major step -> row array
     (``absdomain.RunTable``) covering the output's rows writes each
     once, on consecutive steps; anything else falls back to bounded
     enumeration with witness grid points.
  3. **VMEM pipeline fit** — a double-buffer-aware working-set model:
     2x bytes for every block whose index map moves across the grid
     (Pallas prefetches the next block while computing on the current),
     1x for stationary blocks, plus scratch accumulators; the per-grid-
     step total must fit the 16 MiB VMEM budget. ``wqk_step_bytes``
     exports the wqk account to ``contracts.check_vmem_limits``, which
     previously derived it from a hand-maintained formula.
  4. **dtype/quantization contracts** — int8 pool operands are always
     paired with their f32 scale refs, threaded in the exact positional
     order the kernel unpacks (``paged_attention.build_specs`` is the
     single source for both the wrapper and this proof).

Planner-reachable workloads are enumerated through the real
``score_backend.plan`` and ``jax.eval_shape`` on
``attention.init_kv_cache`` — no hand-copied shape formulas — across
backends x cache quantization x serving/long-context sequence regimes x
model-axis extents, with per-device shapes derived from
``specs.paged_pool_spec``. Combinations whose head axis does not divide
the mesh are classified **fallback-correct** (see
``specs.nondividing_pool_leaves`` and the engine's
``NonDividingShardWarning``) rather than silently clean.

Registering a new kernel = one ``KernelSpec`` builder naming the
kernel's importable index maps (DESIGN.md §12). Module import is
jax-free; jax is only touched inside the planner sweep.

CLI: ``python -m repro.analysis.kernelcheck`` (or
``python -m repro.analysis --only kernelcheck``).
"""
from __future__ import annotations

import dataclasses
from collections.abc import Callable, Sequence

from repro.analysis import absdomain
from repro.analysis.absdomain import NotAffine

VMEM_BUDGET = 16 * 2**20           # bytes of VMEM per TensorCore
ENUM_LIMIT = 1 << 20               # write-once enumeration fallback cap

# serving-shaped paged workload used for the planner sweep (mirrors the
# tier-1 serving tests: small pool, real block math)
PAGED_B, PAGED_N = 4, 1
PAGED_BS, PAGED_NB, PAGED_MAX_LEN = 16, 64, 512
_EXTENTS = (1, 2, 4, 8)


# ---------------------------------------------------------------- specs

@dataclasses.dataclass
class Block:
    """One operand of a kernel: full shape, block shape, and the
    importable index map. ``abstract_eval``, when set, replaces affine
    probing: called with the grid extents, it must return the abstract
    block coordinates (used for the scalar-prefetch gather)."""
    name: str
    shape: tuple
    block: tuple
    index_map: Callable
    dtype_bytes: int
    out: bool = False
    abstract_eval: Callable | None = None


@dataclasses.dataclass
class KernelSpec:
    """Everything the verifier needs about one kernel workload."""
    kernel: str
    grid: tuple
    blocks: list
    scratch_bytes: int = 0
    workload: str = ""

    @property
    def tag(self) -> str:
        w = f" {self.workload}" if self.workload else ""
        return f"{self.kernel}[grid={self.grid}{w}]"

    def signature(self):
        return (self.kernel, self.grid, self.scratch_bytes,
                tuple((b.name, b.shape, b.block, b.dtype_bytes, b.out,
                       b.abstract_eval is None) for b in self.blocks))


def _prod(xs) -> int:
    n = 1
    for x in xs:
        n *= x
    return n


def _block_grid(blk: Block) -> list | None:
    """Blocks-per-dim, or None if some block dim doesn't divide."""
    nb = []
    for full, bdim in zip(blk.shape, blk.block, strict=True):
        if bdim <= 0 or full % bdim:
            return None
        nb.append(full // bdim)
    return nb


def _affine_forms(blk: Block, grid: tuple):
    """Affine forms of the block's index map (or raise NotAffine)."""
    forms = absdomain.affine_coords(blk.index_map, grid)
    if len(forms) != len(blk.block):
        raise NotAffine(
            f"index map returns {len(forms)} coordinates for a rank-"
            f"{len(blk.block)} block")
    return forms


# --------------------------------------------------- proof 1: in-bounds

def _bounds_witness(form, grid, too_high: bool):
    """Grid point extremizing an affine coordinate (the counterexample)."""
    if too_high:
        return tuple(e - 1 if c > 0 else 0
                     for c, e in zip(form.coeffs, grid, strict=True))
    return tuple(e - 1 if c < 0 else 0
                 for c, e in zip(form.coeffs, grid, strict=True))


def check_in_bounds(spec: KernelSpec) -> list[str]:
    out = []
    for blk in spec.blocks:
        nb = _block_grid(blk)
        if nb is None:
            out.append(
                f"{spec.tag} {blk.name}: block shape {blk.block} does "
                f"not divide operand shape {blk.shape}.")
            continue
        if blk.abstract_eval is not None:
            coords = blk.abstract_eval(spec.grid)
            for d, c in enumerate(coords):
                iv = absdomain.as_interval(c)
                if not iv.within(0, nb[d] - 1):
                    out.append(
                        f"{spec.tag} {blk.name}: abstract block index "
                        f"{iv} for dim {d} escapes the valid range "
                        f"[0, {nb[d] - 1}] — the gather can fetch "
                        f"outside the pool (is the table access guarded "
                        f"by its own liveness predicate, and inside the "
                        f"table's width?).")
            continue
        try:
            forms = _affine_forms(blk, spec.grid)
        except NotAffine as e:
            out.append(
                f"{spec.tag} {blk.name}: {e} — in-bounds not provable "
                f"(index maps must be affine in the grid; RA107).")
            continue
        for d, form in enumerate(forms):
            iv = form.interval(spec.grid)
            if not iv.within(0, nb[d] - 1):
                hi = iv.hi > nb[d] - 1
                wit = _bounds_witness(form, spec.grid, hi)
                out.append(
                    f"{spec.tag} {blk.name}: block index for dim {d} "
                    f"ranges over {iv} but only [0, {nb[d] - 1}] is "
                    f"in-bounds — e.g. at grid point {wit} the map "
                    f"selects block {form.at(wit)}.")
    return out


# ------------------------------------------------ proof 2: write-once

def _write_once_affine(spec, blk, forms, nb) -> list | None:
    """Closed-form write-once proof for canonical affine out maps.
    Returns violations, or None if the map is non-canonical (caller
    falls back to enumeration)."""
    used_axes = set()
    varying = set()
    for d, form in enumerate(forms):
        nz = [(a, c) for a, c in enumerate(form.coeffs)
              if c != 0 and spec.grid[a] > 1]
        if not nz:
            # constant coordinate: must cover the single block there is
            if nb[d] != 1:
                return [
                    f"{spec.tag} {blk.name}: output dim {d} is pinned "
                    f"to block {form.const} but has {nb[d]} blocks — "
                    f"blocks 0..{nb[d] - 1} except {form.const} are "
                    f"never written (holes)."]
            continue
        if (len(nz) == 1 and nz[0][1] == 1 and form.const == 0
                and spec.grid[nz[0][0]] == nb[d]
                and nz[0][0] not in used_axes):
            used_axes.add(nz[0][0])
            varying.add(nz[0][0])
            continue
        return None                     # non-canonical: enumerate
    ignored = {a for a, e in enumerate(spec.grid)
               if e > 1 and a not in varying}
    if varying and ignored and max(varying) > min(ignored):
        a = min(ignored)
        first = tuple(0 for _ in spec.grid)
        again = tuple(1 if i == a else 0 for i in range(len(spec.grid)))
        return [
            f"{spec.tag} {blk.name}: output block is revisited non-"
            f"contiguously — grid axis {a} (extent {spec.grid[a]}) "
            f"iterates OUTSIDE the axes selecting the output block "
            f"{sorted(varying)}, so the same tile is written on "
            f"separated grid steps (e.g. {first} and {again}): "
            f"write-twice race on the HBM copy."]
    return []


def _write_once_enumerate(spec, blk, nb) -> list:
    total = _prod(spec.grid)
    if total > ENUM_LIMIT:
        return [
            f"{spec.tag} {blk.name}: output index map is not in "
            f"canonical affine form and the grid has {total} points "
            f"(> {ENUM_LIMIT}) — write-once coverage not provable."]
    last_step: dict = {}
    out = []
    for step, pt in enumerate(absdomain.iter_grid(spec.grid)):
        coord = blk.index_map(*pt)
        if not isinstance(coord, tuple):
            coord = (coord,)
        prev = last_step.get(coord)
        if prev is not None and prev != step - 1 and not out:
            out.append(
                f"{spec.tag} {blk.name}: output block {coord} written "
                f"at grid step {prev} is written AGAIN at step {step} "
                f"(grid point {pt}) after the pipeline flushed it — "
                f"write-twice.")
        last_step[coord] = step
    want = _prod(nb)
    if len(last_step) < want:
        missing = next(c for c in absdomain.iter_grid(tuple(nb))
                       if c not in last_step)
        out.append(
            f"{spec.tag} {blk.name}: only {len(last_step)} of {want} "
            f"output blocks are ever written — e.g. block {missing} is "
            f"a hole.")
    return out


def _write_once_runs(spec, blk, nb) -> list:
    """Write-once for an output map that reads scalar-prefetch arrays:
    provable when one coordinate is a ``RunTable`` read at the grid
    step whose rows are exactly that dim's blocks, and every other
    coordinate pins a dim of one block."""
    coords = blk.abstract_eval(spec.grid)
    runs = [d for d, c in enumerate(coords)
            if isinstance(c, absdomain.ScalarLoad)
            and isinstance(c.table, absdomain.RunTable)]
    if len(runs) != 1:
        return [
            f"{spec.tag} {blk.name}: output index map reads no sequence-"
            f"major step -> row array (or more than one) — write-once "
            f"coverage not provable."]
    out = []
    for d, c in enumerate(coords):
        if d == runs[0]:
            rows = (c.table.lo, c.table.hi)
            if rows != (0, nb[d] - 1):
                out.append(
                    f"{spec.tag} {blk.name}: step -> row array "
                    f"{c.table.name} covers rows {list(rows)} but output "
                    f"dim {d} has blocks [0, {nb[d] - 1}] — holes or "
                    f"writes out of range.")
        elif not (isinstance(c, int) and nb[d] == 1 and c == 0):
            out.append(
                f"{spec.tag} {blk.name}: output dim {d} coordinate {c!r} "
                f"over {nb[d]} blocks is not pinned to its only block — "
                f"write-once coverage not provable.")
    return out


def check_write_once(spec: KernelSpec) -> list[str]:
    out = []
    for blk in spec.blocks:
        if not blk.out:
            continue
        nb = _block_grid(blk)
        if nb is None:
            continue                    # reported by check_in_bounds
        if blk.abstract_eval is not None:
            out.extend(_write_once_runs(spec, blk, nb))
            continue
        try:
            forms = _affine_forms(blk, spec.grid)
        except NotAffine:
            out.extend(_write_once_enumerate(spec, blk, nb))
            continue
        got = _write_once_affine(spec, blk, forms, nb)
        if got is None:
            got = _write_once_enumerate(spec, blk, nb)
        out.extend(got)
    return out


# ------------------------------------------------- proof 3: VMEM fit

def _block_moves(blk: Block, grid: tuple) -> bool:
    """Does the block's index change over the grid sweep? Moving blocks
    are double-buffered by the Pallas pipeline (fetch next while
    computing current); stationary ones are fetched once."""
    if blk.abstract_eval is not None:
        return True
    try:
        forms = _affine_forms(blk, grid)
    except NotAffine:
        return True
    return any(c != 0 and grid[a] > 1
               for form in forms for a, c in enumerate(form.coeffs))


def spec_step_bytes(spec: KernelSpec) -> tuple[int, list[str]]:
    """Per-grid-step VMEM working set: (total bytes, account lines)."""
    total = 0
    lines = []
    for blk in spec.blocks:
        one = _prod(blk.block) * blk.dtype_bytes
        bufs = 2 if _block_moves(blk, spec.grid) else 1
        total += bufs * one
        lines.append(f"{blk.name}: {bufs}x{one}")
    if spec.scratch_bytes:
        total += spec.scratch_bytes
        lines.append(f"scratch: {spec.scratch_bytes}")
    return total, lines


def check_vmem(spec: KernelSpec) -> list[str]:
    for blk in spec.blocks:
        if _block_grid(blk) is None:
            return []                   # reported by check_in_bounds
    total, lines = spec_step_bytes(spec)
    if total > VMEM_BUDGET:
        return [
            f"{spec.tag}: per-grid-step working set {total} bytes "
            f"exceeds the {VMEM_BUDGET >> 20} MiB VMEM budget "
            f"({', '.join(lines)})."]
    return []


def wqk_step_bytes(d: int, block_n: int = 128, block_m: int = 128,
                   heads: int = 2) -> int:
    """The wqk kernel's per-grid-step byte account, derived from its
    real BlockSpecs (plus the in-kernel int32 X·W intermediate, which
    lives in VMEM values, not a pipeline buffer). Consumed by
    ``contracts.check_vmem_limits`` — the VMEM_D_LIMIT derivability
    claim now rests on the same model as the kernel proofs."""
    spec = wqk_spec(heads, 2 * block_n, 2 * block_m, d,
                    block_n=block_n, block_m=block_m)
    total, _ = spec_step_bytes(spec)
    return total + block_n * d * 4


# ------------------------------------------ proof 4: quant contracts

_PAGED_ORDER = ("q", "k_pool", "k_scale", "v_pool", "v_scale", "wv", "bv")
_SCALE_OF = {"k_pool": "k_scale", "v_pool": "v_scale"}
_FLAG_OF = {"k_scale": "has_ks", "v_pool": "has_v", "v_scale": "has_vs",
            "wv": "has_wv", "bv": "has_bv"}


def check_paged_quant(specs: Sequence, flags: dict,
                      workload: str = "") -> list[str]:
    """int8-operand/scale pairing + positional ref-threading proof over
    the output of ``paged_attention.kernel.build_specs``. ``specs`` is
    the ``(name, operand, block_shape, index_maps)`` list in kernel
    unpack order; ``flags`` the has_* kwargs handed to the kernel."""
    tag = f"paged_attention[{workload}]" if workload else "paged_attention"
    out = []
    names = [s[0] for s in specs]
    order = [n for n in _PAGED_ORDER if n in names]
    if names != order:
        out.append(
            f"{tag}: operand order {names} does not match the kernel's "
            f"positional unpack order {order} — the has_* ref threading "
            f"would hand a ref to the wrong consumer.")
    by_name = {s[0]: s for s in specs}
    for pool_name, scale_name in _SCALE_OF.items():
        if pool_name not in by_name:
            continue
        _, op, _, maps = by_name[pool_name]
        if str(op.dtype) != "int8":
            continue
        if scale_name not in by_name:
            out.append(
                f"{tag}: int8 {pool_name} has NO {scale_name} ref — "
                f"the kernel would accumulate raw quantized codes "
                f"without dequantization.")
            continue
        _, sop, sblock, smaps = by_name[scale_name]
        if len(smaps) != len(maps) or any(
                a is not b for a, b in zip(smaps, maps)):
            out.append(
                f"{tag}: {scale_name} uses a different index map than "
                f"its int8 {pool_name} — scales would dequantize rows "
                f"of a DIFFERENT physical block.")
        if str(sop.dtype) != "float32":
            out.append(f"{tag}: {scale_name} dtype {sop.dtype} != "
                       f"float32.")
        if sblock[-1] != 1:
            out.append(f"{tag}: {scale_name} block {sblock} is not a "
                       f"per-row scale column (trailing dim 1).")
    for name, flag in _FLAG_OF.items():
        want = name in by_name
        if bool(flags.get(flag)) != want:
            out.append(
                f"{tag}: flag {flag}={flags.get(flag)} but operand "
                f"{name} is {'present' if want else 'absent'} — the "
                f"kernel would mis-count its positional refs.")
    return out


# --------------------------------------------------------- verify_spec

def verify_spec(spec: KernelSpec) -> list[str]:
    """All structural proofs (1-3) for one kernel workload."""
    out = check_in_bounds(spec)
    out.extend(check_write_once(spec))
    out.extend(check_vmem(spec))
    return out


# -------------------------------------------------- per-kernel builders

def wqk_spec(H, N, M, D, block_n: int = 128,
             block_m: int = 128) -> KernelSpec:
    from repro.kernels.wqk_score import kernel as k
    return KernelSpec(
        kernel="wqk_score",
        grid=(H, N // block_n, M // block_m),
        blocks=[
            Block("x_q", (N, D), (block_n, D), k.x_index_map, 1),
            Block("x_kv", (M, D), (block_m, D), k.y_index_map, 1),
            Block("wqk", (H, D, D), (1, D, D), k.w_index_map, 1),
            Block("scores", (H, N, M), (1, block_n, block_m),
                  k.out_index_map, 4, out=True),
        ],
        workload=f"H={H} N={N} M={M} D={D}")


def flash_spec(H, Hk, N, M, E, dv, block_n: int = 128,
               block_m: int = 128, dtype_bytes: int = 2) -> KernelSpec:
    from repro.kernels.flash_scores import kernel as k
    kidx = k.k_index_map_shared if Hk == 1 else k.k_index_map
    return KernelSpec(
        kernel="flash_scores",
        grid=(H, N // block_n, M // block_m),
        blocks=[
            Block("q", (H, N, E), (1, block_n, E), k.q_index_map,
                  dtype_bytes),
            Block("k", (Hk, M, E), (1, block_m, E), kidx, dtype_bytes),
            Block("v", (Hk, M, dv), (1, block_m, dv), kidx, dtype_bytes),
            Block("out", (H, N, dv), (1, block_n, dv), k.out_index_map,
                  dtype_bytes, out=True),
            Block("lse", (H, N), (1, block_n), k.lse_index_map, 4,
                  out=True),
        ],
        scratch_bytes=(block_n * dv + 2 * block_n) * 4,
        workload=f"H={H} Hk={Hk} N={N} M={M} E={E} dv={dv}")


def bitplane_spec(N, M, D, block_n: int = 64,
                  block_m: int = 64) -> KernelSpec:
    from repro.kernels.bitplane_mac import kernel as k
    return KernelSpec(
        kernel="bitplane_mac",
        grid=(N // block_n, M // block_m),
        blocks=[
            Block("xa", (N, D), (block_n, D), k.xa_index_map, 1),
            Block("xb", (M, D), (block_m, D), k.xb_index_map, 1),
            Block("w", (D, D), (D, D), k.w_index_map, 1),
            Block("scores", (N, M), (block_n, block_m), k.out_index_map,
                  4, out=True),
        ],
        workload=f"N={N} M={M} D={D}")


def _step_eval(index_map, num_blocks: int, B: int, nbk: int, K: int = 1):
    """abstract_eval for a paged map over the flattened grid: the grid
    step and symbolic scalar-prefetch refs through the kernel's OWN
    index map, with the abstract ``where`` injected in place of
    ``jnp.where`` where the map takes one. ``seq_of_step`` is a
    ``RunTable`` and ``start`` its ``RunStart`` (``step_arrays``' order:
    each sequence one contiguous run of at most ceil(nbk/K) steps); the
    block table is as wide as the wrapper pads it, a whole number of
    K-block steps."""
    import inspect

    width = -(-nbk // K)
    params = inspect.signature(index_map).parameters

    def ev(grid):
        (steps,) = grid
        seq = absdomain.RunTable("seq_of_step", 0, B - 1)
        # the wrapper clips blocks_used to [1, nbk]
        used = absdomain.ScalarTable("blocks_used", 1, nbk)
        kw = {"_where": absdomain.where} if "_where" in params else {}
        return index_map(
            absdomain.Sym("i", 0, steps - 1), seq,
            absdomain.RunStart("start", seq, width, 0, steps - 1), used,
            absdomain.GatherTable("tables", num_blocks, used, width * K),
            absdomain.ScalarTable("qpos", 0, absdomain.INT32_MAX),
            absdomain.ScalarTable("win", 0, absdomain.INT32_MAX), **kw)
    return ev


def _stepped(index_map) -> bool:
    """Does the map read the step arrays (a sub-block map is a partial
    of ``block_index_map``)? W_V / b_V maps stay affine."""
    from repro.kernels.paged_attention import kernel as k
    fn = getattr(index_map, "func", index_map)
    return fn in (k.block_index_map, k.q_index_map, k.out_index_map)


def paged_spec(operands: dict, *, B: int, n: int, NB: int, BS: int,
               nbk: int, workload: str = "") -> tuple[KernelSpec, list]:
    """KernelSpec for a paged-attention workload from ShapeDtypeStruct
    operands (same keys as ``build_specs`` kwargs, ``q`` as
    ``paged_attend`` takes it), plus the quant-contract violations for
    the same workload. The kernel's own rule picks K, the blocks a
    grid step covers: each pool operand is K blocks, sub-block t named
    ``<operand>[t]``."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.paged_attention import kernel as k

    q = operands["q"]
    pool = {name: operands.get(name)
            for name in ("v_pool", "k_scale", "v_scale", "wv", "bv")}
    K = k.blocks_per_step(q, operands["k_pool"], nbk, **pool)
    B_, H, n_, E = q.shape
    G = operands["k_pool"].shape[2]
    qg = jax.ShapeDtypeStruct((B_, G, (H // G) * n_, E), q.dtype)
    specs, flags = k.build_specs(qg, operands["k_pool"], K=K, **pool)
    quant_violations = check_paged_quant(specs, flags, workload=workload)

    vp, wv = operands.get("v_pool"), operands.get("wv")
    Hkv, dv = (vp.shape[2], vp.shape[3]) if vp is not None \
        else (wv.shape[1], wv.shape[2])
    R = (H // Hkv) * n_

    def step_eval(imap):
        return _step_eval(imap, NB, B, nbk, K) if _stepped(imap) else None
    blocks = []
    for name, op, block, maps in specs:
        for t, imap in enumerate(maps):
            blocks.append(Block(
                name if len(maps) == 1 else f"{name}[{t}]",
                tuple(op.shape), tuple(block), imap,
                jnp.dtype(op.dtype).itemsize, abstract_eval=step_eval(imap)))
    blocks.append(Block("out", (B, Hkv, R, dv), (1, Hkv, R, dv),
                        k.out_index_map, 4, out=True,
                        abstract_eval=step_eval(k.out_index_map)))
    # the launched grid is the batch's live steps: at most B*ceil(nbk/K)
    spec = KernelSpec(
        kernel="paged_attention",
        grid=(B * -(-nbk // K),),
        blocks=blocks,
        scratch_bytes=2 * k.vmem_bytes(k.stats_shape(Hkv, R), jnp.float32),
        workload=workload)
    return spec, quant_violations


# --------------------------------------------- planner-reachable combos

def _shard_dim(full_with_layers: tuple, msz: int) -> tuple:
    """Per-device trailing shape of one pool leaf under the real layout
    rule (leading layer-stack dim dropped)."""
    from repro.sharding import specs as shspecs
    spec = tuple(shspecs.paged_pool_spec(full_with_layers, msz))
    shape = list(full_with_layers)
    for i, ax in enumerate(spec):
        if ax == "model":
            shape[i] //= msz
    return tuple(shape[1:])


def _paged_operands(cfg, plan, msz: int):
    """Per-device ShapeDtypeStruct operands for the streamed paged
    kernel under (cfg, plan, model-axis extent), via the real
    ``init_kv_cache`` shapes and the real pool layout rule. Returns
    (operands, fallback_leaf_shapes)."""
    import jax
    import jax.numpy as jnp

    from repro.models import attention as attn
    from repro.sharding import specs as shspecs

    mode = plan.cache_mode
    be = plan.backend
    L = cfg.num_layers
    dt = jnp.dtype(cfg.dtype)
    cache = jax.eval_shape(
        lambda: attn.init_kv_cache(cfg, PAGED_NB, PAGED_BS, dt, mode=mode))
    leaf = {f: getattr(cache, f) for f in cache._fields
            if getattr(cache, f) is not None}

    shard = plan.shards_heads and msz > 1
    fallback = shspecs.nondividing_pool_leaves(
        [(L,) + v.shape for v in leaf.values()], msz) if shard else []
    if not plan.shards_heads and msz > 1:
        # factored-style fallback: the pool stays replicated entirely
        fallback = [(L,) + v.shape for v in leaf.values()]

    def dev(v):
        shape = _shard_dim((L,) + v.shape, msz) if shard else v.shape
        return jax.ShapeDtypeStruct(shape, v.dtype)

    leaf = {k: dev(v) for k, v in leaf.items()}
    # query heads split with the kv heads or not at all: a device runs
    # whole GQA groups (``_stream_kv`` replicates a non-dividing pool)
    H, Hkv = cfg.num_heads, cfg.num_kv_heads
    head_shard = shard and Hkv % msz == 0
    if head_shard:
        H //= msz
        Hkv //= msz
    dh = cfg.head_dim
    f32 = jnp.float32
    ops = {}
    if mode == "kv":
        k_pool = leaf["k"]
        ops["q"] = jax.ShapeDtypeStruct(
            (PAGED_B, H, PAGED_N, k_pool.shape[-1]), f32)
        ops["k_pool"] = k_pool
        ops["v_pool"] = leaf["v"]
        if "ks" in leaf:
            ops["k_scale"] = leaf["ks"]
            ops["v_scale"] = leaf["vs"]
    else:
        x = leaf["x"]                          # (NB, BS, D_dev)
        D_dev = x.shape[-1]
        aug = be.d_aug(cfg) == cfg.d_model + 1
        E = D_dev + (1 if aug else 0)
        ops["q"] = jax.ShapeDtypeStruct((PAGED_B, H, PAGED_N, E), f32)
        ops["k_pool"] = jax.ShapeDtypeStruct(
            (x.shape[0], x.shape[1], 1, D_dev), x.dtype)
        if "xs" in leaf:
            xs = leaf["xs"]
            ops["k_scale"] = jax.ShapeDtypeStruct(
                (xs.shape[0], xs.shape[1], 1, 1), xs.dtype)
        if mode == "xv":
            ops["v_pool"] = leaf["v"]
            if "vs" in leaf:
                ops["v_scale"] = leaf["vs"]
        else:                                  # pure-X: V recomputed
            ops["wv"] = jax.ShapeDtypeStruct((D_dev, Hkv, dh), f32)
            ops["bv"] = jax.ShapeDtypeStruct((Hkv, dh), f32)
    return ops, fallback


def _sweep_cfgs():
    """(label, cfg) pairs: the contracts-layer reduced family plus an
    Hkv=2 variant, so the non-dividing fallback class is non-empty on
    the 4/8-way extents."""
    import dataclasses as dc

    from repro.configs.base import get_arch, reduced

    base = reduced(get_arch("qwen2.5-14b"), num_layers=2, num_heads=8,
                   num_kv_heads=4)
    hkv2 = reduced(get_arch("qwen2.5-14b"), num_layers=2, num_heads=8,
                   num_kv_heads=2)
    out = []
    for tag, cfg in (("hkv4", base), ("hkv2", hkv2)):
        for q in (None, "int8"):
            qt = "f" if q is None else "i8"
            out.append((f"{tag}-{qt}",
                        dc.replace(cfg, cache_quant=q, pos_emb="none")))
    return out


def planner_combos():
    """Yield (label, cfg, plan, msz) for every planner-reachable
    combination: backend x cache quantization x sequence regime
    (serving decode vs long-context blockwise) x model-axis extent."""
    from repro.core import score_backend as sb

    for clabel, cfg in _sweep_cfgs():
        for backend in sb.list_backends():
            for seq_len, slabel in ((PAGED_MAX_LEN, "serve"),
                                    (16384, "long")):
                plan = sb.plan(cfg, seq_len=seq_len, device="tpu",
                               backend=backend)
                for msz in _EXTENTS:
                    yield (f"{clabel}/{backend}/{slabel}/tp{msz}",
                           cfg, plan, msz)


def combo_specs(label, cfg, plan, msz):
    """KernelSpecs + quant-contract violations + fallback leaves for one
    planner combo. Only kernels the plan actually dispatches to are
    emitted (stream decode -> paged; pallas quadratic -> wqk; blockwise
    -> the flash schedule's workload family)."""
    specs, quant, fallback = [], [], []
    if plan.decode_schedule == "stream":
        ops, fallback = _paged_operands(cfg, plan, msz)
        nbk = -(-PAGED_MAX_LEN // PAGED_BS)
        spec, qv = paged_spec(ops, B=PAGED_B, n=PAGED_N, NB=PAGED_NB,
                              BS=PAGED_BS, nbk=nbk, workload=label)
        specs.append(spec)
        quant.extend(qv)
    if msz > 1 and not plan.shards_heads and not fallback:
        # factored-style backends never shard heads: the whole pool
        # replicates on a TP mesh — fallback-correct, never "clean"
        fallback = ["pool-replicated"]
    if plan.backend.name == "wqk_int8_pallas" and not plan.blockwise:
        H = cfg.num_heads
        if plan.shards_heads and msz > 1 and H % msz == 0:
            H //= msz
        D = plan.backend.d_aug(cfg)
        specs.append(wqk_spec(H, 256, 256, D))
        specs[-1].workload = label
    if plan.blockwise:
        H, Hkv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        if plan.backend.uses_x_cache:
            E = plan.backend.d_aug(cfg)
            spec = flash_spec(H, 1, 1024, 1024, E, dh)
        else:
            spec = flash_spec(H, H, 1024, 1024, dh, dh)
        spec.workload = label
        specs.append(spec)
    return specs, quant, fallback


def run_all(verbose: bool = True) -> list[str]:
    """The planner sweep + the bitplane envelope. Returns violations."""
    violations = []
    seen = set()
    per_kernel: dict = {}
    fallback_combos = []
    n_combos = 0
    for label, cfg, plan, msz in planner_combos():
        n_combos += 1
        specs, quant, fallback = combo_specs(label, cfg, plan, msz)
        violations.extend(quant)
        if fallback:
            fallback_combos.append(label)
        for spec in specs:
            sig = spec.signature()
            if sig in seen:
                continue
            seen.add(sig)
            per_kernel.setdefault(spec.kernel, [0, 0])
            per_kernel[spec.kernel][0] += 1
            got = verify_spec(spec)
            per_kernel[spec.kernel][1] += len(got)
            violations.extend(got)

    # the bit-exact behavioural model is not planner-dispatched; verify
    # its documented envelope (macro tile 64x64, D <= 512, bits <= 8)
    for N, M, D in ((64, 64, 64), (128, 192, 128), (256, 256, 512)):
        spec = bitplane_spec(N, M, D)
        spec.workload = f"envelope N={N} M={M} D={D}"
        sig = spec.signature()
        if sig not in seen:
            seen.add(sig)
            per_kernel.setdefault(spec.kernel, [0, 0])
            per_kernel[spec.kernel][0] += 1
            got = verify_spec(spec)
            per_kernel[spec.kernel][1] += len(got)
            violations.extend(got)

    if verbose:
        print(f"[kernelcheck] planner sweep: {n_combos} combos, "
              f"{len(seen)} unique kernel workloads, "
              f"{len(fallback_combos)} fallback-correct")
        for kern in sorted(per_kernel):
            n, bad = per_kernel[kern]
            print(f"[kernelcheck] {kern}: {n} workload(s), "
                  f"{'OK' if not bad else f'{bad} violation(s)'}")
        if fallback_combos:
            uniq = sorted({c.rsplit("/", 1)[0] for c in fallback_combos})
            print(f"[kernelcheck] fallback-correct (non-dividing head "
                  f"shard, pool replicated/dim-sharded): {uniq}")
    return violations


def main() -> int:
    violations = run_all()
    for v in violations:
        print(f"VIOLATION: {v}")
    return 1 if violations else 0


if __name__ == "__main__":
    raise SystemExit(main())
