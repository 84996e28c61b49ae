"""Abstract domain for the kernel verifier (repro.analysis.kernelcheck).

Two cooperating abstractions over BlockSpec index-map arithmetic:

* **Affine forms** — probe an index map at a handful of concrete integer
  grid points and reconstruct, per output coordinate, the exact affine
  function ``const + Σ coeff_a · g_a`` of the grid indices, then verify
  the reconstruction at extra probe points. A map that survives probing
  IS affine on the probed box (and lint rule RA107 independently rejects
  data-dependent Python in index maps), so interval bounds computed from
  the coefficients are sound, and write-once coverage can be decided in
  closed form instead of by enumeration.

* **Interval / symbolic values** — the ``paged_attention`` maps index
  scalar-prefetch step arrays and the block table, which is not affine
  in the grid. ``Sym``/``ScalarLoad``/``GatherLoad`` model step indices
  and table reads symbolically (``RunTable`` a sequence-major step ->
  row array, ``RunStart`` its runs' first steps); comparisons build
  ``Guard`` records instead of booleans, and ``where`` implements the
  ONE select pattern we accept as proof of the null-block redirect:
  ``where(j < used[b], tables[b, j], const)``. A gathered table entry
  is only trusted to lie in the live range ``[0, NB)`` when the guard
  is *exactly* the liveness predicate for that same (row, col), over a
  column that cannot be negative — i.e. the engine never asks for a
  dead entry. Any other shape of select degrades soundly to the hull of the
  full int32 range, which the in-bounds proof then rejects.

Everything here is pure Python over ints — no jax import — so the
verifier's core runs anywhere the lint layer runs.
"""
from __future__ import annotations

import dataclasses
import itertools

INT32_MIN = -(2 ** 31)
INT32_MAX = 2 ** 31 - 1


# ------------------------------------------------------------- intervals

@dataclasses.dataclass(frozen=True)
class Interval:
    """Closed integer interval [lo, hi]."""
    lo: int
    hi: int

    def __post_init__(self):
        assert self.lo <= self.hi, (self.lo, self.hi)

    def __add__(self, other):
        o = as_interval(other)
        return Interval(self.lo + o.lo, self.hi + o.hi)

    __radd__ = __add__

    def __sub__(self, other):
        o = as_interval(other)
        return Interval(self.lo - o.hi, self.hi - o.lo)

    def __rsub__(self, other):
        return as_interval(other) - self

    def __mul__(self, other):
        o = as_interval(other)
        c = [self.lo * o.lo, self.lo * o.hi, self.hi * o.lo, self.hi * o.hi]
        return Interval(min(c), max(c))

    __rmul__ = __mul__

    def __floordiv__(self, other):
        o = as_interval(other)
        assert o.lo > 0, f"interval floordiv by non-positive {o}"
        c = [self.lo // o.lo, self.lo // o.hi, self.hi // o.lo,
             self.hi // o.hi]
        return Interval(min(c), max(c))

    def __mod__(self, other):
        o = as_interval(other)
        assert o.lo > 0, f"interval mod by non-positive {o}"
        if self.lo >= 0 and o.lo == o.hi and self.hi - self.lo < o.lo \
                and self.lo % o.lo <= self.hi % o.lo:
            return Interval(self.lo % o.lo, self.hi % o.lo)
        if self.lo >= 0:
            return Interval(0, min(self.hi, o.hi - 1))
        return Interval(-(o.hi - 1), o.hi - 1)

    def __lt__(self, other):
        # a derived index compared, as in a liveness test: unknown truth
        return Guard("lt", self, other)

    def hull(self, other: "Interval") -> "Interval":
        return Interval(min(self.lo, other.lo), max(self.hi, other.hi))

    def within(self, lo: int, hi: int) -> bool:
        """Is the whole interval inside [lo, hi]?"""
        return lo <= self.lo and self.hi <= hi

    def __repr__(self):
        return f"[{self.lo}, {self.hi}]"


FULL_INT32 = Interval(INT32_MIN, INT32_MAX)


def as_interval(v) -> Interval:
    """Coerce an int / Interval / symbolic value to an Interval."""
    if isinstance(v, Interval):
        return v
    if isinstance(v, bool):
        raise TypeError("booleans are not abstract index values")
    if isinstance(v, int):
        return Interval(v, v)
    if isinstance(v, (Sym, ScalarLoad, GatherLoad)):
        return v.to_interval()
    raise TypeError(f"cannot abstract {type(v).__name__}: {v!r}")


# ------------------------------------------------------- symbolic values

@dataclasses.dataclass(frozen=True)
class Guard:
    """A comparison whose truth is unknown: ``lhs <op> rhs``."""
    op: str          # "lt" only, currently
    lhs: object
    rhs: object


class _SymBase:
    """Comparison-building mixin for symbolic index values."""

    def __lt__(self, other):
        return Guard("lt", self, other)

    def __ge__(self, other):
        # only ever used as a negated liveness test; model as the lt
        # guard with swapped branch semantics at the `where` site
        return Guard("lt", other, self)

    def __add__(self, other):
        return self.to_interval() + other

    __radd__ = __add__

    def __sub__(self, other):
        return self.to_interval() - other

    def __rsub__(self, other):
        return as_interval(other) - self.to_interval()

    def __mul__(self, other):
        return self.to_interval() * other

    __rmul__ = __mul__

    def __floordiv__(self, other):
        return self.to_interval() // other

    def __mod__(self, other):
        return self.to_interval() % other


@dataclasses.dataclass(frozen=True, eq=False)
class Sym(_SymBase):
    """A named symbol ranging over [lo, hi] — a grid index."""
    name: str
    lo: int
    hi: int

    def to_interval(self) -> Interval:
        return Interval(self.lo, self.hi)

    def __sub__(self, other):
        # the step's offset inside its run: i - start[runs[i]]
        if (isinstance(other, ScalarLoad)
                and isinstance(other.table, RunStart)
                and isinstance(other.idx, ScalarLoad)
                and other.idx.table is other.table.runs
                and other.idx.idx is self):
            return Sym(f"{self.name}-{other!r}", 0, other.table.width - 1)
        return super().__sub__(other)

    def __repr__(self):
        return f"{self.name}∈[{self.lo},{self.hi}]"


class ScalarTable:
    """A scalar-prefetch vector ref (e.g. ``blocks_used``): indexing it
    yields a ScalarLoad carrying the table's declared value range."""

    def __init__(self, name: str, lo: int, hi: int):
        self.name = name
        self.lo = lo
        self.hi = hi

    def __getitem__(self, idx):
        return ScalarLoad(self, idx)

    def __repr__(self):
        return f"ScalarTable({self.name}, [{self.lo},{self.hi}])"


class RunTable(ScalarTable):
    """A step -> row scalar-prefetch vector in sequence-major order
    (the paged kernel's ``seq_of_step``): non-decreasing over the steps
    and taking every value of [lo, hi], so each row owns one contiguous
    run of steps. An output map that reads it at the grid step writes
    each row's block once, on consecutive steps."""


class RunStart(ScalarTable):
    """The first step of each row's run in a ``RunTable`` (the paged
    kernel's ``start``), each run at most ``width`` steps long. So
    ``i - start[runs[i]]``, a step's offset inside its own run (the
    paged kernel's logical block), lies in [0, width): ``Sym`` models
    that difference as a symbol of its own."""

    def __init__(self, name: str, runs: RunTable, width: int, lo: int,
                 hi: int):
        super().__init__(name, lo, hi)
        self.runs = runs
        self.width = width


@dataclasses.dataclass(frozen=True, eq=False)
class ScalarLoad(_SymBase):
    """``table[idx]`` for a ScalarTable — value in the table's range."""
    table: ScalarTable
    idx: object

    def to_interval(self) -> Interval:
        return Interval(self.table.lo, self.table.hi)

    def __repr__(self):
        return f"{self.table.name}[{self.idx!r}]"


class GatherTable:
    """The block-table ref: a 2-D scalar-prefetch table whose LIVE
    entries (col < used[row]) lie in [0, num_blocks) but whose dead
    entries are arbitrary int32 garbage (freed / never-written slots).

    ``used`` is the ScalarTable holding per-row live lengths; the
    ``where`` select below is the only way to recover the live range,
    and only for a column inside ``width`` when that is given.
    """

    def __init__(self, name: str, num_blocks: int, used: ScalarTable,
                 width: int | None = None):
        self.name = name
        self.live = Interval(0, num_blocks - 1)
        self.used = used
        self.width = width           # columns; None: not modeled

    def __getitem__(self, idx):
        row, col = idx
        return GatherLoad(self, row, col)

    def __repr__(self):
        return f"GatherTable({self.name}, live={self.live})"


@dataclasses.dataclass(frozen=True, eq=False)
class GatherLoad(_SymBase):
    """``table[row, col]`` — FULL int32 unless liveness-guarded."""
    table: GatherTable
    row: object
    col: object

    def to_interval(self) -> Interval:
        return FULL_INT32

    def __repr__(self):
        return f"{self.table.name}[{self.row!r},{self.col!r}]"


def _is_liveness_guard(cond: Guard, load: GatherLoad) -> bool:
    """Is ``cond`` exactly ``load.col < used[load.row]`` for the used
    table the gather table itself was declared with, over a column
    that cannot be negative nor pass the table's width (the select
    guards the value, not the read beside it)?"""
    col = as_interval(load.col)
    width = load.table.width
    return (cond.op == "lt"
            and cond.lhs is load.col
            and col.lo >= 0
            and (width is None or col.hi < width)
            and isinstance(cond.rhs, ScalarLoad)
            and cond.rhs.table is load.table.used
            and cond.rhs.idx is load.row)


def where(cond, if_true, if_false):
    """Abstract select: the verifier's stand-in for ``jnp.where`` inside
    index maps (injected via the map's ``_where`` kwarg).

    The one precise case is the null-block redirect: a gathered table
    entry guarded by its own liveness predicate is live, so the result
    hulls the table's live range with the false branch. Everything else
    is a sound hull of both branches — including an unguarded (or
    mis-guarded) gather, which hulls to full int32 and fails in-bounds.
    """
    if isinstance(cond, bool):
        return if_true if cond else if_false
    if not isinstance(cond, Guard):
        raise TypeError(f"where() condition is not abstract: {cond!r}")
    if isinstance(if_true, GatherLoad) and _is_liveness_guard(cond, if_true):
        return if_true.table.live.hull(as_interval(if_false))
    return as_interval(if_true).hull(as_interval(if_false))


# ----------------------------------------------------- affine extraction

@dataclasses.dataclass(frozen=True)
class AffineCoord:
    """One output coordinate as ``const + Σ coeffs[a] · grid[a]``."""
    const: int
    coeffs: tuple          # one int per grid axis

    def interval(self, grid: tuple) -> Interval:
        """Range over the full grid box ``[0, extent)`` per axis."""
        acc = Interval(self.const, self.const)
        for c, extent in zip(self.coeffs, grid, strict=True):
            acc = acc + Interval(0, extent - 1) * c
        return acc

    def at(self, point: tuple) -> int:
        return self.const + sum(
            c * p for c, p in zip(self.coeffs, point, strict=True))


class NotAffine(Exception):
    """Raised with a witness probe point when a map fails linearity."""

    def __init__(self, msg, point=None):
        super().__init__(msg)
        self.point = point


def _probe_points(grid: tuple):
    """Probe set: origin, unit vectors, far corner, all-ones, and a
    staggered point — enough to fix an affine form and to catch the
    common nonlinear cheats (products of axes, mod/div by extents)."""
    n = len(grid)
    pts = [tuple(0 for _ in grid)]
    for a in range(n):
        pts.append(tuple((1 if i == a else 0) if grid[i] > 1 else 0
                         for i in range(n)))
    pts.append(tuple(e - 1 for e in grid))
    pts.append(tuple(min(1, e - 1) for e in grid))
    pts.append(tuple((a + 1) % e if e > 1 else 0
                     for a, e in enumerate(grid)))
    # dedup, preserving order
    seen, out = set(), []
    for p in pts:
        if p not in seen:
            seen.add(p)
            out.append(p)
    return out


def affine_coords(index_map, grid: tuple, extra_args: tuple = ()):
    """Reconstruct each output coordinate of ``index_map`` as an
    AffineCoord by concrete probing, or raise NotAffine with a witness.

    ``extra_args`` are passed through after the grid indices (for
    scalar-prefetch refs — use concrete stand-ins here; gather maps
    should go through the symbolic path instead).
    """
    origin = tuple(0 for _ in grid)
    base = index_map(*origin, *extra_args)
    if not isinstance(base, tuple):
        base = (base,)
    ncoord = len(base)
    for v in base:
        if not isinstance(v, int) or isinstance(v, bool):
            raise NotAffine(
                f"index map returned non-integer coordinate {v!r} at "
                f"grid origin", origin)

    coeffs = [[0] * len(grid) for _ in range(ncoord)]
    for a in range(len(grid)):
        if grid[a] <= 1:
            continue
        pt = tuple(1 if i == a else 0 for i in range(len(grid)))
        val = index_map(*pt, *extra_args)
        if not isinstance(val, tuple):
            val = (val,)
        if len(val) != ncoord:
            raise NotAffine(
                f"index map arity changed across grid points "
                f"({ncoord} vs {len(val)})", pt)
        for d in range(ncoord):
            coeffs[d][a] = val[d] - base[d]

    forms = tuple(AffineCoord(base[d], tuple(coeffs[d]))
                  for d in range(ncoord))

    for pt in _probe_points(grid):
        val = index_map(*pt, *extra_args)
        if not isinstance(val, tuple):
            val = (val,)
        for d in range(ncoord):
            if forms[d].at(pt) != val[d]:
                raise NotAffine(
                    f"index map coordinate {d} is not affine in the grid: "
                    f"predicted {forms[d].at(pt)}, got {val[d]} at grid "
                    f"point {pt}", pt)
    return forms


def iter_grid(grid: tuple, limit: int | None = None):
    """Iterate grid points in TPU sequential order (last axis fastest).

    With ``limit``, stop after that many points (caller must handle the
    truncation — used only by the bounded-enumeration fallback)."""
    it = itertools.product(*(range(e) for e in grid))
    if limit is None:
        yield from it
    else:
        yield from itertools.islice(it, limit)
