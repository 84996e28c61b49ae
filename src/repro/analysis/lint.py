"""AST lint pass: the JAX footguns this codebase has actually hit.

Eight rules, each encoding a constraint the serving/kernel stack relies
on but Python cannot express (see DESIGN.md §11 for the full contract
list). The linter is pure ``ast`` — importable and runnable without
jax, so pre-commit and CI can execute it in milliseconds:

  RA101 tracer-branch       Python ``if``/``while``/ternary/``assert``
                            whose test calls ``jnp.*`` directly: under
                            ``jit`` that's a TracerBoolConversionError
                            (or a silent host sync when run eagerly).
  RA102 host-sync-in-jit    ``.item()`` / ``np.asarray`` / ``np.array``
                            / ``float(<param>)`` inside a function that
                            is a jit target — a device->host transfer
                            in a hot path (or a trace-time crash).
  RA103 xla-env-mutation    ``os.environ`` writes to XLA* keys outside
                            the conftest subprocess-env guard. In-
                            process mutation is at best a no-op after
                            jax init and at worst poisons xdist-worker
                            siblings (tests/conftest.py's autouse guard
                            is the runtime twin of this rule).
  RA104 late-docstring      a module-level string-literal statement
                            that is not the first statement: a no-op
                            "docstring" (the launch/dryrun.py bug this
                            rule was written for).
  RA105 nonhashable-static  a jit-wrapped function whose declared
                            static arg has a non-hashable default, or a
                            same-module call site passing a list/dict/
                            set literal for a static arg (TypeError at
                            every call).
  RA106 unpinned-jit        in ``serving/``: a ``jax.jit`` result that
                            is not pinned to an attribute, subscript or
                            module-level name, or is invoked where it
                            is created — every call re-enters the
                            compilation cache through a fresh callable,
                            so nothing is ever cached.
  RA107 impure-index-map    a ``pl.BlockSpec`` index map with Python
                            branching in its body, or closing over a
                            name that is neither a parameter nor a
                            module-level binding (a potential tracer).
                            Index maps must be pure affine functions of
                            grid indices and scalar-prefetch refs —
                            that purity is what lets
                            ``repro.analysis.kernelcheck`` prove
                            in-bounds/write-once over them.
  RA108 program-id-branch   Python ``if``/``while``/ternary on a
                            ``pl.program_id(...)`` value inside a
                            kernel body: grid indices are traced
                            scalars, so Python branching freezes one
                            trace-time path for EVERY grid step. Use
                            ``pl.when`` / ``jnp.where``.

Suppressions are explicit and must carry a justification::

    os.environ["XLA_FLAGS"] = ...   # ra: allow[RA103] forced host \
                                    # devices must precede jax import

A bare ``# ra: allow[RA103]`` without a reason is itself a finding
(RA100) — zero suppressions without a reason string.

CLI::

    python -m repro.analysis.lint [paths...]   # default: src tests
                                               # examples
"""
from __future__ import annotations

import ast
import builtins as _py_builtins
import dataclasses
import re
import sys
from pathlib import Path

DEFAULT_PATHS = ("src", "tests", "examples")

_ALLOW_RE = re.compile(r"#\s*ra:\s*allow\[(RA\d{3})\]\s*(.*)")


@dataclasses.dataclass(frozen=True)
class Finding:
    path: str
    line: int
    code: str
    message: str

    def __str__(self):
        return f"{self.path}:{self.line}: {self.code} {self.message}"


# --------------------------------------------------------- suppressions

def _suppressions(source: str):
    """line -> {code: reason} from ``# ra: allow[RAxxx] reason`` comments.
    A marker covers its own line plus the first non-comment line below
    it, so multi-line justification comments above a statement work."""
    out = {}
    lines = source.splitlines()
    for i, text in enumerate(lines, start=1):
        m = _ALLOW_RE.search(text)
        if m is None:
            continue
        reason = m.group(2).strip()
        out.setdefault(i, {})[m.group(1)] = reason
        j = i                        # skip continuation comment lines
        while j < len(lines) and lines[j].lstrip().startswith("#"):
            j += 1
        out.setdefault(j + 1, {})[m.group(1)] = reason
    return out


# ------------------------------------------------------------ rule: 101

_HOST_CONVERSIONS = ("bool", "float", "int")


def _calls_jnp(node: ast.AST) -> bool:
    """True if the expression calls jnp.* OUTSIDE an explicit host
    conversion — ``bool(jnp.all(...))`` is the documented remedy (the
    sync is deliberate and visible), ``if jnp.all(...):`` is the bug."""
    stack = [node]
    while stack:
        sub = stack.pop()
        if isinstance(sub, ast.Call):
            f = sub.func
            if isinstance(f, ast.Name) and f.id in _HOST_CONVERSIONS:
                continue                 # explicit host conversion: ok
            # jnp.any(x), jnp.linalg.norm(x), ...
            while isinstance(f, ast.Attribute):
                f = f.value
            if isinstance(f, ast.Name) and f.id == "jnp":
                return True
        stack.extend(ast.iter_child_nodes(sub))
    return False


def _rule_tracer_branch(tree: ast.Module):
    for node in ast.walk(tree):
        test = None
        if isinstance(node, (ast.If, ast.While, ast.IfExp, ast.Assert)):
            test = node.test
        if test is not None and _calls_jnp(test):
            yield (node.lineno, "RA101",
                   "Python branch on a jnp.* result — under jit this is "
                   "a tracer-bool error; eagerly it blocks on a host "
                   "sync. Use jnp.where/lax.cond, or hoist the value to "
                   "host explicitly outside the hot path.")


# ------------------------------------------------------------ rule: 102

def _jit_target_names(tree: ast.Module) -> set:
    """Function names that are jit entry points in this module:
    decorated with jax.jit / functools.partial(jax.jit, ...), or passed
    to a jax.jit(...) call anywhere in the module."""
    names = set()

    def is_jax_jit(node) -> bool:
        return (isinstance(node, ast.Attribute) and node.attr == "jit"
                and isinstance(node.value, ast.Name)
                and node.value.id == "jax") or (
                    isinstance(node, ast.Name) and node.id == "jit")

    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                if is_jax_jit(dec):
                    names.add(node.name)
                if isinstance(dec, ast.Call):
                    if is_jax_jit(dec.func):
                        names.add(node.name)
                    # functools.partial(jax.jit, ...)
                    if (isinstance(dec.func, ast.Attribute)
                            and dec.func.attr == "partial"
                            and dec.args and is_jax_jit(dec.args[0])):
                        names.add(node.name)
        if isinstance(node, ast.Call) and is_jax_jit(node.func):
            for arg in node.args[:1]:
                if isinstance(arg, ast.Name):
                    names.add(arg.id)
    return names


def _rule_host_sync(tree: ast.Module):
    targets = _jit_target_names(tree)
    if not targets:
        return
    for node in ast.walk(tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                or node.name not in targets:
            continue
        params = {a.arg for a in node.args.args + node.args.kwonlyargs}
        for sub in ast.walk(node):
            if not isinstance(sub, ast.Call):
                continue
            f = sub.func
            if isinstance(f, ast.Attribute) and f.attr == "item":
                yield (sub.lineno, "RA102",
                       f".item() inside jit target {node.name!r} — "
                       f"device->host sync in a hot path (and a trace-"
                       f"time error under jit).")
            elif (isinstance(f, ast.Attribute)
                    and f.attr in ("asarray", "array")
                    and isinstance(f.value, ast.Name)
                    and f.value.id == "np"):
                yield (sub.lineno, "RA102",
                       f"np.{f.attr}() inside jit target {node.name!r} "
                       f"— materializes on host; use jnp, or move the "
                       f"conversion outside the jitted function.")
            elif (isinstance(f, ast.Name) and f.id in ("float", "int")
                    and sub.args
                    and isinstance(sub.args[0], ast.Name)
                    and sub.args[0].id in params):
                yield (sub.lineno, "RA102",
                       f"{f.id}(<arg>) on parameter "
                       f"{sub.args[0].id!r} of jit target {node.name!r} "
                       f"— a host sync if the arg is a device value; "
                       f"accept a Python scalar or keep it an array.")


# ------------------------------------------------------------ rule: 103

def _env_key(node) -> str:
    """String key of an os.environ subscript/setdefault, '' if unknown."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return ""


def _is_os_environ(node) -> bool:
    return (isinstance(node, ast.Attribute) and node.attr == "environ"
            and isinstance(node.value, ast.Name) and node.value.id == "os")


def _rule_env_mutation(tree: ast.Module):
    msg = ("os.environ[{k!r}] mutation — XLA flags are locked in at "
           "first jax init; in-process mutation silently no-ops (or "
           "poisons sibling xdist workers). Spawn a subprocess with "
           "conftest.forced_devices_env instead.")
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign):
            for tgt in node.targets:
                if isinstance(tgt, ast.Subscript) \
                        and _is_os_environ(tgt.value):
                    k = _env_key(tgt.slice)
                    if k.startswith("XLA"):
                        yield node.lineno, "RA103", msg.format(k=k)
        if isinstance(node, ast.Call) \
                and isinstance(node.func, ast.Attribute) \
                and node.func.attr in ("setdefault", "pop", "update") \
                and _is_os_environ(node.func.value):
            k = _env_key(node.args[0]) if node.args else ""
            if k.startswith("XLA") or node.func.attr == "update":
                yield (node.lineno, "RA103",
                       msg.format(k=k or "<dynamic>"))


# ------------------------------------------------------------ rule: 104

def _rule_late_docstring(tree: ast.Module):
    for i, node in enumerate(tree.body):
        if i == 0:
            continue
        if isinstance(node, ast.Expr) \
                and isinstance(node.value, ast.Constant) \
                and isinstance(node.value.value, str):
            yield (node.lineno, "RA104",
                   "module-level string after the first statement is a "
                   "no-op, not a docstring — move it to the top (code "
                   "that must precede imports goes AFTER the docstring; "
                   "a docstring never blocks env-before-jax ordering).")


# ------------------------------------------------------------ rule: 105

_MUTABLE_LITERALS = (ast.List, ast.Dict, ast.Set, ast.ListComp,
                     ast.DictComp, ast.SetComp)


def _static_names_of(call: ast.Call):
    """static_argnames string tuple of a jax.jit / partial(jax.jit, ...)
    call, or None."""
    for kw in call.keywords:
        if kw.arg == "static_argnames":
            vals = []
            nodes = kw.value.elts if isinstance(
                kw.value, (ast.Tuple, ast.List)) else [kw.value]
            for n in nodes:
                if isinstance(n, ast.Constant) and isinstance(n.value, str):
                    vals.append(n.value)
            return vals
    return None


def _jit_static_args(tree: ast.Module):
    """fn-name -> static arg names, for jit wrappers resolvable in this
    module (decorator or jax.jit(fn, static_argnames=...) call)."""
    out = {}
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                if isinstance(dec, ast.Call):
                    names = _static_names_of(dec)
                    if names:
                        out[node.name] = names
        if isinstance(node, ast.Call):
            names = _static_names_of(node)
            if names and node.args and isinstance(node.args[0], ast.Name):
                out[node.args[0].id] = names
    return out


def _rule_nonhashable_static(tree: ast.Module):
    statics = _jit_static_args(tree)
    if not statics:
        return
    defs = {n.name: n for n in ast.walk(tree)
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))}
    for fname, names in statics.items():
        fn = defs.get(fname)
        if fn is None:
            continue
        # align defaults to the trailing positional args + kwonly args
        pos_def = {a.arg: d for a, d in zip(
            fn.args.args[len(fn.args.args) - len(fn.args.defaults):],
            fn.args.defaults, strict=True)}
        kw_def = {a.arg: d for a, d in zip(fn.args.kwonlyargs,
                                           fn.args.kw_defaults,
                                           strict=True)}
        for name in names:
            d = pos_def.get(name, kw_def.get(name))
            if d is not None and isinstance(d, _MUTABLE_LITERALS):
                yield (d.lineno, "RA105",
                       f"static arg {name!r} of jit target {fname!r} "
                       f"defaults to a non-hashable literal — every "
                       f"call raises TypeError; use a tuple/frozen "
                       f"value.")
    # call sites in this module passing mutable literals to static args
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call) \
                or not isinstance(node.func, ast.Name) \
                or node.func.id not in statics:
            continue
        for kw in node.keywords:
            if kw.arg in statics[node.func.id] \
                    and isinstance(kw.value, _MUTABLE_LITERALS):
                yield (kw.value.lineno, "RA105",
                       f"call passes a non-hashable literal for static "
                       f"arg {kw.arg!r} of {node.func.id!r} — TypeError "
                       f"at every invocation; pass a tuple or hashable "
                       f"value.")


# ------------------------------------------------------------ rule: 106

def _is_jax_jit_call(node) -> bool:
    return (isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr == "jit"
            and isinstance(node.func.value, ast.Name)
            and node.func.value.id == "jax")


def _rule_unpinned_jit(tree: ast.Module, path: str):
    if "serving" not in Path(path).parts:
        return
    parent = {}
    for node in ast.walk(tree):
        for child in ast.iter_child_nodes(node):
            parent[id(child)] = node
    toplevel = set(map(id, tree.body))

    for node in ast.walk(tree):
        if not _is_jax_jit_call(node):
            continue
        p = parent.get(id(node))
        if isinstance(p, ast.Call) and p.func is node:
            yield (node.lineno, "RA106",
                   "jax.jit(...)(...) immediately invoked — the "
                   "callable is rebuilt per call, so the compilation "
                   "cache never hits. Pin the jitted function to an "
                   "attribute, subscript or module-level name.")
        elif isinstance(p, ast.Assign):
            # Attribute (self._f = jit(...)) and Subscript
            # (cache[k] = jit(...)) targets persist across calls;
            # a bare Name persists only at module level.
            if any(isinstance(t, ast.Name) for t in p.targets) \
                    and id(p) not in toplevel:
                yield (node.lineno, "RA106",
                       "jax.jit(...) bound to a function-local name — "
                       "recreated (and recompiled) on every enclosing "
                       "call. Pin it to an attribute, subscript or "
                       "module-level name.")
        elif isinstance(p, ast.Expr):
            yield (node.lineno, "RA106",
                   "jax.jit(...) result discarded — pin it to an "
                   "attribute, subscript or module-level name so the "
                   "compiled graph is reused.")
        # Return / argument positions are allowed: factory functions
        # returning a jitted callable pin at their own call site.


# ------------------------------------------------------------ rule: 107

_BUILTIN_NAMES = frozenset(dir(_py_builtins))


def _module_names(tree: ast.Module) -> set:
    """Names bound at module level (defs, classes, assigns, imports)."""
    names = set()
    for node in tree.body:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            names.add(node.name)
        elif isinstance(node, ast.Assign):
            for t in node.targets:
                if isinstance(t, ast.Name):
                    names.add(t.id)
                elif isinstance(t, (ast.Tuple, ast.List)):
                    names.update(e.id for e in t.elts
                                 if isinstance(e, ast.Name))
        elif isinstance(node, ast.AnnAssign) \
                and isinstance(node.target, ast.Name):
            names.add(node.target.id)
        elif isinstance(node, (ast.Import, ast.ImportFrom)):
            for a in node.names:
                names.add(a.asname or a.name.split(".")[0])
    return names


def _is_blockspec_call(node) -> bool:
    if not isinstance(node, ast.Call):
        return False
    f = node.func
    return (isinstance(f, ast.Attribute) and f.attr == "BlockSpec") \
        or (isinstance(f, ast.Name) and f.id == "BlockSpec")


def _index_map_issues(fn, module_names):
    """Purity issues of one index-map Lambda/FunctionDef: Python
    branching, or free names that are neither parameters, local
    bindings, module-level names, nor builtins (potential closed-over
    tracers)."""
    bound = set()
    for sub in ast.walk(fn):
        if isinstance(sub, ast.arg):
            bound.add(sub.arg)
        elif isinstance(sub, ast.Name) and isinstance(sub.ctx, ast.Store):
            bound.add(sub.id)
    for sub in ast.walk(fn):
        if isinstance(sub, (ast.If, ast.IfExp, ast.While)):
            yield (sub.lineno,
                   "Python branching inside a BlockSpec index map — the "
                   "map must be a pure affine function of its grid/"
                   "scalar-prefetch args (kernelcheck proves bounds "
                   "over exactly that form); select with jnp.where on "
                   "the returned coordinate instead.")
        elif isinstance(sub, ast.Name) and isinstance(sub.ctx, ast.Load):
            if sub.id not in bound and sub.id not in module_names \
                    and sub.id not in _BUILTIN_NAMES:
                yield (sub.lineno,
                       f"BlockSpec index map closes over {sub.id!r}, "
                       f"which is neither a parameter nor a module-"
                       f"level name — closures over enclosing-function "
                       f"locals can capture tracers. Hoist the map to a "
                       f"named module-level function (scalar-prefetch "
                       f"refs arrive as arguments).")


def _rule_impure_index_map(tree: ast.Module):
    module_names = _module_names(tree)
    defs = {}
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            defs.setdefault(node.name, node)
    for node in ast.walk(tree):
        if not _is_blockspec_call(node):
            continue
        imap = node.args[1] if len(node.args) >= 2 else None
        for kw in node.keywords:
            if kw.arg == "index_map":
                imap = kw.value
        if isinstance(imap, ast.Lambda):
            for line, msg in _index_map_issues(imap, module_names):
                yield line, "RA107", msg
        elif isinstance(imap, ast.Name) and imap.id in defs:
            for line, msg in _index_map_issues(defs[imap.id],
                                               module_names):
                yield line, "RA107", msg
        # attribute refs (othermod.x_index_map) are checked in the
        # module that defines them — every kernel module carries its
        # own maps next to its pallas_call


# ------------------------------------------------------------ rule: 108

def _is_program_id_call(node) -> bool:
    return (isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr == "program_id"
            and isinstance(node.func.value, ast.Name)
            and node.func.value.id == "pl")


def _rule_program_id_branch(tree: ast.Module):
    for fn in ast.walk(tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if not any(_is_program_id_call(s) for s in ast.walk(fn)):
            continue
        grid_names = set()
        for sub in ast.walk(fn):
            if isinstance(sub, ast.Assign) \
                    and _is_program_id_call(sub.value):
                grid_names.update(t.id for t in sub.targets
                                  if isinstance(t, ast.Name))

        def refs_grid(expr) -> bool:
            for s in ast.walk(expr):
                if _is_program_id_call(s):
                    return True
                if isinstance(s, ast.Name) and s.id in grid_names:
                    return True
            return False

        for sub in ast.walk(fn):
            if isinstance(sub, (ast.If, ast.IfExp, ast.While)) \
                    and refs_grid(sub.test):
                yield (sub.lineno, "RA108",
                       "Python branch on a pl.program_id(...) value "
                       "inside a kernel body — grid indices are traced "
                       "scalars, so this freezes ONE trace-time path "
                       "for every grid step. Use pl.when(...) or "
                       "jnp.where.")


# --------------------------------------------------------------- driver

_RULES = (
    lambda tree, path: _rule_tracer_branch(tree),
    lambda tree, path: _rule_host_sync(tree),
    lambda tree, path: _rule_env_mutation(tree),
    lambda tree, path: _rule_late_docstring(tree),
    lambda tree, path: _rule_nonhashable_static(tree),
    _rule_unpinned_jit,
    lambda tree, path: _rule_impure_index_map(tree),
    lambda tree, path: _rule_program_id_branch(tree),
)


def check_source(source: str, path: str = "<string>"):
    """Lint one module's source text -> list of Finding."""
    try:
        tree = ast.parse(source)
    except SyntaxError as e:
        return [Finding(path, e.lineno or 0, "RA000",
                        f"syntax error: {e.msg}")]
    allows = _suppressions(source)
    findings = []
    for rule in _RULES:
        for line, code, msg in rule(tree, path):
            reason = allows.get(line, {}).get(code)
            if reason is None:
                findings.append(Finding(path, line, code, msg))
            elif not reason:
                findings.append(Finding(
                    path, line, "RA100",
                    f"suppression of {code} without a reason — every "
                    f"allow needs a justification string."))
    # unused-reason check is intentionally omitted: an allow above a
    # line that stopped firing is harmless and self-documents history
    return findings


def check_paths(paths=DEFAULT_PATHS, root: str = "."):
    findings = []
    rootp = Path(root)
    for p in paths:
        target = rootp / p
        files = sorted(target.rglob("*.py")) if target.is_dir() \
            else [target]
        for f in files:
            if "__pycache__" in f.parts:
                continue
            findings.extend(
                check_source(f.read_text(encoding="utf-8"), str(f)))
    return findings


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    paths = argv or list(DEFAULT_PATHS)
    findings = check_paths(paths)
    for f in findings:
        print(f)
    n = len(findings)
    print(f"[repro.analysis.lint] {n} finding{'s' if n != 1 else ''} "
          f"across {len(paths)} path(s)")
    return 1 if findings else 0


if __name__ == "__main__":
    raise SystemExit(main())
