"""MG008 — recompile-hazard: silent per-call retrace/recompile in the
device plane.

``jax.jit`` caches compiled programs on FUNCTION IDENTITY plus abstract
argument signatures. Three codebase patterns defeat that cache without
any error — the program just quietly recompiles on every call, which on
an accelerator costs seconds per invocation and melts the
serving plane's latency budget (the static half of the
``jit.compile_total`` runtime witness):

  * ``jit-per-call`` — ``jax.jit(...)`` applied inside a function (or a
    ``@jax.jit`` decorator on a nested def) whose result is NOT stored
    through a recognized memo: each call builds a fresh closure, so
    jit's identity-keyed cache never hits. Recognized memos: the jit
    value (or a tuple holding it) assigned into a subscript
    (``CACHE[key] = ...``); an enclosing function using the
    get-then-build-then-store idiom (``.get(`` + a subscript store, or
    ``getattr`` + ``object.__setattr__``); or the enclosing function
    being a builder that such a memo function calls / receives as an
    argument (``_pc_cached``, ``_FIXPOINT_CACHE``, plan caches).
  * ``jit-per-object`` — the jit is memoised, but only on an OBJECT
    (``getattr`` + ``object.__setattr__``, or a dict that hangs off
    one): the jitted closure lives as long as that object, and the
    objects of this plane are graph snapshots, replaced by every
    committed write. So a CALL after a write re-traces and lowers the
    whole program although nothing static changed (S15: 3.5-3.8 s of an
    8 s CALL went there). A builder is safe when some path to it goes
    through a keyed TABLE: a module-level (or ``self.``) dict read
    with ``.get``/``setdefault`` and written by subscript, keyed by
    what is static, with the arrays passed as arguments.
  * ``traced-branch`` — Python ``if``/``while``/ternary on a traced
    parameter of a jit root: either a trace-time concretization error,
    or (once someone "fixes" it by making the arg static) one compiled
    program PER VALUE.
  * ``unhashable-static`` — ``static_argnames``/``static_argnums``
    naming a parameter whose default is a list/dict/set literal:
    unhashable statics fail at call time, and mutable defaults that
    vary per call mean one compile per distinct value anyway.

Scope: ``ops/`` and ``parallel/`` (the jitted device plane).
"""

from __future__ import annotations

import ast

from ..core import Finding, Project, qualname_of
from ..locking import dotted
from ..registry import register
from .jax_purity import _ModuleScan, _jit_static_args, _traced_params

_JIT_NAMES = {"jit", "pjit"}


def _in_scope(rel: str) -> bool:
    return "/ops/" in f"/{rel}" or "/parallel/" in f"/{rel}"


def _is_jit_call(node: ast.Call) -> bool:
    name = dotted(node.func) or ""
    return name.split(".")[-1] in _JIT_NAMES


def _enclosing_funcs(node: ast.AST):
    cur = getattr(node, "_mglint_parent", None)
    while cur is not None:
        if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
            yield cur
        cur = getattr(cur, "_mglint_parent", None)


def _module_names(tree: ast.Module) -> set[str]:
    """Names bound at module level: where a keyed table lives."""
    names: set[str] = set()
    for stmt in tree.body:
        targets = (stmt.targets if isinstance(stmt, ast.Assign)
                   else [stmt.target] if isinstance(stmt, ast.AnnAssign)
                   else [])
        names.update(t.id for t in targets if isinstance(t, ast.Name))
    return names


def _is_table(node: ast.AST, tables: set[str]) -> bool:
    """A container that outlives any one snapshot: a module-level name
    or an attribute of ``self``."""
    if isinstance(node, ast.Name):
        return node.id in tables
    return (isinstance(node, ast.Attribute)
            and isinstance(node.value, ast.Name)
            and node.value.id == "self")


def _has_memo_idiom(fn: ast.AST, tables: set[str] | None = None) -> bool:
    """The get-then-build-then-store caching idiom. With ``tables``
    (the module-level names of fn's file) only a memo in a keyed table
    counts: one that hangs off an object (``getattr`` +
    ``object.__setattr__``, a local dict) does not."""
    has_get = has_store = has_getattr = has_setattr = False
    for node in ast.walk(fn):
        if isinstance(node, ast.Call):
            callee = dotted(node.func) or ""
            short = callee.split(".")[-1]
            keyed = isinstance(node.func, ast.Attribute) and (
                tables is None or _is_table(node.func.value, tables))
            if short == "get" and keyed:
                has_get = True
            if short == "setdefault" and keyed:
                has_get = has_store = True
            if callee == "getattr":
                has_getattr = True
            if callee == "object.__setattr__":
                has_setattr = True
        if isinstance(node, ast.Assign):
            if any(isinstance(t, ast.Subscript)
                   and (tables is None or _is_table(t.value, tables))
                   for t in node.targets):
                has_store = True
    if tables is not None:
        return has_get and has_store
    return (has_get and has_store) or (has_getattr and has_setattr)


def _stored_in_subscript(call: ast.Call) -> bool:
    """The jit value (possibly inside a tuple/chained assign) lands in a
    subscript store: ``CACHE[k] = jax.jit(...)`` / ``c[k] = (p, jit)``."""
    cur = call
    parent = getattr(cur, "_mglint_parent", None)
    while parent is not None and isinstance(parent, (ast.Tuple, ast.List)):
        cur = parent
        parent = getattr(cur, "_mglint_parent", None)
    if isinstance(parent, ast.Assign):
        return any(isinstance(t, ast.Subscript) for t in parent.targets)
    if isinstance(parent, ast.Return):
        # returned to the caller: the builder itself decides nothing —
        # resolved through the cached-builder name set instead
        return False
    return False


def _collect_cached_builders(project: Project,
                             tables_only: bool = False) -> set[str]:
    """Names exempt from jit-per-call because a memo-idiom function
    calls them or receives them as call arguments (the builder half of
    the get-then-build-then-store pattern), computed project-wide.
    ``tables_only``: count keyed-table memos alone (jit-per-object)."""
    memo_funcs: set[str] = set()
    infos = []          # (fn node, sf)
    for rel, sf in project.files.items():
        if not rel.endswith(".py"):
            continue
        sf.ensure_parents()
        tables = _module_names(sf.tree) if tables_only else None
        for node in ast.walk(sf.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                infos.append(node)
                if _has_memo_idiom(node, tables):
                    memo_funcs.add(node.name)
    exempt: set[str] = set()
    for fn in infos:
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            callee = (dotted(node.func) or "").split(".")[-1]
            if fn.name in memo_funcs:
                # builders CALLED from a memo function
                exempt.add(callee)
                # builders PASSED INTO another call from a memo function
                for arg in node.args:
                    if isinstance(arg, ast.Name):
                        exempt.add(arg.id)
            elif callee in memo_funcs:
                # builders passed as arguments TO a memo function
                # (the `_pc_cached("kind", _builder, ...)` shape)
                for arg in node.args:
                    if isinstance(arg, ast.Name):
                        exempt.add(arg.id)
    return exempt


_MUTABLE_DEFAULTS = (ast.List, ast.Dict, ast.Set)


@register("MG008", "recompile-hazard")
def check(project: Project):
    """Per-call jit, traced-value branching, unhashable static args."""
    findings: list[Finding] = []
    cached_builders: set[str] | None = None
    table_builders: set[str] | None = None
    for rel, sf in sorted(project.files.items()):
        if not _in_scope(rel):
            continue
        sf.ensure_parents()
        tables = _module_names(sf.tree)

        # --- jit-per-call --------------------------------------------
        for node in ast.walk(sf.tree):
            hit_line = None
            builder_chain = None
            if isinstance(node, ast.Call) and _is_jit_call(node):
                encl = list(_enclosing_funcs(node))
                if not encl:
                    continue          # module-level jit: compiled once
                if _stored_in_subscript(node):
                    continue
                builder_chain = encl
                hit_line = node.lineno
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                jit_deco = next(
                    (d for d in node.decorator_list
                     if _jit_static_args(d)[0]), None)
                if jit_deco is None:
                    continue
                encl = list(_enclosing_funcs(node))
                if not encl:
                    continue          # module-level decorated def
                builder_chain = encl
                hit_line = node.lineno
            if hit_line is None:
                continue
            sym = qualname_of(node if isinstance(node, ast.FunctionDef)
                              else builder_chain[0])
            if cached_builders is None:
                cached_builders = _collect_cached_builders(project)
            if any(_has_memo_idiom(fn) for fn in builder_chain) or any(
                    fn.name in cached_builders for fn in builder_chain):
                # memoised somewhere: in a keyed table, or on an object
                # that the next write replaces?
                if table_builders is None:
                    table_builders = _collect_cached_builders(
                        project, tables_only=True)
                if any(_has_memo_idiom(fn, tables) for fn in builder_chain) \
                        or any(fn.name in table_builders
                               for fn in builder_chain):
                    continue
                findings.append(Finding(
                    rule="MG008", path=rel, line=hit_line,
                    col=getattr(node, "col_offset", 0), symbol=sym,
                    message="jax.jit memoised only on an object (getattr/"
                            "object.__setattr__ or a dict hanging off "
                            "it): every new object — a graph snapshot "
                            "after a write — re-traces and lowers the "
                            "program; look the jitted fn up in a "
                            "module-level table keyed by what is static "
                            "and pass the arrays as arguments",
                    fingerprint=f"jit-per-object@{sym}"))
                continue
            findings.append(Finding(
                rule="MG008", path=rel, line=hit_line,
                col=getattr(node, "col_offset", 0), symbol=sym,
                message="jax.jit applied per call (fresh closure each "
                        "invocation defeats jit's identity-keyed cache: "
                        "silent retrace + recompile every call) — store "
                        "the jitted fn in a keyed cache",
                fingerprint=f"jit-per-call@{sym}"))

        # --- traced-branch + unhashable-static over jit roots ---------
        scan = _ModuleScan(sf)
        for name, static in sorted(scan.jit_roots.items()):
            fn = scan.funcs.get(name)
            if fn is None:
                continue
            traced = _traced_params(fn, static)
            findings.extend(_traced_branches(rel, fn, name, traced))
            findings.extend(_unhashable_statics(rel, fn, name, static))
    return findings


def _branch_names(test: ast.AST, traced: set[str]) -> set[str]:
    """Traced params referenced as bare Names in a branch test —
    excluding structural uses (None checks, .shape/.dtype attributes,
    isinstance/len) that are static at trace time."""
    bad: set[str] = set()
    for node in ast.walk(test):
        if isinstance(node, ast.Compare):
            operands = [node.left] + list(node.comparators)
            if any(isinstance(o, ast.Constant) and o.value is None
                   for o in operands):
                continue  # `x is None`: pytree structure, not a value
        if not isinstance(node, ast.Name) or node.id not in traced:
            continue
        parent = getattr(node, "_mglint_parent", None)
        if isinstance(parent, ast.Attribute):
            continue      # x.shape / x.ndim / x.dtype — static
        if isinstance(parent, ast.Call) and parent.func is not node:
            callee = (dotted(parent.func) or "").split(".")[-1]
            if callee in ("isinstance", "len", "getattr", "hasattr"):
                continue
        if isinstance(parent, ast.Compare):
            operands = [parent.left] + list(parent.comparators)
            if any(isinstance(o, ast.Constant) and o.value is None
                   for o in operands):
                continue
        bad.add(node.id)
    return bad


def _traced_branches(rel, fn, name, traced):
    for node in ast.walk(fn):
        if isinstance(node, (ast.If, ast.While, ast.IfExp)):
            test = node.test
        else:
            continue
        bad = _branch_names(test, traced)
        if not bad:
            continue
        which = ", ".join(sorted(bad))
        yield Finding(
            rule="MG008", path=rel, line=node.lineno,
            col=getattr(node, "col_offset", 0), symbol=name,
            message=f"Python branch on traced argument(s) {which} of "
                    f"jitted {name} — concretization error at trace "
                    "time, or one compiled program per value if made "
                    "static; use lax.cond/jnp.where",
            fingerprint=f"traced-branch:{which}@{name}")


def _unhashable_statics(rel, fn, name, static):
    args = fn.args
    defaults = dict(zip([a.arg for a in args.args[::-1]],
                        list(args.defaults)[::-1]))
    kw_defaults = {a.arg: d for a, d in zip(args.kwonlyargs,
                                            args.kw_defaults) if d}
    defaults.update(kw_defaults)
    for pname in sorted(static):
        default = defaults.get(pname)
        if default is not None and isinstance(default, _MUTABLE_DEFAULTS):
            yield Finding(
                rule="MG008", path=rel, line=default.lineno,
                col=getattr(default, "col_offset", 0), symbol=name,
                message=f"static argument {pname!r} of jitted {name} "
                        "defaults to an unhashable mutable literal — "
                        "static args must be hashable (and stable, or "
                        "every distinct value compiles its own program)",
                fingerprint=f"unhashable-static:{pname}@{name}")
