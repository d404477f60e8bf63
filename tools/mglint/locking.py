"""Shared static lock model for MG001 (lock order) and MG002 (blocking
under lock).

Pass 1 finds every lock *creation* site — ``self.X = threading.Lock()``
(also RLock/Condition and the project's ``tracked_lock(...)`` wrappers)
inside a class body, or a module-level assignment — and gives each lock
a stable identity: ``Class.attr`` or ``module.py:NAME``.

Pass 2 walks every function with an explicit held-lock stack: a
``with <lock>:`` pushes, leaving the block pops. Everything observed
while the stack is non-empty (nested acquisitions, calls) is recorded.
Call targets are resolved conservatively — same-module functions,
``self.method`` in the same class, and methods whose name is unique
across the whole project; anything ambiguous is dropped rather than
guessed, so the graph under-approximates but never invents an edge.

A fixpoint then computes each function's *may-acquire* set (locks it or
any resolved callee can take) and *blocking-ops* set (fsync, socket
I/O, sleep, subprocess). MG001 turns held->acquired pairs into a
digraph and reports strongly-connected components; MG002 reports
blocking operations reachable while a storage/replication/server lock
is held.

Attribute receivers other than ``self`` resolve only when the attribute
name has exactly one creating class project-wide; otherwise the lock is
*anonymous* — it still counts as "a lock is held" for MG002 but never
contributes identity edges to MG001.
"""

from __future__ import annotations

import ast
import re
from dataclasses import dataclass, field

from .core import Project, SourceFile

LOCKISH_ATTR = re.compile(r"(?:^|_)(lock|cond|mutex|sem)", re.I)

_LOCK_CTOR_ATTRS = {"Lock", "RLock", "Condition", "Semaphore",
                    "BoundedSemaphore"}
_TRACKED_CTORS = {"tracked_lock", "tracked_rlock", "tracked_condition",
                  "TrackedLock"}

# call patterns that block the calling thread (syscalls / sleeps)
_BLOCKING_DOTTED = {
    "os.fsync": "fsync", "os.replace": "rename", "os.rename": "rename",
    "time.sleep": "sleep",
    "subprocess.run": "subprocess", "subprocess.call": "subprocess",
    "subprocess.Popen": "subprocess",
    "subprocess.check_call": "subprocess",
    "subprocess.check_output": "subprocess",
    "socket.create_connection": "socket connect",
    # device-plane dispatches (r12): a device call made while holding a
    # storage/server lock is EXACTLY the wedge class the kernel-server
    # supervision exists to contain — a hung runtime or lost chip stalls
    # every thread queued behind that lock
    "jax.device_put": "device dispatch (device_put)",
    "jax.block_until_ready": "device sync (block_until_ready)",
}
_BLOCKING_METHODS = {
    "sendall": "socket send", "sendto": "socket send",
    "recv": "socket recv", "recv_into": "socket recv",
    "accept": "socket accept", "makefile": "socket I/O",
    "fsync": "fsync",
    # project replication protocol helpers (replication/protocol.py)
    "send_json": "socket send", "send_frame": "socket send",
    "recv_frame": "socket recv",
    # device dispatch / sync entry points reachable as methods
    "block_until_ready": "device sync (block_until_ready)",
    "to_device": "device dispatch (to_device)",
    "put_edge_blocks": "device dispatch (device_put)",
    "put_replicated": "device dispatch (device_put)",
    "device_fault_point": "device dispatch (fault boundary)",
}
_BLOCKING_NAMES = {"open": "file open", "sleep": "sleep",
                   # kernel-server protocol helpers
                   # (server/kernel_server.py framing)
                   "_send_msg": "kernel-server send",
                   "_recv_msg": "kernel-server recv",
                   "device_fault_point": "device dispatch "
                                         "(fault boundary)"}

#: subsystems whose locks sit on commit / session critical paths
CRITICAL_DIRS = ("storage", "replication", "server", "coordination")

#: container methods that MUTATE their receiver — `self.shared.append(x)`
#: counts as a write to the shared field for MG006/MG007
_MUTATOR_METHODS = frozenset({
    "append", "appendleft", "add", "remove", "discard", "pop", "popleft",
    "popitem", "clear", "update", "setdefault", "extend", "insert",
    "sort",
})

#: method names that shadow stdlib container/file/thread APIs — never
#: resolved by project-wide uniqueness (a `cache.values()` must not
#: resolve to some class's `values`); `self.x()` still resolves exactly.
_COMMON_METHODS = frozenset({
    "flush", "clear", "values", "keys", "items", "get", "put", "pop",
    "append", "appendleft", "add", "remove", "close", "write", "read",
    "start", "stop", "join", "send", "update", "copy", "count",
    "index", "sort", "extend", "insert", "discard", "popleft", "popitem",
    "release", "set", "wait", "notify", "notify_all", "open", "next",
    "submit", "map", "result", "acquire", "run", "readline", "seek",
    "tell", "name", "encode", "decode", "strip", "split", "format",
    "setdefault", "union", "difference", "intersection", "shutdown",
    "cancel", "done", "exception", "warning", "error", "info", "debug",
})


def dotted(node: ast.AST) -> str | None:
    """'a.b.c' for a Name/Attribute chain, else None."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _is_lock_ctor(call: ast.Call) -> str | None:
    """'plain'/'rlock'/'tracked' when `call` creates a lock, else None."""
    fn = call.func
    if isinstance(fn, ast.Attribute) and fn.attr in _LOCK_CTOR_ATTRS:
        base = dotted(fn.value)
        if base and base.split(".")[-1] == "threading":
            return "rlock" if fn.attr == "RLock" else "plain"
    name = fn.attr if isinstance(fn, ast.Attribute) else (
        fn.id if isinstance(fn, ast.Name) else None)
    if name in _TRACKED_CTORS:
        return "rlock" if name == "tracked_rlock" else "tracked"
    return None


@dataclass
class LockDef:
    lock_id: str
    kind: str              # plain | rlock | tracked
    rel_path: str
    line: int


@dataclass
class Acquisition:
    lock_id: str | None    # None = anonymous (lock-ish but unresolved)
    attr: str              # source-level name, for messages
    line: int
    col: int


@dataclass
class CallSite:
    target: str | None     # resolved function key, or None
    text: str              # rendered call, for messages
    line: int
    col: int


@dataclass
class HeldEvent:
    """Something that happened while >= 1 lock was held."""
    held: tuple[Acquisition, ...]
    acquisition: Acquisition | None = None
    call: CallSite | None = None
    blocking: tuple[str, CallSite] | None = None   # (op label, site)


@dataclass
class FieldAccess:
    """One syntactic access to a declared shared_field, with the lock
    regions held at that point. `held` snapshots the live Acquisition
    objects — two accesses are atomic w.r.t. each other iff they share
    one (identity-compared) acquisition, i.e. sit in the SAME `with`
    region, not merely under the same lock name."""
    cls: str               # declaring class ("Metrics")
    fname: str             # field name ("_counters")
    kind: str              # "r" | "w"
    line: int
    col: int
    held: tuple[Acquisition, ...]
    in_return: bool = False   # load consumed by a `return` statement


@dataclass
class FuncInfo:
    key: str               # "<rel_path>::<qualname>"
    rel_path: str
    qualname: str
    class_name: str | None
    node: ast.AST
    acquisitions: list[Acquisition] = field(default_factory=list)
    calls: list[CallSite] = field(default_factory=list)
    events: list[HeldEvent] = field(default_factory=list)
    direct_blocking: list[tuple[str, CallSite]] = field(
        default_factory=list)
    shared_accesses: list[FieldAccess] = field(default_factory=list)
    # fixpoint results
    may_acquire: set[str] = field(default_factory=set)
    may_block: dict[str, str] = field(default_factory=dict)  # op -> via


def get_model(project: Project) -> "LockModel":
    """The project's LockModel, built exactly ONCE and shared by every
    rule that needs lock regions / call resolution (MG001, MG002, MG006,
    MG007). The model walk dominates mglint runtime, so the single-pass
    driver keeps the tier-1 gate flat as rules accumulate."""
    model = getattr(project, "_mglint_lock_model", None)
    if model is None:
        model = LockModel(project)
        project._mglint_lock_model = model
    return model


class LockModel:
    def __init__(self, project: Project):
        self.project = project
        self.defs: dict[str, LockDef] = {}
        # attr name -> set of owning class names (for unique resolution)
        self._attr_owners: dict[str, set[str]] = {}
        self._module_locks: dict[tuple[str, str], str] = {}
        self.functions: dict[str, FuncInfo] = {}
        self._module_funcs: dict[tuple[str, str], str] = {}
        self._methods: dict[str, list[str]] = {}   # name -> func keys
        # (rel, local name) -> module rel path  /  (module rel, symbol)
        self._mod_alias: dict[tuple[str, str], str] = {}
        self._sym_import: dict[tuple[str, str], tuple[str, str]] = {}
        # shared_field(self, "a", "b") declarations (MG006/MG007):
        # class -> declared fields / field -> declaring classes
        self.shared_decls: dict[str, set[str]] = {}
        self.shared_owners: dict[str, set[str]] = {}
        self._class_bases: dict[str, set[str]] = {}
        self._collect_definitions()
        self._collect_imports()
        self._collect_functions()
        self._fixpoint()

    # --- import resolution ------------------------------------------------

    def _module_file(self, parts: list[str]) -> str | None:
        if not parts or not all(parts):
            return None
        base = "/".join(parts)
        for cand in (f"{base}.py", f"{base}/__init__.py"):
            if cand in self.project.files:
                return cand
        return None

    def _collect_imports(self) -> None:
        for rel, sf in self.project.files.items():
            pkg = rel.split("/")[:-1]
            for node in ast.walk(sf.tree):
                if isinstance(node, ast.ImportFrom):
                    if node.level:
                        if node.level - 1 > len(pkg):
                            continue
                        base = pkg[:len(pkg) - (node.level - 1)]
                        base += node.module.split(".") if node.module \
                            else []
                    else:
                        base = node.module.split(".") if node.module \
                            else []
                    mod_file = self._module_file(base)
                    for a in node.names:
                        if a.name == "*":
                            continue
                        local = a.asname or a.name
                        sub = self._module_file(base + [a.name])
                        if sub is not None:
                            self._mod_alias[(rel, local)] = sub
                        elif mod_file is not None:
                            self._sym_import[(rel, local)] = (mod_file,
                                                              a.name)
                elif isinstance(node, ast.Import):
                    for a in node.names:
                        mod_file = self._module_file(a.name.split("."))
                        if mod_file is not None:
                            local = a.asname or a.name.split(".")[0]
                            self._mod_alias[(rel, local)] = mod_file

    # --- pass 1: lock creation sites ------------------------------------

    def _collect_definitions(self) -> None:
        for rel, sf in self.project.files.items():
            for node in ast.walk(sf.tree):
                if not isinstance(node, ast.ClassDef):
                    continue
                self._class_bases.setdefault(node.name, set()).update(
                    b.id if isinstance(b, ast.Name) else b.attr
                    for b in node.bases
                    if isinstance(b, (ast.Name, ast.Attribute)))
                for sub in ast.walk(node):
                    if (isinstance(sub, ast.Call)
                            and self._is_shared_decl(sub)):
                        fields = {a.value for a in sub.args[1:]
                                  if isinstance(a, ast.Constant)
                                  and isinstance(a.value, str)}
                        if fields:
                            self.shared_decls.setdefault(
                                node.name, set()).update(fields)
                            for f in fields:
                                self.shared_owners.setdefault(
                                    f, set()).add(node.name)
                    if not (isinstance(sub, ast.Assign)
                            and isinstance(sub.value, ast.Call)):
                        continue
                    kind = _is_lock_ctor(sub.value)
                    if kind is None:
                        continue
                    for tgt in sub.targets:
                        if (isinstance(tgt, ast.Attribute)
                                and isinstance(tgt.value, ast.Name)
                                and tgt.value.id == "self"):
                            lock_id = f"{node.name}.{tgt.attr}"
                            self.defs.setdefault(lock_id, LockDef(
                                lock_id, kind, rel, sub.lineno))
                            self._attr_owners.setdefault(
                                tgt.attr, set()).add(node.name)
            # module-level locks
            for stmt in sf.tree.body:
                if (isinstance(stmt, ast.Assign)
                        and isinstance(stmt.value, ast.Call)):
                    kind = _is_lock_ctor(stmt.value)
                    if kind is None:
                        continue
                    for tgt in stmt.targets:
                        if isinstance(tgt, ast.Name):
                            mod = rel.rsplit("/", 1)[-1]
                            lock_id = f"{mod}:{tgt.id}"
                            self.defs.setdefault(lock_id, LockDef(
                                lock_id, kind, rel, stmt.lineno))
                            self._module_locks[(rel, tgt.id)] = lock_id

    @staticmethod
    def _is_shared_decl(call: ast.Call) -> bool:
        """True for `shared_field(<owner>, "f", ...)` calls (any import
        spelling: bare name or `sanitize.shared_field`)."""
        fn = call.func
        name = fn.attr if isinstance(fn, ast.Attribute) else (
            fn.id if isinstance(fn, ast.Name) else None)
        return name == "shared_field" and len(call.args) >= 2

    # --- shared-field access resolution (MG006/MG007) --------------------

    def _inherits(self, cls: str, owner: str) -> bool:
        seen, frontier = set(), {cls}
        while frontier:
            cur = frontier.pop()
            if cur == owner:
                return True
            if cur in seen:
                continue
            seen.add(cur)
            frontier |= self._class_bases.get(cur, set())
        return False

    def resolve_shared_owner(self, node: ast.Attribute,
                             fi: FuncInfo) -> str | None:
        """Declaring class for an `X.field` access, or None.

        `self.field` resolves through the enclosing class (including
        inherited declarations); any other receiver resolves only when
        exactly ONE class project-wide declares that field name —
        ambiguity is dropped, never guessed, mirroring resolve_lock."""
        owners = self.shared_owners.get(node.attr)
        if not owners:
            return None
        recv = node.value
        if isinstance(recv, ast.Name) and recv.id == "self":
            if not fi.class_name:
                return None
            if fi.class_name in owners:
                return fi.class_name
            for owner in owners:
                if self._inherits(fi.class_name, owner):
                    return owner
            return None
        if len(owners) == 1:
            return next(iter(owners))
        return None

    def is_constructor_of(self, fi: FuncInfo, owner: str) -> bool:
        """True when `fi` is __init__/__post_init__ of the declaring
        class (or a subclass): the object is thread-local during
        construction, so unguarded field setup there is not a race."""
        short = fi.qualname.rsplit(".", 1)[-1]
        if short not in ("__init__", "__post_init__"):
            return False
        cls = fi.class_name
        return cls is not None and (cls == owner
                                    or self._inherits(cls, owner))

    @staticmethod
    def _access_kind(node: ast.Attribute) -> str:
        """'w' for stores, subscript-stores (`x.f[k] = v`) and mutating
        method calls (`x.f.append(v)`); 'r' otherwise."""
        if isinstance(node.ctx, (ast.Store, ast.Del)):
            return "w"
        parent = getattr(node, "_mglint_parent", None)
        if (isinstance(parent, ast.Subscript) and parent.value is node
                and isinstance(parent.ctx, (ast.Store, ast.Del))):
            return "w"
        if (isinstance(parent, ast.Attribute) and parent.value is node
                and parent.attr in _MUTATOR_METHODS):
            grand = getattr(parent, "_mglint_parent", None)
            if isinstance(grand, ast.Call) and grand.func is parent:
                return "w"
        return "r"

    @staticmethod
    def _in_return(node: ast.AST) -> bool:
        """True when the access sits inside a `return` expression: the
        function exits with it, so it cannot be the "check" half of a
        check-then-act within this function (MG007)."""
        cur = getattr(node, "_mglint_parent", None)
        while cur is not None and isinstance(cur, ast.expr):
            cur = getattr(cur, "_mglint_parent", None)
        return isinstance(cur, ast.Return)

    # --- lock expression resolution -------------------------------------

    def resolve_lock(self, expr: ast.AST, rel: str,
                     cls: str | None) -> tuple[str | None, str] | None:
        """(lock_id | None, display name) when `expr` looks like a lock;
        None when it clearly is not one."""
        if isinstance(expr, ast.Attribute):
            attr = expr.attr
            owners = self._attr_owners.get(attr, set())
            if (isinstance(expr.value, ast.Name)
                    and expr.value.id == "self" and cls):
                if cls in owners:
                    return f"{cls}.{attr}", f"self.{attr}"
            if len(owners) == 1:
                owner = next(iter(owners))
                return f"{owner}.{attr}", dotted(expr) or attr
            if owners or LOCKISH_ATTR.search(attr):
                return None, dotted(expr) or attr   # anonymous lock
            return None
        if isinstance(expr, ast.Name):
            lock_id = self._module_locks.get((rel, expr.id))
            if lock_id:
                return lock_id, expr.id
            if LOCKISH_ATTR.search(expr.id):
                return None, expr.id
        return None

    # --- pass 2: function walks -----------------------------------------

    def _collect_functions(self) -> None:
        # phase A: register every function so calls resolve project-wide
        for rel, sf in self.project.files.items():
            self._register_scope(sf, sf.tree.body, qual="", cls=None)
        for key, fi in self.functions.items():
            short = fi.qualname.rsplit(".", 1)[-1]
            if fi.class_name:
                self._methods.setdefault(short, []).append(key)
            else:
                self._module_funcs[(fi.rel_path, short)] = key
        # phase B: walk bodies (resolution indexes are now complete);
        # parent links are needed for shared-field access kinds and are
        # attached exactly once per file (shared with MG003 et al.)
        if self.shared_owners:
            for sf in self.project.files.values():
                sf.ensure_parents()
        for fi in self.functions.values():
            sf = self.project.files[fi.rel_path]
            self._walk_function(sf, fi, fi.node.body, held=[])

    def _register_scope(self, sf: SourceFile, body, qual: str,
                        cls: str | None) -> None:
        for stmt in body:
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
                q = f"{qual}.{stmt.name}" if qual else stmt.name
                fi = FuncInfo(key=f"{sf.rel_path}::{q}",
                              rel_path=sf.rel_path, qualname=q,
                              class_name=cls, node=stmt)
                self.functions[fi.key] = fi
                # nested defs become their own FuncInfo
                self._register_scope(sf, stmt.body, qual=q, cls=cls)
            elif isinstance(stmt, ast.ClassDef):
                q = f"{qual}.{stmt.name}" if qual else stmt.name
                self._register_scope(sf, stmt.body, qual=q,
                                     cls=stmt.name)

    def _walk_function(self, sf: SourceFile, fi: FuncInfo, body,
                       held: list[Acquisition]) -> None:
        """Statement-level walk with an explicit held-lock stack. Nested
        compound statements (if/for/while/try/match) recurse with the
        same stack; `with <lock>:` pushes for the extent of its body."""
        for stmt in body:
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef)):
                continue   # deferred execution: separate scope
            if isinstance(stmt, (ast.With, ast.AsyncWith)):
                pushed = 0
                for item in stmt.items:
                    got = self.resolve_lock(item.context_expr,
                                            sf.rel_path, fi.class_name)
                    if got is None:
                        self._scan_expr(sf, fi, item.context_expr, held)
                        continue
                    lock_id, name = got
                    acq = Acquisition(lock_id, name,
                                      item.context_expr.lineno,
                                      item.context_expr.col_offset)
                    fi.acquisitions.append(acq)
                    if held:
                        fi.events.append(HeldEvent(tuple(held),
                                                   acquisition=acq))
                    held.append(acq)
                    pushed += 1
                self._walk_function(sf, fi, stmt.body, held)
                if pushed:
                    del held[-pushed:]
                continue
            for _name, value in ast.iter_fields(stmt):
                if isinstance(value, ast.expr):
                    self._scan_expr(sf, fi, value, held)
                elif isinstance(value, list):
                    for v in value:
                        if isinstance(v, ast.expr):
                            self._scan_expr(sf, fi, v, held)
                        elif isinstance(v, ast.ExceptHandler):
                            if v.type is not None:
                                self._scan_expr(sf, fi, v.type, held)
                            self._walk_function(sf, fi, v.body, held)
                        elif isinstance(v, ast.stmt):
                            self._walk_function(sf, fi, [v], held)
                        elif hasattr(v, "body") and \
                                isinstance(getattr(v, "body"), list):
                            # match_case and friends
                            self._walk_function(sf, fi, v.body, held)

    def _scan_expr(self, sf: SourceFile, fi: FuncInfo, expr: ast.AST,
                   held: list[Acquisition]) -> None:
        """Visit every Call inside an expression (lambda bodies are
        deferred execution and skipped)."""
        stack = [expr]
        while stack:
            node = stack.pop()
            if isinstance(node, ast.Lambda):
                continue
            if isinstance(node, ast.Call):
                self._visit_call(sf, fi, node, held)
            elif (isinstance(node, ast.Attribute)
                    and node.attr in self.shared_owners):
                owner = self.resolve_shared_owner(node, fi)
                if owner is not None:
                    fi.shared_accesses.append(FieldAccess(
                        owner, node.attr, self._access_kind(node),
                        node.lineno, node.col_offset, tuple(held),
                        in_return=self._in_return(node)))
            stack.extend(ast.iter_child_nodes(node))

    def _visit_call(self, sf: SourceFile, fi: FuncInfo, call: ast.Call,
                    held: list[Acquisition]) -> None:
        name = dotted(call.func)
        site = CallSite(None, name or "<call>", call.lineno,
                        call.col_offset)
        # .acquire() is an acquisition event
        if (isinstance(call.func, ast.Attribute)
                and call.func.attr == "acquire"):
            got = self.resolve_lock(call.func.value, sf.rel_path,
                                    fi.class_name)
            if got is not None:
                acq = Acquisition(got[0], got[1], call.lineno,
                                  call.col_offset)
                fi.acquisitions.append(acq)
                if held:
                    fi.events.append(HeldEvent(tuple(held),
                                               acquisition=acq))
            return
        # blocking classification
        op = None
        if name in _BLOCKING_DOTTED:
            op = _BLOCKING_DOTTED[name]
        elif (isinstance(call.func, ast.Attribute)
                and call.func.attr in _BLOCKING_METHODS):
            op = _BLOCKING_METHODS[call.func.attr]
        elif (isinstance(call.func, ast.Name)
                and call.func.id in _BLOCKING_NAMES):
            op = _BLOCKING_NAMES[call.func.id]
        if op is not None:
            entry = (op, site)
            fi.direct_blocking.append(entry)
            if held:
                fi.events.append(HeldEvent(tuple(held), blocking=entry))
            return
        # plain call: resolve for the graph
        site.target = self._resolve_call(call, sf.rel_path, fi.class_name)
        fi.calls.append(site)
        if held:
            fi.events.append(HeldEvent(tuple(held), call=site))

    def _resolve_call(self, call: ast.Call, rel: str,
                      cls: str | None) -> str | None:
        fn = call.func
        if isinstance(fn, ast.Name):
            local = self._module_funcs.get((rel, fn.id))
            if local is not None:
                return local
            # imported symbol: from mod import f
            target = self._sym_import.get((rel, fn.id))
            if target is not None:
                return self._module_funcs.get(target)
            return None
        if isinstance(fn, ast.Attribute):
            short = fn.attr
            if isinstance(fn.value, ast.Name):
                base = fn.value.id
                if base == "self" and cls:
                    for key in self._methods.get(short, ()):
                        fi = self.functions[key]
                        if fi.class_name == cls and fi.rel_path == rel:
                            return key
                # module alias: pr.pagerank() -> ops/pagerank.py::pagerank
                mod = self._mod_alias.get((rel, base))
                if mod is not None:
                    return self._module_funcs.get((mod, short))
                # imported class: Cls.method() (also covers Cls()
                # instances only when unique-name resolution hits below)
                sym = self._sym_import.get((rel, base))
                if sym is not None:
                    key = f"{sym[0]}::{sym[1]}.{short}"
                    if key in self.functions:
                        return key
            if short in _COMMON_METHODS:
                return None
            candidates = self._methods.get(short, ())
            if len(candidates) == 1:
                return candidates[0]
        return None

    # --- fixpoint summaries ----------------------------------------------

    def _fixpoint(self) -> None:
        for fi in self.functions.values():
            fi.may_acquire = {a.lock_id for a in fi.acquisitions
                              if a.lock_id}
            fi.may_block = {op: op for op, _ in fi.direct_blocking}
        changed = True
        while changed:
            changed = False
            for fi in self.functions.values():
                for site in fi.calls:
                    if site.target is None:
                        continue
                    callee = self.functions.get(site.target)
                    if callee is None:
                        continue
                    new_locks = callee.may_acquire - fi.may_acquire
                    if new_locks:
                        fi.may_acquire |= new_locks
                        changed = True
                    for op in callee.may_block:
                        if op not in fi.may_block:
                            fi.may_block[op] = \
                                f"via {callee.qualname}: " \
                                f"{callee.may_block[op]}" \
                                if not callee.may_block[op].startswith(
                                    "via ") else callee.may_block[op]
                            changed = True

    # --- helpers for the rules -------------------------------------------

    def callee(self, site: CallSite) -> FuncInfo | None:
        return self.functions.get(site.target) if site.target else None

    def is_rlock(self, lock_id: str) -> bool:
        d = self.defs.get(lock_id)
        return d is not None and d.kind == "rlock"
