"""MG005 — registry-coverage: every WAL opcode and fault point is fully
wired.

WAL opcodes (``OP_* = 0x..`` in storage/durability/wal.py) need four
handlers to round-trip a commit through crash recovery AND replication:

  * encode   — referenced in wal.py outside its own assignment
               (framed by encode_txn_ops / the txn grouping protocol)
  * replay   — referenced in storage/durability/recovery.py
               (``_apply_wal_txn``), or handled by wal.py's own
               ``_group_txns`` protocol layer (TXN_BEGIN / TXN_END)
  * replication-apply — replication/replica.py must import the shared
               applier ``_apply_wal_txn`` (one applier for recovery and
               replicas is the invariant; a replica-side fork would
               have to re-handle every opcode)

A new opcode with a missing replay arm recovers to silent data loss;
the reference enforces this with exhaustive switch statements the
compiler checks — this rule is the Python stand-in.

Fault points: every ``fire("x")`` / ``faulty_write("x", ...)`` site
must name a point registered in utils/faultinject.py KNOWN_POINTS (a
typo'd point silently never fires), and every registered point must
have at least one live fire site (a dead registration means a fault
campaign "covers" a path that no longer exists).

Nemesis ops: the ``NEMESIS_OPS`` registry (the contract the mgchaos
schedule generator draws from) must stay wired both ways — every
network-level op needs a live ``net_<op>`` installer in faultinject.py,
and every installer (a ``net_*`` function that adds link rules) must be
reachable from a registered op, or chaos campaigns "cover" ops that can
no longer fire (the same dead-registration hazard as fault points; the
per-op *test* coverage half of this contract lives in
tests/test_chaos.py, which asserts the seeded sweep exercises every
registered op).

Span names (r13, mgtrace): every literal span name opened in product
code — ``span("x")`` / ``record_span("x", ...)`` / ``begin_trace("x")``
— must be declared in observability/trace.py ``SPAN_NAMES``, and so
must every key of its ``PHASES`` mark (a typo'd
name silently fragments a trace), and every declared name must have at
least one live open site. Spans may ONLY be opened through that
context-manager API: any call to the private ``_begin_span``/
``_end_span`` primitives outside trace.py is a manual begin/end
imbalance waiting to happen and is flagged outright.
"""

from __future__ import annotations

import ast

from ..core import Finding, Project
from ..locking import dotted
from ..registry import register


def _op_constants(sf) -> dict[str, int]:
    out = {}
    for stmt in sf.tree.body:
        if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1 \
                and isinstance(stmt.targets[0], ast.Name) \
                and stmt.targets[0].id.startswith("OP_") \
                and isinstance(stmt.value, ast.Constant):
            out[stmt.targets[0].id] = (stmt.value.value,
                                       stmt.lineno)
    return out


def _names_used(tree: ast.AST) -> set[str]:
    used = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Name) and \
                isinstance(node.ctx, ast.Load):
            used.add(node.id)
        elif isinstance(node, ast.Attribute):
            used.add(node.attr)
    return used


def _names_in_function(tree: ast.AST, fn_name: str) -> set[str]:
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                and node.name == fn_name:
            return _names_used(node)
    return set()


@register("MG005", "registry-coverage")
def check(project: Project):
    """WAL opcodes and fault points must be fully wired end to end."""
    findings = []
    findings.extend(_check_wal_opcodes(project))
    findings.extend(_check_fault_points(project))
    findings.extend(_check_nemesis_ops(project))
    findings.extend(_check_device_nemesis_ops(project))
    findings.extend(_check_spmv_registry(project))
    findings.extend(_check_span_registry(project))
    findings.extend(_check_stat_registry(project))
    return findings


def _check_wal_opcodes(project: Project):
    wal = project.by_suffix("durability/wal.py")
    if wal is None:
        return []
    recovery = project.by_suffix("durability/recovery.py")
    replica = project.by_suffix("replication/replica.py")
    ops = _op_constants(wal)
    if not ops:
        return []

    # encode side: any use in wal.py beyond the defining assignment
    wal_uses: dict[str, int] = {}
    for node in ast.walk(wal.tree):
        if isinstance(node, ast.Name) and \
                isinstance(node.ctx, ast.Load) and \
                node.id.startswith("OP_"):
            wal_uses[node.id] = wal_uses.get(node.id, 0) + 1
    group_txn_names = _names_in_function(wal.tree, "_group_txns")
    recovery_names = _names_used(recovery.tree) \
        if recovery is not None else set()

    replica_shares_applier = False
    if replica is not None:
        for node in ast.walk(replica.tree):
            if isinstance(node, ast.ImportFrom) and node.module and \
                    "recovery" in node.module:
                if any(a.name == "_apply_wal_txn" for a in node.names):
                    replica_shares_applier = True
    replica_names = _names_used(replica.tree) \
        if replica is not None else set()

    findings = []
    for op_name, (_value, line) in sorted(ops.items()):
        missing = []
        if not wal_uses.get(op_name):
            missing.append("encode (never framed in wal.py)")
        replayed = op_name in recovery_names or \
            op_name in group_txn_names
        if not replayed:
            missing.append("recovery replay (no handler in "
                           "recovery.py/_group_txns)")
        repl_ok = replica_shares_applier or op_name in replica_names \
            or op_name in group_txn_names
        if not repl_ok:
            missing.append("replication apply (replica.py neither "
                           "imports _apply_wal_txn nor handles it)")
        if missing:
            findings.append(Finding(
                rule="MG005", path=wal.rel_path, line=line, col=0,
                symbol=op_name,
                message=f"WAL opcode {op_name} is missing handlers: "
                        + "; ".join(missing),
                fingerprint=f"wal-op:{op_name}"))
    return findings


#: ops the cluster harness (not the network model) implements; they have
#: no net_* installer by design (node churn, the r18 shard-plane ops,
#: and the r17 stream-consumer op drive ChaosCluster / ShardPlane /
#: StreamChaosHarness hooks directly)
_CLUSTER_LEVEL_OPS = {"kill_restart", "shard_move", "shard_worker_kill",
                      "stream_consumer_kill"}


def _nemesis_op_installer(op: str) -> str:
    """Registered op name -> the net_* installer expected to back it
    ("partition_oneway" rides net_partition's bidirectional flag)."""
    if op == "partition_oneway":
        return "net_partition"
    return f"net_{op}"


def _check_nemesis_ops(project: Project):
    fi_mod = project.by_suffix("utils/faultinject.py")
    if fi_mod is None:
        return []
    ops: dict[str, int] = {}
    for stmt in fi_mod.tree.body:
        if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1 \
                and isinstance(stmt.targets[0], ast.Name) \
                and stmt.targets[0].id == "NEMESIS_OPS" \
                and isinstance(stmt.value, (ast.Tuple, ast.List)):
            for el in stmt.value.elts:
                if isinstance(el, ast.Constant) and \
                        isinstance(el.value, str):
                    ops[el.value] = stmt.lineno
    if not ops:
        return []

    # net_* installers = module-level functions whose body calls _net_add
    installers: dict[str, int] = {}
    for stmt in fi_mod.tree.body:
        if not isinstance(stmt, ast.FunctionDef) or \
                not stmt.name.startswith("net_"):
            continue
        for node in ast.walk(stmt):
            if isinstance(node, ast.Call) and \
                    isinstance(node.func, ast.Name) and \
                    node.func.id == "_net_add":
                installers[stmt.name] = stmt.lineno
                break

    findings = []
    for op, line in sorted(ops.items()):
        if op in _CLUSTER_LEVEL_OPS:
            continue
        wanted = _nemesis_op_installer(op)
        if wanted not in installers:
            findings.append(Finding(
                rule="MG005", path=fi_mod.rel_path, line=line, col=0,
                symbol="NEMESIS_OPS",
                message=f"nemesis op {op!r} has no {wanted}() installer "
                        "— scheduling it would be a silent no-op",
                fingerprint=f"nemesis-dead:{op}"))
    expected = {_nemesis_op_installer(op) for op in ops
                if op not in _CLUSTER_LEVEL_OPS}
    for name, line in sorted(installers.items()):
        if name not in expected:
            findings.append(Finding(
                rule="MG005", path=fi_mod.rel_path, line=line, col=0,
                symbol=name,
                message=f"link-rule installer {name}() backs no entry "
                        "of NEMESIS_OPS — chaos campaigns can never "
                        "schedule it",
                fingerprint=f"nemesis-unregistered:{name}"))
    return findings


def _collect_tuple_registry(fi_mod, name: str) -> dict[str, int]:
    """{literal: lineno} for a module-level tuple/list-of-str registry."""
    out: dict[str, int] = {}
    for stmt in fi_mod.tree.body:
        if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1 \
                and isinstance(stmt.targets[0], ast.Name) \
                and stmt.targets[0].id == name \
                and isinstance(stmt.value, (ast.Tuple, ast.List)):
            for el in stmt.value.elts:
                if isinstance(el, ast.Constant) and \
                        isinstance(el.value, str):
                    out[el.value] = stmt.lineno
    return out


def _check_device_nemesis_ops(project: Project):
    """DEVICE_NEMESIS_OPS ↔ device.* fault-point wiring, both ways.

    Device nemesis ops arm SCALAR ``device.*`` points (there is no
    net_* installer — the fault is in the accelerator, not a link), so
    the contract is: every ``device_<x>`` op needs a registered
    ``device.<x>`` point in KNOWN_POINTS, and every ``device.*`` point
    must be reachable from a registered op — else chaos campaigns
    "cover" device faults that can never fire, or a device point exists
    the device sweep can never schedule. The fire-site half (every
    registered point needs a live fire() site) already rides
    ``_check_fault_points``; the dynamic half (the seeded device sweep
    exercises every op) lives in tests/test_device_resilience.py.
    """
    fi_mod = project.by_suffix("utils/faultinject.py")
    if fi_mod is None:
        return []
    ops = _collect_tuple_registry(fi_mod, "DEVICE_NEMESIS_OPS")
    known = _collect_tuple_registry(fi_mod, "KNOWN_POINTS")
    device_points = {p: ln for p, ln in known.items()
                     if p.startswith("device.")}
    if not ops and not device_points:
        return []

    def point_for(op: str) -> str:
        return "device." + op[len("device_"):]

    findings = []
    for op, line in sorted(ops.items()):
        if not op.startswith("device_"):
            findings.append(Finding(
                rule="MG005", path=fi_mod.rel_path, line=line, col=0,
                symbol="DEVICE_NEMESIS_OPS",
                message=f"device nemesis op {op!r} must be named "
                        "device_<point>",
                fingerprint=f"device-nemesis-misnamed:{op}"))
            continue
        if point_for(op) not in device_points:
            findings.append(Finding(
                rule="MG005", path=fi_mod.rel_path, line=line, col=0,
                symbol="DEVICE_NEMESIS_OPS",
                message=f"device nemesis op {op!r} has no registered "
                        f"fault point {point_for(op)!r} — scheduling it "
                        "would be a silent no-op",
                fingerprint=f"device-nemesis-dead:{op}"))
    backed = {point_for(op) for op in ops if op.startswith("device_")}
    for point, line in sorted(device_points.items()):
        if point not in backed:
            findings.append(Finding(
                rule="MG005", path=fi_mod.rel_path, line=line, col=0,
                symbol="KNOWN_POINTS",
                message=f"device fault point {point!r} backs no entry "
                        "of DEVICE_NEMESIS_OPS — device chaos "
                        "campaigns can never schedule it",
                fingerprint=f"device-point-unscheduled:{point}"))
    return findings


def _check_fault_points(project: Project):
    fi_mod = project.by_suffix("utils/faultinject.py")
    if fi_mod is None:
        return []
    known: dict[str, int] = {}
    for stmt in fi_mod.tree.body:
        if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1 \
                and isinstance(stmt.targets[0], ast.Name) \
                and stmt.targets[0].id == "KNOWN_POINTS" \
                and isinstance(stmt.value, (ast.Tuple, ast.List)):
            for el in stmt.value.elts:
                if isinstance(el, ast.Constant) and \
                        isinstance(el.value, str):
                    known[el.value] = stmt.lineno

    findings = []
    fired: set[str] = set()
    for rel, sf in project.files.items():
        if sf is fi_mod:
            continue
        for node in ast.walk(sf.tree):
            if not isinstance(node, ast.Call):
                continue
            name = dotted(node.func) or ""
            short = name.split(".")[-1]
            if short not in ("fire", "faulty_write"):
                continue
            if not node.args or not (
                    isinstance(node.args[0], ast.Constant)
                    and isinstance(node.args[0].value, str)):
                continue
            point = node.args[0].value
            fired.add(point)
            if known and point not in known:
                findings.append(Finding(
                    rule="MG005", path=rel, line=node.lineno,
                    col=node.col_offset, symbol=short,
                    message=f"fault point {point!r} is not registered "
                            "in faultinject.KNOWN_POINTS — arming it "
                            "is impossible and the site never fires",
                    fingerprint=f"fault-unregistered:{point}"))
    for point, line in sorted(known.items()):
        if point not in fired:
            findings.append(Finding(
                rule="MG005", path=fi_mod.rel_path, line=line, col=0,
                symbol="KNOWN_POINTS",
                message=f"registered fault point {point!r} has no "
                        "fire()/faulty_write() site — dead "
                        "registration, campaigns covering it test "
                        "nothing",
                fingerprint=f"fault-dead:{point}"))
    return findings


# --------------------------------------------------------------------------
# SpMV-algorithm semiring-core + mesh coverage (ops/__init__.py
# SPMV_ALGORITHMS)
# --------------------------------------------------------------------------
#
# The semiring kernel core (ops/semiring.py, r10) is only a win if every
# SpMV-shaped algorithm actually rides it. The contract:
#   * ops/__init__.py keeps a SPMV_ALGORITHMS registry; each entry names
#     its single-chip "entry" target, EXACTLY ONE of a "sharded" target
#     or a justified "exempt" string, and (when ops/semiring.py is in
#     the scanned tree) a "core" declaration — a SEMIRINGS key naming
#     the (⊕, ⊗) pair its inner loop iterates, or "blocks" for custom
#     rounds composed from the core's building blocks;
#   * every "module:function" target must statically resolve to a
#     function defined in a scanned file (a typo'd target would only
#     surface when a user requests a mesh);
#   * every ops/ module whose AST shows the SpMV shape (a segment_*
#     reduction AND a while_loop) OR that imports the semiring core
#     must be covered by some entry, so a new algorithm cannot silently
#     miss the mesh path; and
#   * NO ops/ module outside the core engine (semiring / spmv_* /
#     benes*) may contain a function that hand-rolls a direct
#     ``jax.ops.segment_*`` reduction inside a ``while_loop`` pipeline
#     ("spmv-handrolled") — residual hand-rolled kernels bypass the
#     core's backends, precision variants and stage attribution.

_SPMV_MIN_JUSTIFICATION = 40   # chars; "TODO" is not a justification

#: modules that ARE the shared engine (the registry's targets ride
#: them); they may use segment primitives directly
_SPMV_CORE_PREFIXES = ("semiring", "spmv_", "benes")


def _registry_dict(sf, name: str):
    for stmt in sf.tree.body:
        if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1 \
                and isinstance(stmt.targets[0], ast.Name) \
                and stmt.targets[0].id == name \
                and isinstance(stmt.value, ast.Dict):
            return stmt.value, stmt.lineno
    return None, 0


def _literal_or_none(node):
    try:
        return ast.literal_eval(node)
    except (ValueError, SyntaxError):
        return None


def _target_resolves(project: Project, target: str) -> bool:
    """Does 'pkg.mod:fn' point at a def in a scanned file?"""
    if ":" not in target:
        return False
    mod, fn = target.split(":", 1)
    sf = project.by_suffix(mod.replace(".", "/") + ".py")
    if sf is None:
        return False
    return any(isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
               and n.name == fn for n in sf.tree.body)


def _has_spmv_shape(sf) -> bool:
    has_segment = has_loop = False
    for node in ast.walk(sf.tree):
        if isinstance(node, ast.Call):
            name = (dotted(node.func) or "").split(".")[-1]
            if name.startswith("segment_"):
                has_segment = True
            elif name == "while_loop":
                has_loop = True
        if has_segment and has_loop:
            return True
    return False


def _imports_semiring_core(sf) -> bool:
    """Does this module import ops/semiring.py (ride the core)?"""
    for node in ast.walk(sf.tree):
        if isinstance(node, ast.ImportFrom):
            if node.module and node.module.split(".")[-1] == "semiring":
                return True
            if any(a.name == "semiring" for a in node.names):
                return True
        elif isinstance(node, ast.Import):
            if any(a.name.split(".")[-1] == "semiring"
                   for a in node.names):
                return True
    return False


def _handrolled_functions(sf):
    """Top-level functions containing BOTH a direct segment_* call and a
    while_loop call — a residual hand-rolled SpMV pipeline."""
    out = []
    for fn in sf.tree.body:
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        has_segment = has_loop = False
        for node in ast.walk(fn):
            if isinstance(node, ast.Call):
                name = (dotted(node.func) or "").split(".")[-1]
                if name.startswith("segment_"):
                    has_segment = True
                elif name == "while_loop":
                    has_loop = True
        if has_segment and has_loop:
            out.append((fn.name, fn.lineno))
    return out


def _semiring_names(project: Project):
    """Literal keys of ops/semiring.py's SEMIRINGS table (None when the
    core module is not in the scanned tree — fixture projects)."""
    sr_mod = project.by_suffix("ops/semiring.py")
    if sr_mod is None:
        return None
    table, _line = _registry_dict(sr_mod, "SEMIRINGS")
    if table is None:
        return None
    names = set()
    for key_node in table.keys:
        key = _literal_or_none(key_node)
        if isinstance(key, str):
            names.add(key)
    return names


def _check_spmv_registry(project: Project):
    ops_init = project.by_suffix("ops/__init__.py")
    if ops_init is None:
        return []
    reg, reg_line = _registry_dict(ops_init, "SPMV_ALGORITHMS")
    findings = []
    if reg is None:
        findings.append(Finding(
            rule="MG005", path=ops_init.rel_path, line=1, col=0,
            symbol="SPMV_ALGORITHMS",
            message="ops/__init__.py has no SPMV_ALGORITHMS registry — "
                    "the mesh-coverage contract has nothing to check",
            fingerprint="spmv-registry-missing"))
        return findings

    semiring_names = _semiring_names(project)
    covered_modules: set[str] = set()
    for key_node, val_node in zip(reg.keys, reg.values):
        algo = _literal_or_none(key_node)
        entry = _literal_or_none(val_node)
        if not isinstance(algo, str) or not isinstance(entry, dict):
            findings.append(Finding(
                rule="MG005", path=ops_init.rel_path,
                line=getattr(key_node, "lineno", reg_line), col=0,
                symbol="SPMV_ALGORITHMS",
                message="SPMV_ALGORITHMS entries must be literal "
                        "str -> dict",
                fingerprint=f"spmv-nonliteral:{algo!r}"))
            continue
        line = getattr(key_node, "lineno", reg_line)
        sharded = entry.get("sharded")
        exempt = entry.get("exempt")
        if semiring_names is not None:
            core = entry.get("core")
            if not isinstance(core, str) or not core:
                findings.append(Finding(
                    rule="MG005", path=ops_init.rel_path, line=line,
                    col=0, symbol=algo,
                    message=f"SPMV_ALGORITHMS[{algo!r}] must declare "
                            "'core': the SEMIRINGS key its inner loop "
                            "iterates, or 'blocks' for custom rounds "
                            "over the core's building blocks",
                    fingerprint=f"spmv-no-core:{algo}"))
            elif core != "blocks" and core not in semiring_names:
                findings.append(Finding(
                    rule="MG005", path=ops_init.rel_path, line=line,
                    col=0, symbol=algo,
                    message=f"SPMV_ALGORITHMS[{algo!r}].core = "
                            f"{core!r} names no ops/semiring.py "
                            "SEMIRINGS entry (and is not 'blocks')",
                    fingerprint=f"spmv-unknown-core:{algo}:{core}"))
        if (sharded is None) == (exempt is None):
            findings.append(Finding(
                rule="MG005", path=ops_init.rel_path, line=line, col=0,
                symbol=algo,
                message=f"SPMV_ALGORITHMS[{algo!r}] must declare "
                        "exactly one of 'sharded' (mesh entry point) "
                        "or 'exempt' (justification)",
                fingerprint=f"spmv-undeclared:{algo}"))
        if exempt is not None and (not isinstance(exempt, str)
                                   or len(exempt.strip())
                                   < _SPMV_MIN_JUSTIFICATION):
            findings.append(Finding(
                rule="MG005", path=ops_init.rel_path, line=line, col=0,
                symbol=algo,
                message=f"SPMV_ALGORITHMS[{algo!r}] exemption needs a "
                        "real justification (>= "
                        f"{_SPMV_MIN_JUSTIFICATION} chars)",
                fingerprint=f"spmv-stub-exemption:{algo}"))
        for field_name in ("entry", "sharded"):
            target = entry.get(field_name)
            if target is None:
                continue
            if not isinstance(target, str) \
                    or not _target_resolves(project, target):
                findings.append(Finding(
                    rule="MG005", path=ops_init.rel_path, line=line,
                    col=0, symbol=algo,
                    message=f"SPMV_ALGORITHMS[{algo!r}].{field_name} "
                            f"target {target!r} does not resolve to a "
                            "function in the scanned tree",
                    fingerprint=f"spmv-dangling:{algo}:{field_name}"))
            if isinstance(target, str) and ":" in target:
                covered_modules.add(target.split(":", 1)[0]
                                    .rsplit(".", 1)[-1])

    # sweep: every SpMV-shaped or core-riding ops/ module must be
    # covered by an entry, and no non-core module may hand-roll a
    # segment_* + while_loop pipeline
    for rel, sf in sorted(project.files.items()):
        if "/ops/" not in rel or rel.endswith("__init__.py"):
            continue
        mod = rel.rsplit("/", 1)[-1][:-3]
        # the kernel cores themselves (semiring, spmv_mxu*, benes*) are
        # the shared engine the registry's targets ride, not algorithms
        # to register
        if mod.startswith(_SPMV_CORE_PREFIXES):
            continue
        spmv_shaped = _has_spmv_shape(sf)
        rides_core = _imports_semiring_core(sf)
        if (spmv_shaped or rides_core) and mod not in covered_modules:
            findings.append(Finding(
                rule="MG005", path=rel, line=1, col=0, symbol=mod,
                message=f"ops/{mod}.py has an SpMV-shaped kernel "
                        "(segment reduction inside while_loop, or a "
                        "semiring-core import) but no SPMV_ALGORITHMS "
                        "entry references it — it silently misses the "
                        "mesh path",
                fingerprint=f"spmv-uncovered:{mod}"))
        for fn_name, fn_line in _handrolled_functions(sf):
            findings.append(Finding(
                rule="MG005", path=rel, line=fn_line, col=0,
                symbol=fn_name,
                message=f"ops/{mod}.py:{fn_name} hand-rolls a "
                        "segment_* reduction inside a while_loop — "
                        "route it through ops/semiring.py (spmv / "
                        "edge_reduce / fixpoint) so it inherits the "
                        "MXU + mesh backends, precision variants and "
                        "stage attribution",
                fingerprint=f"spmv-handrolled:{mod}:{fn_name}"))
    return findings


# --------------------------------------------------------------------------
# mgtrace span-name coverage (observability/trace.py SPAN_NAMES)
# --------------------------------------------------------------------------

#: the sanctioned span-opening API (all context-manager / atomic-record
#: shaped; no caller can leave a span open by mistake)
_SPAN_OPEN_FUNCS = ("span", "record_span", "begin_trace")
#: trace.py's own open site: Python's cyclic collector's phases
_COLLECTOR_PHASE = "_CollectorPhase"


def _collect_phase_marks(tr) -> dict[str, int]:
    """{span name: lineno} of the module-level ``PHASES`` dict's literal
    keys in observability/trace.py: the spans accounted on every close
    (``span.<name>.seconds_total`` / ``.count``), armed or not."""
    out: dict[str, int] = {}
    for stmt in tr.tree.body:
        if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1 \
                and isinstance(stmt.targets[0], ast.Name) \
                and stmt.targets[0].id == "PHASES" \
                and isinstance(stmt.value, ast.Dict):
            for key in stmt.value.keys:
                if isinstance(key, ast.Constant) and \
                        isinstance(key.value, str):
                    out[key.value] = key.lineno
    return out


def _check_span_registry(project: Project):
    tr = project.by_suffix("observability/trace.py")
    if tr is None:
        return []
    names = _collect_tuple_registry(tr, "SPAN_NAMES")
    if not names:
        return []

    findings = []
    # the phase mark rides the span registry: a phase is a declared span
    for phase, line in sorted(_collect_phase_marks(tr).items()):
        if phase not in names:
            findings.append(Finding(
                rule="MG005", path=tr.rel_path, line=line, col=0,
                symbol="PHASES",
                message=f"phase {phase!r} is not declared in "
                        "SPAN_NAMES — a phase is a span name marked as "
                        "always accounted, not a second vocabulary",
                fingerprint=f"phase-undeclared:{phase}"))
    # the collector's phases open in trace.py itself, through the one
    # class that joins no trace and takes no lock (``_on_collect``)
    opened: set[str] = {
        node.args[0].value for node in ast.walk(tr.tree)
        if isinstance(node, ast.Call)
        and (dotted(node.func) or "") == _COLLECTOR_PHASE
        and node.args and isinstance(node.args[0], ast.Constant)
        and node.args[0].value in names}
    for rel, sf in project.files.items():
        if sf is tr:
            continue
        for node in ast.walk(sf.tree):
            if not isinstance(node, ast.Call):
                continue
            fname = (dotted(node.func) or "").split(".")[-1]
            if fname in ("_begin_span", "_end_span"):
                findings.append(Finding(
                    rule="MG005", path=rel, line=node.lineno,
                    col=node.col_offset, symbol=fname,
                    message=f"{fname}() is private to trace.py — spans "
                            "open only via the context-manager API "
                            "(span / record_span / begin_trace); manual "
                            "begin/end pairs are imbalance hazards",
                    fingerprint=f"span-manual:{fname}"))
                continue
            if fname not in _SPAN_OPEN_FUNCS:
                continue
            if not node.args or not (
                    isinstance(node.args[0], ast.Constant)
                    and isinstance(node.args[0].value, str)):
                continue
            span_name = node.args[0].value
            opened.add(span_name)
            if span_name not in names:
                findings.append(Finding(
                    rule="MG005", path=rel, line=node.lineno,
                    col=node.col_offset, symbol=fname,
                    message=f"span name {span_name!r} is not declared "
                            "in observability/trace.py SPAN_NAMES — an "
                            "undeclared name fragments traces and "
                            "dashboards can never know it exists",
                    fingerprint=f"span-unregistered:{span_name}"))
    for span_name, line in sorted(names.items()):
        if span_name not in opened:
            findings.append(Finding(
                rule="MG005", path=tr.rel_path, line=line, col=0,
                symbol="SPAN_NAMES",
                message=f"declared span name {span_name!r} has no open "
                        "site — dead registration, dashboards covering "
                        "it watch a span that can never fire",
                fingerprint=f"span-dead:{span_name}"))
    return findings


# --------------------------------------------------------------------------
# metric-name coverage (observability/metrics.py STAT_NAMES) — r14, mgstat
# --------------------------------------------------------------------------
#
# Every name emitted through global_metrics.increment()/set_gauge()/
# observe() must be declared exactly once in STAT_NAMES; entries ending
# in "*" declare a dynamic FAMILY (f-string sites whose literal prefix
# matches). Four failure modes fire:
#   * stat-unregistered  — a literal name no registry entry covers
#                          (typo: the series silently splits)
#   * stat-dynamic-unregistered — an f-string name whose literal prefix
#                          matches no declared family
#   * stat-dead          — a declared exact name with no emit site
#   * stat-dead-family   — a declared family with no dynamic emit site
#   * stat-duplicate     — a name declared more than once

_METRIC_EMIT_FUNCS = ("increment", "set_gauge", "observe")


def _collect_registry_with_dupes(sf, name: str):
    """[(literal, lineno)] preserving duplicates (the 'declared once'
    half of the contract needs them)."""
    out = []
    for stmt in sf.tree.body:
        if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1 \
                and isinstance(stmt.targets[0], ast.Name) \
                and stmt.targets[0].id == name \
                and isinstance(stmt.value, (ast.Tuple, ast.List)):
            for el in stmt.value.elts:
                if isinstance(el, ast.Constant) and \
                        isinstance(el.value, str):
                    out.append((el.value, getattr(el, "lineno",
                                                  stmt.lineno)))
    return out


def _check_stat_registry(project: Project):
    mx = project.by_suffix("observability/metrics.py")
    if mx is None:
        return []
    declared = _collect_registry_with_dupes(mx, "STAT_NAMES")
    if not declared:
        return []

    findings = []
    seen: set[str] = set()
    for name, line in declared:
        if name in seen:
            findings.append(Finding(
                rule="MG005", path=mx.rel_path, line=line, col=0,
                symbol="STAT_NAMES",
                message=f"metric name {name!r} is declared more than "
                        "once in STAT_NAMES — every name is declared "
                        "exactly once",
                fingerprint=f"stat-duplicate:{name}"))
        seen.add(name)
    exact = {n for n, _l in declared if not n.endswith("*")}
    families = {n[:-1] for n, _l in declared if n.endswith("*")}

    def family_of(prefix: str):
        for fam in families:
            if prefix.startswith(fam):
                return fam
        return None

    used_exact: set[str] = set()
    used_family: set[str] = set()
    # the span.* family's emit site is the phase registry itself: every
    # PHASES key emits span.<name>.seconds_total / .count on close
    tr = project.by_suffix("observability/trace.py")
    if tr is not None and _collect_phase_marks(tr):
        used_family.add("span.")
    for rel, sf in project.files.items():
        if sf is mx:
            continue
        for node in ast.walk(sf.tree):
            if not isinstance(node, ast.Call) or not node.args:
                continue
            d = (dotted(node.func) or "").split(".")
            if len(d) < 2 or d[-1] not in _METRIC_EMIT_FUNCS \
                    or d[-2] != "global_metrics":
                continue
            arg = node.args[0]
            if isinstance(arg, ast.Constant) and isinstance(arg.value,
                                                            str):
                stat = arg.value
                fam = family_of(stat)
                if stat in exact:
                    used_exact.add(stat)
                elif fam is not None:
                    used_family.add(fam)
                else:
                    findings.append(Finding(
                        rule="MG005", path=rel, line=node.lineno,
                        col=node.col_offset, symbol=d[-1],
                        message=f"metric name {stat!r} is not declared "
                                "in observability/metrics.py STAT_NAMES "
                                "— a typo'd name silently splits the "
                                "series and dashboards never learn it "
                                "exists",
                        fingerprint=f"stat-unregistered:{stat}"))
            elif isinstance(arg, ast.JoinedStr):
                first = arg.values[0] if arg.values else None
                prefix = first.value \
                    if isinstance(first, ast.Constant) and \
                    isinstance(first.value, str) else ""
                fam = family_of(prefix)
                if fam is not None:
                    used_family.add(fam)
                else:
                    findings.append(Finding(
                        rule="MG005", path=rel, line=node.lineno,
                        col=node.col_offset, symbol=d[-1],
                        message=f"dynamic metric name (prefix "
                                f"{prefix!r}) matches no STAT_NAMES "
                                "family — declare '<prefix>*' so the "
                                "family is discoverable",
                        fingerprint=f"stat-dynamic-unregistered:"
                                    f"{prefix}"))
    for name, line in declared:
        if name.endswith("*"):
            if name[:-1] not in used_family:
                findings.append(Finding(
                    rule="MG005", path=mx.rel_path, line=line, col=0,
                    symbol="STAT_NAMES",
                    message=f"declared metric family {name!r} has no "
                            "dynamic emit site — dead registration",
                    fingerprint=f"stat-dead-family:{name}"))
        elif name not in used_exact and family_of(name) is None:
            findings.append(Finding(
                rule="MG005", path=mx.rel_path, line=line, col=0,
                symbol="STAT_NAMES",
                message=f"declared metric name {name!r} has no emit "
                        "site — dead registration, dashboards covering "
                        "it watch a metric that can never move",
                fingerprint=f"stat-dead:{name}"))
    return findings
