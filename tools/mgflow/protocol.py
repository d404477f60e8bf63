"""Typed-outcome protocol drift check (both directions, MG005-style).

For every ``WIRES`` entry: read the server-emitted vocabulary and the
client-decoded vocabulary straight out of the source (the ``extract``
directives documented on ``flowspec.WireSide``), then enforce

  server -> client:
    * every emitted outcome is in the declared vocabulary
    * every declared (or emitted) outcome has a client decoder — a
      literal comparison site — or is listed ``handled_inline``
  client -> server:
    * every decoded outcome is declared (no dead decoders: a decoder
      for an outcome no server can emit is drift that already happened)
    * every ``handled_inline`` value is declared

Extraction collects CONSTANTS only; an outcome shipped through a
variable is simply not collected (it cannot create a false positive,
and the declared-vocabulary direction still covers it).
"""

from __future__ import annotations

import ast

from ..mglint.core import Finding, Project, qualname_of
from ..mglint.locking import dotted
from .spec import FlowSpec, WireSideSpec, WireSpec, extract_specs


def _in_scope(node, scope: tuple) -> bool:
    if not scope:
        return True
    qual = qualname_of(node)
    return any(qual == s or qual.startswith(s + ".") for s in scope)


def _module_assign(sf, name: str):
    for stmt in sf.tree.body:
        if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1 \
                and isinstance(stmt.targets[0], ast.Name) \
                and stmt.targets[0].id == name:
            return stmt
    return None


def _extract_side(project: Project, side: WireSideSpec) -> dict:
    """{outcome: (rel, line)} — first site wins as the witness."""
    sf = project.by_suffix(side.path)
    if sf is None:
        return {}
    sf.ensure_parents()
    out: dict[str, tuple] = {}

    def add(value, line):
        if isinstance(value, str):
            out.setdefault(value, (sf.rel_path, line))

    for directive, arg in side.extract:
        if directive == "dict_keys":
            stmt = _module_assign(sf, arg)
            if stmt is not None and isinstance(stmt.value, ast.Dict):
                for k in stmt.value.keys:
                    if isinstance(k, ast.Constant):
                        add(k.value, k.lineno)
            continue
        if directive == "tuple_const":
            stmt = _module_assign(sf, arg)
            if stmt is not None and \
                    isinstance(stmt.value, (ast.Tuple, ast.List)):
                for el in stmt.value.elts:
                    if isinstance(el, ast.Constant):
                        add(el.value, el.lineno)
            continue
        for node in ast.walk(sf.tree):
            if not _in_scope(node, side.scope):
                continue
            if directive == "dict_value" and \
                    isinstance(node, ast.Dict):
                for k, v in zip(node.keys, node.values):
                    if isinstance(k, ast.Constant) and \
                            k.value == arg and \
                            isinstance(v, ast.Constant):
                        add(v.value, v.lineno)
            elif directive == "send_tuple0" and \
                    isinstance(node, ast.Call) and \
                    (dotted(node.func) or "").split(".")[-1] == arg:
                for a in node.args:
                    if isinstance(a, ast.Tuple) and a.elts and \
                            isinstance(a.elts[0], ast.Constant):
                        add(a.elts[0].value, a.lineno)
            elif directive == "return_tuple0" and \
                    isinstance(node, ast.Return) and \
                    isinstance(node.value, ast.Tuple) and \
                    node.value.elts and \
                    isinstance(node.value.elts[0], ast.Constant):
                add(node.value.elts[0].value, node.lineno)
            elif directive == "compare" and \
                    isinstance(node, ast.Compare):
                operands = [node.left] + list(node.comparators)
                if not any(_matches_var(op, arg) for op in operands):
                    continue
                for op in operands:
                    if isinstance(op, ast.Constant):
                        add(op.value, op.lineno)
                    elif isinstance(op, (ast.Tuple, ast.List,
                                         ast.Set)):
                        for el in op.elts:
                            if isinstance(el, ast.Constant):
                                add(el.value, el.lineno)
    return out


def _matches_var(node, var: str) -> bool:
    if var == "[0]":
        return isinstance(node, ast.Subscript) and \
            isinstance(node.slice, ast.Constant) and \
            node.slice.value == 0
    name = dotted(node)
    return bool(name) and name.split(".")[-1] == var


def _declared(project: Project, wire: WireSpec, emitted: dict) -> dict:
    if wire.declared is None:
        return dict(emitted)
    path, symbol = wire.declared
    sf = project.by_suffix(path)
    if sf is None:
        return dict(emitted)
    stmt = _module_assign(sf, symbol)
    out: dict[str, tuple] = {}
    if stmt is not None and isinstance(stmt.value,
                                       (ast.Tuple, ast.List)):
        for el in stmt.value.elts:
            if isinstance(el, ast.Constant) and \
                    isinstance(el.value, str):
                out.setdefault(el.value, (sf.rel_path, el.lineno))
    return out or dict(emitted)


def check_wires(project: Project,
                spec: FlowSpec | None = None) -> list[Finding]:
    if spec is None:
        spec = extract_specs(project)
    findings = []
    for wire in spec.wires:
        emitted: dict[str, tuple] = {}
        for side in wire.server:
            for v, site in _extract_side(project, side).items():
                emitted.setdefault(v, site)
        decoded: dict[str, tuple] = {}
        for side in wire.client:
            for v, site in _extract_side(project, side).items():
                decoded.setdefault(v, site)
        declared = _declared(project, wire, emitted)
        inline = set(wire.handled_inline)
        wid = wire.wire_id

        for v, (rel, line) in sorted(emitted.items()):
            if v not in declared:
                findings.append(Finding(
                    rule="MGF-PROTO", path=rel, line=line, col=0,
                    symbol=wid,
                    message=f"wire {wid!r}: server emits outcome {v!r} "
                            "missing from the declared vocabulary "
                            f"({'::'.join(wire.declared)})"
                            if wire.declared else
                            f"wire {wid!r}: server emits undeclared "
                            f"outcome {v!r}",
                    fingerprint=f"undeclared-emit:{wid}:{v}"))
        for v in sorted(set(declared) | set(emitted)):
            if v in decoded or v in inline:
                continue
            rel, line = declared.get(v) or emitted[v]
            findings.append(Finding(
                rule="MGF-PROTO", path=rel, line=line, col=0,
                symbol=wid,
                message=f"wire {wid!r}: outcome {v!r} has no client "
                        "decoder — the client would see it as a "
                        "generic failure, losing the typed classification",
                fingerprint=f"undecoded:{wid}:{v}"))
        for v, (rel, line) in sorted(decoded.items()):
            if v not in declared:
                findings.append(Finding(
                    rule="MGF-PROTO", path=rel, line=line, col=0,
                    symbol=wid,
                    message=f"wire {wid!r}: client decodes outcome "
                            f"{v!r} that no server declares or emits — "
                            "dead decoder, the drift already happened",
                    fingerprint=f"dead-decoder:{wid}:{v}"))
        for v in sorted(inline):
            if v not in declared:
                findings.append(Finding(
                    rule="MGF-PROTO", path=wire.decl_rel,
                    line=wire.decl_line, col=0, symbol=wid,
                    message=f"wire {wid!r}: handled_inline value {v!r} "
                            "is not in the declared vocabulary",
                    fingerprint=f"inline-undeclared:{wid}:{v}"))
    return findings
