"""Unsafe-retry check (the MG013 core).

A **retry region** is a ``for _ in <policy>.attempts():`` loop or a
``<policy>.call(fn, ...)`` expression, where the policy receiver is
named like a retry policy ("retry"/"policy" in its dotted text) or is a
direct ``RetryPolicy(...)`` construction. Every region must be
classified in ``utils/retry.py``'s ``IDEMPOTENCY`` registry by the
qualname of the operation it implements (the enclosing function, or a
callee resolved inside the loop):

  * unclassified region            -> finding (classify it)
  * region swallows class C where
    IDEMPOTENCY[C] == "unsafe"     -> finding (the oom/shed rule:
                                      deterministic outcomes are never
                                      retried)
  * region op is "unsafe" and it
    swallows C not registered
    "retryable"                    -> finding (blind re-send of a
                                      non-idempotent op)
  * registry entry matched by
    nothing                        -> finding (dead registration)

"Swallows" means an except handler inside an ``attempts()`` loop whose
body contains no ``raise`` (the attempt loop continues), or the
``retry_on=`` classes of a ``.call`` region (default
ConnectionError/OSError). A handler that re-raises — even
conditionally — is treated as surfacing, which under-approximates
swallowing; the justified leftovers carry baseline entries instead.
"""

from __future__ import annotations

import ast

from ..mglint.core import Finding, Project, qualname_of
from ..mglint.locking import dotted, get_model
from .spec import FlowSpec, extract_specs


def _is_policy_recv(node) -> bool:
    name = dotted(node)
    if name and ("retry" in name.lower() or "policy" in name.lower()):
        return True
    return isinstance(node, ast.Call) and \
        (dotted(node.func) or "").split(".")[-1] == "RetryPolicy"


def _qual_matches(qualname: str, key: str) -> bool:
    """Do the key's dotted segments appear contiguously in qualname's?
    ("ShardedClient.scatter_read" matches the nested
    "ShardedClient.scatter_read.one")."""
    q = qualname.split(".")
    k = key.split(".")
    n = len(k)
    return any(q[i:i + n] == k for i in range(len(q) - n + 1))


def _handler_tokens(handler: ast.ExceptHandler) -> list[str]:
    t = handler.type
    if t is None:
        return []
    elts = t.elts if isinstance(t, ast.Tuple) else [t]
    out = []
    for el in elts:
        name = dotted(el)
        if name:
            out.append(name.split(".")[-1])
    return out


def _body_raises(handler: ast.ExceptHandler) -> bool:
    for node in ast.walk(handler):
        if isinstance(node, ast.Raise):
            return True
    return False


class _Region:
    def __init__(self, rel, line, qualname, kind):
        self.rel = rel
        self.line = line
        self.qualname = qualname
        self.kind = kind              # "attempts" | "call"
        self.callee_quals: list[str] = []
        self.swallowed: list[tuple[str, int]] = []   # (token, line)
        self.handled: set[str] = set()


def _collect_regions(project: Project) -> list[_Region]:
    model = get_model(project)
    regions = []
    for rel, sf in sorted(project.files.items()):
        sf.ensure_parents()
        for node in ast.walk(sf.tree):
            if isinstance(node, ast.For) and \
                    isinstance(node.iter, ast.Call) and \
                    isinstance(node.iter.func, ast.Attribute) and \
                    node.iter.func.attr == "attempts" and \
                    _is_policy_recv(node.iter.func.value):
                regions.append(_attempts_region(model, rel, sf, node))
            elif isinstance(node, ast.Call) and \
                    isinstance(node.func, ast.Attribute) and \
                    node.func.attr == "call" and \
                    _is_policy_recv(node.func.value):
                regions.append(_call_region(model, rel, sf, node))
    return regions


def _enclosing_info(sf, node):
    """(qualname, class name) of the function enclosing `node`."""
    qual = qualname_of(node) or "<module>"
    cls = None
    cur = getattr(node, "_mglint_parent", None)
    while cur is not None:
        if isinstance(cur, ast.ClassDef):
            cls = cur.name
            break
        cur = getattr(cur, "_mglint_parent", None)
    return qual, cls


def _attempts_region(model, rel, sf, node: ast.For) -> _Region:
    qual, cls = _enclosing_info(sf, node)
    region = _Region(rel, node.lineno, qual, "attempts")
    for sub in ast.walk(node):
        if isinstance(sub, ast.Call):
            target = model._resolve_call(sub, rel, cls)
            if target is not None:
                region.callee_quals.append(
                    model.functions[target].qualname)
        elif isinstance(sub, ast.ExceptHandler):
            tokens = _handler_tokens(sub)
            region.handled.update(tokens)
            if not _body_raises(sub):
                region.swallowed.extend(
                    (t, sub.lineno) for t in tokens)
    return region


def _call_region(model, rel, sf, node: ast.Call) -> _Region:
    qual, cls = _enclosing_info(sf, node)
    region = _Region(rel, node.lineno, qual, "call")
    if node.args:
        pseudo = ast.Call(func=node.args[0], args=[], keywords=[])
        ast.copy_location(pseudo, node)
        target = model._resolve_call(pseudo, rel, cls)
        if target is not None:
            region.callee_quals.append(model.functions[target].qualname)
    retry_on = ("ConnectionError", "OSError")
    for kw in node.keywords:
        if kw.arg == "retry_on":
            elts = kw.value.elts if isinstance(
                kw.value, (ast.Tuple, ast.List)) else [kw.value]
            retry_on = tuple((dotted(el) or "?").split(".")[-1]
                             for el in elts)
    region.swallowed.extend((t, node.lineno) for t in retry_on)
    region.handled.update(retry_on)
    return region


def check_retries(project: Project,
                  spec: FlowSpec | None = None) -> list[Finding]:
    if spec is None:
        spec = extract_specs(project)
    if not spec.idempotency:
        return []
    entries = spec.idem_by_name
    op_keys = {n for n in entries if n not in _class_names(entries)}
    class_keys = _class_names(entries)

    used: set[str] = set()
    findings = []
    for region in _collect_regions(project):
        # classify: enclosing qualname first, then resolved callees
        matched = [k for k in op_keys
                   if _qual_matches(region.qualname, k)]
        for cq in region.callee_quals:
            matched.extend(k for k in op_keys if _qual_matches(cq, k))
        used.update(matched)
        if not matched:
            findings.append(Finding(
                rule="MG013", path=region.rel, line=region.line, col=0,
                symbol=region.qualname,
                message=f"retry region in {region.qualname} matches no "
                        "operation entry of utils/retry.py IDEMPOTENCY "
                        "— classify it 'retryable' (idempotent, blind "
                        "re-send safe) or 'unsafe'",
                fingerprint=f"unclassified:{region.qualname}"))
            continue
        op_unsafe = any(entries[k].classification == "unsafe"
                        for k in matched)
        used.update(c for c in region.handled if c in class_keys)
        for token, line in region.swallowed:
            entry = entries.get(token)
            if entry is not None and entry.classification == "unsafe":
                findings.append(Finding(
                    rule="MG013", path=region.rel, line=line, col=0,
                    symbol=region.qualname,
                    message=f"{region.qualname} retries after "
                            f"swallowing {token}, registered 'unsafe' "
                            "in IDEMPOTENCY — this outcome is "
                            "deterministic against the current state; "
                            "retrying it is a storm, surface it",
                    fingerprint=f"retry-unsafe-class:"
                                f"{region.qualname}:{token}"))
            elif op_unsafe and (entry is None or
                                entry.classification != "retryable"):
                findings.append(Finding(
                    rule="MG013", path=region.rel, line=line, col=0,
                    symbol=region.qualname,
                    message=f"{region.qualname} is registered 'unsafe' "
                            f"(non-idempotent) but swallows {token} "
                            "and re-sends — only classes registered "
                            "'retryable' (pre-apply bounces) may be "
                            "retried here; surface the rest typed",
                    fingerprint=f"blind-retry:"
                                f"{region.qualname}:{token}"))
    for name, entry in sorted(entries.items()):
        if name not in used:
            findings.append(Finding(
                rule="MG013", path=entry.decl_rel, line=entry.decl_line,
                col=0, symbol="IDEMPOTENCY",
                message=f"IDEMPOTENCY entry {name!r} matches no retry "
                        "region or handled exception class — dead "
                        "registration, the classification guards "
                        "nothing",
                fingerprint=f"idem-unused:{name}"))
    return findings


def _class_names(entries: dict) -> set[str]:
    """Entries naming exception classes rather than operations: no dot,
    CamelCase-looking (matches the classification's naming)."""
    return {n for n in entries
            if "." not in n and n[:1].isupper() and "_" not in n}
