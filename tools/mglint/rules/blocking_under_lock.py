"""MG002 — blocking-under-lock: no fsync / socket I/O / sleep /
subprocess / DEVICE DISPATCH while a storage, replication, server, or
coordination lock is held.

A commit-critical lock held across a syscall turns one slow disk or one
wedged peer into a stall for every thread behind the lock (the
reference's "never fsync under the engine lock" discipline). Findings
are deduplicated per (function, lock): one finding lists every blocking
operation reachable inside that function's critical section, directly
or through a resolved call chain.

Device dispatches (r12) — `jax.device_put`, `.to_device()` /
`put_edge_blocks` placements, compiled-call invocations entering
through the `device_fault_point()` boundary, and kernel-server
`_send_msg`/`_recv_msg` frames — are classified as blocking too: a
hung device runtime or a lost chip under a storage/server lock is
EXACTLY the wedge class the kernel-server supervision (deadline +
health-check restart) exists to contain, and it must never hide behind
a lock the rest of the system waits on.

Deliberate cases — e.g. the WAL writer's own append lock, whose entire
purpose is serializing write+fsync, or the kernel server's dispatch
lock, which is supervised by construction — belong in the baseline
with a justification, not silently ignored.
"""

from __future__ import annotations

from ..core import Finding, Project
from ..locking import CRITICAL_DIRS, get_model
from ..registry import register


def _critical(rel_path: str) -> bool:
    parts = rel_path.split("/")
    return any(p in CRITICAL_DIRS for p in parts[:-1])


@register("MG002", "blocking-under-lock")
def check(project: Project):
    """No fsync/socket/sleep/subprocess inside a critical section."""
    model = get_model(project)
    # (func key, lock display) -> {"ops": [...], "line": first line, ...}
    grouped: dict[tuple[str, str], dict] = {}

    for fi in model.functions.values():
        if not _critical(fi.rel_path):
            continue
        for ev in fi.events:
            ops: list[tuple[str, int]] = []
            if ev.blocking is not None:
                op, site = ev.blocking
                ops.append((f"{op} [{site.text}]", site.line))
            elif ev.call is not None:
                callee = model.callee(ev.call)
                if callee is not None and callee.may_block:
                    for op, via in sorted(callee.may_block.items()):
                        label = via if via.startswith("via ") else \
                            f"via {callee.qualname}(): {op}"
                        ops.append((label, ev.call.line))
            if not ops:
                continue
            innermost = ev.held[-1]
            lock_name = innermost.lock_id or innermost.attr
            key = (fi.key, lock_name)
            entry = grouped.setdefault(key, {
                "fi": fi, "lock": lock_name, "ops": [],
                "line": ops[0][1]})
            entry["ops"].extend(ops)

    findings = []
    for (_fk, _lock), entry in sorted(grouped.items()):
        fi = entry["fi"]
        op_list = sorted({op for op, _ln in entry["ops"]})
        shown = "; ".join(op_list[:4])
        if len(op_list) > 4:
            shown += f"; +{len(op_list) - 4} more"
        findings.append(Finding(
            rule="MG002", path=fi.rel_path, line=entry["line"], col=0,
            symbol=fi.qualname,
            message=f"blocking operation(s) while holding "
                    f"{entry['lock']}: {shown}",
            fingerprint=f"block-under:{entry['lock']}"))
    return findings
