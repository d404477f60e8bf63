"""mgmem check driver: facts -> models -> admission + envelope gates.

Violation keys are stable strings (``kernel:check:detail``) consumed by
``tools/mgmem/baseline.json`` under the exact loader / justification
discipline mglint and mgxla use: every accepted violation needs a
written justification, and an entry no longer matched by any violation
is reported as UNUSED so the baseline can only shrink honestly.

Checks per manifest kernel:

* ``build``              — the product builder failed to lower/compile;
* ``donation-dropped``   — a declared donation XLA silently copied
                           (the UserWarning trap), with the bytes;
* ``donation-copied``    — the contract declares donations but the
                           compiled artifact aliased ZERO bytes;
* ``model-fit``          — the peak is not linear in (n, e) within
                           :data:`~.model.FIT_TOLERANCE` (a hidden
                           super-linear intermediate);
* ``envelope``           — canonical-point peak grew past the
                           BASELINE.json memory envelope (the
                           memory-regression gate);
* ``admission-*``        — the serving estimators vs the models
                           (:mod:`.admission`).
"""

from __future__ import annotations

import json
import os
from dataclasses import dataclass, field

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BASELINE_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "baseline.json")
REPO_BASELINE_PATH = os.path.join(REPO, "BASELINE.json")

#: envelope headroom: canonical-point peak may grow this fraction
#: before the gate fails (buffer assignment is deterministic, so
#: drift is a change)
DEFAULT_MAX_GROWTH = 0.10


@dataclass(frozen=True)
class Violation:
    kernel: str
    check: str    # build|donation-dropped|donation-copied|model-fit|
    #               envelope|admission|admission-underestimate|
    #               admission-overestimate|padding-mirror
    detail: str
    snippet: str = ""

    @property
    def key(self) -> str:
        return f"{self.kernel}:{self.check}:{self.detail}"

    def render(self) -> str:
        out = f"{self.kernel}: {self.check}: {self.detail}"
        if self.snippet:
            out += "\n    | " + self.snippet.replace("\n", "\n    | ")
        return out


@dataclass
class CheckReport:
    violations: list = field(default_factory=list)    # unbaselined
    baselined: list = field(default_factory=list)
    unused_baseline: list = field(default_factory=list)
    kernels_checked: int = 0
    facts: dict = field(default_factory=dict)     # kernel -> [MemFacts]
    models: dict = field(default_factory=dict)    # kernel -> FootprintModel

    @property
    def ok(self) -> bool:
        return not self.violations and not self.unused_baseline

    def render(self) -> str:
        lines = [f"mgmem: {self.kernels_checked} kernels checked, "
                 f"{len(self.models)} footprint models fitted"]
        for v in self.violations:
            lines.append("VIOLATION " + v.render())
        for v in self.baselined:
            lines.append("baselined " + v.render().splitlines()[0])
        for key in self.unused_baseline:
            lines.append(f"UNUSED baseline entry (fixed or drifted): "
                         f"{key}")
        lines.append("mgmem: " + ("OK" if self.ok else "FAILED"))
        return "\n".join(lines)


def load_memory_envelope(path: str | None = None) -> dict | None:
    """BASELINE.json ``envelopes.memory`` (None when not yet written —
    bootstrap via ``python -m tools.mgmem envelopes --write``)."""
    path = path or REPO_BASELINE_PATH
    if not os.path.exists(path):
        return None
    with open(path, encoding="utf-8") as f:
        doc = json.load(f)
    return (doc.get("envelopes") or {}).get("memory")


def _check_kernel(kernel: str, report: CheckReport) -> None:
    from tools.mgxla.manifest import MANIFEST

    from . import facts as F
    from .model import FIT_TOLERANCE, fit
    try:
        fl = F.extract_all(kernel)
    except Exception as e:  # noqa: BLE001 — typed as a build violation
        report.violations.append(Violation(
            kernel, "build", type(e).__name__,
            snippet=str(e).splitlines()[0][:200] if str(e) else ""))
        return
    report.facts[kernel] = fl
    f0 = fl[0]
    if f0.donation_dropped > 0:
        report.violations.append(Violation(
            kernel, "donation-dropped", f"{f0.dropped_bytes}B",
            snippet=f"{f0.donation_dropped} declared donation(s) XLA "
                    f"silently copied ({f0.dropped_bytes} bytes at the "
                    f"canonical point) — the donated carry costs a "
                    f"full extra buffer on device"))
    min_donated = MANIFEST[kernel].min_donated if kernel in MANIFEST \
        else 0
    if min_donated > 0 and f0.alias_bytes <= 0:
        report.violations.append(Violation(
            kernel, "donation-copied",
            f"declared>={min_donated},aliased=0B",
            snippet="the contract declares donated params but the "
                    "compiled artifact aliased zero bytes"))
    model = fit(kernel, fl)
    if model.residual > FIT_TOLERANCE:
        report.violations.append(Violation(
            kernel, "model-fit", f"residual={model.residual:.4f}",
            snippet="peak bytes are not linear in (n, e) — a "
                    "super-linear intermediate joined the buffer "
                    "assignment; the footprint model cannot "
                    "extrapolate this kernel"))
    else:
        report.models[kernel] = model


def _check_envelopes(report: CheckReport, envelope: dict | None) -> None:
    if envelope is None:
        return
    kernels = envelope.get("kernels") or {}
    max_growth = float(envelope.get("max_growth", DEFAULT_MAX_GROWTH))
    for kernel, fl in sorted(report.facts.items()):
        peak = fl[0].peak_bytes
        ref = kernels.get(kernel)
        if ref is None:
            report.violations.append(Violation(
                kernel, "envelope", "missing",
                snippet=f"canonical peak {peak}B has no BASELINE.json "
                        f"memory envelope — add one via `python -m "
                        f"tools.mgmem envelopes --write`"))
            continue
        ceiling = int(ref * (1.0 + max_growth))
        if peak > ceiling:
            report.violations.append(Violation(
                kernel, "envelope",
                f"peak={peak}B>ceiling={ceiling}B",
                snippet=f"canonical-point peak grew "
                        f"{(peak / ref - 1) * 100:+.1f}% past the "
                        f"envelope reference {ref}B (allowed "
                        f"+{max_growth * 100:.0f}%)"))
    for kernel in sorted(set(kernels) - set(report.facts)):
        report.violations.append(Violation(
            kernel, "envelope", "stale",
            snippet="envelope names a kernel the manifest no longer "
                    "has — regenerate with `envelopes --write`"))


def run_check(only=None, baseline: dict | None = None,
              estimators=None, envelope: dict | None = "load",
              admission: bool = True) -> CheckReport:
    """Extract, fit, and gate. ``only`` restricts to named kernels
    (envelope staleness + admission checks then skip, like mgxla's
    structural checks). ``estimators`` injects an
    :class:`~.admission.Estimators` fixture."""
    from . import facts as F
    baseline = baseline or {}
    report = CheckReport()
    kernels = sorted(only) if only else F.manifest_kernels()
    partial = only is not None
    for kernel in kernels:
        _check_kernel(kernel, report)
    report.kernels_checked = len(kernels)
    if not partial:
        if envelope == "load":
            envelope = load_memory_envelope()
        _check_envelopes(report, envelope)
        if admission:
            from .admission import run_admission_checks
            report.violations += run_admission_checks(
                report.models, Violation, estimators)
    matched = set()
    unbaselined = []
    for v in report.violations:
        if v.key in baseline:
            matched.add(v.key)
            report.baselined.append(v)
        else:
            unbaselined.append(v)
    report.violations = unbaselined
    if not partial:
        report.unused_baseline = sorted(set(baseline) - matched)
    return report


def memory_envelope_from(report: CheckReport,
                         max_growth: float = DEFAULT_MAX_GROWTH) -> dict:
    """Fresh ``envelopes.memory`` content for BASELINE.json."""
    return {"_comment": "per-kernel compiled peak bytes at the mgmem "
                        "canonical point (n_pad=64, n_edges=256; "
                        "mesh kernels whole-mesh). Enforced by `python "
                        "-m tools.mgmem check` over the freshly "
                        "compiled facts. Regenerate: `python -m "
                        "tools.mgmem envelopes --write`.",
            "max_growth": max_growth,
            "kernels": {k: fl[0].peak_bytes
                        for k, fl in sorted(report.facts.items())}}
