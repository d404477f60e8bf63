"""mgmem command line: ``python -m tools.mgmem check``.

Exit codes: 0 clean (or everything baselined), 1 violations / unused
baseline entries, 2 bad invocation, broken baseline, or an environment
that cannot lower the manifest (a host without the jax toolchain must
skip LOUDLY in the gate, never silently pass).
"""

from __future__ import annotations

import argparse
import json
import sys


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="python -m tools.mgmem",
        description="compiled-artifact HBM accounting: machine-check "
                    "the admission guard against XLA's buffer "
                    "assignment")
    sub = p.add_subparsers(dest="cmd")
    chk = sub.add_parser("check", help="extract, fit, and gate")
    chk.add_argument("--only", action="append", default=None,
                     metavar="KERNEL",
                     help="check only this manifest kernel "
                          "(repeatable; skips envelope + admission "
                          "cross-checks)")
    chk.add_argument("--json", action="store_true",
                     help="machine-readable JSON output")
    chk.add_argument("--baseline", default=None,
                     help="baseline file (default: tools/mgmem/"
                          "baseline.json)")
    chk.add_argument("--no-baseline", action="store_true",
                     help="ignore the baseline: show every violation")
    env = sub.add_parser(
        "envelopes",
        help="print (or --write into BASELINE.json) the per-kernel "
             "canonical-point peak envelopes")
    env.add_argument("--write", action="store_true")
    lst = sub.add_parser("list", help="list manifest kernels and their "
                                      "fitted models")
    lst.add_argument("--json", action="store_true")
    return p


def _load_baseline(path: str | None):
    """Same loader discipline as mglint/mgxla: every entry needs a key
    and a non-empty justification."""
    import os

    from tools.mglint.core import load_baseline

    from .check import BASELINE_PATH
    path = path or BASELINE_PATH
    if not os.path.exists(path):
        return {}
    return load_baseline(path)


def main(argv: list[str] | None = None) -> int:
    args = build_parser().parse_args(argv)
    if args.cmd is None:
        build_parser().print_help()
        return 2

    try:
        from tools.mgxla.manifest import MANIFEST
    except Exception as e:  # noqa: BLE001 — toolchainless host
        print(f"mgmem: cannot import the mgxla manifest ({e})",
              file=sys.stderr)
        return 2

    if args.cmd == "list":
        from .facts import kernel_lanes, shape_points
        if args.json:
            print(json.dumps(
                {k: {"lanes": kernel_lanes(k),
                     "shape_points": [[d.n_pad, d.n_edges]
                                      for d in shape_points(k)]}
                 for k in sorted(MANIFEST)}, indent=2))
        else:
            for k in sorted(MANIFEST):
                print(k)
        return 0

    from .check import (REPO_BASELINE_PATH, memory_envelope_from,
                        run_check)

    if args.cmd == "envelopes":
        report = run_check(envelope=None, admission=False)
        if report.violations:
            print(report.render())
            print("mgmem: refusing to write envelopes over a failing "
                  "sweep", file=sys.stderr)
            return 1
        envelope = memory_envelope_from(report)
        if args.write:
            with open(REPO_BASELINE_PATH, encoding="utf-8") as f:
                doc = json.load(f)
            doc.setdefault("envelopes", {})["memory"] = envelope
            with open(REPO_BASELINE_PATH, "w", encoding="utf-8") as f:
                json.dump(doc, f, indent=1)
                f.write("\n")
            print(f"mgmem: wrote {len(envelope['kernels'])} kernel "
                  f"envelopes into BASELINE.json")
        else:
            print(json.dumps(envelope, indent=2))
        return 0

    try:
        baseline = {} if args.no_baseline else _load_baseline(
            args.baseline)
    except (ValueError, OSError) as e:
        print(f"mgmem: broken baseline: {e}", file=sys.stderr)
        return 2

    only = set(args.only) if args.only else None
    if only:
        unknown = only - set(MANIFEST)
        if unknown:
            print(f"mgmem: unknown kernels {sorted(unknown)}; see "
                  "`python -m tools.mgmem list`", file=sys.stderr)
            return 2
    try:
        report = run_check(only=only, baseline=baseline)
    except ImportError as e:
        print(f"mgmem: lowering unavailable on this host ({e}) — "
              "NOTHING was checked", file=sys.stderr)
        return 2

    if args.json:
        print(json.dumps({
            "kernels_checked": report.kernels_checked,
            "violations": [{"kernel": v.kernel, "check": v.check,
                            "detail": v.detail, "key": v.key,
                            "snippet": v.snippet}
                           for v in report.violations],
            "baselined": [v.key for v in report.baselined],
            "unused_baseline": report.unused_baseline,
            "ok": report.ok}, indent=2))
    else:
        print(report.render())
    return 0 if report.ok else 1
