"""Which host phase the device's longest idle gaps lie under.

    JAX_PLATFORMS=cpu python benchmarks/chipbench/gap_spans.py <trace dir> [<out.json> [<summary.json>]]

The program's phase spans (``memgraph_tpu/observability/trace.py``
``PHASES``) sit in the profiler's host plane as ``mgtrace:<name>`` while
a session is live, on the clock of the device's ``XLA Ops``. For the
ten longest idle gaps of each device plane this lists the ``mgtrace:*``
host events that overlap the gap with their overlap seconds, innermost
(shortest event) first, the seconds of the gap that any of them covers,
and the gap's ``parts``: its seconds split among the innermost span over
each instant, with ``unattributed`` for what no span covers.

Two stages, as in ``trace_reduce.py`` beside it, which supplies the
device side: ``extract`` reads the ``.xplane.pb`` with
``jax.profiler.ProfileData`` (nothing but JAX) into the device planes'
``[[op name, start ns, duration ns], ...]`` and the host's
``[[event name, start ns, duration ns], ...]``; ``attribute`` is plain
arithmetic over those lists. ``run.py`` runs this file once a traced
run's owner has exited (``reduce_trace``), held to the CPU, with a
third argument: ``trace_reduce.py``'s summary of the same planes is
written there, so the xplane is read once.
"""

from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import trace_reduce  # noqa: E402
from trace_reduce import find_xplane  # noqa: E402,F401

PREFIX = "mgtrace:"
TOP = 10


def extract(xplane_path: str):
    """(device planes as trace_reduce.extract gives them, the host's
    ``mgtrace:*`` events sorted by start)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(xplane_path)
    planes = trace_reduce.planes_of(data)
    host = []
    for plane in data.planes:
        if plane.name.startswith(trace_reduce.DEVICE_PLANE):
            continue
        for line in plane.lines:
            host.extend([e.name, float(e.start_ns), float(e.duration_ns)]
                        for e in line.events if e.name.startswith(PREFIX))
    host.sort(key=lambda e: e[1])
    return planes, host


def _claim(covered: list, lo: float, hi: float) -> float:
    """The ns of [lo, hi] that `covered` (disjoint intervals) does not
    hold yet; they are added to it."""
    free = hi - lo
    kept = []
    for a, b in covered:
        if b < lo or a > hi:
            kept.append((a, b))
        else:
            free -= min(b, hi) - max(a, lo)
            lo, hi = min(lo, a), max(hi, b)
    kept.append((lo, hi))
    covered[:] = kept
    return free


def attribute(planes: dict, host: list, top: int = TOP) -> list:
    """One row per gap, longest first within each device plane:
    ``{"plane", "start_ns", "seconds", "spans": [{"name", "overlap_s"}],
    "covered_s", "parts": [[name, seconds], ...]}``."""
    rows = []
    for name, ops in planes.items():
        if name.startswith(trace_reduce.MODULES_KEY):
            continue
        gaps = trace_reduce.summarize_plane(ops)["idle_gaps"][:top]
        for start, seconds in gaps:
            end = start + seconds * 1e9
            under = []
            for event, e_start, e_dur in host:
                lo, hi = max(start, e_start), min(end, e_start + e_dur)
                if hi > lo:
                    under.append((e_dur, event[len(PREFIX):], lo, hi))
            under.sort(key=lambda u: (u[0], u[2]))
            covered: list = []
            parts: dict = {}
            for _, span, lo, hi in under:       # innermost claims first
                mine = _claim(covered, lo, hi) / 1e9
                if mine > 0:
                    parts[span] = parts.get(span, 0.0) + mine
            covered_s = sum(parts.values())     # the union, not the sum
            if seconds - covered_s > 1e-9:
                parts["unattributed"] = seconds - covered_s
            rows.append({
                "plane": name, "start_ns": start, "seconds": seconds,
                "spans": [{"name": span, "overlap_s": (hi - lo) / 1e9}
                          for _, span, lo, hi in under],
                "covered_s": covered_s,
                "parts": sorted(parts.items(), key=lambda kv: -kv[1]),
            })
    return rows


def main(argv=None) -> int:
    args = list(argv if argv is not None else sys.argv[1:])
    xplane = find_xplane(args[0]) if args else None
    if xplane is None:
        print("usage: gap_spans.py <trace dir> [<out.json>]; no .xplane.pb "
              "found", file=sys.stderr)
        return 1
    planes, host = extract(xplane)
    rows = attribute(planes, host)
    if len(args) > 1:
        with open(args[1], "w") as f:
            json.dump({"gaps": rows, "host_events": len(host)}, f)
    if len(args) > 2:
        summary = trace_reduce.summarize(planes)
        summary["xplane_bytes"] = os.path.getsize(xplane)
        with open(args[2], "w") as f:
            json.dump(summary, f)
    for row in rows:
        merged: dict = {}
        for span in row["spans"]:
            merged[span["name"]] = merged.get(span["name"], 0.0) \
                + span["overlap_s"]
        print(f"{row['plane']}  gap {row['seconds']:.6f} s  covered "
              f"{row['covered_s']:.6f} s  "
              + ", ".join(f"{k} {v:.6f}" for k, v in merged.items()))
    return 0


if __name__ == "__main__":
    sys.exit(main())
