"""From the profiler's xplane to device intervals, and from intervals to
busy time, an op table and the longest idle gaps.

    JAX_PLATFORMS=cpu python benchmarks/chipbench/trace_reduce.py <trace dir> <out.json>

Two stages, so the second can be checked on a recorded slice without
the profiler: ``extract`` reads the ``.xplane.pb`` with
``jax.profiler.ProfileData`` (nothing but JAX) into
``{plane: [[op name, start ns, duration ns], ...]}``; ``summarize`` is
plain arithmetic over those lists. The parent runs this file in a
process of its own after the chip owner has exited, held to the CPU,
so no second process reaches for the chip.

A device op is an event of a ``/device:...`` plane's ``XLA Ops`` line;
the ``XLA Modules`` line beside it holds one event per run of a jitted
program, under the program's name, and is summed apart (``modules``).
Where a trace has no such plane (the CPU rehearsal), events that carry
an ``hlo_op`` stat stand in, one plane for all threads; the harness
never reports those as a device's.
"""

from __future__ import annotations

import glob
import json
import os
import sys

DEVICE_PLANE = "/device:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
MODULES_KEY = "#modules#"       # extract()'s key for the module events
TOP = 10


def find_xplane(trace_dir: str) -> str | None:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def short_name(name: str) -> str:
    """An XLA op's event name is its whole HLO line; keep the result's
    name, and say where it is a Pallas kernel or a loop around others."""
    head, _, rest = name.partition(" = ")
    if "tpu_custom_call" in rest:
        head += " (tpu_custom_call)"
    elif " while(" in rest:
        head += " (while)"
    return head


def extract(xplane_path: str) -> dict:
    from jax.profiler import ProfileData
    return planes_of(ProfileData.from_file(xplane_path))


def planes_of(data) -> dict:
    """The device planes of a ProfileData that has been read."""
    planes: dict = {}
    stand_in: list = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            # "/device:TPU:0 ..." — sparse-core and other sub-planes keep
            # their own names and have no "XLA Ops" line
            for line in plane.lines:
                key = {OPS_LINE: plane.name,
                       MODULES_LINE: MODULES_KEY + plane.name}.get(line.name)
                if key is not None:
                    planes.setdefault(key, []).extend(
                        [short_name(e.name), float(e.start_ns),
                         float(e.duration_ns)] for e in line.events)
        elif not any(not k.startswith(MODULES_KEY) for k in planes):
            for line in plane.lines:
                for e in line.events:
                    if any(k == "hlo_op" for k, _ in e.stats):
                        stand_in.append([e.name, float(e.start_ns),
                                         float(e.duration_ns)])
    if not planes and stand_in:
        planes["host-stand-in"] = stand_in
    for ops in planes.values():
        ops.sort(key=lambda op: op[1])
    return planes


def summarize_plane(ops: list) -> dict:
    """ops: [[name, start ns, duration ns], ...] sorted by start."""
    table: dict = {}
    for name, _, dur in ops:
        row = table.setdefault(name, [0, 0.0])
        row[0] += 1
        row[1] += dur
    busy = 0.0
    gaps = []
    reach = None            # end of the union so far
    for _, start, dur in ops:
        end = start + dur
        if reach is None:
            busy, reach = dur, end
            continue
        if start > reach:
            gaps.append([reach, start - reach])
            busy += dur
        elif end > reach:
            busy += end - reach
        reach = max(reach, end)
    gaps.sort(key=lambda g: -g[1])
    return {
        "events": len(ops),
        "first_ns": ops[0][1] if ops else None,
        "last_ns": reach,
        "busy_s": busy / 1e9,
        "ops": {name: {"count": c, "seconds": ns / 1e9}
                for name, (c, ns) in table.items()},
        "idle_gaps": [[start, dur / 1e9] for start, dur in gaps[:TOP]],
    }


def _merged(summaries, n: int) -> dict:
    merged: dict = {}
    for summary in summaries:
        for name, row in summary["ops"].items():
            into = merged.setdefault(name, {"count": 0, "seconds": 0.0})
            into["count"] += row["count"] / n
            into["seconds"] += row["seconds"] / n
    return merged


def summarize(planes: dict) -> dict:
    per_plane = {name: summarize_plane(ops) for name, ops in planes.items()
                 if not name.startswith(MODULES_KEY)}
    modules = [summarize_plane(ops) for name, ops in planes.items()
               if name.startswith(MODULES_KEY)]
    n = len(per_plane)
    return {
        "planes": per_plane,
        "device_planes": sorted(per_plane),
        "stand_in": list(per_plane) == ["host-stand-in"],
        # averaged over the chips used
        "busy_s": sum(p["busy_s"] for p in per_plane.values()) / n
        if n else 0.0,
        "ops": _merged(per_plane.values(), n),
        "modules": _merged(modules, n),
    }


def main(argv=None) -> int:
    trace_dir, out_path = (argv or sys.argv[1:])[:2]
    xplane = find_xplane(trace_dir)
    if xplane is None:
        print(f"no .xplane.pb under {trace_dir}", file=sys.stderr)
        return 1
    summary = summarize(extract(xplane))
    summary["xplane_bytes"] = os.path.getsize(xplane)
    with open(out_path, "w") as f:
        json.dump(summary, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
