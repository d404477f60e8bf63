"""The chip owner of one benchmark run: the program's Bolt server,
untouched, with the benchmark's profiler switch beside it.

    python benchmarks/chipbench/owner.py --ctl <dir> -- <memgraph_tpu.main flags>

Calls ``memgraph_tpu.main.main(argv)`` on the main thread, exactly as
``python -m memgraph_tpu.main`` does. One side thread serves the
parent's requests, because only the process that holds the chip can
trace it or read its memory. The parent writes ``<dir>/req.json``
(``{"seq": n, "op": ...}``); the answer is ``<dir>/ack_<n>.json``.

  trace_start {"dir": path}   jax.profiler.start_trace (no Python tracer)
  trace_stop                  jax.profiler.stop_trace; the xplane is on disk
  memory                      peak_bytes_in_use of the fullest local device
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

POLL_S = 0.02


def _answer(op: str, req: dict) -> dict:
    import jax
    if op == "trace_start":
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(req["dir"], profiler_options=options)
        return {"started_ns": time.time_ns()}
    if op == "trace_stop":
        stopped_ns = time.time_ns()
        jax.profiler.stop_trace()
        return {"stopped_ns": stopped_ns}
    if op == "memory":
        peaks = []
        for device in jax.local_devices():
            stats = device.memory_stats() or {}
            peaks.append(stats.get("peak_bytes_in_use"))
        known = [p for p in peaks if p is not None]
        return {"memory_peak_bytes": max(known) if known else None}
    raise ValueError(f"no op named {op!r}")


def serve_requests(ctl_dir: str, stop: threading.Event) -> None:
    req_path = os.path.join(ctl_dir, "req.json")
    while not stop.is_set():
        try:
            with open(req_path) as f:
                req = json.load(f)
        except (OSError, ValueError):       # none yet, or half renamed
            time.sleep(POLL_S)
            continue
        os.unlink(req_path)
        try:
            ack = _answer(req["op"], req)
        except Exception as e:  # noqa: BLE001 — reported to the parent
            ack = {"error": f"{type(e).__name__}: {e}"}
        tmp = os.path.join(ctl_dir, f".ack_{req['seq']}.tmp")
        with open(tmp, "w") as f:
            json.dump(ack, f)
        os.replace(tmp, os.path.join(ctl_dir, f"ack_{req['seq']}.json"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--ctl", required=True,
                    help="directory of the parent's requests")
    ap.add_argument("server_argv", nargs=argparse.REMAINDER)
    args = ap.parse_args(argv)
    server_argv = [a for a in args.server_argv if a != "--"]

    from memgraph_tpu.main import main as server_main
    stop = threading.Event()
    side = threading.Thread(target=serve_requests, args=(args.ctl, stop),
                            name="chipbench-ctl", daemon=True)
    side.start()
    try:
        return server_main(server_argv)
    finally:
        stop.set()
        side.join(5)


if __name__ == "__main__":
    sys.exit(main())
