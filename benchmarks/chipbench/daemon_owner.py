"""The chip's holder of the daemon layout: the program's kernel-server
daemon, untouched, with the benchmark's switch beside it.

    python benchmarks/chipbench/daemon_owner.py --ctl <dir> -- --socket <path> [daemon flags]

Calls ``memgraph_tpu.server.kernel_server.main()`` on the main thread,
exactly as ``python -m memgraph_tpu.server.kernel_server`` does. One
side thread serves the parent's requests (``owner.py``'s files: the
parent writes ``<dir>/req.json``, the answer is ``<dir>/ack_<n>.json``),
because only the process that holds the chip can trace it, read its
memory or say what it counted:

  trace_start, trace_stop, memory   as ``owner.py`` answers them
  device    {"platform", "kind", "count"}: the platform the daemon's
            own ``health`` reports once it serves, and this process's
            ``jax.devices()``
  health    the daemon's ``health`` reply (its counters, its resident
            generations), asked over its own socket
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import owner  # noqa: E402

UP_TIMEOUT_S = 170.0


def _health(socket_path: str, wait_s: float = 0.0) -> dict:
    """The daemon's own report, over a connection of this request's.
    Waits up to `wait_s` for the socket to be served: the daemon binds
    it only after JAX has reached the device."""
    from memgraph_tpu.server.kernel_server import KernelClient
    deadline = time.monotonic() + wait_s
    while True:
        try:
            client = KernelClient(socket_path, timeout=60.0)
            break
        except OSError:
            if time.monotonic() > deadline:
                raise
            time.sleep(0.05)
    try:
        return client.health()
    finally:
        client.close()


def _answer(op: str, req: dict, socket_path: str) -> dict:
    if op == "health":
        return _health(socket_path)
    if op == "device":
        health = _health(socket_path, UP_TIMEOUT_S)
        import jax
        devices = jax.devices()
        return {"platform": health["platform"],
                "kind": devices[0].device_kind, "count": len(devices),
                "pid": health["pid"]}
    return owner._answer(op, req)


def serve_requests(ctl_dir: str, stop: threading.Event,
                   socket_path: str) -> None:
    """``owner.serve_requests`` with this module's answers (that one
    takes no answer function, and a PR that adds a deployment edits no
    file the benchmark has)."""
    req_path = os.path.join(ctl_dir, "req.json")
    while not stop.is_set():
        try:
            with open(req_path) as f:
                req = json.load(f)
        except (OSError, ValueError):       # none yet, or half renamed
            time.sleep(owner.POLL_S)
            continue
        os.unlink(req_path)
        try:
            ack = _answer(req["op"], req, socket_path)
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
    ap.add_argument("daemon_argv", nargs=argparse.REMAINDER)
    args = ap.parse_args(argv)
    daemon_argv = [a for a in args.daemon_argv if a != "--"]
    socket_path = daemon_argv[daemon_argv.index("--socket") + 1]

    from memgraph_tpu.server.kernel_server import main as daemon_main
    stop = threading.Event()
    side = threading.Thread(target=serve_requests, name="chipbench-ctl",
                            args=(args.ctl, stop, socket_path), daemon=True)
    side.start()
    sys.argv = ["memgraph_tpu.server.kernel_server"] + daemon_argv
    try:
        daemon_main()
        return 0
    finally:
        stop.set()
        side.join(5)


if __name__ == "__main__":
    sys.exit(main())
