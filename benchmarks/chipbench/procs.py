"""What every owner layout (``owners/<kind>.py``) starts, talks to and
stops its processes with. ``run.py`` uses the same functions, so a child
that any layout starts is one ``stop_all`` ends.

Never imports jax: a parent that has touched JAX holds the chip.
"""

from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import time
import urllib.request

#: the checkout's root: where ``memgraph_tpu`` is imported from
REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

#: every child a layout has started and not yet stopped
CHILDREN: list = []

#: the sections of the program's ``GET /stats`` that hold counters
STATS_SECTIONS = ("device", "delta", "lane", "ppr")


class RunFailure(Exception):
    """The run cannot give a result (no chip, a child that died, ...)."""


def say(msg: str) -> None:
    print(msg, flush=True)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def tail(path: str, n: int = 3000) -> str:
    try:
        with open(path, "rb") as f:
            f.seek(0, os.SEEK_END)
            f.seek(max(0, f.tell() - n))
            return f.read().decode(errors="replace")
    except OSError:
        return ""


def spawn(args: list, env: dict, log_path: str, cwd: str):
    """Start one child in a session of its own, its output appended to
    `log_path`, and remember it until `stop_child` has ended it."""
    with open(log_path, "ab") as log:
        p = subprocess.Popen(args, cwd=cwd, env=env, stdout=log,
                             stderr=subprocess.STDOUT,
                             start_new_session=True)
    CHILDREN.append(p)
    return p


def connect(port: int, alive, timeout_s: float = 180.0):
    """A Bolt client on `port`, waiting for the server to come up while
    `alive()` says its process still runs."""
    from memgraph_tpu.server.client import BoltClient
    deadline = time.monotonic() + timeout_s
    while True:
        try:
            return BoltClient(port=port, timeout=900.0)
        except OSError:
            if not alive() or time.monotonic() > deadline:
                raise RunFailure("the Bolt server did not come up")
            time.sleep(0.1)


def stop_child(p, grace_s: float = 60.0) -> int:
    """SIGTERM, wait, then kill the group; returns the exit code."""
    if p.poll() is None:
        p.terminate()
        try:
            p.wait(grace_s)
        except subprocess.TimeoutExpired:
            pass
    try:
        os.killpg(p.pid, signal.SIGKILL)    # stragglers of the group
    except (ProcessLookupError, PermissionError):
        pass
    rc = p.wait(30)
    if p in CHILDREN:
        CHILDREN.remove(p)
    return rc


def stop_all() -> None:
    for p in list(CHILDREN):
        try:
            stop_child(p, grace_s=5.0)
        except (OSError, subprocess.TimeoutExpired):
            pass


def flatten(stats: dict, sections=None) -> dict:
    """Nested counters to {"section/.../name": number}; with `sections`,
    only those top-level keys."""
    flat: dict = {}

    def walk(prefix, node):
        if isinstance(node, dict):
            for key, value in node.items():
                walk(f"{prefix}/{key}" if prefix else key, value)
        elif isinstance(node, (int, float)) and not isinstance(node, bool):
            flat[prefix] = float(node)

    for section in stats if sections is None else sections:
        walk(section, stats.get(section, {}))
    return flat


def flat_stats(metrics_port: int) -> dict:
    """The program's GET /stats, flattened."""
    with urllib.request.urlopen(
            f"http://127.0.0.1:{metrics_port}/stats", timeout=60) as r:
        return flatten(json.load(r), STATS_SECTIONS)


class CtlFiles:
    """The parent's side of owner.py's request files: `ask` writes
    ``<ctl>/req.json`` and waits for ``<ctl>/ack_<n>.json``, which the
    side thread of the process that holds the chip writes."""

    def __init__(self, ctl_dir: str, child):
        self.ctl = ctl_dir
        self.child = child
        self.seq = 0

    def ask(self, op: str, timeout_s: float = 60.0, **fields) -> dict:
        self.seq += 1
        tmp = os.path.join(self.ctl, ".req.tmp")
        with open(tmp, "w") as f:
            json.dump(dict(fields, seq=self.seq, op=op), f)
        os.replace(tmp, os.path.join(self.ctl, "req.json"))
        ack_path = os.path.join(self.ctl, f"ack_{self.seq}.json")
        deadline = time.monotonic() + timeout_s
        while not os.path.exists(ack_path):
            if self.child.poll() is not None:
                raise RunFailure(f"the owner exited before answering {op}")
            if time.monotonic() > deadline:
                raise RunFailure(f"the owner did not answer {op} "
                                 f"within {timeout_s:.0f} s")
            time.sleep(0.005)
        with open(ack_path) as f:
            ack = json.load(f)
        if "error" in ack:
            raise RunFailure(f"the owner could not {op}: {ack['error']}")
        return ack
