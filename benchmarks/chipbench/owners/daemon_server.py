"""The other layout (README, "One owner per chip"): the kernel-server
daemon holds the chip, the Bolt server runs on the CPU backend and
routes every analytics CALL to it over the daemon's unix socket.

Two processes of the program's own entry points. ``daemon_owner.py`` is
``python -m memgraph_tpu.server.kernel_server`` untouched, with the
benchmark's switch on a side thread; it is started first and waited
for. The Bolt server is ``python -m memgraph_tpu.main`` itself, with
``JAX_PLATFORMS=cpu`` and ``MEMGRAPH_TPU_ANALYTICS_KERNEL_SERVER`` set
to the daemon's socket. Clients speak Bolt to that one only.

The handle is ``owners/inproc_server.py``'s (its docstring has the
contract). What differs, because the chip's holder is not the Bolt
server:

  device(c)     the daemon's report, through its request files: the
                Bolt server would report the CPU it was given
  ask(op, ...)  answered by the daemon's process: it has the device to
                trace and the memory to read
  stats()       the Bolt server's GET /stats under the paths the default
                layout gives them (``device/span.bolt.run.seconds_total``
                ...), so the accepted metric files read them as they
                are; its ``analytics.*`` counters (the route's: routed,
                fallbacks), which no section of ``/stats`` holds, from
                ``GET /metrics`` as ``server/<counter>``; the daemon's
                counters (its ``health`` reply's) as
                ``daemon/<counter>``, and the reply's other numbers as
                ``daemon/health/...`` (``graphs_cached``,
                ``memory/modeled_peak_bytes``). A reading that finds
                a fallback fails the run: that CALL ran the MXU plan
                on the Bolt server's CPU backend, so the run's times
                are another deployment's, however right its rows
  log_tail()    both logs, and the ``<socket>.log`` a daemon spawned by
                the program would write

Both processes run in the work directory and name the socket by its
relative path: a unix socket's path holds 107 bytes, and a driver's
``TMPDIR`` can be longer than that.

The configuration's ``owner``: ``server_flags`` / ``env`` are the Bolt
server's, ``daemon_flags`` / ``daemon_env`` the daemon's. A control's
``owner_env`` goes to the daemon: it is the chip's owner.
"""

from __future__ import annotations

import json
import os
import sys
import urllib.request

import procs

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SOCKET = "kernel_server.sock"           # relative to the work directory
FALLBACKS = "server/analytics.kernel_route_fallback_total"
DAEMON_UP_S = 180.0


class Layout:
    def __init__(self, config: dict, workdir: str, extra_env: dict | None):
        owner = config["owner"]
        base = dict(os.environ)
        base["PYTHONPATH"] = procs.REPO + os.pathsep \
            + base.get("PYTHONPATH", "")
        self.workdir = workdir
        ctl = os.path.join(workdir, "ctl")
        os.makedirs(ctl, exist_ok=True)

        # the chip's holder: JAX picks its default backend, as in the
        # default layout; it must not route to itself
        env = dict(base)
        env.pop("MEMGRAPH_TPU_ANALYTICS_KERNEL_SERVER", None)
        env.update(owner.get("daemon_env", {}))
        env.update(extra_env or {})
        self.daemon_log = os.path.join(workdir, "daemon.log")
        self.daemon = procs.spawn(
            [sys.executable, os.path.join(HERE, "daemon_owner.py"),
             "--ctl", ctl, "--", "--socket", SOCKET]
            + list(owner.get("daemon_flags", [])),
            env, self.daemon_log, workdir)
        self.ctl = procs.CtlFiles(ctl, self.daemon)
        # waited for: the answer comes once the daemon serves its socket
        try:
            self._device = self.ctl.ask("device", DAEMON_UP_S)
        except procs.RunFailure as e:
            raise procs.RunFailure(
                f"{e}\n--- daemon.log ---\n"
                f"{procs.tail(self.daemon_log)}") from e
        procs.say(f"kernel-server daemon up: {self._device}")

        env = dict(base, JAX_PLATFORMS="cpu",
                   MEMGRAPH_TPU_ANALYTICS_KERNEL_SERVER=SOCKET)
        env.update(owner.get("env", {}))
        self.bolt, self.metrics = procs.free_port(), procs.free_port()
        self.server_log = os.path.join(workdir, "server.log")
        self.server = procs.spawn(
            [sys.executable, "-m", "memgraph_tpu.main",
             "--bolt-port", str(self.bolt),
             "--metrics-port", str(self.metrics),
             "--data-directory", os.path.join(workdir, "data")]
            + list(owner["server_flags"]),
            env, self.server_log, workdir)

    def port(self, client_index: int) -> int:
        return self.bolt

    def alive(self) -> bool:
        return self.daemon.poll() is None and self.server.poll() is None

    def device(self, client) -> dict:
        _, rows, _ = client.execute("SHOW BUILD INFO")
        info = {k: v for k, v in rows}
        procs.say(f"Bolt server's SHOW BUILD INFO: {info}")
        if info.get("device_platform") != "cpu":
            raise procs.RunFailure(
                f"the Bolt server of the daemon layout runs on the CPU "
                f"backend; it reports {info.get('device_platform')!r}")
        return {key: self._device[key]
                for key in ("platform", "kind", "count")}

    def ask(self, op: str, timeout_s: float = 60.0, **fields) -> dict:
        return self.ctl.ask(op, timeout_s, **fields)

    def stats(self) -> dict:
        flat = procs.flat_stats(self.metrics)
        # the route's own counters are in no section of GET /stats
        with urllib.request.urlopen(
                f"http://127.0.0.1:{self.metrics}/metrics?format=json",
                timeout=60) as r:
            flat.update((f"server/{name}", float(value))
                        for name, value in json.load(r).items()
                        if name.startswith("analytics."))
        # the Bolt server is this run's own, so the counter starts at 0
        if flat.get(FALLBACKS, 0.0) > 0:
            raise procs.RunFailure(
                f"{flat[FALLBACKS]:g} analytics CALL(s) fell back to the "
                f"Bolt server's CPU backend with the daemon "
                f"{'alive' if self.daemon.poll() is None else 'gone'}: "
                f"not a run of this deployment\n{self.log_tail()}")
        health = self.ctl.ask("health")
        counters = health.pop("counters", {})
        flat.update((f"daemon/{name}", float(value))
                    for name, value in counters.items()
                    if isinstance(value, (int, float)))
        flat.update(procs.flatten({"daemon": {"health": health}}))
        return flat

    def log_tail(self) -> str:
        socket_log = os.path.join(self.workdir, SOCKET + ".log")
        return "\n".join(
            f"--- {os.path.basename(path)} ---\n{procs.tail(path)}"
            for path in (self.server_log, self.daemon_log, socket_log))

    def stop(self) -> list:
        return [procs.stop_child(self.server),
                procs.stop_child(self.daemon)]


def start(config: dict, chips: int, workdir: str,
          extra_env: dict | None = None) -> Layout:
    return Layout(config, workdir, extra_env)
