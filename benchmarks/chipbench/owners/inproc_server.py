"""The default layout: one process owns the chip and serves Bolt.

``owner.py`` (the program's server, untouched, with the profiler switch
on a side thread) is the only process. Every client connects to it, it
reports the device through ``SHOW BUILD INFO``, it answers
``trace_start`` / ``trace_stop`` / ``memory`` through the request files
of its ``--ctl`` directory, and its ``GET /stats`` holds the counters.

A layout is a module with ``start(config, chips, workdir, extra_env)``
that starts the deployment's processes (``procs.spawn``, so that
``procs.stop_all`` ends them whatever happens) and returns the handle
``run.py`` drives:

  port(i)       the Bolt port client number i connects to
  alive()       whether every process still runs
  device(c)     {"platform", "kind", "count"} as the process that holds
                the chip(s) reports them: what ``require_tpu`` judges.
                `c` is client 0's Bolt connection, up and not yet used,
                for a layout whose Bolt server is that process
  ask(op, ...)  ``trace_start`` (dir=), ``trace_stop``, ``memory``,
                answered by that same process
  stats()       {"section/.../name": number} of every process that
                counts something
  log_tail()    the end of the processes' output, for a failure
  stop()        ends every process; returns their exit codes
"""

from __future__ import annotations

import os
import sys

import procs

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class Layout:
    def __init__(self, config: dict, workdir: str, extra_env: dict | None):
        """The owner's environment is the one given (JAX picks its
        default backend; the compile cache goes where
        JAX_COMPILATION_CACHE_DIR says, else <checkout>/.jax_cache),
        minus the switch that would route analytics to a daemon."""
        env = dict(os.environ)
        env.pop("MEMGRAPH_TPU_ANALYTICS_KERNEL_SERVER", None)
        env["PYTHONPATH"] = procs.REPO + os.pathsep + env.get("PYTHONPATH", "")
        env.update(config["owner"].get("env", {}))
        env.update(extra_env or {})
        ctl = os.path.join(workdir, "ctl")
        os.makedirs(ctl, exist_ok=True)
        self.bolt, self.metrics = procs.free_port(), procs.free_port()
        self.log = os.path.join(workdir, "owner.log")
        args = [sys.executable, os.path.join(HERE, "owner.py"), "--ctl", ctl,
                "--", "--bolt-port", str(self.bolt),
                "--metrics-port", str(self.metrics),
                "--data-directory", os.path.join(workdir, "data")] \
            + list(config["owner"]["server_flags"])
        self.child = procs.spawn(args, env, self.log, procs.REPO)
        self.ctl = procs.CtlFiles(ctl, self.child)

    def port(self, client_index: int) -> int:
        return self.bolt

    def alive(self) -> bool:
        return self.child.poll() is None

    def device(self, client) -> dict:
        _, rows, _ = client.execute("SHOW BUILD INFO")
        info = {k: v for k, v in rows}
        procs.say(f"SHOW BUILD INFO: {info}")
        return {"platform": info.get("device_platform"),
                "kind": info.get("device_kind"),
                "count": info.get("device_count")}

    def ask(self, op: str, timeout_s: float = 60.0, **fields) -> dict:
        return self.ctl.ask(op, timeout_s, **fields)

    def stats(self) -> dict:
        return procs.flat_stats(self.metrics)

    def log_tail(self) -> str:
        return procs.tail(self.log)

    def stop(self) -> list:
        return [procs.stop_child(self.child)]


def start(config: dict, chips: int, workdir: str,
          extra_env: dict | None = None) -> Layout:
    return Layout(config, workdir, extra_env)
