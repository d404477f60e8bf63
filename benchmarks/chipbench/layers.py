"""The readers of per-layer metrics, one per source kind. A metric is
``layer_metrics/<name>.json``: its kind and that kind's parameters. A
reader that finds nothing to read returns None and the harness leaves
the metric out of the line; it never returns 0 for a share.

  trace_idle      100 * (1 - device busy / traced slice)
  trace_ops       seconds of the rows of "table" ("ops": device ops,
                  "modules": jitted programs) whose names match, per cycle
  trace_roofline  least seconds for the traced iterations / their ops' seconds;
                  the iterations are the delta of the program's counters
                  across the trace slice ("iterations_counter"), or else
                  the runs of an op the compiler named ("once_per_iteration")
  stats_delta     a ratio of /stats counter deltas over the window
  client_class    the median of the named classes' request times
"""

from __future__ import annotations

import fnmatch
import statistics

import seams


def _matching(ops: dict, patterns: list) -> dict:
    return {name: row for name, row in ops.items()
            if any(fnmatch.fnmatchcase(name, p) for p in patterns)}


def _device_trace(ctx):
    trace = ctx.get("trace")
    if not trace or trace.get("stand_in") or not trace.get("device_planes"):
        return None
    return trace


def trace_idle(params, ctx):
    trace = _device_trace(ctx)
    if trace is None or not ctx.get("trace_window_s"):
        return None
    return 100.0 * (1.0 - trace["busy_s"] / ctx["trace_window_s"])


def trace_ops(params, ctx):
    trace = _device_trace(ctx)
    if trace is None:
        return None
    rows = _matching(trace[params["table"]], params["patterns"])
    cycles = ctx.get("traced_cycles")
    if not rows or not cycles:
        return None
    return params.get("scale", 1.0) * sum(
        r["seconds"] for r in rows.values()) / cycles


def _counter_sum(stats: dict, names: list) -> float:
    return sum(value for key, value in stats.items()
               if any(fnmatch.fnmatchcase(key, n) for n in names))


def _traced_iterations(params, ctx, trace) -> float:
    """The iterations whose device time the trace holds: by the named
    counters where the metric names them, which count the same work
    whatever the compiler calls its ops; else by the runs of the op the
    loop body makes once. A counter named where the slice has no
    snapshots counts nothing."""
    names = params.get("iterations_counter")
    if names is None:
        ticks = _matching(trace["ops"], params["once_per_iteration"])
        return sum(r["count"] for r in ticks.values())
    before = ctx.get("trace_stats_before")
    after = ctx.get("trace_stats_after")
    if before is None or after is None:
        return 0
    return _counter_sum(after, names) - _counter_sum(before, names)


def trace_roofline(params, ctx):
    trace = _device_trace(ctx)
    if trace is None:
        return None
    rows = _matching(trace[params["table"]], params["patterns"])
    seconds = sum(r["seconds"] for r in rows.values())
    iterations = _traced_iterations(params, ctx, trace)
    if not seconds or iterations <= 0:
        return None
    module = seams.load_module(ctx.get("dirs"), "rooflines",
                               params["roofline"])
    least = module.least_seconds(ctx["n_nodes"], ctx["n_edges"], iterations,
                                 ctx["peak"])
    return 100.0 * least["seconds"] / seconds


def stats_delta(params, ctx):
    before, after = ctx.get("stats_before"), ctx.get("stats_after")
    if before is None or after is None:
        return None

    def delta(names):
        if isinstance(names, str):                   # "cycles", "requests"
            value = ctx.get(names) or 0
            return float(len(value) if isinstance(value, list) else value)
        return _counter_sum(after, names) - _counter_sum(before, names)

    bottom = delta(params["denominator"])
    if not bottom:
        return None
    return params.get("scale", 1.0) * delta(params["numerator"]) / bottom


def client_class(params, ctx):
    times = [r.end - r.start for r in ctx["requests"]
             if r.name in params["classes"] and r.ok]
    if len(times) < 2:
        return None
    return 1000.0 * statistics.median(times)


READERS = {"trace_idle": trace_idle, "trace_ops": trace_ops,
           "trace_roofline": trace_roofline, "stats_delta": stats_delta,
           "client_class": client_class}


def read(metric: dict, ctx: dict):
    return READERS[metric["kind"]](metric.get("params", {}), ctx)
