"""Monitoring HTTP endpoint: Prometheus metrics + JSON status + traces
+ workload stats + readiness.

Counterpart of the reference's metrics/monitoring servers
(/root/reference/src/glue/PrometheusServerT.cpp, src/http_handlers/):
GET /metrics → Prometheus text; GET /status → JSON storage info;
GET /traces → retained mgtrace traces (JSON), ?format=chrome for
Chrome-trace-event JSON loadable in Perfetto, ?trace_id=<id> to fetch
the one trace a slow-query log line names; GET /stats → per-fingerprint
workload statistics (mgstat top-K, linked trace_ids, plan-cache hit
counts); GET /health → the saturation plane's readiness verdict —
HTTP 200 when ready, 503 with machine-readable reasons when any bounded
resource is saturated (the shape load balancers and admission control
consume).
"""

from __future__ import annotations

import asyncio
import json

from . import stats as mgstats
from . import trace as mgtrace
from .metrics import global_metrics


def _lane_stats() -> dict:
    """Compiled-read-lane residency table (import deferred: the lane
    lives in ops/, which must not load just to serve /metrics)."""
    try:
        from ..ops.pipeline import lane_stats
        return lane_stats()
    except Exception as e:  # noqa: BLE001 — stats must never break /stats
        import logging
        logging.getLogger(__name__).debug("lane stats unavailable: %s", e)
        return {"resident_programs": 0, "fingerprints": {}}


async def start_monitoring_server(host: str, port: int, ictx):
    async def handle(reader, writer):
        try:
            request = await asyncio.wait_for(reader.readline(), timeout=5)
            while True:
                line = await asyncio.wait_for(reader.readline(), timeout=5)
                if line in (b"\r\n", b"\n", b""):
                    break
            path = request.split()[1].decode() if request.split() else "/"
            status = "200 OK"
            if path.startswith("/metrics"):
                # --metrics-format picks the default payload; the
                # /metrics?format= query overrides per request
                fmt = ictx.config.get("metrics_format", "PROMETHEUS")
                if "format=json" in path.lower():
                    fmt = "JSON"
                elif "format=prometheus" in path.lower():
                    fmt = "PROMETHEUS"
                if fmt == "JSON":
                    body = json.dumps({
                        name: value for name, _k, value
                        in global_metrics.snapshot()})
                    ctype = "application/json"
                else:
                    body = global_metrics.prometheus_text()
                    ctype = "text/plain; version=0.0.4"
            elif path.startswith("/traces"):
                trace_id = None
                if "trace_id=" in path:
                    trace_id = path.split("trace_id=", 1)[1] \
                        .split("&", 1)[0]
                if "format=chrome" in path.lower():
                    body = json.dumps(mgtrace.chrome_trace(
                        mgtrace.traces_json(trace_id)))
                else:
                    body = json.dumps({
                        "armed": mgtrace.armed(),
                        "counts": mgtrace.TRACER.counts(),
                        "traces": mgtrace.traces_json(trace_id)},
                        default=str)
                ctype = "application/json"
            elif path.startswith("/stats"):
                # exception-flow contract surface (mgflow): refresh the
                # registry gauges on read — static by construction,
                # they move only when flowspec.py itself changes
                from ..flowspec import flow_stats
                flow = flow_stats()
                global_metrics.set_gauge("mgflow.contract_roots",
                                         float(flow["contract_roots"]))
                global_metrics.set_gauge("mgflow.escapes_total",
                                         float(flow["escapes_total"]))
                # mgstat workload statistics: bounded top-K fingerprints
                # with latency quantiles, error/plan-cache-hit counts,
                # and the retained trace_ids each shape links to
                body = json.dumps({
                    "enabled": mgstats.global_query_stats.enabled(),
                    "capacity": mgstats.global_query_stats.capacity,
                    "fingerprints": mgstats.global_query_stats.snapshot(),
                    # PPR serving plane: coalescing/cache counters
                    # (local, plus the daemon's mirrored gauges)
                    "ppr": {name: value for name, _k, value
                            in global_metrics.snapshot()
                            if name.startswith(
                                ("ppr.", "kernel_server.daemon.ppr."))},
                    # the chip owner's plane: the compile witness
                    # (jit.*), the MXU program table's hits and misses
                    # (mxu.*), in-process fixpoint iterations (device.*),
                    # the analytics procedures' calls, iterations and
                    # routing (analytics.*) and every phase span's
                    # seconds and closes (span.*), with the two counters
                    # that say which operator the query.sort span's
                    # closes were (TopK or OrderBy) and the CALLs that
                    # yielded a TopK's bound only
                    "device": {name: value for name, _k, value
                               in global_metrics.snapshot()
                               if name.startswith(
                                   ("jit.", "mxu.", "device.", "span.",
                                    "analytics.", "query.topk_",
                                    "query.sort_full_"))},
                    # incremental analytics plane (r19, mgdelta):
                    # delta applies/compactions/fallbacks, warm-start
                    # counters, resident-generation gauge (local plus
                    # the daemon's counters mirrored through health)
                    "delta": {name: value for name, _k, value
                              in global_metrics.snapshot()
                              if name.startswith(
                                  ("delta.",
                                   "kernel_server.daemon.delta."))},
                    # sharded OLTP execution plane (r18, mgshard):
                    # per-shard ops/latency/queue-depth, 2PC counters,
                    # move durations, routing-table epoch
                    "sharding": {name: value for name, _k, value
                                 in global_metrics.snapshot()
                                 if name.startswith("shard.")},

                    # out-of-core streamed tier (r21, mgtier):
                    # admission verdicts, blocks/bytes streamed,
                    # compression + overlap histograms (local plus the
                    # daemon's counters mirrored through health)
                    "tier": {name: value for name, _k, value
                             in global_metrics.snapshot()
                             if name.startswith(
                                 ("tier.",
                                  "kernel_server.daemon.tier."))},
                    # streaming ingestion plane (r17, mgstream):
                    # batch/record counters, redeliveries, dead-letter
                    # quarantine, backpressure pauses, per-stream lag
                    # gauges — plus the trigger firing/error counters
                    # that ride the same ingest path
                    "streams": {name: value for name, _k, value
                                in global_metrics.snapshot()
                                if name.startswith(
                                    ("stream.", "trigger."))},
                    # device memory accounting plane (mgmem): the
                    # admission budget vs the modeled resident peak —
                    # the headroom capacity planning reads (local
                    # gauges plus the daemon's mirror through health)
                    "memory": {name: value for name, _k, value
                               in global_metrics.snapshot()
                               if name.startswith(
                                   ("kernel_server.hbm_",
                                    "kernel_server.daemon.hbm_"))},
                    # compiled Cypher read lane (r20, mglane):
                    # compile/hit/typed-fallback counters plus the
                    # per-fingerprint lane residency table
                    "lane": dict(_lane_stats(), metrics={
                        name: value for name, _k, value
                        in global_metrics.snapshot()
                        if name.startswith("lane.")}),
                    # exception-flow contracts (mgflow, r24): the
                    # declared serving-root contracts and wire ids —
                    # the surface `python -m tools.mgflow check` gates
                    "flow": flow},
                    default=str)
                ctype = "application/json"
            elif path.startswith("/health"):
                verdict = mgstats.global_saturation.evaluate(ictx)
                if not verdict["ready"]:
                    status = "503 Service Unavailable"
                body = json.dumps(verdict, default=str)
                ctype = "application/json"
            else:
                info = dict(ictx.storage.info())
                with ictx._rq_lock:
                    info["running_queries"] = len(ictx.running_queries)
                body = json.dumps(info)
                ctype = "application/json"
            payload = body.encode("utf-8")
            writer.write(
                f"HTTP/1.1 {status}\r\n".encode()
                + f"Content-Type: {ctype}\r\n".encode()
                + f"Content-Length: {len(payload)}\r\n".encode()
                + b"Connection: close\r\n\r\n" + payload)
            await writer.drain()
        except (OSError, ValueError):
            # OSError: client went away mid-response. ValueError: a
            # stats payload json.dumps refused (circular/oversized
            # object) — drop this response, never the serving task
            pass
        finally:
            writer.close()

    return await asyncio.start_server(handle, host, port)
