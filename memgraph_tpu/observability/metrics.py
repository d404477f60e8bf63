"""Counters / gauges / histograms registry.

Counterpart of the reference's metrics layer
(/root/reference/src/metrics/prometheus_metrics.hpp): named counters with
types, snapshot for SHOW METRICS INFO, Prometheus text exposition for the
monitoring endpoint.

r13 (mgtrace): ``observe()`` now records into a REAL histogram — fixed
exponential buckets with correct cumulative Prometheus exposition
(``_bucket{le=...}`` monotone, ``+Inf`` bucket == ``_count``) instead of
the windowed-summary approximation, so p50/p99 survive scrape-side
``histogram_quantile()`` and rate() math. Latency observations taken
inside an armed trace carry the trace id as an OpenMetrics exemplar, so
a p99 spike links straight to a retained trace in /traces.
"""

from __future__ import annotations

import bisect
import re
import time
from collections import defaultdict

from ..utils.locks import tracked_rlock
from ..utils.sanitize import shared_field, shared_read, shared_write

_NAME_BAD = re.compile(r"[^a-zA-Z0-9_:]")


def _promname(name: str) -> str:
    """Prometheus metric-name sanitization: every invalid character maps
    to '_' and a leading digit gets a '_' prefix (names like
    "edge_count[Knows]" must not produce an unparseable exposition)."""
    out = _NAME_BAD.sub("_", name)
    if out and out[0].isdigit():
        out = "_" + out
    return out


def _promlabel(value: str) -> str:
    """Prometheus label-VALUE escaping (backslash, quote, newline)."""
    return str(value).replace("\\", "\\\\").replace('"', '\\"') \
        .replace("\n", "\\n")


#: fixed exponential bucket bounds (seconds): 100µs .. ~1677s, factor 2.
#: One shared layout for every histogram keeps exposition predictable
#: and cross-metric comparisons honest.
DEFAULT_BUCKETS = tuple(0.0001 * (2 ** i) for i in range(24))

#: Every metric name product code may emit through ``global_metrics``
#: (r14, mgstat). Entries ending in ``*`` declare a dynamic FAMILY whose
#: members share the literal prefix (``operator.*`` covers
#: ``operator.ScanAll`` etc.). mglint MG005 (stat-registry) statically
#: enforces that (a) every literal name passed to increment()/
#: set_gauge()/observe() appears here (or matches a family), (b) every
#: f-string name's literal prefix matches a declared family, (c) every
#: declared name/family has at least one live emit site, and (d) no
#: name is declared twice — a typo'd metric silently splits a series,
#: and a dead registration means dashboards "cover" a metric that can
#: never move.
STAT_NAMES = (
    # query engine
    "query.prepared",
    "query.finished",
    "query.execution_latency_sec",
    "operator.*",                  # per-operator completion counters
    "storage.*",                   # per-query write-stat counters
    "mgstat.evictions_total",      # space-saving top-K evictions
    # ORDER BY, by the operator that ran it (plan/operators.py)
    "query.topk_total",            # a TopK cursor ran: ORDER BY … LIMIT
    "query.sort_full_total",       # an OrderBy sorted its whole input
    "query.topk_pushdown_total",   # a CALL under a TopK yielded its bound
    # bolt session pool
    "bolt.connections_rejected_total",
    "bolt.sessions_live",
    "bolt.sessions_max",
    # multiprocess read executor
    "mp_executor.in_flight",
    "mp_executor.workers",
    "mp_executor.errors_total",
    "mp_executor.worker_respawn_total",
    # sharded OLTP execution plane (r18, mgshard)
    "shard.requests_total",
    "shard.scatter_gather_total",
    "shard.stale_epoch_bounces_total",
    "shard.twopc_total",
    "shard.twopc_aborts_total",
    "shard.moves_total",
    "shard.move_duration_sec",
    "shard.map_epoch",              # routing-table fencing epoch gauge
    "shard.worker_respawn_total",
    "shard.write_in_doubt_total",   # writes surfaced as WriteInDoubtError
    "shard.ops.*",                  # per-shard routed-op counters
    "shard.op_latency_sec.*",       # per-shard latency histograms
    "shard.queue_depth.*",          # per-shard in-flight gauges
    # kernel server (local process + mirrored daemon state)
    "kernel_server.dispatch.*",    # typed per-outcome dispatch counters
    "kernel_server.daemon.*",      # daemon counters mirrored as gauges
    "kernel_server.admission_rejected_total",
    "kernel_server.dispatch_latency_sec",
    "kernel_server.in_flight",
    "kernel_server.hbm_budget_bytes",
    "kernel_server.hbm_modeled_peak_bytes",
    "kernel_server.supervisor.health_checks_total",
    "kernel_server.supervisor.wedge_detected_total",
    "kernel_server.supervisor.restarts_total",
    "kernel_server.client.retries_total",
    # PPR serving plane (r16): coalesced batched multi-source PPR
    "ppr.requests_total",
    "ppr.batches_total",
    "ppr.riders_total",            # members of every executed batch
    "ppr.batch_size",              # histogram of executed batch widths
    "ppr.coalesced_total",         # requests that shared a batch
    "ppr.cache_hit_total",
    "ppr.cache_miss_total",
    "ppr.cache_invalidate_total",
    "ppr.warm_start_total",
    "ppr.shed_total",
    # the cache fill's invalidation sets (_source_neighborhood)
    "ppr.neigh_offsets_total",     # read from the snapshot's host CSR rows
    "ppr.neigh_scan_total",        # offsets built first: one O(E) pass
    "ppr.queue_depth",             # coalescing queue backlog gauge
    "ppr.window_occupancy",        # last batch width / max width gauge
    # device compile plane (r17, mgxla): runtime witness for the static
    # compile budget — every XLA backend compile bumps it (executable
    # loads served from the persistent cache too)
    "jit.compile_total",
    "jit.backend_seconds_total",    # seconds of those compiles and loads
    "jit.cache_miss_total",         # compiles the persistent cache missed
    # the MXU fixpoint's program table (ops/spmv_mxu.py _PROGRAMS): a
    # kernel whose signature was there calls an already-traced program
    "mxu.program_hit_total",
    "mxu.program_miss_total",       # a first-seen signature: trace + load
    # phase spans (mgtrace PHASES): seconds and closes of every phase,
    # armed or not — span.<name>.seconds_total / span.<name>.count
    "span.*",
    # device fixpoints (ops/pagerank.py; the partition-centric PageRank
    # of parallel/distributed.py, the daemon's): iterations run
    "device.fixpoint_iterations_total",
    # compiled Cypher read lane (r20, mglane)
    "lane.compiled_total",          # lane programs compiled (per shape)
    "lane.hit_total",               # queries served from a compiled lane
    "lane.fallback_total.*",        # typed per-reason loud fallbacks
    "lane.compile_latency_sec",     # histogram: per-program compile cost
    "lane.resident",                # resident compiled-programs gauge
    "lane.remote_dispatch_total",   # hop programs routed via kernel srv
    # incremental analytics plane (r19, mgdelta): commit-to-fresh-result
    "delta.applied_total",          # EdgeDelta splices applied
    "delta.compacted_total",        # bounded-accumulation full rebuilds
    "delta.fallback_rebuild_total",  # wrapped log / failed splice colds
    "delta.plan_applied_total",     # in-process CALL: MXU DeltaPlan refresh
    "delta.plan_rebuild_total",     # in-process CALL: full MXU plan build
    "delta.export_applied_total",   # GraphCache miss served by the splice
    "delta.export_rebuild_total",   # GraphCache miss served by a full export
    "delta.columnar_applied_total",  # columnar cache miss served by a patch
    "delta.columnar_rebuild_total",  # columnar cache miss served by a sweep
    "delta.columnar_patch_failed_total",  # of those: a patch that raised
    "delta.vector_applied_total",   # vector-index miss served by a refresh
    "delta.vector_rebuild_total",   # vector-index miss served by a full build
    "delta.edge_count",             # histogram: edges per applied delta
    "delta.warm_start_total",
    "delta.cold_start_total",       # LOUD monotone-unsafe cold starts
    "delta.warm_start_iterations",  # histogram: iterations after warm
    "delta.resident_generations",   # resident graph generations gauge
    # out-of-core streamed tier (r21, mgtier)
    "tier.admission_*",             # resident/streamed/shed verdicts
    "tier.blocks_streamed_total",   # edge blocks shipped host→device
    "tier.bytes_streamed_total",    # int32+f32-equivalent volume swept
    "tier.compressed_bytes_total",  # wire bytes actually shipped
    "tier.blocks_repacked_total",   # delta-spliced rows re-encoded
    "tier.blocks_reused_total",     # rows the splice left untouched
    "tier.modeled_request_bytes",   # admission-estimator price of the run
    "tier.block_transfer_latency_sec",   # histogram: per-block H2D
    "tier.transfer_hidden_fraction",     # histogram: overlap efficiency
    # analytics / checkpoint plane
    "analytics.checkpoint.saved_total",
    "analytics.checkpoint.restored_total",
    "analytics.resume_total",
    "analytics.chunk_deadline_exceeded_total",
    "analytics.resumable_run_seconds",
    "analytics.device_fault.*",    # typed per-kind device-fault counters
    "analytics.kernel_routed_total",
    "analytics.kernel_route_fallback_total",
    # the Graphalytics procedures: one call each, and the iterations its
    # kernel returned (0 for a stored answer)
    "analytics.bfs.calls_total",
    "analytics.bfs.iterations_total",
    "analytics.sssp.calls_total",
    "analytics.sssp.iterations_total",
    "analytics.wcc.calls_total",
    "analytics.wcc.iterations_total",
    "analytics.cdlp.calls_total",
    "analytics.cdlp.iterations_total",
    # label propagation's election by counts (a view without a weight
    # property, ops/labelprop.py): one a call that runs its program
    "analytics.labelprop.count_election_total",
    # streaming ingestion plane (r17, mgstream): supervised exactly-once
    # consumers — transactional offsets, quarantine, backpressure
    "stream.batches_total",         # batches durably committed
    "stream.records_total",         # records durably committed
    "stream.batch_latency_sec",     # histogram: poll→commit per batch
    "stream.redeliveries_total",    # failed batches rolled back for retry
    "stream.dead_letter_total",     # poison batches quarantined
    "stream.reconnects_total",      # RetryPolicy-backed source reconnects
    "stream.poll_errors_total",     # source poll failures (pre-reconnect)
    "stream.ack_failures_total",    # post-commit consumer acks that failed
    "stream.pauses_total",          # backpressure pause transitions
    "stream.paused",                # gauge: 1 while polling is paused
    "stream.lag.*",                 # per-stream source-backlog gauges
    # triggers (fired on the committed delta)
    "trigger.fired_total",
    "trigger.errors_total",         # failing trigger statements (LOUD)
    # durability
    "wal.fsync_latency_sec",
    "wal.fsync_backlog_bytes",
    "wal.segments_rotated",
    "wal.recovery_truncations",
    # replication
    "replication.rpc_failures",
    "replication.ship_latency_sec",
    "replication.fenced_total",
    "replication.strict_sync_demotions",
    "replication.replica_lag.*",       # per-replica txn lag gauges
    "replication.replica_health.*",    # per-replica up/down gauges
    "replication.replica_degraded.*",  # per-replica STRICT_SYNC demotions
    # coordination
    "coordination.current_epoch",
    "coordination.failover_attempts",
    "coordination.failovers_total",
    "coordination.federation_scrapes_total",
    # saturation plane
    "health.ready",
    "health.not_ready_total",
    # exception-flow contracts (mgflow, r24): registry-shape gauges,
    # refreshed on every GET /stats read
    "mgflow.contract_roots",        # serving roots under contract
    "mgflow.escapes_total",         # escape types the contracts admit
)


class Histogram:
    """Fixed-bucket histogram with cumulative exposition + exemplars.

    Not thread-safe on its own — the owning :class:`Metrics` registry
    serializes access under its lock.
    """

    __slots__ = ("bounds", "bucket_counts", "count", "sum", "exemplars")

    def __init__(self, bounds=DEFAULT_BUCKETS) -> None:
        self.bounds = tuple(bounds)
        self.bucket_counts = [0] * (len(self.bounds) + 1)  # last = +Inf
        self.count = 0
        self.sum = 0.0
        #: bucket index -> (value, trace_id, unix_ts) — the latest
        #: traced observation landing in that bucket
        self.exemplars: dict[int, tuple[float, str, float]] = {}

    def observe(self, value: float, trace_id: str | None = None) -> None:
        idx = bisect.bisect_left(self.bounds, value)
        self.bucket_counts[idx] += 1
        self.count += 1
        self.sum += value
        if trace_id:
            self.exemplars[idx] = (value, trace_id, time.time())

    def quantile(self, q: float) -> float:
        """Estimate via linear interpolation inside the hit bucket (the
        same math PromQL's histogram_quantile applies)."""
        if self.count == 0:
            return 0.0
        rank = q * self.count
        seen = 0
        for i, c in enumerate(self.bucket_counts):
            if c == 0:
                continue
            if seen + c >= rank:
                lo = self.bounds[i - 1] if i > 0 else 0.0
                hi = self.bounds[i] if i < len(self.bounds) \
                    else self.bounds[-1] * 2
                frac = (rank - seen) / c
                return lo + (hi - lo) * min(max(frac, 0.0), 1.0)
            seen += c
        return self.bounds[-1] * 2

    def cumulative(self):
        """[(le_bound_or_inf, cumulative_count)] — exposition order."""
        total = 0
        out = []
        for i, c in enumerate(self.bucket_counts):
            total += c
            bound = self.bounds[i] if i < len(self.bounds) else None
            out.append((bound, total))
        return out


class Metrics:
    def __init__(self) -> None:
        # reentrant: a coroutine's `finally` that sets a gauge
        # (server/bolt.py _handle) can be run by the garbage collector
        # on a thread that is inside one of these methods; with a plain
        # lock that thread waits for itself, and every other thread for
        # it (a tier-1 run hung so, ROADMAP D12)
        self._lock = tracked_rlock("Metrics._lock")
        self._counters: dict[str, float] = defaultdict(int)
        self._gauges: dict[str, float] = {}
        self._histograms: dict[str, Histogram] = {}
        #: run before every read, outside the lock: how a writer that
        #: may take no lock hands its counts over (Python's cyclic
        #: collector, observability/trace.py ``_hand_over``)
        self._folds: list = []
        shared_field(self, "_counters", "_gauges", "_histograms")

    def add_fold(self, fold) -> None:
        self._folds.append(fold)

    def increment(self, name: str, delta: float = 1) -> None:
        with self._lock:
            shared_write(self, "_counters")
            self._counters[name] += delta

    def add_seconds(self, seconds_name: str, count_name: str,
                    seconds: float) -> None:
        """One closed phase span (observability/trace.py): its seconds
        and 1, under one lock."""
        with self._lock:
            shared_write(self, "_counters")
            self._counters[seconds_name] += seconds
            self._counters[count_name] += 1

    def set_gauge(self, name: str, value: float) -> None:
        with self._lock:
            shared_write(self, "_gauges")
            self._gauges[name] = value

    def observe(self, name: str, value: float,
                trace_id: str | None = None) -> None:
        if trace_id is None:
            # latency observed inside an armed trace links back to it
            # (exemplar); disarmed this is one attribute read
            from .trace import current_trace_id
            trace_id = current_trace_id()
        with self._lock:
            shared_write(self, "_histograms")
            h = self._histograms.get(name)
            if h is None:
                h = self._histograms[name] = Histogram()
            h.observe(value, trace_id)

    def snapshot(self) -> list[tuple[str, str, float]]:
        for fold in self._folds:
            fold()
        with self._lock:
            shared_read(self, "_counters")
            out = [(n, "Counter", float(v))
                   for n, v in sorted(self._counters.items())]
            out += [(n, "Gauge", float(v))
                    for n, v in sorted(self._gauges.items())]
            for n, h in sorted(self._histograms.items()):
                if not h.count:
                    continue
                for q, suffix in ((0.5, "p50"), (0.9, "p90"), (0.99, "p99")):
                    out.append((f"{n}_{suffix}", "Histogram",
                                float(h.quantile(q))))
            return out

    def prometheus_text(self) -> str:
        lines = []
        for fold in self._folds:
            fold()
        with self._lock:
            shared_read(self, "_counters")
            counters = sorted(self._counters.items())
            gauges = sorted(self._gauges.items())
            histograms = [
                (n, h.cumulative(), h.count, h.sum, dict(h.exemplars),
                 h.bounds)
                for n, h in sorted(self._histograms.items())]
        for name, value in counters:
            metric = _promname(name)
            lines.append(f"# TYPE {metric} counter")
            lines.append(f"{metric} {float(value)}")
        for name, value in gauges:
            metric = _promname(name)
            lines.append(f"# TYPE {metric} gauge")
            lines.append(f"{metric} {float(value)}")
        # cumulative histogram exposition (reference:
        # prometheus_metrics.hpp histogram family): every bucket line is
        # the count of observations ≤ le, the +Inf bucket equals _count,
        # and traced observations append OpenMetrics exemplars
        for name, cumulative, count, total, exemplars, bounds in histograms:
            if not count:
                continue
            metric = _promname(name)
            lines.append(f"# TYPE {metric} histogram")
            for i, (bound, cum) in enumerate(cumulative):
                le = "+Inf" if bound is None else repr(float(bound))
                line = f'{metric}_bucket{{le="{le}"}} {cum}'
                ex = exemplars.get(i)
                if ex is not None:
                    value, trace_id, ts = ex
                    line += (f' # {{trace_id="{_promlabel(trace_id)}"}}'
                             f" {float(value)} {ts:.3f}")
                lines.append(line)
            lines.append(f"{metric}_count {count}")
            lines.append(f"{metric}_sum {float(total)}")
        return "\n".join(lines) + "\n"


global_metrics = Metrics()
