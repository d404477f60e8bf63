"""Resident kernel server: keeps one JAX/TPU runtime warm for
short-lived client processes — now a SUPERVISED service.

Every fresh process pays a runtime start (some 15 s to reach a TPU)
before its first kernel dispatch. The production server process
(memgraph_tpu.main) is naturally resident and owns the chip in the
default deployment; this daemon is the OTHER layout: a unix-socket
service that owns the chip and holds the device runtime, compiled
kernels, and graph caches, so a cold client's first CALL costs one
socket round-trip plus device compute. A chip belongs to one process at
a time, so it is one layout or the other per chip: a process that has
initialised the TPU backend refuses to spawn this daemon
(:func:`ensure_server`, ``utils.devicefault.ChipOwnedError``).

Resilience (r12) — device failure is a first-class, typed, recoverable
event end to end:

  * every dispatch returns a TYPED outcome: completed /
    deadline_exceeded / device_error / oom / shed / invalid. Clients
    raise matching exception types (AdmissionRejected, KernelOom, ...)
    so callers branch on class, not message text;
  * a per-request ``deadline_s`` bounds how long a client waits on the
    device — the dispatch runs on a worker thread, and a device hang
    yields a prompt ``deadline_exceeded`` instead of a wedged client;
  * an HBM ADMISSION GUARD estimates each request's device footprint
    against a budget and sheds (typed, counted, loudly logged) instead
    of letting one oversized request OOM the resident runtime for
    everyone;
  * compute routes through the RESUMABLE mesh entry points
    (parallel/analytics.py): long pagerank runs checkpoint every k
    iterations, so a mid-run device fault costs ≤ k redone iterations;
  * :class:`SupervisedKernelClient` is the client-side supervisor:
    idempotent requests retry under a shared RetryPolicy (per-attempt
    timeout + overall deadline), a health-check loop watches the
    daemon's ``health`` op, and a WEDGED (dispatch overdue) or LOST
    (device.lost killed the process) server is restarted;
  * everything is counted through observability.metrics — the server's
    own counters ride the ``health`` reply across the process boundary.

PPR serving plane (r16) — the first end-to-end query-serving path:
production graph traffic is per-user point queries, not whole-graph
sweeps, and N concurrent personalization vectors are ONE (n, B) SpMM
batch over the semiring core. The ``ppr`` op therefore does NOT dispatch
directly: requests enter a COALESCING QUEUE (:class:`PprServingPlane`)
and accumulate for a bounded window (time- or count-triggered,
``MEMGRAPH_TPU_PPR_BATCH_WINDOW_MS`` / ``_MAX_BATCH``), then execute as
one batched multi-source fixpoint — per-request top-k extracted on
device before the reply, typed per-request outcomes (one shed/oom/
deadline must never poison its batchmates), HBM admission accounting
for the whole batch footprint. A per-source RESULT CACHE keyed on
(graph version, source set, params) serves repeats without touching the
device; commits bump the storage change log, the server consumes the
deltas to invalidate only sources whose neighborhoods changed, and
invalidated vectors seed the next fixpoint (warm start — PPR is a
contraction, any seed converges). See docs/architecture.md §PPR
serving plane.

Protocol (local trusted unix socket): length-prefixed frames, each a
JSON header {op, arrays: [{name, dtype, shape}], ...params} followed by
the raw array bytes in order. Ops: ping, health, probe, pagerank,
ppr, shutdown.

Reference analog: none directly — the reference is a resident C++
daemon by construction (src/memgraph.cpp); this component restores that
property for out-of-process analytics callers.
"""

from __future__ import annotations

import json
import logging
import os
import signal
import socket
import struct
import subprocess
import sys
import threading
import time

import numpy as np

from ..observability import stats as mgstats
from ..observability import trace as mgtrace
from ..observability.metrics import global_metrics
from ..utils.devicefault import (classify_device_error, device_fault_point,
                                 refuse_chip_child)
from ..utils.retry import RetryPolicy

log = logging.getLogger(__name__)

DEFAULT_SOCKET = os.environ.get(
    "MEMGRAPH_TPU_KERNEL_SERVER_SOCKET",
    os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".kernel_server.sock"))

#: typed per-dispatch outcomes (the classification tests assert against)
DISPATCH_OUTCOMES = ("completed", "deadline_exceeded", "device_error",
                     "oom", "shed", "invalid")


def _resolve_hbm_budget() -> int:
    """Admission budget: env override, else 75% of the device's reported
    byte limit. A backend that reports no limit gets a conservative
    4 GiB, except a TPU: there a missing limit is an error, not a guess
    four times under the chip's memory. The choice is logged (once per
    server: the constructor is the one caller)."""
    budget, why = _hbm_budget_and_reason()
    log.info("HBM admission budget %d bytes (%s)", budget, why)
    return budget


def _hbm_budget_and_reason() -> tuple:
    env = os.environ.get("MEMGRAPH_TPU_HBM_BUDGET_BYTES")
    if env:
        try:
            return int(env), "MEMGRAPH_TPU_HBM_BUDGET_BYTES"
        except ValueError:
            log.warning("bad MEMGRAPH_TPU_HBM_BUDGET_BYTES=%r; ignoring",
                        env)
    import jax
    device = jax.devices()[0]
    try:
        stats = device.memory_stats() or {}
    except Exception as e:  # noqa: BLE001 — backends without memory_stats
        log.debug("no device memory stats (%s)", e)
        stats = {}
    limit = int(stats.get("bytes_limit") or 0)
    if limit > 0:
        return int(limit * 0.75), \
            f"75% of the {limit} the {device.platform} device reports"
    if device.platform == "tpu":
        raise RuntimeError(
            f"{device.device_kind} reports no bytes_limit; refusing to "
            "guess an HBM admission budget on a TPU (set "
            "MEMGRAPH_TPU_HBM_BUDGET_BYTES)")
    return 4 << 30, f"the {device.platform} backend reports no limit"


def _resolve_checkpoint_every() -> int:
    try:
        return max(0, int(os.environ.get(
            "MEMGRAPH_TPU_CHECKPOINT_EVERY", "16")))
    except ValueError:
        return 16


# --------------------------------------------------------------------------
# admission estimators — machine-checked by `python -m tools.mgmem check`
# against XLA's buffer assignment for every manifest kernel
# --------------------------------------------------------------------------

def _pow2_bucket(n: int, minimum: int = 8) -> int:
    """Next power-of-two size class — mirrors ``ops.csr._bucket``, the
    padding the placed device arrays ACTUALLY get (tools/mgmem verifies
    the mirror stays exact)."""
    b = minimum
    while b < n:
        b <<= 1
    return b


def _padded_graph_dims(n_nodes: int, n_edges: int) -> tuple[int, int]:
    """(n_pad, e_pad) a ``from_coo`` device placement allocates for the
    declared counts. Estimates priced on RAW counts undercount by up to
    2x right past every bucket boundary — the compile pays for the
    bucket, not the request."""
    return (_pow2_bucket(int(n_nodes) + 1), _pow2_bucket(int(n_edges)))


#: per-algorithm device footprint coefficients over the PADDED dims:
#: ``node_bytes * n_pad + edge_bytes * e_pad`` bounds the compiled peak
#: (XLA argument + output + temp - alias bytes) of every manifest
#: kernel the algorithm can route to on the resident path (segment and
#: mesh backends; the streamed tier path is priced by
#: ``ops.tier.streamed_request_bytes``, and the MXU route is a
#: justified mgmem baseline exclusion). The values come from the
#: fitted footprint models and are enforced within [1x, 2x] of the
#: modeled peak by ``python -m tools.mgmem check`` — edit under that
#: gate, not by re-counting slots by hand.
_ALGO_FOOTPRINT = {
    "pagerank": (76, 36),
    "katz": (132, 24),
    "wcc": (132, 24),
    "labelprop": (68, 48),
    "bfs": (100, 20),
    "ppr": (28, 36),
}

#: unknown algorithms are priced at the column-wise max (shed-safe)
_ALGO_FOOTPRINT_DEFAULT = (max(n for n, _ in _ALGO_FOOTPRINT.values()),
                           max(e for _, e in _ALGO_FOOTPRINT.values()))


def _graph_footprint_bytes(algorithm, n_nodes: int, n_edges: int) -> int:
    """Modeled device peak of one resident fixpoint over the padded
    graph — the request estimate WITHOUT the wire-staging term. This is
    the cached-generation sizing path (r16): a graph_key-only request
    ships no bytes, but the fixpoint still pays the full padded-graph
    footprint."""
    node_b, edge_b = _ALGO_FOOTPRINT.get(str(algorithm),
                                         _ALGO_FOOTPRINT_DEFAULT)
    n_pad, e_pad = _padded_graph_dims(n_nodes, n_edges)
    return n_pad * node_b + e_pad * edge_b


def _estimate_request_bytes(header: dict, arrays: dict) -> int:
    """Request HBM footprint estimate: the padded-graph fixpoint peak
    (per-algorithm coefficients from XLA's buffer assignment) plus one
    copy of the wire arrays — the H2D staging form that briefly
    coexists with the placed graph."""
    wire_bytes = sum(int(np.prod(a.shape, dtype=np.int64))
                     * a.dtype.itemsize for a in arrays.values())
    n_nodes = int(header.get("n_nodes") or 0)
    src = arrays.get("src")
    n_edges = int(src.shape[0]) if src is not None \
        else int(header.get("n_edges") or 0)
    return wire_bytes + _graph_footprint_bytes(
        header.get("algorithm", "pagerank"), n_nodes, n_edges)


def _generation_modeled_bytes(gen) -> int:
    """Modeled device peak of one RESIDENT generation, priced at the
    column-wise worst case across algorithms: the daemon cannot know
    which fixpoint the next request will run over a cached graph, so
    the capacity gauge must be shed-safe (an overestimate wastes
    headroom; an underestimate lies to the planner)."""
    return _graph_footprint_bytes("*", gen.n_nodes, gen.n_edges)


#: f32 slots of per-lane, per-node iteration state the batched PPR
#: fixpoint keeps live (x, new, acc, personalization + err scratch)
_PPR_LANE_NODE_SLOTS = 6

#: bytes per PADDED edge PER LANE: the batched SpMM gather materializes
#: each edge's contribution once per personalization column
_PPR_LANE_EDGE_BYTES = 6

#: compile-time lane buckets — mirrors ops.pagerank._PPR_LANE_BUCKETS
#: (tools/mgmem verifies the mirror stays exact)
_PPR_LANE_BUCKETS = (1, 2, 4, 8, 16, 32, 64, 128)


def _lane_state_bytes(n_nodes: int, n_edges: int,
                      n_lanes: int = 1) -> int:
    """Device bytes the batched PPR fixpoint pays for its lanes, priced
    at the POWER-OF-TWO BUCKET the compile actually allocates: 33
    requested lanes build the 64-wide kernel, and every lane column
    carries O(n) state plus a per-edge gather slice."""
    lanes = next((b for b in _PPR_LANE_BUCKETS
                  if b >= max(1, int(n_lanes))), _PPR_LANE_BUCKETS[-1])
    n_pad, e_pad = _padded_graph_dims(n_nodes, n_edges)
    return lanes * (n_pad * 4 * _PPR_LANE_NODE_SLOTS
                    + e_pad * _PPR_LANE_EDGE_BYTES)


def _ppr_chunk_lanes(n_nodes: int, n_edges: int, budget: int) -> int:
    """Widest lane bucket whose priced batch (graph footprint +
    bucketed lane state) fits the budget — the chunk size the batch
    drain admits. Falls back to single-lane chunks past the budget;
    submit-side admission already bounded that case."""
    graph = _graph_footprint_bytes("ppr", n_nodes, n_edges)
    for b in reversed(_PPR_LANE_BUCKETS):
        if graph + _lane_state_bytes(n_nodes, n_edges, b) <= budget:
            return b
    return 1


def _tier_precision(precision) -> str:
    """Block-compression precision for a streamed run: the request's
    precision when the tier codec supports it, f32 otherwise."""
    p = str(precision)
    return p if p in ("f32", "bf16", "int8") else "f32"


def probe_device():
    """Tiny end-to-end device check: a compiled matmul with a host
    transfer forcing completion. Shared by the server warm-up and the
    ``probe`` op — and guarded by the device fault point so probe
    failures are injectable too.
    Returns (checksum, platform)."""
    device_fault_point()
    import jax
    import jax.numpy as jnp
    x = jnp.ones((128, 128), jnp.float32)
    return float((x @ x).sum()), jax.devices()[0].platform


# --------------------------------------------------------------------------
# typed client errors (one per server outcome)
# --------------------------------------------------------------------------


class KernelServerError(RuntimeError):
    """Base kernel-server failure; carries the typed outcome."""

    def __init__(self, message: str, outcome: str = "invalid",
                 retryable: bool = False) -> None:
        super().__init__(message)
        self.outcome = outcome
        self.retryable = retryable


class AdmissionRejected(KernelServerError):
    """The HBM admission guard shed this request (outcome "shed").
    Deliberately NOT retryable: the same request against the same budget
    sheds again — resize the request or raise the budget."""

    def __init__(self, message: str) -> None:
        super().__init__(message, outcome="shed", retryable=False)


class KernelOom(KernelServerError):
    """Device memory exhausted during dispatch (outcome "oom")."""

    def __init__(self, message: str) -> None:
        super().__init__(message, outcome="oom", retryable=False)


class KernelDeviceError(KernelServerError):
    """Device-side dispatch failure (outcome "device_error"); the op is
    pure, so idempotent retry is safe."""

    def __init__(self, message: str) -> None:
        super().__init__(message, outcome="device_error", retryable=True)


class KernelDeadlineExceeded(KernelServerError):
    """The dispatch missed its deadline (outcome "deadline_exceeded") —
    possibly a wedged device; the supervisor health-checks on this."""

    def __init__(self, message: str) -> None:
        super().__init__(message, outcome="deadline_exceeded",
                         retryable=True)


_OUTCOME_ERRORS = {
    "shed": AdmissionRejected,
    "oom": KernelOom,
    "device_error": KernelDeviceError,
    "deadline_exceeded": KernelDeadlineExceeded,
}


def _raise_for_reply(header: dict):
    outcome = header.get("outcome", "invalid")
    cls = _OUTCOME_ERRORS.get(outcome)
    msg = header.get("error", "kernel server error")
    if cls is not None:
        raise cls(msg)
    raise KernelServerError(msg, outcome=outcome,
                            retryable=bool(header.get("retryable")))


# --------------------------------------------------------------------------
# framing
# --------------------------------------------------------------------------

def _send_msg(sock: socket.socket, header: dict,
              arrays: dict[str, np.ndarray] | None = None) -> None:
    arrays = arrays or {}
    header = dict(header)
    header["arrays"] = [
        {"name": k, "dtype": str(v.dtype), "shape": list(v.shape)}
        for k, v in arrays.items()]
    hb = json.dumps(header).encode("utf-8")
    parts = [struct.pack("<I", len(hb)), hb]
    for v in arrays.values():
        parts.append(np.ascontiguousarray(v).tobytes())
    sock.sendall(b"".join(parts))


def _recv_exact(sock: socket.socket, n: int) -> bytes:
    buf = bytearray()
    while len(buf) < n:
        chunk = sock.recv(min(1 << 20, n - len(buf)))
        if not chunk:
            raise ConnectionError("peer closed")
        buf += chunk
    return bytes(buf)


def _recv_msg(sock: socket.socket):
    (hlen,) = struct.unpack("<I", _recv_exact(sock, 4))
    header = json.loads(_recv_exact(sock, hlen))
    arrays = {}
    for spec in header.pop("arrays", []):
        dt = np.dtype(spec["dtype"])
        count = int(np.prod(spec["shape"], dtype=np.int64)) if spec["shape"] \
            else 1
        raw = _recv_exact(sock, count * dt.itemsize)
        arrays[spec["name"]] = np.frombuffer(raw, dtype=dt).reshape(
            spec["shape"])
    return header, arrays


# --------------------------------------------------------------------------
# PPR serving plane: result cache + coalescing queue
# --------------------------------------------------------------------------


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, "") or default)
    except ValueError:
        return default


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, "") or default)
    except ValueError:
        return default


#: above this neighborhood size an entry records None — "invalidate on
#: any change" — instead of an exact set (hub sources touch everything)
PPR_NEIGH_CAP = 4096


def _host_offsets(graph):
    """Host ``(row_ptr, col_idx)`` of ``graph``'s true edges, or None for
    a snapshot without host edges. ``from_coo`` hands them on with every
    snapshot it builds (``DeviceGraph.host_csr``: two references). One
    built elsewhere gets them here, once: a stable argsort of ``src``,
    O(E), remembered on the snapshot object for every later rider."""
    offsets = graph.host_csr
    if offsets is None and graph.host_coo is not None:
        src, dst, _w = graph.host_coo
        src = np.asarray(src)
        row_ptr = np.zeros(graph.n_nodes + 1, dtype=np.int64)
        np.cumsum(np.bincount(src, minlength=graph.n_nodes),
                  out=row_ptr[1:])
        offsets = (row_ptr,
                   np.asarray(dst)[np.argsort(src, kind="stable")])
        # the snapshot is frozen for its users, not for what it caches
        object.__setattr__(graph, "host_csr", offsets)
        global_metrics.increment("ppr.neigh_scan_total")
    return offsets


def _source_neighborhood(graph, sources, cap: int = PPR_NEIGH_CAP):
    """Dense indices whose mutation must invalidate a cached PPR vector
    restarted on ``sources``: the sources plus their out-neighbors (the
    rows the restart mass crosses first). None = unbounded (treat every
    change as relevant): more than ``cap`` of them, or a snapshot
    without host edges.

    Costs the sources' out-degrees: each row is one slice of the
    snapshot's host CSR, ``col_idx[row_ptr[s]:row_ptr[s + 1]]``
    (:func:`_host_offsets`). Never a pass over ``host_coo`` per call
    and never a readback of the device's ``row_ptr`` / ``col_idx``."""
    offsets = _host_offsets(graph)
    if offsets is None:
        return None
    row_ptr, col_idx = offsets
    global_metrics.increment("ppr.neigh_offsets_total")
    sources = np.asarray(sources, dtype=np.int64)
    rows = sources[(sources >= 0) & (sources < len(row_ptr) - 1)]
    neigh = np.unique(np.concatenate(
        [sources] + [col_idx[row_ptr[s]:row_ptr[s + 1]] for s in rows]))
    if len(neigh) > cap:
        return None
    return frozenset(int(i) for i in neigh)


class _PprCacheEntry:
    """One cached PPR vector. ``fresh`` entries serve directly; STALE
    entries (their source neighborhood changed) are never served but
    seed the recomputation's fixpoint (warm start)."""

    __slots__ = ("version", "ranks", "err", "iters", "neigh", "fresh")

    def __init__(self, version, ranks, err, iters, neigh) -> None:
        self.version = version
        self.ranks = ranks              # np (n_nodes,) float32
        self.err = err
        self.iters = iters
        self.neigh = neigh              # frozenset | None (= any change)
        self.fresh = True


class PprResultCache:
    """Per-source PPR result cache with change-log-driven invalidation.

    Keyed on (graph_key, source set, damping, tol, precision); bounded
    LRU. The consumer-side route layer ships each commit's change-log
    delta (dense indices) with the next request; :meth:`note_version`
    applies it: entries whose source neighborhood intersects the delta
    are DEMOTED to warm-start seeds, everything else is promoted to the
    new version — a stale read across a version bump is impossible, and
    untouched sources keep their hits. An unknowable delta (log
    evicted, node set changed) invalidates the whole graph_key.
    """

    def __init__(self, capacity: int | None = None) -> None:
        from collections import OrderedDict
        from ..utils.locks import tracked_lock
        from ..utils.sanitize import shared_field
        self.capacity = capacity if capacity is not None \
            else _env_int("MEMGRAPH_TPU_PPR_CACHE_ENTRIES", 512)
        self._lock = tracked_lock("PprResultCache._lock")
        self._entries: "OrderedDict[tuple, _PprCacheEntry]" = OrderedDict()
        self._known: dict[str, int] = {}    # graph_key -> newest version
        shared_field(self, "_entries", "_known")

    @staticmethod
    def key(graph_key, sources, damping, tol, precision) -> tuple:
        return (graph_key, tuple(int(s) for s in sources),
                float(damping), float(tol), str(precision))

    def known_version(self, graph_key) -> int | None:
        from ..utils.sanitize import shared_read
        with self._lock:
            shared_read(self, "_known")
            return self._known.get(graph_key)

    def note_version(self, graph_key, version: int, base_version,
                     changed, ids_stable: bool) -> None:
        """Advance a graph_key to ``version``. ``changed`` is the dense
        index delta covering (base_version, version] or None when
        unknowable; ``ids_stable`` says the dense-id layout survived."""
        from ..utils.sanitize import shared_write
        if graph_key is None:
            return
        with self._lock:
            shared_write(self, "_known")
            known = self._known.get(graph_key)
            if known is None or version <= known:
                self._known.setdefault(graph_key, version)
                return
            targeted = (ids_stable and base_version == known
                        and changed is not None)
            changed_set = frozenset(int(i) for i in changed) \
                if targeted else None
            for key, entry in list(self._entries.items()):
                if key[0] != graph_key:
                    continue
                if targeted:
                    if entry.neigh is not None and \
                            not (entry.neigh & changed_set):
                        entry.version = version      # provably untouched
                        continue
                    entry.fresh = False              # warm-start seed
                    global_metrics.increment("ppr.cache_invalidate_total")
                elif ids_stable:
                    entry.fresh = False
                    global_metrics.increment("ppr.cache_invalidate_total")
                else:
                    # dense-id layout changed: the vector indexes the
                    # wrong nodes — useless even as a seed
                    del self._entries[key]
                    global_metrics.increment("ppr.cache_invalidate_total")
            self._known[graph_key] = version

    def lookup(self, key: tuple):
        """("hit", entry) | ("warm", entry) | ("miss", None)."""
        from ..utils.sanitize import shared_read
        with self._lock:
            shared_read(self, "_entries")
            entry = self._entries.get(key)
            if entry is None:
                return "miss", None
            if entry.fresh and entry.version == self._known.get(key[0]):
                self._entries.move_to_end(key)
                return "hit", entry
            return "warm", entry

    def insert(self, key: tuple, entry: _PprCacheEntry) -> None:
        from ..utils.sanitize import shared_write
        with self._lock:
            shared_write(self, "_entries")
            known = self._known.get(key[0])
            if known is not None and entry.version < known:
                return          # a newer delta landed mid-compute
            self._entries[key] = entry
            self._entries.move_to_end(key)
            while len(self._entries) > self.capacity:
                self._entries.popitem(last=False)


class _PprPending:
    """One queued PPR request awaiting its batch."""

    __slots__ = ("header", "arrays", "carrier", "event", "reply",
                 "out_arrays", "warm_entry", "abandoned", "t_enqueued")

    def __init__(self, header, arrays, carrier, warm_entry) -> None:
        self.header = header
        self.arrays = arrays
        self.carrier = carrier
        self.event = threading.Event()
        self.reply = None
        self.out_arrays = None
        self.warm_entry = warm_entry
        self.abandoned = False
        self.t_enqueued = time.monotonic()


def _topk_host(vec: np.ndarray, k: int):
    """Host-side top-k for cache hits (no device round trip)."""
    k = max(1, min(int(k), len(vec)))
    # everything at or above the k-th best, in index order, then a
    # stable sort: ties go to the lower index, as the device's top_k
    # breaks them, so a hit and a computed answer are the same rows
    kth = np.partition(vec, len(vec) - k)[len(vec) - k]
    idx = np.flatnonzero(vec >= kth)
    idx = idx[np.argsort(-vec[idx], kind="stable")][:k]
    return vec[idx].astype(np.float32), idx.astype(np.int32)


class PprServingPlane:
    """Request-coalescing batched PPR with result caching.

    Concurrent ``ppr`` requests accumulate for a bounded window —
    time-triggered (MEMGRAPH_TPU_PPR_BATCH_WINDOW_MS, default 4ms) or
    count-triggered (MEMGRAPH_TPU_PPR_MAX_BATCH, default 32) — then
    execute as ONE batched multi-source SpMM fixpoint per parameter
    group (requests with differing damping/tol/precision NEVER share a
    fixpoint). Each member gets a TYPED outcome; admission accounts the
    whole batch footprint and splits oversized groups into sub-batches
    instead of shedding riders.
    """

    def __init__(self, server: "KernelServer") -> None:
        import queue as _queue
        from ..utils.locks import tracked_lock
        self.server = server
        self.window_s = _env_float(
            "MEMGRAPH_TPU_PPR_BATCH_WINDOW_MS", 4.0) / 1e3
        self.max_batch = max(1, _env_int("MEMGRAPH_TPU_PPR_MAX_BATCH", 32))
        self.max_queue = max(1, _env_int("MEMGRAPH_TPU_PPR_MAX_QUEUE", 256))
        self.cache = PprResultCache()
        self._queue: "_queue.Queue[_PprPending]" = _queue.Queue()
        self._thread = None
        self._thread_lock = tracked_lock("PprServingPlane._thread_lock")
        self._graph_versions: dict = {}   # batcher-thread only
        self._warmed: set = set()         # batcher-thread only

    # --- request side (connection threads) ---------------------------------

    def submit(self, header: dict, arrays: dict):
        """Blocking request entry: cache probe → admission → coalescing
        queue → (reply, out_arrays). Runs on the connection thread."""
        global_metrics.increment("ppr.requests_total")
        sources = arrays.get("sources")
        if sources is None or len(sources) == 0:
            return ({"ok": False, "outcome": "invalid",
                     "error": "ppr request carries no sources"}, None)
        carrier = header.pop("trace", None)
        graph_key = header.get("graph_key")
        version = int(header.get("graph_version") or 0)
        self.cache.note_version(
            graph_key, version, header.get("base_version"),
            arrays.get("changed") if header.get("has_delta") else None,
            bool(header.get("ids_stable", True)))
        ckey = self.cache.key(graph_key, sources,
                              header.get("damping", 0.85),
                              header.get("tol", 1e-6),
                              header.get("precision", "f32"))
        warm_entry = None
        if graph_key is not None:
            t0 = time.perf_counter()
            t_wall = time.time()
            status, entry = self.cache.lookup(ckey)
            if status == "hit":
                global_metrics.increment("ppr.cache_hit_total")
                return self._reply_from_vector(
                    header, entry.ranks, entry.err, entry.iters,
                    cache="hit", batch_size=1, coalesced=False,
                    carrier=carrier, t_wall=t_wall,
                    dur=time.perf_counter() - t0)
            if status == "warm":
                warm_entry = entry
            global_metrics.increment("ppr.cache_miss_total")

        n_nodes = int(header.get("n_nodes") or 0)
        src = arrays.get("src")
        n_edges = int(src.shape[0]) if src is not None else 0
        if src is None and graph_key is not None:
            # cached-generation sizing (r16): a graph_key-only request
            # ships no edges, so the wire-driven estimate misses the
            # real footprint — size admission off the resident
            # generation's CURRENT counts (same benign unlocked peek as
            # the supervised path)
            gen = self.server._graphs.get(graph_key)  # mglint: disable=MG006 — benign unlocked estimate read; admission must not queue behind a dispatch holding _dispatch_lock
            if gen is not None:
                n_nodes = n_nodes or gen._n_nodes
                n_edges = int(np.asarray(gen._coo[0]).shape[0])
        est = _estimate_request_bytes(
            {**header, "algorithm": "ppr", "n_nodes": n_nodes,
             "n_edges": n_edges}, arrays) \
            + _lane_state_bytes(n_nodes, n_edges, 1)
        if est > self.server.hbm_budget_bytes:
            return self._shed(
                f"estimated footprint {est} bytes exceeds HBM budget "
                f"{self.server.hbm_budget_bytes} bytes")
        depth = self._queue.qsize()
        if depth >= self.max_queue:
            # backpressure: the saturation plane flips /health to 503
            # before this point; past it we shed typed instead of
            # letting the queue (and every rider's latency) grow
            return self._shed(
                f"PPR coalescing queue saturated ({depth} >= "
                f"{self.max_queue} pending)")
        pending = _PprPending(header, arrays, carrier, warm_entry)
        self._ensure_thread()
        self._queue.put(pending)
        global_metrics.set_gauge("ppr.queue_depth",
                                 float(self._queue.qsize()))
        deadline_s = header.get("deadline_s")
        wait_s = float(deadline_s) if deadline_s \
            else self.server.wedge_after_s + 30.0
        if not pending.event.wait(wait_s):
            pending.abandoned = True
            self.server._count("deadline_exceeded")
            log.warning("ppr: request exceeded its %.3fs deadline in "
                        "the coalescing plane", wait_s)
            return ({"ok": False, "outcome": "deadline_exceeded",
                     "retryable": True,
                     "error": f"ppr request exceeded {wait_s}s "
                              "deadline"}, None)
        return pending.reply, pending.out_arrays

    def _shed(self, why: str):
        self.server._count("shed")
        global_metrics.increment("ppr.shed_total")
        global_metrics.increment("kernel_server.admission_rejected_total")
        log.warning("ppr: SHED request — %s", why)
        return ({"ok": False, "outcome": "shed", "retryable": False,
                 "error": f"AdmissionRejected: {why}"}, None)

    def _reply_from_vector(self, header, ranks, err, iters, *, cache,
                           batch_size, coalesced, stages=None,
                           carrier=None, t_wall=None, dur=None,
                           topk=None):
        k = int(header.get("top_k") or 0)
        reply = {"ok": True, "outcome": "completed", "err": float(err),
                 "iters": int(iters), "cache": cache,
                 "batch_size": int(batch_size),
                 "coalesced": bool(coalesced)}
        if stages:
            reply["stages"] = stages
        if carrier and carrier.get("trace_id"):
            with mgtrace.adopt(carrier):
                mgtrace.record_span(
                    "kernel.dispatch", t_wall or time.time(), dur or 0.0,
                    op="ppr", batch=int(batch_size), cache=cache)
            spans = mgtrace.take_trace(carrier["trace_id"])
            if spans:
                reply["trace_spans"] = spans
        global_metrics.observe("kernel_server.dispatch_latency_sec",
                               dur if dur is not None else 0.0,
                               trace_id=(carrier or {}).get("trace_id"))
        if k > 0:
            if topk is not None:
                vals, idx = topk
                vals, idx = vals[:k], idx[:k]
            else:
                vals, idx = _topk_host(np.asarray(ranks), k)
            return reply, {"topk_val": np.asarray(vals, dtype=np.float32),
                           "topk_idx": np.asarray(idx, dtype=np.int32)}
        return reply, {"ranks": np.asarray(ranks, dtype=np.float32)}

    # --- batch side (the one batcher thread) -------------------------------

    def _ensure_thread(self) -> None:
        import threading
        with self._thread_lock:
            if self._thread is not None and self._thread.is_alive():
                return
            self._thread = threading.Thread(
                target=self._run, daemon=True, name="ks-ppr-batcher")
            self._thread.start()

    def _run(self) -> None:
        import queue as _queue
        while not self.server._shutdown.is_set():
            try:
                first = self._queue.get(timeout=0.25)
            except _queue.Empty:
                continue
            batch = [first]
            deadline = time.monotonic() + self.window_s
            while len(batch) < self.max_batch:
                rem = deadline - time.monotonic()
                if rem <= 0:
                    break
                try:
                    batch.append(self._queue.get(
                        timeout=max(rem, 0.0005)))
                except _queue.Empty:
                    break
            global_metrics.set_gauge("ppr.queue_depth",
                                     float(self._queue.qsize()))
            global_metrics.set_gauge("ppr.window_occupancy",
                                     len(batch) / self.max_batch)
            groups: dict = {}
            for m in batch:
                h = m.header
                gk = (h.get("graph_key"), float(h.get("damping", 0.85)),
                      float(h.get("tol", 1e-6)),
                      int(h.get("max_iterations", 100)),
                      str(h.get("precision", "f32")))
                groups.setdefault(gk, []).append(m)
            for members in groups.values():
                try:
                    self._execute_group(members)
                except Exception:   # noqa: BLE001 — serving must survive
                    log.exception("ppr: group execution failed "
                                  "unexpectedly")
                    self._fail_group(members, "invalid", False,
                                     "internal ppr batch failure")
        # drain: pending requests must not leave connection threads
        # blocked across shutdown
        while True:
            try:
                m = self._queue.get_nowait()
            except _queue.Empty:
                break
            self._fail_group([m], "invalid", False,
                             "kernel server shutting down")

    def _fail_group(self, members, outcome, retryable, error) -> None:
        """Typed failure for EVERY live member — a batch dies whole or
        answers whole, never half (device_chaos contract)."""
        for m in members:
            if m.reply is not None:
                continue
            self.server._count(outcome)
            m.reply = {"ok": False, "outcome": outcome,
                       "retryable": retryable, "error": error}
            m.event.set()

    def _resolve_group_graph(self, members):
        """Resolve (importing/refreshing if needed) the group's graph.
        Runs under _dispatch_lock on the batcher thread.

        Rides the resident-generation layer (r19 mgdelta): the carrier
        member is whichever request can ADVANCE the resident graph —
        full edge arrays, or the change-log delta payload (``changed``
        + the changed vertices' current incident edges), which
        refreshes the resident snapshot O(delta) instead of
        re-importing the full edge list. The cache demotion path
        (note_version) and this refresh consume the SAME shipped delta,
        so a commit costs one O(delta) splice, not a re-import plus a
        private neighborhood walk."""
        key = members[0].header.get("graph_key")
        carrier_m = None

        def _version(m):
            return int(m.header.get("graph_version") or 0)

        for m in members:
            if ("src" in m.arrays or ("changed" in m.arrays
                                      and "inc_src" in m.arrays)) \
                    and (carrier_m is None
                         or _version(m) > _version(carrier_m)):
                carrier_m = m
        m = carrier_m or members[0]
        gen = self.server._resolve_generation(m.header, m.arrays)
        if gen is None:
            return None
        if key is not None:
            self._graph_versions[key] = max(
                gen.version, self._graph_versions.get(key) or 0)
        return gen.graph

    def _execute_group(self, members) -> None:
        """One parameter group → one batched fixpoint dispatch."""
        from ..observability import stats as mgstats
        server = self.server
        did = server._dispatch_begin(server.wedge_after_s)
        global_metrics.increment("ppr.batches_total")
        global_metrics.increment("ppr.riders_total", delta=len(members))
        global_metrics.observe("ppr.batch_size", float(len(members)))
        if len(members) > 1:
            global_metrics.increment("ppr.coalesced_total",
                                     delta=len(members))
        t0 = time.perf_counter()
        t_wall = time.time()
        now = time.monotonic()
        for m in members:
            # what a rider waited: the window it was held for, and the
            # batches that ran ahead of its own
            waited = now - m.t_enqueued
            mgtrace.record_span("ppr.queue", t_wall - waited, waited)
        acc = mgstats.StageAccumulator()
        results = None
        live = []
        try:
            try:
                with mgstats.collecting_stages(acc), \
                        mgtrace.span("ppr.batch", riders=len(members)):
                    with server._dispatch_lock:
                        device_fault_point()
                        g = self._resolve_group_graph(members)
                        if g is None:
                            self._fail_group(
                                members, "invalid", False,
                                "unknown graph_key and no edge arrays "
                                "supplied")
                            return
                        live, results = self._compute(g, members)
            except BaseException as e:  # noqa: BLE001 — classified below
                kind = classify_device_error(e)
                if kind == "oom":
                    outcome, retryable = "oom", False
                elif kind in ("device_error", "device_lost"):
                    outcome, retryable = "device_error", True
                else:
                    outcome, retryable = "invalid", False
                log.warning("ppr: batch of %d failed [%s]: %s",
                            len(members), outcome, e)
                self._fail_group(members, outcome, retryable,
                                 f"{type(e).__name__}: {e}")
                return
            dur = time.perf_counter() - t0
            # pro-rata device-stage attribution: the batch's HBM-seconds
            # split evenly across its riders, so per-query PROFILE sums
            # stay truthful instead of charging the whole batch to one
            snap = acc.snapshot()
            share = 1.0 / max(1, len(live))
            stages = {name: {"seconds": slot["seconds"] * share,
                             "count": slot["count"]}
                      for name, slot in snap.items()} if snap else None
            with mgtrace.span("ppr.reply", riders=len(live)):
                self._fill_cache(g, live, results)
                for m, res in zip(live, results):
                    ranks, err, iters, cache_state, topk = res
                    m.reply, m.out_arrays = self._reply_from_vector(
                        m.header, ranks, err, iters, cache=cache_state,
                        batch_size=len(members),
                        coalesced=len(members) > 1, stages=stages,
                        carrier=m.carrier, t_wall=t_wall, dur=dur,
                        topk=topk)
                    server._count("completed")
                    m.event.set()
        finally:
            server._dispatch_end(did)
        if live:
            self._warm_lane_buckets(g, live)

    def _warm_lane_buckets(self, g, members) -> None:
        """Once per graph shape and parameter group, right after its
        first batch has been answered: run the batched program and its
        top-k once at every lane bucket a batch can be padded to, so
        that each is compiled (or loaded from the compile cache) now. A
        bucket first met under load compiles inside its riders'
        requests with every other rider queued behind it: 2.8-3.8 s a
        program on a v5e's host, 9 such compiles in one 51 s window of
        twelve clients (PERF.md section 5, PR 35). Warm-started batches
        (a seed matrix after a commit) are other programs and are not
        warmed here."""
        from ..ops.pagerank import (_PPR_LANE_BUCKETS, _bucket_lanes,
                                    personalized_pagerank_batch, ppr_topk)
        h0 = members[0].header
        params = (float(h0.get("damping", 0.85)), float(h0.get("tol", 1e-6)),
                  int(h0.get("max_iterations", 100)),
                  str(h0.get("precision", "f32")))
        top_k = max(int(m.header.get("top_k") or 0) for m in members)
        key = (g.n_pad, int(g.csc_src.shape[0]), params[2:], top_k)
        if key in self._warmed:
            return
        self._warmed.add(key)
        server = self.server
        widest = min(self.max_batch, _ppr_chunk_lanes(
            g.n_nodes, g.n_edges, server.hbm_budget_bytes))
        sources = [np.asarray(members[0].arrays["sources"], dtype=np.int32)]
        did = server._dispatch_begin(server.wedge_after_s)
        try:
            with server._dispatch_lock:
                for lanes in _PPR_LANE_BUCKETS:
                    if lanes >= 2 * widest:
                        break
                    if lanes == _bucket_lanes(len(members)):
                        continue        # the batch just ran it
                    x_dev, _err, _iters = personalized_pagerank_batch(
                        g, sources * lanes, damping=params[0],
                        max_iterations=params[2], tol=params[1],
                        precision=params[3], raw=True)
                    if top_k:
                        ppr_topk(x_dev.T, g.n_nodes, top_k, raw=True)
        except Exception:   # noqa: BLE001 — warming is best effort
            log.exception("ppr: warming the lane buckets failed")
        finally:
            server._dispatch_end(did)

    def _compute(self, g, members):
        """Batched fixpoint over the group's live members (under
        _dispatch_lock). Returns (live_members, results) where results
        align with live_members as (ranks, err, iters, cache_state).
        Invalid members are replied typed HERE — they must not poison
        the batch."""
        from ..ops.pagerank import personalized_pagerank_batch, ppr_topk
        h0 = members[0].header
        damping = float(h0.get("damping", 0.85))
        tol = float(h0.get("tol", 1e-6))
        max_iterations = int(h0.get("max_iterations", 100))
        precision = str(h0.get("precision", "f32"))

        live = []
        for m in members:
            sources = np.asarray(m.arrays["sources"], dtype=np.int32)
            if sources.size == 0 or sources.min() < 0 \
                    or sources.max() >= g.n_nodes:
                self.server._count("invalid")
                m.reply = {"ok": False, "outcome": "invalid",
                           "retryable": False,
                           "error": f"sources out of range for graph "
                                    f"with {g.n_nodes} nodes"}
                m.event.set()
                continue
            live.append(m)
        if not live:
            return [], []

        # admission: chunk the batch at the widest LANE BUCKET whose
        # priced footprint (graph + bucketed lane state) fits the HBM
        # budget. The compile allocates the power-of-two bucket, so
        # pricing requested lanes would undercount right past every
        # bucket boundary (33 live members -> the 64-wide kernel)
        max_lanes = _ppr_chunk_lanes(g.n_nodes, g.n_edges,
                                     self.server.hbm_budget_bytes)

        results = []
        for lo in range(0, len(live), max_lanes):
            chunk = live[lo:lo + max_lanes]
            source_sets = [np.asarray(m.arrays["sources"],
                                      dtype=np.int32) for m in chunk]
            x0 = None
            warm_lanes = []
            if any(m.warm_entry is not None
                   and len(m.warm_entry.ranks) == g.n_nodes
                   for m in chunk):
                x0 = np.zeros((g.n_pad, len(chunk)), dtype=np.float32)
                for lane, m in enumerate(chunk):
                    e = m.warm_entry
                    if e is not None and len(e.ranks) == g.n_nodes:
                        x0[:g.n_nodes, lane] = e.ranks
                        warm_lanes.append(lane)
                        global_metrics.increment("ppr.warm_start_total")
                    else:
                        s = source_sets[lane]
                        x0[s, lane] = np.float32(1.0) \
                            / np.float32(len(s))
            x_dev, err_dev, iter_dev = personalized_pagerank_batch(
                g, source_sets, damping=damping,
                max_iterations=max_iterations, tol=tol,
                precision=precision, x0=x0, raw=True)
            # per-request top-k extracted ON DEVICE (one jitted top_k
            # over the whole batch) before the O(n) host transfer the
            # cache fill pays anyway
            k_max = max((int(m.header.get("top_k") or 0) for m in chunk),
                        default=0)
            tvals = tidx = None
            device_out = [x_dev, err_dev, iter_dev]
            if k_max > 0:
                # over every lane of the bucket, padding included: one
                # program a bucket, not one a rider count
                device_out += list(ppr_topk(x_dev.T, g.n_nodes, k_max,
                                            raw=True))
            # THE one fused host sync per chunk: every device output of
            # the batch (iterate, per-lane err/iters, top-k) crosses in
            # a single device_get instead of one transfer per epilogue
            import jax
            host = jax.device_get(device_out)  # mglint: disable=MG009 — replies must ship host bytes; this IS the single fused result transfer the drain loop pays per chunk
            x_host, errs, iters = host[0], host[1], host[2]
            if k_max > 0:
                tvals, tidx = host[3], host[4]
            ranks = x_host[:g.n_nodes, :len(chunk)].T
            warm_set = set(warm_lanes)
            for lane, m in enumerate(chunk):
                vec = np.ascontiguousarray(ranks[lane])
                topk = (tvals[lane], tidx[lane]) \
                    if tvals is not None else None
                results.append((vec, float(errs[lane]),
                                int(iters[lane]),
                                "warm" if lane in warm_set else "miss",
                                topk))
        return live, results

    def _fill_cache(self, g, live, results) -> None:
        """Every rider's vector into the result cache, under the
        version its batch resolved the graph at."""
        if not live:
            return
        h0 = live[0].header
        graph_key = h0.get("graph_key")
        if graph_key is None:
            return
        version = self._graph_versions.get(graph_key, 0)
        for m, (vec, err, iters, _state, _topk) in zip(live, results):
            ckey = self.cache.key(
                graph_key, m.arrays["sources"],
                float(h0.get("damping", 0.85)), float(h0.get("tol", 1e-6)),
                str(h0.get("precision", "f32")))
            self.cache.insert(ckey, _PprCacheEntry(
                version, vec, err, iters,
                _source_neighborhood(g, m.arrays["sources"])))


# --------------------------------------------------------------------------
# server
# --------------------------------------------------------------------------

class KernelServer:
    """One thread per connection; device dispatch serialized by a lock
    (one chip — concurrent kernels would just queue anyway). Every
    dispatch runs on a worker thread under a per-request deadline: a
    wedged device costs the caller a typed ``deadline_exceeded``, never
    a silent hang, and the ``health`` op exposes the overdue dispatch so
    the client-side supervisor can restart the process."""

    def __init__(self, socket_path: str = DEFAULT_SOCKET,
                 idle_timeout_s: float = 0.0,
                 hbm_budget_bytes: int | None = None,
                 checkpoint_every: int | None = None,
                 wedge_after_s: float | None = None) -> None:
        import threading
        self.socket_path = socket_path
        self.idle_timeout_s = idle_timeout_s
        self.hbm_budget_bytes = hbm_budget_bytes \
            if hbm_budget_bytes is not None else _resolve_hbm_budget()
        self.checkpoint_every = checkpoint_every \
            if checkpoint_every is not None else _resolve_checkpoint_every()
        self.wedge_after_s = wedge_after_s if wedge_after_s is not None \
            else float(os.environ.get(
                "MEMGRAPH_TPU_KS_WEDGE_AFTER_S", "60"))
        self._graphs: dict = {}      # graph_key -> delta.ResidentGraph
        from ..utils.locks import tracked_lock
        from ..utils.sanitize import shared_field
        self._dispatch_lock = tracked_lock("KernelServer._dispatch_lock")
        self._shutdown = threading.Event()
        # written by every connection thread, read by the accept loop's
        # idle-timeout check — a leaf lock, never held across dispatch
        self._activity_lock = tracked_lock("KernelServer._activity_lock")
        self._last_activity = time.monotonic()
        # dispatch bookkeeping for the health op — a leaf lock too: the
        # health reply must never wait behind a wedged dispatch
        self._stats_lock = tracked_lock("KernelServer._stats_lock")
        self._active: dict[int, tuple[float, float | None]] = {}
        self._dispatch_seq = 0
        self._graphs_cached = 0
        self._modeled_peaks: dict = {}  # graph_key -> modeled peak bytes
        self._started = time.monotonic()
        self._platform = "unknown"
        self._sock_ino = None        # inode of OUR bound socket path
        shared_field(self, "_graphs", "_last_activity", "_active",
                     "_dispatch_seq", "_graphs_cached", "_platform",
                     "_modeled_peaks")
        # saturation plane: the admission budget is a bounded resource —
        # export it so capacity planning can see utilization vs limit
        global_metrics.set_gauge("kernel_server.hbm_budget_bytes",
                                 float(self.hbm_budget_bytes))
        global_metrics.set_gauge("kernel_server.hbm_modeled_peak_bytes",
                                 0.0)
        # PPR serving plane: coalescing queue + result cache (r16)
        self._ppr = PprServingPlane(self)

    def _touch_activity(self) -> None:
        from ..utils.sanitize import shared_write
        with self._activity_lock:
            shared_write(self, "_last_activity")
            self._last_activity = time.monotonic()

    def _idle_for(self) -> float:
        from ..utils.sanitize import shared_read
        with self._activity_lock:
            shared_read(self, "_last_activity")
            return time.monotonic() - self._last_activity

    def _warm(self) -> None:
        """Touch the device so the first client request pays no init.
        The compile cache and its witness (``jit.*``) are switched on
        first: no op on the pagerank path does it for this process."""
        from ..utils.jax_cache import ensure_compile_cache
        from ..utils.sanitize import shared_write
        ensure_compile_cache()
        _, platform = probe_device()
        with self._stats_lock:
            shared_write(self, "_platform")
            self._platform = platform

    def serve_forever(self) -> None:
        import errno
        import threading

        # Spawn-race discipline (ADVICE r5): never unlink-before-bind.
        # A live responder on the path means another daemon already won —
        # exit and let clients use it. Only a provably-stale path (connect
        # refused) is unlinked, and shutdown unlinks only while the inode
        # still belongs to THIS server, so a losing daemon's exit can
        # never orphan the winner's socket.
        try:
            probe = KernelClient(self.socket_path, timeout=5.0)
            alive = probe.ping()
            probe.close()
            if alive:
                return           # already running; we lost the race
        except OSError:
            pass                 # nothing listening (or no socket yet)
        srv = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        try:
            srv.bind(self.socket_path)
        except OSError as e:
            if e.errno != errno.EADDRINUSE:
                raise
            # path exists but nobody answered the probe: stale socket
            # from a crashed daemon — reclaim it
            try:
                os.unlink(self.socket_path)
            except OSError:
                pass
            srv.bind(self.socket_path)
        try:
            self._sock_ino = os.stat(self.socket_path).st_ino
        except OSError:
            self._sock_ino = None
        # serving-plane backlog: the PPR coalescer exists precisely for
        # bursts of concurrent clients, so simultaneous connects must
        # not bounce off a tiny accept queue
        srv.listen(128)
        self._warm()
        self._touch_activity()
        srv.settimeout(1.0)
        while not self._shutdown.is_set():
            try:
                conn, _ = srv.accept()
            except socket.timeout:
                if self.idle_timeout_s and \
                        self._idle_for() > self.idle_timeout_s:
                    break
                continue
            threading.Thread(target=self._serve_conn, args=(conn,),
                             daemon=True).start()
        srv.close()
        try:
            if self._sock_ino is not None and \
                    os.stat(self.socket_path).st_ino == self._sock_ino:
                os.unlink(self.socket_path)
        except OSError:
            pass

    def _serve_conn(self, conn: socket.socket) -> None:
        try:
            while not self._shutdown.is_set():
                try:
                    header, arrays = _recv_msg(conn)
                except (ConnectionError, struct.error, OSError,
                        ValueError):
                    # ValueError: garbage JSON header / bad dtype from
                    # a confused client — drop the connection, not the
                    # serving thread
                    return
                self._touch_activity()
                op = header.get("op")
                try:
                    if op == "ping":
                        _send_msg(conn, {"ok": True, "pid": os.getpid()})
                    elif op == "health":
                        _send_msg(conn, self._health_reply())
                    elif op == "shutdown":
                        _send_msg(conn, {"ok": True})
                        self._shutdown.set()
                        return
                    elif op == "ppr":
                        # the coalescing plane: this connection thread
                        # blocks while its request rides a batch; the
                        # batcher thread owns the device dispatch
                        reply, out_arrays = self._ppr.submit(header,
                                                             arrays)
                        _send_msg(conn, reply, out_arrays)
                    elif op in ("pagerank", "semiring", "probe", "lane"):
                        # supervised: admission guard + worker thread +
                        # per-request deadline; the reply ships AFTER
                        # the dispatch lock is released — a slow client
                        # must not hold up other clients' dispatches
                        reply, out_arrays = self._supervised(op, header,
                                                             arrays)
                        _send_msg(conn, reply, out_arrays)
                    else:
                        _send_msg(conn, {"ok": False, "outcome": "invalid",
                                         "error": f"unknown op {op!r}"})
                except KernelServerError as e:
                    # typed dispatch failures keep their outcome on the
                    # wire so clients rehydrate the classification instead of
                    # a generic "invalid"
                    try:
                        _send_msg(conn, {"ok": False,
                                         "outcome": e.outcome,
                                         "retryable": e.retryable,
                                         "error": str(e)})
                    except (OSError, ValueError, struct.error):
                        return
                except Exception as e:  # noqa: BLE001 — report, continue
                    try:
                        _send_msg(conn, {"ok": False, "outcome": "invalid",
                                         "error": str(e)})
                    except (OSError, ValueError, struct.error):
                        return
        finally:
            conn.close()

    # --- supervised dispatch ----------------------------------------------

    def _count(self, outcome: str) -> None:
        global_metrics.increment(f"kernel_server.dispatch.{outcome}_total")

    def _dispatch_begin(self, deadline_s) -> int:
        """Register an in-flight dispatch for the health op's wedge
        detection; returns its id for :meth:`_dispatch_end`."""
        from ..utils.sanitize import shared_write
        with self._stats_lock:
            shared_write(self, "_dispatch_seq")
            self._dispatch_seq += 1
            did = self._dispatch_seq
            self._active[did] = (time.monotonic(), deadline_s)
            global_metrics.set_gauge("kernel_server.in_flight",
                                     float(len(self._active)))
        return did

    def _dispatch_end(self, did: int) -> None:
        from ..utils.sanitize import shared_write
        with self._stats_lock:
            shared_write(self, "_active")
            self._active.pop(did, None)
            global_metrics.set_gauge("kernel_server.in_flight",
                                     float(len(self._active)))

    def _supervised(self, op: str, header: dict, arrays: dict):
        """Admission guard → worker-thread dispatch → typed outcome.

        The admission guard has THREE verdicts (r21 mgtier): requests
        whose resident footprint fits the HBM budget run resident;
        graph-shaped requests that exceed it degrade to the STREAMED
        out-of-core path when the streamed working set (O(n) vectors +
        two block buffers) still fits; shed remains the honest answer
        only past that."""
        est = _estimate_request_bytes(header, arrays)
        if op in ("pagerank", "semiring"):
            from ..ops import tier as mgtier
            algorithm = str(header.get("algorithm", "pagerank"))
            n_nodes = int(header.get("n_nodes") or 0)
            n_edges = (int(arrays["src"].shape[0])
                       if "src" in arrays else 0)
            if "src" not in arrays:
                # graph_key-only request: the wire carries no edges, so
                # the request estimate misses the real footprint — size
                # admission off the cached generation's CURRENT edge
                # count or a cached oversized graph would silently ride
                # the resident path past the budget
                # unlocked read-only peek: admission must not queue
                # behind a long dispatch holding _dispatch_lock, and a
                # momentarily stale generation only skews the byte
                # ESTIMATE (the verdict is re-derived next request)
                gen = self._graphs.get(header.get("graph_key"))  # mglint: disable=MG006 — benign unlocked estimate read; blocking admission on _dispatch_lock would defeat the guard
                if gen is not None:
                    n_nodes = n_nodes or gen._n_nodes
                    n_edges = int(np.asarray(gen._coo[0]).shape[0])
                    est = max(est, _graph_footprint_bytes(
                        algorithm, n_nodes, n_edges))
            verdict, est_run = mgtier.admission_verdict(
                est, self.hbm_budget_bytes,
                n_nodes=n_nodes, n_edges=n_edges,
                streamable=algorithm in ("pagerank", "katz", "wcc"),
                precision=str(header.get("precision", "f32")),
                algorithm=algorithm)
            global_metrics.increment(f"tier.admission_{verdict}_total")
            if verdict == "streamed":
                header["_tier_streamed"] = True
                log.info(
                    "kernel_server: STREAMED %s request — resident "
                    "estimate %d bytes exceeds HBM budget %d, streamed "
                    "working set %d bytes fits", op, est,
                    self.hbm_budget_bytes, est_run)
                est = est_run
        if est > self.hbm_budget_bytes:
            self._count("shed")
            global_metrics.increment(
                "kernel_server.admission_rejected_total")
            log.warning(
                "kernel_server: SHED %s request — estimated footprint "
                "%d bytes exceeds HBM budget %d bytes", op, est,
                self.hbm_budget_bytes)
            return ({"ok": False, "outcome": "shed", "retryable": False,
                     "error": f"AdmissionRejected: estimated footprint "
                              f"{est} bytes exceeds HBM budget "
                              f"{self.hbm_budget_bytes} bytes"}, None)

        deadline_s = header.get("deadline_s")
        deadline_s = float(deadline_s) if deadline_s else None
        # trace carrier off the request protocol: the dispatch (and the
        # device stages under it) joins the caller's trace; its spans
        # ship home on the reply (take_trace below)
        carrier = header.pop("trace", None)
        did = self._dispatch_begin(deadline_s or self.wedge_after_s)
        box: dict = {}
        t_dispatch = time.perf_counter()

        def work():
            try:
                # the activation is thread-local; the worker thread must
                # adopt the remote context itself. The stage accumulator
                # collects this dispatch's device attribution (transfer/
                # compile/iterate splits from the mesh entry points);
                # its snapshot ships home in the reply header so the
                # CALLER's PROFILE sees where the HBM-seconds went.
                acc = mgstats.StageAccumulator()
                with mgstats.collecting_stages(acc):
                    with mgtrace.adopt(carrier):
                        with mgtrace.span("kernel.dispatch", op=op,
                                          pid=os.getpid()):
                            with self._dispatch_lock:
                                device_fault_point()
                                box["result"] = self._dispatch_op(
                                    op, header, arrays)
                box["stages"] = acc.snapshot()
            except BaseException as e:  # noqa: BLE001 — classified below
                box["exc"] = e
            finally:
                self._dispatch_end(did)

        def ship_trace(reply: dict) -> dict:
            """Attach this dispatch's spans + stage splits + latency."""
            global_metrics.observe(
                "kernel_server.dispatch_latency_sec",
                time.perf_counter() - t_dispatch,
                trace_id=(carrier or {}).get("trace_id"))
            if carrier and carrier.get("trace_id"):
                spans = mgtrace.take_trace(carrier["trace_id"])
                if spans:
                    reply["trace_spans"] = spans
            stages = box.get("stages")
            if stages:
                reply["stages"] = stages
            return reply

        t = threading.Thread(target=work, daemon=True,
                             name=f"ks-dispatch-{did}")
        t.start()
        t.join(deadline_s)
        if t.is_alive():
            # the dispatch is overdue; it stays in _active, so the
            # health op reports the server as wedged until it finishes
            self._count("deadline_exceeded")
            log.warning("kernel_server: dispatch %d (%s) exceeded its "
                        "%.3fs deadline — device possibly wedged",
                        did, op, deadline_s)
            return ({"ok": False, "outcome": "deadline_exceeded",
                     "retryable": True,
                     "error": f"dispatch exceeded {deadline_s}s "
                              "deadline"}, None)
        if "exc" in box:
            e = box["exc"]
            kind = classify_device_error(e)
            if kind == "oom":
                outcome, retryable = "oom", False
            elif kind in ("device_error", "device_lost"):
                outcome, retryable = "device_error", True
            else:
                outcome, retryable = "invalid", False
            self._count(outcome)
            log.warning("kernel_server: dispatch %d (%s) failed "
                        "[%s]: %s", did, op, outcome, e)
            return (ship_trace({"ok": False, "outcome": outcome,
                                "retryable": retryable,
                                "error": f"{type(e).__name__}: {e}"}),
                    None)
        reply, out_arrays = box["result"]
        if reply.get("ok", True):
            reply.setdefault("outcome", "completed")
            self._count("completed")
        else:
            reply.setdefault("outcome", "invalid")
            self._count("invalid")
        return ship_trace(reply), out_arrays

    def _dispatch_op(self, op: str, header: dict, arrays: dict):
        """Runs under _dispatch_lock on the worker thread."""
        if op == "probe":
            checksum, platform = probe_device()
            return ({"ok": True, "platform": platform,
                     "sum": checksum}, None)
        if op == "semiring":
            return self._op_semiring(header, arrays)
        if op == "lane":
            return self._op_lane(header, arrays)
        return self._op_pagerank(header, arrays)

    def _health_reply(self) -> dict:
        """Liveness + wedge detection + counters; NEVER touches the
        dispatch lock (a wedged dispatch must not wedge health)."""
        from ..utils.sanitize import shared_read
        now = time.monotonic()
        with self._stats_lock:
            shared_read(self, "_active")
            entries = list(self._active.values())
            cached = self._graphs_cached
            platform = self._platform
            shared_read(self, "_modeled_peaks")
            peaks = dict(self._modeled_peaks)
        ages = [now - t0 for t0, _dl in entries]
        wedged = any(dl is not None and now - t0 > dl
                     for t0, dl in entries)
        counters = {name: value for name, _kind, value
                    in global_metrics.snapshot()
                    if name.startswith(("kernel_server.", "analytics.",
                                        "ppr.", "delta.", "lane.",
                                        "tier.", "span.", "jit.", "mxu.",
                                        "device."))}
        return {"ok": True, "pid": os.getpid(),
                "uptime_s": round(now - self._started, 3),
                "in_flight": len(entries),
                "oldest_dispatch_s": round(max(ages, default=0.0), 3),
                "wedged": wedged,
                "graphs_cached": cached,
                "hbm_budget_bytes": self.hbm_budget_bytes,
                # device memory accounting (mgmem): modeled resident
                # peak per generation (worst-case algorithm columns of
                # the admission table, verified against XLA buffer
                # assignment by tools/mgmem) + the headroom a new
                # request's admission estimate competes for
                "memory": {
                    "hbm_budget_bytes": self.hbm_budget_bytes,
                    "modeled_peak_bytes": sum(peaks.values()),
                    "headroom_bytes": self.hbm_budget_bytes
                    - sum(peaks.values()),
                    "resident_generations": peaks,
                },
                "checkpoint_every": self.checkpoint_every,
                "wedge_after_s": self.wedge_after_s,
                "platform": platform,
                "counters": counters}

    MAX_CACHED_GRAPHS = 8     # LRU cap: the daemon is long-lived and a
    #                           resident generation pins device HBM + host

    def _update_memory_gauge(self) -> None:
        """Recompute the modeled-peak gauge + the per-generation
        snapshot _health_reply serves. Runs under the caller's
        _dispatch_lock (the only _graphs writer); the snapshot is
        handed over under _stats_lock so health never waits behind a
        wedged dispatch."""
        from ..utils.sanitize import shared_write
        peaks = {str(key): _generation_modeled_bytes(g)
                 for key, g in self._graphs.items()}  # mglint: disable=MG006 — under caller's _dispatch_lock (every _graphs mutation site calls this)
        global_metrics.set_gauge("kernel_server.hbm_modeled_peak_bytes",
                                 float(sum(peaks.values())))
        with self._stats_lock:
            shared_write(self, "_modeled_peaks")
            self._modeled_peaks = peaks

    def _resolve_generation(self, header, arrays, place: bool = True):
        """graph_key -> resident-generation lookup shared by every
        graph-shaped op. Runs under _dispatch_lock (see _op_pagerank).
        ``place=False`` (the streamed admission verdict) keeps a fresh
        generation HOST-side: the whole point of the out-of-core path
        is that the edge set never lands on the device at once, so the
        import must not place it either — the generation's lazy
        snapshot and host COO are all the tier needs.

        The generation layer (ops/delta.py, r19 mgdelta): the LRU holds
        :class:`~..ops.delta.ResidentGraph` records keyed
        ``(graph_key, base_version)`` semantics — a request carrying
        ``graph_version``/``base_version`` plus the change-log delta
        payload (``changed`` dense indices + the changed vertices'
        CURRENT incident edges ``inc_src``/``inc_dst``/``inc_w``)
        advances the resident generation O(delta) instead of
        re-importing the full edge list; the request rides the freshly
        spliced graph. A request at the resident version runs directly.
        Returns the ResidentGraph or None (caller replies invalid).
        """
        from ..ops import delta as mgdelta
        from ..ops.csr import from_coo
        from ..utils.sanitize import shared_write
        with mgtrace.span("kernel.generation"):
            key = header.get("graph_key")
            want = header.get("graph_version")
            # mglint: disable=MG006 — the dispatcher (_supervised worker) holds _dispatch_lock across this whole handler; intraprocedural analysis cannot see caller locks
            gen = self._graphs.pop(key, None) if key else None
            if gen is not None:
                self._graphs[key] = gen            # re-insert: LRU refresh
            if gen is not None and want is not None \
                    and int(want) > gen.version:
                base = header.get("base_version")
                applied = False
                if header.get("has_delta") \
                        and header.get("ids_stable", True) \
                        and base is not None and int(base) == gen.version \
                        and "changed" in arrays and "inc_src" in arrays:
                    d = mgdelta.diff_incident(
                        gen.coo, arrays["changed"],
                        arrays["inc_src"], arrays["inc_dst"],
                        arrays.get("inc_w"), gen.n_nodes,
                        int(base), int(want))
                    applied = gen.apply(d)
                    if applied:
                        # the spliced edge set resizes the generation's
                        # modeled footprint though the LRU is unchanged
                        self._update_memory_gauge()
                if not applied:
                    # stale resident and no usable delta: a full
                    # re-import (below) is the only honest path — serving
                    # the old generation would return pre-commit results
                    # as fresh
                    self._graphs.pop(key, None)  # mglint: disable=MG006,MG007 — under caller's _dispatch_lock
                    gen = None
                    self._update_memory_gauge()
            if gen is None:
                if "src" not in arrays:
                    return None
                g = from_coo(arrays["src"].astype(np.int64),
                             arrays["dst"].astype(np.int64),
                             arrays.get("weights"),
                             n_nodes=header.get("n_nodes"))
                if place:
                    g = g.to_device()
                gen = mgdelta.ResidentGraph(key, int(want or 0), g)
                if key:
                    # mglint: disable=MG006,MG007 — same _dispatch_lock contract as above: the LRU insert+evict runs under the dispatcher's lock
                    self._graphs[key] = gen
                    while len(self._graphs) > self.MAX_CACHED_GRAPHS:  # mglint: disable=MG006 — under caller's _dispatch_lock
                        self._graphs.pop(next(iter(self._graphs)))  # mglint: disable=MG006,MG007 — under caller's _dispatch_lock
                    global_metrics.set_gauge("delta.resident_generations",
                                             float(len(self._graphs)))  # mglint: disable=MG006 — len snapshot under caller's _dispatch_lock
                    self._update_memory_gauge()
                    with self._stats_lock:
                        shared_write(self, "_graphs_cached")
                        self._graphs_cached = len(self._graphs)  # mglint: disable=MG006 — len snapshot for health; insert path holds _dispatch_lock
            return gen

    def _resolve_graph(self, header, arrays):
        """Back-compat DeviceGraph view of :meth:`_resolve_generation`
        (the PPR batcher and tests consume the snapshot directly)."""
        gen = self._resolve_generation(header, arrays)
        return None if gen is None else gen.graph

    def _op_pagerank(self, header, arrays):
        """Runs under _dispatch_lock; returns (reply_header,
        reply_arrays) for the caller to ship outside the lock. Routes
        through the RESUMABLE mesh entry point (mesh-of-1 unless
        MEMGRAPH_TPU_MESH_DEVICES configures a wider mesh), so a device
        fault mid-run redoes at most checkpoint_every iterations.

        Rides the resident-generation layer (r19 mgdelta): a request at
        a known ``(graph_key, base_version)`` with a delta payload
        refreshes the resident ShardedCSR O(delta) and warm-starts the
        fixpoint from this generation's previous solution — the
        commit-then-CALL path converges in the few iterations the
        perturbation actually needs."""
        from ..ops import delta as mgdelta
        from ..ops import semiring as S
        from ..parallel.mesh import analytics_mesh, get_mesh_context
        streamed = bool(header.pop("_tier_streamed", False))
        gen = self._resolve_generation(header, arrays,
                                       place=not streamed)
        if gen is None:
            return ({"ok": False, "error": "unknown graph_key "
                     "and no edge arrays supplied"}, None)
        key = header.get("graph_key")
        damping = header.get("damping", 0.85)
        tol = header.get("tol", 1e-6)
        precision = header.get("precision", "f32")
        max_iterations = header.get("max_iterations", 100)
        params_key = ("pagerank", float(damping), float(tol),
                      str(precision))
        # unchanged generation + same params: the stored solution is
        # THE answer — identical repeated requests get identical bytes
        hit = gen.cached_result("pagerank", params_key, max_iterations)
        if hit is not None:
            return ({"ok": True, "err": float(hit.err or 0.0),
                     "iters": int(hit.iters or 0), "cache": "hit",
                     "warm_started": True,
                     "graph_version": gen.version},
                    {"ranks": np.asarray(hit.x, dtype=np.float32)})
        x0, _reason = gen.warm_x0("pagerank", params_key)
        if streamed:
            # out-of-core: the edge set never places — blocks stream
            # from the generation's host-pinned paging plan, the rank
            # vector stays device-resident, chunks checkpoint as usual
            from ..parallel.distributed import pagerank_streamed
            t = gen.ensure_tier(precision=_tier_precision(precision))
            ranks, err, iters = pagerank_streamed(
                t, damping=damping, max_iterations=max_iterations,
                tol=tol, x0=x0,
                checkpoint_every=self.checkpoint_every,
                job=f"kernel_server:pagerank:{key}" if key else None)
        else:
            ctx = analytics_mesh() or get_mesh_context(1)
            # run straight off the resident partition-centric variant
            # (the spliced layout) — the DeviceGraph snapshot stays
            # lazy, so a commit costs O(delta), never a CSR rebuild
            scsr = gen.ensure_sharded(ctx, by="src")
            from ..parallel.distributed import pagerank_partition_centric
            with S.backend_extent("mesh"):
                ranks, err, iters = pagerank_partition_centric(
                    scsr, ctx, damping=damping,
                    max_iterations=max_iterations,
                    tol=tol, precision=precision, x0=x0,
                    checkpoint_every=self.checkpoint_every,
                    job=f"kernel_server:pagerank:{key}" if key else None)
        ranks = np.asarray(ranks, dtype=np.float32)
        gen.note_solution("pagerank", params_key, ranks,
                          err=float(err), iters=int(iters),
                          max_iterations=int(max_iterations))
        if x0 is not None:
            mgdelta.record_warm_start("pagerank", int(iters))
        return ({"ok": True, "err": float(err), "iters": int(iters),
                 "warm_started": x0 is not None,
                 "tier": "streamed" if streamed else "resident",
                 "graph_version": gen.version},
                {"ranks": ranks})

    def _op_semiring(self, header, arrays):
        """Semiring-core dispatch: run a named core-routed algorithm at
        a requested precision through the resident runtime.  Serves
        `pagerank` (plus-times, any precision), `katz`, `wcc`,
        `labelprop` — all four
        riding the resident-generation warm-start layer (r19 mgdelta,
        per-algorithm contracts in ops/delta.py) — and `bfs` (min-plus
        levels via the GENERIC mesh semiring kernel; source-dependent,
        never warm-started).  Runs under _dispatch_lock."""
        from ..ops import delta as mgdelta
        from ..ops import semiring as S
        from ..parallel import analytics
        from ..parallel.mesh import analytics_mesh, get_mesh_context
        streamed = bool(header.pop("_tier_streamed", False))
        gen = self._resolve_generation(header, arrays,
                                       place=not streamed)
        if gen is None:
            return ({"ok": False, "error": "unknown graph_key "
                     "and no edge arrays supplied"}, None)
        # streamed: never materialize the snapshot — the paging plan
        # (gen.ensure_tier) is built straight off the host COO
        g = None if streamed else gen.graph
        algorithm = header.get("algorithm", "pagerank")
        precision = header.get("precision", "f32")
        max_iterations = header.get("max_iterations", 100)
        if algorithm == "pagerank":
            from ..ops.pagerank import pagerank
            damping = header.get("damping", 0.85)
            tol = header.get("tol", 1e-6)
            params_key = ("pagerank", float(damping), float(tol),
                          str(precision))
            hit = gen.cached_result("pagerank", params_key,
                                    max_iterations)
            if hit is not None:
                return ({"ok": True, "err": float(hit.err or 0.0),
                         "iters": int(hit.iters or 0), "cache": "hit",
                         "algorithm": algorithm,
                         "precision": precision, "warm_started": True,
                         "graph_version": gen.version},
                        {"ranks": np.asarray(hit.x,
                                             dtype=np.float32)})
            x0, _reason = gen.warm_x0("pagerank", params_key)
            if streamed:
                from ..parallel.distributed import pagerank_streamed
                t = gen.ensure_tier(
                    precision=_tier_precision(precision))
                ranks, err, iters = pagerank_streamed(
                    t, damping=damping,
                    max_iterations=max_iterations, tol=tol, x0=x0,
                    checkpoint_every=self.checkpoint_every)
            else:
                # ops-level entry: route_backend picks mesh/mxu/segment
                # and records the per-backend stage PROFILE shows
                ranks, err, iters = pagerank(
                    g, damping=damping, max_iterations=max_iterations,
                    tol=tol, precision=precision, x0=x0)
            ranks = np.asarray(ranks, dtype=np.float32)
            gen.note_solution("pagerank", params_key, ranks,
                              err=float(err), iters=int(iters),
                              max_iterations=int(max_iterations))
            if x0 is not None:
                mgdelta.record_warm_start("pagerank", int(iters))
            return ({"ok": True, "err": float(err), "iters": int(iters),
                     "algorithm": algorithm, "precision": precision,
                     "warm_started": x0 is not None,
                     "tier": "streamed" if streamed else "resident",
                     "graph_version": gen.version},
                    {"ranks": ranks})
        if algorithm == "katz":
            from ..ops.katz import katz_centrality
            alpha = header.get("alpha", 0.2)
            tol = header.get("tol", 1e-6)
            params_key = ("katz", float(alpha),
                          float(header.get("beta", 1.0)), float(tol),
                          str(precision))
            hit = gen.cached_result("katz", params_key, max_iterations)
            if hit is not None:
                return ({"ok": True, "err": float(hit.err or 0.0),
                         "iters": int(hit.iters or 0), "cache": "hit",
                         "algorithm": algorithm,
                         "precision": precision, "warm_started": True,
                         "graph_version": gen.version},
                        {"ranks": np.asarray(hit.x,
                                             dtype=np.float32)})
            x0, _reason = gen.warm_x0("katz", params_key)
            if streamed:
                from ..parallel.distributed import katz_streamed
                t = gen.ensure_tier(
                    precision=_tier_precision(precision))
                xs, err, iters = katz_streamed(
                    t, alpha=alpha, beta=header.get("beta", 1.0),
                    max_iterations=max_iterations, tol=tol, x0=x0,
                    checkpoint_every=self.checkpoint_every)
            else:
                xs, err, iters = katz_centrality(
                    g, alpha=alpha, beta=header.get("beta", 1.0),
                    max_iterations=max_iterations, tol=tol,
                    precision=precision, x0=x0)
            xs = np.asarray(xs, dtype=np.float32)
            gen.note_solution("katz", params_key, xs, err=float(err),
                              iters=int(iters),
                              max_iterations=int(max_iterations))
            if x0 is not None:
                mgdelta.record_warm_start("katz", int(iters))
            return ({"ok": True, "err": float(err), "iters": int(iters),
                     "algorithm": algorithm, "precision": precision,
                     "warm_started": x0 is not None,
                     "tier": "streamed" if streamed else "resident",
                     "graph_version": gen.version},
                    {"ranks": xs})
        if algorithm == "wcc":
            from ..ops.components import weakly_connected_components
            params_key = ("wcc",)
            hit = gen.cached_result("wcc", params_key, max_iterations)
            if hit is not None:
                return ({"ok": True, "iters": int(hit.iters or 0),
                         "cache": "hit", "algorithm": algorithm,
                         "warm_started": True,
                         "graph_version": gen.version},
                        {"components": np.asarray(hit.x,
                                                  dtype=np.int32)})
            comp0, _reason = gen.warm_x0("wcc", params_key)
            if streamed:
                from ..parallel.distributed import wcc_streamed
                t = gen.ensure_tier(precision="f32")
                comp, _changed, iters = wcc_streamed(
                    t, max_iterations=max_iterations, comp0=comp0,
                    checkpoint_every=self.checkpoint_every)
            else:
                comp, iters = weakly_connected_components(
                    g, max_iterations=max_iterations, comp0=comp0)
            comp = np.asarray(comp, dtype=np.int32)
            gen.note_solution("wcc", params_key, comp,
                              iters=int(iters),
                              max_iterations=int(max_iterations))
            if comp0 is not None:
                mgdelta.record_warm_start("wcc", int(iters))
            return ({"ok": True, "iters": int(iters),
                     "algorithm": algorithm,
                     "warm_started": comp0 is not None,
                     "tier": "streamed" if streamed else "resident",
                     "graph_version": gen.version},
                    {"components": comp})
        if algorithm == "labelprop":
            from ..ops.labelprop import label_propagation
            self_weight = header.get("self_weight", 0.0)
            directed = bool(header.get("directed", False))
            params_key = ("labelprop", float(self_weight), directed)
            hit = gen.cached_result("labelprop", params_key,
                                    max_iterations)
            if hit is not None:
                return ({"ok": True, "iters": int(hit.iters or 0),
                         "cache": "hit", "algorithm": algorithm,
                         "warm_started": True,
                         "graph_version": gen.version},
                        {"labels": np.asarray(hit.x, dtype=np.int32)})
            labels0, _reason = gen.warm_x0("labelprop", params_key)
            labels, iters = label_propagation(
                g, max_iterations=max_iterations,
                self_weight=self_weight, directed=directed,
                labels0=labels0)
            labels = np.asarray(labels, dtype=np.int32)
            gen.note_solution("labelprop", params_key, labels,
                              iters=int(iters),
                              max_iterations=int(max_iterations))
            if labels0 is not None:
                mgdelta.record_warm_start("labelprop", int(iters))
            return ({"ok": True, "iters": int(iters),
                     "algorithm": algorithm,
                     "warm_started": labels0 is not None,
                     "graph_version": gen.version},
                    {"labels": labels})
        if algorithm == "bfs":
            ctx = analytics_mesh() or get_mesh_context(1)
            with S.backend_extent("mesh"):
                levels, iters = analytics.bfs_mesh(
                    g, ctx, int(header.get("source", 0)),
                    max_iterations=max_iterations, precision=precision,
                    checkpoint_every=self.checkpoint_every)
            return ({"ok": True, "iters": int(iters),
                     "algorithm": algorithm, "precision": precision},
                    {"levels": np.asarray(levels, dtype=np.int32)})
        return ({"ok": False,
                 "error": f"unknown semiring algorithm {algorithm!r}"},
                None)

    def _op_lane(self, header, arrays):
        """Compiled read-lane hop-count dispatch (r20 mglane): the same
        masked plus_first SpMV chain the in-process lane runs
        (ops/pipeline.py hop_counts), served from the resident device
        plane so OLTP frontends can route their compiled expansions
        like any analytics op. Runs under _dispatch_lock."""
        from ..ops import pipeline as pl
        for need in ("src", "dst", "emask", "smask", "midmask", "tmask"):
            if need not in arrays:
                return ({"ok": False,
                         "error": f"lane op needs array {need!r}"}, None)
        global_metrics.increment("lane.remote_dispatch_total")
        try:
            totals = pl.hop_counts(
                arrays["src"], arrays["dst"], arrays["emask"],
                arrays["smask"], arrays["midmask"], arrays["tmask"],
                int(header.get("n_nodes", len(arrays["smask"]))),
                hops=int(header.get("hops", 2)),
                include_lower=bool(header.get("include_lower", False)),
                edge_unique=bool(header.get("edge_unique", True)),
                need_rows=bool(header.get("need_rows", True)),
                need_distinct=bool(header.get("need_distinct", False)),
                fingerprint=header.get("fingerprint"))
        except pl.LaneRefused as e:
            return ({"ok": False, "outcome": "invalid",
                     "lane_refused": e.reason,
                     "error": f"lane refused: {e.reason}"}, None)
        return ({"ok": True, **totals}, None)


# --------------------------------------------------------------------------
# client
# --------------------------------------------------------------------------

class KernelClient:
    def __init__(self, socket_path: str = DEFAULT_SOCKET,
                 timeout: float = 300.0) -> None:
        self.socket_path = socket_path
        self._sock = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        self._sock.settimeout(timeout)
        self._sock.connect(socket_path)
        #: False while a call is between its request and its whole
        #: reply: the stream then holds part of a frame, and the
        #: connection cannot carry another call
        self.in_sync = True

    def settimeout(self, timeout: float | None) -> None:
        self._sock.settimeout(timeout)

    def call(self, header: dict, arrays=None):
        """One request and its reply. The stream carries one call at a
        time: two threads never share a KernelClient
        (:class:`SupervisedKernelClient` leases one per call)."""
        self.in_sync = False
        _send_msg(self._sock, header, arrays)
        h, out = _recv_msg(self._sock)
        self.in_sync = True
        # spans the server recorded for OUR trace come home on the
        # reply; adopt them so the retained trace is connected
        spans = h.pop("trace_spans", None)
        if spans:
            mgtrace.adopt_spans(spans)
        # same for the dispatch's device-stage splits: merge into the
        # caller's active stage accumulator (PROFILE attribution)
        mgstats.merge_stages(h.pop("stages", None))
        return h, out

    def ping(self) -> bool:
        try:
            h, _ = self.call({"op": "ping"})
            return bool(h.get("ok"))
        except (OSError, ConnectionError):
            return False

    def health(self) -> dict:
        h, _ = self.call({"op": "health"})
        return h

    def probe(self) -> dict:
        """Typed device probe through the resident runtime."""
        header = {"op": "probe"}
        carrier = mgtrace.inject()
        if carrier is not None:
            header["trace"] = carrier
        h, _ = self.call(header)
        return h

    @staticmethod
    def _serving_arrays(arrays: dict, changed, inc_src, inc_dst,
                        inc_w) -> None:
        """Attach the analytics serving-plane delta payload (r19
        mgdelta): the change-log's dense changed indices plus the
        changed vertices' CURRENT incident edges — the server diffs
        them against its resident generation and refreshes O(delta)."""
        if changed is not None:
            arrays["changed"] = np.asarray(changed, dtype=np.int32)
        if inc_src is not None:
            arrays["inc_src"] = np.asarray(inc_src, dtype=np.int64)
            arrays["inc_dst"] = np.asarray(inc_dst, dtype=np.int64)
            if inc_w is not None:
                arrays["inc_w"] = np.asarray(inc_w, dtype=np.float32)

    def pagerank(self, src=None, dst=None, weights=None, n_nodes=None,
                 graph_key=None, deadline_s=None, graph_version=None,
                 base_version=None, ids_stable=True, changed=None,
                 inc_src=None, inc_dst=None, inc_w=None, **params):
        arrays = {}
        if src is not None:
            arrays["src"] = np.asarray(src, dtype=np.int64)
            arrays["dst"] = np.asarray(dst, dtype=np.int64)
            if weights is not None:
                arrays["weights"] = np.asarray(weights, dtype=np.float32)
        self._serving_arrays(arrays, changed, inc_src, inc_dst, inc_w)
        header = {"op": "pagerank", "graph_key": graph_key,
                  "n_nodes": n_nodes, **params}
        if graph_version is not None:
            header["graph_version"] = int(graph_version)
            header["base_version"] = base_version
            header["ids_stable"] = bool(ids_stable)
            header["has_delta"] = changed is not None
        if deadline_s is not None:
            header["deadline_s"] = deadline_s
        carrier = mgtrace.inject()
        if carrier is not None:
            header["trace"] = carrier
        h, out = self.call(header, arrays)
        if not h.get("ok"):
            _raise_for_reply(h)
        return out["ranks"], h["err"], h["iters"]

    def ppr(self, sources, src=None, dst=None, weights=None, n_nodes=None,
            graph_key=None, graph_version=0, base_version=None,
            ids_stable=True, changed=None, inc_src=None, inc_dst=None,
            inc_w=None, top_k=0, damping=0.85,
            tol=1e-6, max_iterations=100, precision="f32",
            deadline_s=None):
        """One personalized-PageRank request through the server's
        COALESCING plane: concurrent callers batch into one multi-source
        SpMM fixpoint; repeats hit the change-log-invalidated result
        cache. Returns (reply_header, arrays) — arrays carry either
        ``ranks`` (top_k == 0) or ``topk_val``/``topk_idx``.

        ``graph_version``/``base_version``/``changed``/``ids_stable``
        are the cache-invalidation protocol: ``changed`` lists the dense
        node indices mutated in (base_version, graph_version] (from the
        storage change log); omitted → the server conservatively
        invalidates every cached vector for this graph_key on a version
        bump. ``inc_src``/``inc_dst``/``inc_w`` (r19 mgdelta) carry the
        changed vertices' CURRENT incident edges so the server can
        refresh its resident snapshot O(delta) instead of needing the
        full edge arrays after every commit."""
        arrays = {"sources": np.asarray(sources, dtype=np.int32)}
        if src is not None:
            arrays["src"] = np.asarray(src, dtype=np.int64)
            arrays["dst"] = np.asarray(dst, dtype=np.int64)
            if weights is not None:
                arrays["weights"] = np.asarray(weights, dtype=np.float32)
        self._serving_arrays(arrays, changed, inc_src, inc_dst, inc_w)
        header = {"op": "ppr", "graph_key": graph_key, "n_nodes": n_nodes,
                  "graph_version": int(graph_version),
                  "base_version": base_version,
                  "ids_stable": bool(ids_stable),
                  "has_delta": changed is not None,
                  "damping": float(damping), "tol": float(tol),
                  "max_iterations": int(max_iterations),
                  "precision": str(precision), "top_k": int(top_k)}
        if deadline_s is not None:
            header["deadline_s"] = deadline_s
        carrier = mgtrace.inject()
        if carrier is not None:
            header["trace"] = carrier
        h, out = self.call(header, arrays)
        if not h.get("ok"):
            _raise_for_reply(h)
        return h, out

    def semiring(self, algorithm: str = "pagerank", src=None, dst=None,
                 weights=None, n_nodes=None, graph_key=None,
                 precision: str = "f32", deadline_s=None,
                 graph_version=None, base_version=None, ids_stable=True,
                 changed=None, inc_src=None, inc_dst=None, inc_w=None,
                 **params):
        """Run a semiring-core-routed algorithm on the resident daemon.
        Returns the reply header + arrays dict (algorithm-shaped:
        pagerank/katz -> ranks/err/iters, wcc -> components/iters,
        labelprop -> labels/iters, bfs -> levels/iters). The
        graph_version/base_version/changed/inc_* kwargs are the r19
        delta protocol (see :meth:`pagerank`)."""
        arrays = {}
        if src is not None:
            arrays["src"] = np.asarray(src, dtype=np.int64)
            arrays["dst"] = np.asarray(dst, dtype=np.int64)
            if weights is not None:
                arrays["weights"] = np.asarray(weights, dtype=np.float32)
        self._serving_arrays(arrays, changed, inc_src, inc_dst, inc_w)
        header = {"op": "semiring", "algorithm": algorithm,
                  "graph_key": graph_key, "n_nodes": n_nodes,
                  "precision": precision, **params}
        if graph_version is not None:
            header["graph_version"] = int(graph_version)
            header["base_version"] = base_version
            header["ids_stable"] = bool(ids_stable)
            header["has_delta"] = changed is not None
        if deadline_s is not None:
            header["deadline_s"] = deadline_s
        carrier = mgtrace.inject()
        if carrier is not None:
            header["trace"] = carrier
        h, out = self.call(header, arrays)
        if not h.get("ok"):
            _raise_for_reply(h)
        return h, out

    def lane_hops(self, src, dst, emask, smask, midmask, tmask, *,
                  n_nodes, hops=2, include_lower=False, edge_unique=True,
                  need_rows=True, need_distinct=False, deadline_s=None,
                  fingerprint=None) -> dict:
        """Dispatch one compiled read-lane hop-count program (r20
        mglane) on the resident daemon. The server refuses with a typed
        reason exactly like the in-process lane; the caller's LOUD
        fallback contract is identical. Returns {"rows": n,
        "distinct": n} per request flags."""
        from ..ops.pipeline import LaneRefused
        arrays = {"src": np.asarray(src, dtype=np.int32),
                  "dst": np.asarray(dst, dtype=np.int32),
                  "emask": np.asarray(emask, dtype=bool),
                  "smask": np.asarray(smask, dtype=bool),
                  "midmask": np.asarray(midmask, dtype=np.float32),
                  "tmask": np.asarray(tmask, dtype=np.float32)}
        header = {"op": "lane", "n_nodes": int(n_nodes),
                  "hops": int(hops),
                  "include_lower": bool(include_lower),
                  "edge_unique": bool(edge_unique),
                  "need_rows": bool(need_rows),
                  "need_distinct": bool(need_distinct),
                  "fingerprint": fingerprint}
        if deadline_s is not None:
            header["deadline_s"] = deadline_s
        carrier = mgtrace.inject()
        if carrier is not None:
            header["trace"] = carrier
        h, _out = self.call(header, arrays)
        if not h.get("ok"):
            if h.get("lane_refused"):
                raise LaneRefused(h["lane_refused"],
                                  h.get("error", ""))
            _raise_for_reply(h)
        return {k: int(v) for k, v in h.items()
                if k in ("rows", "distinct")}

    def shutdown(self) -> None:
        try:
            self.call({"op": "shutdown"})
        except (OSError, ConnectionError):
            pass

    def close(self) -> None:
        self._sock.close()


# --------------------------------------------------------------------------
# client-side supervisor
# --------------------------------------------------------------------------

class SupervisedKernelClient:
    """Supervised access to the resident kernel server.

    Wraps :class:`KernelClient` with the client half of the resilience
    contract:

      * requests carry a per-request ``deadline_s`` and retry under a
        shared :class:`RetryPolicy` (per-attempt timeout + overall
        deadline) — but ONLY idempotent ones; non-idempotent calls
        surface the first typed failure;
      * connection loss (the daemon died — e.g. device.lost killed it)
        respawns the server via :func:`ensure_server` and retries;
      * ``check_once()`` (and the optional background health loop)
        polls the ``health`` op and RESTARTS a wedged or unreachable
        server process — SIGKILL + respawn; the daemon's stale-socket
        reclaim logic makes that safe;
      * typed non-retryable outcomes (AdmissionRejected, KernelOom)
        propagate immediately.
    """

    def __init__(self, socket_path: str = DEFAULT_SOCKET,
                 retry: RetryPolicy | None = None,
                 spawn_timeout_s: float = 120.0,
                 idle_timeout_s: float = 900.0,
                 deadline_s: float | None = None,
                 spawn: bool = True) -> None:
        import threading
        from ..utils.locks import tracked_lock
        from ..utils.sanitize import shared_field
        self.socket_path = socket_path
        self.retry = retry or RetryPolicy(
            base_delay=0.2, max_delay=2.0, max_retries=4,
            attempt_timeout=300.0)
        self.spawn_timeout_s = spawn_timeout_s
        self.idle_timeout_s = idle_timeout_s
        self.deadline_s = deadline_s
        self.spawn = spawn
        # leaf lock guarding the pool and the pid: touched by every
        # caller thread AND the health loop; network I/O always happens
        # OUTSIDE it
        self._state_lock = tracked_lock("SupervisedKernelClient._state_lock")
        self._idle: list[KernelClient] = []
        self._epoch = 0         # bumped by _drop(): older leases close
        self._pid: int | None = None
        self._stop = threading.Event()
        self._health_thread = None
        shared_field(self, "_idle", "_pid")

    # --- connection management ---------------------------------------------
    #
    # The daemon's stream protocol carries one call at a time, and the
    # Bolt server runs queries on many threads through ONE supervised
    # client per daemon (shared_client). So every in-flight call leases
    # a connection of its own: concurrent callers neither share a stream
    # nor wait for each other, and the daemon, which serves each
    # connection on its own thread, sees them as concurrently as they
    # were sent (its PPR plane can only coalesce riders it holds at
    # once). Connections are opened on demand and kept for reuse.

    #: idle connections kept for reuse, one for each of the Bolt
    #: server's executor threads (server/bolt.py); a lease returned
    #: beyond that is closed
    POOL_KEEP = 32

    def _set_pid(self, pid: int | None) -> None:
        from ..utils.sanitize import shared_write
        with self._state_lock:
            shared_write(self, "_pid")
            self._pid = pid

    def _get_pid(self) -> int | None:
        from ..utils.sanitize import shared_read
        with self._state_lock:
            shared_read(self, "_pid")
            return self._pid

    def _open(self) -> KernelClient:
        timeout = self.retry.attempt_timeout or 300.0
        if self.spawn:
            c = ensure_server(self.socket_path,
                              spawn_timeout_s=self.spawn_timeout_s,
                              idle_timeout_s=self.idle_timeout_s)
            if c is None:
                raise ConnectionError(
                    "kernel server spawn starved (no responder within "
                    f"{self.spawn_timeout_s}s)")
            c.settimeout(timeout)
        else:
            c = KernelClient(self.socket_path, timeout=timeout)
        try:
            h, _ = c.call({"op": "ping"})
            self._set_pid(h.get("pid"))
        except (OSError, ConnectionError) as e:
            log.debug("post-connect ping failed: %s", e)
        return c

    def _lease(self) -> tuple:
        """(connection, epoch) for one call: an idle one, else new."""
        from ..utils.sanitize import shared_write
        with self._state_lock:
            shared_write(self, "_idle")
            epoch = self._epoch
            if self._idle:
                return self._idle.pop(), epoch
        return self._open(), epoch

    def _release(self, lease: tuple) -> None:
        """Back to the pool, unless a call was cut short on it, the pool
        was dropped since it was leased, or the pool is full."""
        from ..utils.sanitize import shared_write
        c, epoch = lease
        with self._state_lock:
            shared_write(self, "_idle")
            if c.in_sync and epoch == self._epoch \
                    and len(self._idle) < self.POOL_KEEP:
                self._idle.append(c)
                return
        self._close(c)

    @staticmethod
    def _close(c: KernelClient) -> None:
        try:
            c.close()
        except OSError as e:
            log.debug("closing kernel client: %s", e)

    def _drop(self) -> None:
        """Forget every connection (the daemon is gone or being
        replaced): the idle ones close now, the leased ones when their
        calls return."""
        from ..utils.sanitize import shared_write
        with self._state_lock:
            shared_write(self, "_idle")
            idle, self._idle = self._idle, []
            self._epoch += 1
        for c in idle:
            self._close(c)

    # --- supervision --------------------------------------------------------

    def health(self, timeout: float = 5.0) -> dict | None:
        """The daemon's health reply over a FRESH connection (a wedged
        request stream must not block the health probe), or None when
        nothing answers."""
        try:
            c = KernelClient(self.socket_path, timeout=timeout)
        except OSError:
            return None
        try:
            return c.health()
        except (OSError, ConnectionError):
            return None
        finally:
            try:
                c.close()
            except OSError as e:
                log.debug("closing health probe connection: %s", e)

    def _mirror_daemon_counters(self, h: dict) -> None:
        """Publish the daemon's health-reply counters through the LOCAL
        global Metrics registry so the supervisor's prometheus_text()
        carries them (restarts, sheds, deadline_exceeded, oom, ...) —
        not only callers of the ``health`` op. Gauges, not counters:
        they mirror another process's monotonic state and must not
        double-count across supervision rounds."""
        for name, value in (h.get("counters") or {}).items():
            short = name[len("kernel_server."):] \
                if name.startswith("kernel_server.") else name
            global_metrics.set_gauge(f"kernel_server.daemon.{short}",
                                     float(value))
        global_metrics.set_gauge("kernel_server.daemon.in_flight",
                                 float(h.get("in_flight", 0)))
        global_metrics.set_gauge("kernel_server.daemon.wedged",
                                 1.0 if h.get("wedged") else 0.0)

    def check_once(self) -> str:
        """One supervision round: health-check, restart when wedged or
        unreachable. Returns "ok" or "restarted"."""
        global_metrics.increment(
            "kernel_server.supervisor.health_checks_total")
        h = self.health()
        if h is None:
            self.restart_server(reason="unreachable")
            return "restarted"
        self._mirror_daemon_counters(h)
        if h.get("wedged"):
            global_metrics.increment(
                "kernel_server.supervisor.wedge_detected_total")
            self.restart_server(reason="wedged", pid=h.get("pid"))
            return "restarted"
        self._set_pid(h.get("pid"))
        return "ok"

    def restart_server(self, reason: str = "manual",
                       pid: int | None = None) -> None:
        """Kill the (wedged / device-lost) daemon and let the next call
        respawn it. The daemon's probe-then-bind + stale-socket reclaim
        makes the SIGKILL safe: the successor reclaims the path."""
        pid = pid or self._get_pid()
        self._drop()
        self._set_pid(None)
        if pid and pid != os.getpid():
            try:
                os.kill(pid, signal.SIGKILL)
            except (OSError, ProcessLookupError) as e:
                log.debug("kernel server pid %s already gone: %s", pid, e)
        global_metrics.increment("kernel_server.supervisor.restarts_total")
        log.warning("kernel_server supervisor: restarting server "
                    "(reason=%s pid=%s)", reason, pid)

    def start_health_loop(self, interval_s: float = 5.0) -> None:
        """Background supervision: health-check every interval_s,
        restarting a wedged/lost daemon. Idempotent."""
        import threading
        if self._health_thread is not None:
            return

        def loop():
            while not self._stop.wait(interval_s):
                try:
                    self.check_once()
                except Exception:  # noqa: BLE001 — supervision must survive
                    log.exception("kernel_server supervisor health "
                                  "check failed")

        self._health_thread = threading.Thread(
            target=loop, daemon=True, name="ks-supervisor")
        self._health_thread.start()

    # --- supervised calls ---------------------------------------------------

    def _call_supervised(self, op: str, invoke, idempotent: bool):
        """The shared supervised-retry skeleton: ``invoke(client)`` runs
        under the retry policy with the typed-outcome branching every
        supervised op shares (pagerank, ppr, ...)."""
        last: Exception | None = None
        for _attempt in self.retry.attempts():
            lease = None
            try:
                lease = self._lease()
                t0 = time.perf_counter()
                with mgtrace.span("kernel.request", op=op,
                                  attempt=_attempt):
                    result = invoke(lease[0])
                # client-observed dispatch wall time (request + device +
                # reply) for the caller's PROFILE attribution
                mgstats.record_stage("kernel_dispatch",
                                     time.perf_counter() - t0)
                return result
            except (AdmissionRejected, KernelOom):
                # deterministic against this budget/graph: retry is noise
                raise
            except KernelDeadlineExceeded as e:
                last = e
                if not idempotent:
                    raise
                global_metrics.increment(
                    "kernel_server.client.retries_total")
                self.check_once()    # a wedged server gets restarted here
            except KernelDeviceError as e:
                last = e
                if not idempotent:
                    raise
                global_metrics.increment(
                    "kernel_server.client.retries_total")
            except (ConnectionError, OSError) as e:
                # daemon gone (device.lost kill) or socket timed out:
                # drop the connections; _open respawns when allowed
                last = e
                self._drop()
                if not idempotent:
                    raise
                global_metrics.increment(
                    "kernel_server.client.retries_total")
            finally:
                if lease is not None:
                    self._release(lease)
        raise KernelServerError(
            f"kernel request failed after {self.retry.max_retries + 1} "
            f"supervised attempts: {last}",
            outcome=getattr(last, "outcome", "invalid"),
            retryable=False) from last

    def pagerank(self, src=None, dst=None, weights=None, n_nodes=None,
                 graph_key=None, idempotent: bool = True,
                 deadline_s: float | None = None, **params):
        """PageRank with supervised retries. Pure computation ⇒
        idempotent by default; callers piping through side-effecting
        wrappers pass idempotent=False and get fail-fast semantics."""
        if deadline_s is None:
            deadline_s = self.deadline_s
        return self._call_supervised(
            "pagerank",
            lambda c: c.pagerank(src=src, dst=dst, weights=weights,
                                 n_nodes=n_nodes, graph_key=graph_key,
                                 deadline_s=deadline_s, **params),
            idempotent)

    def lane_hops(self, src, dst, emask, smask, midmask, tmask, *,
                  n_nodes, idempotent: bool = True,
                  deadline_s: float | None = None, **params):
        """Compiled read-lane hop counts with supervised retries (r20
        mglane). Pure computation ⇒ idempotent; LaneRefused passes
        through untouched so the caller's typed fallback fires."""
        if deadline_s is None:
            deadline_s = self.deadline_s
        # a typed LaneRefused from the reply propagates untouched (it
        # is not one of the supervised retry classes), so the caller's
        # loud fallback fires instead of a retry storm
        return self._call_supervised(
            "lane",
            lambda c: c.lane_hops(src, dst, emask, smask, midmask,
                                  tmask, n_nodes=n_nodes,
                                  deadline_s=deadline_s, **params),
            idempotent)

    def ppr(self, sources, idempotent: bool = True,
            deadline_s: float | None = None, **params):
        """Coalesced personalized PageRank with supervised retries (see
        :meth:`KernelClient.ppr` for the serving protocol). Pure
        computation ⇒ idempotent by default; a device fault mid-batch
        fails every rider typed, so the retry here re-enters the
        coalescing queue cleanly."""
        if deadline_s is None:
            deadline_s = self.deadline_s
        return self._call_supervised(
            "ppr",
            lambda c: c.ppr(sources, deadline_s=deadline_s, **params),
            idempotent)

    def close(self) -> None:
        self._stop.set()
        if self._health_thread is not None:
            self._health_thread.join(timeout=10)
            self._health_thread = None
        self._drop()


def ensure_server(socket_path: str = DEFAULT_SOCKET,
                  spawn_timeout_s: float = 120.0,
                  idle_timeout_s: float = 900.0):
    """Connect to the resident server, spawning it if absent.

    Returns a connected KernelClient, or None when the spawn TIMED OUT
    (the stillborn daemon is killed so it cannot keep competing for
    CPU). A daemon that DIED during init raises RuntimeError — that is
    a real regression, not an environmental condition, and callers'
    skip/fallback paths must not mask it. A caller that already owns
    the chip gets a :class:`ChipOwnedError` instead of a daemon that
    would die (or hang) on the chip's lock. The daemon's stderr goes to
    ``<socket_path>.log``, so a daemon that dies leaves its reason."""
    try:
        c = KernelClient(socket_path, timeout=spawn_timeout_s)
        if c.ping():
            return c
        c.close()
    except OSError:
        pass
    refuse_chip_child("spawn the kernel-server daemon")
    with open(socket_path + ".log", "ab") as daemon_log:
        proc = subprocess.Popen(
            [sys.executable, "-m", "memgraph_tpu.server.kernel_server",
             "--socket", socket_path,
             "--idle-timeout", str(idle_timeout_s)],
            stdout=subprocess.DEVNULL, stderr=daemon_log,
            start_new_session=True)   # survives the spawning client
    deadline = time.monotonic() + spawn_timeout_s
    while time.monotonic() < deadline:
        # keep polling the socket even if OUR child died: in a spawn
        # race the loser exits after probing a live responder (or on the
        # bind conflict) while the winner is still importing jax — its
        # server arrives soon
        try:
            c = KernelClient(socket_path, timeout=spawn_timeout_s)
            if c.ping():
                return c
            c.close()
        except OSError:
            time.sleep(0.1)
    if proc.poll() is not None:
        # nobody ever served AND our daemon died: a real init failure
        # (import error, crash), not environmental starvation
        raise RuntimeError(
            f"kernel server died during init (rc={proc.returncode}); "
            f"its stderr is in {socket_path}.log")
    try:
        proc.kill()               # a starved spawn must not linger
        proc.wait(timeout=10)
    except (OSError, subprocess.TimeoutExpired):
        pass
    return None


#: per-socket supervised clients shared process-wide (a client owns a
#: pool of connections + supervision state; one per daemon is the
#: contract)
_SHARED_CLIENTS: dict = {}
_shared_clients_guard = threading.Lock()


def shared_client(socket_path: str = DEFAULT_SOCKET,
                  spawn: bool = False) -> SupervisedKernelClient:
    """The process-wide SupervisedKernelClient for a socket — ops-level
    kernel routing (ops/pagerank.py) and the procedure layer share one
    supervisor per daemon instead of each minting connections."""
    with _shared_clients_guard:
        client = _SHARED_CLIENTS.get(socket_path)
        if client is None:
            client = _SHARED_CLIENTS[socket_path] = \
                SupervisedKernelClient(socket_path, spawn=spawn)
        return client


def main() -> None:
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--socket", default=DEFAULT_SOCKET)
    ap.add_argument("--idle-timeout", type=float, default=900.0)
    args = ap.parse_args()
    KernelServer(args.socket, idle_timeout_s=args.idle_timeout).serve_forever()


if __name__ == "__main__":
    main()
