"""Centrality / community / component / path modules on TPU.

API parity with the reference's modules:
  pagerank.get            (query_modules/pagerank_module/pagerank_online_module.cpp)
  pagerank.stream-free static variant (mage/cpp/pagerank_module)
  katz_centrality.get     (query_modules/katz_centrality_module/)
  community_detection.get (query_modules/community_detection_module/)
  weakly_connected_components.get / wcc.get (mage/cpp/connectivity_module)
  strongly_connected_components.get
  degree_centrality.get   (mage/cpp/degree_centrality_module)
  betweenness_centrality.get (sampled Brandes via multi-source BFS)
  hits.get                (cugraph_module/algorithms/hits.cu analog)
  bfs.get / sssp.get path utilities

All `*_tpu` aliases expose the same procedures for explicit dispatch.
"""

from __future__ import annotations

import logging
import os
import threading
import time

import numpy as np

from ..observability import trace as mgtrace
from ..observability.metrics import global_metrics
from . import mgp

log = logging.getLogger(__name__)

#: per-(socket, graph_key) serving-plane sync state: the last
#: (topology_version, node_gids) this process pushed to the daemon, so
#: the next request ships the change-log DELTA covering the gap —
#: the PPR plane invalidates only the cached sources it touches, and
#: the analytics ops (r19 mgdelta) refresh the resident generation
#: O(delta) and warm-start from its previous solution
_PPR_PUSHED: dict = {}
_PPR_PUSHED_LOCK = threading.Lock()


def _rank_results(ctx, graph, values, field_name):
    """One row per vertex, or under the call's ``row_bound`` on this
    field only the rows that can reach the result (_bounded_rows)."""
    def choose():
        bound = ctx.row_bound
        if bound is not None and bound.field == field_name:
            indices = _bounded_rows(ctx, graph, values, bound)
            if indices is not None:
                global_metrics.increment("query.topk_pushdown_total")
                return indices
        return range(graph.n_nodes)
    yield from _vertex_rows(ctx, graph, choose, values, field_name, float)


def _vertex_rows(ctx, graph, indices, values, field_name, convert):
    """``{"node", field_name: convert(values[i])}`` for each index that
    ``indices()`` gives, in that order, where the vertex is visible. Two
    phases, recorded once when the generator ends: ``analytics.rows``,
    the time spent in here choosing and making the rows, and
    ``analytics.consume``, the time the plan's operators above the CALL
    took between two rows (a TopK's selection on the sort keys; without
    a LIMIT, Produce's expressions and OrderBy's collecting, or an
    aggregation's grouping)."""
    started = time.time()
    inside = outside = 0.0
    t0 = time.perf_counter()
    try:
        for i in indices():
            node = ctx.vertex_by_index(graph, i)
            if node is not None:
                row = {"node": node, field_name: convert(values[i])}
                t1 = time.perf_counter()
                inside += t1 - t0
                yield row
                t0 = time.perf_counter()
                outside += t0 - t1
        inside += time.perf_counter() - t0
    finally:
        mgtrace.record_span("analytics.rows", started, inside)
        mgtrace.record_span("analytics.consume", started, outside)


def _bounded_rows(ctx, graph, values, bound):
    """The indices of the ``bound.count`` visible vertices whose values
    sort first and of every vertex tied with the last of them, in index
    order: the order the full stream has them, so that a TopK's
    tie-break by arrival sees what it saw. None where every row is to be
    yielded: a NaN among the values (the sort orders it apart), or a
    bound that leaves nothing out."""
    n = graph.n_nodes
    values = np.asarray(values)[:n]
    if not 0 < bound.count < n or np.isnan(values).any():
        return None
    keys = -values if bound.descending else values
    taken = bound.count
    while taken < n:
        best = np.argpartition(keys, taken - 1)[:taken]
        visible = 0
        for i in best[np.lexsort((best, keys[best]))].tolist():
            if ctx.vertex_by_index(graph, i) is not None:
                visible += 1
                if visible == bound.count:
                    # every key below this one's is among the ``taken``
                    # best, so it is the count-th best visible key
                    return np.flatnonzero(keys <= keys[i]).tolist()
        taken *= 2      # deleted vertices among the best: look further
    return None


def _top_rank_results(ctx, graph, indices, values, field_name):
    """The rows of a top-k answer, in the order given."""
    with mgtrace.span("analytics.rows"):
        rows = []
        for i, value in zip(indices, values):
            node = ctx.vertex_by_index(graph, int(i))
            if node is not None:
                rows.append({"node": node, field_name: float(value)})
    yield from rows


def _kernel_route_socket(ctx) -> str | None:
    """The resident-kernel-server socket analytics should route through,
    or None for the in-process path. Config key ``kernel_server_socket``
    (the server entry point sets it) or the
    MEMGRAPH_TPU_ANALYTICS_KERNEL_SERVER env var; the value "1" means
    the default socket."""
    ictx = getattr(ctx.exec_ctx, "interpreter_context", None)
    cfg = getattr(ictx, "config", None) or {}
    sock = cfg.get("kernel_server_socket") or os.environ.get(
        "MEMGRAPH_TPU_ANALYTICS_KERNEL_SERVER")
    if not sock:
        return None
    if sock in ("1", "default"):
        from ..server.kernel_server import DEFAULT_SOCKET
        return DEFAULT_SOCKET
    return str(sock)


def _kernel_client(sock: str, spawn: bool):
    from ..server.kernel_server import shared_client
    return shared_client(sock, spawn=spawn)


def _graph_coo(graph):
    """Host COO arrays of the true edges (weights only when real)."""
    if graph.host_coo is not None:
        src, dst, w = graph.host_coo
        return (np.asarray(src, dtype=np.int64),
                np.asarray(dst, dtype=np.int64),
                None if w is None else np.asarray(w, dtype=np.float32))
    n = graph.n_edges
    return (np.asarray(graph.src_idx, dtype=np.int64)[:n],
            np.asarray(graph.col_idx, dtype=np.int64)[:n],
            np.asarray(graph.weights, dtype=np.float32)[:n])


def _serving_delta_meta(ctx, graph, sock: str, graph_key: str):
    """Shared serving-plane sync envelope (the `_ppr_serving_meta`
    pattern promoted to ALL analytics ops, r19 mgdelta): a stable
    per-storage graph_key, the reader's topology version, and — when
    this process already pushed an earlier version — the change-log
    delta payload covering the gap (dense changed indices PLUS those
    vertices' current incident edges), so the server refreshes its
    resident generation O(delta) and never needs the full edge list
    re-shipped. ``send_graph`` says whether the edge arrays must ride
    along (server behind with no usable delta, or never fed)."""
    from ..ops.delta import incident_edges
    with mgtrace.span("analytics.route_meta"):
        storage = ctx.storage
        version = getattr(ctx.accessor, "topology_snapshot",
                          storage.topology_version)
        meta = {"graph_key": graph_key, "graph_version": version,
                "base_version": None, "ids_stable": True,
                "send_graph": True}
        with _PPR_PUSHED_LOCK:
            prev = _PPR_PUSHED.get((sock, graph_key))
        if prev is None:
            return meta
        prev_version, prev_gids = prev
        ids_stable = prev_gids is graph.node_gids or \
            np.array_equal(prev_gids, graph.node_gids)
        meta["ids_stable"] = ids_stable
        if not ids_stable:
            return meta
        if prev_version == version:
            meta["send_graph"] = False
            meta["base_version"] = version
            return meta
        if prev_version < version and graph.host_coo is not None:
            gids = storage.changes_between(prev_version, version)
            # typed wrap verdict (ChangeLogUnknowable) → full re-ship: the
            # gap is unreconstructable and a partial delta would corrupt
            # the resident generation
            if isinstance(gids, frozenset):
                changed_idx = [graph.gid_to_idx[g] for g in gids
                               if g in graph.gid_to_idx]
                bitmap = np.zeros(graph.n_nodes, dtype=bool)
                if changed_idx:
                    bitmap[np.asarray(changed_idx, dtype=np.int64)] = True
                inc_src, inc_dst, inc_w = incident_edges(
                    *graph.host_coo, bitmap)
                meta.update(base_version=prev_version, changed=changed_idx,
                            inc_src=inc_src, inc_dst=inc_dst, inc_w=inc_w,
                            send_graph=False)
        return meta


def _drop_pushed(sock: str, graph_key: str) -> None:
    """Forget the pushed version after a kernel-plane failure: the next
    request re-ships the full graph instead of a delta the (possibly
    respawned) server cannot anchor."""
    with _PPR_PUSHED_LOCK:
        _PPR_PUSHED.pop((sock, graph_key), None)


def _kernel_server_pagerank(ctx, graph, damping, max_iterations, tol):
    """Route pagerank through the resident kernel server when one is
    configured; returns ranks or None (→ caller runs in-process).

    Rides the resident-generation layer (r19 mgdelta): the graph_key is
    stable per storage, commits ship the change-log delta instead of
    the full edge list, and the server warm-starts the fixpoint from
    its previous solution — commit-then-CALL costs O(delta) apply plus
    the few iterations the perturbation needs.

    The dispatch's device attribution (transfer/compile/iterate splits)
    ships home in the reply and lands in the active stage accumulator,
    so PROFILE on the routed query still shows where HBM-seconds went.
    A kernel-plane failure falls back to the in-process path LOUDLY —
    analytics availability beats routing purity."""
    sock = _kernel_route_socket(ctx)
    if sock is None:
        return None
    from ..observability.metrics import global_metrics
    from ..server.kernel_server import KernelServerError
    graph_key = f"analytics:{hex(id(ctx.storage))}"
    meta = _serving_delta_meta(ctx, graph, sock, graph_key)
    kwargs = {}
    if meta.pop("send_graph"):
        src, dst, weights = _graph_coo(graph)
        kwargs.update(src=src, dst=dst, weights=weights)
    try:
        client = _kernel_client(sock, spawn=False)
        ranks, _err, _iters = client.pagerank(
            n_nodes=graph.n_nodes,
            damping=float(damping), max_iterations=int(max_iterations),
            tol=float(tol), **meta, **kwargs)
        _note_ppr_pushed(sock, graph_key, meta["graph_version"],
                         graph.node_gids)
        global_metrics.increment("analytics.kernel_routed_total")
        return np.asarray(ranks)[:graph.n_nodes]
    except (KernelServerError, ConnectionError, OSError) as e:
        _drop_pushed(sock, graph_key)
        global_metrics.increment("analytics.kernel_route_fallback_total")
        log.warning("kernel-server pagerank route failed (%s: %s); "
                    "falling back to the in-process path",
                    type(e).__name__, e)
        return None


def _ppr_serving_meta(ctx, graph, sock: str):
    """The PPR serving-plane sync envelope: the shared
    :func:`_serving_delta_meta` layer under the PPR graph_key. Since
    r19 the delta payload carries the changed vertices' current
    incident edges too, so the server's resident snapshot refreshes
    O(delta) (and the result cache demotes off that SAME shipped delta)
    instead of the client re-shipping the full edge list after every
    commit."""
    return _serving_delta_meta(ctx, graph, sock,
                               f"ppr:{hex(id(ctx.storage))}")


def _note_ppr_pushed(sock: str, graph_key: str, version, node_gids):
    with _PPR_PUSHED_LOCK:
        _PPR_PUSHED[(sock, graph_key)] = (version, node_gids)


def _kernel_server_ppr(ctx, graph, sources, damping, max_iterations,
                       tol, top_k=0):
    """Route one PPR through the resident server's COALESCING plane.
    Concurrent Cypher queries batch into one multi-source SpMM fixpoint
    and repeats ride the change-log-invalidated result cache. Returns
    the (reply_header, arrays) pair or None (→ in-process fallback,
    LOUD)."""
    sock = _kernel_route_socket(ctx)
    if sock is None:
        return None
    from ..observability.metrics import global_metrics
    from ..server.kernel_server import KernelServerError
    meta = _ppr_serving_meta(ctx, graph, sock)
    kwargs = {}
    if meta.pop("send_graph"):
        src, dst, weights = _graph_coo(graph)
        kwargs.update(src=src, dst=dst, weights=weights)
    try:
        client = _kernel_client(sock, spawn=False)
        h, out = client.ppr(
            sources=np.asarray(sources, dtype=np.int32),
            n_nodes=graph.n_nodes, damping=float(damping),
            max_iterations=int(max_iterations), tol=float(tol),
            top_k=int(top_k), **meta, **kwargs)
        _note_ppr_pushed(sock, meta["graph_key"], meta["graph_version"],
                         graph.node_gids)
        global_metrics.increment("analytics.kernel_routed_total")
        return h, out
    except (KernelServerError, ConnectionError, OSError) as e:
        _drop_pushed(sock, meta["graph_key"])
        global_metrics.increment("analytics.kernel_route_fallback_total")
        log.warning("kernel-server PPR route failed (%s: %s); "
                    "falling back to the in-process path",
                    type(e).__name__, e)
        return None


def _warm_prepare(ctx, graph, algo: str, params_key: tuple):
    """In-process commit-then-CALL state without a kernel server
    (ops/delta.py LocalWarmPool): (cached_result | None, x0 | None,
    store_fn). A non-None cached_result is the UNCHANGED graph's stored
    solution, served verbatim (identical repeated CALLs must return
    identical bytes); x0 seeds the fixpoint after a commit."""
    from ..observability import stats as mgstats
    from ..ops import delta as mgdelta
    storage = ctx.storage
    version = getattr(ctx.accessor, "topology_snapshot",
                      storage.topology_version)
    cached, x0 = mgdelta.GLOBAL_WARM_POOL.prepare(storage, graph,
                                                  version, algo,
                                                  params_key)
    if cached is not None and mgstats.stages_active():
        # PROFILE-d CALL: a verbatim cache hit would attribute zero
        # device stages — exactly what the profile exists to measure.
        # Demote the hit to a warm seed (the fixpoint re-converges in
        # O(1) iterations) and DON'T store the re-iterated bytes: the
        # stored solution stays the cache of record, so unprofiled
        # repeated CALLs keep returning identical bytes.
        return None, np.asarray(cached), (lambda x, iters=None: None)

    def store(x, iters=None):
        mgdelta.GLOBAL_WARM_POOL.store(storage, graph, version, algo,
                                       params_key, np.asarray(x))
        if x0 is not None and iters is not None:
            mgdelta.record_warm_start(algo, int(iters))

    return cached, x0, store


def _pagerank_impl(ctx, max_iterations=100, damping_factor=0.85,
                   stop_epsilon=1e-5, weight_property=None):
    from ..ops.pagerank import pagerank
    graph = ctx.device_graph(weight_property=weight_property)
    if graph.n_nodes == 0:
        return
    ranks = _kernel_server_pagerank(ctx, graph, damping_factor,
                                    max_iterations, stop_epsilon)
    if ranks is None:
        cached, x0, store = _warm_prepare(
            ctx, graph, "pagerank",
            ("pagerank", float(damping_factor), float(stop_epsilon),
             int(max_iterations), weight_property))
        if cached is not None:
            ranks = cached
        else:
            ranks, _, iters = pagerank(
                graph, damping=float(damping_factor),
                max_iterations=int(max_iterations),
                tol=float(stop_epsilon), x0=x0)
            store(ranks, iters)
    ranks = np.asarray(ranks)
    yield from _rank_results(ctx, graph, ranks, "rank")


for _name in ("pagerank.get", "pagerank_tpu.get", "pagerank_online.get"):
    mgp.read_proc(_name,
                  opt_args=[("max_iterations", "INTEGER", 100),
                            ("damping_factor", "FLOAT", 0.85),
                            ("stop_epsilon", "FLOAT", 1e-5),
                            ("weight_property", "STRING", None)],
                  results=[("node", "NODE"), ("rank", "FLOAT")])(_pagerank_impl)


@mgp.read_proc("pagerank.personalized",
               args=[("source_nodes", "LIST")],
               opt_args=[("max_iterations", "INTEGER", 100),
                         ("damping_factor", "FLOAT", 0.85),
                         ("top_k", "INTEGER", None)],
               results=[("node", "NODE"), ("rank", "FLOAT")])
def personalized_pagerank(ctx, source_nodes, max_iterations=100,
                          damping_factor=0.85, top_k=None):
    """Personalized PageRank restarted uniformly on ``source_nodes``.

    ``top_k`` (not in the reference's signature) asks for the best k
    rows only, best first, ties by the lower dense index: through a
    resident kernel server the plane takes them on the device and k
    rows cross the socket instead of a rank per vertex. Without it,
    one row per vertex in the graph's order."""
    from ..ops.pagerank import personalized_pagerank as ppr
    if top_k is not None and int(top_k) < 1:
        from ..exceptions import QueryException
        raise QueryException("pagerank.personalized: top_k must be a "
                             "positive integer")
    graph = ctx.device_graph()
    if graph.n_nodes == 0:
        return
    sources = [graph.gid_to_idx[v.gid] for v in source_nodes
               if v is not None and v.gid in graph.gid_to_idx]
    if not sources:
        return
    k = 0 if top_k is None else min(int(top_k), graph.n_nodes)
    served = _kernel_server_ppr(ctx, graph, sources,
                                float(damping_factor),
                                int(max_iterations), 1e-6, top_k=k)
    if served is not None and k:
        best, values = served[1]["topk_idx"], served[1]["topk_val"]
    else:
        if served is not None:
            ranks = served[1]["ranks"]
        else:
            ranks, _, _ = ppr(graph, sources,
                              damping=float(damping_factor),
                              max_iterations=int(max_iterations))
        ranks = np.asarray(ranks)[:graph.n_nodes]
        if not k:
            yield from _rank_results(ctx, graph, ranks, "rank")
            return
        best = np.argsort(-ranks, kind="stable")[:k]
        values = ranks[best]
    yield from _top_rank_results(ctx, graph, best, values, "rank")


def _katz_impl(ctx, alpha=0.2, epsilon=1e-2):
    from ..ops.katz import katz_centrality
    graph = ctx.device_graph()
    if graph.n_nodes == 0:
        return
    cached, x0, store = _warm_prepare(
        ctx, graph, "katz", ("katz", float(alpha), float(epsilon)))
    if cached is not None:
        xs = cached
    else:
        xs, _, iters = katz_centrality(graph, alpha=float(alpha),
                                       tol=float(epsilon),
                                       max_iterations=500, x0=x0)
        store(xs, iters)
    yield from _rank_results(ctx, graph, np.asarray(xs), "rank")


for _name in ("katz_centrality.get", "katz_centrality_tpu.get",
              "katz_centrality_online.get"):
    mgp.read_proc(_name,
                  opt_args=[("alpha", "FLOAT", 0.2),
                            ("epsilon", "FLOAT", 1e-2)],
                  results=[("node", "NODE"), ("rank", "FLOAT")])(_katz_impl)


def _community_impl(ctx, max_iterations=30, weight_property=None,
                    online=False):
    """LDBC Graphalytics CDLP: ``max_iterations`` synchronous rounds from
    the vertex ids, each vertex taking the label most frequent among its
    neighbours (weighted by ``weight_property`` where given), ties to
    the smallest id; labels are dense indices, which are the vertices'
    creation order. After a commit the exact procedures run cold: T
    rounds from the previous labels elect other labels
    (ops/delta.py ``WARM_START_POLICY``). ``online`` (the approximate
    community_detection_online.get) seeds the election from the previous
    labels after an adds-only commit."""
    from ..ops.labelprop import label_propagation
    graph = ctx.device_graph(weight_property=weight_property)
    if graph.n_nodes == 0:
        return
    algo = "labelprop" if online else "cdlp"
    cached, labels0, store = _warm_prepare(
        ctx, graph, algo, (algo, int(max_iterations), weight_property))
    iters = 0
    if cached is not None:
        labels = cached
    else:
        # an exact election never seeds, not even from a PROFILE-d
        # CALL's demoted cache hit
        labels, iters = label_propagation(
            graph, max_iterations=int(max_iterations),
            labels0=labels0 if online else None,
            weighted=weight_property is not None)
        store(labels, iters if online else None)
    # apart from device.fixpoint_iterations_total, which is PageRank's
    global_metrics.increment("analytics.cdlp.calls_total")
    global_metrics.increment("analytics.cdlp.iterations_total", iters)
    # compact community ids to 1..k (reference convention: ids start at 1)
    _, community = np.unique(np.asarray(labels), return_inverse=True)
    community += 1
    yield from _vertex_rows(ctx, graph, lambda: range(graph.n_nodes),
                            community, "community_id", int)


for _name in ("community_detection.get", "community_detection_tpu.get",
              "label_propagation.get"):
    mgp.read_proc(_name,
                  opt_args=[("max_iterations", "INTEGER", 30),
                            ("weight_property", "STRING", None)],
                  results=[("node", "NODE"),
                           ("community_id", "INTEGER")])(_community_impl)


@mgp.read_proc("community_detection_online.get",
               opt_args=[("max_iterations", "INTEGER", 30),
                         ("weight_property", "STRING", None)],
               results=[("node", "NODE"), ("community_id", "INTEGER")])
def _community_online(ctx, max_iterations=30, weight_property=None):
    yield from _community_impl(ctx, max_iterations, weight_property,
                               online=True)


def _wcc_impl(ctx):
    from ..ops.components import weakly_connected_components
    graph = ctx.device_graph()
    if graph.n_nodes == 0:
        return
    # warm seed only over monotone (adds-only) deltas — min-labels can
    # merge components but never split; removals cold-start LOUDLY
    cached, comp0, store = _warm_prepare(ctx, graph, "wcc", ("wcc",))
    iters = 0
    if cached is not None:
        comp = cached
    else:
        comp, iters = weakly_connected_components(graph, comp0=comp0)
        store(comp, iters)
    global_metrics.increment("analytics.wcc.calls_total")
    global_metrics.increment("analytics.wcc.iterations_total", iters)
    yield from _vertex_rows(ctx, graph, lambda: range(graph.n_nodes),
                            np.asarray(comp), "component_id", int)


for _name in ("weakly_connected_components.get", "wcc.get",
              "connectivity.get", "wcc_tpu.get"):
    mgp.read_proc(_name,
                  results=[("node", "NODE"),
                           ("component_id", "INTEGER")])(_wcc_impl)


@mgp.read_proc("strongly_connected_components.get",
               results=[("node", "NODE"), ("component_id", "INTEGER")])
def scc_get(ctx):
    from ..ops.components import strongly_connected_components
    graph = ctx.device_graph()
    if graph.n_nodes == 0:
        return
    comp = np.asarray(strongly_connected_components(graph))
    for i in range(graph.n_nodes):
        node = ctx.vertex_by_index(graph, i)
        if node is not None:
            yield {"node": node, "component_id": int(comp[i])}


@mgp.read_proc("degree_centrality.get",
               opt_args=[("type", "STRING", "undirected")],
               results=[("node", "NODE"), ("degree", "FLOAT")])
def degree_get(ctx, type="undirected"):
    from ..ops.katz import degree_centrality
    graph = ctx.device_graph()
    if graph.n_nodes == 0:
        return
    direction = {"in": "in", "out": "out"}.get(str(type).lower(), "total")
    degs = np.asarray(degree_centrality(graph, direction))
    yield from _rank_results(ctx, graph, degs, "degree")


@mgp.read_proc("hits.get",
               opt_args=[("max_iterations", "INTEGER", 100),
                         ("tolerance", "FLOAT", 1e-6)],
               results=[("node", "NODE"), ("hub", "FLOAT"),
                        ("authority", "FLOAT")])
def hits_get(ctx, max_iterations=100, tolerance=1e-6):
    from ..ops.katz import hits
    graph = ctx.device_graph()
    if graph.n_nodes == 0:
        return
    hub, auth, _, _ = hits(graph, max_iterations=int(max_iterations),
                           tol=float(tolerance))
    hub, auth = np.asarray(hub), np.asarray(auth)
    for i in range(graph.n_nodes):
        node = ctx.vertex_by_index(graph, i)
        if node is not None:
            yield {"node": node, "hub": float(hub[i]),
                   "authority": float(auth[i])}


@mgp.read_proc("betweenness_centrality.get",
               opt_args=[("normalized", "BOOLEAN", True),
                         ("directed", "BOOLEAN", True),
                         ("num_samples", "INTEGER", 64)],
               results=[("node", "NODE"),
                        ("betweenness_centrality", "FLOAT")])
def betweenness_get(ctx, normalized=True, directed=True, num_samples=64):
    """Sampled Brandes: pivots' BFS distances on device, dependency
    accumulation per pivot (reference: mage/cpp/betweenness_centrality_module;
    the sampling approach matches its online variant's spirit)."""
    from ..ops.traversal import multi_source_sssp
    graph = ctx.device_graph()
    n = graph.n_nodes
    if n == 0:
        return
    rng = np.random.default_rng(0)
    k = min(int(num_samples), n)
    pivots = rng.choice(n, size=k, replace=False)
    dist = np.asarray(multi_source_sssp(graph, pivots, weighted=False,
                                        directed=bool(directed)))
    # host-side dependency accumulation over the (small) pivot set
    src = np.asarray(graph.src_idx)[:graph.n_edges]
    dst = np.asarray(graph.col_idx)[:graph.n_edges]
    bc = np.zeros(n, dtype=np.float64)
    for pi in range(k):
        d = dist[pi]
        finite = np.isfinite(d)
        # count shortest paths via BFS layers
        sigma = np.zeros(n)
        sigma[pivots[pi]] = 1.0
        maxd = int(d[finite].max()) if finite.any() else 0
        for level in range(1, maxd + 1):
            on_edge = finite[src] & finite[dst] & \
                (d[src] == level - 1) & (d[dst] == level)
            np.add.at(sigma, dst[on_edge], sigma[src[on_edge]])
        delta = np.zeros(n)
        for level in range(maxd, 0, -1):
            on_edge = finite[src] & finite[dst] & \
                (d[src] == level - 1) & (d[dst] == level)
            with np.errstate(divide="ignore", invalid="ignore"):
                contrib = np.where(sigma[dst[on_edge]] > 0,
                                   sigma[src[on_edge]] / sigma[dst[on_edge]]
                                   * (1.0 + delta[dst[on_edge]]), 0.0)
            np.add.at(delta, src[on_edge], contrib)
        delta[pivots[pi]] = 0.0
        bc += delta
    bc *= n / max(k, 1)  # scale sample to population
    if normalized and n > 2:
        scale = 1.0 / ((n - 1) * (n - 2))
        if not directed:
            scale *= 2.0
        bc *= scale
    if not directed:
        bc /= 2.0
    for i in range(n):
        node = ctx.vertex_by_index(graph, i)
        if node is not None:
            yield {"node": node, "betweenness_centrality": float(bc[i])}


@mgp.read_proc("bfs.get",
               args=[("source", "NODE")],
               opt_args=[("directed", "BOOLEAN", True)],
               results=[("node", "NODE"), ("level", "INTEGER")])
def bfs_get(ctx, source, directed=True):
    """Graph500 kernel 2 with ``directed`` false: levels over both
    orientations of every relationship."""
    from ..ops.traversal import bfs_levels
    graph = ctx.device_graph()
    if graph.n_nodes == 0 or source is None:
        return
    sidx = graph.gid_to_idx.get(source.gid)
    if sidx is None:
        return
    levels, iters = bfs_levels(graph, sidx, directed=bool(directed))
    global_metrics.increment("analytics.bfs.calls_total")
    global_metrics.increment("analytics.bfs.iterations_total", iters)
    yield from _vertex_rows(ctx, graph,
                            lambda: np.flatnonzero(levels >= 0).tolist(),
                            levels, "level", int)


@mgp.read_proc("sssp.get",
               args=[("source", "NODE")],
               opt_args=[("weight_property", "STRING", "weight"),
                         ("directed", "BOOLEAN", True)],
               results=[("node", "NODE"), ("distance", "FLOAT")])
def sssp_get(ctx, source, weight_property="weight", directed=True):
    """Graph500 kernel 3 with ``directed`` false: distances over both
    orientations of every relationship."""
    from ..ops.traversal import sssp
    graph = ctx.device_graph(weight_property=weight_property)
    if graph.n_nodes == 0 or source is None:
        return
    sidx = graph.gid_to_idx.get(source.gid)
    if sidx is None:
        return
    dist, iters = sssp(graph, sidx, weighted=True, directed=bool(directed))
    global_metrics.increment("analytics.sssp.calls_total")
    global_metrics.increment("analytics.sssp.iterations_total", iters)
    yield from _vertex_rows(ctx, graph,
                            lambda: np.flatnonzero(np.isfinite(dist)).tolist(),
                            dist, "distance", float)


@mgp.read_proc("graph_util.khop",
               args=[("sources", "LIST"), ("hops", "INTEGER")],
               opt_args=[("directed", "BOOLEAN", False)],
               results=[("node", "NODE")])
def khop_get(ctx, sources, hops, directed=False):
    from ..ops.traversal import khop_neighborhood
    graph = ctx.device_graph()
    if graph.n_nodes == 0:
        return
    idxs = [graph.gid_to_idx[v.gid] for v in sources
            if v is not None and v.gid in graph.gid_to_idx]
    if not idxs:
        return
    mask = np.asarray(khop_neighborhood(graph, idxs, int(hops),
                                        directed=bool(directed)))
    for i in np.nonzero(mask)[0]:
        node = ctx.vertex_by_index(graph, int(i))
        if node is not None:
            yield {"node": node}
