"""Graph snapshot → device CSR export.

The seam between the MVCC host store and the TPU kernels, playing the role
the reference's `mg_graph::Graph` snapshot plays for MAGE modules
(/root/reference/include/mg_utils.hpp:128-170 builds an adjacency-list copy
by iterating the mgp_graph view): here the snapshot is a set of padded,
immutable device arrays in CSR form.

Design points for XLA (SURVEY.md §7 "hard parts"):
  - **Static shapes**: `n_nodes`/`n_edges` are padded up to bucket sizes
    (powers of two by default) so repeated exports of a mutating graph hit
    the same compiled kernels. Padding edges point at a sink row whose
    weight is 0 and whose src degree is 0, so segment reductions ignore them.
  - **Dense ids**: storage gids are compacted to [0, n); the mapping back to
    gids rides along host-side for result streaming.
  - **Topology cache**: exports are cached per (storage, topology_version,
    weight_property) so repeated CALLs don't re-export an unchanged graph —
    the staleness contract matches the reference's "online" modules, which
    also compute over their own snapshot of the graph.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass, field
from typing import Optional

import numpy as np

from ..storage.common import View


def _bucket(n: int, minimum: int = 8) -> int:
    """Round up to the next power of two (compilation-amortizing bucket)."""
    n = max(n, minimum)
    return 1 << (n - 1).bit_length()


def _coerce_weight(w) -> float:
    """Edge-weight property -> float; non-numeric/missing -> 1.0."""
    return (float(w) if isinstance(w, (int, float))
            and not isinstance(w, bool) else 1.0)


@dataclass(frozen=True)
class DeviceGraph:
    """Immutable CSR+CSC snapshot. Arrays may live on device (jax) or host (np).

    CSR layout (edges lexsorted by (src, dst)) — feeds walks / out-expansion:
      row_ptr:    (n_pad+1,) int32 — CSR offsets
      col_idx:    (e_pad,)   int32 — destination node per edge
      src_idx:    (e_pad,)   int32 — source node per edge (COO mirror)
      weights:    (e_pad,)   float32 — edge weight (1.0 default, 0.0 padding)

    CSC layout (same edges lexsorted by (dst, src)) — feeds the pull-style
    segment reductions (pagerank/katz/...): destination-sorted indices let
    XLA use its fast sorted-segment-sum lowering instead of scatter, which
    profiled ~3x faster per iteration on TPU v5e:
      csc_src / csc_dst: (e_pad,) int32
      csc_weights:       (e_pad,) float32

    out_degree: (n_pad,) float32 — true out-degrees (0 for padding rows)
    n_nodes / n_edges: true counts;  n_pad / e_pad: padded counts
    node_gids:  (n_nodes,) int64 host array — dense index -> storage gid
    host_coo:   optional (src, dst, w) HOST arrays of the true edges —
                kept so a successor snapshot can diff edges for the
                O(delta) MXU plan refresh (ops/spmv_mxu.DeltaPlan)
    host_csr:   optional (row_ptr, col_idx) HOST arrays, the ones from_coo
                built before to_device placed them — kept by reference so
                a host reader takes a row as col_idx[row_ptr[s]:row_ptr[s+1]]
                without a pass over host_coo or a device readback
    """

    row_ptr: object
    col_idx: object
    src_idx: object
    weights: object
    csc_src: object
    csc_dst: object
    csc_weights: object
    out_degree: object
    n_nodes: int
    n_edges: int
    n_pad: int
    e_pad: int
    node_gids: np.ndarray
    gid_to_idx: dict = field(repr=False, hash=False, compare=False)
    host_coo: tuple = field(default=None, repr=False, hash=False,
                            compare=False)
    host_csr: tuple = field(default=None, repr=False, hash=False,
                            compare=False)

    def to_device(self) -> "DeviceGraph":
        from .blob import put_packed
        if not isinstance(self.row_ptr, np.ndarray):
            # arrays already device-resident: shipping them through
            # pack_blob would round-trip device->host->device
            return self
        dev = put_packed({
            "row_ptr": self.row_ptr, "col_idx": self.col_idx,
            "src_idx": self.src_idx, "weights": self.weights,
            "csc_src": self.csc_src, "csc_dst": self.csc_dst,
            "csc_weights": self.csc_weights,
            "out_degree": self.out_degree})
        return DeviceGraph(
            row_ptr=dev["row_ptr"],
            col_idx=dev["col_idx"],
            src_idx=dev["src_idx"],
            weights=dev["weights"],
            csc_src=dev["csc_src"],
            csc_dst=dev["csc_dst"],
            csc_weights=dev["csc_weights"],
            out_degree=dev["out_degree"],
            n_nodes=self.n_nodes, n_edges=self.n_edges,
            n_pad=self.n_pad, e_pad=self.e_pad,
            node_gids=self.node_gids, gid_to_idx=self.gid_to_idx,
            host_coo=self.host_coo, host_csr=self.host_csr)


def from_coo(src: np.ndarray, dst: np.ndarray,
             weights: Optional[np.ndarray] = None,
             n_nodes: Optional[int] = None,
             node_gids: Optional[np.ndarray] = None,
             pad: bool = True) -> DeviceGraph:
    """Build a host-side DeviceGraph from COO edge arrays (dense node ids)."""
    src = np.asarray(src, dtype=np.int64)
    dst = np.asarray(dst, dtype=np.int64)
    n_edges = len(src)
    if n_nodes is None:
        n_nodes = int(max(src.max(initial=-1), dst.max(initial=-1)) + 1)
    if weights is None:
        weights = np.ones(n_edges, dtype=np.float32)
    else:
        weights = np.asarray(weights, dtype=np.float32)

    n_pad = _bucket(n_nodes + 1) if pad else n_nodes + 1
    e_pad = _bucket(n_edges) if pad else max(n_edges, 1)
    # padding edges: sink->sink self loops with zero weight; the sink is the
    # extra padding row n_nodes (guaranteed to exist since n_pad >= n_nodes+1)
    sink = n_nodes

    # fast path: native C++ counting-sort builder (O(E+N), ops/native.py)
    from .native import build_csr_csc_native
    native = build_csr_csc_native(src, dst, weights, n_nodes, n_pad, e_pad) \
        if n_edges > 0 else None
    if native is not None:
        src_full = native["csr_src"]
        dst_full = native["csr_dst"]
        w_full = native["csr_w"]
        csc_src = native["csc_src"]
        csc_dst = native["csc_dst"]
        csc_w = native["csc_w"]
        row_ptr = native["row_ptr"]
        out_degree = native["out_degree"]
    else:
        # numpy fallback — lexicographic (src, dst) order: rows contiguous
        # AND sorted by dst, so device-side edge-membership queries can
        # binary-search within a row
        order = np.lexsort((dst, src))
        s_sorted = src[order]
        d_sorted = dst[order]
        w_sorted = weights[order]

        src_full = np.full(e_pad, sink, dtype=np.int32)
        dst_full = np.full(e_pad, sink, dtype=np.int32)
        w_full = np.zeros(e_pad, dtype=np.float32)
        src_full[:n_edges] = s_sorted
        dst_full[:n_edges] = d_sorted
        w_full[:n_edges] = w_sorted

        # CSC mirror: (dst, src)-sorted. Reuse the (src, dst)-sorted arrays
        # with one single-key stable sort — stability preserves the src order
        # within equal dst, giving (dst, src) order at half the sort cost.
        corder = np.argsort(d_sorted, kind="stable")
        csc_src = np.full(e_pad, sink, dtype=np.int32)
        csc_dst = np.full(e_pad, sink, dtype=np.int32)
        csc_w = np.zeros(e_pad, dtype=np.float32)
        csc_src[:n_edges] = s_sorted[corder]
        csc_dst[:n_edges] = d_sorted[corder]
        csc_w[:n_edges] = w_sorted[corder]

        counts = np.bincount(s_sorted, minlength=n_pad).astype(np.int64)
        row_ptr = np.zeros(n_pad + 1, dtype=np.int32)
        np.cumsum(counts, out=row_ptr[1:])

        out_degree = np.zeros(n_pad, dtype=np.float32)
        out_degree[:n_nodes] = np.bincount(
            src, minlength=n_nodes).astype(np.float32)[:n_nodes]

    if node_gids is None:
        node_gids = np.arange(n_nodes, dtype=np.int64)
    gid_to_idx = {int(g): i for i, g in enumerate(node_gids)}

    return DeviceGraph(row_ptr=row_ptr, col_idx=dst_full, src_idx=src_full,
                       weights=w_full,
                       csc_src=csc_src, csc_dst=csc_dst, csc_weights=csc_w,
                       out_degree=out_degree,
                       n_nodes=n_nodes, n_edges=n_edges,
                       n_pad=n_pad, e_pad=e_pad,
                       node_gids=np.asarray(node_gids, dtype=np.int64),
                       gid_to_idx=gid_to_idx,
                       host_coo=(src.astype(np.int32), dst.astype(np.int32),
                                 weights),
                       host_csr=(row_ptr, dst_full))


def export_csr(accessor, weight_property: Optional[int] = None,
               label_filter: Optional[int] = None,
               edge_type_filter: Optional[set] = None,
               view: View = View.OLD,
               pad: bool = True,
               to_device: bool = True) -> DeviceGraph:
    """Export the accessor's visible graph as CSR arrays.

    Fast path: objects with no delta chain are read directly (no MVCC
    materialization); only objects with version chains pay the walk.
    """
    storage = accessor.storage
    txn = accessor.txn

    node_gids = []
    gid_to_idx: dict[int, int] = {}
    for vertex in list(storage._vertices.values()):
        if vertex.delta is None:
            if vertex.deleted:
                continue
            if label_filter is not None and label_filter not in vertex.labels:
                continue
        else:
            from ..storage.storage import VertexAccessor
            va = VertexAccessor(vertex, accessor)
            if not va.is_visible(view):
                continue
            if label_filter is not None and not va.has_label(label_filter, view):
                continue
        gid_to_idx[vertex.gid] = len(node_gids)
        node_gids.append(vertex.gid)

    srcs, dsts, ws = [], [], []
    has_w = weight_property is not None
    for edge in list(storage._edges.values()):
        if edge.delta is None:
            if edge.deleted:
                continue
            props = edge.properties if has_w else None
        else:
            from ..storage.storage import EdgeAccessor
            ea = EdgeAccessor(edge, accessor)
            if not ea.is_visible(view):
                continue
            props = ea.properties(view) if has_w else None
        if edge_type_filter is not None and edge.edge_type not in edge_type_filter:
            continue
        si = gid_to_idx.get(edge.from_vertex.gid)
        di = gid_to_idx.get(edge.to_vertex.gid)
        if si is None or di is None:
            continue
        srcs.append(si)
        dsts.append(di)
        if has_w:
            ws.append(_coerce_weight(
                props.get(weight_property) if props else None))

    g = from_coo(np.asarray(srcs, dtype=np.int64),
                 np.asarray(dsts, dtype=np.int64),
                 np.asarray(ws, dtype=np.float32) if has_w else None,
                 n_nodes=len(node_gids),
                 node_gids=np.asarray(node_gids, dtype=np.int64),
                 pad=pad)
    return g.to_device() if to_device else g


def export_csr_delta(prev: DeviceGraph, accessor, changed_gids,
                     weight_property=None, label_filter=None,
                     edge_type_filter=None, pad: bool = True,
                     to_device: bool = True):
    """O(changed) re-export: splice the changed vertices' edges into the
    previous snapshot's host arrays instead of walking ALL vertices and
    edges in Python (the full export is the dominant per-version cost at
    10M edges). Rebuild = drop every edge incident to a changed vertex
    from the previous COO, append the changed vertices' current edges
    read from storage (O(changed x degree)), then one native/numpy
    from_coo pass.

    The view's vertex set may GROW across the gap: a changed vertex that
    the accessor sees (with the filter's label, where one is set) and
    that `prev` lacks JOINS, at the next dense index after
    `prev.n_nodes`, in ascending gid. It has no edge in `prev.host_coo`,
    so the rule for every changed vertex (all its out-edges re-emit, and
    its in-edges from unchanged sources) covers it, also where it gained
    the label and brings edges it already had. For vertices committed in
    creation order the result is export_csr's, array for array; a vertex
    that joins behind a younger one stands later in the dense order than
    export_csr would put it, the same graph under `node_gids`.

    The set may not SHRINK: removing a row shifts every dense id behind
    it. Returns None (caller falls back to export_csr) for a vertex of
    `prev` that left the view (deleted, lost the label), a vertex gone
    from storage, an edge whose other endpoint is neither in `prev` nor
    joined, and a `prev` without host arrays. A changed vertex that is
    in neither view (created and deleted inside the gap, or one that
    never carried the label) is skipped: it has no row and, being
    invisible or unindexed, no edge that export_csr would emit.
    """
    if prev.host_coo is None:
        return None
    storage = accessor.storage
    from ..storage.storage import EdgeAccessor, VertexAccessor
    in_view = []                  # (gid, vertex) with a row in the result
    joined = []
    for gid in changed_gids:
        vertex = storage._vertices.get(gid)
        if vertex is None:
            return None               # vertex gone: node set changed
        va = VertexAccessor(vertex, accessor)
        visible = va.is_visible(View.OLD)
        if label_filter is not None and visible:
            visible = va.has_label(label_filter, View.OLD)
        if gid in prev.gid_to_idx:
            if not visible:
                return None           # left the view: dense ids shift
        elif visible:
            joined.append(gid)
        else:
            continue                  # in neither view: no row, no edge
        in_view.append((gid, vertex))
    joined.sort()
    joined_idx = {gid: prev.n_nodes + i for i, gid in enumerate(joined)}

    def index_of(gid):
        idx = prev.gid_to_idx.get(gid)
        return joined_idx.get(gid) if idx is None else idx

    n_nodes = prev.n_nodes + len(joined)
    rows = [(index_of(gid), vertex) for gid, vertex in in_view]
    bitmap = np.zeros(n_nodes, dtype=bool)
    for idx, _vertex in rows:
        bitmap[idx] = True
    fresh_src: list = []
    fresh_dst: list = []
    fresh_w: list = []
    has_w = weight_property is not None
    for idx, vertex in rows:
        # raw MVCC state, NOT VertexAccessor.out_edges/in_edges: those
        # apply the SESSION's fine-grained permissions (_fg_edge_ok),
        # and a globally cached snapshot must match export_csr's
        # permission-free content regardless of which user built it
        st = accessor._vertex_state(vertex, View.OLD)
        for (etype, _other, edge) in st.out_edges:
            if edge_type_filter is not None and \
                    etype not in edge_type_filter:
                continue
            ea = EdgeAccessor(edge, accessor)
            if not ea.is_visible(View.OLD):
                continue
            di = index_of(edge.to_vertex.gid)
            if di is None:
                return None           # endpoint in neither prev nor joined
            # every out-edge of a changed vertex re-emits exactly once
            # here; edges INTO a changed vertex from an UNCHANGED source
            # re-emit in the in_edges pass below
            fresh_src.append(idx)
            fresh_dst.append(di)
            if has_w:
                fresh_w.append(_coerce_weight(
                    ea.properties(View.OLD).get(weight_property)))
        for (etype, _other, edge) in st.in_edges:
            if edge_type_filter is not None and \
                    etype not in edge_type_filter:
                continue
            ea = EdgeAccessor(edge, accessor)
            if not ea.is_visible(View.OLD):
                continue
            si = index_of(edge.from_vertex.gid)
            if si is None:
                return None
            if bitmap[si]:
                continue              # its changed src re-emits it
            fresh_src.append(si)
            fresh_dst.append(idx)
            if has_w:
                fresh_w.append(_coerce_weight(
                    ea.properties(View.OLD).get(weight_property)))
    p_src, p_dst, p_w = prev.host_coo
    keep = ~(bitmap[p_src] | bitmap[p_dst])
    src = np.concatenate([p_src[keep].astype(np.int64),
                          np.asarray(fresh_src, dtype=np.int64)])
    dst = np.concatenate([p_dst[keep].astype(np.int64),
                          np.asarray(fresh_dst, dtype=np.int64)])
    weights = None
    if has_w:
        weights = np.concatenate(
            [p_w[keep], np.asarray(fresh_w, dtype=np.float32)])
    node_gids = prev.node_gids
    if joined:
        node_gids = np.concatenate(
            [node_gids, np.asarray(joined, dtype=np.int64)])
    g = from_coo(src, dst, weights, n_nodes=n_nodes,
                 node_gids=node_gids, pad=pad)
    return g.to_device() if to_device else g


# --------------------------------------------------------------------------
# Partition-centric sharded layout (multi-chip analytics)
# --------------------------------------------------------------------------

@dataclass(frozen=True)
class ShardedCSR:
    """Partition-centric (src-shard, dst-shard)-blocked edge layout.

    The mesh analog of DeviceGraph: vertices are split into `n_shards`
    contiguous blocks of `block` ids (padded to n_pad2 = n_shards*block,
    so uneven `n_nodes % n_shards` just pads the last block); every edge
    is owned by the shard of its `by` endpoint ("src" for the pull-style
    SpMV kernels, "dst" for label propagation). Within a shard, edges
    are (dst, src)-sorted, which makes the per-device edge list a
    concatenation of (owner, dst-shard) BLOCKS — the partition-centric
    layout of "Accelerating PageRank using Partition-Centric Processing"
    (PAPERS.md): a device's contribution to remote shard q is the
    contiguous run block_ptr[p, q]:block_ptr[p, q+1], and one
    psum/psum_scatter per iteration moves exactly those partials.

    Arrays are stacked (n_shards, edges_per_shard) and, once
    `.to_device(ctx)` runs, placed one row per device via the
    MeshContext's edge_blocks sharding — CSR shards resident per device,
    so graphs larger than one chip's HBM fit.

    Padding edges: src = shard base (locally index 0), dst = n_nodes
    (the sink row, always < n_pad2), weight 0 — inert under every
    segment reduction, and appended at the tail so dst stays sorted.
    """

    src: object          # (P, per) int32
    dst: object          # (P, per) int32
    weights: object      # (P, per) float32
    block_ptr: np.ndarray  # (P, P+1) int32 — (p, q)-block boundaries
    n_nodes: int
    n_edges: int
    n_shards: int
    block: int           # vertices per shard
    n_pad2: int          # n_shards * block
    per: int             # edges per shard row (incl. padding)
    by: str              # "src" | "dst" — owning endpoint

    def to_device(self, ctx) -> "ShardedCSR":
        """Place edge rows one-per-device under ctx's edge sharding."""
        if not isinstance(self.src, np.ndarray):
            return self
        return ShardedCSR(
            src=ctx.put_edge_blocks(self.src),
            dst=ctx.put_edge_blocks(self.dst),
            weights=ctx.put_edge_blocks(self.weights),
            block_ptr=self.block_ptr, n_nodes=self.n_nodes,
            n_edges=self.n_edges, n_shards=self.n_shards,
            block=self.block, n_pad2=self.n_pad2, per=self.per,
            by=self.by)

    def refresh(self, ctx) -> "ShardedCSR":
        """Re-place the edge rows on the mesh — the device_lost recovery
        hook (parallel/checkpoint.py): after a backend loss the resident
        rows are gone, so pull the host copy and re-run placement. On a
        host-side (not yet placed) layout this is a no-op."""
        if isinstance(self.src, np.ndarray):
            return self
        return ShardedCSR(
            src=ctx.put_edge_blocks(np.asarray(self.src)),
            dst=ctx.put_edge_blocks(np.asarray(self.dst)),
            weights=ctx.put_edge_blocks(np.asarray(self.weights)),
            block_ptr=self.block_ptr, n_nodes=self.n_nodes,
            n_edges=self.n_edges, n_shards=self.n_shards,
            block=self.block, n_pad2=self.n_pad2, per=self.per,
            by=self.by)


def _ceil_multiple(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def shard_edges(src, dst, weights, n_nodes: int, n_shards: int,
                by: str = "src", block_multiple: int = 8) -> ShardedCSR:
    """Block COO edges partition-centrically over `n_shards` shards.

    Host-side layout only — call `.to_device(ctx)` to make the rows
    device-resident. `block` is rounded to `block_multiple` so vertex
    blocks tile the VPU lanes on TPU.
    """
    if by not in ("src", "dst"):
        raise ValueError(f"by must be 'src' or 'dst', got {by!r}")
    src = np.asarray(src, dtype=np.int64)
    dst = np.asarray(dst, dtype=np.int64)
    n_edges = len(src)
    w = (np.ones(n_edges, dtype=np.float32) if weights is None
         else np.asarray(weights, dtype=np.float32))
    # +1: the sink row n_nodes must exist inside the padded vertex space
    block = _ceil_multiple(max((n_nodes + 1 + n_shards - 1) // n_shards, 1),
                           block_multiple)
    n_pad2 = n_shards * block

    key = src if by == "src" else dst
    owner = key // block
    order = np.lexsort((src, dst, owner))
    s_s, d_s, w_s, o_s = src[order], dst[order], w[order], owner[order]
    counts = np.bincount(o_s, minlength=n_shards)
    per = _ceil_multiple(max(int(counts.max(initial=0)), 1), block_multiple)

    sink = n_nodes
    src_b = np.empty((n_shards, per), dtype=np.int32)
    dst_b = np.full((n_shards, per), sink, dtype=np.int32)
    w_b = np.zeros((n_shards, per), dtype=np.float32)
    # padding src must gather in-bounds LOCALLY on its shard: shard base
    src_b[:] = (np.arange(n_shards, dtype=np.int32) * block)[:, None]
    offsets = np.concatenate([[0], np.cumsum(counts)])
    for p in range(n_shards):
        lo, hi = offsets[p], offsets[p + 1]
        src_b[p, :hi - lo] = s_s[lo:hi]
        dst_b[p, :hi - lo] = d_s[lo:hi]
        w_b[p, :hi - lo] = w_s[lo:hi]

    # partition-centric block boundaries: device p's edges into dst
    # shard q are dst_b[p, block_ptr[p, q]:block_ptr[p, q+1]] (the dst
    # sort within each shard makes these contiguous runs)
    block_ptr = np.empty((n_shards, n_shards + 1), dtype=np.int32)
    for p in range(n_shards):
        block_ptr[p] = np.searchsorted(
            dst_b[p], np.arange(n_shards + 1, dtype=np.int64) * block)

    return ShardedCSR(src=src_b, dst=dst_b, weights=w_b,
                      block_ptr=block_ptr, n_nodes=n_nodes,
                      n_edges=n_edges, n_shards=n_shards, block=block,
                      n_pad2=n_pad2, per=per, by=by)


_sharded_csr_guard = threading.Lock()


def shard_csr(graph: DeviceGraph, ctx, by: str = "src",
              doubled: bool = False) -> ShardedCSR:
    """Partition-centric ShardedCSR for `graph` on `ctx`, cached on the
    (immutable) DeviceGraph snapshot per (mesh, by, doubled) — repeated
    mesh CALLs on an unchanged graph pay the blocking and transfer once.

    `doubled=True` concatenates both edge directions before blocking
    (the undirected view label propagation iterates over)."""
    key = (ctx.cache_key, by, doubled)
    cache = getattr(graph, "_sharded_csr", None)
    if cache is not None and key in cache:
        return cache[key]
    with _sharded_csr_guard:
        cache = getattr(graph, "_sharded_csr", None)
        if cache is None:
            cache = {}
            object.__setattr__(graph, "_sharded_csr", cache)
        if key not in cache:
            if graph.host_coo is not None:
                src, dst, w = graph.host_coo
            else:
                src = np.asarray(graph.src_idx)[:graph.n_edges]
                dst = np.asarray(graph.col_idx)[:graph.n_edges]
                w = np.asarray(graph.weights)[:graph.n_edges]
            if doubled:
                src, dst = (np.concatenate([src, dst]),
                            np.concatenate([dst, src]))
                w = np.concatenate([w, w])
            scsr = shard_edges(src, dst, w, graph.n_nodes,
                               ctx.n_shards, by=by)
            cache[key] = scsr.to_device(ctx)
    return cache[key]


class GraphCache:
    """Per-storage cache of device CSR snapshots keyed by topology version.

    The framework-level staleness contract: a cached snapshot is valid while
    `storage.topology_version` is unchanged; any commit that touches
    topology (or properties, conservatively) bumps the version.

    A miss with an older snapshot of the same view in the cache follows
    the change log (export_csr_delta: edge and property changes, and
    vertices that join the view); a vertex that left, a gap the log
    cannot answer or a large change set takes the full export_csr. Which
    of the two served a miss is counted: `delta.export_applied_total` /
    `delta.export_rebuild_total`.

    Keyed on the storage object itself via a WeakKeyDictionary so snapshots
    die with their storage (no id()-recycling hazard, no leak).
    """

    def __init__(self) -> None:
        import weakref
        self._lock = threading.Lock()
        self._cache: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()

    def get(self, accessor, weight_property=None, label_filter=None,
            edge_type_filter=None) -> DeviceGraph:
        storage = accessor.storage
        etf = (tuple(sorted(edge_type_filter))
               if edge_type_filter is not None else None)
        # key on the TRANSACTION's topology snapshot, not the live
        # version: a concurrent commit after this txn began must not be
        # visible in (or poison) the snapshot cached for this view —
        # the bump is atomic with the visibility flip relative to this
        # capture (storage._commit), so the snapshot id and the MVCC
        # view agree (r5 review findings 2+3)
        version = getattr(accessor, "topology_snapshot", None)
        if version is None:
            version = storage.topology_version
        key = (version, weight_property, label_filter, etf)
        base_key = ("base", weight_property, label_filter, etf)
        newest = None
        with self._lock:
            per_storage = self._cache.get(storage)
            hit = per_storage.get(key) if per_storage else None
            base = per_storage.get(base_key) if per_storage else None
            for k, v in (per_storage or {}).items():
                if k[0] == "base" or k[1:] != key[1:]:
                    continue
                # base anchor: newest snapshot with a FULL mxu plan
                # (_mxu_base_self post-dates its get(), so scan live)
                if getattr(v, "_mxu_base_self", False) \
                        and (base is None or base[0] < k[0]):
                    base = (k[0], v)
                # delta-export base: newest snapshot STRICTLY OLDER than
                # this view (a newer one may contain commits this txn
                # cannot see)
                if k[0] < version and (newest is None
                                       or k[0] > newest[0]):
                    newest = (k[0], v)
        if hit is not None:
            return hit
        from ..observability.metrics import global_metrics
        g = None
        # O(changed) incremental export (the python walk over ALL edges
        # is the dominant per-version cost at 10M+ edges); bulk commits
        # touching a large fraction of the graph fall back to the full
        # export, whose delta-free fast path is cheaper per edge
        if newest is not None:
            from ..storage.storage import (ChangeLogUnknowable,
                                           change_set_is_small)
            changed = storage.changes_between(newest[0], version)
            if isinstance(changed, ChangeLogUnknowable):
                # typed wrap verdict: the log cannot reconstruct the
                # gap — full export, LOUDLY counted (a silently-partial
                # delta here would cache a wrong snapshot)
                import logging
                global_metrics.increment("delta.fallback_rebuild_total")
                logging.getLogger(__name__).info(
                    "change log unknowable (%s) for versions (%d, %d]; "
                    "full CSR export", changed.reason, newest[0],
                    version)
                changed = None
            if changed is not None and \
                    change_set_is_small(len(changed), newest[1].n_nodes):
                try:
                    g = export_csr_delta(
                        newest[1], accessor, changed,
                        weight_property=weight_property,
                        label_filter=label_filter,
                        edge_type_filter=edge_type_filter)
                except Exception:  # noqa: BLE001 — any doubt: full export
                    import logging
                    logging.getLogger(__name__).debug(
                        "delta CSR export failed; falling back to full "
                        "export", exc_info=True)
                    g = None
        if g is not None:
            global_metrics.increment("delta.export_applied_total")
        else:
            global_metrics.increment("delta.export_rebuild_total")
            g = export_csr(accessor, weight_property=weight_property,
                           label_filter=label_filter,
                           edge_type_filter=edge_type_filter)
        # Delta lineage: if an earlier snapshot of this view carries a
        # fully-built MXU plan, record it plus the changed-vertex set so
        # the analytics layer can refresh O(delta) instead of replanning
        # (ops/pagerank._try_delta_plan).
        if base is not None:
            from ..storage.storage import ChangeLogUnknowable
            base_version, base_g = base
            changed = storage.changes_between(base_version, version)
            # an unknowable gap (typed wrap verdict) anchors nothing:
            # the MXU layer would replan from an incomplete diff
            if isinstance(changed, frozenset) \
                    and getattr(base_g, "_mxu_state", None) is not None:
                object.__setattr__(g, "_delta_ctx", (base_g, changed))
        with self._lock:
            # keep base anchors, NEWER versions (an older-view txn
            # storing must not evict a newer snapshot — r5 review) and
            # the other views' snapshots (e.g. other weight properties:
            # each is the next delta export's base of its own view);
            # drop this view's strictly older snapshots
            per = self._cache.get(storage) or {}
            prev = {k: v for k, v in per.items()
                    if k[0] == "base" or k[0] >= version
                    or k[1:] != key[1:]}
            # the previous snapshot becomes the base anchor once a FULL
            # plan was built on it (pagerank marks _mxu_base_self)
            for k, v in per.items():
                if k[0] not in ("base", version) \
                        and k[1:] == key[1:] \
                        and getattr(v, "_mxu_base_self", False):
                    cur_base = prev.get(base_key)
                    if cur_base is None or cur_base[0] < k[0]:
                        prev[base_key] = (k[0], v)
            prev[key] = g
            self._cache[storage] = prev
        return g

    def clear(self) -> None:
        with self._lock:
            self._cache = __import__("weakref").WeakKeyDictionary()


GLOBAL_GRAPH_CACHE = GraphCache()
