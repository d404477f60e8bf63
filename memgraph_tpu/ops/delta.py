"""mgdelta: incremental semiring fixpoints on a device-resident graph.

Every analytics CALL used to rebuild the CSR from storage (a Python
MVCC walk over ALL edges), re-shard it (a global lexsort), and recompute
the fixpoint from a cold start — so results went stale the moment write
traffic flowed, and the only incremental path was the pagerank-MXU-only
``DeltaPlan`` (ops/spmv_mxu.py). This module generalizes that side-net
idea to the whole semiring core:

  * :class:`EdgeDelta` — one commit range's change-log entries compiled
    into added/removed edge COO blocks over DENSE node indices (plus the
    per-node out-weight adjustments they imply). The generalization of
    DeltaPlan's signed side-nets: instead of routing the delta through a
    separate Benes net, the delta is SPLICED into the resident
    partition-centric layout, so every backend (mesh / MXU / segment)
    sees the exact updated graph through unchanged kernels.
  * :func:`apply_edge_delta` — the O(delta + affected shard rows)
    refresh of a resident :class:`~.csr.ShardedCSR`: removed edges are
    matched inside their owning shard row (binary search on the
    (dst, src) sort), added edges merge-insert in order, padding and
    ``block_ptr`` are repaired per affected row only. Unaffected shard
    rows are untouched; the global re-sort of a full rebuild never runs.
  * :class:`ResidentGraph` — one device-resident generation keyed
    ``(graph_key, base_version)``: the DeviceGraph snapshot, its host
    ShardedCSR variants, and the per-algorithm last solutions that seed
    warm-started fixpoints. Bounded delta accumulation: once the edges
    applied since the last full build exceed
    ``DELTA_COMPACT_FRACTION`` of the edge count, the next delta
    triggers a compacting rebuild (restoring per-row padding slack).
  * Warm-start contracts (:data:`WARM_START_POLICY`): pagerank / PPR /
    katz iterate contractions with a unique fixpoint — ANY seed
    converges to the same answer at the same tol, so the previous
    solution is always a valid x0 (residual-equivalent to cold,
    enforced by tests/test_delta.py). WCC's min-label propagation is
    warm-safe when the delta is monotone (edge ADDITIONS only —
    components can merge but never split, so the fixpoint from the old
    labels is the cold one); a delta with removals forces a LOUD cold
    start (``delta.cold_start_total``). A label-propagation election
    is NOT warm-safe: its answer is the labels after T synchronous
    rounds from the vertex ids (LDBC Graphalytics CDLP), and T rounds
    from the previous labels elect other labels after almost any
    commit. The exact procedures (``label_propagation.get``,
    ``community_detection.get``) therefore run cold on a moved graph
    (``cdlp``: ``never``) and only serve their stored answer on the
    unchanged one; the warm seed is kept for
    ``community_detection_online.get`` alone (``labelprop``), whose
    upstream counterpart (LabelRankT) is approximate by nature, under
    the monotone gate.

The warm-start framing follows "Accelerating Personalized PageRank
Vector Computation" (PAPERS.md): after a small perturbation the residual
of the previous solution is O(delta), so the fixpoint needs the few
iterations the perturbation actually costs, not the cold count.

Metrics (STAT_NAMES, surfaced under ``GET /stats`` → ``delta``):
``delta.applied_total`` / ``delta.compacted_total`` /
``delta.fallback_rebuild_total`` counters, ``delta.edge_count`` and
``delta.warm_start_iterations`` histograms, the
``delta.resident_generations`` gauge, and
``delta.warm_start_total`` / ``delta.cold_start_total``.
"""

from __future__ import annotations

import logging
import os
from dataclasses import dataclass

import numpy as np

from ..observability.metrics import global_metrics
from .csr import DeviceGraph, ShardedCSR, from_coo, shard_edges

log = logging.getLogger(__name__)

#: once the edges applied since the last full build exceed this fraction
#: of the resident edge count, the next delta triggers a compacting
#: rebuild (padding slack restored, per-row capacity re-sized)
DELTA_COMPACT_FRACTION = float(
    os.environ.get("MEMGRAPH_TPU_DELTA_COMPACT_FRACTION", "0.25"))

#: a single delta larger than this fraction of the edge set skips the
#: splice outright — the full rebuild is cheaper per edge at that size
DELTA_MAX_FRACTION = float(
    os.environ.get("MEMGRAPH_TPU_DELTA_MAX_FRACTION", "0.25"))

#: per-algorithm warm-start contracts (see module docstring):
#:   "always"     — contraction with a unique fixpoint; any seed is
#:                  residual-equivalent to cold at the same tol
#:   "adds_only"  — monotone iteration; warm only when the cumulative
#:                  delta since the seed solution added edges but never
#:                  removed any, else LOUD cold start
#:   "never"      — the answer depends on the seed, and the seed is the
#:                  vertex ids: a moved graph runs cold, silently, and
#:                  only the unchanged graph's stored answer is served
WARM_START_POLICY = {
    "pagerank": "always",
    "ppr": "always",
    "katz": "always",
    "wcc": "adds_only",
    # community_detection_online.get only: an election seeded by the
    # previous labels is a fixpoint of the election on the new graph,
    # not the cold answer (see the module docstring)
    "labelprop": "adds_only",
    # label_propagation.get / community_detection.get: exact CDLP
    "cdlp": "never",
}


# --------------------------------------------------------------------------
# EdgeDelta: the compiled change-log side-net
# --------------------------------------------------------------------------


@dataclass(frozen=True)
class EdgeDelta:
    """Added/removed edge COO blocks over dense node indices.

    The node set must be unchanged across the covered range — node
    additions/removals shift the dense relabeling and require a full
    re-export (the compiler returns None there). Weight updates are a
    remove + add of the same (src, dst) pair.
    """

    base_version: int
    version: int
    add_src: np.ndarray        # (a,) int64 dense indices
    add_dst: np.ndarray
    add_w: np.ndarray          # (a,) float32
    rem_src: np.ndarray        # (r,) int64 dense indices
    rem_dst: np.ndarray
    rem_w: np.ndarray          # (r,) float32

    @property
    def n_delta(self) -> int:
        return len(self.add_src) + len(self.rem_src)

    @property
    def adds_only(self) -> bool:
        """True iff the delta is monotone (no removed edges) — the
        warm-start precondition for WCC / labelprop."""
        return len(self.rem_src) == 0

    def doubled(self) -> "EdgeDelta":
        """Both edge directions (the undirected view labelprop's
        dst-owned doubled ShardedCSR iterates over)."""
        return EdgeDelta(
            base_version=self.base_version, version=self.version,
            add_src=np.concatenate([self.add_src, self.add_dst]),
            add_dst=np.concatenate([self.add_dst, self.add_src]),
            add_w=np.concatenate([self.add_w, self.add_w]),
            rem_src=np.concatenate([self.rem_src, self.rem_dst]),
            rem_dst=np.concatenate([self.rem_dst, self.rem_src]),
            rem_w=np.concatenate([self.rem_w, self.rem_w]))

    def wsum_adjust(self, n_nodes: int) -> np.ndarray:
        """Per-node out-weight-sum adjustment the delta implies — the
        degree/weight rescale vector of the DeltaPlan formulation (the
        mesh kernels recompute wsum from the spliced rows in-kernel, so
        this is exposed for the MXU side-net path and for tests)."""
        adj = np.zeros(n_nodes, dtype=np.float64)
        if len(self.add_src):
            np.add.at(adj, self.add_src, self.add_w.astype(np.float64))
        if len(self.rem_src):
            np.subtract.at(adj, self.rem_src,
                           self.rem_w.astype(np.float64))
        return adj

    def touched_nodes(self) -> np.ndarray:
        """Unique dense indices incident to the delta (the invalidation
        set serving-plane caches demote by)."""
        return np.unique(np.concatenate([
            self.add_src, self.add_dst, self.rem_src, self.rem_dst]))

    def to_arrays(self) -> dict:
        """Socket-shippable arrays (kernel-server request payload)."""
        return {"delta_add_src": self.add_src.astype(np.int64),
                "delta_add_dst": self.add_dst.astype(np.int64),
                "delta_add_w": self.add_w.astype(np.float32),
                "delta_rem_src": self.rem_src.astype(np.int64),
                "delta_rem_dst": self.rem_dst.astype(np.int64),
                "delta_rem_w": self.rem_w.astype(np.float32)}

    @classmethod
    def from_arrays(cls, base_version: int, version: int,
                    arrays: dict) -> "EdgeDelta | None":
        need = ("delta_add_src", "delta_add_dst", "delta_add_w",
                "delta_rem_src", "delta_rem_dst", "delta_rem_w")
        if any(k not in arrays for k in need):
            return None
        return cls(
            base_version=int(base_version), version=int(version),
            add_src=np.asarray(arrays["delta_add_src"], dtype=np.int64),
            add_dst=np.asarray(arrays["delta_add_dst"], dtype=np.int64),
            add_w=np.asarray(arrays["delta_add_w"], dtype=np.float32),
            rem_src=np.asarray(arrays["delta_rem_src"], dtype=np.int64),
            rem_dst=np.asarray(arrays["delta_rem_dst"], dtype=np.int64),
            rem_w=np.asarray(arrays["delta_rem_w"], dtype=np.float32))


def empty_delta(base_version: int, version: int) -> EdgeDelta:
    z = np.zeros(0, dtype=np.int64)
    zf = np.zeros(0, dtype=np.float32)
    return EdgeDelta(base_version, version, z, z, zf, z.copy(), z.copy(),
                     zf.copy())


# --------------------------------------------------------------------------
# delta compilation: change-log gids -> EdgeDelta
# --------------------------------------------------------------------------


def incident_edges(src, dst, w, bitmap: np.ndarray):
    """Edges with at least one endpoint in ``bitmap`` (dense bool mask).
    One vectorized pass over the COO arrays."""
    src = np.asarray(src)
    dst = np.asarray(dst)
    sel = bitmap[src] | bitmap[dst]
    return (src[sel].astype(np.int64), dst[sel].astype(np.int64),
            np.asarray(w, dtype=np.float32)[sel])


def multiset_edge_diff(old_edges, new_edges):
    """Multiset diff of two (src, dst, w) edge lists.

    Returns ((add_src, add_dst, add_w), (rem_src, rem_dst, rem_w)).
    Weights compare bit-exactly (a weight update is a remove + add).
    One lexsort + run-length net-count pass — O(m log m) with memcpy
    constants (the np.unique(axis=0) formulation's void-view sort cost
    dominated the whole delta pipeline at bench scale).
    """
    o_s, o_d, o_w = (np.asarray(a) for a in old_edges)
    n_s, n_d, n_w = (np.asarray(a) for a in new_edges)
    if len(o_s) + len(n_s) == 0:
        z = np.zeros(0, dtype=np.int64)
        zf = np.zeros(0, dtype=np.float32)
        return (z, z.copy(), zf), (z.copy(), z.copy(), zf.copy())
    src = np.concatenate([n_s.astype(np.int64), o_s.astype(np.int64)])
    dst = np.concatenate([n_d.astype(np.int64), o_d.astype(np.int64)])
    wb = np.concatenate([n_w.astype(np.float32),
                         o_w.astype(np.float32)]).view(np.int32) \
        .astype(np.int64)
    sign = np.concatenate([np.ones(len(n_s), dtype=np.int64),
                           -np.ones(len(o_s), dtype=np.int64)])
    order = np.lexsort((wb, dst, src))
    s2, d2, w2, sg = src[order], dst[order], wb[order], sign[order]
    boundary = (s2[1:] != s2[:-1]) | (d2[1:] != d2[:-1]) \
        | (w2[1:] != w2[:-1])
    starts = np.concatenate([[0], np.nonzero(boundary)[0] + 1])
    net = np.add.reduceat(sg, starts)
    add_rep = np.repeat(starts, np.maximum(net, 0))
    rem_rep = np.repeat(starts, np.maximum(-net, 0))

    def w_back(col):
        return col.astype(np.int32).view(np.float32)

    added = (s2[add_rep], d2[add_rep], w_back(w2[add_rep]))
    removed = (s2[rem_rep], d2[rem_rep], w_back(w2[rem_rep]))
    return added, removed


def diff_incident(prev_coo, changed_idx, inc_src, inc_dst, inc_w,
                  n_nodes: int, base_version: int,
                  version: int) -> EdgeDelta:
    """EdgeDelta from the CURRENT incident edges of the changed
    vertices (the route layer ships exactly these — O(delta
    neighborhood) on the wire, never the full edge list): the previous
    incident set is extracted from the resident snapshot's COO, the two
    are multiset-diffed. Edges between unchanged vertices are identical
    by the change-log contract and never compared."""
    bitmap = np.zeros(n_nodes, dtype=bool)
    ci = np.asarray(changed_idx, dtype=np.int64)
    if len(ci):
        bitmap[ci] = True
    old_inc = incident_edges(*prev_coo, bitmap)
    inc_src = np.asarray(inc_src, dtype=np.int64)
    inc_dst = np.asarray(inc_dst, dtype=np.int64)
    inc_w = (np.ones(len(inc_src), dtype=np.float32) if inc_w is None
             else np.asarray(inc_w, dtype=np.float32))
    (a_s, a_d, a_w), (r_s, r_d, r_w) = multiset_edge_diff(
        old_inc, (inc_src, inc_dst, inc_w))
    return EdgeDelta(base_version, version, a_s, a_d, a_w, r_s, r_d, r_w)


def diff_changed_coo(prev_coo, cur_coo, changed_idx, n_nodes: int,
                     base_version: int, version: int) -> EdgeDelta:
    """EdgeDelta between two COO snapshots of the SAME node set,
    restricted to edges incident to ``changed_idx`` (the dense indices
    the change log reported)."""
    bitmap = np.zeros(n_nodes, dtype=bool)
    ci = np.asarray(changed_idx, dtype=np.int64)
    if len(ci):
        bitmap[ci] = True
    cur = incident_edges(*cur_coo, bitmap)
    return diff_incident(prev_coo, changed_idx, cur[0], cur[1], cur[2],
                         n_nodes, base_version, version)


def compile_edge_delta(storage, prev_graph: DeviceGraph,
                       cur_graph: DeviceGraph, base_version: int,
                       version: int):
    """Compile the change-log entries covering (base_version, version]
    into an :class:`EdgeDelta` between two already-exported snapshots.

    Returns the delta, a falsy ``ChangeLogUnknowable`` when the bounded
    log wrapped past the range (callers fall back to a full rebuild,
    LOUDLY), or None when the node set changed (dense ids shifted — a
    delta over stale indices would corrupt the resident layout).
    """
    from ..storage.storage import ChangeLogUnknowable
    if base_version == version:
        return empty_delta(base_version, version)
    changed = storage.changes_between(base_version, version)
    if isinstance(changed, ChangeLogUnknowable):
        return changed
    if prev_graph.host_coo is None or cur_graph.host_coo is None:
        return None
    if prev_graph.n_nodes != cur_graph.n_nodes or \
            not np.array_equal(prev_graph.node_gids,
                               cur_graph.node_gids):
        return None
    changed_idx = [cur_graph.gid_to_idx[g] for g in changed
                   if g in cur_graph.gid_to_idx]
    if len(changed_idx) != len(changed):
        return None               # a changed vertex left/joined the view
    return diff_changed_coo(prev_graph.host_coo, cur_graph.host_coo,
                            changed_idx, cur_graph.n_nodes,
                            base_version, version)


# --------------------------------------------------------------------------
# O(delta) refresh of a resident ShardedCSR
# --------------------------------------------------------------------------


def _row_real_count(dst_row: np.ndarray, sink: int) -> int:
    """Real edges in a (dst, src)-sorted shard row (padding entries all
    carry dst == sink and sort to the tail)."""
    return int(np.searchsorted(dst_row, sink, side="left"))


def _match_removals(row_src, row_dst, row_w, rem_src, rem_dst, rem_w,
                    n_pad2: int):
    """Indices of row positions matching each removal triple, or None if
    any removal has no match (inconsistent delta -> caller rebuilds).
    The row is (dst, src)-sorted, so each (dst, src) run is a binary
    search; weight matching scans the (tiny) run."""
    key_row = row_dst.astype(np.int64) * n_pad2 + row_src
    out = []
    used: set = set()
    for s, d, w in zip(rem_src, rem_dst, rem_w):
        k = int(d) * n_pad2 + int(s)
        lo = int(np.searchsorted(key_row, k, side="left"))
        hi = int(np.searchsorted(key_row, k, side="right"))
        hit = -1
        for i in range(lo, hi):
            if i not in used and row_w[i] == w:
                hit = i
                break
        if hit < 0:
            # tolerate weight drift: match any unused duplicate of the
            # (src, dst) pair — NO: a miss means the delta and the
            # resident rows disagree; a silent partial apply would
            # corrupt the generation. Rebuild instead.
            return None
        used.add(hit)
        out.append(hit)
    return out


def apply_edge_delta(scsr: ShardedCSR, delta: EdgeDelta):
    """Splice an EdgeDelta into a HOST-side ShardedCSR.

    O(delta) index work plus O(row) merge cost for AFFECTED shard rows
    only — unaffected rows (arrays and block_ptr) are reused untouched,
    and the full rebuild's global lexsort never runs. Returns the new
    host ShardedCSR, or None when the splice cannot preserve the layout
    (a row overflows its ``per`` capacity, or a removal doesn't match
    the resident rows) — the caller falls back to a compacting rebuild.
    """
    if not isinstance(scsr.src, np.ndarray):
        raise ValueError("apply_edge_delta needs the HOST-side layout; "
                         "splice then re-place with .to_device(ctx)")
    if delta.n_delta == 0:
        return scsr
    block, n_shards, per = scsr.block, scsr.n_shards, scsr.per
    sink = scsr.n_nodes
    key = "src" if scsr.by == "src" else "dst"
    add_owner = (delta.add_src if key == "src" else delta.add_dst) // block
    rem_owner = (delta.rem_src if key == "src" else delta.rem_dst) // block
    affected = np.union1d(np.unique(add_owner), np.unique(rem_owner))
    if len(affected) and (affected.min() < 0
                          or affected.max() >= n_shards):
        return None               # delta references nodes outside layout

    src_b = scsr.src.copy()
    dst_b = scsr.dst.copy()
    w_b = scsr.weights.copy()
    block_ptr = scsr.block_ptr.copy()
    shard_bounds = np.arange(n_shards + 1, dtype=np.int64) * block

    for p in affected:
        p = int(p)
        rc = _row_real_count(dst_b[p], sink)
        r_sel = rem_owner == p
        a_sel = add_owner == p
        row_s = src_b[p, :rc]
        row_d = dst_b[p, :rc]
        row_w = w_b[p, :rc]
        keep = np.ones(rc, dtype=bool)
        if r_sel.any():
            hits = _match_removals(
                row_s, row_d, row_w, delta.rem_src[r_sel],
                delta.rem_dst[r_sel], delta.rem_w[r_sel], scsr.n_pad2)
            if hits is None:
                return None
            keep[hits] = False
        a_s = delta.add_src[a_sel]
        a_d = delta.add_dst[a_sel]
        a_w = delta.add_w[a_sel]
        new_rc = int(keep.sum()) + len(a_s)
        if new_rc > per:
            return None           # capacity overflow -> compaction
        k_s, k_d, k_w = row_s[keep], row_d[keep], row_w[keep]
        if len(a_s):
            order = np.lexsort((a_s, a_d))
            a_s, a_d, a_w = a_s[order], a_d[order], a_w[order]
            # merge-insert into the (dst, src)-sorted survivors
            kept_key = k_d.astype(np.int64) * scsr.n_pad2 + k_s
            add_key = a_d.astype(np.int64) * scsr.n_pad2 + a_s
            pos = np.searchsorted(kept_key, add_key, side="left")
            k_s = np.insert(k_s, pos, a_s.astype(np.int32))
            k_d = np.insert(k_d, pos, a_d.astype(np.int32))
            k_w = np.insert(k_w, pos, a_w)
        src_b[p, :new_rc] = k_s
        dst_b[p, :new_rc] = k_d
        w_b[p, :new_rc] = k_w
        src_b[p, new_rc:] = np.int32(p * block)   # padding convention
        dst_b[p, new_rc:] = np.int32(sink)
        w_b[p, new_rc:] = 0.0
        block_ptr[p] = np.searchsorted(dst_b[p], shard_bounds)

    n_edges = scsr.n_edges + len(delta.add_src) - len(delta.rem_src)
    return ShardedCSR(src=src_b, dst=dst_b, weights=w_b,
                      block_ptr=block_ptr, n_nodes=scsr.n_nodes,
                      n_edges=n_edges, n_shards=n_shards, block=block,
                      n_pad2=scsr.n_pad2, per=per, by=scsr.by)


def splice_coo(coo, delta: EdgeDelta, n_nodes: int):
    """Apply an EdgeDelta to a host COO triple. Removal matching is
    vectorized over the incident subset (the non-incident edges are
    untouched by construction). Returns the new (src, dst, w) or None
    when a removal doesn't match."""
    src, dst, w = (np.asarray(a) for a in coo)
    w = w.astype(np.float32, copy=False)
    keep = np.ones(len(src), dtype=bool)
    if len(delta.rem_src):
        bitmap = np.zeros(n_nodes, dtype=bool)
        bitmap[delta.rem_src] = True
        bitmap[delta.rem_dst] = True
        cand = np.nonzero(bitmap[src] | bitmap[dst])[0]
        c_key = (src[cand].astype(np.int64) * n_nodes
                 + dst[cand].astype(np.int64))
        c_w = w[cand]
        order = np.argsort(c_key, kind="stable")
        c_key, c_w, cand = c_key[order], c_w[order], cand[order]
        used = np.zeros(len(cand), dtype=bool)
        for s, d, rw in zip(delta.rem_src, delta.rem_dst, delta.rem_w):
            k = int(s) * n_nodes + int(d)
            lo = int(np.searchsorted(c_key, k, side="left"))
            hi = int(np.searchsorted(c_key, k, side="right"))
            hit = -1
            for i in range(lo, hi):
                if not used[i] and c_w[i] == rw:
                    hit = i
                    break
            if hit < 0:
                return None
            used[hit] = True
            keep[cand[hit]] = False
    new_src = np.concatenate([src[keep].astype(np.int64),
                              delta.add_src])
    new_dst = np.concatenate([dst[keep].astype(np.int64),
                              delta.add_dst])
    new_w = np.concatenate([w[keep], delta.add_w])
    return new_src, new_dst, new_w


def refresh_device_graph(prev: DeviceGraph, delta: EdgeDelta):
    """New DeviceGraph snapshot = resident snapshot + delta, node set
    preserved. The COO splice is vectorized and the CSR/CSC build rides
    the native counting-sort builder — no Python MVCC walk, no storage
    access. Returns None when the splice fails (caller re-imports)."""
    if prev.host_coo is None:
        return None
    coo = splice_coo(prev.host_coo, delta, prev.n_nodes)
    if coo is None:
        return None
    src, dst, w = coo
    return from_coo(src, dst, w, n_nodes=prev.n_nodes,
                    node_gids=prev.node_gids, pad=True)


# --------------------------------------------------------------------------
# warm-start contracts
# --------------------------------------------------------------------------


def warm_start_decision(algo: str, monotone_ok: bool):
    """(warm: bool, reason: str) for seeding ``algo`` from a previous
    solution whose graph moved by a delta with ``monotone_ok`` =
    "every covered delta added edges only, and none was unknowable".

    Callers must treat a False verdict for an ``adds_only`` algorithm
    as a LOUD cold start (log + ``delta.cold_start_total``)."""
    policy = WARM_START_POLICY.get(algo)
    if policy == "always":
        return True, "contraction"
    if policy == "adds_only":
        if monotone_ok:
            return True, "monotone_adds_only"
        return False, "monotone_unsafe"
    return False, "no_policy"


def record_warm_start(algo: str, iters: int) -> None:
    global_metrics.increment("delta.warm_start_total")
    global_metrics.observe("delta.warm_start_iterations", float(iters))
    log.debug("delta: warm-started %s converged in %d iterations",
              algo, iters)


def record_cold_start(algo: str, reason: str) -> None:
    """The LOUD cold start of the warm-start contract: monotone-unsafe
    deltas (or unknowable change-log ranges) must never warm-start a
    non-contraction algorithm silently."""
    global_metrics.increment("delta.cold_start_total")
    log.warning("delta: COLD start for %s (%s) — previous solution "
                "cannot seed this fixpoint", algo, reason)


# --------------------------------------------------------------------------
# resident generations
# --------------------------------------------------------------------------


@dataclass
class _Solution:
    x: np.ndarray
    version: int
    params_key: tuple
    monotone_ok: bool = True
    err: float | None = None
    iters: int | None = None
    max_iterations: int | None = None


class ResidentGraph:
    """One device-resident graph generation for a ``graph_key``.

    Owned by a single dispatcher thread (the kernel server's dispatch
    lock / the procedures' warm pool lock) — no internal locking, same
    contract as the server's graph LRU.

    The snapshot is LAZY: the canonical state is the host COO (spliced
    O(delta) per commit) plus the partition-centric host variants; the
    DeviceGraph (CSR/CSC arrays, a native O(E) counting-sort build) is
    only materialized when a consumer actually reads it (the segment /
    PPR-SpMM paths) — the mesh-served path never pays it per commit.
    """

    __slots__ = ("graph_key", "version", "host_variants", "solutions",
                 "delta_edges", "base_edges", "tiers", "_graph", "_coo",
                 "_n_nodes", "_node_gids", "_gid_to_idx", "_placed")

    def __init__(self, graph_key, version: int,
                 graph: DeviceGraph) -> None:
        self.graph_key = graph_key
        self.version = int(version)
        self._graph = graph
        if graph.host_coo is None:
            raise ValueError("ResidentGraph needs a snapshot with host "
                             "COO arrays (from_coo keeps them)")
        self._coo = graph.host_coo
        self._n_nodes = int(graph.n_nodes)
        self._node_gids = graph.node_gids
        self._gid_to_idx = graph.gid_to_idx
        self._placed = not isinstance(graph.row_ptr, np.ndarray)
        #: (by, doubled) -> host-side ShardedCSR (the splice substrate)
        self.host_variants: dict = {}
        #: (precision, block_bytes) -> TierCSR (out-of-core paging plan)
        self.tiers: dict = {}
        #: algo -> _Solution (the warm-start seeds)
        self.solutions: dict = {}
        self.delta_edges = 0
        self.base_edges = int(graph.n_edges)

    # --- lazy snapshot -----------------------------------------------------

    @property
    def coo(self):
        """Canonical host (src, dst, w) COO of the CURRENT generation
        (the diff substrate)."""
        return self._coo

    @property
    def n_nodes(self) -> int:
        return self._n_nodes

    @property
    def n_edges(self) -> int:
        return len(self._coo[0])

    @property
    def node_gids(self):
        return self._node_gids

    @property
    def gid_to_idx(self):
        return self._gid_to_idx

    @property
    def graph(self) -> DeviceGraph:
        """The DeviceGraph snapshot — materialized on first read after
        a delta (native counting-sort build + placement matching the
        original import). Mesh-only consumers never trigger this."""
        if self._graph is None:
            g = from_coo(self._coo[0].astype(np.int64),
                         self._coo[1].astype(np.int64),
                         np.asarray(self._coo[2], dtype=np.float32),
                         n_nodes=self._n_nodes,
                         node_gids=self._node_gids)
            self._graph = g.to_device() if self._placed else g
        return self._graph

    # --- sharded variants --------------------------------------------------

    def ensure_sharded(self, ctx, by: str = "src",
                       doubled: bool = False) -> ShardedCSR:
        """Device-resident partition-centric variant for ``ctx``; the
        host layout is kept as the splice substrate and the placed copy
        is cached per mesh context so the serving path never re-sorts
        or re-transfers an unchanged generation.

        The blocking + placement extent attributes to the active
        mgtrace span / mgstat stage accumulator exactly like the
        GraphCache path's ``_shard_traced`` — PROFILE on a resident-
        served query still shows where transfer seconds went (cache
        hits show as ~zero-duration extents, itself useful signal)."""
        from ..observability import trace as mgtrace
        with mgtrace.span("device.transfer") as sp:
            hv = self.host_variants.get((by, doubled))
            if hv is None:
                hv = self._reshard(by, doubled, ctx.n_shards)
                self.host_variants[(by, doubled)] = hv
            dev = self._install(ctx, by, doubled, hv)
            if sp:
                sp.set(n_shards=ctx.n_shards, by=by,
                       n_nodes=int(self._n_nodes), resident=True)
        return dev

    def ensure_tier(self, precision: str = "f32",
                    block_bytes: int | None = None):
        """Host-pinned streamed paging plan (``ops/tier.py``) for this
        generation — the out-of-core analogue of :meth:`ensure_sharded`
        for graphs whose edges exceed the HBM budget. Nothing places:
        the plan's compressed wire blocks stay pinned host-side and the
        execution plane streams them per sweep. Committed deltas splice
        into the plan through :meth:`apply` (only touched rows
        re-encode), so a churned beyond-HBM graph never re-ships cold."""
        from . import tier as mgtier
        key = (precision, block_bytes)
        t = self.tiers.get(key)
        if t is None:
            src, dst, w = self._coo
            t = mgtier.plan_tier(
                src.astype(np.int64), dst.astype(np.int64),
                np.asarray(w, dtype=np.float32), self._n_nodes,
                precision=precision, block_bytes=block_bytes)
            self.tiers[key] = t
        return t

    def _install(self, ctx, by, doubled, host_scsr) -> ShardedCSR:
        # device placements ride the materialized-or-not snapshot? No:
        # they live on the HOST variant object itself (one placement per
        # mesh context), so laziness of the snapshot never matters here
        cache = getattr(host_scsr, "_placed_cache", None)
        key = (ctx.cache_key,)
        if cache is None:
            cache = {}
            object.__setattr__(host_scsr, "_placed_cache", cache)
        dev = cache.get(key)
        if dev is None:
            dev = host_scsr.to_device(ctx)
            cache[key] = dev
        return dev

    # --- delta application -------------------------------------------------

    def apply(self, delta: EdgeDelta, ctx=None) -> bool:
        """Advance this generation by one EdgeDelta.

        Splices the canonical COO and every host variant O(delta +
        affected rows) and DEFERS the snapshot rebuild; a failed
        splice, or accumulated deltas past ``DELTA_COMPACT_FRACTION``
        of the edge count, triggers the compacting rebuild instead
        (counted ``delta.compacted_total``). Returns False only when
        even the rebuild is impossible (caller must re-import the graph
        from storage).
        """
        if delta.n_delta == 0:
            # property-only bump: the edge set is unchanged — advance
            # the version, keep every warm seed monotone-valid
            self._note_moved(delta)
            global_metrics.increment("delta.applied_total")
            global_metrics.observe("delta.edge_count", 0.0)
            return True
        if delta.n_delta > max(DELTA_MAX_FRACTION * max(self.base_edges,
                                                        1), 1024):
            return self._compact(delta, ctx, why="oversized delta")
        new_coo = splice_coo(self._coo, delta, self._n_nodes)
        if new_coo is None:
            global_metrics.increment("delta.fallback_rebuild_total")
            log.warning("delta: splice failed for %s (removal mismatch) "
                        "— generation must be re-imported",
                        self.graph_key)
            return False
        self._coo = (new_coo[0].astype(np.int32),
                     new_coo[1].astype(np.int32),
                     new_coo[2].astype(np.float32))
        self._graph = None                     # snapshot: rebuilt lazily
        self.delta_edges += delta.n_delta
        if self.delta_edges > DELTA_COMPACT_FRACTION * max(
                self.base_edges, 1):
            # accumulated padding debt: rebuild the variants fresh from
            # the spliced COO (the COO itself is already exact)
            self._note_moved(delta)
            return self._compact(None, ctx, why="accumulated deltas")
        # variant splice: each layout variant moves by the same delta
        # (doubled variants by the doubled delta)
        new_variants = {}
        for (by, doubled), hv in self.host_variants.items():
            d = delta.doubled() if doubled else delta
            nv = apply_edge_delta(hv, d)
            if nv is None:
                global_metrics.increment("delta.compacted_total")
                log.info("delta: variant (%s, doubled=%s) of %s "
                         "overflowed its row capacity — recompacting",
                         by, doubled, self.graph_key)
                nv = self._reshard(by, doubled, hv.n_shards)
            new_variants[(by, doubled)] = nv
        self.host_variants = new_variants
        # streamed paging plans move by the same splice; a row overflow
        # drops the plan (ensure_tier rebuilds it from the exact COO)
        new_tiers = {}
        for key, t in self.tiers.items():
            nt = t.apply_delta(delta)
            if nt is None:
                global_metrics.increment("delta.compacted_total")
                log.info("delta: tier %s of %s overflowed its row "
                         "capacity — dropping for lazy rebuild", key,
                         self.graph_key)
            else:
                new_tiers[key] = nt
        self.tiers = new_tiers
        if ctx is not None:
            for (by, doubled), hv in new_variants.items():
                self._install(ctx, by, doubled, hv)
        self._note_moved(delta)
        global_metrics.increment("delta.applied_total")
        global_metrics.observe("delta.edge_count", float(delta.n_delta))
        return True

    def _reshard(self, by, doubled, n_shards) -> ShardedCSR:
        src, dst, w = self._coo
        src = src.astype(np.int64)
        dst = dst.astype(np.int64)
        if doubled:
            src, dst = (np.concatenate([src, dst]),
                        np.concatenate([dst, src]))
            w = np.concatenate([w, w])
        return shard_edges(src, dst, w, self._n_nodes, n_shards, by=by)

    def _compact(self, delta, ctx, why: str) -> bool:
        """Full rebuild of the variants from the updated COO — the
        bounded-accumulation escape hatch (the snapshot stays lazy)."""
        if delta is not None:
            new_coo = splice_coo(self._coo, delta, self._n_nodes)
            if new_coo is None:
                global_metrics.increment("delta.fallback_rebuild_total")
                return False
            self._coo = (new_coo[0].astype(np.int32),
                         new_coo[1].astype(np.int32),
                         new_coo[2].astype(np.float32))
            self._graph = None
            self._note_moved(delta)
        shards = {(by, doubled): hv.n_shards
                  for (by, doubled), hv in self.host_variants.items()}
        self.host_variants = {
            key: self._reshard(key[0], key[1], n)
            for key, n in shards.items()}
        if ctx is not None:
            for (by, doubled), hv in self.host_variants.items():
                self._install(ctx, by, doubled, hv)
        self.tiers = {}                        # lazily rebuilt, exact
        self.delta_edges = 0
        self.base_edges = self.n_edges
        global_metrics.increment("delta.compacted_total")
        log.info("delta: compacted generation %s (%s)", self.graph_key,
                 why)
        return True

    def _note_moved(self, delta: EdgeDelta) -> None:
        self.version = int(delta.version)
        for sol in self.solutions.values():
            sol.monotone_ok = sol.monotone_ok and delta.adds_only

    # --- warm-start seeds --------------------------------------------------

    def note_solution(self, algo: str, params_key: tuple,
                      x: np.ndarray, err: float | None = None,
                      iters: int | None = None,
                      max_iterations: int | None = None) -> None:
        self.solutions[algo] = _Solution(
            x=np.asarray(x), version=self.version,
            params_key=tuple(params_key), monotone_ok=True,
            err=err, iters=iters, max_iterations=max_iterations)

    def cached_result(self, algo: str, params_key: tuple,
                      max_iterations=None):
        """The stored solution VERBATIM when the generation hasn't
        moved since it was computed and the request parameters match —
        result-cache semantics (same contract as the PPR result cache):
        identical repeated requests get identical bytes, never a
        re-iterated answer drifting in the low-order bits."""
        sol = self.solutions.get(algo)
        if sol is None or sol.params_key != tuple(params_key) \
                or sol.version != self.version:
            return None
        if max_iterations is not None and sol.max_iterations is not None \
                and int(max_iterations) != int(sol.max_iterations):
            return None
        return sol

    def warm_x0(self, algo: str, params_key: tuple):
        """(x0, reason) — x0 is None for a cold start; a loud cold
        (monotone-unsafe seed discarded) is already counted here."""
        sol = self.solutions.get(algo)
        if sol is None or sol.params_key != tuple(params_key):
            return None, "no_seed"
        warm, reason = warm_start_decision(algo, sol.monotone_ok)
        if not warm:
            record_cold_start(algo, reason)
            self.solutions.pop(algo, None)
            return None, reason
        return sol.x, reason


class ResidentRegistry:
    """Bounded graph_key -> ResidentGraph LRU (the kernel server's
    ``_graphs`` replacement). Callers serialize through the dispatcher
    (same single-thread contract the old DeviceGraph LRU had)."""

    def __init__(self, capacity: int = 8) -> None:
        from collections import OrderedDict
        self.capacity = capacity
        self._gens: "OrderedDict[object, ResidentGraph]" = OrderedDict()

    def get(self, graph_key) -> ResidentGraph | None:
        gen = self._gens.get(graph_key)
        if gen is not None:
            self._gens.move_to_end(graph_key)
        return gen

    def put(self, gen: ResidentGraph) -> None:
        self._gens[gen.graph_key] = gen
        self._gens.move_to_end(gen.graph_key)
        while len(self._gens) > self.capacity:
            self._gens.popitem(last=False)
        self._gauge()

    def pop(self, graph_key) -> None:
        self._gens.pop(graph_key, None)
        self._gauge()

    def __len__(self) -> int:
        return len(self._gens)

    def _gauge(self) -> None:
        global_metrics.set_gauge("delta.resident_generations",
                             float(len(self._gens)))


# --------------------------------------------------------------------------
# in-process warm pool (commit-then-CALL without a kernel server)
# --------------------------------------------------------------------------


class LocalWarmPool:
    """Per-storage warm-start state for the in-process analytics path.

    GraphCache already makes the re-export O(changed); this pool closes
    the other half of commit-then-CALL: the previous solution (and the
    COO snapshot it was computed on) is kept per storage so the next
    CALL seeds its fixpoint and — for the monotone-gated algorithms —
    the adds-only precondition is verified against the real edge diff.
    """

    def __init__(self) -> None:
        import weakref
        from ..utils.locks import tracked_lock
        self._lock = tracked_lock("LocalWarmPool._lock")
        self._pool: "weakref.WeakKeyDictionary" = \
            weakref.WeakKeyDictionary()

    def _entry(self, storage):
        return self._pool.get(storage)

    def prepare(self, storage, graph: DeviceGraph, version: int,
                algo: str, params_key: tuple):
        """(cached_result, warm_seed) — at most one is non-None.

        ``cached_result`` is the stored solution VERBATIM when the
        graph hasn't moved since it was computed (result-cache
        semantics: identical repeated CALLs return identical bytes,
        never a re-iterated answer drifting in the low-order bits).
        ``warm_seed`` is the (n_nodes,) x0 for a moved graph under the
        per-algorithm warm-start contract; the monotone-unsafe loud
        cold is counted/logged here."""
        from ..storage.storage import ChangeLogUnknowable
        with self._lock:
            entry = self._entry(storage)
            if entry is None:
                return None, None
            sol = entry["solutions"].get(algo)
            if sol is None or sol.params_key != tuple(params_key):
                return None, None
            if not np.array_equal(entry["node_gids"], graph.node_gids):
                return None, None  # dense ids shifted: seed meaningless
            if version == sol.version:
                return np.asarray(sol.x), None
            if WARM_START_POLICY.get(algo) == "never":
                return None, None
            monotone_ok = sol.monotone_ok
            if version != entry["version"]:
                changed = storage.changes_between(entry["version"],
                                                  version)
                if isinstance(changed, ChangeLogUnknowable) \
                        or graph.host_coo is None:
                    monotone_ok = False
                else:
                    changed_idx = [graph.gid_to_idx[g] for g in changed
                                   if g in graph.gid_to_idx]
                    d = diff_changed_coo(
                        entry["host_coo"], graph.host_coo, changed_idx,
                        graph.n_nodes, entry["version"], version)
                    monotone_ok = monotone_ok and d.adds_only
            warm, reason = warm_start_decision(algo, monotone_ok)
            if not warm:
                record_cold_start(algo, reason)
                entry["solutions"].pop(algo, None)
                return None, None
            return None, np.asarray(sol.x)

    def store(self, storage, graph: DeviceGraph, version: int,
              algo: str, params_key: tuple, x) -> None:
        if graph.host_coo is None:
            return
        from ..storage.storage import ChangeLogUnknowable
        with self._lock:
            entry = self._entry(storage)
            if entry is None or not np.array_equal(
                    entry["node_gids"], graph.node_gids):
                entry = {"version": int(version),
                         "host_coo": graph.host_coo,
                         "node_gids": graph.node_gids,
                         "solutions": {}}
            elif entry["version"] != version:
                # the pool snapshot moves to this version: fold the step
                # delta into every retained solution's monotone flag
                changed = storage.changes_between(entry["version"],
                                                  version)
                if isinstance(changed, ChangeLogUnknowable):
                    for s in entry["solutions"].values():
                        s.monotone_ok = False
                else:
                    changed_idx = [graph.gid_to_idx[g] for g in changed
                                   if g in graph.gid_to_idx]
                    d = diff_changed_coo(
                        entry["host_coo"], graph.host_coo, changed_idx,
                        graph.n_nodes, entry["version"], version)
                    if not d.adds_only:
                        for s in entry["solutions"].values():
                            s.monotone_ok = False
                entry["version"] = int(version)
                entry["host_coo"] = graph.host_coo
            entry["solutions"][algo] = _Solution(
                x=np.asarray(x), version=int(version),
                params_key=tuple(params_key), monotone_ok=True)
            self._pool[storage] = entry

    def clear(self) -> None:
        import weakref
        with self._lock:
            self._pool = weakref.WeakKeyDictionary()


GLOBAL_WARM_POOL = LocalWarmPool()
