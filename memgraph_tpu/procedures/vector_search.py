"""Vector search module: brute-force/IVF kNN over node embedding properties,
with O(delta) incremental maintenance.

Counterpart of /root/reference/query_modules/vector_search_module.cpp (which
fronts the usearch HNSW index, src/storage/v2/indices/vector_index.cpp:22-73
for the update path): here search IS the index — batched MXU matmul + top_k
over a device-resident embedding matrix.

Incremental maintenance design (four holes it closes):
  1. replica WAL apply bypasses commit hooks → there are NO hooks: the
     storage records changed-gid sets at every topology bump (including
     WAL apply and recovery), and the index PULLS the delta via
     storage.changes_between(entry.version, reader.version).
  2. snapshot-isolation readers could bake pre-commit values → entries
     are keyed by the READER's topology snapshot (Accessor.topology
     _snapshot), and a bounded per-property version map serves concurrent
     readers at different snapshots; all reads go through the reader's
     own MVCC accessor.
  3. rebuild errors could lose invalidations → pull-based: a failed
     build leaves no entry; the next call simply retries.
  4. dominant-dimension filtering could drop clean rows → per-dimension
     candidate counts are maintained through deltas; if the dominant
     dimension changes, the index falls back to a full rebuild.

Rows live in a capacity-padded device matrix with a validity mask; delta
refresh is one batched .at[rows].set scatter (device) + O(delta) MVCC
reads (host) instead of an O(n) full scan.

Observability: the phase spans ``vector.index`` (one ``_get_index``,
whole), ``vector.refresh`` / ``vector.build`` (the miss it served) and
``vector.search`` (upload, kernel, readback), and the counters
``delta.vector_applied_total`` / ``delta.vector_rebuild_total``: a
lookup that had to make an entry, by what made it.
"""

from __future__ import annotations

import threading
import weakref
from collections import Counter
from dataclasses import dataclass, field

import numpy as np

from . import mgp
from ..observability import trace as mgtrace
from ..observability.metrics import global_metrics

_CACHE_LOCK = threading.Lock()
# storage (weak) -> {property_name: {version: _IndexEntry}}
_CACHE: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()
_KEEP_VERSIONS = 4          # concurrent readers at older snapshots
_DELTA_MAX_FRACTION = 0.5   # larger deltas rebuild outright


@dataclass
class _IndexEntry:
    version: int
    pid: int | None
    dim: int | None                      # dominant dimension (rows kept)
    dim_counts: Counter                  # candidate count per dimension
    gid_to_row: dict = field(default_factory=dict)
    row_gids: list = field(default_factory=list)   # row -> gid | None
    free_rows: list = field(default_factory=list)
    offdim: dict = field(default_factory=dict)     # gid -> non-dominant dim
    matrix: object = None                # jnp (capacity, dim)
    valid: object = None                 # jnp (capacity,) f32

    @property
    def size(self) -> int:
        return len(self.gid_to_row)


def _read_vector(va, pid, view):
    """The vertex's embedding candidate, or None."""
    if va is None or not va.is_visible(view):
        return None
    vec = va.get_property(pid, view)
    if isinstance(vec, (list, tuple)) and vec and \
            all(isinstance(x, (int, float)) and not isinstance(x, bool)
                for x in vec):
        return [float(x) for x in vec]
    return None


def _full_build(ctx, pid, version) -> _IndexEntry:
    import jax.numpy as jnp
    global_metrics.increment("delta.vector_rebuild_total")
    vectors, gids = [], []
    if pid is not None:
        for va in ctx.accessor.vertices(ctx.view):
            vec = _read_vector(va, pid, ctx.view)
            if vec is not None:
                vectors.append(vec)
                gids.append(va.gid)
    dim_counts = Counter(len(v) for v in vectors)
    if not vectors:
        return _IndexEntry(version, pid, None, dim_counts)
    dim = dim_counts.most_common(1)[0][0]
    kept = [(v, g) for v, g in zip(vectors, gids) if len(v) == dim]
    mat_np = np.asarray([v for v, _ in kept], dtype=np.float32)
    row_gids = [g for _, g in kept]
    entry = _IndexEntry(
        version, pid, dim, dim_counts,
        gid_to_row={g: i for i, g in enumerate(row_gids)},
        row_gids=list(row_gids),
        offdim={g: len(v) for v, g in zip(vectors, gids)
                if len(v) != dim},
        matrix=jnp.asarray(mat_np),
        valid=jnp.ones(len(row_gids), dtype=jnp.float32))
    return entry


def _delta_refresh(ctx, parent: _IndexEntry, changed, version):
    """New entry at `version` from `parent` by patching only `changed`
    gids; returns None if a full rebuild is required (dominant dimension
    flip, or parent has no matrix yet but vectors appeared)."""
    import jax.numpy as jnp
    pid, view = parent.pid, ctx.view
    dim_counts = Counter(parent.dim_counts)
    gid_to_row = dict(parent.gid_to_row)
    row_gids = list(parent.row_gids)
    free_rows = list(parent.free_rows)
    offdim = dict(parent.offdim)
    set_rows: list[int] = []
    set_vals: list[list[float]] = []
    clear_rows: list[int] = []
    new_vecs: dict = {}

    def drop_row(gid):
        row = gid_to_row.pop(gid, None)
        if row is not None:
            row_gids[row] = None
            free_rows.append(row)
            clear_rows.append(row)

    for gid in changed:
        va = ctx.accessor.find_vertex(gid, view)
        vec = _read_vector(va, pid, view)
        # retire the gid's previous candidate (row or off-dimension)
        if gid in gid_to_row:
            dim_counts[parent.dim] -= 1
        elif gid in offdim:
            dim_counts[offdim.pop(gid)] -= 1
        if vec is None:
            drop_row(gid)
        elif parent.dim is not None and len(vec) == parent.dim:
            dim_counts[parent.dim] += 1
            new_vecs[gid] = vec
        else:
            # off-dimension candidate: counted (dominance tracking,
            # hole 4 above) but holds no row
            dim_counts[len(vec)] += 1
            offdim[gid] = len(vec)
            drop_row(gid)

    dim_counts = Counter({d: c for d, c in dim_counts.items() if c > 0})
    if parent.dim is None:
        return None if dim_counts else _IndexEntry(
            version, pid, None, dim_counts)
    if dim_counts and dim_counts.most_common(1)[0][0] != parent.dim:
        return None                      # dominant dimension flipped

    matrix, valid = parent.matrix, parent.valid
    for gid, vec in new_vecs.items():
        row = gid_to_row.get(gid)
        if row is None:
            if free_rows:
                row = free_rows.pop()
            else:
                row = len(row_gids)
                row_gids.append(None)
                if matrix is None or row >= matrix.shape[0]:
                    grow = max(16, (matrix.shape[0] if matrix is not None
                                    else 0))
                    pad = jnp.zeros((grow, parent.dim), jnp.float32)
                    matrix = (jnp.concatenate([matrix, pad])
                              if matrix is not None else pad)
                    valid = (jnp.concatenate(
                        [valid, jnp.zeros(grow, jnp.float32)])
                        if valid is not None
                        else jnp.zeros(grow, jnp.float32))
            gid_to_row[gid] = row
            row_gids[row] = gid
        set_rows.append(row)
        set_vals.append(vec)

    # clears BEFORE sets: a freed row reused for a new vector in this
    # same refresh must end up valid
    if clear_rows:
        rows = jnp.asarray(np.asarray(clear_rows, dtype=np.int32))
        valid = valid.at[rows].set(0.0)
    if set_rows:
        rows = jnp.asarray(np.asarray(set_rows, dtype=np.int32))
        vals = jnp.asarray(np.asarray(set_vals, dtype=np.float32))
        matrix = matrix.at[rows].set(vals)
        valid = valid.at[rows].set(1.0)

    global_metrics.increment("delta.vector_applied_total")
    return _IndexEntry(version, pid, parent.dim, dim_counts,
                       gid_to_row=gid_to_row, row_gids=row_gids,
                       free_rows=free_rows, offdim=offdim,
                       matrix=matrix, valid=valid)


def _get_index(ctx, property_name: str) -> _IndexEntry:
    with mgtrace.span("vector.index") as sp:
        entry, kind = _lookup_index(ctx, property_name)
        if sp:
            sp.set(kind=kind)
    return entry


def _lookup_index(ctx, property_name: str):
    """(entry for the reader's snapshot, how it was come by: hit, alias,
    delta, full)."""
    storage = ctx.storage
    version = getattr(ctx.accessor, "topology_snapshot",
                      storage.topology_version)
    # a transaction with its OWN writes sees state no other reader at
    # this version sees: serve it a PRIVATE entry (parent + own touched
    # gids as extra delta) and never store it — read-your-own-writes
    # without poisoning the shared version map
    own_writes = frozenset(
        getattr(getattr(ctx.accessor, "txn", None), "touched_vertices",
                None) or ())
    with _CACHE_LOCK:
        per = _CACHE.get(storage) or {}
        by_version = dict(per.get(property_name) or {})
    entry = by_version.get(version)
    if entry is not None and not own_writes:
        return entry, "hit"

    parent = entry
    if parent is None:
        candidates = [e for v, e in by_version.items() if v < version]
        if candidates:
            parent = max(candidates, key=lambda e: e.version)

    entry = None
    if parent is not None:
        from ..storage.storage import ChangeLogUnknowable
        changed = storage.changes_between(parent.version, version)
        if isinstance(changed, ChangeLogUnknowable):
            # typed wrap verdict: the gap is unreconstructable — fall
            # through to the full rebuild below (a partial delta would
            # leave the index silently missing rows)
            changed = None
        else:
            changed = changed | own_writes
        if changed is not None and not changed:
            # nothing relevant changed: alias the parent at this version
            entry, kind = parent, "alias"
        elif changed is not None and (
                parent.size == 0
                or len(changed) <= max(64,
                                       _DELTA_MAX_FRACTION * parent.size)):
            with mgtrace.span("vector.refresh"):
                entry = _delta_refresh(ctx, parent, changed, version)
            kind = "delta"
    if entry is None:
        pid = storage.property_mapper.maybe_name_to_id(property_name)
        with mgtrace.span("vector.build"):
            entry = _full_build(ctx, pid, version)
        kind = "full"

    if own_writes:
        return entry, kind             # private view: never cached

    with _CACHE_LOCK:
        per = _CACHE.get(storage)
        if per is None:
            per = {}
        by_version = per.setdefault(property_name, {})
        by_version[version] = entry
        # keep only the newest few versions (older concurrent readers)
        for v in sorted(by_version)[:-_KEEP_VERSIONS]:
            del by_version[v]
        per[property_name] = by_version
        _CACHE[storage] = per
    return entry, kind


def _search_entry(entry: _IndexEntry, query_rows, k: int, metric: str):
    """(scores (q, k'), row indices (q, k')) over live rows, read back
    to the host; `query_rows` is host rows or a device array."""
    import jax.numpy as jnp
    from ..ops.knn import knn
    k = min(k, entry.size)
    if k <= 0 or entry.matrix is None:
        return None, None
    with mgtrace.span("vector.search"):
        q = jnp.asarray(query_rows, dtype=jnp.float32)
        scores, idx = knn(entry.matrix, q, k=k, metric=metric,
                          valid_mask=entry.valid)
        return np.asarray(scores), np.asarray(idx)


@mgp.read_proc("vector_search.search",
               args=[("property", "STRING"), ("query", "LIST"),
                     ("limit", "INTEGER")],
               opt_args=[("metric", "STRING", "cosine")],
               results=[("node", "NODE"), ("similarity", "FLOAT")])
def search(ctx, property, query, limit, metric="cosine"):
    entry = _get_index(ctx, property)
    scores, idx = _search_entry(entry, [query], int(limit), str(metric))
    if scores is None:
        return
    for score, i in zip(scores[0], idx[0]):
        gid = entry.row_gids[int(i)]
        if gid is None:
            continue
        node = ctx.accessor.find_vertex(gid, ctx.view)
        if node is not None:
            yield {"node": node, "similarity": float(score)}


@mgp.read_proc("vector_search.show_index_info",
               results=[("index_name", "STRING"), ("label", "STRING"),
                        ("property", "STRING"), ("dimension", "INTEGER"),
                        ("size", "INTEGER")])
def show_index_info(ctx):
    with _CACHE_LOCK:
        per = {prop: dict(bv)
               for prop, bv in (_CACHE.get(ctx.storage) or {}).items()}
    for prop, by_version in sorted(per.items()):
        if not by_version:
            continue
        entry = by_version[max(by_version)]
        yield {"index_name": f"vector::{prop}", "label": "*",
               "property": prop,
               "dimension": int(entry.dim or 0),
               "size": entry.size}


@mgp.read_proc("vector_search.ppr_search",
               args=[("property", "STRING"), ("query", "LIST"),
                     ("k_seeds", "INTEGER"), ("limit", "INTEGER")],
               opt_args=[("damping", "FLOAT", 0.85),
                         ("metric", "STRING", "cosine")],
               results=[("node", "NODE"), ("score", "FLOAT"),
                        ("seed_similarity", "FLOAT")])
def ppr_search(ctx, property, query, k_seeds, limit, damping=0.85,
               metric="cosine"):
    """ANN seed → coalesced PPR expansion → rerank.

    The serving-plane sibling of plain ``search``: the k nearest
    embedding rows seed a personalized-PageRank restart, so results
    rank by graph proximity to the semantic matches instead of raw
    cosine alone. With a resident kernel server configured the PPR leg
    is ONE coalesced round trip (batched with every concurrent caller,
    top-k extracted on device, result cache consulted); otherwise it
    runs in-process."""
    from ..ops.pagerank import personalized_pagerank
    from .graph_algorithms import _kernel_server_ppr

    entry = _get_index(ctx, str(property))
    if entry.matrix is None:
        return
    graph = ctx.device_graph()
    if graph.n_nodes == 0:
        return
    sims, idx = _search_entry(entry, [query], int(k_seeds), str(metric))
    if sims is None:
        return
    seed_sim: dict[int, float] = {}
    seed_indices: list[int] = []
    for sim, i in zip(sims[0], idx[0]):
        gid = entry.row_gids[int(i)]
        di = graph.gid_to_idx.get(gid) if gid is not None else None
        if di is not None:
            seed_indices.append(di)
            seed_sim[di] = float(sim)
    if not seed_indices:
        return

    served = _kernel_server_ppr(ctx, graph, seed_indices, float(damping),
                                100, 1e-6, top_k=int(limit))
    if served is not None:
        _h, out = served
        pairs = zip(out["topk_val"], out["topk_idx"])
    else:
        ranks, _, _ = personalized_pagerank(graph, seed_indices,
                                            damping=float(damping),
                                            max_iterations=100)
        ranks = np.asarray(ranks)
        order = np.argsort(-ranks)[:int(limit)]
        pairs = ((ranks[i], i) for i in order)
    for score, i in pairs:
        if score <= 0:
            break
        node = ctx.vertex_by_index(graph, int(i))
        if node is not None:
            yield {"node": node, "score": float(score),
                   "seed_similarity": seed_sim.get(int(i), 0.0)}


@mgp.read_proc("knn.get",
               args=[("node", "NODE"), ("property", "STRING"),
                     ("k", "INTEGER")],
               opt_args=[("metric", "STRING", "cosine")],
               results=[("neighbor", "NODE"), ("similarity", "FLOAT")])
def knn_get(ctx, node, property, k, metric="cosine"):
    """k nearest neighbors of an existing node by embedding similarity
    (counterpart of mage/cpp/knn_module)."""
    entry = _get_index(ctx, property)
    if node is None or entry.matrix is None:
        return
    row = entry.gid_to_row.get(node.gid)
    if row is None:
        return
    q = entry.matrix[row:row + 1]
    scores, idx = _search_entry(entry, q, int(k) + 1, str(metric))
    if scores is None:
        return
    emitted = 0
    for score, i in zip(scores[0], idx[0]):
        if int(i) == row:
            continue
        if emitted >= int(k):
            break
        gid = entry.row_gids[int(i)]
        if gid is None:
            continue
        nb = ctx.accessor.find_vertex(gid, ctx.view)
        if nb is not None:
            emitted += 1
            yield {"neighbor": nb, "similarity": float(score)}
