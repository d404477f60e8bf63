"""Columnar property snapshots for intra-query parallel execution.

TPU-native counterpart of the reference's intra-query parallelism
(/root/reference/src/query/plan/operator.hpp:1925-2273 ScanAllParallel*/
AggregateParallel and plan/rewrite/parallel_rewrite.hpp): instead of a
work-stealing thread pool iterating record batches, the scan's property
accesses are exported ONCE into dense typed columns (the same
export-and-cache contract as the CSR snapshot in ops/csr.py), and
filter+aggregate lower onto whole-column vectorized kernels.

Execution runs on host numpy rather than the chip: predicate/aggregate
semantics need exact int64 (vertex ids and integer properties exceed
f32's 2^24 mantissa, and this jax build keeps x64 disabled), and a
column pass is a single streaming sweep — the layout here is
device-ready (dense values + present bitmask) for f32-safe offload, but
the win over the row-at-a-time Volcano path (~100x at 10M rows) comes
from the columnar representation itself.

Columns:
  kind "int"   int64 values  (all_int aggregates stay integers)
  kind "float" float64 values
  kind "bool"  int8 0/1
  kind "str"   int32 dictionary codes + vocab (equality only)
  kind "other" present mask only (count(prop) works; predicates do not)
Absent properties and deleted rows are absent from `present`.
"""

from __future__ import annotations

import logging
import threading
from dataclasses import dataclass, field

import numpy as np

from ..exceptions import HintedAbortError
from ..observability.metrics import global_metrics
from ..storage.common import IsolationLevel

log = logging.getLogger(__name__)

_INT53 = 2**53      # the integers a float64 holds exactly


@dataclass
class Column:
    kind: str                      # int | float | bool | str | other
    values: np.ndarray | None      # typed values (None for "other")
    present: np.ndarray            # (n,) bool
    vocab: dict | None = None      # str value -> code, for kind "str"
    big: bool = False              # int column holds |v| > 2^53: a float
    #                                rhs comparison would lose exactness
    mixed: bool = False            # float column coerced from int+float
    #                                values: original per-row types lost


@dataclass
class ColumnarSnapshot:
    n: int
    gids: np.ndarray               # (n,) int64 storage gids
    columns: dict = field(default_factory=dict)   # prop name -> Column


def _kind_of(v) -> str:
    if isinstance(v, bool):
        return "bool"
    if isinstance(v, int):
        return "int"
    if isinstance(v, float):
        return "float"
    if isinstance(v, str):
        return "str"
    return "other"


def _classify(values: list, present: np.ndarray) -> Column:
    """Pick the narrowest uniform kind covering all present values."""
    kinds = set()
    for v, p in zip(values, present):
        if not p:
            continue
        kinds.add(_kind_of(v))
        if len(kinds) > 1 and kinds != {"int", "float"}:
            return Column("other", None, present)
    if not kinds:
        return Column("other", None, present)
    if kinds == {"int"}:
        if any(p and not -2**63 <= v < 2**63
               for v, p in zip(values, present)):
            return Column("other", None, present)   # beyond int64
        out = np.zeros(len(values), dtype=np.int64)
        for i, (v, p) in enumerate(zip(values, present)):
            if p:
                out[i] = v
        big = any(p and not -2**53 <= v <= 2**53
                  for v, p in zip(values, present))
        return Column("int", out, present, big=big)
    if kinds <= {"int", "float"}:
        # mixed numerics coerce to f64; an int beyond 2^53 would lose
        # exactness (= / < would diverge from the row path) -> opt out
        if any(p and isinstance(v, int) and not -2**53 <= v <= 2**53
               for v, p in zip(values, present)):
            return Column("other", None, present)
        out = np.zeros(len(values), dtype=np.float64)
        for i, (v, p) in enumerate(zip(values, present)):
            if p:
                out[i] = v
        return Column("float", out, present, mixed=("int" in kinds))
    if kinds == {"bool"}:
        out = np.zeros(len(values), dtype=np.int8)
        for i, (v, p) in enumerate(zip(values, present)):
            if p:
                out[i] = 1 if v else 0
        return Column("bool", out, present)
    if kinds == {"str"}:
        vocab: dict = {}
        out = np.zeros(len(values), dtype=np.int32)
        for i, (v, p) in enumerate(zip(values, present)):
            if p:
                out[i] = vocab.setdefault(v, len(vocab))
        return Column("str", out, present, vocab)
    return Column("other", None, present)


def _typed(raw: list) -> Column:
    """One property's raw values (None: absent) as a typed column."""
    present = np.fromiter((v is not None for v in raw), dtype=bool,
                          count=len(raw))
    return _classify(raw, present)


def export_columns(accessor, label: str | None,
                   props: tuple[str, ...], view,
                   abort_check=None) -> ColumnarSnapshot:
    """One sweep over the accessor's visible vertices of `label` (or all),
    materializing the requested properties as typed columns.
    abort_check (if given) is called periodically so TERMINATE/timeout
    interrupts the sweep like the row path's per-row check."""
    storage = accessor.storage
    prop_ids = []
    for p in props:
        prop_ids.append(storage.property_mapper.maybe_name_to_id(p))

    gids: list[int] = []
    raw: list[list] = [[] for _ in props]
    if label is not None:
        lid = storage.label_mapper.maybe_name_to_id(label)
        it = (accessor.vertices_by_label(lid, view) if lid is not None
              else iter(()))
    else:
        it = accessor.vertices(view)
    for i, va in enumerate(it):
        if abort_check is not None and (i & 0x1FFF) == 0:
            abort_check()
        gids.append(va.gid)
        pd = va.properties(view)
        for j, pid in enumerate(prop_ids):
            raw[j].append(None if pid is None else pd.get(pid))

    n = len(gids)
    snap = ColumnarSnapshot(n=n, gids=np.asarray(gids, dtype=np.int64))
    for j, p in enumerate(props):
        snap.columns[p] = _typed(raw[j])
    return snap


@dataclass
class EdgeSnapshot:
    """Columnar edge table: one row per visible edge, with endpoint gids,
    type ids and requested edge-property columns (the edge analog of
    ColumnarSnapshot; feeds the columnar Expand collapse)."""
    n: int
    gids: np.ndarray               # (n,) int64 edge gids
    src: np.ndarray                # (n,) int64 from-vertex gids
    dst: np.ndarray                # (n,) int64 to-vertex gids
    type_ids: np.ndarray           # (n,) int32 edge type ids
    columns: dict = field(default_factory=dict)   # prop name -> Column


def export_edges(accessor, props: tuple[str, ...], view,
                 abort_check=None) -> EdgeSnapshot:
    """One MVCC-correct sweep over the accessor's visible edges."""
    storage = accessor.storage
    prop_ids = [storage.property_mapper.maybe_name_to_id(p) for p in props]
    gids: list[int] = []
    src: list[int] = []
    dst: list[int] = []
    types: list[int] = []
    raw: list[list] = [[] for _ in props]
    for i, ea in enumerate(accessor.edges(view)):
        if abort_check is not None and (i & 0x1FFF) == 0:
            abort_check()
        gids.append(ea.gid)
        src.append(ea.from_vertex().gid)
        dst.append(ea.to_vertex().gid)
        types.append(ea.edge_type)
        pd = ea.properties(view)
        for j, pid in enumerate(prop_ids):
            raw[j].append(None if pid is None else pd.get(pid))
    n = len(gids)
    snap = EdgeSnapshot(
        n=n, gids=np.asarray(gids, dtype=np.int64),
        src=np.asarray(src, dtype=np.int64),
        dst=np.asarray(dst, dtype=np.int64),
        type_ids=np.asarray(types, dtype=np.int32))
    for j, p in enumerate(props):
        snap.columns[p] = _typed(raw[j])
    return snap


# --------------------------------------------------------------------------
# O(changed) refresh: a snapshot of one version patched into the next
# --------------------------------------------------------------------------
# The sweeps above stay the fallback and the tests' oracle
# (tests/test_columnar_delta.py): a patched snapshot holds the sweep's
# rows, keyed by gid, in another order. Copy on write: the previous
# snapshot may be in another thread's hands, so every array is new.


def _column_patched(col: Column, rows: np.ndarray, keep: np.ndarray,
                    vals: list) -> Column | None:
    """`col` with `rows` rewritten to the first len(rows) of `vals`
    (None: absent), cut to the rows `keep` marks, the rest of `vals`
    appended. None where only a sweep can say what the column is now: a
    value that does not fit the kind, or a value gone from a column
    whose kind was decided by values it does not hold ("other", a
    coerced float)."""
    kind, vocab, mixed = col.kind, col.vocab, col.mixed
    fresh_present = np.fromiter((v is not None for v in vals), dtype=bool,
                                count=len(vals))
    live = [v for v in vals if v is not None]
    touched = ~keep
    touched[rows] = True
    gone = bool((col.present & touched).any())
    if kind == "other":
        # absorbing under additions while a value that made it so stays
        if gone or (live and not col.present.any()):
            return None
    elif kind == "int":
        if any(_kind_of(v) != "int" or not -2**63 <= v < 2**63
               for v in live):
            return None
    elif kind == "float":
        if mixed and gone:
            return None         # was the value that went the last int?
        for v in live:
            k = _kind_of(v)
            if k == "int" and -_INT53 <= v <= _INT53:
                mixed = True
            elif k != "float":
                return None
    elif any(_kind_of(v) != kind for v in live):    # bool, str
        return None

    n_rows = len(rows)
    present = col.present.copy()
    present[rows] = fresh_present[:n_rows]
    present = np.concatenate([present[keep], fresh_present[n_rows:]])
    if kind == "other" or not present.any():
        return Column("other", None, present)       # as _classify: no value
    if kind == "str":
        vocab = dict(vocab)
        fresh = np.fromiter(
            (0 if v is None else vocab.setdefault(v, len(vocab))
             for v in vals), dtype=np.int32, count=len(vals))
    else:
        fresh = np.array([0 if v is None else v for v in vals],
                         dtype=col.values.dtype)
    values = col.values.copy()
    values[rows] = fresh[:n_rows]
    values = np.concatenate([values[keep], fresh[n_rows:]])
    if kind == "str":
        used = np.zeros(len(vocab), dtype=bool)
        used[values[present]] = True
        if not used.all():      # a string's last row went: codes close up
            remap = (np.cumsum(used) - 1).astype(np.int32)
            values = np.where(present, remap[values], 0).astype(np.int32)
            vocab = {s: int(remap[c]) for s, c in vocab.items() if used[c]}
    big = kind == "int" and bool(
        ((values > _INT53) | (values < -_INT53)).any())
    return Column(kind, values, present, vocab, big=big, mixed=mixed)


def patch_columns(prev: ColumnarSnapshot, accessor, label: str | None,
                  changed, view, abort_check=None) -> ColumnarSnapshot | None:
    """`prev` (another version of the same table) brought to the
    accessor's view by reading only the `changed` vertices: a row the
    table has is rewritten in every column `prev` holds, a vertex new to
    the view (created, gained the label) is appended, one that left it
    is dropped. None where a column needs the sweep (_column_patched)."""
    storage = accessor.storage
    lid = (storage.label_mapper.maybe_name_to_id(label)
           if label is not None else None)
    props = tuple(prev.columns)
    prop_ids = [storage.property_mapper.maybe_name_to_id(p) for p in props]
    gids = sorted(changed)
    hit = np.flatnonzero(np.isin(prev.gids,
                                 np.asarray(gids, dtype=np.int64)))
    row_of = dict(zip(prev.gids[hit].tolist(), hit.tolist()))
    keep = np.ones(prev.n, dtype=bool)
    rows: list[int] = []
    tail_gids: list[int] = []
    row_vals: list[list] = [[] for _ in props]
    tail_vals: list[list] = [[] for _ in props]
    for i, gid in enumerate(gids):
        if abort_check is not None and (i & 0x1FFF) == 0:
            abort_check()
        row = row_of.get(gid)
        vertex = storage._vertices.get(gid)
        st = (accessor._vertex_state(vertex, view, need_edges=False)
              if vertex is not None else None)
        if st is None or not st.exists or st.deleted \
                or (label is not None and lid not in st.labels):
            if row is not None:
                keep[row] = False
            continue
        if row is None:
            tail_gids.append(gid)
            into = tail_vals
        else:
            rows.append(row)
            into = row_vals
        for j, pid in enumerate(prop_ids):
            into[j].append(None if pid is None else st.properties.get(pid))
    rows_arr = np.asarray(rows, dtype=np.int64)
    columns = {}
    for j, p in enumerate(props):
        col = _column_patched(prev.columns[p], rows_arr, keep,
                              row_vals[j] + tail_vals[j])
        if col is None:
            return None
        columns[p] = col
    out = np.concatenate([prev.gids[keep],
                          np.asarray(tail_gids, dtype=np.int64)])
    return ColumnarSnapshot(n=len(out), gids=out, columns=columns)


def patch_edges(prev: EdgeSnapshot, accessor, changed, view,
                abort_check=None) -> EdgeSnapshot | None:
    """The edge table's patch: every row with an endpoint in `changed`
    is dropped and the changed vertices' visible edges are read from
    their adjacency, each once (the pattern of csr.export_csr_delta)."""
    storage = accessor.storage
    props = tuple(prev.columns)
    prop_ids = [storage.property_mapper.maybe_name_to_id(p) for p in props]
    changed_arr = np.fromiter(changed, dtype=np.int64, count=len(changed))
    keep = ~(np.isin(prev.src, changed_arr) | np.isin(prev.dst, changed_arr))
    gids: list[int] = []
    src: list[int] = []
    dst: list[int] = []
    types: list[int] = []
    raw: list[list] = [[] for _ in props]
    seen = 0
    for gid in sorted(changed):
        vertex = storage._vertices.get(gid)
        if vertex is None:
            continue
        # raw MVCC state, not VertexAccessor.out_edges/in_edges: those
        # apply the session's fine-grained filter, and a shared snapshot
        # holds what export_edges holds whoever built it
        st = accessor._vertex_state(vertex, view)
        for entries, outgoing in ((st.out_edges, True),
                                  (st.in_edges, False)):
            for (_etype, _other, edge) in entries:
                if abort_check is not None and (seen & 0x1FFF) == 0:
                    abort_check()
                seen += 1
                if not outgoing and edge.from_vertex.gid in changed:
                    continue            # its changed source emits it
                est = accessor._edge_state(edge, view)
                if not est.exists or est.deleted:
                    continue
                gids.append(edge.gid)
                src.append(edge.from_vertex.gid)
                dst.append(edge.to_vertex.gid)
                types.append(edge.edge_type)
                for j, pid in enumerate(prop_ids):
                    raw[j].append(None if pid is None
                                  else est.properties.get(pid))
    no_rows = np.zeros(0, dtype=np.int64)
    columns = {}
    for j, p in enumerate(props):
        col = _column_patched(prev.columns[p], no_rows, keep, raw[j])
        if col is None:
            return None
        columns[p] = col

    def spliced(old, fresh):
        return np.concatenate([old[keep], np.asarray(fresh, dtype=old.dtype)])
    out = spliced(prev.gids, gids)
    return EdgeSnapshot(n=len(out), gids=out, src=spliced(prev.src, src),
                        dst=spliced(prev.dst, dst),
                        type_ids=spliced(prev.type_ids, types),
                        columns=columns)


def fill_columns(snap, accessor, props: tuple[str, ...], view,
                 abort_check=None) -> dict:
    """Properties `props` of the rows `snap` already has, read by gid in
    the snapshot's own row order: a patched snapshot's rows are not in
    storage order, so a second sweep would not align with them."""
    storage = accessor.storage
    prop_ids = [storage.property_mapper.maybe_name_to_id(p) for p in props]
    if isinstance(snap, EdgeSnapshot):
        objects = storage._edges
        state = accessor._edge_state
    else:
        objects = storage._vertices

        def state(vertex, view):
            return accessor._vertex_state(vertex, view, need_edges=False)
    raw: list[list] = [[] for _ in props]
    for i, gid in enumerate(snap.gids.tolist()):
        if abort_check is not None and (i & 0x1FFF) == 0:
            abort_check()
        # a row of an MVCC view's snapshot is visible to it, so its
        # object is in the store (GC waits for the oldest reader); the
        # analytical mode's live view may have lost it since
        obj = objects.get(gid)
        pd = state(obj, view).properties if obj is not None else {}
        for j, pid in enumerate(prop_ids):
            raw[j].append(None if pid is None else pd.get(pid))
    return {p: _typed(raw[j]) for j, p in enumerate(props)}


class ColumnarCache:
    """Per-storage cache of columnar snapshots keyed by (version, table),
    under GraphCache's contract (ops/csr.py).

    The version is the TRANSACTION's topology snapshot, captured under
    the engine lock together with its start timestamp: what a
    snapshot-isolation reader with no writes of its own exports is the
    committed state at that version whatever commits land meanwhile, so
    it is always stored, and a reader that began before the newest
    commit is served at its own version. Own uncommitted writes, weaker
    isolation levels and fine-grained views bypass the cache.

    A miss with another version's entry of the same table is served by
    patching that entry with the change log's vertices (patch_columns,
    patch_edges), read through the missing reader's own view, when the
    log can say what changed between the two and the change set is
    small against the table; anything else is the sweep. The base is
    as a rule the entry the request before left; for a reader that
    began before the newest entry's version it is that newer entry.
    """

    def __init__(self) -> None:
        import weakref
        self._lock = threading.Lock()
        self._cache: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()
        self._patch_failed: set = set()     # tables warned about, once each

    def _cacheable(self, accessor) -> bool:
        if getattr(accessor, "fine_grained", None) is not None:
            # label-restricted view: never share via the plain cache
            return False
        txn = accessor.txn
        if txn is None:
            return True
        if getattr(txn, "deltas", None):
            return False
        # a committed transaction's view advanced to its commit ts
        # (effective_start_ts), which no topology snapshot names
        if txn.commit_ts is not None:
            return False
        # READ_COMMITTED / READ_UNCOMMITTED resolve visibility against the
        # *live* latest commit ts, so a commit landing mid-sweep yields a
        # mixed snapshot that must never be shared under a version key.
        return txn.isolation is IsolationLevel.SNAPSHOT_ISOLATION

    def _get_cached(self, accessor, table, props, sweep, patch, view,
                    abort_check):
        """Shared skeleton for vertex and edge snapshots: the entry at
        the accessor's version, refreshed from the nearest entry of
        `table` on a miss, with column-level sharing — a later query
        needing extra properties reads only the missing columns, by gid,
        in the entry's row order."""
        storage = accessor.storage
        # analytical mode has no MVCC: a reader sees the live state, and
        # every write bumps the version as it lands
        version = (storage.topology_version
                   if getattr(accessor, "_analytical", False)
                   else accessor.topology_snapshot)
        key = (version, table)
        with self._lock:
            per = self._cache.get(storage) or {}
            entry = per.get(key)
            # the table's entry nearest in version, on either side: the
            # rows of the vertices that did not change between the two
            # are the same read forwards or backwards
            base = None if entry is not None else min(
                ((k[0], v) for k, v in per.items() if k[1] == table),
                key=lambda kv: abs(kv[0] - version), default=None)
        if entry is None:
            snap = self._refreshed(storage, table, base, version, sweep,
                                   patch)
            entry = self._stored(storage, key, snap)
        missing = tuple(p for p in props if p not in entry.columns)
        if missing:
            filled = fill_columns(entry, accessor, missing, view,
                                  abort_check)
            with self._lock:
                for p in missing:
                    entry.columns.setdefault(p, filled[p])
        return entry

    def _refreshed(self, storage, table, base, version, sweep, patch):
        from ..storage.storage import (ChangeLogUnknowable,
                                       change_set_is_small)
        snap = None
        if base is not None:
            lo, hi = sorted((base[0], version))
            changed = storage.changes_between(lo, hi)
            # the log counts vertices, so the table it is weighed against
            # is counted in vertices: the edge table's is the store's
            rows = (len(storage._vertices) if table == _EDGES_KEY
                    else base[1].n)
            if isinstance(changed, ChangeLogUnknowable):
                log.info("change log unknowable (%s) for versions "
                         "(%d, %d]; full columnar sweep", changed.reason,
                         lo, hi)
            elif not changed:
                snap = base[1]      # an abort's bump: the same rows
            elif change_set_is_small(len(changed), rows):
                try:
                    snap = patch(base[1], changed)
                except HintedAbortError:
                    raise           # a timeout or TERMINATE, not a doubt
                except Exception:  # noqa: BLE001 — any doubt: the sweep
                    global_metrics.increment(
                        "delta.columnar_patch_failed_total")
                    first = table not in self._patch_failed
                    self._patch_failed.add(table)
                    log.log(logging.WARNING if first else logging.DEBUG,
                            "columnar patch of table %r failed; falling "
                            "back to the sweep", table, exc_info=True)
        if snap is not None:
            global_metrics.increment("delta.columnar_applied_total")
            return snap
        global_metrics.increment("delta.columnar_rebuild_total")
        return sweep()

    def _stored(self, storage, key, snap):
        """`snap` put under `key` unless another thread's build stands
        there already (its row order may differ: one of them serves).
        Of the same table this version and NEWER ones stay (an older
        view must not evict a newer entry), the newest of them as the
        next patch's base; of other tables, what the change log can
        still reach from."""
        version, table = key
        with self._lock:
            per = self._cache.get(storage) or {}
            entry = per.get(key)
            if entry is not None:
                return entry
            reach = storage.oldest_logged_version - 1
            per = {k: v for k, v in per.items()
                   if (k[0] > version if k[1] == table else k[0] >= reach)}
            per[key] = snap
            self._cache[storage] = per
        return snap

    def get(self, accessor, label: str | None, props: tuple[str, ...],
            view, abort_check=None) -> ColumnarSnapshot:
        if not self._cacheable(accessor):
            return export_columns(accessor, label, props, view,
                                  abort_check)
        return self._get_cached(
            accessor, label, props,
            lambda: export_columns(accessor, label, props, view,
                                   abort_check),
            lambda prev, changed: patch_columns(prev, accessor, label,
                                                changed, view, abort_check),
            view, abort_check)

    def get_edges(self, accessor, props: tuple[str, ...], view,
                  abort_check=None) -> EdgeSnapshot:
        """Edge-table analog of get(): cached under (version, _EDGES_KEY)
        with the same MVCC staleness contract."""
        if not self._cacheable(accessor):
            return export_edges(accessor, props, view, abort_check)
        return self._get_cached(
            accessor, _EDGES_KEY, props,
            lambda: export_edges(accessor, props, view, abort_check),
            lambda prev, changed: patch_edges(prev, accessor, changed,
                                              view, abort_check),
            view, abort_check)


_EDGES_KEY = "\x00edges"   # sentinel: no label can collide (labels never contain NUL)

COLUMNAR_CACHE = ColumnarCache()
