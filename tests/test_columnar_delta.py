"""The columnar cache follows the change log (ops/columnar.py): a miss
at a newer version patches the newest older snapshot of the table with
the changed vertices instead of sweeping the store.

The oracle is the sweep: whatever the history, a patched snapshot holds
`export_columns` / `export_edges`' rows through the same accessor, keyed
by gid (the row ORDER may differ), with the same kinds and flags. The
sweep stays reachable, and is what every gap the log cannot answer, or
that is large against the table, takes: counted."""

import sys
import threading
import types

import numpy as np
import pytest

from memgraph_tpu.observability.metrics import global_metrics
from memgraph_tpu.ops.columnar import (ColumnarCache, export_columns,
                                       export_edges)
from memgraph_tpu.storage import InMemoryStorage
from memgraph_tpu.storage.common import IsolationLevel, View

APPLIED = "delta.columnar_applied_total"
REBUILD = "delta.columnar_rebuild_total"


def counters():
    now = {n: v for n, _k, v in global_metrics.snapshot()}
    return now.get(APPLIED, 0), now.get(REBUILD, 0)


def moved(before):
    after = counters()
    return after[0] - before[0], after[1] - before[1]


# --- a snapshot as rows keyed by gid ---------------------------------------

def _cell(col, i):
    if not col.present[i]:
        return None
    if col.kind == "other":
        return "present"
    v = col.values[i]
    if col.kind == "str":
        return {c: s for s, c in col.vocab.items()}[int(v)]
    return {"int": int, "float": float, "bool": bool}[col.kind](v)


def _columns(snap, props):
    """The asked-for columns (a cached entry may hold others' too)."""
    cols = {p: snap.columns[p] for p in props}
    for c in cols.values():
        assert len(c.present) == snap.n
        assert c.values is None or len(c.values) == snap.n
    return cols, {p: (c.kind, c.big, c.mixed,
                      None if c.vocab is None else frozenset(c.vocab))
                  for p, c in cols.items()}


def vertex_rows(snap, props):
    assert snap.n == len(snap.gids) == len(set(snap.gids.tolist()))
    cols, flags = _columns(snap, props)
    return ({int(g): {p: _cell(c, i) for p, c in cols.items()}
             for i, g in enumerate(snap.gids)}, flags)


def edge_rows(snap, props):
    assert snap.n == len(set(snap.gids.tolist()))
    assert len(snap.src) == len(snap.dst) == len(snap.type_ids) == snap.n
    cols, flags = _columns(snap, props)
    return ({int(g): (int(snap.src[i]), int(snap.dst[i]),
                      int(snap.type_ids[i]),
                      {p: _cell(c, i) for p, c in cols.items()})
             for i, g in enumerate(snap.gids)}, flags)


VERTEX_TABLES = [(None, ()), (None, ("x",)), ("L", ()), ("L", ("x",)),
                 ("L", ("x", "k"))]
EDGE_TABLES = [(), ("w",)]


def check_against_the_sweep(storage, cache):
    """Every table of the history through one reader: the cache's answer
    (patched or swept) against the sweep's through the same accessor."""
    acc = storage.access()
    try:
        for label, props in VERTEX_TABLES:
            got = cache.get(acc, label, props, View.OLD)
            want = export_columns(acc, label, props, View.OLD)
            assert vertex_rows(got, props) == vertex_rows(want, props), \
                (label, props)
        for props in EDGE_TABLES:
            got = cache.get_edges(acc, props, View.OLD)
            want = export_edges(acc, props, View.OLD)
            assert edge_rows(got, props) == edge_rows(want, props), props
    finally:
        acc.abort()


# --- the seeded history -----------------------------------------------------

class History:
    """Random committed (and one kind of aborted) steps over a small
    graph; every value kind `_classify` tells apart passes through the
    properties `k` and `w` (mostly ints, so that their columns change
    kind now and then), while `x` stays an int column."""

    KINDS = [lambda r: int(r.integers(-50, 50))] * 12 + [
             lambda r: float(r.random() * 10 - 5),
             lambda r: str(r.choice(["red", "green", "blue", "teal"])),
             lambda r: [1, 2],
             lambda r: bool(r.integers(0, 2)),
             lambda r: 2**53 + 1 + int(r.integers(0, 9)),
             lambda r: None]

    def __init__(self, seed, wal_sink=None):
        self.rng = np.random.default_rng(seed)
        self.s = s = InMemoryStorage()
        s.wal_sink = wal_sink       # every commit's frame, the first too
        self.L = s.label_mapper.name_to_id("L")
        self.M = s.label_mapper.name_to_id("M")
        self.x = s.property_mapper.name_to_id("x")
        self.k = s.property_mapper.name_to_id("k")
        self.w = s.property_mapper.name_to_id("w")
        self.T = s.edge_type_mapper.name_to_id("T")
        self.U = s.edge_type_mapper.name_to_id("U")
        self.vertices: list[int] = []
        self.edges: list[int] = []
        with s.access() as acc:
            vs = [self._new_vertex(acc) for _ in range(120)]
            for _ in range(400):
                a, b = self.rng.integers(0, len(vs), 2)
                self._new_edge(acc, vs[a], vs[b])
            acc.commit()

    def _new_vertex(self, acc):
        va = acc.create_vertex()
        if self.rng.integers(0, 3):
            va.add_label(self.L)
        if self.rng.integers(0, 4) == 0:
            va.add_label(self.M)
        if self.rng.integers(0, 5):
            va.set_property(self.x, int(self.rng.integers(-50, 50)))
        self.vertices.append(va.gid)
        return va

    def _new_edge(self, acc, a, b):
        ea = acc.create_edge(a, b, self.T if self.rng.integers(0, 3)
                             else self.U)
        if self.rng.integers(0, 2):
            ea.set_property(self.w, int(self.rng.integers(0, 9)))
        self.edges.append(ea.gid)
        return ea

    def _vertex(self, acc):
        while self.vertices:
            gid = self.vertices[int(self.rng.integers(0, len(self.vertices)))]
            va = acc.find_vertex(gid, View.NEW)
            if va is not None:
                return va
            self.vertices.remove(gid)
        return self._new_vertex(acc)

    def _edge(self, acc):
        while self.edges:
            gid = self.edges[int(self.rng.integers(0, len(self.edges)))]
            ea = acc.find_edge(gid, View.NEW)
            if ea is not None:
                return ea
            self.edges.remove(gid)
        return self._new_edge(acc, self._vertex(acc), self._vertex(acc))

    def op(self, acc, name):
        r = self.rng
        if name == "create_vertex":
            va = self._new_vertex(acc)
            self._new_edge(acc, va, self._vertex(acc))
        elif name == "delete_vertex":
            acc.delete_vertex(self._vertex(acc), detach=True)
        elif name == "add_label":
            self._vertex(acc).add_label(self.L)
        elif name == "remove_label":
            self._vertex(acc).remove_label(self.L)
        elif name == "set_x":
            self._vertex(acc).set_property(self.x, int(r.integers(-50, 50)))
        elif name == "set_x_big":
            self._vertex(acc).set_property(self.x, 2**53 + 7)
        elif name == "remove_x":
            self._vertex(acc).set_property(self.x, None)
        elif name == "set_k":
            make = self.KINDS[int(r.integers(0, len(self.KINDS)))]
            self._vertex(acc).set_property(self.k, make(r))
        elif name == "create_edge":
            self._new_edge(acc, self._vertex(acc), self._vertex(acc))
        elif name == "delete_edge":
            acc.delete_edge(self._edge(acc))
        elif name == "set_w":
            make = self.KINDS[int(r.integers(0, len(self.KINDS)))]
            self._edge(acc).set_property(self.w, make(r))
        else:
            raise AssertionError(name)

    OPS = ["create_vertex", "delete_vertex", "add_label", "remove_label",
           "set_x", "set_x", "set_x_big", "remove_x", "set_k", "set_k",
           "create_edge", "delete_edge", "set_w"]

    def step(self, abort=False):
        acc = self.s.access()
        for _ in range(int(self.rng.integers(1, 4))):
            self.op(acc, self.OPS[int(self.rng.integers(0, len(self.OPS)))])
        acc.abort() if abort else acc.commit()

    def bulk(self, records):
        """One commit that touches more vertices than the threshold
        (1,024, or a fifth of a larger table)."""
        with self.s.access() as acc:
            vs = [self._new_vertex(acc) for _ in range(3 * records // 4)]
            for i in range(records - len(vs)):
                self._new_edge(acc, vs[i % len(vs)], vs[(i * 7) % len(vs)])
            acc.commit()


@pytest.mark.parametrize("seed", [3, 2_147_483_929, 77])
def test_patched_snapshots_hold_the_sweeps_rows(seed):
    h = History(seed)
    cache = ColumnarCache()
    check_against_the_sweep(h.s, cache)           # the first entries: swept
    start = counters()
    for i in range(60):
        h.step(abort=(i % 9 == 4))
        check_against_the_sweep(h.s, cache)
    applied, rebuilt = moved(start)
    # three tables (all vertices, :L, edges) met sixty new versions; a
    # kind change, or a value gone from a coerced or mixed-kind column,
    # took the sweep, the rest were patches
    assert applied + rebuilt == 180
    assert applied > rebuilt, (applied, rebuilt)

    before = counters()
    h.bulk(2_000)
    check_against_the_sweep(h.s, cache)
    applied, rebuilt = moved(before)
    assert applied == 0
    assert rebuilt == 3
    for _ in range(5):                              # and patches again
        h.step()
        check_against_the_sweep(h.s, cache)
    assert moved(before)[0] > 0


def _one_commit(h):
    with h.s.access() as acc:
        h.op(acc, "set_x")
        h.op(acc, "create_edge")
        acc.commit()


@pytest.mark.parametrize("gap", ["log_wrapped", "untracked_bump"])
def test_a_gap_the_log_cannot_answer_takes_the_sweep(gap):
    h = History(5)
    cache = ColumnarCache()
    check_against_the_sweep(h.s, cache)
    if gap == "log_wrapped":
        for _ in range(1_100):                      # the log holds 1,024
            h.s._bump_topology(frozenset())
    else:
        h.s._bump_topology(None)        # recovery, a replica's snapshot load
    _one_commit(h)
    before = counters()
    acc = h.s.access()
    try:
        got = cache.get(acc, "L", ("x",), View.OLD)
        assert moved(before) == (0, 1)
        assert vertex_rows(got, ("x",)) == vertex_rows(
            export_columns(acc, "L", ("x",), View.OLD), ("x",))
        got = cache.get_edges(acc, ("w",), View.OLD)
        assert moved(before) == (0, 2)
        assert edge_rows(got, ("w",)) == edge_rows(
            export_edges(acc, ("w",), View.OLD), ("w",))
    finally:
        acc.abort()
    _one_commit(h)                                  # knowable again
    before = counters()
    check_against_the_sweep(h.s, cache)
    assert moved(before)[0] >= 2


def _follower(kind):
    """A store that only applies shipped WAL frames, with the caller
    that bumps its topology: a replica, or a shard move's target."""
    storage = InMemoryStorage()
    if kind == "replica":
        from memgraph_tpu.replication.replica import ReplicaServer
        return storage, ReplicaServer(storage, port=0)._apply_wal_frame
    from memgraph_tpu.sharding.worker import _WorkerState
    state = types.SimpleNamespace(storage=storage, needs_snapshot=False)
    return storage, lambda frame: _WorkerState.apply_frame(state, frame)


@pytest.mark.parametrize("kind", ["replica", "shard_worker"])
def test_a_wal_frame_applied_on_a_follower_names_what_it_changed(kind):
    """A follower's apply logs the gids `_apply_wal_txn` returns, not
    None: an edge-property record has to name its edge's endpoints, or
    the edge table is patched around the row and serves the old value."""
    frames: list = []
    h = History(31, wal_sink=lambda frame, ts: frames.append(frame))
    storage, apply = _follower(kind)
    cache = ColumnarCache()

    def ship():
        while frames:
            apply(frames.pop(0))
    ship()
    check_against_the_sweep(storage, cache)         # the first entries: swept
    with h.s.access() as acc:                       # an edge property alone
        ea = next(ea for ea in acc.edges(View.NEW)
                  if isinstance(ea.properties(View.NEW).get(h.w), int))
        changed_edge = ea.gid
        ea.set_property(h.w, 4_242)
        acc.commit()
    version = storage.topology_version
    ship()
    edge = storage._edges[changed_edge]
    assert storage.changes_between(version, storage.topology_version) == {
        edge.from_vertex.gid, edge.to_vertex.gid}
    before = counters()
    check_against_the_sweep(storage, cache)
    assert moved(before) == (3, 0)                  # all, :L, edges: patched
    acc = storage.access()
    try:
        got = cache.get_edges(acc, ("w",), View.OLD)
        row = int(np.flatnonzero(got.gids == changed_edge)[0])
        assert int(got.columns["w"].values[row]) == 4_242
    finally:
        acc.abort()
    for _ in range(25):                             # and every other record
        h.step()
        ship()
        check_against_the_sweep(storage, cache)
    assert moved(before)[0] > moved(before)[1]


def test_the_edge_tables_threshold_counts_vertices_as_the_log_does():
    """1,100 changed vertices are few against 8,000 edge rows and many
    against the 1,300 vertices that own them: the sweep."""
    s = InMemoryStorage()
    x = s.property_mapper.name_to_id("x")
    t = s.edge_type_mapper.name_to_id("T")
    with s.access() as acc:
        vs = [acc.create_vertex() for _ in range(1_300)]
        for i in range(8_000):
            acc.create_edge(vs[i % 1_300], vs[(i * 7 + 1) % 1_300], t)
        acc.commit()
    cache = ColumnarCache()
    with s.access() as acc:
        assert cache.get_edges(acc, (), View.OLD).n == 8_000
    with s.access() as acc:
        for va in list(acc.vertices(View.NEW))[:1_100]:
            va.set_property(x, 1)
        acc.commit()
    before = counters()
    with s.access() as acc:
        got = cache.get_edges(acc, (), View.OLD)
        assert moved(before) == (0, 1)
        assert edge_rows(got, ()) == edge_rows(
            export_edges(acc, (), View.OLD), ())
    with s.access() as acc:                         # 1,000: a patch again
        for va in list(acc.vertices(View.NEW))[:1_000]:
            va.set_property(x, 2)
        acc.commit()
    with s.access() as acc:
        got = cache.get_edges(acc, (), View.OLD)
        assert moved(before) == (1, 1)
        assert edge_rows(got, ()) == edge_rows(
            export_edges(acc, (), View.OLD), ())


def test_a_query_abort_inside_a_patch_is_the_querys_and_not_a_sweep():
    from memgraph_tpu.exceptions import HintedAbortError
    h = History(33)
    cache = ColumnarCache()
    check_against_the_sweep(h.s, cache)
    _one_commit(h)

    def timed_out():
        raise HintedAbortError("query timed out")
    before = counters()
    acc = h.s.access()
    try:
        with pytest.raises(HintedAbortError):
            cache.get(acc, None, ("x",), View.OLD, abort_check=timed_out)
        with pytest.raises(HintedAbortError):
            cache.get_edges(acc, ("w",), View.OLD, abort_check=timed_out)
        assert moved(before) == (0, 0)
        check = []
        got = cache.get_edges(acc, ("w",), View.OLD,
                              abort_check=lambda: check.append(1))
        assert check and moved(before) == (1, 0)
        assert edge_rows(got, ("w",)) == edge_rows(
            export_edges(acc, ("w",), View.OLD), ("w",))
    finally:
        acc.abort()


def test_a_patch_that_raises_is_swept_counted_and_warned_of_once(
        monkeypatch, caplog):
    from memgraph_tpu.ops import columnar
    failed = "delta.columnar_patch_failed_total"

    def n_failed():
        return {n: v for n, _k, v in global_metrics.snapshot()}.get(failed, 0)

    def broken(*args, **kwargs):
        raise IndexError("index 7 is out of bounds")
    h = History(35)
    cache = ColumnarCache()
    check_against_the_sweep(h.s, cache)
    monkeypatch.setattr(columnar, "patch_edges", broken)
    start = n_failed()
    with caplog.at_level("WARNING", logger=columnar.__name__):
        for i in range(2):
            _one_commit(h)
            before = counters()
            acc = h.s.access()
            try:
                got = cache.get_edges(acc, ("w",), View.OLD)
                assert moved(before) == (0, 1)
                assert n_failed() - start == i + 1
                assert edge_rows(got, ("w",)) == edge_rows(
                    export_edges(acc, ("w",), View.OLD), ("w",))
            finally:
                acc.abort()
    warned = [r for r in caplog.records if "columnar patch" in r.getMessage()]
    assert len(warned) == 1 and warned[0].exc_info is not None


def test_a_property_first_asked_for_after_a_patch_aligns_with_its_rows():
    h = History(9)
    cache = ColumnarCache()
    with h.s.access() as acc:
        cache.get(acc, "L", (), View.OLD)
        cache.get_edges(acc, (), View.OLD)
    with h.s.access() as acc:                       # moves rows about
        acc.delete_vertex(h._vertex(acc), detach=True)
        h.op(acc, "create_vertex")
        # the first vertex outside :L joins it: the patch appends its
        # row, the sweep meets it early
        next(va for va in acc.vertices(View.NEW)
             if not va.has_label(h.L, View.NEW)).add_label(h.L)
        acc.commit()
    before = counters()
    acc = h.s.access()
    try:
        bare = cache.get(acc, "L", (), View.OLD)
        bare_edges = cache.get_edges(acc, (), View.OLD)
        assert moved(before) == (2, 0)
        want = export_columns(acc, "L", ("x",), View.OLD)
        assert not np.array_equal(bare.gids, want.gids)   # another order
        got = cache.get(acc, "L", ("x",), View.OLD)
        assert got is bare                          # filled, not rebuilt
        assert vertex_rows(got, ("x",)) == vertex_rows(want, ("x",))
        got = cache.get_edges(acc, ("w",), View.OLD)
        assert got is bare_edges
        assert edge_rows(got, ("w",)) == edge_rows(
            export_edges(acc, ("w",), View.OLD), ("w",))
        assert moved(before) == (2, 0)
    finally:
        acc.abort()


def test_a_snapshot_in_a_readers_hands_is_never_written():
    h = History(13)
    cache = ColumnarCache()
    old_acc = h.s.access()
    old = cache.get(old_acc, None, ("x",), View.OLD)
    old_edges = cache.get_edges(old_acc, ("w",), View.OLD)
    held = (vertex_rows(old, ("x",)), edge_rows(old_edges, ("w",)))
    arrays = [old.gids, old.columns["x"].values, old.columns["x"].present,
              old_edges.gids, old_edges.src, old_edges.dst,
              old_edges.type_ids, old_edges.columns["w"].present]
    copies = [a.copy() for a in arrays]
    for _ in range(10):
        h.step()
        check_against_the_sweep(h.s, cache)
    for a, c in zip(arrays, copies):
        assert np.array_equal(a, c)
    assert (vertex_rows(old, ("x",)), edge_rows(old_edges, ("w",))) == held
    old_acc.abort()


def test_an_older_reader_is_served_at_its_version_and_evicts_nothing():
    h = History(17)
    cache = ColumnarCache()
    check_against_the_sweep(h.s, cache)
    old_acc = h.s.access()                          # begins here
    for _ in range(3):
        _one_commit(h)
    new_acc = h.s.access()
    assert old_acc.topology_snapshot < new_acc.topology_snapshot
    assert cache._cacheable(old_acc)
    try:
        newest = cache.get(new_acc, None, ("x",), View.OLD)
        newest_edges = cache.get_edges(new_acc, ("w",), View.OLD)
        # the entries older than the newest went with its store: the
        # older reader's base is the NEWER entry, patched backwards
        assert {version for version, table in cache._cache[h.s]
                if table != "L"} == {new_acc.topology_snapshot}
        before = counters()
        got = cache.get(old_acc, None, ("x",), View.OLD)
        got_edges = cache.get_edges(old_acc, ("w",), View.OLD)
        assert moved(before) == (2, 0)
        assert vertex_rows(got, ("x",)) == vertex_rows(
            export_columns(old_acc, None, ("x",), View.OLD), ("x",))
        assert edge_rows(got_edges, ("w",)) == edge_rows(
            export_edges(old_acc, ("w",), View.OLD), ("w",))
        assert vertex_rows(got, ("x",)) != vertex_rows(newest, ("x",))
        # stored under its own version, beside the newer entries
        assert cache.get(old_acc, None, ("x",), View.OLD) is got
        assert cache.get(new_acc, None, ("x",), View.OLD) is newest
        assert cache.get_edges(new_acc, ("w",), View.OLD) is newest_edges
    finally:
        old_acc.abort()
        new_acc.abort()


class _AllowAll:
    def can_read_vertex(self, labels):
        return True

    def can_read_edge(self, edge_type):
        return True


@pytest.mark.parametrize("private", ["own_deltas", "read_committed",
                                     "fine_grained", "committed"])
def test_views_that_are_no_committed_version_bypass_the_cache(private):
    h = History(21)
    cache = ColumnarCache()
    acc = h.s.access(IsolationLevel.READ_COMMITTED
                     if private == "read_committed" else None)
    if private in ("own_deltas", "committed"):
        va = acc.create_vertex()
        va.add_label(h.L)
        va.set_property(h.x, 424242)
    if private == "committed":
        acc.commit()            # its view advanced to its commit ts
    if private == "fine_grained":
        acc.fine_grained = _AllowAll()
    before = counters()
    assert not cache._cacheable(acc)
    view = View.NEW
    got = cache.get(acc, "L", ("x",), view)
    assert cache.get(acc, "L", ("x",), view) is not got
    assert vertex_rows(got, ("x",)) == vertex_rows(
        export_columns(acc, "L", ("x",), view), ("x",))
    if private in ("own_deltas", "committed"):
        assert got.columns["x"].values.max() == 424242
    got = cache.get_edges(acc, ("w",), view)
    assert edge_rows(got, ("w",)) == edge_rows(
        export_edges(acc, ("w",), view), ("w",))
    assert moved(before) == (0, 0)
    assert not cache._cache.get(h.s)
    acc.abort()


def _hop_op(edge_types, hops):
    from memgraph_tpu.query.plan.lane import LaneHopCount
    return LaneHopCount(
        input=None, fallback=None, source=("all",), src_label=None,
        src_preds=[], mid_label=None, mid_preds=[], dst_label=None,
        dst_preds=[], direction="out", edge_types=edge_types, hops=hops,
        include_lower=False, edge_unique=True, row_aggs=["c"],
        distinct_aggs=[])


def test_eight_threads_stage_one_fresh_snapshot_without_a_torn_read():
    """D13: `_lane_order` was published before `_lane_sorted`, and a
    second thread that saw the first read the second too early."""
    h = History(25)
    op = _hop_op(["T"], 2)
    ctx = types.SimpleNamespace(storage=h.s)
    failures: list = []
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for _ in range(40):
            acc = h.s.access()
            full = export_columns(acc, None, (), View.OLD)
            edges = export_edges(acc, (), View.OLD)
            acc.abort()
            barrier = threading.Barrier(8)
            results: list = []

            def stage():
                try:
                    barrier.wait(timeout=30)
                    out = op._endpoints(ctx, full, edges)
                    results.append((out[2].copy(), out[3].copy(),
                                    out[4].copy()))
                except Exception as e:  # noqa: BLE001 — the assertion
                    failures.append(repr(e))
            threads = [threading.Thread(target=stage) for _ in range(8)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
                assert not t.is_alive()
            assert not failures, failures
            assert len(results) == 8
            for s_idx, d_idx, emask in results[1:]:
                assert np.array_equal(s_idx, results[0][0])
                assert np.array_equal(d_idx, results[0][1])
                assert np.array_equal(emask, results[0][2])
            # the rows are the vertex table's: gid for gid
            ok = results[0][2]
            assert np.array_equal(full.gids[results[0][0][ok]],
                                  edges.src[ok])
    finally:
        sys.setswitchinterval(interval)


def test_endpoint_rows_follow_the_vertex_table_they_index():
    """Two builds of one version's vertex table (a patch, a sweep) order
    their rows differently: the rows cached on the edge snapshot are
    recomputed against the table in hand."""
    h = History(29)
    op = _hop_op(None, 1)
    ctx = types.SimpleNamespace(storage=h.s)
    acc = h.s.access()
    edges = export_edges(acc, (), View.OLD)
    full = export_columns(acc, None, (), View.OLD)
    other = type(full)(n=full.n, gids=full.gids[::-1].copy())
    acc.abort()
    for table in (full, other, full):
        _, _, s_idx, d_idx, emask, _, _ = op._endpoints(ctx, table, edges)
        assert emask.all()
        assert np.array_equal(table.gids[s_idx], edges.src)
        assert np.array_equal(table.gids[d_idx], edges.dst)
