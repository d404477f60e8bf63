"""Incremental CSR export (ops/csr.export_csr_delta): splicing changed
vertices' edges into the previous snapshot must produce EXACTLY the
arrays a full export produces — adds, removes, weight changes, filter
views, vertices that join the view — and the fall-back-to-full
conditions (a vertex that leaves it)."""

import numpy as np
import pytest

from memgraph_tpu.ops.csr import GraphCache, export_csr, export_csr_delta
from memgraph_tpu.storage import InMemoryStorage, StorageConfig, StorageMode


def _graphs_equal(a, b):
    for field in ("row_ptr", "col_idx", "src_idx", "weights",
                  "csc_src", "csc_dst", "csc_weights", "out_degree"):
        if not np.array_equal(np.asarray(getattr(a, field)),
                              np.asarray(getattr(b, field))):
            return field
    if not np.array_equal(a.node_gids, b.node_gids):
        return "node_gids"
    return None


@pytest.fixture
def setup():
    storage = InMemoryStorage(StorageConfig(
        storage_mode=StorageMode.IN_MEMORY_TRANSACTIONAL))
    rng = np.random.default_rng(5)
    n, e = 400, 2500
    acc = storage.access()
    et = storage.edge_type_mapper.name_to_id("E")
    vs = [acc.create_vertex() for _ in range(n)]
    for s, d in zip(rng.integers(0, n, e), rng.integers(0, n, e)):
        acc.create_edge(vs[s], vs[d], et)
    acc.commit()
    return storage, vs, et, n


def _mutate(storage, vs, et, rng, adds=30, removes=10):
    from memgraph_tpu.storage.storage import EdgeAccessor
    acc = storage.access()
    for _ in range(adds):
        acc.create_edge(vs[int(rng.integers(0, len(vs)))],
                        vs[int(rng.integers(0, len(vs)))], et)
    removed = 0
    for ve in list(storage._edges.values()):
        if removed >= removes:
            break
        ea = EdgeAccessor(ve, acc)
        if ea.is_visible():
            acc.delete_edge(ea)
            removed += 1
    acc.commit()


def test_delta_export_equals_full(setup):
    storage, vs, et, n = setup
    v0 = storage.topology_version
    acc = storage.access()
    prev = export_csr(acc, to_device=False)
    acc.abort()
    rng = np.random.default_rng(0)
    _mutate(storage, vs, et, rng)
    changed = storage.changes_between(v0, storage.topology_version)
    assert changed
    acc = storage.access()
    got = export_csr_delta(prev, acc, changed, to_device=False)
    want = export_csr(acc, to_device=False)
    acc.abort()
    assert got is not None
    assert _graphs_equal(got, want) is None


def test_delta_export_weighted(setup):
    storage, vs, et, n = setup
    wprop = storage.property_mapper.name_to_id("w")
    from memgraph_tpu.storage.storage import EdgeAccessor
    acc = storage.access()
    for ve in list(storage._edges.values())[:100]:
        EdgeAccessor(ve, acc).set_property(wprop, 2.5)
    acc.commit()
    v0 = storage.topology_version
    acc = storage.access()
    prev = export_csr(acc, weight_property=wprop, to_device=False)
    acc.abort()
    # weight change on one edge
    acc = storage.access()
    victim = next(iter(storage._edges.values()))
    EdgeAccessor(victim, acc).set_property(wprop, 9.0)
    acc.commit()
    changed = storage.changes_between(v0, storage.topology_version)
    acc = storage.access()
    got = export_csr_delta(prev, acc, changed, weight_property=wprop,
                           to_device=False)
    want = export_csr(acc, weight_property=wprop, to_device=False)
    acc.abort()
    assert got is not None
    assert _graphs_equal(got, want) is None
    assert 9.0 in np.asarray(got.weights)


def _snapshot(storage, **view):
    acc = storage.access()
    prev = export_csr(acc, to_device=False, **view)
    acc.abort()
    return storage.topology_version, prev


def _delta_and_full(storage, v0, prev, **view):
    """(delta export, full export) of the newest committed state, both
    on ONE accessor, from the change log's answer for the gap."""
    changed = storage.changes_between(v0, storage.topology_version)
    assert changed
    acc = storage.access()
    got = export_csr_delta(prev, acc, changed, to_device=False, **view)
    want = export_csr(acc, to_device=False, **view)
    acc.abort()
    return got, want


def _gid_edges(g):
    """The graph as a sorted multiset of (src gid, dst gid, weight):
    what two exports agree on where their dense orders differ."""
    src, dst, w = g.host_coo
    rows = zip(g.node_gids[src].tolist(), g.node_gids[dst].tolist(),
               np.asarray(w).tolist())
    return sorted(g.node_gids.tolist()), sorted(rows)


def _assert_well_formed(g):
    """The padded arrays say what host_coo and node_gids say."""
    assert g.n_nodes == len(g.node_gids) == len(g.gid_to_idx)
    assert all(g.gid_to_idx[int(gid)] == i
               for i, gid in enumerate(g.node_gids))
    assert g.n_pad > g.n_nodes and g.e_pad >= g.n_edges
    src, dst, _w = g.host_coo
    assert len(src) == g.n_edges
    assert np.array_equal(np.bincount(src, minlength=g.n_nodes),
                          np.asarray(g.out_degree)[:g.n_nodes])
    assert np.asarray(g.row_ptr)[g.n_nodes] == g.n_edges


def test_delta_export_follows_new_vertex(setup):
    storage, vs, et, n = setup
    v0, prev = _snapshot(storage)
    acc = storage.access()
    nv = acc.create_vertex()
    acc.create_edge(nv, vs[0], et)
    acc.commit()
    got, want = _delta_and_full(storage, v0, prev)
    assert got is not None    # the vertex joins: no full export
    assert got.n_nodes == n + 1 and got.node_gids[-1] == nv.gid
    assert _graphs_equal(got, want) is None


def test_new_vertex_with_out_and_in_edges(setup):
    storage, vs, et, n = setup
    v0, prev = _snapshot(storage)
    acc = storage.access()
    nv = acc.create_vertex()
    for k in (1, 2, 3):
        acc.create_edge(nv, vs[k], et)
    for k in (3, 4):
        acc.create_edge(vs[k], nv, et)
    acc.create_edge(nv, nv, et)           # and a self-loop
    acc.commit()
    got, want = _delta_and_full(storage, v0, prev)
    assert got is not None
    assert got.n_edges == prev.n_edges + 6
    assert _graphs_equal(got, want) is None
    _assert_well_formed(got)


def test_several_new_vertices_with_an_edge_between_them(setup):
    storage, vs, et, n = setup
    v0, prev = _snapshot(storage)
    acc = storage.access()
    a, b, c = (acc.create_vertex() for _ in range(3))
    acc.create_edge(a, b, et)             # between two that join
    acc.create_edge(c, a, et)
    acc.create_edge(b, vs[7], et)
    acc.create_edge(vs[8], c, et)
    acc.commit()
    # a second commit of the same gap: one more vertex, no edge at all
    acc = storage.access()
    lone = acc.create_vertex()
    acc.commit()
    got, want = _delta_and_full(storage, v0, prev)
    assert got is not None
    assert got.node_gids[-4:].tolist() == [a.gid, b.gid, c.gid, lone.gid]
    assert _graphs_equal(got, want) is None
    _assert_well_formed(got)


def test_one_insert_with_eight_edges_and_a_property_change(setup):
    """The retrieval cell's gap: a document with 8 edges comes in, and
    another vertex's property (its embedding) is replaced."""
    storage, vs, et, n = setup
    prop = storage.property_mapper.name_to_id("embedding")
    v0, prev = _snapshot(storage)
    acc = storage.access()
    nv = acc.create_vertex()
    nv.set_property(prop, [0.25, 0.5])
    for k in range(10, 18):
        acc.create_edge(nv, vs[k], et)
    acc.commit()
    acc = storage.access()
    acc.find_vertex(vs[200].gid).set_property(prop, [1.0, 0.0])
    acc.commit()
    changed = storage.changes_between(v0, storage.topology_version)
    assert {nv.gid, vs[200].gid} <= changed and len(changed) == 10
    got, want = _delta_and_full(storage, v0, prev)
    assert got is not None
    assert _graphs_equal(got, want) is None


def test_vertex_that_gains_the_label_brings_its_edges(setup):
    storage, vs, et, n = setup
    label = storage.label_mapper.name_to_id("Doc")
    acc = storage.access()
    for v in vs[:300]:
        acc.find_vertex(v.gid).add_label(label)
    late = acc.create_vertex()            # the youngest: joins in place
    acc.create_edge(late, vs[5], et)
    acc.create_edge(vs[6], late, et)
    acc.create_edge(late, vs[350], et)    # to a vertex outside the view
    acc.commit()
    v0, prev = _snapshot(storage, label_filter=label)
    assert prev.n_nodes == 300 and late.gid not in prev.gid_to_idx
    acc = storage.access()
    acc.find_vertex(late.gid).add_label(label)
    acc.commit()
    got, want = _delta_and_full(storage, v0, prev, label_filter=label)
    # the edge to the unlabelled vertex is an endpoint of neither view
    assert got is None
    acc = storage.access()
    for ve in list(storage._edges.values()):
        if ve.from_vertex.gid == late.gid and ve.to_vertex.gid == vs[350].gid:
            from memgraph_tpu.storage.storage import EdgeAccessor
            acc.delete_edge(EdgeAccessor(ve, acc))
    acc.commit()
    got, want = _delta_and_full(storage, v0, prev, label_filter=label)
    assert got is not None and got.n_nodes == 301
    assert got.n_edges == prev.n_edges + 2
    assert _graphs_equal(got, want) is None
    # an older vertex gains the label: export_csr puts it in the middle,
    # the delta at the end; the same graph under node_gids
    v1, prev1 = storage.topology_version, got
    acc = storage.access()
    acc.find_vertex(vs[350].gid).add_label(label)
    acc.commit()
    got, want = _delta_and_full(storage, v1, prev1, label_filter=label)
    assert got is not None and got.node_gids[-1] == vs[350].gid
    assert _gid_edges(got) == _gid_edges(want)
    assert got.n_edges > prev1.n_edges    # the edges it already had
    _assert_well_formed(got)


def test_changed_vertex_outside_the_label_view_is_skipped(setup):
    storage, vs, et, n = setup
    label = storage.label_mapper.name_to_id("Doc")
    prop = storage.property_mapper.name_to_id("p")
    acc = storage.access()
    for v in vs[:300]:
        acc.find_vertex(v.gid).add_label(label)
    lone = acc.create_vertex()            # no label, no edge
    p, q = acc.create_vertex(), acc.create_vertex()
    p.add_label(label)
    q.add_label(label)
    acc.commit()
    v0, prev = _snapshot(storage, label_filter=label)
    acc = storage.access()
    acc.find_vertex(lone.gid).set_property(prop, 1)
    acc.create_edge(acc.find_vertex(p.gid), acc.find_vertex(q.gid), et)
    acc.commit()
    got, want = _delta_and_full(storage, v0, prev, label_filter=label)
    assert got is not None and got.n_nodes == 302
    assert got.n_edges == prev.n_edges + 1
    assert _graphs_equal(got, want) is None


def test_new_vertex_with_weights(setup):
    storage, vs, et, n = setup
    wprop = storage.property_mapper.name_to_id("w")
    v0, prev = _snapshot(storage, weight_property=wprop)
    acc = storage.access()
    nv = acc.create_vertex()
    acc.create_edge(nv, vs[0], et).set_property(wprop, 2.5)
    acc.create_edge(vs[1], nv, et).set_property(wprop, 0.125)
    acc.create_edge(nv, vs[2], et)        # no weight: 1.0
    acc.commit()
    got, want = _delta_and_full(storage, v0, prev, weight_property=wprop)
    assert got is not None
    assert _graphs_equal(got, want) is None
    assert {2.5, 0.125} <= set(np.asarray(got.weights).tolist())


def _delta_counts():
    from memgraph_tpu.observability.metrics import global_metrics
    snap = {name: value for name, _kind, value in global_metrics.snapshot()}
    return (snap.get("delta.export_applied_total", 0),
            snap.get("delta.export_rebuild_total", 0))


def test_chain_of_inserts_through_the_cache_crosses_a_bucket():
    """20 commits, each one new vertex with edges, each CALL's snapshot
    a delta of the one before and equal to a fresh full export; n_pad
    (the bucket of n_nodes + 1) doubles inside the chain."""
    storage = InMemoryStorage(StorageConfig(
        storage_mode=StorageMode.IN_MEMORY_TRANSACTIONAL))
    rng = np.random.default_rng(11)
    acc = storage.access()
    et = storage.edge_type_mapper.name_to_id("E")
    vs = [acc.create_vertex() for _ in range(120)]
    for s, d in zip(rng.integers(0, 120, 600), rng.integers(0, 120, 600)):
        acc.create_edge(vs[s], vs[d], et)
    acc.commit()
    cache = GraphCache()
    acc = storage.access()
    first = cache.get(acc)
    acc.abort()
    assert first.n_pad == 128
    applied0, rebuilt0 = _delta_counts()
    pads = set()
    for step in range(20):
        acc = storage.access()
        nv = acc.create_vertex()
        for k in rng.integers(0, len(vs), 8):
            acc.create_edge(nv, vs[int(k)], et)
        acc.create_edge(vs[step], nv, et)
        acc.commit()
        vs.append(nv)
        acc = storage.access()
        g = cache.get(acc)
        want = export_csr(acc, to_device=False)
        acc.abort()
        assert g.n_nodes == 121 + step
        assert _graphs_equal(g, want) is None, step
        assert _delta_counts() == (applied0 + step + 1, rebuilt0), step
        pads.add(g.n_pad)
    assert pads == {128, 256}
    _assert_well_formed(g)


def _delete_vertex(storage, gid):
    acc = storage.access()
    acc.delete_vertex(acc.find_vertex(gid), detach=True)
    acc.commit()


def _drop_label(storage, gid, label):
    acc = storage.access()
    acc.find_vertex(gid).remove_label(label)
    acc.commit()


@pytest.mark.parametrize("leave", ["deleted", "lost_label"])
def test_vertex_that_leaves_takes_the_full_export(setup, leave):
    storage, vs, et, n = setup
    label = storage.label_mapper.name_to_id("Doc")
    acc = storage.access()
    for v in vs:
        acc.find_vertex(v.gid).add_label(label)
    acc.commit()
    view = {"label_filter": label} if leave == "lost_label" else {}
    cache = GraphCache()
    acc = storage.access()
    prev = cache.get(acc, **view)
    acc.abort()
    v0 = storage.topology_version
    if leave == "deleted":
        _delete_vertex(storage, vs[9].gid)
    else:
        _drop_label(storage, vs[9].gid, label)
    got, want = _delta_and_full(storage, v0, prev, **view)
    assert got is None        # a row less shifts every id behind it
    assert want.n_nodes == n - 1
    applied0, rebuilt0 = _delta_counts()
    acc = storage.access()
    g = cache.get(acc, **view)
    acc.abort()
    assert _graphs_equal(g, want) is None
    assert _delta_counts() == (applied0, rebuilt0 + 1)


def test_vertex_created_and_deleted_inside_the_gap_is_skipped(setup):
    storage, vs, et, n = setup
    v0, prev = _snapshot(storage)
    acc = storage.access()
    ghost = acc.create_vertex()
    acc.create_edge(ghost, vs[0], et)
    acc.create_edge(vs[1], vs[2], et)
    acc.commit()
    _delete_vertex(storage, ghost.gid)
    changed = storage.changes_between(v0, storage.topology_version)
    assert ghost.gid in changed and ghost.gid in storage._vertices
    got, want = _delta_and_full(storage, v0, prev)
    assert got is not None and got.n_nodes == n
    assert got.n_edges == prev.n_edges + 1
    assert _graphs_equal(got, want) is None
    # once the collector has taken it out of storage: any doubt, None
    storage.collect_garbage()
    assert ghost.gid not in storage._vertices
    acc = storage.access()
    assert export_csr_delta(prev, acc, changed, to_device=False) is None
    acc.abort()


def test_two_writers_commit_out_of_creation_order(setup):
    """The older vertex commits second: export_csr walks creation order
    and puts it first, the delta gives it the next index after the
    younger one's. The same graph under node_gids, at every step."""
    storage, vs, et, n = setup
    cache = GraphCache()
    acc = storage.access()
    cache.get(acc)
    acc.abort()
    w1, w2 = storage.access(), storage.access()
    older = w1.create_vertex()
    younger = w2.create_vertex()
    assert older.gid < younger.gid
    w1.create_edge(older, w1.find_vertex(vs[3].gid), et)
    w2.create_edge(w2.find_vertex(vs[4].gid), younger, et)
    w2.commit()
    applied0, rebuilt0 = _delta_counts()
    acc = storage.access()
    g1 = cache.get(acc)
    want1 = export_csr(acc, to_device=False)
    acc.abort()
    assert _graphs_equal(g1, want1) is None
    w1.commit()
    acc = storage.access()
    g2 = cache.get(acc)
    want2 = export_csr(acc, to_device=False)
    acc.abort()
    assert _delta_counts() == (applied0 + 2, rebuilt0)
    assert g2.node_gids[-2:].tolist() == [younger.gid, older.gid]
    assert want2.node_gids[-2:].tolist() == [older.gid, younger.gid]
    assert _gid_edges(g2) == _gid_edges(want2)
    _assert_well_formed(g2)
    # both in one gap: ascending gid whatever the set's iteration order
    v0, prev = _snapshot(storage)
    w1, w2 = storage.access(), storage.access()
    a, b = w1.create_vertex(), w2.create_vertex()
    w2.create_edge(b, w2.find_vertex(vs[5].gid), et)
    w2.commit()
    w1.commit()
    got, want = _delta_and_full(storage, v0, prev)
    assert got.node_gids[-2:].tolist() == [a.gid, b.gid]
    assert _graphs_equal(got, want) is None


def test_graph_cache_uses_delta_path(setup, monkeypatch):
    storage, vs, et, n = setup
    cache = GraphCache()
    acc = storage.access()
    g1 = cache.get(acc)
    acc.abort()
    calls = {"full": 0}
    import memgraph_tpu.ops.csr as csr_mod
    real_full = csr_mod.export_csr

    def counting_full(*a, **k):
        calls["full"] += 1
        return real_full(*a, **k)
    monkeypatch.setattr(csr_mod, "export_csr", counting_full)
    rng = np.random.default_rng(1)
    _mutate(storage, vs, et, rng, adds=10, removes=3)
    acc = storage.access()
    g2 = cache.get(acc)
    want = real_full(acc, to_device=False)
    acc.abort()
    assert calls["full"] == 0, "delta export did not engage"
    assert _graphs_equal(g2, want) is None
    # chained: a second mutation delta-exports from g2, not g1
    _mutate(storage, vs, et, rng, adds=5, removes=2)
    acc = storage.access()
    g3 = cache.get(acc)
    want3 = real_full(acc, to_device=False)
    acc.abort()
    assert calls["full"] == 0     # chained delta: still no full export
    assert _graphs_equal(g3, want3) is None


def test_delta_export_ignores_session_fine_grained_filters(setup):
    """The globally cached snapshot's content must not depend on WHICH
    user's session triggered the refresh: a fine-grained edge deny on
    the triggering accessor must not leak into the delta-exported
    arrays (r5 review finding)."""
    from memgraph_tpu.auth.fine_grained import FgStorageView
    from memgraph_tpu.auth.auth import Auth
    storage, vs, et, n = setup
    v0 = storage.topology_version
    acc = storage.access()
    prev = export_csr(acc, to_device=False)
    acc.abort()
    rng = np.random.default_rng(2)
    _mutate(storage, vs, et, rng, adds=20, removes=5)
    changed = storage.changes_between(v0, storage.topology_version)
    # restricted accessor: no fine-grained edge grants for this session
    auth = Auth(None)
    auth.create_user("restricted", "pw")
    auth.grant("restricted", ["MATCH"])
    # fine-grained is opt-in: granting on an unrelated edge type makes
    # the session restricted, and type E (ungranted) becomes invisible
    auth.grant_fine_grained("restricted", "edge_types", ["OTHER"], "READ")
    acc = storage.access()
    checker = auth.fine_grained_checker("restricted")
    assert checker.restricted
    acc.fine_grained = FgStorageView(checker, storage)
    # sanity: the session filter really does hide edges from accessors
    some_v = next(iter(storage._vertices.values()))
    from memgraph_tpu.storage.storage import VertexAccessor
    va = VertexAccessor(some_v, acc)
    assert va.out_edges() == [] and va.in_edges() == []
    got = export_csr_delta(prev, acc, changed, to_device=False)
    want = export_csr(storage.access(), to_device=False)
    acc.abort()
    assert got is not None
    assert _graphs_equal(got, want) is None
