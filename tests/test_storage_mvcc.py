"""MVCC storage engine tests.

Modeled on the reference's tests/unit/storage_v2*.cpp coverage: visibility
across snapshots, write-write conflicts, abort rollback, detach delete, GC.
"""

import threading

import pytest

from memgraph_tpu.exceptions import SerializationError, StorageError
from memgraph_tpu.storage import InMemoryStorage, StorageConfig, StorageMode, View
from memgraph_tpu.storage.common import IsolationLevel


def test_create_and_read_own_writes(storage):
    acc = storage.access()
    v = acc.create_vertex()
    label = storage.label_mapper.name_to_id("Person")
    prop = storage.property_mapper.name_to_id("name")
    v.add_label(label)
    v.set_property(prop, "alice")
    # own writes visible under NEW, not OLD
    assert v.is_visible(View.NEW)
    assert not v.is_visible(View.OLD)
    assert v.labels(View.NEW) == [label]
    assert v.get_property(prop, View.NEW) == "alice"
    acc.commit()

    acc2 = storage.access()
    v2 = acc2.find_vertex(v.gid)
    assert v2 is not None
    assert v2.get_property(prop) == "alice"
    acc2.abort()


def test_snapshot_isolation(storage):
    prop = storage.property_mapper.name_to_id("x")
    acc1 = storage.access()
    v = acc1.create_vertex()
    v.set_property(prop, 1)
    gid = v.gid
    acc1.commit()

    reader = storage.access()  # snapshot taken now
    rv = reader.find_vertex(gid)
    assert rv.get_property(prop) == 1

    writer = storage.access()
    wv = writer.find_vertex(gid)
    wv.set_property(prop, 2)
    writer.commit()

    # reader still sees the old value (snapshot isolation)
    assert rv.get_property(prop, View.OLD) == 1
    assert rv.get_property(prop, View.NEW) == 1
    reader.abort()

    # fresh reader sees new value
    acc3 = storage.access()
    assert acc3.find_vertex(gid).get_property(prop) == 2
    acc3.abort()


def test_uncommitted_invisible_to_others(storage):
    acc1 = storage.access()
    v = acc1.create_vertex()
    gid = v.gid

    acc2 = storage.access()
    assert acc2.find_vertex(gid) is None
    assert list(acc2.vertices()) == []
    acc2.abort()
    acc1.commit()

    acc3 = storage.access()
    assert acc3.find_vertex(gid) is not None
    acc3.abort()


def test_write_write_conflict(storage):
    prop = storage.property_mapper.name_to_id("x")
    acc = storage.access()
    v = acc.create_vertex()
    gid = v.gid
    acc.commit()

    t1 = storage.access()
    t2 = storage.access()
    t1.find_vertex(gid).set_property(prop, 1)
    with pytest.raises(SerializationError):
        t2.find_vertex(gid).set_property(prop, 2)
    t1.commit()
    t2.abort()


def test_conflict_with_committed_after_start(storage):
    prop = storage.property_mapper.name_to_id("x")
    acc = storage.access()
    gid = acc.create_vertex().gid
    acc.commit()

    t1 = storage.access()  # starts before t2 commits
    t2 = storage.access()
    t2.find_vertex(gid).set_property(prop, 2)
    t2.commit()
    with pytest.raises(SerializationError):
        t1.find_vertex(gid).set_property(prop, 1)
    t1.abort()


def test_abort_rolls_back(storage):
    label = storage.label_mapper.name_to_id("L")
    prop = storage.property_mapper.name_to_id("p")
    acc = storage.access()
    v = acc.create_vertex()
    v.add_label(label)
    v.set_property(prop, 10)
    gid = v.gid
    acc.commit()

    t = storage.access()
    tv = t.find_vertex(gid)
    tv.remove_label(label)
    tv.set_property(prop, 20)
    t.abort()

    check = storage.access()
    cv = check.find_vertex(gid)
    assert cv.labels() == [label]
    assert cv.get_property(prop) == 10
    check.abort()


def test_abort_created_vertex_disappears(storage):
    t = storage.access()
    gid = t.create_vertex().gid
    t.abort()
    check = storage.access()
    assert check.find_vertex(gid) is None
    check.abort()
    storage.collect_garbage()
    assert gid not in storage._vertices


def test_edges_and_expansion(storage):
    knows = storage.edge_type_mapper.name_to_id("KNOWS")
    acc = storage.access()
    a = acc.create_vertex()
    b = acc.create_vertex()
    e = acc.create_edge(a, b, knows)
    acc.commit()

    r = storage.access()
    ra = r.find_vertex(a.gid)
    rb = r.find_vertex(b.gid)
    outs = ra.out_edges()
    assert len(outs) == 1
    assert outs[0].to_vertex().gid == b.gid
    assert outs[0].edge_type == knows
    ins = rb.in_edges()
    assert len(ins) == 1
    assert ins[0].from_vertex().gid == a.gid
    r.abort()


def test_delete_vertex_requires_detach(storage):
    t = storage.edge_type_mapper.name_to_id("E")
    acc = storage.access()
    a = acc.create_vertex()
    b = acc.create_vertex()
    acc.create_edge(a, b, t)
    acc.commit()

    d = storage.access()
    da = d.find_vertex(a.gid)
    with pytest.raises(StorageError):
        d.delete_vertex(da, detach=False)
    d.abort()

    d2 = storage.access()
    da2 = d2.find_vertex(a.gid)
    _, deleted_edges = d2.delete_vertex(da2, detach=True)
    assert len(deleted_edges) == 1
    d2.commit()

    check = storage.access()
    assert check.find_vertex(a.gid) is None
    assert check.find_vertex(b.gid) is not None
    assert check.find_vertex(b.gid).in_edges() == []
    check.abort()


def test_edge_delete_visibility(storage):
    t = storage.edge_type_mapper.name_to_id("E")
    acc = storage.access()
    a = acc.create_vertex()
    b = acc.create_vertex()
    e = acc.create_edge(a, b, t)
    acc.commit()

    reader = storage.access()
    writer = storage.access()
    writer.delete_edge(writer.find_vertex(a.gid).out_edges()[0])
    writer.commit()

    # reader's snapshot predates the delete
    assert len(reader.find_vertex(a.gid).out_edges(View.OLD)) == 1
    reader.abort()

    after = storage.access()
    assert after.find_vertex(a.gid).out_edges() == []
    after.abort()


def test_gc_truncates_chains(storage):
    prop = storage.property_mapper.name_to_id("x")
    acc = storage.access()
    v = acc.create_vertex()
    gid = v.gid
    acc.commit()
    for i in range(10):
        a = storage.access()
        a.find_vertex(gid).set_property(prop, i)
        a.commit()
    vertex = storage._vertices[gid]
    assert vertex.delta is not None
    stats = storage.collect_garbage()
    assert stats["deltas_freed"] >= 10
    assert vertex.delta is None
    # value survives
    check = storage.access()
    assert check.find_vertex(gid).get_property(prop) == 9
    check.abort()


def test_gc_respects_active_readers(storage):
    prop = storage.property_mapper.name_to_id("x")
    acc = storage.access()
    v = acc.create_vertex()
    v.set_property(prop, 0)
    gid = v.gid
    acc.commit()

    reader = storage.access()
    w = storage.access()
    w.find_vertex(gid).set_property(prop, 1)
    w.commit()

    storage.collect_garbage()
    # reader must still reconstruct value 0
    assert reader.find_vertex(gid).get_property(prop) == 0
    reader.abort()
    storage.collect_garbage()
    assert storage._vertices[gid].delta is None


def test_analytical_mode_direct_mutation():
    storage = InMemoryStorage(StorageConfig(
        storage_mode=StorageMode.IN_MEMORY_ANALYTICAL))
    acc = storage.access()
    v = acc.create_vertex()
    prop = storage.property_mapper.name_to_id("x")
    v.set_property(prop, 42)
    acc.commit()
    acc2 = storage.access()
    assert acc2.find_vertex(v.gid).get_property(prop) == 42
    acc2.commit()
    assert storage._vertices[v.gid].delta is None


def test_concurrent_counter_increments(storage):
    """Concurrency smoke test: retried increments sum correctly."""
    prop = storage.property_mapper.name_to_id("n")
    acc = storage.access()
    gid = acc.create_vertex().gid
    acc2_v = acc.find_vertex(gid, View.NEW)
    acc2_v.set_property(prop, 0)
    acc.commit()

    N_THREADS, N_INCR = 4, 25
    barrier = threading.Barrier(N_THREADS)

    def worker():
        barrier.wait()
        for _ in range(N_INCR):
            while True:
                a = storage.access()
                try:
                    v = a.find_vertex(gid)
                    v.set_property(prop, v.get_property(prop) + 1)
                    a.commit()
                    break
                except SerializationError:
                    a.abort()

    threads = [threading.Thread(target=worker) for _ in range(N_THREADS)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    check = storage.access()
    assert check.find_vertex(gid).get_property(prop) == N_THREADS * N_INCR
    check.abort()


def test_read_committed_sees_latest():
    storage = InMemoryStorage()
    prop = storage.property_mapper.name_to_id("x")
    acc = storage.access()
    v = acc.create_vertex()
    v.set_property(prop, 1)
    gid = v.gid
    acc.commit()

    rc = storage.access(IsolationLevel.READ_COMMITTED)
    assert rc.find_vertex(gid).get_property(prop) == 1
    w = storage.access()
    w.find_vertex(gid).set_property(prop, 2)
    w.commit()
    assert rc.find_vertex(gid).get_property(prop) == 2
    rc.abort()


def test_post_commit_accessor_sees_own_committed_state(storage):
    """Regression: an accessor returned to the client (RETURN n,
    materialized after the transaction committed and stream exhausted) must
    see the transaction's OWN committed writes, not the pre-txn state —
    commit rewrites delta timestamps to the commit ts, so the own-write
    (ts == txn_id) rule no longer matches and effective_start_ts() must
    advance to the commit ts."""
    prop = storage.property_mapper.name_to_id("name")
    lbl = storage.label_mapper.name_to_id("Extra")
    acc = storage.access()
    v = acc.create_vertex()
    v.set_property(prop, "Andres")
    gid = v.gid
    acc.commit()

    acc2 = storage.access()
    va = acc2.find_vertex(gid)
    va.set_property(prop, "Michael")
    va.add_label(lbl)
    acc2.commit()
    # post-commit reads through the SAME accessor object, both views
    assert va.get_property(prop, View.NEW) == "Michael"
    assert va.get_property(prop, View.OLD) == "Michael"
    assert va.has_label(lbl, View.OLD)

    # a later writer's commit must stay invisible to the finished txn
    acc3 = storage.access()
    acc3.find_vertex(gid).set_property(prop, "Peter")
    acc3.commit()
    assert va.get_property(prop, View.NEW) == "Michael"


def test_post_commit_deleted_accessor_reports_deleted(storage):
    acc = storage.access()
    v = acc.create_vertex()
    gid = v.gid
    acc.commit()
    acc2 = storage.access()
    va = acc2.find_vertex(gid)
    acc2.delete_vertex(va, detach=True)
    acc2.commit()
    assert not va.is_visible(View.NEW)
    assert not va.is_visible(View.OLD)


def test_read_only_commit_keeps_snapshot(storage):
    """A no-delta (read-only) SI transaction's retained accessors must NOT
    advance to later commits when the transaction commits."""
    prop = storage.property_mapper.name_to_id("p")
    acc = storage.access()
    v = acc.create_vertex()
    v.set_property(prop, 1)
    gid = v.gid
    acc.commit()

    r = storage.access()            # SI reader, no writes
    va = r.find_vertex(gid)
    assert va.get_property(prop) == 1
    w = storage.access()
    w.find_vertex(gid).set_property(prop, 2)
    w.commit()
    assert va.get_property(prop) == 1   # snapshot holds pre-commit
    r.commit()                          # read-only commit
    assert va.get_property(prop) == 1   # ... and post-commit
