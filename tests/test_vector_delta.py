"""Vector-index O(delta) maintenance: parity vs full rebuild, concurrent
snapshot readers, replica WAL apply, dominant-dimension flips.

Locks in the four holes procedures/vector_search.py lists; reference:
src/storage/v2/indices/vector_index.cpp:22-73 (usearch update path).
"""

import numpy as np
import pytest

from memgraph_tpu.observability.metrics import global_metrics
from memgraph_tpu.procedures import vector_search as vs
from memgraph_tpu.query.interpreter import Interpreter, InterpreterContext
from memgraph_tpu.storage import InMemoryStorage


@pytest.fixture
def db():
    return InterpreterContext(InMemoryStorage())


def metric(name):
    return dict((n, v) for n, _k, v in global_metrics.snapshot()).get(
        name, 0.0)


def counter(name):
    """The index's counters (GET /stats section ``delta``):
    ``delta.vector_applied_total`` moves with a change-log refresh,
    ``delta.vector_rebuild_total`` with a full build."""
    return metric(f"delta.vector_{name}_total")


def run(db, q, params=None):
    _, rows, _ = Interpreter(db).execute(q, params)
    return rows


def _search(db, vec, k=50):
    return run(db, "CALL vector_search.search('emb', $q, $k) "
                   "YIELD node, similarity "
                   "RETURN node.name AS name, similarity "
                   "ORDER BY similarity DESC, name",
               {"q": vec, "k": k})


def _seed(db, n=30, dim=4, seed=0):
    rng = np.random.default_rng(seed)
    for i in range(n):
        run(db, "CREATE (:V {name: $n, emb: $e})",
            {"n": f"v{i:03d}", "e": [float(x) for x in rng.random(dim)]})


def test_streaming_inserts_use_delta_and_match_full_rebuild(db):
    _seed(db, n=30)
    q = [1.0, 0.0, 0.0, 0.0]
    _search(db, q)                      # prime: full build
    full_builds_before = counter("rebuild")
    deltas_before = counter("applied")

    # streaming inserts, a deletion, and an update across commits
    rng = np.random.default_rng(7)
    for i in range(30, 40):
        run(db, "CREATE (:V {name: $n, emb: $e})",
            {"n": f"v{i:03d}", "e": [float(x) for x in rng.random(4)]})
        _search(db, q)
    run(db, "MATCH (v:V {name: 'v001'}) DELETE v")
    run(db, "MATCH (v:V {name: 'v002'}) SET v.emb = [9.0, 0.0, 0.0, 0.0]")
    got = _search(db, q)

    assert counter("rebuild") == full_builds_before, \
        "streaming updates triggered full rebuilds"
    assert counter("applied") > deltas_before

    # parity: identical results from a cold full rebuild
    vs._CACHE.clear()
    expect = _search(db, q)
    assert [r[0] for r in got] == [r[0] for r in expect]
    np.testing.assert_allclose([r[1] for r in got],
                               [r[1] for r in expect], rtol=1e-5)
    assert got[0][0] == "v002"          # the updated vector dominates
    assert not any(r[0] == "v001" for r in got)


def test_concurrent_snapshot_readers_see_their_version(db):
    """Hole #2: a reader opened before a commit must not see (or bake)
    the newer vectors."""
    _seed(db, n=5)
    interp = Interpreter(db)
    interp.execute("BEGIN")
    _, before, _ = interp.execute(
        "CALL vector_search.search('emb', [1.0,0.0,0.0,0.0], 50) "
        "YIELD node RETURN count(node)")

    run(db, "CREATE (:V {name: 'late', emb: [1.0, 0.0, 0.0, 0.0]})")
    # a NEW reader sees 6
    assert _search(db, [1.0, 0.0, 0.0, 0.0])[0:1] and \
        len(_search(db, [1.0, 0.0, 0.0, 0.0])) == 6
    # the OLD transaction still sees 5 through its snapshot
    _, again, _ = interp.execute(
        "CALL vector_search.search('emb', [1.0,0.0,0.0,0.0], 50) "
        "YIELD node RETURN count(node)")
    interp.execute("COMMIT")
    assert before == [[5]]
    assert again == [[5]]
    # and the baked entries didn't poison the new version
    assert len(_search(db, [1.0, 0.0, 0.0, 0.0])) == 6


def test_dimension_flip_triggers_full_rebuild(db):
    """Hole #4: when another dimension becomes dominant the index must
    re-center on it, not silently drop rows."""
    for i in range(3):
        run(db, "CREATE (:V {name: $n, emb: [1.0, $i]})",
            {"n": f"d2_{i}", "i": float(i)})
    assert len(_search(db, [1.0, 0.0])) == 3
    before_full = counter("rebuild")
    # add 4 three-dimensional vectors one commit at a time: dominance flips
    for i in range(4):
        run(db, "CREATE (:V {name: $n, emb: [1.0, $i, 0.5]})",
            {"n": f"d3_{i}", "i": float(i)})
    got = run(db, "CALL vector_search.search('emb', [1.0,0.0,0.5], 50) "
                  "YIELD node RETURN node.name ORDER BY node.name")
    assert [r[0] for r in got] == ["d3_0", "d3_1", "d3_2", "d3_3"]
    assert counter("rebuild") > before_full


def test_replica_wal_apply_feeds_delta_refresh():
    """Hole #1: WAL apply on a replica records changed gids in the change
    log, so the replica's vector index delta-refreshes like MAIN's."""
    import socket

    main_ictx = InterpreterContext(InMemoryStorage())
    replica_ictx = InterpreterContext(InMemoryStorage())
    main = Interpreter(main_ictx)
    replica = Interpreter(replica_ictx)
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    replica.execute(f"SET REPLICATION ROLE TO REPLICA WITH PORT {port}")
    try:
        _seed(main_ictx, n=10)
        main.execute(f'REGISTER REPLICA r1 SYNC TO "127.0.0.1:{port}"')
        # prime the REPLICA's index (full build once)
        assert len(_search(replica_ictx, [1.0, 0.0, 0.0, 0.0])) == 10
        full_before = counter("rebuild")
        # streamed inserts arrive via WAL apply on the replica
        for i in range(5):
            run(main_ictx, "CREATE (:V {name: $n, emb: [1.0,0.0,0.0,$i]})",
                {"n": f"w{i}", "i": float(i)})
            got = _search(replica_ictx, [1.0, 0.0, 0.0, 0.0])
            assert len(got) == 10 + i + 1
        assert counter("rebuild") == full_before, \
            "replica WAL apply forced full rebuilds"
    finally:
        if getattr(replica_ictx, "replication", None) and \
                replica_ictx.replication.replica_server:
            replica_ictx.replication.replica_server.stop()
        if getattr(main_ictx, "replication", None):
            for c in main_ictx.replication.replicas.values():
                c.close()


def test_changes_between_log_semantics():
    storage = InMemoryStorage()
    v0 = storage.topology_version
    acc = storage.access()
    a = acc.create_vertex()
    b = acc.create_vertex()
    acc.commit()
    v1 = storage.topology_version
    from memgraph_tpu.storage.storage import ChangeLogUnknowable
    changed = storage.changes_between(v0, v1)
    assert isinstance(changed, frozenset) \
        and {a.gid, b.gid} <= set(changed)
    # unknown ranges (beyond the log) report the typed falsy verdict
    wrapped = storage.changes_between(-10_000, v1)
    assert isinstance(wrapped, ChangeLogUnknowable) and not wrapped
    assert wrapped.reason == "log_wrapped"
    # empty range
    assert storage.changes_between(v1, v1) == frozenset()
    # full-invalidation bumps poison the covering range
    storage._bump_topology(None)
    v2 = storage.topology_version
    untracked = storage.changes_between(v1, v2)
    assert isinstance(untracked, ChangeLogUnknowable)
    assert untracked.reason == "untracked_bump"


def test_read_your_own_writes_in_transaction(db):
    """A transaction that writes a vector must see it in its OWN later
    searches, and its uncommitted entry must never reach the shared
    cache for same-snapshot readers."""
    _seed(db, n=3)
    _search(db, [1.0, 0.0, 0.0, 0.0])      # prime shared cache
    w = Interpreter(db)
    w.execute("BEGIN")
    w.execute("CREATE (:V {name: 'mine', emb: [5.0, 0.0, 0.0, 0.0]})")
    _, rows, _ = w.execute(
        "CALL vector_search.search('emb', [1.0,0.0,0.0,0.0], 50) "
        "YIELD node RETURN node.name ORDER BY node.name")
    assert ["mine"] in rows                # read-your-own-writes
    # a concurrent reader at the same committed snapshot must NOT see it
    assert len(_search(db, [1.0, 0.0, 0.0, 0.0])) == 3
    w.execute("ROLLBACK")
    assert len(_search(db, [1.0, 0.0, 0.0, 0.0])) == 3


def test_background_index_drop_race():
    """DROP INDEX during a background build must not resurrect."""
    from memgraph_tpu.storage import InMemoryStorage, View
    storage = InMemoryStorage()
    lid = storage.label_mapper.name_to_id("L")
    acc = storage.access()
    for _ in range(5000):
        acc.create_vertex().add_label(lid)
    acc.commit()
    event = storage.create_label_index(lid, background=True)
    storage.indices.label.drop(lid)
    event.wait(20)
    assert not storage.indices.label.has(lid)
    assert storage.indices.label.candidates(lid) is None


def test_spans_and_counters_account_for_every_lookup(db):
    """One ``vector.index`` span a lookup and one ``vector.search`` a
    search; a lookup that had to make an entry closes ``vector.build``
    or ``vector.refresh`` once and moves the matching counter, so the
    two counters add up to the lookups that were no hit and no alias."""
    from memgraph_tpu.observability import trace as T
    _seed(db, n=12)
    q = [1.0, 0.0, 0.0, 0.0]
    names = ("index", "refresh", "build", "search")
    T.enable()
    try:
        T.TRACER.reset()
        before = {n: metric(f"span.vector.{n}.count") for n in names}
        applied, rebuilt = counter("applied"), counter("rebuild")
        _search(db, q)                              # full build
        _search(db, q)                              # hit
        run(db, "CREATE (:V {name: 'v900', emb: [1.0, 0.0, 0.0, 0.0]})")
        _search(db, q)                              # change-log refresh
        run(db, "CREATE (:W {name: 'no embedding'})")
        _search(db, q)                              # refresh, nothing to set
        _search(db, q)                              # hit
        got = {n: metric(f"span.vector.{n}.count") - before[n] for n in names}
        kinds = [s["attrs"].get("kind")
                 for spans in T.TRACER.finished_traces()
                 for s in spans if s["name"] == "vector.index"]
    finally:
        T.disable()
        T.TRACER.reset()
    assert got == {"index": 5, "refresh": 2, "build": 1, "search": 5}
    assert sorted(kinds) == ["delta", "delta", "full", "hit", "hit"]
    assert counter("rebuild") - rebuilt == 1 == kinds.count("full")
    assert counter("applied") - applied == 2 == kinds.count("delta")
