"""mglane: the compiled Cypher read lane (query/plan/lane.py +
ops/pipeline.py).

Oracle: the serial Volcano path (MEMGRAPH_TPU_DISABLE_PARALLEL disables
both the columnar rewrite and the lane riding it) — the lane is an
execution strategy, so results must be identical on every shape,
including NULL/absent-property, string, MVCC and deleted-vertex
semantics. Refusal shapes must fall back LOUDLY (typed reason, counted
per fingerprint) and still answer correctly; compilation must happen
exactly once per plan-cache fingerprint (compile-counter witness)."""

import os

import numpy as np
import pytest

from memgraph_tpu.ops import pipeline as pl
from memgraph_tpu.query.interpreter import Interpreter, InterpreterContext
from memgraph_tpu.storage import (InMemoryStorage, StorageConfig,
                                  StorageMode)

HINT = "USING PARALLEL EXECUTION "


@pytest.fixture()
def db():
    storage = InMemoryStorage(StorageConfig(
        storage_mode=StorageMode.IN_MEMORY_TRANSACTIONAL))
    ctx = InterpreterContext(storage)
    acc = storage.access()
    lid = storage.label_mapper.name_to_id("P")
    qid = storage.label_mapper.name_to_id("Q")
    px = storage.property_mapper.name_to_id("x")
    pf = storage.property_mapper.name_to_id("f")
    ps = storage.property_mapper.name_to_id("s")
    pb = storage.property_mapper.name_to_id("b")
    rng = np.random.default_rng(11)
    vs = []
    for i in range(300):
        v = acc.create_vertex()
        v.add_label(lid)
        if i % 3 == 0:
            v.add_label(qid)
        v.set_property(px, int(rng.integers(-50, 50)))
        if i % 4 == 0:
            v.set_property(pf, float(rng.random() * 10 - 5))
        if i % 5 != 0:
            v.set_property(ps,
                           str(rng.choice(["red", "green", "blue"])))
        if i % 7 == 0:
            v.set_property(pb, bool(rng.integers(0, 2)))
        vs.append(v)
    te = storage.edge_type_mapper.name_to_id("E")
    tr = storage.edge_type_mapper.name_to_id("R")
    for _ in range(1200):
        a, b = rng.integers(0, 300, 2)
        acc.create_edge(vs[a], vs[b],
                        te if rng.integers(0, 4) else tr)
    for i in range(6):                # self-loops: uniqueness correction
        acc.create_edge(vs[i], vs[i], te)
    hub = vs[0]                       # supernode-ish hub
    for i in range(1, 150):
        acc.create_edge(vs[i], hub, te)
    acc.commit()
    return ctx


def run(ctx, q, params=None):
    interp = Interpreter(ctx)
    _, rows, _ = interp.execute(q, params)
    return rows


def both(ctx, q, params=None, expect_hit=True):
    """Lane path vs serial Volcano oracle; asserts identical rows and
    (by default) that the lane really served the query."""
    ctx.invalidate_plans()
    snap = {n: v for n, _k, v in _metrics()}
    lane = run(ctx, q, params)
    hits = _metric_delta(snap, "lane.hit_total")
    os.environ["MEMGRAPH_TPU_DISABLE_PARALLEL"] = "1"
    ctx.invalidate_plans()
    try:
        ser = run(ctx, q, params)
    finally:
        os.environ.pop("MEMGRAPH_TPU_DISABLE_PARALLEL", None)
        ctx.invalidate_plans()
    assert _approx(lane, ser), (q, lane, ser)
    if expect_hit:
        assert hits >= 1, f"lane did not serve: {q}"
    return lane


def _metrics():
    from memgraph_tpu.observability.metrics import global_metrics
    return global_metrics.snapshot()


def _metric_delta(before, name):
    now = {n: v for n, _k, v in _metrics()}
    return now.get(name, 0) - before.get(name, 0)


def _approx(a, b):
    if isinstance(a, float) and isinstance(b, float):
        return a == pytest.approx(b, rel=1e-12, abs=1e-12)
    if isinstance(a, list) and isinstance(b, list):
        return len(a) == len(b) and all(
            _approx(x, y) for x, y in zip(a, b))
    return a == b and type(a) is type(b)


class TestAggregateParity:
    @pytest.mark.parametrize("q", [
        "MATCH (n:P) %s RETURN count(*) AS c",
        "MATCH (n:P) %s WHERE n.x > 10 RETURN count(*) AS c, "
        "sum(n.x) AS s, min(n.x) AS mn, max(n.x) AS mx",
        "MATCH (n:P) %s WHERE n.x >= -5 AND n.x <= 5 "
        "RETURN sum(n.x) AS s",
        "MATCH (n:P) %s WHERE n.s = 'red' RETURN count(*) AS c, "
        "min(n.x) AS mn",
        "MATCH (n:P) %s WHERE n.s <> 'red' RETURN count(*) AS c",
        "MATCH (n:P) %s WHERE n.b = true RETURN count(*) AS c",
        "MATCH (n:P) %s RETURN count(n.x) AS cx, count(n.s) AS cs, "
        "count(n.f) AS cf",
        # absent property -> NULL -> excluded; empty aggregates
        "MATCH (n:P) %s WHERE n.missing > 0 RETURN count(*) AS c, "
        "sum(n.x) AS s, min(n.x) AS mn",
        "MATCH (n:P) %s WHERE n.x > 10000 RETURN count(*) AS c, "
        "max(n.x) AS mx",
    ])
    def test_scan_parity(self, db, q):
        both(db, q % HINT)

    def test_parameter_rhs(self, db):
        r = both(db, f"MATCH (n:P) {HINT}WHERE n.x > $k "
                     "RETURN count(*) AS c", {"k": 25})
        assert r[0][0] > 0

    def test_expand_edge_table_parity(self, db):
        both(db, f"MATCH (a:P) {HINT}MATCH (a)-[:E]->(m) "
                 "WHERE m.x < 0 RETURN count(m) AS c, sum(m.x) AS s")
        both(db, f"MATCH (a:P) {HINT}MATCH (a)-[e:E]->(m) "
                 "WHERE a.x > 0 AND m.x < 20 RETURN count(*) AS c")


class TestHopParity:
    @pytest.mark.parametrize("q", [
        "MATCH (a:P) %s WHERE a.x > 0 MATCH (a)-[:E]->(b)-[:E]->(m) "
        "RETURN count(m) AS c",
        "MATCH (a:P)-[:E]->(b)-[:E]->(m) %s WHERE a.x > 0 AND "
        "b.x < 25 RETURN count(m) AS c",
        "MATCH (a:P) %s MATCH (a)-[:E*2..2]->(m) "
        "RETURN count(m) AS c, count(DISTINCT m) AS d",
        "MATCH (a:P) %s MATCH (a)-[:E*1..2]->(m) RETURN count(m) AS c",
        "MATCH (a:P) %s MATCH (a)-[:E*1..1]->(m) WHERE m.x > 0 "
        "RETURN count(m) AS c",
        "MATCH (a:P) %s MATCH (a)<-[:E]-(b)<-[:E]-(m) "
        "RETURN count(m) AS c",
        # the supernode hub rides the same masked spmv
        "MATCH (a:P) %s WHERE a.x <> 9999 MATCH (a)-[:E*2..2]->(m) "
        "RETURN count(DISTINCT m) AS d",
    ])
    def test_hop_parity(self, db, q):
        both(db, q % HINT)

    def test_self_target_not_claimed(self, db):
        # (a)-[*2..2]->(a): the bound-destination constraint is not a
        # lane shape — must stay on the row path with exact results
        both(db, f"MATCH (a:P) {HINT}MATCH (a)-[:E*2..2]->(a) "
                 "RETURN count(a) AS c", expect_hit=False)

    def test_two_match_no_edge_uniqueness(self, db):
        # separate MATCH clauses: relationship uniqueness does NOT
        # apply, so self-loop paths (e, e) COUNT — the lane must not
        # subtract its correction here
        both(db, f"MATCH (a:P) {HINT}MATCH (a)-[:E]->(b) "
                 "MATCH (b)-[:E]->(m) RETURN count(m) AS c")


class TestTopK:
    @pytest.mark.parametrize("q", [
        "MATCH (n:P) %s WHERE n.x > -40 RETURN n.x AS x "
        "ORDER BY x DESC LIMIT 7",
        "MATCH (n:P) %s RETURN n.x AS x ORDER BY x LIMIT 5",
        # null keys: last ascending, first descending (openCypher)
        "MATCH (n:P) %s RETURN n.b AS k, n.x AS x ORDER BY n.x LIMIT 4",
    ])
    def test_topk_parity(self, db, q):
        both(db, q % HINT)

    def test_topk_null_placement(self, db):
        # f is absent on 3/4 of rows: DESC puts nulls first
        rows = both(db, f"MATCH (n:P) {HINT}RETURN n.missing AS k "
                        "ORDER BY k DESC LIMIT 3", expect_hit=False)
        assert rows == [[None], [None], [None]]


class TestFallbacks:
    def _reason_count(self, fp_sub, reason):
        snap = pl.LANE_REGISTRY.snapshot()
        return sum(e["fallbacks"].get(reason, 0)
                   for fp, e in snap.items() if fp_sub in fp)

    def test_avg_falls_back_typed(self, db):
        q = f"MATCH (n:P) {HINT}RETURN count(*) AS c, avg(n.x) AS av"
        before = self._reason_count("avg", "agg_avg")
        r = both(db, q, expect_hit=False)
        assert r[0][0] == 300
        assert self._reason_count("avg", "agg_avg") > before

    def test_float_column_falls_back_typed(self, db):
        q = f"MATCH (n:P) {HINT}RETURN sum(n.f) AS s"
        before = self._reason_count("n.f", "float_column")
        r = both(db, q, expect_hit=False)
        assert isinstance(r[0][0], float)
        assert self._reason_count("n.f", "float_column") > before

    def test_group_by_falls_back_typed(self, db):
        q = f"MATCH (n:P) {HINT}RETURN n.s AS s, count(*) AS c"
        before = self._reason_count("n.s AS s", "group_by")
        both(db, q, expect_hit=False)
        assert self._reason_count("n.s AS s", "group_by") > before

    def test_point_source_declines_device(self, db):
        # unhinted point-source two-hop: the row path IS the fast path
        os.environ.pop("MEMGRAPH_TPU_DISABLE_PARALLEL", None)
        run(db, "CREATE INDEX ON :P(x)")   # makes the scan a point scan
        db.invalidate_plans()
        q = ("MATCH (a:P {x: $v}) MATCH (a)-[:E*2..2]->(m) "
             "RETURN count(m) AS c")
        snap = {n: v for n, _k, v in _metrics()}
        lane = run(db, q, {"v": 3})
        assert _metric_delta(
            snap, "lane.fallback_total.small_frontier") >= 1
        os.environ["MEMGRAPH_TPU_DISABLE_PARALLEL"] = "1"
        db.invalidate_plans()
        try:
            ser = run(db, q, {"v": 3})
        finally:
            os.environ.pop("MEMGRAPH_TPU_DISABLE_PARALLEL", None)
            db.invalidate_plans()
        assert lane == ser

    def test_min_over_strings_row_fallback(self, db):
        r = both(db, f"MATCH (n:P) {HINT}RETURN min(n.s) AS m",
                 expect_hit=False)
        assert r[0][0] == "blue"


class TestCompileOnce:
    def test_fingerprint_compiles_exactly_once(self, db):
        from memgraph_tpu.observability.stats import global_query_stats
        from memgraph_tpu.utils.jax_cache import install_compile_counter
        counter = install_compile_counter()
        q = (f"MATCH (n:P) {HINT}WHERE n.x > 12 "
             "RETURN count(*) AS c1, sum(n.x) AS s1")
        fp = global_query_stats.fingerprint(q)
        db.invalidate_plans()
        run(db, q)
        assert pl.LANE_REGISTRY.compiles_for(fp) == 1
        # literals are traced parameters: a different literal is the
        # same fingerprint AND the same compiled program
        snap = {n: v for n, _k, v in _metrics()}
        run(db, f"MATCH (n:P) {HINT}WHERE n.x > 33 "
                "RETURN count(*) AS c1, sum(n.x) AS s1")
        run(db, q)
        assert pl.LANE_REGISTRY.compiles_for(fp) == 1
        assert _metric_delta(snap, "lane.compiled_total") == 0
        if counter:
            # PR 12 runtime witness: no XLA backend compile either
            assert _metric_delta(snap, "jit.compile_total") == 0
        assert _metric_delta(snap, "lane.hit_total") == 2


class TestInvalidation:
    def test_index_ddl_drops_lanes_and_results_match(self, db):
        q = f"MATCH (n:P) {HINT}WHERE n.x > 5 RETURN count(*) AS c"
        db.invalidate_plans()
        before = run(db, q)
        assert pl.resident_programs() > 0
        run(db, "CREATE INDEX ON :P(x)")
        # the stale lane must be gone the moment DDL lands
        assert pl.resident_programs() == 0
        assert db._plan_cache == {}
        after = run(db, q)
        assert after == before
        os.environ["MEMGRAPH_TPU_DISABLE_PARALLEL"] = "1"
        db.invalidate_plans()
        try:
            oracle = run(db, q)
        finally:
            os.environ.pop("MEMGRAPH_TPU_DISABLE_PARALLEL", None)
            db.invalidate_plans()
        assert after == oracle

    def test_constraint_ddl_invalidates_plans(self, db):
        q = f"MATCH (n:P) {HINT}WHERE n.x > 5 RETURN count(*) AS c"
        db.invalidate_plans()
        run(db, q)
        assert pl.resident_programs() > 0
        run(db, "CREATE CONSTRAINT ON (n:Q) ASSERT EXISTS (n.x)")
        assert pl.resident_programs() == 0, \
            "constraint DDL must drop compiled lanes like index DDL"
        assert db._plan_cache == {}

    def test_delta_freshness_after_commit(self, db):
        q = f"MATCH (n:P) {HINT}WHERE n.x = 77777 RETURN count(*) AS c"
        db.invalidate_plans()
        assert run(db, q) == [[0]]
        run(db, "CREATE (:P {x: 77777}), (:P {x: 77777})")
        assert run(db, q) == [[2]]
        q2 = (f"MATCH (a:P) {HINT}WHERE a.x = 88888 "
              "MATCH (a)-[:E]->(b)-[:E]->(m) RETURN count(m) AS c")
        assert run(db, q2) == [[0]]
        run(db, "CREATE (a:P {x: 88888})-[:E]->(b:P)-[:E]->(:P)")
        assert run(db, q2) == [[1]]


class TestMVCC:
    def test_own_uncommitted_writes_fall_back_correctly(self, db):
        interp = Interpreter(db)
        db.invalidate_plans()
        interp.execute("BEGIN")
        interp.execute("CREATE (:P {x: 424242})")
        snap = {n: v for n, _k, v in _metrics()}
        q = f"MATCH (n:P) {HINT}WHERE n.x = 424242 RETURN count(*) AS c"
        _, rows, _ = interp.execute(q)
        assert rows == [[1]]
        assert _metric_delta(
            snap, "lane.fallback_total.mvcc_private") >= 1
        interp.execute("ROLLBACK")
        _, rows, _ = interp.execute(q)
        assert rows == [[0]]

    def test_deleted_vertices_not_counted(self, db):
        db.invalidate_plans()
        q = f"MATCH (n:P) {HINT}WHERE n.x > -1000 RETURN count(*) AS c"
        before = run(db, q)[0][0]
        run(db, "MATCH (n:P) WHERE n.x > 40 DETACH DELETE n")
        after = run(db, q)[0][0]
        assert after < before
        os.environ["MEMGRAPH_TPU_DISABLE_PARALLEL"] = "1"
        db.invalidate_plans()
        try:
            oracle = run(db, q)[0][0]
        finally:
            os.environ.pop("MEMGRAPH_TPU_DISABLE_PARALLEL", None)
            db.invalidate_plans()
        assert after == oracle

    def test_snapshot_isolation_under_concurrent_writer(self, db):
        from memgraph_tpu.storage.common import IsolationLevel
        db.invalidate_plans()
        q = f"MATCH (n:P) {HINT}WHERE n.x = 99999 RETURN count(*) AS c"
        run(db, q)                      # warm the lane
        reader = Interpreter(db)
        reader.session_isolation = IsolationLevel.SNAPSHOT_ISOLATION
        reader.execute("BEGIN")
        _, rows, _ = reader.execute(q)
        assert rows == [[0]]
        run(db, "CREATE (:P {x: 99999})")   # concurrent commit
        # the open snapshot must NOT see it, lane or no lane
        _, rows, _ = reader.execute(q)
        assert rows == [[0]]
        reader.execute("COMMIT")
        assert run(db, q) == [[1]]


Q_AGG = (f"MATCH (n:User) {HINT}WHERE n.age > 40 RETURN count(*), "
         "sum(n.age), min(n.age), max(n.age)")
Q_HOP = (f"MATCH (a:User)-[:FRIEND]->(b)-[:FRIEND]->(m) {HINT}"
         "WHERE a.age < 2 RETURN count(m)")
WRITES = [("MATCH (n:User {id: $id}) SET n.age = n.age + 1", ("id",)),
          ("MATCH (a:User {id: $a}), (b:User {id: $b}) "
           "CREATE (a)-[:FRIEND]->(b)", ("a", "b")),
          ("CREATE (:User {id: $id, age: $id % 80})", ())]


@pytest.fixture()
def pokec():
    """The small cell's schema and its three write classes, at 400 /
    2,400: two contexts on one store, so that the lane's plans and the
    row path's (planned with the rewrite off) both stay cached."""
    storage = InMemoryStorage()
    lane, rows = InterpreterContext(storage), InterpreterContext(storage)
    rng = np.random.default_rng(5)
    run(lane, "UNWIND range(0, 399) AS i "
              "CREATE (:User {id: i, age: i % 80})")
    run(lane, "CREATE INDEX ON :User(id)")
    pairs = [[int(a), int(b)] for a, b in rng.integers(0, 400, (2400, 2))]
    run(lane, "UNWIND $pairs AS p MATCH (a:User {id: p[0]}), "
              "(b:User {id: p[1]}) CREATE (a)-[:FRIEND]->(b)",
        {"pairs": pairs})
    os.environ["MEMGRAPH_TPU_DISABLE_PARALLEL"] = "1"
    try:
        for q in (Q_AGG, Q_HOP):
            run(rows, q)
    finally:
        os.environ.pop("MEMGRAPH_TPU_DISABLE_PARALLEL", None)
    return lane, rows, rng


def _write(ctx, rng, i, next_id):
    query, keys = WRITES[i % 3]
    params = {k: int(rng.integers(0, 400)) for k in keys} or \
        {"id": next_id}
    run(ctx, query, params)


class TestSnapshotFollowsWrites:
    """The columnar cache is keyed on the reader's version and refreshed
    from the change log (tests/test_columnar_delta.py holds the patch to
    the sweep); here the lane's answers through the interpreter."""

    def test_lane_classes_hold_after_each_of_fifty_writes(self, pokec):
        lane, rows, rng = pokec
        for q in (Q_AGG, Q_HOP):
            assert run(lane, q) == run(rows, q)
        snap = {n: v for n, _k, v in _metrics()}
        for i in range(50):
            _write(lane, rng, i, 400 + i)
            for q in (Q_AGG, Q_HOP):
                assert run(lane, q) == run(rows, q), (i, q)
        assert _metric_delta(snap, "lane.hit_total") == 100
        assert _metric_delta(snap, "delta.columnar_rebuild_total") == 0
        # edges, all vertices and :User, once a version
        assert _metric_delta(snap, "delta.columnar_applied_total") == 150

    def test_one_patch_a_table_a_version_and_stats_lists_them(self, pokec):
        import asyncio
        import json
        import socket
        import threading
        import urllib.request
        lane, _rows, rng = pokec
        run(lane, Q_HOP)
        _write(lane, rng, 1, 0)
        snap = {n: v for n, _k, v in _metrics()}
        run(lane, Q_HOP)
        assert _metric_delta(snap, "delta.columnar_applied_total") == 3
        run(lane, Q_HOP)
        run(lane, Q_AGG)                # :User again, at the same version
        assert _metric_delta(snap, "delta.columnar_applied_total") == 3
        assert _metric_delta(snap, "delta.columnar_rebuild_total") == 0

        from memgraph_tpu.observability.http import start_monitoring_server
        with socket.socket() as probe:
            probe.bind(("127.0.0.1", 0))
            port = probe.getsockname()[1]
        loop = asyncio.new_event_loop()
        started = threading.Event()

        def serve():
            asyncio.set_event_loop(loop)
            loop.run_until_complete(
                start_monitoring_server("127.0.0.1", port, lane))
            started.set()
            loop.run_forever()

        threading.Thread(target=serve, daemon=True).start()
        assert started.wait(10)
        try:
            doc = json.loads(urllib.request.urlopen(
                f"http://127.0.0.1:{port}/stats", timeout=5).read())
        finally:
            loop.call_soon_threadsafe(loop.stop)
        assert doc["delta"]["delta.columnar_applied_total"] >= 3
        assert doc["delta"]["delta.columnar_rebuild_total"] >= 3

    def test_a_reader_older_than_a_commit_is_not_private(self, pokec):
        from memgraph_tpu.storage.common import IsolationLevel
        lane, rows, rng = pokec
        reader = Interpreter(lane)
        reader.session_isolation = IsolationLevel.SNAPSHOT_ISOLATION
        reader.execute("BEGIN")
        want = {q: run(rows, q) for q in (Q_AGG, Q_HOP)}
        snap = {n: v for n, _k, v in _metrics()}
        for i in range(3):              # commits the reader cannot see
            _write(lane, rng, i, 900 + i)
            run(lane, Q_HOP)            # a newer entry stands beside its own
            for q in (Q_AGG, Q_HOP):
                assert reader.execute(q)[1] == want[q], (i, q)
        assert _metric_delta(snap, "lane.hit_total") == 9
        assert _metric_delta(snap, "lane.fallback_total.mvcc_private") == 0
        # its own writes still are
        reader.execute("CREATE (:User {id: 999, age: 41})")
        got = reader.execute(Q_AGG)[1]
        assert got[0][0] == want[Q_AGG][0][0] + 1
        assert _metric_delta(snap, "lane.fallback_total.mvcc_private") == 1
        reader.execute("ROLLBACK")


class TestKernelServerLane:
    def test_lane_op_served_in_process(self):
        """The kernel server's lane op runs the same hop program the
        in-process lane compiles (dispatch-handler level: no socket)."""
        from memgraph_tpu.server.kernel_server import KernelServer
        srv = KernelServer.__new__(KernelServer)
        src = np.array([0, 1, 2, 2], dtype=np.int32)
        dst = np.array([1, 2, 3, 2], dtype=np.int32)
        n = 4
        header = {"hops": 2, "edge_unique": True, "need_rows": True,
                  "need_distinct": True, "n_nodes": n}
        arrays = {"src": src, "dst": dst,
                  "emask": np.ones(4, bool),
                  "smask": np.ones(n, bool),
                  "midmask": np.ones(n, np.float32),
                  "tmask": np.ones(n, np.float32)}
        h, _ = srv._op_lane(header, arrays)
        assert h["ok"]
        # paths of length exactly 2 without edge reuse:
        # 0>1>2, 1>2>3, 1>2>2, 2>2>3 (self-loop pair 2>2>2 excluded)
        assert h["rows"] == 4
        assert h["distinct"] == 2      # distinct targets {2, 3}
        missing = srv._op_lane(header, {"src": src})
        assert not missing[0]["ok"]


class TestStatsSurface:
    def test_lane_stats_shape(self, db):
        db.invalidate_plans()
        run(db, f"MATCH (n:P) {HINT}WHERE n.x > 1 RETURN count(*) AS c")
        stats = pl.lane_stats()
        assert stats["resident_programs"] >= 1
        assert any(e["hits"] >= 1 for e in
                   stats["fingerprints"].values())
        from memgraph_tpu.observability.stats import STAGE_NAMES
        for stage in ("lane_compile", "lane_dispatch", "lane_iterate"):
            assert stage in STAGE_NAMES

    def test_profile_attributes_lane_stages(self, db):
        db.invalidate_plans()
        q = f"MATCH (n:P) {HINT}WHERE n.x > 1 RETURN count(*) AS c"
        run(db, q)                      # compile outside the profile
        rows = run(db, "PROFILE " + q)
        stages = [r[0] for r in rows if str(r[0]).startswith(">>")]
        assert any("lane_" in s for s in stages), stages
