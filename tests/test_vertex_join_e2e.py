"""A CALL after a vertex insert: the CSR snapshot follows the vertex
(ops/csr.export_csr_delta admits it) and every consumer of the snapshot
answers what it answers on a cold cache; the MXU plan, whose dense ids
the join extended, is rebuilt in full and never derived across it.
"""

import numpy as np

from memgraph_tpu.observability.metrics import global_metrics
from memgraph_tpu.ops import pagerank as pr_mod
from memgraph_tpu.ops.csr import GraphCache
from memgraph_tpu.query.interpreter import Interpreter, InterpreterContext
from memgraph_tpu.storage import InMemoryStorage, StorageConfig, StorageMode

RETRIEVE = ("CALL graphrag.retrieve('embedding', $q, 5, 2, 12) "
            "YIELD node, score RETURN node.id, score")
RANKS = ("CALL pagerank.get() YIELD node, rank "
         "RETURN node.id, rank ORDER BY rank DESC, node.id LIMIT 25")


def _reference_pagerank(src, dst, n, iters=60, damping=0.85):
    """float64 power iteration with the dangling mass spread evenly."""
    out = np.bincount(src, minlength=n).astype(np.float64)
    m = np.zeros((n, n))
    np.add.at(m, (dst, src), 1.0 / out[src])
    rank = np.full(n, 1.0 / n)
    for _ in range(iters):
        dangling = rank[out == 0].sum()
        rank = (1 - damping) / n + damping * (m @ rank + dangling / n)
    return rank


def run(db, q, params=None):
    _, rows, _ = Interpreter(db).execute(q, params)
    return rows


def _exports():
    snap = {name: value for name, _kind, value in global_metrics.snapshot()}
    return (snap.get("delta.export_applied_total", 0),
            snap.get("delta.export_rebuild_total", 0))


def _load(db, rng, n=80, n_edges=400, width=16):
    vectors = rng.standard_normal((n, width))
    run(db, "CREATE INDEX ON :User(id)")
    run(db, "UNWIND $rows AS r CREATE (:User {id: r.id, embedding: r.v})",
        {"rows": [{"id": i, "v": vectors[i].tolist()} for i in range(n)]})
    pairs = np.stack([rng.integers(0, n, n_edges),
                      rng.integers(0, n, n_edges)], axis=1)
    run(db, "UNWIND $pairs AS p MATCH (a:User {id: p[0]}), (b:User {id: p[1]}) "
            "CREATE (a)-[:FRIEND]->(b)", {"pairs": pairs.tolist()})


def _insert(db, doc):
    run(db, "CREATE (u:User {id: $id, embedding: $v}) WITH u "
            "UNWIND $friends AS f MATCH (b:User {id: f}) "
            "CREATE (u)-[:FRIEND]->(b)", doc)
    run(db, "MATCH (a:User {id: $back}), (u:User {id: $id}) "
            "CREATE (a)-[:FRIEND]->(u)", doc)


def test_calls_after_an_insert_answer_as_on_a_cold_cache():
    """Two databases take the same statements. The first CALLs between
    the inserts, so each of its snapshots is a splice of the one before;
    the second CALLs once at the end, on a cache that has nothing of it:
    a full export."""
    warm = InterpreterContext(InMemoryStorage())
    cold = InterpreterContext(InMemoryStorage())
    _load(warm, np.random.default_rng(41))
    _load(cold, np.random.default_rng(41))
    rng = np.random.default_rng(43)
    q = rng.standard_normal(16).tolist()
    run(warm, RETRIEVE, {"q": q})
    run(warm, RANKS)
    docs = [{"id": 1000 + k, "v": rng.standard_normal(16).tolist(),
             "friends": rng.integers(0, 80, 8).tolist(), "back": 3 + k}
            for k in range(3)]
    applied0, rebuilt0 = _exports()
    for doc in docs:
        _insert(warm, doc)
        got_retrieve = run(warm, RETRIEVE, {"q": q})
        got_ranks = run(warm, RANKS)
    # two commits an insert; the first CALL after them is the one miss
    assert _exports() == (applied0 + len(docs), rebuilt0)
    for doc in docs:
        _insert(cold, doc)
    want_retrieve = run(cold, RETRIEVE, {"q": q})
    want_ranks = run(cold, RANKS)
    assert _exports() == (applied0 + len(docs), rebuilt0 + 1)
    for got, want in ((got_retrieve, want_retrieve), (got_ranks, want_ranks)):
        assert [r[0] for r in got] == [r[0] for r in want]
        np.testing.assert_allclose([r[1] for r in got],
                                   [r[1] for r in want], rtol=1e-6)
    assert {1000, 1001, 1002} <= {r[0] for r in run(
        warm, "CALL pagerank.get() YIELD node RETURN node.id")}


def test_no_delta_plan_is_derived_across_a_join(monkeypatch):
    # force the MXU path at test scale (and on the CPU backend)
    monkeypatch.setattr(pr_mod, "MXU_MIN_EDGES", 1)
    monkeypatch.setenv("MEMGRAPH_TPU_FORCE_MXU", "1")
    storage = InMemoryStorage(StorageConfig(
        storage_mode=StorageMode.IN_MEMORY_TRANSACTIONAL))
    rng = np.random.default_rng(3)
    n, e = 600, 3600
    acc = storage.access()
    et = storage.edge_type_mapper.name_to_id("E")
    vs = [acc.create_vertex() for _ in range(n)]
    for s, d in zip(rng.integers(0, n, e), rng.integers(0, n, e)):
        acc.create_edge(vs[s], vs[d], et)
    acc.commit()
    cache = GraphCache()

    def ranks():
        acc = storage.access()
        g = cache.get(acc)
        r, _, _ = pr_mod.pagerank(g, max_iterations=60, tol=0.0)
        acc.abort()
        return g, np.asarray(r)

    g1, _r1 = ranks()
    assert getattr(g1, "_mxu_base_self", False)
    acc = storage.access()
    nv = acc.create_vertex()
    for k in (1, 2, 3):
        acc.create_edge(nv, vs[k], et)
    acc.create_edge(vs[4], nv, et)
    acc.commit()
    applied0, rebuilt0 = _exports()
    g2, r2 = ranks()
    assert _exports() == (applied0 + 1, rebuilt0)     # the export: a splice
    assert g2.n_nodes == n + 1 and g2.node_gids[-1] == nv.gid
    base_g, changed = g2._delta_ctx
    assert base_g is g1 and nv.gid in changed
    assert pr_mod._edge_diff(base_g, g2, changed) is None
    # the plan: built in full on the new snapshot, which anchors the next
    assert g2._mxu_state[0] is not g1._mxu_state[0]
    assert getattr(g2, "_mxu_base_self", False)
    s2, d2, _w2 = g2.host_coo
    want = _reference_pagerank(s2, d2, n + 1)
    np.testing.assert_allclose(r2, want, rtol=3e-4, atol=1e-9)
    # an edge-only commit after the join rides a DeltaPlan again
    acc = storage.access()
    acc.create_edge(vs[5], acc.find_vertex(nv.gid), et)
    acc.commit()
    g3, r3 = ranks()
    assert g3._mxu_state[0] is g2._mxu_state[0]
    s3, d3, _w3 = g3.host_coo
    want = _reference_pagerank(s3, d3, n + 1)
    np.testing.assert_allclose(r3, want, rtol=3e-4, atol=1e-9)
