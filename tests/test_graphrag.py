"""GraphRAG hybrid pipeline e2e: streaming ingest → kNN → expand → rerank.

Covers BASELINE.md config #5 end-to-end: documents arrive over a stream,
get embeddings, and hybrid retrieval composes vector similarity with graph
structure.
"""

import json
import time

import pytest

from memgraph_tpu.query.interpreter import Interpreter, InterpreterContext
from memgraph_tpu.storage import InMemoryStorage


@pytest.fixture
def db():
    return InterpreterContext(InMemoryStorage())


def run(db, q, params=None):
    _, rows, _ = Interpreter(db).execute(q, params)
    return rows


def _seed_docs(db):
    # topic clusters in embedding space: tpu-ish near [1,0,...],
    # cooking-ish near [0,1,...]; citation edges inside the tpu cluster
    docs = [
        ("tpu kernels", [1.0, 0.1, 0.0, 0.0]),
        ("xla compiler", [0.9, 0.2, 0.0, 0.1]),
        ("mesh sharding", [0.8, 0.0, 0.2, 0.0]),
        ("pasta recipe", [0.0, 1.0, 0.1, 0.0]),
        ("bread baking", [0.1, 0.9, 0.0, 0.1]),
    ]
    for title, emb in docs:
        run(db, "CREATE (:Doc {title: $t, emb: $e})",
            {"t": title, "e": emb})
    run(db, """MATCH (a:Doc {title:'tpu kernels'}),
                     (b:Doc {title:'xla compiler'}),
                     (c:Doc {title:'mesh sharding'})
               CREATE (a)-[:CITES]->(b), (b)-[:CITES]->(c)""")


def test_graphrag_retrieve(db):
    _seed_docs(db)
    rows = run(db, "CALL graphrag.retrieve('emb', [1.0, 0.0, 0.0, 0.0], 2, "
                   "2, 5) YIELD node, score, seed_similarity "
                   "RETURN node.title, score, seed_similarity")
    titles = [r[0] for r in rows]
    # the tpu cluster dominates; cooking docs are absent (not in 2-hop of seeds)
    assert "tpu kernels" in titles
    assert "mesh sharding" in titles  # pulled in by graph structure
    assert "pasta recipe" not in titles
    # scores descending
    scores = [r[1] for r in rows]
    assert scores == sorted(scores, reverse=True)
    # seeds carry their vector similarity
    seed_sims = {r[0]: r[2] for r in rows}
    assert seed_sims["tpu kernels"] > 0.9


def test_graphrag_context(db):
    _seed_docs(db)
    rows = run(db, "MATCH (n:Doc) WHERE n.title CONTAINS 'tpu' OR "
                   "n.title CONTAINS 'xla' WITH collect(n) AS ns "
                   "CALL graphrag.context(ns) YIELD context RETURN context")
    text = rows[0][0]
    assert "tpu kernels" in text and "CITES" in text


def test_graphrag_schema(db):
    _seed_docs(db)
    rows = run(db, "CALL graphrag.schema() YIELD schema RETURN schema")
    text = rows[0][0]
    assert ":Doc" in text and "CITES" in text and "title" in text


def test_graphrag_with_streaming_ingest(db, tmp_path):
    """The full config-5 shape: stream ingest feeding hybrid retrieval."""
    _seed_docs(db)
    feed = tmp_path / "docs.jsonl"
    feed.write_text(json.dumps({
        "query": "CREATE (d:Doc {title: $title, emb: $emb}) "
                 "WITH d MATCH (x:Doc {title: 'tpu kernels'}) "
                 "CREATE (d)-[:CITES]->(x)",
        "parameters": {"title": "pallas guide",
                       "emb": [0.95, 0.05, 0.1, 0.0]}}) + "\n")
    run(db, f"CREATE FILE STREAM docs TOPICS '{feed}' "
            f"TRANSFORM transform.cypher BATCH_INTERVAL 50")
    run(db, "START STREAM docs")
    deadline = time.time() + 5
    while time.time() < deadline:
        if run(db, "MATCH (n:Doc {title:'pallas guide'}) RETURN count(n)") \
                == [[1]]:
            break
        time.sleep(0.05)
    run(db, "STOP STREAM docs")
    rows = run(db, "CALL graphrag.retrieve('emb', [1.0, 0.0, 0.0, 0.0], 2, "
                   "2, 6) YIELD node RETURN node.title")
    assert "pallas guide" in [r[0] for r in rows]


def test_vector_index_incremental_maintenance(db):
    """New/updated/deleted embeddings appear in search without full rebuild."""
    _seed_docs(db)
    rows = run(db, "CALL vector_search.search('emb', [1.0,0.0,0.0,0.0], 10) "
                   "YIELD node RETURN count(node)")
    n0 = rows[0][0]
    run(db, "CREATE (:Doc {title: 'new doc', emb: [0.99, 0.0, 0.0, 0.0]})")
    rows = run(db, "CALL vector_search.search('emb', [1.0,0.0,0.0,0.0], 10) "
                   "YIELD node, similarity RETURN node.title, similarity "
                   "ORDER BY similarity DESC")
    assert len(rows) == n0 + 1
    assert rows[0][0] in ("new doc", "tpu kernels")
    # update an embedding: it must re-rank
    run(db, "MATCH (n:Doc {title: 'pasta recipe'}) "
            "SET n.emb = [1.0, 0.0, 0.0, 0.0]")
    rows = run(db, "CALL vector_search.search('emb', [1.0,0.0,0.0,0.0], 3) "
                   "YIELD node RETURN node.title")
    assert "pasta recipe" in [r[0] for r in rows]
    # delete: it must disappear
    run(db, "MATCH (n:Doc {title: 'pasta recipe'}) DETACH DELETE n")
    rows = run(db, "CALL vector_search.search('emb', [1.0,0.0,0.0,0.0], 10) "
                   "YIELD node RETURN node.title")
    assert "pasta recipe" not in [r[0] for r in rows]
    # index info reflects maintained state
    rows = run(db, "CALL vector_search.show_index_info() "
                   "YIELD property, size RETURN property, size")
    assert rows == [["emb", n0]]


def test_vector_search_ppr_search_in_process(db):
    """ANN seed -> PPR expansion -> rerank, in-process fallback path (no
    resident server configured)."""
    _seed_docs(db)
    rows = run(db, "CALL vector_search.ppr_search('emb', "
                   "[1.0, 0.0, 0.0, 0.0], 2, 5) "
                   "YIELD node, score, seed_similarity "
                   "RETURN node.title, score, seed_similarity")
    titles = [r[0] for r in rows]
    assert "tpu kernels" in titles
    scores = [r[1] for r in rows]
    assert scores == sorted(scores, reverse=True)


def test_graphrag_retrieve_through_resident_server(db, tmp_path,
                                                   monkeypatch):
    """The serving-plane round trip: retrieve routes its PPR leg through
    an in-thread kernel server (env-configured socket), results ranked
    by the server's device-extracted top-k; a repeat rides the result
    cache; kernel_routed counter moves."""
    import threading as _threading
    import time as _time

    from memgraph_tpu.observability.metrics import global_metrics
    from memgraph_tpu.server.kernel_server import (KernelClient,
                                                   KernelServer)

    _seed_docs(db)
    sock = str(tmp_path / "ks.sock")
    srv = KernelServer(sock, wedge_after_s=30)
    _threading.Thread(target=srv.serve_forever, daemon=True).start()
    deadline = _time.monotonic() + 120
    probe = None
    while _time.monotonic() < deadline:
        try:
            probe = KernelClient(sock, timeout=60)
            break
        except OSError:
            _time.sleep(0.05)
    assert probe is not None

    monkeypatch.setenv("MEMGRAPH_TPU_ANALYTICS_KERNEL_SERVER", sock)
    before = {n: v for n, _k, v in global_metrics.snapshot()}
    try:
        rows = run(db, "CALL graphrag.retrieve('emb', "
                       "[1.0, 0.0, 0.0, 0.0], 2, 2, 5) "
                       "YIELD node, score RETURN node.title, score")
        titles = [r[0] for r in rows]
        assert "tpu kernels" in titles
        assert [r[1] for r in rows] == sorted((r[1] for r in rows),
                                              reverse=True)
        after = {n: v for n, _k, v in global_metrics.snapshot()}
        assert after.get("analytics.kernel_routed_total", 0) > \
            before.get("analytics.kernel_routed_total", 0)
        # the repeat rides the serving plane's result cache
        hit_before = after.get("ppr.cache_hit_total", 0)
        run(db, "CALL graphrag.retrieve('emb', [1.0, 0.0, 0.0, 0.0], 2, "
                "2, 5) YIELD node RETURN node.title")
        final = {n: v for n, _k, v in global_metrics.snapshot()}
        assert final.get("ppr.cache_hit_total", 0) > hit_before
    finally:
        probe.shutdown()
        probe.close()


# --------------------------------------------------------------------------
# PR 33: the search kernel's precision, the phase spans, and the CALL
# against the benchmark's float64 reference
# --------------------------------------------------------------------------

import os  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

_BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks", "chipbench")


def _span_counts():
    from memgraph_tpu.observability.metrics import global_metrics
    return {n: v for n, _k, v in global_metrics.snapshot()
            if n.startswith("span.") and n.endswith(".count")}


def _closes(before, after, name):
    key = f"span.{name}.count"
    return after.get(key, 0.0) - before.get(key, 0.0)


#: |score - float64 score| a float32 product may carry at width 384 on
#: rows of norm <= 1: 384 products of magnitude <= 1 summed in float32
#: stay within a few units of 6e-8 (measured here: under 5e-7), while
#: operands rounded to bfloat16 (8 bits of mantissa, 4e-3 relative a
#: component) carry about 1e-4. 2e-6 lies between with room on both sides.
KNN_SCORE_TOL = 2e-6


@pytest.mark.parametrize("metric", ["cosine", "l2sq", "dot"])
def test_knn_scores_are_float32_products(metric):
    import jax.numpy as jnp
    from memgraph_tpu.ops.knn import knn
    rng = np.random.default_rng(33)
    corpus = rng.standard_normal((5_000, 384)) / np.sqrt(384)
    queries = rng.standard_normal((3, 384)) / np.sqrt(384)
    k = 10

    def exact(x, q):
        if metric == "cosine":
            x = x / np.linalg.norm(x, axis=1, keepdims=True)
            q = q / np.linalg.norm(q, axis=1, keepdims=True)
        scores = q @ x.T
        if metric == "l2sq":
            scores = 2.0 * scores - np.sum(x ** 2, axis=1)[None, :]
        return scores

    want = exact(corpus, queries)
    scores, idx = knn(corpus.astype(np.float32), queries.astype(np.float32),
                      k=k, metric=metric)
    scores, idx = np.asarray(scores, np.float64), np.asarray(idx)
    for row in range(len(queries)):
        best = np.sort(want[row])[::-1][:k]
        # the values of the returned rows, and the rows themselves: a
        # swap is allowed only between scores closer than the tolerance
        assert np.abs(scores[row] - want[row][idx[row]]).max() < KNN_SCORE_TOL
        assert np.abs(want[row][idx[row]] - best).max() < KNN_SCORE_TOL
        assert len(set(idx[row].tolist())) == k
    # the tolerance is tight enough that bfloat16 operands fail it
    low = exact(*(x.astype(jnp.bfloat16).astype(np.float64)
                  for x in (corpus, queries)))
    assert np.abs(low - want).max() > 10 * KNN_SCORE_TOL


def _embedded_graph(db, n=60, n_edges=240, width=16, seed=5):
    rng = np.random.default_rng(seed)
    vectors = rng.standard_normal((n, width))
    run(db, "CREATE INDEX ON :User(id)")
    run(db, "UNWIND $rows AS r CREATE (:User {id: r.id, embedding: r.v})",
        {"rows": [{"id": i, "v": vectors[i].tolist()} for i in range(n)]})
    src = rng.integers(0, n - 1, n_edges)       # the last node is dangling
    dst = rng.integers(0, n, n_edges)
    run(db, "UNWIND $pairs AS p MATCH (a:User {id: p[0]}), (b:User {id: p[1]}) "
            "CREATE (a)-[:FRIEND]->(b)",
        {"pairs": np.stack([src, dst], axis=1).tolist()})
    return vectors, src, dst, rng


def test_retrieve_matches_the_reference_equations(db):
    """graphrag.retrieve against benchmarks/chipbench/semantics/graphrag.py
    (float64: seeds, 2-hop mask in both directions, personalized PageRank
    with restart and dangling mass on the seeds) on seeded data, before
    and after a committed insert."""
    if _BENCH not in sys.path:
        sys.path.insert(0, _BENCH)
    import seams
    sem = seams.load_module(None, "semantics", "graphrag")
    vectors, src, dst, rng = _embedded_graph(db)
    state = sem.RagState(len(vectors), src, dst, vectors)
    text = ("CALL graphrag.retrieve('embedding', $q, 5, 2, 12) "
            "YIELD node, score RETURN node.id, score")
    for step in range(2):
        q = rng.standard_normal(vectors.shape[1])
        rows = run(db, text, {"q": q.tolist()})
        want = sem.hybrid_scores(state, q, seeds_k=5, hops=2)
        assert len(rows) == 12
        ids = [r[0] for r in rows]
        got = np.asarray([r[1] for r in rows])
        assert (np.diff(got) <= 0).all()
        # float32 fixpoint stopped at tol 1e-6 against float64 at 1e-10
        assert np.abs(got - want[ids]).max() < 2e-6
        assert np.abs(np.sort(want)[::-1][:12] - want[ids]).max() < 2e-6
        # then a document arrives, and the next retrieval must see it
        new = {"id": len(vectors) + 1 + step, "v": q.tolist(),
               "friends": [int(ids[0]), int(ids[1]), 3]}
        run(db, "CREATE (u:User {id: $id, embedding: $v}) WITH u "
                "UNWIND $friends AS f MATCH (b:User {id: f}) "
                "CREATE (u)-[:FRIEND]->(b)", new)
        sem.apply("doc_insert", state, new)


def test_each_retrieval_span_closes_once_per_call(db):
    _embedded_graph(db)
    q = [1.0] * 16
    text = ("CALL graphrag.retrieve('embedding', $q, 5, 2, 10) "
            "YIELD node RETURN node.id")
    run(db, text, {"q": q})
    before = _span_counts()
    for _ in range(3):
        run(db, text, {"q": q})
    after = _span_counts()
    for name in ("vector.index", "vector.search", "graphrag.expand",
                 "graphrag.ppr", "graphrag.rows", "analytics.export"):
        assert _closes(before, after, name) == 3, name
    # nothing was written in between: the index was a hit every time
    assert _closes(before, after, "vector.refresh") == 0
    assert _closes(before, after, "vector.build") == 0
    from memgraph_tpu.observability import trace as T
    for name in ("vector.index", "vector.refresh", "vector.build",
                 "vector.search", "graphrag.expand", "graphrag.ppr",
                 "graphrag.rows"):
        assert name in T.SPAN_NAMES and name in T.PHASES
