"""BFS, SSSP, WCC and CDLP as served, per vertex, against a plain
reference of the Graph500 and LDBC Graphalytics specifications
(tests/graphalytics_reference.py), on seeded Kronecker graphs loaded
through Cypher as the graph500_s17 deployment loads them: ``:Vertex
{id}`` created in ascending id, one ``:EDGE {weight}`` per undirected
pair from the lower id to the higher, dyadic weights.
"""

import socket

import numpy as np
import pytest
from scipy.sparse import coo_matrix
from scipy.sparse.csgraph import dijkstra

import graphalytics_reference as ref
from memgraph_tpu.observability.metrics import global_metrics
from memgraph_tpu.ops import semiring as S
from memgraph_tpu.ops.delta import GLOBAL_WARM_POOL
from memgraph_tpu.query import Interpreter, InterpreterContext
from memgraph_tpu.storage import InMemoryStorage

#: (scale, seed) of the graphs every kernel is held on
GRAPHS = [(8, 2_147_483_659), (9, 11), (10, 12), (11, 13)]
ROUNDS = 10

BFS = ("MATCH (s:Vertex {id: $root}) CALL bfs.get(s, false) "
       "YIELD node, level RETURN node.id AS id, level")
SSSP = ("MATCH (s:Vertex {id: $root}) CALL sssp.get(s, 'weight', false) "
        "YIELD node, distance RETURN node.id AS id, distance")
WCC = "CALL wcc.get() YIELD node, component_id RETURN node.id, component_id"
CDLP = ("CALL label_propagation.get($rounds) YIELD node, community_id "
        "RETURN node.id, community_id")
ADD_EDGES = ("UNWIND $edges AS e MATCH (a:Vertex {id: e[0]}), "
             "(b:Vertex {id: e[1]}) CREATE (a)-[:EDGE {weight: e[2]}]->(b)")


def execute(ictx, query, params=None):
    return Interpreter(ictx).execute(query, params)[1]


def load(graph, run):
    n, src, dst, weights, _ = graph
    run("CREATE INDEX ON :Vertex(id)")
    run("UNWIND range(0, $n - 1) AS i CREATE (:Vertex {id: i})", {"n": n})
    run(ADD_EDGES, {"edges": edges(src, dst, weights)})


def edges(src, dst, weights):
    return [[int(a), int(b), float(w)] for a, b, w in zip(src, dst, weights)]


def loaded(graph):
    ictx = InterpreterContext(InMemoryStorage())
    load(graph, lambda q, p=None: execute(ictx, q, p))
    return ictx


@pytest.fixture(scope="module", params=GRAPHS,
                ids=[f"scale{s}" for s, _ in GRAPHS])
def served(request):
    scale, seed = request.param
    graph = ref.kronecker(scale, 16, seed)
    return graph, loaded(graph)


def roots(graph, count=2):
    rng = np.random.default_rng(graph[0])
    return rng.choice(graph[0], size=count, replace=False).tolist()


def per_vertex(rows, n, fill):
    out = np.full(n, fill, dtype=np.float64)
    for vid, value in rows:
        out[vid] = value
    return out


def community_ids(labels):
    """The procedure's community_id for each vertex of a labelling by
    ids: 1 + the rank of its label among the labels there are."""
    return np.unique(labels, return_inverse=True)[1] + 1


def counter(name):
    return {n: v for n, _k, v in global_metrics.snapshot()}.get(name, 0.0)


# --------------------------------------------------------------------------
# each kernel, per vertex, in process
# --------------------------------------------------------------------------

def test_bfs_levels_equal_the_reference(served):
    (n, src, dst, _, _), ictx = served
    for root in roots(served[0]):
        got = per_vertex(execute(ictx, BFS, {"root": root}), n, -1)
        assert np.array_equal(got, ref.bfs_levels(n, src, dst, root))


def test_sssp_distances_equal_the_reference(served):
    """Dyadic weights: float32 sums are exact, so the tolerance is 0."""
    (n, src, dst, weights, _), ictx = served
    for root in roots(served[0]):
        got = per_vertex(execute(ictx, SSSP, {"root": root}), n, np.inf)
        assert np.array_equal(got, ref.sssp(n, src, dst, weights, root))


def by_least_id(component_ids):
    """The partition that per-vertex component ids give, each vertex
    named by the least id of its component, as the reference names it."""
    least = {}
    for vid, c in enumerate(np.asarray(component_ids).tolist()):
        least.setdefault(c, vid)
    return np.asarray([least[c] for c in np.asarray(component_ids).tolist()])


def test_wcc_partition_equals_the_reference(served):
    (n, src, dst, _, _), ictx = served
    got = per_vertex(execute(ictx, WCC), n, -1)
    assert np.array_equal(by_least_id(got), ref.wcc(n, src, dst))


def test_cdlp_labels_equal_the_reference(served):
    (n, src, dst, _, _), ictx = served
    got = per_vertex(execute(ictx, CDLP, {"rounds": ROUNDS}), n, -1)
    assert np.array_equal(got, community_ids(ref.cdlp(n, src, dst,
                                                      ROUNDS)))


# --------------------------------------------------------------------------
# once through Bolt
# --------------------------------------------------------------------------

def test_the_four_kernels_through_bolt():
    from memgraph_tpu.server.bolt import BoltServer
    from memgraph_tpu.server.client import BoltClient
    graph = ref.kronecker(9, 16, 21)
    n, src, dst, weights, _ = graph
    sock = socket.socket()
    sock.bind(("127.0.0.1", 0))
    port = sock.getsockname()[1]
    sock.close()
    server = BoltServer(InterpreterContext(InMemoryStorage()), "127.0.0.1",
                        port)
    _thread, loop = server.run_in_thread()
    client = BoltClient(port=port)
    try:
        load(graph, lambda q, p=None: client.execute(q, p or {}))
        root = roots(graph, 1)[0]
        rows = client.execute(BFS, {"root": root})[1]
        assert np.array_equal(per_vertex(rows, n, -1),
                              ref.bfs_levels(n, src, dst, root))
        rows = client.execute(SSSP, {"root": root})[1]
        assert np.array_equal(per_vertex(rows, n, np.inf),
                              ref.sssp(n, src, dst, weights, root))
        rows = client.execute(WCC)[1]
        assert np.array_equal(by_least_id(per_vertex(rows, n, -1)),
                              ref.wcc(n, src, dst))
        rows = client.execute(CDLP, {"rounds": ROUNDS})[1]
        assert np.array_equal(per_vertex(rows, n, -1), community_ids(
            ref.cdlp(n, src, dst, ROUNDS)))
    finally:
        client.close()
        loop.call_soon_threadsafe(loop.stop)


# --------------------------------------------------------------------------
# after a commit
# --------------------------------------------------------------------------

def new_pairs(graph, count, seed):
    """`count` pairs of loaded vertices that are not related yet."""
    n, src, dst, _, _ = graph
    have = set(zip(src.tolist(), dst.tolist()))
    rng = np.random.default_rng(seed)
    out = []
    while len(out) < count:
        a, b = sorted(rng.choice(n, 2, replace=False).tolist())
        if (a, b) not in have:
            have.add((a, b))
            out.append([a, b, float(rng.integers(0, 1024)) / 1024])
    return out


def test_cdlp_after_an_adds_only_commit_equals_a_cold_run():
    """Ten rounds from the previous labels are not ten rounds from the
    ids: a read after a commit must equal the cold answer on the graph
    as of that read. Edge factor 4 leaves the election unsettled after
    ten rounds, where a seeded one lands elsewhere (on this graph the
    first two of the three bursts did, from the previous labels)."""
    graph = ref.kronecker(9, 4, 31)
    n, src, dst, _, _ = graph
    ictx = loaded(graph)
    execute(ictx, CDLP, {"rounds": ROUNDS})
    added = []
    for seed in (100, 101, 102):
        added += new_pairs(graph, 64, seed)
        execute(ictx, ADD_EDGES, {"edges": added[-64:]})
        after = per_vertex(execute(ictx, CDLP, {"rounds": ROUNDS}), n, -1)
        GLOBAL_WARM_POOL.clear()
        cold = per_vertex(execute(ictx, CDLP, {"rounds": ROUNDS}), n, -1)
        assert np.array_equal(after, cold)
        a = np.asarray(added)[:, :2].astype(np.int64)
        assert np.array_equal(after, community_ids(ref.cdlp(
            n, np.concatenate([src, a[:, 0]]),
            np.concatenate([dst, a[:, 1]]), ROUNDS)))


def test_an_unchanged_graph_serves_the_stored_labels():
    graph = ref.kronecker(8, 16, 41)
    ictx = loaded(graph)
    first = execute(ictx, CDLP, {"rounds": ROUNDS})
    iterations = counter("analytics.cdlp.iterations_total")
    assert execute(ictx, CDLP, {"rounds": ROUNDS}) == first
    assert counter("analytics.cdlp.iterations_total") == iterations


def test_the_online_variant_keeps_its_warm_start():
    graph = ref.kronecker(8, 16, 42)
    ictx = loaded(graph)
    query = CDLP.replace("label_propagation", "community_detection_online")
    execute(ictx, query, {"rounds": ROUNDS})
    execute(ictx, ADD_EDGES, {"edges": new_pairs(graph, 8, 43)})
    warm = counter("delta.warm_start_total")
    execute(ictx, query, {"rounds": ROUNDS})
    assert counter("delta.warm_start_total") == warm + 1


def test_wcc_after_adds_only_commits_equals_the_reference():
    """WCC keeps its warm start: min-labels merge and never split."""
    graph = ref.kronecker(9, 16, 51)
    n, src, dst, _, _ = graph
    ictx = loaded(graph)
    execute(ictx, WCC)
    added = []
    for seed in (52, 53):
        added += new_pairs(graph, 32, seed)
        execute(ictx, ADD_EDGES, {"edges": added[-32:]})
        got = per_vertex(execute(ictx, WCC), n, -1)
        a = np.asarray(added)[:, :2].astype(np.int64)
        assert np.array_equal(by_least_id(got), ref.wcc(
            n, np.concatenate([src, a[:, 0]]), np.concatenate([dst, a[:, 1]])))


# --------------------------------------------------------------------------
# the procedures' own contract
# --------------------------------------------------------------------------

def test_sssp_is_directed_unless_told(served):
    (n, src, dst, weights, _), ictx = served
    root = roots(served[0], 1)[0]
    rows = execute(ictx, "MATCH (s:Vertex {id: $root}) "
                         "CALL sssp.get(s, 'weight') YIELD node, distance "
                         "RETURN node.id, distance", {"root": root})
    matrix = coo_matrix((weights, (src, dst)), shape=(n, n)).tocsr()
    assert np.array_equal(per_vertex(rows, n, np.inf),
                          dijkstra(matrix, indices=root))
    undirected = per_vertex(execute(ictx, SSSP, {"root": root}), n, np.inf)
    assert np.isfinite(undirected).sum() > np.isfinite(
        per_vertex(rows, n, np.inf)).sum()


def test_undirected_bfs_runs_a_program_of_its_own(served):
    ictx = served[1]
    root = roots(served[0], 1)[0]
    execute(ictx, BFS, {"root": root})
    execute(ictx, SSSP, {"root": root})
    names = {fn.__name__ for fn in S._FIXPOINT_CACHE.values()}
    assert {"fixpoint_bfs_undirected", "fixpoint_sssp"} <= names


@pytest.mark.parametrize("algo,query,kernel", [
    ("bfs", BFS, "memgraph_tpu.ops.traversal.bfs_levels"),
    ("sssp", SSSP, "memgraph_tpu.ops.traversal.sssp"),
    ("wcc", WCC,
     "memgraph_tpu.ops.components.weakly_connected_components"),
    ("cdlp", CDLP, "memgraph_tpu.ops.labelprop.label_propagation"),
])
def test_counters_and_spans_move_by_one_call(algo, query, kernel,
                                             monkeypatch):
    """A call moves analytics.<algo>.calls_total by one, its
    iterations_total by what the kernel returned, and closes one
    analytics.launch, analytics.device_wait and analytics.rows."""
    import importlib
    module_name, name = kernel.rsplit(".", 1)
    module = importlib.import_module(module_name)
    returned = []
    inner = getattr(module, name)

    def watched(*args, **kw):
        out = inner(*args, **kw)
        returned.append(out[-1])
        return out
    monkeypatch.setattr(module, name, watched)
    graph = ref.kronecker(8, 16, 61)
    ictx = loaded(graph)
    names = [f"analytics.{algo}.calls_total",
             f"analytics.{algo}.iterations_total"] + [
        f"span.analytics.{phase}.count"
        for phase in ("launch", "device_wait", "rows")]
    before = [counter(n) for n in names]
    execute(ictx, query, {"root": roots(graph, 1)[0], "rounds": ROUNDS})
    moved = [counter(n) - b for n, b in zip(names, before)]
    assert len(returned) == 1 and returned[0] > 0
    assert moved == [1, returned[0], 1, 1, 1]


@pytest.mark.parametrize("weight_argument,elections", [("", 1),
                                                       (", 'weight'", 0)],
                         ids=["unweighted", "weighted"])
def test_cdlp_elects_by_counts_unless_the_view_is_weighted(
        weight_argument, elections):
    """A view without a weight property runs the election by counts,
    once a call; a weighted view sums its weights and never counts."""
    graph = ref.kronecker(8, 16, 81)
    ictx = loaded(graph)
    names = ("analytics.labelprop.count_election_total",
             "analytics.cdlp.calls_total")
    before = [counter(n) for n in names]
    execute(ictx, CDLP.replace("$rounds", "$rounds" + weight_argument),
            {"rounds": ROUNDS})
    assert [counter(n) - b for n, b in zip(names, before)] == \
        [elections, 1]


def test_index_order_is_creation_order():
    """CDLP's smallest-label rule is Graphalytics's smallest-id rule only
    where a vertex's dense index follows its id: the device graph's
    index order is the order the vertices were created in."""
    from memgraph_tpu.ops.csr import GLOBAL_GRAPH_CACHE
    ictx = InterpreterContext(InMemoryStorage())
    ids = np.random.default_rng(71).permutation(300).tolist()
    execute(ictx, "UNWIND $ids AS i CREATE (:Vertex {id: i})", {"ids": ids})
    execute(ictx, "MATCH (a:Vertex), (b:Vertex) WHERE b.id = a.id + 1 "
                  "CREATE (a)-[:EDGE {weight: 0.5}]->(b)")
    storage = ictx.storage
    accessor = storage.access()
    graph = GLOBAL_GRAPH_CACHE.get(accessor)
    prop = storage.property_mapper.maybe_name_to_id("id")
    by_gid = {v.gid: v.get_property(prop) for v in accessor.vertices()}
    assert [by_gid[int(g)] for g in graph.node_gids] == ids


def test_two_views_of_one_graph_each_splice_their_own_snapshot():
    """BFS reads the unweighted view and SSSP the weighted one after
    every commit: each view's newest snapshot stays the base of its next
    delta export, whichever view stored last."""
    graph = ref.kronecker(8, 16, 81)
    ictx = loaded(graph)
    root = roots(graph, 1)[0]
    execute(ictx, BFS, {"root": root})
    execute(ictx, SSSP, {"root": root})
    for seed in (82, 83):
        execute(ictx, ADD_EDGES, {"edges": new_pairs(graph, 8, seed)})
        applied = counter("delta.export_applied_total")
        rebuilt = counter("delta.export_rebuild_total")
        execute(ictx, BFS, {"root": root})
        execute(ictx, SSSP, {"root": root})
        assert counter("delta.export_applied_total") == applied + 2
        assert counter("delta.export_rebuild_total") == rebuilt
