"""TPU kernel parity tests vs NetworkX/scipy oracles.

This is the SURVEY.md §4 test strategy step (1): pure-function kernel tests
against host reference implementations, with rank-match tolerances.
"""

import numpy as np
import pytest

import networkx as nx

from memgraph_tpu.ops import csr
from memgraph_tpu.ops.pagerank import pagerank, personalized_pagerank
from memgraph_tpu.ops.katz import katz_centrality, hits, degree_centrality
from memgraph_tpu.ops.components import (weakly_connected_components,
                                         strongly_connected_components)
from memgraph_tpu.ops.labelprop import label_propagation
from memgraph_tpu.ops.traversal import sssp, bfs_levels, khop_neighborhood
from memgraph_tpu.ops.knn import knn, IvfIndex
from memgraph_tpu.ops.walks import random_walks, walks_to_skipgram_pairs


def _random_digraph(n=60, p=0.08, seed=7, weights=False):
    rng = np.random.default_rng(seed)
    g = nx.gnp_random_graph(n, p, seed=seed, directed=True)
    src = np.array([u for u, v in g.edges()], dtype=np.int64)
    dst = np.array([v for u, v in g.edges()], dtype=np.int64)
    w = None
    if weights:
        w = rng.uniform(0.5, 2.0, size=len(src)).astype(np.float32)
        for (u, v), wi in zip(g.edges(), w):
            g[u][v]["weight"] = float(wi)
    graph = csr.from_coo(src, dst, w, n_nodes=n)
    return g, graph


def test_csr_padding_and_degrees():
    src = np.array([0, 0, 1, 2])
    dst = np.array([1, 2, 2, 0])
    g = csr.from_coo(src, dst)
    assert g.n_nodes == 3 and g.n_edges == 4
    assert g.n_pad >= 4 and (g.n_pad & (g.n_pad - 1)) == 0
    rp = np.asarray(g.row_ptr)
    assert rp[0] == 0 and rp[3] == 4  # 3 real rows cover all 4 edges
    deg = np.asarray(g.out_degree)
    assert list(deg[:3]) == [2, 1, 1]
    assert deg[3:].sum() == 0
    # rows sorted by destination for binary-search membership
    ci = np.asarray(g.col_idx)
    assert list(ci[rp[0]:rp[1]]) == [1, 2]


def test_pagerank_matches_networkx():
    g, graph = _random_digraph()
    ranks, err, iters = pagerank(graph, damping=0.85, tol=1e-10,
                                 max_iterations=200)
    expected = nx.pagerank(g, alpha=0.85, tol=1e-12, max_iter=500)
    got = np.asarray(ranks)
    exp = np.array([expected[i] for i in range(graph.n_nodes)])
    np.testing.assert_allclose(got, exp, atol=1e-5)
    assert abs(got.sum() - 1.0) < 1e-4


def test_pagerank_weighted_matches_networkx():
    g, graph = _random_digraph(weights=True)
    ranks, _, _ = pagerank(graph, damping=0.85, tol=1e-10, max_iterations=300)
    expected = nx.pagerank(g, alpha=0.85, tol=1e-12, max_iter=500,
                           weight="weight")
    exp = np.array([expected[i] for i in range(graph.n_nodes)])
    np.testing.assert_allclose(np.asarray(ranks), exp, atol=1e-5)


def test_pagerank_dangling_nodes():
    # node 2 dangles; mass must redistribute, ranks sum to 1
    graph = csr.from_coo(np.array([0, 1]), np.array([1, 2]), n_nodes=4)
    ranks, _, _ = pagerank(graph, tol=1e-12, max_iterations=300)
    got = np.asarray(ranks)
    g = nx.DiGraph()
    g.add_nodes_from(range(4))
    g.add_edges_from([(0, 1), (1, 2)])
    exp_d = nx.pagerank(g, alpha=0.85, tol=1e-12, max_iter=500)
    np.testing.assert_allclose(got, [exp_d[i] for i in range(4)], atol=1e-5)


def test_personalized_pagerank():
    g, graph = _random_digraph()
    ranks, _, _ = pagerank(graph, tol=1e-10)
    pranks, _, _ = personalized_pagerank(graph, [0], tol=1e-10,
                                         max_iterations=300)
    expected = nx.pagerank(g, alpha=0.85, personalization={0: 1.0},
                           tol=1e-12, max_iter=500)
    exp = np.array([expected[i] for i in range(graph.n_nodes)])
    np.testing.assert_allclose(np.asarray(pranks), exp, atol=1e-4)


def test_katz_matches_networkx():
    g, graph = _random_digraph(n=40, p=0.06)
    got, _, _ = katz_centrality(graph, alpha=0.05, beta=1.0, tol=1e-10,
                                max_iterations=500, normalized=True)
    expected = nx.katz_centrality(g, alpha=0.05, beta=1.0, tol=1e-12,
                                  max_iter=1000)
    exp = np.array([expected[i] for i in range(graph.n_nodes)])
    np.testing.assert_allclose(np.asarray(got), exp, atol=1e-5)


def test_hits_matches_networkx():
    g, graph = _random_digraph(n=30, p=0.15, seed=3)
    hub, auth, _, _ = hits(graph, tol=1e-12, max_iterations=500)
    eh, ea = nx.hits(g, tol=1e-12, max_iter=1000)
    # networkx normalizes by sum; ours by l2 — compare up to scale
    hub = np.asarray(hub)
    auth = np.asarray(auth)
    exp_h = np.array([eh[i] for i in range(graph.n_nodes)])
    exp_a = np.array([ea[i] for i in range(graph.n_nodes)])
    np.testing.assert_allclose(hub / max(hub.sum(), 1e-12), exp_h, atol=1e-4)
    np.testing.assert_allclose(auth / max(auth.sum(), 1e-12), exp_a, atol=1e-4)


def test_degree_centrality():
    g, graph = _random_digraph(n=25, p=0.2, seed=11)
    got = np.asarray(degree_centrality(graph, "total"))
    exp = np.array([(g.in_degree(i) + g.out_degree(i)) / (25 - 1)
                    for i in range(25)])
    np.testing.assert_allclose(got, exp, atol=1e-6)


def test_wcc_matches_networkx():
    g, graph = _random_digraph(n=80, p=0.02, seed=5)
    comp, _ = weakly_connected_components(graph)
    comp = np.asarray(comp)
    for component in nx.weakly_connected_components(g):
        ids = {comp[v] for v in component}
        assert len(ids) == 1
    # distinct components get distinct labels
    assert len(set(comp.tolist())) == nx.number_weakly_connected_components(g)


def test_scc_matches_networkx():
    g, graph = _random_digraph(n=50, p=0.06, seed=9)
    comp = np.asarray(strongly_connected_components(graph))
    nx_comps = list(nx.strongly_connected_components(g))
    for component in nx_comps:
        ids = {comp[v] for v in component}
        assert len(ids) == 1, f"SCC split: {component} -> {ids}"
    assert len(set(comp.tolist())) == len(nx_comps)


def test_scc_chain_of_cycles():
    # C0: 0-1-2, C1: 3-4-5, bridge 2->3; two SCCs
    src = np.array([0, 1, 2, 3, 4, 5, 2])
    dst = np.array([1, 2, 0, 4, 5, 3, 3])
    graph = csr.from_coo(src, dst, n_nodes=6)
    comp = np.asarray(strongly_connected_components(graph))
    assert comp[0] == comp[1] == comp[2]
    assert comp[3] == comp[4] == comp[5]
    assert comp[0] != comp[3]


def test_scc_long_cycle():
    """Regression: one 500-node directed cycle is ONE SCC (needs inner
    propagation to run to fixpoint, beyond any small iteration cap)."""
    n = 500
    src = np.arange(n)
    dst = (np.arange(n) + 1) % n
    graph = csr.from_coo(src, dst, n_nodes=n)
    comp = np.asarray(strongly_connected_components(graph))
    assert len(set(comp.tolist())) == 1


def test_ivf_small_corpus():
    rng = np.random.default_rng(4)
    corpus = rng.normal(size=(10, 8)).astype(np.float32)  # < default clusters
    index = IvfIndex(corpus)
    _, ids = index.search(corpus[:2], k=3)
    assert ids.shape == (2, 3)


def test_label_propagation_two_cliques():
    # two 5-cliques joined by a single bridge edge
    edges = []
    for base in (0, 5):
        for i in range(5):
            for j in range(i + 1, 5):
                edges.append((base + i, base + j))
    edges.append((0, 5))
    src = np.array([e[0] for e in edges])
    dst = np.array([e[1] for e in edges])
    graph = csr.from_coo(src, dst, n_nodes=10)
    labels, _ = label_propagation(graph, max_iterations=50)
    labels = np.asarray(labels)
    assert len(set(labels[:5])) == 1
    assert len(set(labels[5:])) == 1
    assert labels[0] != labels[5]


def test_sssp_matches_networkx():
    g, graph = _random_digraph(n=40, p=0.1, seed=13, weights=True)
    dist, _ = sssp(graph, source=0, weighted=True, directed=True)
    dist = np.asarray(dist)
    exp = nx.single_source_dijkstra_path_length(g, 0, weight="weight")
    for v in range(40):
        if v in exp:
            assert abs(dist[v] - exp[v]) < 1e-4, v
        else:
            assert np.isinf(dist[v]), v


def test_bfs_levels():
    g, graph = _random_digraph(n=40, p=0.1, seed=13)
    levels, _ = bfs_levels(graph, source=0)
    levels = np.asarray(levels)
    exp = nx.single_source_shortest_path_length(g, 0)
    for v in range(40):
        assert levels[v] == exp.get(v, -1), v


def test_khop_neighborhood():
    src = np.array([0, 1, 2, 3])
    dst = np.array([1, 2, 3, 4])
    graph = csr.from_coo(src, dst, n_nodes=6)
    mask = np.asarray(khop_neighborhood(graph, [0], k=2, directed=True))
    assert list(mask[:6]) == [True, True, True, False, False, False]


def test_knn_cosine():
    rng = np.random.default_rng(0)
    corpus = rng.normal(size=(100, 16)).astype(np.float32)
    queries = corpus[:3] + 0.001 * rng.normal(size=(3, 16)).astype(np.float32)
    scores, idx = knn(corpus, queries, k=5, metric="cosine")
    idx = np.asarray(idx)
    for qi in range(3):
        assert idx[qi, 0] == qi  # nearest neighbor of a near-copy is itself


def test_knn_l2():
    rng = np.random.default_rng(1)
    corpus = rng.normal(size=(50, 8)).astype(np.float32)
    q = rng.normal(size=(4, 8)).astype(np.float32)
    _, idx = knn(corpus, q, k=3, metric="l2sq")
    idx = np.asarray(idx)
    d = ((corpus[None, :, :] - q[:, None, :]) ** 2).sum(-1)
    exp = np.argsort(d, axis=1)[:, :3]
    # the 2q·x - ||x||^2 formulation can swap float near-ties; compare the
    # achieved distances, not the indices
    got_d = np.take_along_axis(d, idx, axis=1)
    exp_d = np.take_along_axis(d, exp, axis=1)
    np.testing.assert_allclose(got_d, exp_d, atol=1e-2)


def test_ivf_recall():
    rng = np.random.default_rng(2)
    corpus = rng.normal(size=(500, 16)).astype(np.float32)
    q = rng.normal(size=(5, 16)).astype(np.float32)
    index = IvfIndex(corpus, n_clusters=8)
    _, ids = index.search(q, k=10, n_probe=8)  # probe all cells → exact
    _, exact = knn(corpus, q, k=10, metric="cosine")
    exact = np.asarray(exact)
    for qi in range(5):
        assert set(ids[qi]) == set(exact[qi])


def test_random_walks_follow_edges():
    src = np.array([0, 1, 2, 3])
    dst = np.array([1, 2, 3, 0])  # directed cycle
    graph = csr.from_coo(src, dst, n_nodes=4)
    walks = np.asarray(random_walks(graph, [0, 1, 2, 3], length=8))
    assert walks.shape == (4, 9)
    for b in range(4):
        for t in range(8):
            assert walks[b, t + 1] == (walks[b, t] + 1) % 4


def test_random_walks_stall_at_sink():
    graph = csr.from_coo(np.array([0]), np.array([1]), n_nodes=2)
    walks = np.asarray(random_walks(graph, [0], length=5))
    assert list(walks[0]) == [0, 1, 1, 1, 1, 1]


def test_skipgram_pairs():
    import jax.numpy as jnp
    walks = jnp.array([[0, 1, 2, 3]])
    pairs = np.asarray(walks_to_skipgram_pairs(walks, window=1))
    real = {tuple(p) for p in pairs if p[0] != -1 and p[1] != -1}
    assert real == {(1, 0), (2, 1), (3, 2), (0, 1), (1, 2), (2, 3)}
