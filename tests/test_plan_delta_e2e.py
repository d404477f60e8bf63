"""Commit-then-CALL plan refresh: a topology-mutating commit must NOT
force a full MXU replan — the next pagerank call derives an O(delta)
side-plan from the storage change log.
"""

import numpy as np
import pytest

from memgraph_tpu.ops import pagerank as pr_mod
from memgraph_tpu.ops.csr import GraphCache
from memgraph_tpu.storage import InMemoryStorage, StorageConfig, StorageMode


def _scipy_pagerank(src, dst, n, iters=60, damping=0.85):
    import scipy.sparse as sp
    w = np.ones(len(src))
    wsum = np.bincount(src, weights=w, minlength=n)
    inv = np.where(wsum > 0, 1.0 / np.maximum(wsum, 1e-300), 0.0)
    m = sp.csr_matrix((w * inv[src], (dst, src)), shape=(n, n))
    dang = wsum <= 0
    rank = np.full(n, 1.0 / n)
    for _ in range(iters):
        dm = rank[dang].sum()
        rank = (1 - damping) / n + damping * (m @ rank + dm / n)
    return rank


@pytest.fixture
def setup(monkeypatch):
    # force the MXU path at test scale (and on the CPU backend)
    monkeypatch.setattr(pr_mod, "MXU_MIN_EDGES", 1)
    monkeypatch.setenv("MEMGRAPH_TPU_FORCE_MXU", "1")
    storage = InMemoryStorage(StorageConfig(
        storage_mode=StorageMode.IN_MEMORY_TRANSACTIONAL))
    rng = np.random.default_rng(3)
    n, e = 1500, 9000
    acc = storage.access()
    et = storage.edge_type_mapper.name_to_id("E")
    vs = [acc.create_vertex() for _ in range(n)]
    src = rng.integers(0, n, e)
    dst = rng.integers(0, n, e)
    for s, d in zip(src, dst):
        acc.create_edge(vs[s], vs[d], et)
    acc.commit()
    return storage, vs, et, src.tolist(), dst.tolist(), n


def _ranks(storage, cache):
    acc = storage.access()
    g = cache.get(acc)
    r, _, _ = pr_mod.pagerank(g, max_iterations=60, tol=0.0)
    acc.abort()
    return g, np.asarray(r)


def test_commit_then_call_uses_delta(setup):
    storage, vs, et, src, dst, n = setup
    cache = GraphCache()
    g1, r1 = _ranks(storage, cache)
    assert getattr(g1, "_mxu_base_self", False)
    base_plan = g1._mxu_state[0]

    # mutate: add 40 edges, remove 10 (topology-bumping commit)
    acc = storage.access()
    rng = np.random.default_rng(7)
    added = []
    for _ in range(40):
        s, d = int(rng.integers(0, n)), int(rng.integers(0, n))
        acc.create_edge(vs[s], vs[d], et)
        added.append((s, d))
    removed = []
    victims = set()
    for ve in list(storage._edges.values()):
        if len(removed) >= 10 or ve.gid in victims:
            continue
        victims.add(ve.gid)
        from memgraph_tpu.storage.storage import EdgeAccessor
        ea = EdgeAccessor(ve, acc)
        acc.delete_edge(ea)
        removed.append((g1.gid_to_idx[ve.from_vertex.gid],
                        g1.gid_to_idx[ve.to_vertex.gid]))
    acc.commit()

    g2, r2 = _ranks(storage, cache)
    # the second snapshot must have refreshed via delta, not a full build
    assert g2._mxu_state[0] is base_plan, "full replan happened"
    assert not getattr(g2, "_mxu_base_self", False)

    # and the numbers must be exact for the mutated graph (oracle from
    # the snapshot's own edge list — the MVCC-visible set)
    s2, d2, _w2 = g2.host_coo
    want = _scipy_pagerank(s2.astype(np.int64), d2.astype(np.int64), n)
    np.testing.assert_allclose(r2, want, rtol=3e-4, atol=1e-9)
    assert not np.allclose(r1, r2)     # the mutation actually changed ranks


def test_edge_weight_change_invalidates_plan(setup):
    """A transactional SET on an edge property must enter the change
    log (via the edge's endpoints) so weighted pagerank never serves
    stale multipliers (r5 review finding)."""
    storage, vs, et, src, dst, n = setup
    wprop = storage.property_mapper.name_to_id("w")
    acc = storage.access()
    from memgraph_tpu.storage.storage import EdgeAccessor
    for ve in list(storage._edges.values())[:50]:
        EdgeAccessor(ve, acc).set_property(wprop, 5.0)
    acc.commit()
    cache = GraphCache()
    acc = storage.access()
    g1 = cache.get(acc, weight_property=wprop)
    r1, _, _ = pr_mod.pagerank(g1, max_iterations=40, tol=0.0)
    acc.abort()
    # transactional edge-property write, then re-CALL
    acc = storage.access()
    victim = next(iter(storage._edges.values()))
    EdgeAccessor(victim, acc).set_property(wprop, 250.0)
    acc.commit()
    acc = storage.access()
    g2 = cache.get(acc, weight_property=wprop)
    r2, _, _ = pr_mod.pagerank(g2, max_iterations=40, tol=0.0)
    acc.abort()
    s2, d2, w2 = g2.host_coo
    import scipy.sparse as sp
    wsum = np.bincount(s2, weights=w2.astype(np.float64), minlength=n)
    inv = np.where(wsum > 0, 1.0 / np.maximum(wsum, 1e-300), 0.0)
    m = sp.csr_matrix((w2 * inv[s2], (d2, s2)), shape=(n, n))
    dang = wsum <= 0
    rank = np.full(n, 1.0 / n)
    for _ in range(40):
        dm = rank[dang].sum()
        rank = 0.15 / n + 0.85 * (m @ rank + dm / n)
    np.testing.assert_allclose(r2, rank, rtol=3e-4, atol=1e-9)
    assert not np.allclose(r1, r2)


def test_huge_delta_recompacts(setup):
    storage, vs, et, src, dst, n = setup
    cache = GraphCache()
    g1, _ = _ranks(storage, cache)
    base_plan = g1._mxu_state[0]
    # add 30% more edges: beyond DELTA_RECOMPACT_FRACTION -> full replan
    acc = storage.access()
    rng = np.random.default_rng(9)
    for _ in range(2700):
        acc.create_edge(vs[int(rng.integers(0, n))],
                        vs[int(rng.integers(0, n))], et)
    acc.commit()
    g2, r2 = _ranks(storage, cache)
    assert g2._mxu_state[0] is not base_plan
    assert getattr(g2, "_mxu_base_self", False)


def test_chained_commits_delta_from_original_base(setup):
    """Two successive commits: the second delta still anchors on the
    ORIGINAL full plan (cumulative diff), not on the first delta."""
    storage, vs, et, src, dst, n = setup
    cache = GraphCache()
    g1, _ = _ranks(storage, cache)
    base_plan = g1._mxu_state[0]
    rng = np.random.default_rng(11)
    for _round in range(2):
        acc = storage.access()
        for _ in range(25):
            acc.create_edge(vs[int(rng.integers(0, n))],
                            vs[int(rng.integers(0, n))], et)
        acc.commit()
        g, r = _ranks(storage, cache)
        assert g._mxu_state[0] is base_plan
    s2, d2, _w2 = g.host_coo
    want = _scipy_pagerank(s2.astype(np.int64), d2.astype(np.int64), n)
    np.testing.assert_allclose(r, want, rtol=3e-4, atol=1e-9)
