"""The PPR cache's invalidation set, read from the snapshot's row offsets.

``_source_neighborhood`` (server/kernel_server.py) used to find a rider's
sources' out-neighbours with ``np.isin`` over every host edge. It now
slices the snapshot's host CSR. These tests hold it to the definition it
replaced, set for set, and to what it may cost: nothing linear in E per
rider or per batch, which the plane's two counters witness (through a
served plane: tests/test_ppr_serving.py).
"""

import dataclasses

import numpy as np
import pytest

from memgraph_tpu.observability.metrics import global_metrics
from memgraph_tpu.ops import csr
from memgraph_tpu.ops import delta as D
from memgraph_tpu.server.kernel_server import (PPR_NEIGH_CAP,
                                               _source_neighborhood)

N = 400


def _definition(graph, sources, cap=PPR_NEIGH_CAP):
    """The function as it stood before: one pass over every edge."""
    if graph.host_coo is None:
        return None
    src, dst, _w = graph.host_coo
    sel = np.isin(np.asarray(src), np.asarray(sources))
    neigh = set(int(i) for i in np.asarray(dst)[sel])
    neigh.update(int(s) for s in np.asarray(sources))
    if len(neigh) > cap:
        return None
    return frozenset(neigh)


def _counter(name):
    return dict((n, v) for n, _k, v in global_metrics.snapshot()).get(
        name, 0.0)


def _edges(seed, n=N, e=3000):
    """A seeded graph in which vertex 0 is dangling, 1 -> 2 is a
    parallel edge three times over, and vertex 3 is a hub."""
    rng = np.random.default_rng(seed)
    src = rng.integers(4, n, e)
    dst = rng.integers(0, n, e)
    src = np.concatenate([src, [1, 1, 1, 1], np.full(n - 1, 3)])
    dst = np.concatenate([dst, [2, 2, 2, 7], np.arange(1, n)])
    order = rng.permutation(len(src))       # no order a reader may lean on
    return src[order], dst[order]


def _snapshot(seed):
    src, dst = _edges(seed)
    return csr.from_coo(src, dst, n_nodes=N).to_device()


def _draw(seed, k):
    rng = np.random.default_rng(1000 + seed)
    return rng.choice(np.arange(4, N), size=k, replace=False)


CASES = {
    "single_source": lambda seed: (_draw(seed, 1), PPR_NEIGH_CAP),
    "set_of_4": lambda seed: (_draw(seed, 4), PPR_NEIGH_CAP),
    "set_of_10": lambda seed: (_draw(seed, 10), PPR_NEIGH_CAP),
    "dangling_source": lambda seed: (np.array([0]), PPR_NEIGH_CAP),
    "dangling_among_others": lambda seed: (
        np.concatenate([[0], _draw(seed, 3)]), PPR_NEIGH_CAP),
    "parallel_edges": lambda seed: (np.array([1]), PPR_NEIGH_CAP),
    "source_repeated": lambda seed: (
        np.repeat(_draw(seed, 2), 2), PPR_NEIGH_CAP),
    "hub_under_the_cap": lambda seed: (np.array([3]), PPR_NEIGH_CAP),
    "hub_past_the_cap": lambda seed: (np.array([3, 5]), 64),
    # the hub reaches 1..N-1, itself among them: N - 1 indices
    "exactly_the_cap": lambda seed: (np.array([3]), N - 1),
    "one_past_the_cap": lambda seed: (np.array([3]), N - 2),
    # the plane refuses these before its batch; the function itself
    # keeps the old answer (no row, so no neighbour) and never wraps
    "source_beyond_the_rows": lambda seed: (
        np.array([N + 5, 10**6, 7]), PPR_NEIGH_CAP),
    "negative_source": lambda seed: (np.array([-1, 7]), PPR_NEIGH_CAP),
}


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("case", sorted(CASES))
def test_neighborhood_is_the_definition_it_replaced(case, seed):
    g = _snapshot(seed)
    sources, cap = CASES[case](seed)
    got = _source_neighborhood(g, sources, cap)
    want = _definition(g, sources, cap)
    assert got == want
    if case in ("hub_past_the_cap", "one_past_the_cap"):
        assert got is None
    else:
        assert got is not None and set(int(s) for s in sources) <= got
        assert all(type(i) is int for i in got)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_snapshot_without_host_edges_reads_none(seed):
    g = dataclasses.replace(_snapshot(seed), host_coo=None, host_csr=None)
    assert _source_neighborhood(g, _draw(seed, 4)) is None
    assert _definition(g, _draw(seed, 4)) is None


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("case", ["set_of_4", "parallel_edges",
                                  "dangling_source", "hub_past_the_cap"])
def test_offsets_built_once_for_a_snapshot_that_lacks_them(case, seed):
    """A DeviceGraph built elsewhere: the same sets, one O(E) pass for
    the snapshot object, none for the riders that follow."""
    g = dataclasses.replace(_snapshot(seed), host_csr=None)
    sources, cap = CASES[case](seed)
    scans = _counter("ppr.neigh_scan_total")
    reads = _counter("ppr.neigh_offsets_total")
    assert _source_neighborhood(g, sources, cap) == \
        _definition(g, sources, cap)
    assert g.host_csr is not None
    for k in (1, 4, 10):
        more = _draw(seed + k, k)
        assert _source_neighborhood(g, more) == _definition(g, more)
    assert _counter("ppr.neigh_scan_total") == scans + 1
    assert _counter("ppr.neigh_offsets_total") == reads + 4


def test_from_coo_hands_on_the_arrays_it_built():
    """Two references, no copy and no sort: the host graph's own CSR
    arrays are what the placed snapshot carries."""
    src, dst = _edges(7)
    host = csr.from_coo(src, dst, n_nodes=N)
    assert host.host_csr[0] is host.row_ptr
    assert host.host_csr[1] is host.col_idx
    placed = host.to_device()
    assert placed.host_csr[0] is host.row_ptr
    assert placed.host_csr[1] is host.col_idx
    assert placed.to_device() is placed


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_a_rematerialised_generation_brings_its_own_offsets(seed):
    """After a delta ResidentGraph.graph is a new from_coo(...) snapshot:
    the neighbourhood is the new generation's, and nothing is scanned."""
    g = _snapshot(seed)
    gen = D.ResidentGraph("k", 1, g)
    s = int(_draw(seed, 1)[0])
    src, dst, _w = g.host_coo
    old_out = set(int(d) for d in dst[src == s])
    gone = sorted(old_out)[0]
    fresh = next(v for v in range(4, N) if v not in old_out and v != s)
    # the changed vertex's CURRENT incident edges: its old ones, less the
    # edges to `gone`, plus one to `fresh`
    inc = (src == s) | (dst == s)
    keep = inc & ~((src == s) & (dst == gone))
    d = D.diff_incident(gen.coo, [s], np.append(src[keep], s),
                        np.append(dst[keep], fresh), None, N, 1, 2)
    assert gen.apply(d)
    g2 = gen.graph
    assert g2 is not g and g2.host_csr is not None
    scans = _counter("ppr.neigh_scan_total")
    got = _source_neighborhood(g2, [s])
    assert got == _definition(g2, [s])
    assert fresh in got and (gone in got) == (gone == s)
    assert _source_neighborhood(g, [s]) == _definition(g, [s])
    assert fresh not in _source_neighborhood(g, [s])
    assert _counter("ppr.neigh_scan_total") == scans
