"""The PPR-serving cell's reference, its shortcut and the system, tied
together at a small size: ``semantics/ppr_sets.py``'s ``vector`` (the
mean of one solve per restart user) against ``ppr_set_direct`` (the
equations as loops on the set's own restart vector), and
``ops.pagerank.personalized_pagerank_batch`` on the CPU against both
under the cell's limit. Graphs are seeded, with dangling users.
"""

import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmarks", "chipbench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import reference  # noqa: E402
import run  # noqa: E402
import seams  # noqa: E402

sem = seams.load_module(None, "semantics", "ppr_sets")

with open(os.path.join(BENCH, "cells",
                       "pokec_medium_ppr_serve.ppr_sets.json")) as _f:
    LIMIT = json.load(_f)["limits"]["rank_dev_max"]

GRAPHS = [(200, 900, 40, 21), (700, 5_000, 60, 22), (2_000, 9_000, 300, 23)]


def graph(n, e, dangling, seed):
    """A state whose last `dangling` users have no out-edge."""
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n - dangling, e)
    dst = (rng.random(e) ** 2 * n).astype(np.int64)
    return reference.GraphState(n, src, dst)


def sets_of(state, seed):
    """Sets of 1, 4 and 10 users with an out-edge, as the data set's
    catalogue holds: a lone dangling restart user keeps all its mass,
    and 19 of its 20 best are zeros."""
    out = np.bincount(state.edge_arrays()[0], minlength=state.n_loaded)
    rng = np.random.default_rng([seed, 1])
    return [rng.choice(np.flatnonzero(out > 0), size=k,
                       replace=False).tolist() for k in (1, 4, 10)]


@pytest.mark.parametrize("n,e,dangling,seed", GRAPHS)
def test_the_shortcut_equals_the_plain_reference(n, e, dangling, seed):
    state = graph(n, e, dangling, seed)
    out = np.bincount(state.edge_arrays()[0], minlength=n)
    assert (out == 0).sum() >= dangling
    for ids in sets_of(state, seed):
        direct, rounds = sem.ppr_set_direct(state, ids)
        assert rounds < 2000 and direct.sum() == pytest.approx(1.0,
                                                               abs=1e-12)
        got = sem.vector("ppr_set_top", state.copy(), {"ids": ids})
        assert np.abs(got - direct).max() < 1e-12, (len(ids), rounds)
    # a set that holds a dangling user, and a repeated id counting once
    ids = [n - 1, 3, 3, 8]
    direct, _ = sem.ppr_set_direct(state, ids)
    got = sem.vector("ppr_set_top", state, {"ids": ids})
    assert np.abs(got - direct).max() < 1e-12
    assert got[n - 1] > 0.15 / 3


@pytest.mark.parametrize("n,e,dangling,seed", GRAPHS)
def test_the_system_agrees_with_both_under_the_cells_limit(n, e, dangling,
                                                           seed):
    from memgraph_tpu.ops import csr
    from memgraph_tpu.ops.pagerank import (personalized_pagerank_batch,
                                           ppr_topk)
    state = graph(n, e, dangling, seed)
    src, dst = state.edge_arrays()
    g = csr.from_coo(src, dst, n_nodes=n).to_device()
    sets = sets_of(state, seed)
    x, _err, iters = personalized_pagerank_batch(g, sets, raw=True)
    vals, idx = ppr_topk(x.T, g.n_nodes, 20)
    assert np.asarray(iters)[:len(sets)].max() < 100
    for lane, ids in enumerate(sets):
        rows = [[int(i), float(v)] for i, v in zip(idx[lane], vals[lane])]
        direct, _ = sem.ppr_set_direct(state, ids)
        shortcut = sem.vector("ppr_set_top", state, {"ids": ids})
        for want in (direct, shortcut):
            one = run.compare_ranks(rows, want, 20)
            assert one["fault"] == 0
            assert max(one["rel_err"], one["gap"]) < LIMIT, (len(ids), one)


def test_a_changed_graph_is_solved_again():
    """What is solved is kept for one version of the edges only."""
    state = graph(300, 1_500, 30, 24)
    before = sem.vector("ppr_set_top", state, {"ids": [5, 6]})
    state.apply("add_edge", {"a": 5, "b": 299})
    after = sem.vector("ppr_set_top", state, {"ids": [5, 6]})
    direct, _ = sem.ppr_set_direct(state, [5, 6])
    assert np.abs(after - direct).max() < 1e-12
    assert np.abs(after - before).max() > 1e-4


def test_the_bfloat16_reading_lies_above_the_limit():
    """The reference solved with every contribution rounded to bfloat16,
    held to itself: not correct by the cell's limit."""
    state = graph(2_000, 20_000, 100, 25)
    for ids in sets_of(state, 25):
        want = sem.vector("ppr_set_top", state, {"ids": ids})
        low = sem.vector("ppr_set_top", state, {"ids": ids},
                         precision="bf16")
        order, ranks = reference.top_ranks(low, 20)
        rows = [[int(i), float(r)] for i, r in zip(order, ranks)]
        one = run.compare_ranks(rows, want, 20)
        assert one["fault"] == 0
        assert max(one["rel_err"], one["gap"]) > 10 * LIMIT, one
