"""Semiring kernel core (ops/semiring.py, r10).

Covers the ISSUE-10 acceptance criteria:

  * the semiring table — every (⊕, ⊗) pair's spmv against a numpy
    reference, plus masking and the or_and boolean pair;
  * OLD-vs-NEW f32 BIT-EXACTNESS: frozen copies of every pre-refactor
    hand-rolled kernel (pagerank, PPR, katz, HITS, labelprop, WCC,
    SSSP directed/undirected, BFS, mean-aggregate, Brandes chunk) are
    compared byte-for-byte against the core-routed implementations;
  * bf16 / int8 error bounds (PRECISION_BOUNDS, L1 + L∞ vs the f32
    reference on a seeded skewed graph) and top-k rank-order
    preservation for pagerank;
  * direction-optimizing push/pull (select_pull heuristic + push ≡ pull
    exactness on BFS);
  * per-backend mgstat stage attribution of the core dispatch;
  * the extended mglint MG005 sub-checks (core declarations, residual
    hand-rolled pipelines) with TP fixtures.

Mesh-of-1 / 8-device uneven-shard equivalence for the core-routed
algorithms piggybacks tests/test_sharded_analytics.py (its single-chip
side IS the core now; the precision mesh cases live there too).
"""

from functools import partial

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from memgraph_tpu.ops import SPMV_ALGORITHMS, csr
from memgraph_tpu.ops import semiring as S

N, E = 203, 1500


@pytest.fixture(scope="module")
def graph():
    rng = np.random.default_rng(42)
    src = rng.integers(0, N, E)
    dst = rng.integers(0, N, E)
    w = rng.uniform(0.5, 2.0, E).astype(np.float32)
    return csr.from_coo(src, dst, w, n_nodes=N)


@pytest.fixture(scope="module")
def skewed_graph():
    """Hub-skewed graph (bench-style squared dst sampling): top ranks
    are well separated, so rank-order checks are meaningful."""
    rng = np.random.default_rng(7)
    n, e = 300, 3000
    src = rng.integers(0, n, e)
    dst = (rng.random(e) ** 2 * n).astype(np.int64)
    return csr.from_coo(src, dst, None, n_nodes=n)


# --------------------------------------------------------------------------
# the semiring table vs numpy references
# --------------------------------------------------------------------------

def _np_spmv(add, mul, x, src, dst, w, n):
    identity = {"sum": 0.0, "min": np.inf, "max": -np.inf}[add]
    y = np.full(n, identity)
    for s, d, wi in zip(src, dst, w):
        if mul == "times":
            v = x[s] * wi
        elif mul == "plus":
            v = x[s] + wi
        elif mul == "min":
            v = min(x[s], wi)
        else:                      # first
            v = x[s]
        if add == "sum":
            y[d] += v
        elif add == "min":
            y[d] = min(y[d], v)
        else:
            y[d] = max(y[d], v)
    return y


@pytest.mark.parametrize("name", ["plus_times", "min_plus", "max_min",
                                  "plus_first", "min_first"])
def test_spmv_matches_numpy_reference(name):
    rng = np.random.default_rng(3)
    n, e = 40, 200
    src = rng.integers(0, n, e)
    dst = rng.integers(0, n, e)
    w = rng.uniform(0.1, 2.0, e).astype(np.float32)
    x = rng.uniform(0.1, 1.0, n).astype(np.float32)
    sr = S.SEMIRINGS[name]
    got = np.asarray(S.spmv(name, jnp.asarray(x), jnp.asarray(src),
                            jnp.asarray(dst), jnp.asarray(w), n_out=n))
    want = _np_spmv(sr.add, sr.mul, x, src, dst, w, n)
    # empty segments: jax sum fills 0, min/max fill dtype extrema —
    # compare only rows with incident edges
    touched = np.zeros(n, dtype=bool)
    touched[dst] = True
    np.testing.assert_allclose(got[touched], want[touched], rtol=1e-6)


def test_spmv_or_and_reachability():
    # 0 -> 1 -> 2, 3 isolated: one step from {0, 1} reaches {1, 2}
    src = jnp.asarray([0, 1])
    dst = jnp.asarray([1, 2])
    x = jnp.asarray([True, True, False, False])
    w = jnp.asarray([True, True])
    got = np.asarray(S.spmv("or_and", x, src, dst, w, n_out=4))
    assert got.tolist() == [False, True, True, False]


def test_spmv_masked_uses_fill():
    src = jnp.asarray([0, 1]); dst = jnp.asarray([2, 2])
    x = jnp.asarray([5, 7], dtype=jnp.int32)
    got = S.spmv("min_first", x, src, dst, n_out=3,
                 mask=jnp.asarray([False, True]),
                 mask_fill=jnp.int32(99))
    assert int(got[2]) == 7
    got_all_masked = S.spmv("min_first", x, src, dst, n_out=3,
                            mask=jnp.asarray([False, False]),
                            mask_fill=jnp.int32(99))
    assert int(got_all_masked[2]) == 99


def test_registry_core_declarations_resolve():
    """Runtime half of the MG005 core-declaration check."""
    for name, entry in SPMV_ALGORITHMS.items():
        core = entry.get("core")
        assert isinstance(core, str) and core, f"{name}: missing core"
        assert core == "blocks" or core in S.SEMIRINGS, \
            f"{name}: unknown core {core!r}"


def test_quantize_int8_bound():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=512).astype(np.float32))
    q, scale = S.quantize_int8(x)
    deq = np.asarray(q, dtype=np.float32) * float(scale)
    assert np.max(np.abs(np.asarray(x) - deq)) <= \
        float(np.max(np.abs(np.asarray(x)))) / 254.0 + 1e-7


# --------------------------------------------------------------------------
# OLD vs NEW: frozen pre-refactor kernels, f32 bit-exactness
# --------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("n_pad", "max_iterations"))
def _old_pagerank(src, dst, weights, csr_src, csr_weights, n_nodes,
                  n_pad, damping, max_iterations, tol):
    n_f = n_nodes.astype(jnp.float32)
    valid = (jnp.arange(n_pad, dtype=jnp.int32) < n_nodes)
    valid_f = valid.astype(jnp.float32)
    wsum = jax.ops.segment_sum(csr_weights, csr_src, num_segments=n_pad,
                               indices_are_sorted=True)
    inv_wsum = jnp.where(wsum > 0, 1.0 / jnp.maximum(wsum, 1e-30), 0.0)
    dangling_f = (valid & (wsum <= 0)).astype(jnp.float32)
    edge_mult = weights * inv_wsum[src]
    rank0 = valid_f / n_f

    def body(c):
        rank, _, it = c
        contrib = rank[src] * edge_mult
        acc = jax.ops.segment_sum(contrib, dst, num_segments=n_pad,
                                  indices_are_sorted=True)
        dm = jnp.sum(rank * dangling_f)
        new = valid_f * ((1.0 - damping) / n_f + damping * (acc + dm / n_f))
        return new, jnp.sum(jnp.abs(new - rank)), it + 1

    return jax.lax.while_loop(
        lambda c: (c[1] > tol) & (c[2] < max_iterations), body,
        (rank0, jnp.float32(jnp.inf), jnp.int32(0)))


def test_pagerank_bit_exact(graph):
    from memgraph_tpu.ops.pagerank import pagerank
    old, oerr, oit = _old_pagerank(
        graph.csc_src, graph.csc_dst, graph.csc_weights,
        graph.src_idx, graph.weights, np.int32(N), graph.n_pad,
        np.float32(0.85), 100, np.float32(1e-6))
    new, nerr, nit = pagerank(graph)
    assert oit == nit and float(oerr) == nerr
    assert np.array_equal(np.asarray(old[:N]), np.asarray(new))


@partial(jax.jit, static_argnames=("n_pad", "max_iterations"))
def _old_ppr(src, dst, weights, csr_src, csr_weights, n_nodes, n_pad,
             personalization, damping, max_iterations, tol):
    valid = (jnp.arange(n_pad, dtype=jnp.int32) < n_nodes)
    valid_f = valid.astype(jnp.float32)
    p = personalization * valid_f
    p = p / jnp.maximum(jnp.sum(p), 1e-30)
    wsum = jax.ops.segment_sum(csr_weights, csr_src, num_segments=n_pad,
                               indices_are_sorted=True)
    inv_wsum = jnp.where(wsum > 0, 1.0 / jnp.maximum(wsum, 1e-30), 0.0)
    dangling_f = (valid & (wsum <= 0)).astype(jnp.float32)
    edge_mult = weights * inv_wsum[src]

    def body(c):
        rank, _, it = c
        contrib = rank[src] * edge_mult
        acc = jax.ops.segment_sum(contrib, dst, num_segments=n_pad,
                                  indices_are_sorted=True)
        dm = jnp.sum(rank * dangling_f)
        new = (1.0 - damping) * p + damping * (acc + dm * p)
        return new, jnp.sum(jnp.abs(new - rank)), it + 1

    return jax.lax.while_loop(
        lambda c: (c[1] > tol) & (c[2] < max_iterations), body,
        (p, jnp.float32(jnp.inf), jnp.int32(0)))


def test_personalized_pagerank_bit_exact(graph):
    from memgraph_tpu.ops.pagerank import personalized_pagerank
    p = jnp.zeros(graph.n_pad, dtype=jnp.float32
                  ).at[jnp.asarray([3, 7], dtype=jnp.int32)].set(1.0)
    old, _, oit = _old_ppr(
        graph.csc_src, graph.csc_dst, graph.csc_weights,
        graph.src_idx, graph.weights, np.int32(N), graph.n_pad, p,
        np.float32(0.85), 100, np.float32(1e-6))
    new, _, nit = personalized_pagerank(graph, [3, 7])
    assert oit == nit
    assert np.array_equal(np.asarray(old[:N]), np.asarray(new))


@partial(jax.jit, static_argnames=("n_pad", "max_iterations"))
def _old_katz(src, dst, weights, n_nodes, n_pad, alpha, beta,
              max_iterations, tol, normalized):
    valid_f = (jnp.arange(n_pad, dtype=jnp.int32) < n_nodes
               ).astype(jnp.float32)
    x0 = jnp.zeros(n_pad, dtype=jnp.float32)

    def body(c):
        x, _, it = c
        acc = jax.ops.segment_sum(x[src] * weights, dst,
                                  num_segments=n_pad,
                                  indices_are_sorted=True)
        new_x = valid_f * (alpha * acc + beta)
        return new_x, jnp.max(jnp.abs(new_x - x)), it + 1

    x, err, iters = jax.lax.while_loop(
        lambda c: (c[1] > tol) & (c[2] < max_iterations), body,
        (x0, jnp.float32(jnp.inf), jnp.int32(0)))
    norm = jnp.sqrt(jnp.sum(x * x))
    x = jnp.where(normalized, x / jnp.maximum(norm, 1e-30), x)
    return x, err, iters


@pytest.mark.parametrize("normalized", [False, True])
def test_katz_bit_exact(graph, normalized):
    from memgraph_tpu.ops.katz import katz_centrality
    old, oerr, oit = _old_katz(
        graph.csc_src, graph.csc_dst, graph.csc_weights,
        jnp.int32(N), graph.n_pad, jnp.float32(0.05), jnp.float32(1.0),
        100, jnp.float32(1e-8), jnp.bool_(normalized))
    new, nerr, nit = katz_centrality(graph, alpha=0.05,
                                     max_iterations=100, tol=1e-8,
                                     normalized=normalized)
    assert oit == nit
    assert np.array_equal(np.asarray(old[:N]), np.asarray(new))


@partial(jax.jit, static_argnames=("n_pad", "max_iterations"))
def _old_hits(src, dst, weights, csrc, cdst, cweights, n_nodes, n_pad,
              max_iterations, tol):
    valid_f = (jnp.arange(n_pad, dtype=jnp.int32) < n_nodes
               ).astype(jnp.float32)

    def body(c):
        hub, auth, _, it = c
        new_auth = jax.ops.segment_sum(hub[csrc] * cweights, cdst,
                                       num_segments=n_pad,
                                       indices_are_sorted=True) * valid_f
        new_auth = new_auth / jnp.maximum(
            jnp.sqrt(jnp.sum(new_auth ** 2)), 1e-30)
        new_hub = jax.ops.segment_sum(new_auth[dst] * weights, src,
                                      num_segments=n_pad,
                                      indices_are_sorted=True) * valid_f
        new_hub = new_hub / jnp.maximum(
            jnp.sqrt(jnp.sum(new_hub ** 2)), 1e-30)
        err = jnp.max(jnp.abs(new_auth - auth)) \
            + jnp.max(jnp.abs(new_hub - hub))
        return new_hub, new_auth, err, it + 1

    return jax.lax.while_loop(
        lambda c: (c[2] > tol) & (c[3] < max_iterations), body,
        (valid_f, valid_f, jnp.float32(jnp.inf), jnp.int32(0)))


def test_hits_bit_exact(graph):
    from memgraph_tpu.ops.katz import hits
    ohub, oauth, oerr, oit = _old_hits(
        graph.src_idx, graph.col_idx, graph.weights,
        graph.csc_src, graph.csc_dst, graph.csc_weights,
        jnp.int32(N), graph.n_pad, 50, jnp.float32(1e-6))
    nhub, nauth, nerr, nit = hits(graph, max_iterations=50)
    assert int(oit) == nit
    assert np.array_equal(np.asarray(ohub[:N]), np.asarray(nhub))
    assert np.array_equal(np.asarray(oauth[:N]), np.asarray(nauth))


@partial(jax.jit, static_argnames=("n_pad", "e2", "max_iterations"))
def _old_labelprop(src2, dst2, w2, n_pad, e2, max_iterations,
                   self_weight):
    labels0 = jnp.arange(n_pad, dtype=jnp.int32)
    big_w = jnp.float32(0.0)

    def one_round(labels):
        lab_e = labels[src2]
        d_s, l_s, w_s = jax.lax.sort((dst2, lab_e, w2), num_keys=2)
        first = jnp.concatenate([
            jnp.ones((1,), dtype=jnp.bool_),
            (d_s[1:] != d_s[:-1]) | (l_s[1:] != l_s[:-1])])
        run_id = jnp.cumsum(first.astype(jnp.int32)) - 1
        run_w = jax.ops.segment_sum(w_s, run_id, num_segments=e2)
        idx = jnp.arange(e2, dtype=jnp.int32)
        first_idx = jax.ops.segment_min(jnp.where(first, idx, e2), run_id,
                                        num_segments=e2)
        first_idx = jnp.minimum(first_idx, e2 - 1)
        run_dst = d_s[first_idx]
        run_lab = l_s[first_idx]
        valid_run = idx <= run_id[-1]
        run_w = jnp.where(valid_run, run_w, big_w)
        best_w = jax.ops.segment_max(run_w, run_dst, num_segments=n_pad)
        is_best = run_w >= best_w[run_dst] - 1e-12
        cand_lab = jnp.where(valid_run & is_best, run_lab, jnp.int32(n_pad))
        best_lab = jax.ops.segment_min(cand_lab, run_dst,
                                       num_segments=n_pad)
        has_nb = best_lab < n_pad
        own_wins = (~has_nb) | (self_weight >= best_w) | \
                   (jnp.isclose(self_weight, best_w) & (labels <= best_lab))
        return jnp.where(own_wins, labels, best_lab)

    def body(c):
        labels, _, it = c
        new = one_round(labels)
        return new, jnp.any(new != labels), it + 1

    labels, _, iters = jax.lax.while_loop(
        lambda c: c[1] & (c[2] < max_iterations), body,
        (labels0, jnp.bool_(True), jnp.int32(0)))
    return labels, iters


def test_labelprop_bit_exact(graph):
    from memgraph_tpu.ops.labelprop import label_propagation
    src2 = jnp.concatenate([graph.src_idx, graph.col_idx])
    dst2 = jnp.concatenate([graph.col_idx, graph.src_idx])
    w2 = jnp.concatenate([graph.weights, graph.weights])
    old, oit = _old_labelprop(src2, dst2, w2, graph.n_pad,
                              2 * graph.e_pad, 30, jnp.float32(0.0))
    new, nit = label_propagation(graph, max_iterations=30)
    assert int(oit) == nit
    assert np.array_equal(np.asarray(old[:N]), np.asarray(new))


_UNIT_CASES = ("uniform", "isolated", "hub_ties", "skewed", "self_weight",
               "directed", "warm")


def _unit_weight_case(case):
    """A seeded unit-weight graph and label_propagation's options for it:
    (n, src, dst, options)."""
    rng = np.random.default_rng(_UNIT_CASES.index(case))
    n, e = 300, 2000
    src, dst = rng.integers(0, n, e), rng.integers(0, n, e)
    options = {}
    if case == "isolated":          # the upper half has no edge
        src, dst = src // 2, dst // 2
    elif case == "hub_ties":        # a star over pairs: equal counts
        leaves = np.arange(1, n)
        src = np.concatenate([np.zeros(n - 1, np.int64), leaves[:-1:2]])
        dst = np.concatenate([leaves, leaves[1::2]])
    elif case == "skewed":          # one dst's segment is most of them
        dst = (rng.random(e) ** 6 * n).astype(np.int64)
    elif case == "self_weight":
        options["self_weight"] = 2.0
    elif case == "directed":
        options["directed"] = True
    elif case == "warm":
        options["labels0"] = rng.integers(0, n // 10, n)
    return n, src, dst, options


@pytest.mark.parametrize("case", _UNIT_CASES)
def test_labelprop_count_election_is_exact(case):
    """On a unit-weight graph the election by counts (a view without a
    weight property) gives the labels and rounds of the election by
    weights, of the frozen pre-refactor kernel where it applies (it
    starts from the ids) and of the Graphalytics CDLP reference where
    that applies (undirected, no self weight, from the ids)."""
    import graphalytics_reference as ref
    from memgraph_tpu.ops.labelprop import label_propagation
    n, src, dst, options = _unit_weight_case(case)
    g = csr.from_coo(src, dst, None, n_nodes=n)
    got, it = label_propagation(g, max_iterations=10, weighted=False,
                                **options)
    want, want_it = label_propagation(g, max_iterations=10, **options)
    assert it == want_it
    assert np.array_equal(got, want)
    if "labels0" not in options:
        if options.get("directed"):
            src2, dst2, w2 = g.src_idx, g.col_idx, g.weights
        else:
            src2 = jnp.concatenate([g.src_idx, g.col_idx])
            dst2 = jnp.concatenate([g.col_idx, g.src_idx])
            w2 = jnp.concatenate([g.weights, g.weights])
        old, oit = _old_labelprop(
            src2, dst2, w2, g.n_pad, int(src2.shape[0]), 10,
            jnp.float32(options.get("self_weight", 0.0)))
        assert int(oit) == it
        assert np.array_equal(np.asarray(old[:n]), got)
    if not options:
        assert np.array_equal(got, ref.cdlp(n, src, dst, 10))


@partial(jax.jit, static_argnames=("n_pad", "max_iterations"))
def _old_wcc(src, dst, n_pad, max_iterations):
    comp0 = jnp.arange(n_pad, dtype=jnp.int32)

    def body(c):
        comp, _, it = c
        fwd = jax.ops.segment_min(comp[src], dst, num_segments=n_pad)
        bwd = jax.ops.segment_min(comp[dst], src, num_segments=n_pad)
        new = jnp.minimum(comp, jnp.minimum(fwd, bwd))
        new = new[new]
        return new, jnp.any(new != comp), it + 1

    return jax.lax.while_loop(
        lambda c: c[1] & (c[2] < max_iterations), body,
        (comp0, jnp.bool_(True), jnp.int32(0)))


def test_wcc_bit_exact(graph):
    from memgraph_tpu.ops.components import weakly_connected_components
    old, _, oit = _old_wcc(graph.src_idx, graph.col_idx, graph.n_pad, 200)
    new, nit = weakly_connected_components(graph)
    assert int(oit) == nit
    assert np.array_equal(np.asarray(old[:N]), np.asarray(new))


_INF = jnp.float32(3.4e38)


@partial(jax.jit, static_argnames=("n_pad", "max_iterations", "directed"))
def _old_sssp(src, dst, w, source, n_pad, max_iterations, directed):
    dist0 = jnp.full((n_pad,), _INF, dtype=jnp.float32).at[source].set(0.0)

    def body(c):
        dist, _, it = c
        relax = dist[src] + w
        cand = jax.ops.segment_min(relax, dst, num_segments=n_pad)
        new = jnp.minimum(dist, cand)
        if not directed:
            relax_b = new[dst] + w
            cand_b = jax.ops.segment_min(relax_b, src, num_segments=n_pad)
            new = jnp.minimum(new, cand_b)
        return new, jnp.any(new < dist), it + 1

    return jax.lax.while_loop(
        lambda c: c[1] & (c[2] < max_iterations), body,
        (dist0, jnp.bool_(True), jnp.int32(0)))


@pytest.mark.parametrize("directed", [True, False])
def test_sssp_bit_exact(graph, directed):
    from memgraph_tpu.ops.traversal import sssp
    w = jnp.where(jnp.arange(graph.e_pad) < graph.n_edges,
                  graph.weights, _INF)
    old, _, oit = _old_sssp(graph.src_idx, graph.col_idx, w,
                            jnp.int32(0), graph.n_pad, 10_000, directed)
    new, nit = sssp(graph, 0, weighted=True, directed=directed)
    assert int(oit) == nit
    old_out = np.asarray(old[:N])
    old_out = np.where(old_out >= float(_INF) / 2, np.inf, old_out)
    assert np.array_equal(old_out, np.asarray(new))


def test_bfs_levels_bit_exact(graph):
    """DO-BFS (push/pull) is level-exact vs the frozen min-plus BFS."""
    from memgraph_tpu.ops.traversal import bfs_levels
    w = jnp.where(jnp.arange(graph.e_pad) < graph.n_edges, 1.0,
                  _INF).astype(jnp.float32)
    old, _, oit = _old_sssp(graph.src_idx, graph.col_idx, w,
                            jnp.int32(0), graph.n_pad, 10_000, True)
    old_lv = np.where(np.asarray(old[:N]) >= float(_INF) / 2, -1,
                      np.asarray(old[:N])).astype(np.int32)
    new, nit = bfs_levels(graph, 0)
    assert int(oit) == nit
    assert np.array_equal(old_lv, np.asarray(new))


def test_mean_aggregate_bit_exact(graph):
    from memgraph_tpu.ops.gnn import _mean_aggregate, degree_features

    @partial(jax.jit, static_argnames=("n_pad",))
    def old_agg(feats, csc_src, csc_dst, n_pad):
        summed = jax.ops.segment_sum(feats[csc_src], csc_dst, n_pad,
                                     indices_are_sorted=True)
        summed = summed + jax.ops.segment_sum(feats[csc_dst], csc_src,
                                              n_pad)
        deg = jax.ops.segment_sum(
            jnp.ones_like(csc_dst, dtype=feats.dtype), csc_dst, n_pad,
            indices_are_sorted=True)
        deg = deg + jax.ops.segment_sum(
            jnp.ones_like(csc_src, dtype=feats.dtype), csc_src, n_pad)
        return summed / jnp.maximum(deg, 1.0)[:, None]

    feats = degree_features(graph, dim=8)
    old = old_agg(feats, graph.csc_src, graph.csc_dst, graph.n_pad)
    new = jax.jit(_mean_aggregate, static_argnames=("n_pad",))(
        feats, graph.csc_src, graph.csc_dst, graph.n_pad)
    assert np.array_equal(np.asarray(old), np.asarray(new))


def test_brandes_chunk_bit_exact(graph):
    """The batched Brandes chunk routes its batched reductions through
    the core; byte-compare against a frozen pre-refactor chunk."""
    from memgraph_tpu.ops.betweenness import _brandes_chunk

    @partial(jax.jit, static_argnames=("n_pad", "max_levels"))
    def old_chunk(src, dst, edge_valid, sources, weights, n_pad,
                  max_levels):
        INF = jnp.float32(3.0e38)
        B = sources.shape[0]
        rows = jnp.arange(B)
        seg_ids = rows[:, None] * n_pad + dst[None, :]
        seg_ids_back = rows[:, None] * n_pad + src[None, :]
        dist0 = jnp.full((B, n_pad), INF,
                         jnp.float32).at[rows, sources].set(0.0)
        sigma0 = jnp.zeros((B, n_pad),
                           jnp.float32).at[rows, sources].set(1.0)

        def fwd_body(c):
            dist, sigma, level, _ = c
            on_frontier = (dist[:, src] == level) & edge_valid[None, :]
            contrib = jnp.where(on_frontier, sigma[:, src], 0.0)
            sig_new = jax.ops.segment_sum(
                contrib.reshape(-1), seg_ids.reshape(-1),
                num_segments=B * n_pad).reshape(B, n_pad)
            newly = (dist >= INF / 2) & (sig_new > 0)
            dist = jnp.where(newly, level + 1.0, dist)
            sigma = jnp.where(newly, sig_new, sigma)
            return dist, sigma, level + 1.0, jnp.any(newly)

        dist, sigma, top_level, _ = jax.lax.while_loop(
            lambda c: c[3] & (c[2] < max_levels), fwd_body,
            (dist0, sigma0, jnp.float32(0.0), jnp.bool_(True)))

        def bwd_body(c):
            delta, level = c
            on_edge = (dist[:, src] == level) \
                & (dist[:, dst] == level + 1.0) & edge_valid[None, :]
            safe_sigma = jnp.maximum(sigma[:, dst], 1.0)
            contrib = jnp.where(
                on_edge,
                sigma[:, src] / safe_sigma * (1.0 + delta[:, dst]), 0.0)
            add = jax.ops.segment_sum(
                contrib.reshape(-1), seg_ids_back.reshape(-1),
                num_segments=B * n_pad).reshape(B, n_pad)
            delta = jnp.where(dist == level, add, delta)
            return delta, level - 1.0

        delta0 = jnp.zeros((B, n_pad), jnp.float32)
        delta, _ = jax.lax.while_loop(
            lambda c: c[1] >= 0.0, bwd_body, (delta0, top_level - 1.0))
        delta = delta.at[rows, sources].set(0.0)
        return (weights[:, None] * delta).sum(axis=0)

    s_np = np.asarray(graph.src_idx)[:graph.n_edges]
    d_np = np.asarray(graph.col_idx)[:graph.n_edges]
    keep = s_np != d_np
    pairs = np.unique(np.stack([s_np[keep], d_np[keep]], axis=1), axis=0)
    src = jnp.asarray(pairs[:, 0], jnp.int32)
    dst = jnp.asarray(pairs[:, 1], jnp.int32)
    edge_valid = jnp.ones(src.shape, bool)
    sources = jnp.asarray(np.arange(8, dtype=np.int32))
    weights = jnp.ones(8, jnp.float32)
    old = old_chunk(src, dst, edge_valid, sources, weights,
                    graph.n_pad, 64)
    new = _brandes_chunk(src, dst, edge_valid, sources, weights,
                         graph.n_pad, 64)
    assert np.array_equal(np.asarray(old), np.asarray(new))


# --------------------------------------------------------------------------
# mixed precision: error bounds + rank-order preservation
# --------------------------------------------------------------------------

@pytest.mark.parametrize("precision", ["bf16", "int8"])
def test_pagerank_precision_error_bounds(skewed_graph, precision):
    from memgraph_tpu.ops.pagerank import pagerank
    n = skewed_graph.n_nodes
    f32, _, _ = pagerank(skewed_graph, tol=1e-10, max_iterations=200)
    var, _, _ = pagerank(skewed_graph, tol=1e-10, max_iterations=200,
                         precision=precision)
    diff = np.abs(np.asarray(var) - np.asarray(f32))
    bounds = S.PRECISION_BOUNDS[precision]
    assert float(diff.max()) <= bounds["pagerank_linf"], \
        f"L-inf {diff.max():.2e} over bound {bounds['pagerank_linf']:.2e}"
    assert float(diff.sum()) <= bounds["pagerank_l1"], \
        f"L1 {diff.sum():.2e} over bound {bounds['pagerank_l1']:.2e}"
    # top-k rank ORDER preserved exactly (hub-skewed graph: separated)
    k = bounds["topk_order"]
    assert np.array_equal(np.argsort(-np.asarray(f32))[:k],
                          np.argsort(-np.asarray(var))[:k]), \
        f"top-{k} order not preserved under {precision}"


def test_katz_precision_variants_close(graph):
    from memgraph_tpu.ops.katz import katz_centrality
    f32, _, _ = katz_centrality(graph, alpha=0.05, tol=1e-8)
    b16, _, _ = katz_centrality(graph, alpha=0.05, tol=1e-8,
                                precision="bf16")
    np.testing.assert_allclose(np.asarray(b16), np.asarray(f32),
                               atol=5e-2, rtol=2e-2)


def test_mxu_backend_matches_segment(graph, monkeypatch):
    """FORCE_MXU + tiny threshold: the generalized MXU semiring kernel
    (pagerank epilogue AND the new katz ride) agrees with the segment
    backend."""
    from memgraph_tpu.ops import pagerank as pr_mod
    from memgraph_tpu.ops.katz import katz_centrality
    from memgraph_tpu.ops.pagerank import pagerank
    seg_pr, _, _ = pagerank(graph, tol=1e-10, max_iterations=200)
    seg_kz, _, _ = katz_centrality(graph, alpha=0.05, tol=1e-10,
                                   max_iterations=200)
    monkeypatch.setattr(pr_mod, "MXU_MIN_EDGES", 1)
    monkeypatch.setattr(S, "MXU_MIN_EDGES", 1)
    monkeypatch.setenv("MEMGRAPH_TPU_FORCE_MXU", "1")
    mxu_pr, _, _ = pagerank(graph, tol=1e-10, max_iterations=200)
    mxu_kz, _, _ = katz_centrality(graph, alpha=0.05, tol=1e-10,
                                   max_iterations=200)
    np.testing.assert_allclose(np.asarray(mxu_pr), np.asarray(seg_pr),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(mxu_kz), np.asarray(seg_kz),
                               atol=1e-4, rtol=1e-4)


# --------------------------------------------------------------------------
# direction optimization
# --------------------------------------------------------------------------

def test_select_pull_threshold():
    deg = jnp.asarray(np.full(100, 10.0, dtype=np.float32))
    sparse = jnp.zeros(100, bool).at[0].set(True)       # m_f = 10
    dense = jnp.ones(100, bool)                         # m_f = 1000
    n_edges = 1000.0
    assert not bool(S.select_pull(sparse, deg, n_edges))
    assert bool(S.select_pull(dense, deg, n_edges))


def test_push_equals_pull_for_bfs(graph):
    """The frontier-masked (push) relaxation produces the same next
    level as the full (pull) reduction — the exactness select_pull
    relies on."""
    dist = np.full(graph.n_pad, float(_INF), dtype=np.float32)
    dist[0] = 0.0
    frontier = np.zeros(graph.n_pad, dtype=bool)
    frontier[0] = True
    w = jnp.where(jnp.arange(graph.e_pad) < graph.n_edges, 1.0,
                  _INF).astype(jnp.float32)
    pull = S.spmv("min_plus", jnp.asarray(dist), graph.src_idx,
                  graph.col_idx, w, n_out=graph.n_pad)
    push = S.spmv("min_plus", jnp.asarray(dist), graph.src_idx,
                  graph.col_idx, w, n_out=graph.n_pad,
                  frontier=jnp.asarray(frontier))
    # non-frontier sources hold dist = INF, so their pull contributions
    # are >= INF/2 — both sides agree on every finite candidate
    pl = np.asarray(pull)
    ps = np.asarray(push)
    finite = pl < float(_INF) / 2
    assert np.array_equal(pl[finite], ps[finite])


# --------------------------------------------------------------------------
# per-backend stage attribution (mgstat)
# --------------------------------------------------------------------------

def test_core_dispatch_records_backend_stages(graph):
    from memgraph_tpu.observability import stats as mgstats
    from memgraph_tpu.ops.pagerank import pagerank
    acc = mgstats.StageAccumulator()
    with mgstats.collecting_stages(acc):
        pagerank(graph, max_iterations=5, tol=-1.0)
    snap = acc.snapshot()
    assert "semiring_segment" in snap and "device_iterate" in snap
    acc2 = mgstats.StageAccumulator()
    from memgraph_tpu.parallel.mesh import get_mesh_context
    with mgstats.collecting_stages(acc2):
        pagerank(graph, max_iterations=5, tol=-1.0,
                 mesh=get_mesh_context(1))
    assert "semiring_mesh" in acc2.snapshot()


# --------------------------------------------------------------------------
# mglint MG005 semiring sub-checks (TP fixtures, tmp_path)
# --------------------------------------------------------------------------

_MINI_SEMIRING = (
    "SEMIRINGS = {\n"
    "    'plus_times': 1,\n"
    "    'min_plus': 2,\n"
    "}\n")


def _spmv_project(tmp_path, init_text, extra_files=()):
    from tools.mglint.core import Project
    pkg = tmp_path / "pkg" / "ops"
    pkg.mkdir(parents=True)
    (pkg / "__init__.py").write_text(init_text)
    (pkg / "semiring.py").write_text(_MINI_SEMIRING)
    for name, text in extra_files:
        (pkg / name).write_text(text)
    return Project([str(tmp_path / "pkg")], cwd=str(tmp_path))


def test_mg005_flags_handrolled_pipeline(tmp_path):
    """A residual segment_* + while_loop function outside the core
    fires spmv-handrolled even when the module is registered."""
    from tools.mglint.rules.registry_coverage import _check_spmv_registry
    project = _spmv_project(
        tmp_path,
        "SPMV_ALGORITHMS = {\n"
        "  'rogue': {'entry': 'pkg.ops.rogue:run',\n"
        "            'core': 'plus_times',\n"
        "            'exempt': 'a long enough justification string "
        "covering the forty-character minimum'},\n"
        "}\n",
        [("rogue.py",
          "import jax\n"
          "def run(x, seg):\n"
          "    def body(c):\n"
          "        return jax.ops.segment_sum(c, seg, num_segments=4)\n"
          "    return jax.lax.while_loop(lambda c: True, body, x)\n")])
    fps = {f.fingerprint for f in _check_spmv_registry(project)}
    assert "spmv-handrolled:rogue:run" in fps


def test_mg005_flags_missing_and_unknown_core(tmp_path):
    from tools.mglint.rules.registry_coverage import _check_spmv_registry
    project = _spmv_project(
        tmp_path,
        "SPMV_ALGORITHMS = {\n"
        "  'a': {'entry': 'pkg.ops.a:run',\n"
        "        'exempt': 'a long enough justification string "
        "covering the forty-character minimum'},\n"
        "  'b': {'entry': 'pkg.ops.b:run', 'core': 'tropical',\n"
        "        'exempt': 'a long enough justification string "
        "covering the forty-character minimum'},\n"
        "}\n",
        [("a.py", "def run():\n    pass\n"),
         ("b.py", "def run():\n    pass\n")])
    fps = {f.fingerprint for f in _check_spmv_registry(project)}
    assert "spmv-no-core:a" in fps
    assert "spmv-unknown-core:b:tropical" in fps


def test_mg005_core_import_requires_registry_entry(tmp_path):
    """A module that rides the core (imports semiring) but skips the
    registry is uncovered even without a hand-rolled segment loop."""
    from tools.mglint.rules.registry_coverage import _check_spmv_registry
    project = _spmv_project(
        tmp_path, "SPMV_ALGORITHMS = {}\n",
        [("quiet.py",
          "from . import semiring as S\n"
          "def run(x, src, dst, n):\n"
          "    return S.spmv('plus_times', x, src, dst, n_out=n)\n")])
    fps = {f.fingerprint for f in _check_spmv_registry(project)}
    assert "spmv-uncovered:quiet" in fps


def test_mg005_clean_core_module_passes(tmp_path):
    from tools.mglint.rules.registry_coverage import _check_spmv_registry
    project = _spmv_project(
        tmp_path,
        "SPMV_ALGORITHMS = {\n"
        "  'good': {'entry': 'pkg.ops.good:run',\n"
        "           'core': 'min_plus',\n"
        "           'exempt': 'a long enough justification string "
        "covering the forty-character minimum'},\n"
        "}\n",
        [("good.py",
          "from . import semiring as S\n"
          "def run(x, src, dst, n):\n"
          "    return S.spmv('min_plus', x, src, dst, n_out=n)\n")])
    assert not _check_spmv_registry(project)


# --------------------------------------------------------------------------
# kernel server semiring op (socket round trip)
# --------------------------------------------------------------------------

def test_kernel_server_semiring_op(tmp_path):
    import threading
    import time
    from memgraph_tpu.server.kernel_server import (KernelClient,
                                                   KernelServer)
    sock = str(tmp_path / "ks.sock")
    srv = KernelServer(sock, idle_timeout_s=0)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    import os
    for _ in range(200):
        if os.path.exists(sock):
            break
        time.sleep(0.05)
    rng = np.random.default_rng(0)
    n, e = 100, 600
    src = rng.integers(0, n, e)
    dst = rng.integers(0, n, e)
    c = KernelClient(sock)
    try:
        h, out = c.semiring("pagerank", src=src, dst=dst, n_nodes=n,
                            graph_key="g", max_iterations=50, tol=1e-8)
        assert h["precision"] == "f32"
        assert abs(float(out["ranks"].sum()) - 1.0) < 1e-3
        h2, out2 = c.semiring("pagerank", graph_key="g",
                              precision="bf16", max_iterations=50,
                              tol=1e-8)
        assert h2["precision"] == "bf16"
        assert float(np.max(np.abs(out2["ranks"] - out["ranks"]))) < 1e-3
        h3, out3 = c.semiring("bfs", graph_key="g", source=0)
        from memgraph_tpu.ops.traversal import bfs_levels
        g = csr.from_coo(src, dst, None, n_nodes=n)
        want, _ = bfs_levels(g, 0)
        assert np.array_equal(out3["levels"], np.asarray(want))
        with pytest.raises(Exception):
            c.semiring("mystery", graph_key="g")
    finally:
        c.shutdown()
        c.close()
