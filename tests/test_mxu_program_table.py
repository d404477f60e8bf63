"""The MXU fixpoint's program table (ops/spmv_mxu._PROGRAMS): a kernel
is a jitted program looked up by what is static plus the data it is
called with, so a second DeltaPlan of the same quantised shapes runs
the functions the first one traced. CPU, roll path.
"""

import threading
import time

import numpy as np
import pytest

from memgraph_tpu.observability.metrics import global_metrics
from memgraph_tpu.ops import spmv_mxu


def _counter(name):
    return {n: v for n, _k, v in global_metrics.snapshot()}.get(name, 0.0)


def _counters():
    return tuple(_counter(n) for n in (
        "mxu.program_hit_total", "mxu.program_miss_total",
        "jit.compile_total"))


def _ranks(plan, delta=None, iters=40):
    import jax.numpy as jnp
    run = spmv_mxu.make_pagerank_kernel(plan, delta=delta)
    rank, _err, _it = run(None, jnp.float32(0.85), iters, jnp.float32(0.0))
    return np.asarray(rank)[plan.out_relabel]


def _signature_shapes(delta):
    return delta.R_G, delta.C, delta.net_log2


@pytest.fixture(autouse=True)
def empty_table():
    spmv_mxu._PROGRAMS.clear()
    yield
    spmv_mxu._PROGRAMS.clear()


@pytest.fixture(scope="module")
def base_graph():
    rng = np.random.default_rng(11)
    n, e = 3000, 20000
    src = rng.integers(0, n, e)
    dst = (rng.random(e) ** 2 * n).astype(np.int64)   # skewed in-degree
    return n, src, dst, np.ones(e)


@pytest.fixture(scope="module")
def base_plan(base_graph):
    n, src, dst, w = base_graph
    return spmv_mxu.build_plan(src, dst, w, n)


def _burst(base_graph, kind, seed, size):
    """(added, removed) edge triples; removals are real edges."""
    n, src, dst, w = base_graph
    rng = np.random.default_rng(seed)
    none = np.zeros(0, dtype=np.int64)
    add = (rng.integers(0, n, size), rng.integers(0, n, size))
    rm = rng.choice(len(src), size, replace=False)
    if kind == "additions":
        return add, (none, none, none)
    if kind == "removals":
        return (none, none), (src[rm], dst[rm], w[rm])
    return add, (src[rm], dst[rm], w[rm])


def _delta(base_plan, burst):
    (a_s, a_d), (r_s, r_d, r_w) = burst
    return spmv_mxu.build_delta_plan(base_plan, a_s, a_d, None,
                                     r_s, r_d, r_w)


def _replanned(base_graph, burst):
    """The full replan of the mutated graph, through the same kernel."""
    n, src, dst, w = base_graph
    (a_s, a_d), (r_s, r_d, _r_w) = burst
    keep = np.ones(len(src), dtype=bool)
    # one base edge per removed (src, dst) pair: multiset semantics
    order = np.lexsort((dst, src))
    keys = src[order] * n + dst[order]
    for k in r_s * n + r_d:
        at = np.searchsorted(keys, k)
        while not keep[order[at]]:
            at += 1
        assert keys[at] == k
        keep[order[at]] = False
    m_src = np.concatenate([src[keep], a_s])
    m_dst = np.concatenate([dst[keep], a_d])
    plan = spmv_mxu.build_plan(m_src, m_dst, np.ones(len(m_src)), n)
    return _ranks(plan)


def test_two_bursts_of_one_signature_share_one_program(base_graph,
                                                       base_plan):
    first = _delta(base_plan, _burst(base_graph, "additions", 1, 40))
    second = _delta(base_plan, _burst(base_graph, "additions", 2, 90))
    assert first.n_delta != second.n_delta
    assert _signature_shapes(first) == _signature_shapes(second)
    # the floors: a gather row per source row, two chunks a window
    assert first.R_G == spmv_mxu.SG_ROWS and first.C == 2 * base_plan.W

    run_a = spmv_mxu.make_pagerank_kernel(base_plan, delta=first)
    _ranks(base_plan, first)                 # traces and compiles
    hits, misses, compiles = _counters()
    run_b = spmv_mxu.make_semiring_kernel(
        base_plan, spmv_mxu.pagerank_mxu_epilogue, delta=second)
    assert _counters() == (hits + 1, misses, compiles)
    got = _ranks(base_plan, second)          # a hit, and JAX's fast path
    assert _counters() == (hits + 2, misses, compiles)
    del run_a
    # the same jitted objects, on another delta blob and one base blob
    run_c = spmv_mxu.make_semiring_kernel(
        base_plan, spmv_mxu.pagerank_mxu_epilogue, delta=first)
    assert run_b.jitted is run_c.jitted
    assert run_b.jitted_default is run_c.jitted_default
    assert run_b.blob is run_c.blob
    assert run_b.delta_blob is not run_c.delta_blob
    assert np.isfinite(got).all()


def test_crossing_a_quantisation_step_is_one_miss(base_graph, base_plan):
    n = base_graph[0]
    small = _delta(base_plan, _burst(base_graph, "additions", 3, 64))
    _ranks(base_plan, small)
    hits, misses, _ = _counters()
    # 200 new edges out of one node take 200 gather rows, more than
    # the floor holds, so R_G takes one step of 4
    rng = np.random.default_rng(4)
    crowd = spmv_mxu.build_delta_plan(
        base_plan, np.full(200, 7), rng.integers(0, n, 200))
    assert crowd.R_G == spmv_mxu.DELTA_SHAPE_STEP * small.R_G
    spmv_mxu.make_pagerank_kernel(base_plan, delta=crowd)
    assert _counters()[:2] == (hits, misses + 1)
    spmv_mxu.make_pagerank_kernel(base_plan, delta=crowd)
    spmv_mxu.make_pagerank_kernel(base_plan, delta=small)
    assert _counters()[:2] == (hits + 2, misses + 1)


@pytest.mark.parametrize("kind", ["additions", "removals", "mixed"])
def test_reused_program_equals_fresh_kernel_and_replan(base_graph,
                                                       base_plan, kind):
    warm = _delta(base_plan, _burst(base_graph, kind, 20, 30))
    burst = _burst(base_graph, kind, 21, 50)
    delta = _delta(base_plan, burst)
    assert _signature_shapes(warm) == _signature_shapes(delta)
    assert delta.R_G == spmv_mxu.SG_ROWS          # padded, not exact
    _ranks(base_plan, warm)
    program = spmv_mxu.make_pagerank_kernel(base_plan, delta=warm)
    hits = _counter("mxu.program_hit_total")
    reused = _ranks(base_plan, delta)
    assert _counter("mxu.program_hit_total") == hits + 1
    del program
    spmv_mxu._PROGRAMS.clear()
    misses = _counter("mxu.program_miss_total")
    fresh = _ranks(base_plan, delta)
    assert _counter("mxu.program_miss_total") == misses + 1
    np.testing.assert_array_equal(reused, fresh)
    np.testing.assert_allclose(reused, _replanned(base_graph, burst),
                               rtol=2e-4, atol=1e-9)


def test_exact_shapes_without_bucketing(base_graph, base_plan):
    (a_s, a_d), _ = _burst(base_graph, "additions", 5, 10)
    exact = spmv_mxu.build_delta_plan(base_plan, a_s, a_d, bucket=False)
    padded = spmv_mxu.build_delta_plan(base_plan, a_s, a_d)
    assert exact.R_G < padded.R_G and exact.C <= padded.C
    np.testing.assert_array_equal(_ranks(base_plan, exact),
                                  _ranks(base_plan, padded))


def test_statics_are_fields_of_the_signature(base_plan):
    """Epilogue, route dtype, start state and "no delta" each make a
    program of their own, and none of them packs the base again."""
    import jax.numpy as jnp

    def other_epilogue(x, acc, env, params):
        return spmv_mxu.pagerank_mxu_epilogue(x, acc, env, params)

    runs = [
        spmv_mxu.make_semiring_kernel(base_plan,
                                      spmv_mxu.pagerank_mxu_epilogue),
        spmv_mxu.make_semiring_kernel(base_plan, other_epilogue),
        spmv_mxu.make_semiring_kernel(base_plan,
                                      spmv_mxu.pagerank_mxu_epilogue,
                                      route_dtype=jnp.bfloat16),
        spmv_mxu.make_semiring_kernel(base_plan,
                                      spmv_mxu.pagerank_mxu_epilogue,
                                      x0_default="zeros"),
    ]
    assert len({id(r.jitted_default) for r in runs}) == len(runs)
    assert len(spmv_mxu._PROGRAMS) == len(runs)
    assert all(r.blob is runs[0].blob for r in runs)
    assert all(r.delta_blob is None for r in runs)


def test_table_is_bounded(base_plan, monkeypatch):
    monkeypatch.setattr(spmv_mxu, "_PROGRAMS_MAX", 2)
    epilogues = [lambda x, acc, env, P, _i=i:
                 spmv_mxu.pagerank_mxu_epilogue(x, acc, env, P)
                 for i in range(3)]
    first = spmv_mxu.make_semiring_kernel(base_plan, epilogues[0])
    spmv_mxu.make_semiring_kernel(base_plan, epilogues[1])
    # a hit moves the first to the young end: the second is evicted
    assert spmv_mxu.make_semiring_kernel(
        base_plan, epilogues[0]).jitted is first.jitted
    spmv_mxu.make_semiring_kernel(base_plan, epilogues[2])
    assert len(spmv_mxu._PROGRAMS) == 2
    kept = {sig.epilogue for sig in spmv_mxu._PROGRAMS}
    assert kept == {epilogues[0], epilogues[2]}
    misses = _counter("mxu.program_miss_total")
    spmv_mxu.make_semiring_kernel(base_plan, epilogues[1])
    assert _counter("mxu.program_miss_total") == misses + 1


def test_two_threads_build_one_signature_once(base_plan, monkeypatch):
    built = []
    build = spmv_mxu._build_program

    def slow_build(sig):
        built.append(sig)
        time.sleep(0.2)
        return build(sig)

    monkeypatch.setattr(spmv_mxu, "_build_program", slow_build)
    spmv_mxu.make_semiring_kernel(base_plan,       # the base is resident
                                  spmv_mxu.pagerank_mxu_epilogue)
    built.clear()
    spmv_mxu._PROGRAMS.clear()
    runs, gate = [], threading.Barrier(2)

    def ask():
        gate.wait()
        runs.append(spmv_mxu.make_semiring_kernel(
            base_plan, spmv_mxu.pagerank_mxu_epilogue))

    threads = [threading.Thread(target=ask) for _ in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(built) == 1
    assert runs[0].jitted is runs[1].jitted
