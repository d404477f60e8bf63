"""PPR serving plane (ISSUE 11): request-coalescing batched
multi-source PPR with result caching.

Layers of coverage:

1. Batched multi-source kernel (ops/pagerank.py
   personalized_pagerank_batch): batched-vs-sequential BIT-EXACTNESS at
   f32 (converged lanes freeze at exactly the sequential stopping
   state), bf16 batches inside PRECISION_BOUNDS, warm-start convergence
   never slower than cold, on-device top-k extraction.
2. Serving plane (server/kernel_server.py PprServingPlane): coalescing
   of concurrent requests, mixed parameter groups never sharing a
   fixpoint, the change-log-driven cache protocol (hit on repeat,
   stale read impossible across a version bump, targeted invalidation
   keeping untouched sources hot, warm-start seeding), typed
   per-request outcomes (one bad/oversized request must not poison its
   batchmates; queue saturation sheds typed), and the device_chaos case
   (device fault mid-batch fails EVERY rider typed, never half).
3. Observability: ppr.* counters registered + riding the health reply,
   pro-rata device-stage attribution across batch members, per-member
   trace carriers yielding one connected trace, saturation-plane
   queue-depth/window checks flipping the /health verdict.
4. Kernel routing: ops-level personalized_pagerank(kernel=...) and the
   procedure layer's serving-route fallback honesty.
"""

import threading
import time

import numpy as np
import pytest

from memgraph_tpu.observability import stats as mgstats
from memgraph_tpu.observability.metrics import global_metrics
from memgraph_tpu.ops import csr
from memgraph_tpu.ops.pagerank import (personalized_pagerank,
                                       personalized_pagerank_batch,
                                       ppr_topk)
from memgraph_tpu.ops.semiring import PRECISION_BOUNDS
from memgraph_tpu.server.kernel_server import (
    AdmissionRejected, KernelClient, KernelDeviceError, KernelServer,
    SupervisedKernelClient)
from memgraph_tpu.utils import faultinject as FI

TOL = 1e-8


@pytest.fixture(autouse=True)
def _clean_faults():
    FI.reset()
    yield
    FI.reset()


def _graph(seed=0, n=300, e=1800):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n, e)
    dst = rng.integers(0, n, e)
    return csr.from_coo(src, dst, n_nodes=n).to_device(), (src, dst, n)


# ==========================================================================
# 1. batched multi-source kernel
# ==========================================================================


def test_batched_vs_sequential_bit_exact_f32():
    g, _ = _graph()
    rng = np.random.default_rng(1)
    sets = [rng.choice(g.n_nodes, size=rng.integers(1, 6), replace=False)
            for _ in range(6)]
    batch_ranks, _, batch_iters = personalized_pagerank_batch(
        g, sets, tol=TOL)
    for lane, sources in enumerate(sets):
        ranks, _, iters = personalized_pagerank(g, sources, tol=TOL)
        np.testing.assert_array_equal(np.asarray(ranks),
                                      batch_ranks[lane])
        assert iters == int(batch_iters[lane])


def test_batched_bf16_within_precision_bounds():
    g, _ = _graph()
    sets = [[3], [7, 11], [42]]
    f32, _, _ = personalized_pagerank_batch(g, sets, tol=TOL)
    bf16, _, _ = personalized_pagerank_batch(g, sets, tol=TOL,
                                             precision="bf16")
    bounds = PRECISION_BOUNDS["bf16"]
    assert np.abs(bf16 - f32).max() <= bounds["pagerank_linf"]
    assert np.abs(bf16 - f32).sum(axis=1).max() <= bounds["pagerank_l1"]


def test_warm_start_converges_no_slower_than_cold():
    g, _ = _graph()
    sets = [[3], [7], [11, 13]]
    # 1e-7, not TOL: a lane that stops within f32 rounding of 1e-8
    # (ranks ~3e-3, ulp ~2e-10 per element) re-verifies in a few noisy
    # steps instead of one, which says nothing about the warm start
    tol = 1e-7
    cold, _, cold_iters = personalized_pagerank_batch(g, sets, tol=tol)
    x0 = np.zeros((g.n_pad, len(sets)), dtype=np.float32)
    x0[:g.n_nodes] = cold.T
    _, _, warm_iters = personalized_pagerank_batch(g, sets, tol=tol,
                                                   x0=x0)
    assert (warm_iters <= cold_iters).all()
    assert warm_iters.max() <= 2     # converged seed: instant re-verify


def test_topk_on_device_matches_full_vector():
    g, _ = _graph()
    ranks, _, _ = personalized_pagerank_batch(g, [[3], [7]], tol=TOL)
    vals, idx = ppr_topk(ranks, g.n_nodes, 5)
    assert vals.shape == idx.shape == (2, 5)
    for lane in range(2):
        want = np.sort(ranks[lane])[::-1][:5]
        np.testing.assert_allclose(vals[lane], want, rtol=0)
        np.testing.assert_allclose(ranks[lane][idx[lane]], vals[lane],
                                   rtol=0)


def test_empty_batch_and_lane_bucketing():
    g, _ = _graph()
    ranks, err, iters = personalized_pagerank_batch(g, [], tol=TOL)
    assert ranks.shape == (0, g.n_nodes)
    # 3 lanes pad to the 4-bucket; padding lanes must not leak out
    ranks3, _, _ = personalized_pagerank_batch(g, [[1], [2], [3]],
                                               tol=TOL)
    assert ranks3.shape == (3, g.n_nodes)


def test_lane_bucket_compile_budget_via_mgxla():
    """The compile-count budget across lane buckets is the mgxla
    checker's claim, asserted here rather than re-derived: every batch
    width 1..128 folds onto exactly the declared bucket set (same
    bucket ⇒ cache hit, no silent recompile), every bucket has a
    contract-checked manifest kernel, and the manifest's mirror of the
    bucket table matches the product's."""
    from tools.mgxla import checker as mgxla_checker
    violations = mgxla_checker.check_lane_buckets()
    assert not violations, "\n".join(v.render() for v in violations)


# ==========================================================================
# 2. serving plane (in-thread daemon)
# ==========================================================================


@pytest.fixture(scope="module")
def server(tmp_path_factory):
    sock = str(tmp_path_factory.mktemp("pprsrv") / "ks.sock")
    srv = KernelServer(sock, wedge_after_s=30)
    srv._ppr.window_s = 0.03     # generous window: threads must coalesce
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    client = None
    deadline = time.monotonic() + 120
    while time.monotonic() < deadline:
        try:
            client = KernelClient(sock, timeout=60)
            break
        except OSError:
            time.sleep(0.05)
    assert client is not None, "in-thread kernel server never bound"
    yield srv, client, sock
    client.shutdown()
    client.close()


def _counter(name):
    return dict((n, v) for n, _k, v in global_metrics.snapshot()).get(
        name, 0.0)


def test_coalescing_concurrent_requests(server):
    """Concurrent clients ride ONE batch; each answer is bit-exact vs
    the sequential in-process PPR."""
    srv, _client, sock = server
    g, (src, dst, n) = _graph(seed=2)
    _client.ppr([0], src=src, dst=dst, n_nodes=n, graph_key="co",
                graph_version=1, tol=TOL)
    before = _counter("ppr.coalesced_total")
    results = {}
    barrier = threading.Barrier(8)

    def worker(i):
        c = KernelClient(sock, timeout=120)
        try:
            barrier.wait(timeout=30)
            results[i] = c.ppr([i + 1], graph_key="co", graph_version=1,
                               n_nodes=n, tol=TOL)
        finally:
            c.close()

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert len(results) == 8
    assert max(h["batch_size"] for h, _ in results.values()) > 1
    assert any(h["coalesced"] for h, _ in results.values())
    assert _counter("ppr.coalesced_total") > before
    for i, (h, out) in results.items():
        ranks, _, iters = personalized_pagerank(g, [i + 1], tol=TOL)
        np.testing.assert_array_equal(np.asarray(ranks), out["ranks"])
        assert h["iters"] == iters


def test_shared_client_gives_every_thread_its_own_answer(server):
    """The product path: the Bolt server's threads all call the ONE
    supervised client that ``shared_client`` keeps per daemon. Riders
    released together through it each get their own answer, bit for bit
    the sequential one, and they share a fixpoint: the client leases
    every call a connection of its own, where one shared stream would
    let the daemon see one rider at a time and the threads read each
    other's replies."""
    from memgraph_tpu.server.kernel_server import shared_client
    _srv, _client, sock = server
    g, (src, dst, n) = _graph(seed=5)
    _client.ppr([0], src=src, dst=dst, n_nodes=n, graph_key="shared",
                graph_version=1, tol=TOL)
    client = shared_client(sock)
    assert client is shared_client(sock)
    riders = 8
    before = _counter("ppr.coalesced_total")
    results, failures = {}, {}
    barrier = threading.Barrier(riders)

    def worker(i):
        try:
            barrier.wait(timeout=30)
            results[i] = client.ppr([i + 1, i + 40], graph_key="shared",
                                    graph_version=1, n_nodes=n, tol=TOL,
                                    top_k=5, deadline_s=60.0)
        except Exception as e:      # noqa: BLE001 - the test reports it
            failures[i] = repr(e)

    threads = [threading.Thread(target=worker, args=(i,), daemon=True)
               for i in range(riders)]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not failures, failures
        assert sorted(results) == list(range(riders))
        for i, (h, out) in results.items():
            ranks, _, iters = personalized_pagerank(g, [i + 1, i + 40],
                                                    tol=TOL)
            ranks = np.asarray(ranks)
            best = np.argsort(-ranks, kind="stable")[:5]
            np.testing.assert_array_equal(out["topk_idx"], best)
            np.testing.assert_array_equal(out["topk_val"], ranks[best])
            assert h["iters"] == iters
        assert _counter("ppr.coalesced_total") > before
        assert max(h["batch_size"] for h, _ in results.values()) > 1
    finally:
        client.close()


def test_lane_buckets_are_warm_after_the_first_request(tmp_path):
    """A graph shape's first batch warms every lane bucket a batch can
    be padded to: riders that later meet in a wider bucket compile
    nothing (the witness counts every backend compile and cache load of
    the process)."""
    from memgraph_tpu.utils.jax_cache import install_compile_counter
    assert install_compile_counter()
    sock = str(tmp_path / "ks.sock")
    srv = KernelServer(sock, wedge_after_s=60)
    srv._ppr.window_s, srv._ppr.max_batch = 0.05, 8
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    deadline = time.monotonic() + 120
    while True:
        try:
            client = KernelClient(sock, timeout=120)
            break
        except OSError:
            assert time.monotonic() < deadline
            time.sleep(0.05)
    try:
        # a shape no other test of this process has used
        rng = np.random.default_rng(9)
        n, e = 1100, 5000
        src, dst = rng.integers(0, n, e), rng.integers(0, n, e)
        client.ppr([1, 2], src=src, dst=dst, n_nodes=n, graph_key="warm",
                   graph_version=1, top_k=5)
        # answered after the warming, which holds the batcher
        client.ppr([3, 4], graph_key="warm", graph_version=1, n_nodes=n,
                   top_k=5)
        assert len(srv._ppr._warmed) == 1
        compiled = _counter("jit.compile_total")
        assert compiled > 0
        results = {}
        barrier = threading.Barrier(6)

        def worker(i):
            c = KernelClient(sock, timeout=120)
            try:
                barrier.wait(timeout=30)
                results[i] = c.ppr([10 + i, 20 + i], graph_key="warm",
                                   graph_version=1, n_nodes=n, top_k=5)
            finally:
                c.close()

        threads = [threading.Thread(target=worker, args=(i,), daemon=True)
                   for i in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert len(results) == 6
        assert max(h["batch_size"] for h, _ in results.values()) > 2
        assert _counter("jit.compile_total") == compiled
        assert len(srv._ppr._warmed) == 1
    finally:
        client.shutdown()
        client.close()


def test_mixed_parameter_groups_never_share_a_fixpoint(server):
    """Requests with differing damping/tol in one arrival window
    execute as SEPARATE fixpoints — each bit-exact vs its own
    sequential counterpart."""
    _srv, client, sock = server
    g, (src, dst, n) = _graph(seed=3)
    client.ppr([0], src=src, dst=dst, n_nodes=n, graph_key="mix",
               graph_version=1, tol=TOL)
    params = [(0.85, TOL), (0.7, TOL), (0.85, 1e-4), (0.7, 1e-4)]
    results = {}
    barrier = threading.Barrier(len(params))

    def worker(i, damping, tol):
        c = KernelClient(sock, timeout=120)
        try:
            barrier.wait(timeout=30)
            results[i] = c.ppr([5], graph_key="mix", graph_version=1,
                               n_nodes=n, damping=damping, tol=tol)
        finally:
            c.close()

    threads = [threading.Thread(target=worker, args=(i, d, t))
               for i, (d, t) in enumerate(params)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert len(results) == len(params)
    for i, (damping, tol) in enumerate(params):
        h, out = results[i]
        ranks, _, iters = personalized_pagerank(g, [5], damping=damping,
                                                tol=tol)
        np.testing.assert_array_equal(np.asarray(ranks), out["ranks"])
        assert h["iters"] == iters


def test_cache_hit_on_repeat_and_stale_read_impossible(server):
    """Repeat → hit (no device). Commit touching the source's
    neighborhood → the old vector is never served again; the recompute
    warm-starts from it."""
    _srv, client, _ = server
    _, (src, dst, n) = _graph(seed=4)
    h1, out1 = client.ppr([3], src=src, dst=dst, n_nodes=n,
                          graph_key="inv", graph_version=1, tol=TOL)
    assert h1["cache"] == "miss"
    h2, out2 = client.ppr([3], graph_key="inv", graph_version=1,
                          n_nodes=n, tol=TOL)
    assert h2["cache"] == "hit"
    np.testing.assert_array_equal(out1["ranks"], out2["ranks"])

    # commit: rewire one of node 3's out-edges; delta names 3 + the dst
    src2, dst2 = src.copy(), dst.copy()
    edge = np.where(src2 == 3)[0][0]
    dst2[edge] = (dst2[edge] + 7) % n
    h3, out3 = client.ppr([3], src=src2, dst=dst2, n_nodes=n,
                          graph_key="inv", graph_version=2,
                          base_version=1,
                          changed=[3, int(dst2[edge]), int(dst[edge])],
                          tol=TOL)
    assert h3["cache"] == "warm"          # invalidated + warm-started
    assert not np.array_equal(out1["ranks"], out3["ranks"])
    g2 = csr.from_coo(src2, dst2, n_nodes=n).to_device()
    want, _, _ = personalized_pagerank(g2, [3], tol=TOL)
    np.testing.assert_allclose(out3["ranks"], np.asarray(want),
                               atol=float(TOL))


def test_targeted_invalidation_keeps_untouched_sources_hot(server):
    _srv, client, _ = server
    _, (src, dst, n) = _graph(seed=5)
    client.ppr([100], src=src, dst=dst, n_nodes=n, graph_key="tgt",
               graph_version=1, tol=TOL)
    h, _ = client.ppr([100], graph_key="tgt", graph_version=1,
                      n_nodes=n, tol=TOL)
    assert h["cache"] == "hit"
    # bump with a delta that cannot touch node 100's out-neighborhood
    far = [int(i) for i in range(n)
           if i != 100 and i not in set(dst[src == 100])][:2]
    h, _ = client.ppr([100], src=src, dst=dst, n_nodes=n,
                      graph_key="tgt", graph_version=2, base_version=1,
                      changed=far, tol=TOL)
    assert h["cache"] == "hit"            # provably untouched: still hot


def test_unknowable_delta_invalidates_whole_key(server):
    _srv, client, _ = server
    _, (src, dst, n) = _graph(seed=6)
    client.ppr([9], src=src, dst=dst, n_nodes=n, graph_key="flush",
               graph_version=1, tol=TOL)
    # version bump with NO delta (change log evicted): conservative
    h, _ = client.ppr([9], src=src, dst=dst, n_nodes=n,
                      graph_key="flush", graph_version=2, tol=TOL)
    assert h["cache"] in ("warm", "miss")
    assert h["cache"] != "hit"


def _round_of_sets(sock, key, n, base, riders=6):
    """``riders`` clients released together, each with its own 4 sources."""
    replies, failures = {}, {}
    barrier = threading.Barrier(riders)

    def worker(i):
        c = KernelClient(sock, timeout=120)
        try:
            barrier.wait(timeout=30)
            replies[i] = c.ppr([base + 4 * i + j for j in range(4)],
                               graph_key=key, graph_version=1, n_nodes=n,
                               top_k=5)
        except Exception as e:      # noqa: BLE001 - the test reports it
            failures[i] = repr(e)
        finally:
            c.close()

    threads = [threading.Thread(target=worker, args=(i,), daemon=True)
               for i in range(riders)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not failures, failures
    assert len(replies) == riders
    return replies


def test_cache_fill_reads_offsets_and_scans_nothing(server):
    """Batches of several riders: a neighbourhood a rider, every one
    from the row offsets its snapshot carried (no pass over the edges),
    and both counters in the daemon's health reply."""
    _srv, client, sock = server
    _, (src, dst, n) = _graph(seed=11)
    scans = _counter("ppr.neigh_scan_total")
    reads = _counter("ppr.neigh_offsets_total")
    batches = _counter("ppr.batches_total")
    client.ppr([0, 1, 2, 3], src=src, dst=dst, n_nodes=n,
               graph_key="neigh", graph_version=1, top_k=5)
    riders = 1
    for rnd in range(3):
        replies = _round_of_sets(sock, "neigh", n, 4 + 24 * rnd)
        assert all(h["cache"] == "miss" for h, _ in replies.values())
        riders += len(replies)
    assert _counter("ppr.batches_total") - batches < riders
    assert _counter("ppr.neigh_scan_total") == scans
    assert _counter("ppr.neigh_offsets_total") == reads + riders
    counters = client.health()["counters"]
    assert counters["ppr.neigh_offsets_total"] == reads + riders
    assert counters.get("ppr.neigh_scan_total", 0.0) == scans


def test_snapshot_handed_over_without_offsets_is_scanned_once(server):
    """A DeviceGraph that reaches the plane without host offsets gets
    them built once, however many riders follow, and the sets filed
    from them invalidate exactly as the definition's do."""
    import dataclasses
    srv, client, sock = server
    _, (src, dst, n) = _graph(seed=12)
    client.ppr([0, 1, 2, 3], src=src, dst=dst, n_nodes=n,
               graph_key="bare", graph_version=1, top_k=5)
    with srv._dispatch_lock:
        gen = srv._graphs["bare"]
        gen._graph = bare = dataclasses.replace(gen.graph, host_csr=None)
    scans = _counter("ppr.neigh_scan_total")
    reads = _counter("ppr.neigh_offsets_total")
    riders = sum(len(_round_of_sets(sock, "bare", n, 4 + 24 * rnd))
                 for rnd in range(2))
    assert srv._graphs["bare"].graph is bare
    assert bare.host_csr is not None
    assert _counter("ppr.neigh_scan_total") == scans + 1
    assert _counter("ppr.neigh_offsets_total") == reads + riders
    assert client.health()["counters"]["ppr.neigh_scan_total"] == \
        scans + 1
    mine = [4, 5, 6, 7]
    reach = set(mine) | set(int(d) for d in dst[np.isin(src, mine)])
    far = [v for v in range(n) if v not in reach][:2]
    h, _ = client.ppr(mine, src=src, dst=dst, graph_key="bare",
                      graph_version=2, base_version=1, changed=far,
                      n_nodes=n, top_k=5)
    assert h["cache"] == "hit"            # provably untouched
    near = sorted(reach - set(mine))[:1]
    h, _ = client.ppr(mine, src=src, dst=dst, graph_key="bare",
                      graph_version=3, base_version=2, changed=near,
                      n_nodes=n, top_k=5)
    assert h["cache"] == "warm"           # demoted by a neighbour's change


def test_one_bad_request_does_not_poison_the_batch(server):
    """Outcome matrix: an invalid request (sources out of range) and an
    oversized request ride the same window as good ones — each gets its
    own typed outcome, the good ones complete."""
    srv, client, sock = server
    g, (src, dst, n) = _graph(seed=7)
    client.ppr([0], src=src, dst=dst, n_nodes=n, graph_key="mixed",
               graph_version=1, tol=TOL)
    outcomes = {}
    barrier = threading.Barrier(3)

    def good(i):
        c = KernelClient(sock, timeout=120)
        try:
            barrier.wait(timeout=30)
            outcomes[i] = ("ok", c.ppr([i], graph_key="mixed",
                                       graph_version=1, n_nodes=n,
                                       tol=TOL))
        except Exception as e:  # noqa: BLE001 — recorded for assertion
            outcomes[i] = ("exc", e)
        finally:
            c.close()

    def bad():
        c = KernelClient(sock, timeout=120)
        try:
            barrier.wait(timeout=30)
            outcomes["bad"] = ("ok", c.ppr([n + 50], graph_key="mixed",
                                           graph_version=1, n_nodes=n,
                                           tol=TOL))
        except Exception as e:  # noqa: BLE001 — recorded for assertion
            outcomes["bad"] = ("exc", e)
        finally:
            c.close()

    threads = [threading.Thread(target=good, args=(1,)),
               threading.Thread(target=good, args=(2,)),
               threading.Thread(target=bad)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    kind, err = outcomes["bad"]
    assert kind == "exc" and "out of range" in str(err)
    for i in (1, 2):
        kind, (h, out) = outcomes[i]
        assert kind == "ok" and h["outcome"] == "completed"
        ranks, _, _ = personalized_pagerank(g, [i], tol=TOL)
        np.testing.assert_array_equal(np.asarray(ranks), out["ranks"])


def test_oversized_request_sheds_typed(server):
    srv, client, _ = server
    _, (src, dst, n) = _graph(seed=8)
    old = srv.hbm_budget_bytes
    srv.hbm_budget_bytes = 1024
    try:
        with pytest.raises(AdmissionRejected) as ei:
            client.ppr([1], src=src, dst=dst, n_nodes=n,
                       graph_key="shed", graph_version=1, tol=TOL)
        assert ei.value.outcome == "shed"
        assert not ei.value.retryable
    finally:
        srv.hbm_budget_bytes = old
    assert _counter("ppr.shed_total") >= 1


def test_queue_saturation_sheds_typed(server):
    srv, client, _ = server
    _, (src, dst, n) = _graph(seed=9)
    client.ppr([0], src=src, dst=dst, n_nodes=n, graph_key="sat",
               graph_version=1, tol=TOL)
    old = srv._ppr.max_queue
    srv._ppr.max_queue = 0
    try:
        with pytest.raises(AdmissionRejected) as ei:
            client.ppr([1], graph_key="sat", graph_version=1, n_nodes=n,
                       tol=TOL)
        assert "queue saturated" in str(ei.value)
    finally:
        srv._ppr.max_queue = old


def test_ppr_counters_ride_the_health_reply(server):
    _srv, client, _ = server
    h = client.health()
    names = set(h["counters"])
    assert any(nm.startswith("ppr.") for nm in names)
    assert "ppr.requests_total" in names
    assert "ppr.batches_total" in names


def test_prorata_stage_attribution_across_batch_members(server):
    """The batch's device seconds split evenly across its riders: each
    member's shipped stages carry 1/B of the batch total, so per-query
    PROFILE sums stay truthful."""
    _srv, _client, sock = server
    _, (src, dst, n) = _graph(seed=10)
    _client.ppr([0], src=src, dst=dst, n_nodes=n, graph_key="stage",
                graph_version=1, tol=TOL)
    shares = {}
    barrier = threading.Barrier(4)

    def worker(i):
        c = KernelClient(sock, timeout=120)
        acc = mgstats.StageAccumulator()
        try:
            barrier.wait(timeout=30)
            with mgstats.collecting_stages(acc):
                h, _ = c.ppr([i + 1], graph_key="stage",
                             graph_version=1, n_nodes=n, tol=TOL)
            shares[i] = (h["batch_size"], acc.snapshot())
        finally:
            c.close()

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert len(shares) == 4
    batched = [(b, snap) for b, snap in shares.values() if b > 1]
    assert batched, "no coalescing happened — widen the window"
    for b, snap in batched:
        assert snap.get("device_iterate", {}).get("seconds", 0) > 0
    # riders of the SAME batch carry identical (pro-rata) shares
    by_size: dict = {}
    for b, snap in batched:
        by_size.setdefault(b, []).append(
            snap["device_iterate"]["seconds"])
    for vals in by_size.values():
        assert max(vals) - min(vals) < 1e-9


def test_per_member_trace_carrier_yields_connected_trace(server):
    from memgraph_tpu.observability import trace as mgtrace
    _srv, client, _ = server
    _, (src, dst, n) = _graph(seed=11)
    mgtrace.enable(sample=1.0)
    try:
        handle = mgtrace.begin_trace("query")
        with mgtrace.activate(handle.ctx):
            client.ppr([2], src=src, dst=dst, n_nodes=n,
                       graph_key="tr", graph_version=1, tol=TOL)
        handle.finish(force_keep=True)
        traces = mgtrace.traces_json(handle.ctx.trace_id)
        assert traces
        names = {s["name"] for s in traces[0]}
        assert "kernel.dispatch" in names
        disp = [s for s in traces[0] if s["name"] == "kernel.dispatch"]
        assert disp[0]["attrs"].get("op") == "ppr"
        assert all(s["trace_id"] == handle.ctx.trace_id
                   for s in traces[0])
    finally:
        mgtrace.disable()


def test_saturation_plane_trips_on_ppr_queue_depth():
    plane = mgstats.SaturationPlane()
    plane.evaluate()                      # prime
    global_metrics.set_gauge("ppr.queue_depth", plane.max_ppr_queue + 8)
    try:
        verdict = plane.evaluate()
        assert not verdict["ready"]
        assert any(r["check"] == "ppr_queue"
                   for r in verdict["reasons"])
    finally:
        global_metrics.set_gauge("ppr.queue_depth", 0.0)
    assert plane.evaluate()["checks"]["ppr_queue"] == "ok"


def test_saturation_plane_trips_on_window_occupancy_with_backlog():
    plane = mgstats.SaturationPlane()
    plane.evaluate()
    global_metrics.set_gauge("ppr.window_occupancy", 1.0)
    global_metrics.set_gauge("ppr.queue_depth", 4.0)
    try:
        verdict = plane.evaluate()
        assert any(r["check"] == "ppr_window"
                   for r in verdict["reasons"])
    finally:
        global_metrics.set_gauge("ppr.window_occupancy", 0.0)
        global_metrics.set_gauge("ppr.queue_depth", 0.0)
    assert plane.evaluate()["checks"]["ppr_window"] == "ok"


# ==========================================================================
# 3. kernel routing (ops + supervised client)
# ==========================================================================


def test_ops_level_kernel_routing_matches_in_process(server):
    _srv, _client, sock = server
    g, _ = _graph(seed=12)
    want, werr, witers = personalized_pagerank(g, [4, 8], tol=TOL)
    sup = SupervisedKernelClient(sock, spawn=False)
    try:
        got, gerr, giters = personalized_pagerank(g, [4, 8], tol=TOL,
                                                  kernel=sup)
        np.testing.assert_array_equal(np.asarray(want), got)
        assert witers == giters
    finally:
        sup.close()


def test_kernel_routing_falls_back_loudly_on_dead_socket(tmp_path):
    g, _ = _graph(seed=13)
    before = _counter("analytics.kernel_route_fallback_total")
    ranks, _, _ = personalized_pagerank(
        g, [3], tol=TOL, kernel=str(tmp_path / "nothing.sock"))
    want, _, _ = personalized_pagerank(g, [3], tol=TOL)
    np.testing.assert_array_equal(np.asarray(want), np.asarray(ranks))
    assert _counter("analytics.kernel_route_fallback_total") > before


def test_supervised_client_ppr_retries_transient_device_error(server):
    _srv, _client, sock = server
    _, (src, dst, n) = _graph(seed=14)
    _client.ppr([0], src=src, dst=dst, n_nodes=n, graph_key="ret",
                graph_version=1, tol=TOL)
    FI.arm("device.call", "raise", at=1)
    sup = SupervisedKernelClient(sock, spawn=False)
    try:
        h, out = sup.ppr([6], graph_key="ret", graph_version=1,
                         n_nodes=n, tol=TOL)
        assert h["outcome"] == "completed"
        g = csr.from_coo(src, dst, n_nodes=n).to_device()
        want, _, _ = personalized_pagerank(g, [6], tol=TOL)
        np.testing.assert_array_equal(np.asarray(want), out["ranks"])
    finally:
        sup.close()


# ==========================================================================
# 4. device chaos: a batch dies whole or answers whole
# ==========================================================================


@pytest.mark.device_chaos
def test_device_lost_mid_batch_never_half_answers(server):
    """device.lost during a coalesced batch: EVERY rider gets the same
    typed retryable failure — no member is left with a stale or partial
    answer — and the next batch completes."""
    _srv, client, sock = server
    g, (src, dst, n) = _graph(seed=15)
    client.ppr([0], src=src, dst=dst, n_nodes=n, graph_key="chaos",
               graph_version=1, tol=TOL)
    FI.arm("device.lost", "raise", at=1)
    outcomes = {}
    barrier = threading.Barrier(4)

    def worker(i):
        c = KernelClient(sock, timeout=120)
        try:
            barrier.wait(timeout=30)
            outcomes[i] = ("ok", c.ppr([i + 1], graph_key="chaos",
                                       graph_version=1, n_nodes=n,
                                       tol=TOL))
        except KernelDeviceError as e:
            outcomes[i] = ("typed", e)
        except Exception as e:  # noqa: BLE001 — recorded for assertion
            outcomes[i] = ("other", e)
        finally:
            c.close()

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    FI.reset()
    assert len(outcomes) == 4
    kinds = {k for k, _ in outcomes.values()}
    # the fault fires once (at=1): riders of the faulted batch fail
    # TYPED; riders of any later batch complete exactly. Nothing else.
    assert kinds <= {"typed", "ok"}
    assert "typed" in kinds
    for kind, payload in outcomes.values():
        if kind == "ok":
            h, out = payload
            assert h["outcome"] == "completed"
    # the plane recovered: a fresh request completes bit-exact
    h, out = client.ppr([1], graph_key="chaos", graph_version=1,
                        n_nodes=n, tol=TOL)
    want, _, _ = personalized_pagerank(g, [1], tol=TOL)
    np.testing.assert_array_equal(np.asarray(want), out["ranks"])
