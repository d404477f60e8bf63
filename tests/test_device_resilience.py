"""Resilient accelerator plane (ISSUE 7): seeded device-nemesis matrix.

Four layers of coverage:

1. Checkpoint/resume core (parallel/checkpoint.py): chunked kernels are
   bit-exact vs monolithic; a device fault (call/oom/lost) mid-pagerank
   resumes from the last checkpoint — bit-exact vs an unfaulted run,
   re-executing at most k iterations; a hang is observed as a slow
   chunk; a persistent fault exhausts the retry budget loudly.
2. Supervised kernel server: typed outcomes (completed /
   deadline_exceeded / device_error / oom / shed / invalid) end to end
   over the wire, the HBM admission guard, health/wedge reporting, and
   the client-side supervisor's retry + restart logic. Includes the
   CHECKER-HONESTY case: with supervision disabled a device hang wedges
   the client — and the harness detects and flags exactly that.
3. Seeded device-nemesis schedules (tools/mgchaos/device.py): byte
   identity, full (op x context) matrix coverage, and — device_chaos
   marked — the 10-seed sweep of the whole matrix plus the real
   subprocess kill/respawn path.
4. RetryPolicy deadline semantics (utils/retry.py).
"""

import os
import pathlib
import sys
import threading
import time

import numpy as np
import pytest

from memgraph_tpu.ops import csr
from memgraph_tpu.parallel import analytics
from memgraph_tpu.parallel.checkpoint import (Checkpoint, CheckpointStore,
                                              RunReport, default_store)
from memgraph_tpu.parallel.mesh import get_mesh_context
from memgraph_tpu.server.kernel_server import (
    AdmissionRejected, KernelClient, KernelDeadlineExceeded,
    KernelDeviceError, KernelOom, KernelServer, SupervisedKernelClient,
    probe_device)
from memgraph_tpu.utils import faultinject as FI
from memgraph_tpu.utils.devicefault import (DeviceLostError, DeviceOomError,
                                            classify_device_error)
from memgraph_tpu.utils.retry import RetryPolicy

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO)) if str(REPO) not in sys.path else None

from tools.mgchaos.device import (DEVICE_CONTEXTS, device_schedule,  # noqa: E402
                                  device_schedule_text, run_device_matrix)

K = 4              # checkpoint interval the resume tests run with
ITERS = 16         # tol=-1 pins runs to exactly this many iterations
SWEEP_SEEDS = range(10)


@pytest.fixture(autouse=True)
def _clean_faults():
    FI.reset()
    yield
    FI.reset()


@pytest.fixture(scope="module")
def graph():
    rng = np.random.default_rng(0)
    n, e = 203, 1200           # uneven n: pads the last vertex block
    return csr.from_coo(rng.integers(0, n, e), rng.integers(0, n, e),
                        n_nodes=n)


@pytest.fixture(scope="module")
def ctx4():
    return get_mesh_context(4)


def _pagerank(graph, ctx, k=K, report=None, **kw):
    return analytics.pagerank_mesh(graph, ctx, max_iterations=ITERS,
                                   tol=-1.0, checkpoint_every=k,
                                   report=report, **kw)


# ==========================================================================
# 1. checkpoint/resume core
# ==========================================================================


def test_chunked_pagerank_bit_exact_vs_monolithic(graph, ctx4):
    mono, err_m, it_m = _pagerank(graph, ctx4, k=0)
    chunk, err_c, it_c = _pagerank(graph, ctx4, k=3)
    assert it_m == it_c == ITERS
    assert err_m == err_c
    np.testing.assert_array_equal(np.asarray(mono), np.asarray(chunk))


def test_chunked_katz_labelprop_wcc_bit_exact(graph, ctx4):
    km, _, ikm = analytics.katz_mesh(graph, ctx4, alpha=0.05,
                                     max_iterations=30, tol=1e-8,
                                     normalized=True)
    kc, _, ikc = analytics.katz_mesh(graph, ctx4, alpha=0.05,
                                     max_iterations=30, tol=1e-8,
                                     normalized=True, checkpoint_every=4)
    assert ikm == ikc
    np.testing.assert_array_equal(np.asarray(km), np.asarray(kc))
    lm, ilm = analytics.label_propagation_mesh(graph, ctx4,
                                               max_iterations=20)
    lc, ilc = analytics.label_propagation_mesh(graph, ctx4,
                                               max_iterations=20,
                                               checkpoint_every=3)
    assert ilm == ilc
    np.testing.assert_array_equal(np.asarray(lm), np.asarray(lc))
    cm, icm = analytics.components_mesh(graph, ctx4)
    cc, icc = analytics.components_mesh(graph, ctx4, checkpoint_every=2)
    assert icm == icc
    np.testing.assert_array_equal(np.asarray(cm), np.asarray(cc))


@pytest.mark.parametrize("point,expect", [
    ("device.call", "device_error"),
    ("device.oom", "oom"),
    ("device.lost", "device_lost"),
])
@pytest.mark.parametrize("hit", [1, 3])
def test_fault_mid_pagerank_resumes_bit_exact(graph, ctx4, point, expect,
                                              hit):
    """A device fault at chunk `hit` resumes from the last checkpoint:
    result bit-exact vs the unfaulted run, at most k iterations redone."""
    ref, _, _ = _pagerank(graph, ctx4)
    FI.arm(point, "raise", at=hit)
    report = RunReport()
    out, _, iters = _pagerank(graph, ctx4, report=report)
    assert iters == ITERS
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(out))
    assert report.resumes == 1
    assert report.faults == [expect]
    assert report.lost_spans and max(report.lost_spans) <= K
    if expect == "device_lost":
        assert report.rebuilds == 1    # inputs were re-placed


def test_hang_mid_pagerank_completes_and_is_observed(graph, ctx4):
    from memgraph_tpu.parallel.distributed import pagerank_partition_centric
    ref, _, _ = _pagerank(graph, ctx4)
    scsr = csr.shard_csr(graph, ctx4, by="src")
    FI.arm("device.hang", "delay", arg=0.3, at=2)
    report = RunReport()
    out, _, _ = pagerank_partition_centric(
        scsr, ctx4, max_iterations=ITERS, tol=-1.0, checkpoint_every=K,
        chunk_deadline_s=0.05, report=report)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(out))
    assert report.slow_chunks >= 1
    assert report.resumes == 0         # a hang completes, late


def test_persistent_fault_exhausts_retry_budget(graph, ctx4):
    FI.arm("device.call", "raise")     # every hit
    report = RunReport()
    with pytest.raises(Exception) as ei:
        _pagerank(graph, ctx4, report=report)
    assert classify_device_error(ei.value) == "device_error"
    assert report.resumes >= 1         # it DID try before giving up


def test_fault_during_first_chunk_resumes_from_start(graph, ctx4):
    ref, _, _ = _pagerank(graph, ctx4)
    FI.arm("device.oom", "raise", at=1)
    report = RunReport()
    out, _, _ = _pagerank(graph, ctx4, report=report)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(out))
    assert report.resumes == 1 and report.lost_spans == [K]


def test_resumable_metrics_counted(graph, ctx4):
    from memgraph_tpu.observability.metrics import global_metrics

    def counter(name):
        return dict((n, v) for n, _k, v in global_metrics.snapshot()
                    ).get(name, 0.0)

    saved0 = counter("analytics.checkpoint.saved_total")
    resumed0 = counter("analytics.resume_total")
    FI.arm("device.call", "raise", at=2)
    _pagerank(graph, ctx4)
    assert counter("analytics.checkpoint.saved_total") > saved0
    assert counter("analytics.resume_total") == resumed0 + 1
    assert counter("analytics.device_fault.device_error_total") >= 1


def test_checkpoint_store_roundtrip_and_lru():
    store = CheckpointStore()
    for i in range(store.MAX_JOBS + 5):
        store.put(f"job{i}", Checkpoint("pagerank", i, (np.arange(3),)))
    assert len(store.jobs()) == store.MAX_JOBS
    assert store.get("job0") is None          # evicted
    got = store.get(f"job{store.MAX_JOBS + 4}")
    assert got.iteration == store.MAX_JOBS + 4
    store.drop(f"job{store.MAX_JOBS + 4}")
    assert store.get(f"job{store.MAX_JOBS + 4}") is None
    assert default_store() is default_store()


def test_named_job_resume_across_callers(graph, ctx4):
    """A caller that died mid-run resumes from the named job's
    checkpoint: the second run starts at the stored iteration."""
    store = CheckpointStore()
    FI.arm("device.call", "raise")     # permanent: first run must die
    with pytest.raises(Exception):
        _pagerank(graph, ctx4, job="resume-me", store=store,
                  retry=RetryPolicy(max_retries=0, base_delay=0.01))
    ck = store.get("resume-me")
    assert ck is not None and ck.iteration == 0
    FI.reset()
    ref, _, _ = _pagerank(graph, ctx4)
    report = RunReport()
    out, _, _ = _pagerank(graph, ctx4, job="resume-me", store=store,
                          report=report)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(out))
    assert store.get("resume-me") is None     # completed → dropped


# ==========================================================================
# 2. supervised kernel server (in-thread daemon)
# ==========================================================================


@pytest.fixture(scope="module")
def server(tmp_path_factory):
    sock = str(tmp_path_factory.mktemp("devres") / "ks.sock")
    srv = KernelServer(sock, wedge_after_s=0.4, checkpoint_every=K)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    client = None
    deadline = time.monotonic() + 120
    while time.monotonic() < deadline:
        try:
            client = KernelClient(sock, timeout=30)
            break
        except OSError:
            time.sleep(0.05)
    assert client is not None, "in-thread kernel server never bound"
    yield srv, client, sock
    client.shutdown()
    client.close()


@pytest.fixture(scope="module")
def served_graph(server):
    """A graph preloaded into the server cache + its unfaulted ranks."""
    _, client, _ = server
    rng = np.random.default_rng(1)
    n, e = 300, 1800
    src, dst = rng.integers(0, n, e), rng.integers(0, n, e)
    ranks, _, _ = client.pagerank(src=src, dst=dst, n_nodes=n,
                                  graph_key="devres",
                                  max_iterations=ITERS, tol=1e-12)
    return np.asarray(ranks), (src, dst, n)


@pytest.mark.parametrize("point,exc,outcome", [
    ("device.call", KernelDeviceError, "device_error"),
    ("device.oom", KernelOom, "oom"),
    ("device.lost", KernelDeviceError, "device_error"),
])
def test_typed_outcome_mid_kernel_request(server, served_graph, point,
                                          exc, outcome):
    """A device fault at the dispatch boundary surfaces as a TYPED
    client exception; the server survives and the next request works."""
    _, client, _ = server
    ref, _ = served_graph
    FI.arm(point, "raise", at=1)
    with pytest.raises(exc) as ei:
        client.pagerank(graph_key="devres", max_iterations=ITERS,
                        tol=1e-12)
    assert ei.value.outcome == outcome
    FI.reset()
    assert client.ping()
    ranks, _, _ = client.pagerank(graph_key="devres",
                                  max_iterations=ITERS, tol=1e-12)
    np.testing.assert_array_equal(np.asarray(ranks), ref)


def test_fault_mid_compute_is_resumed_server_side(server, served_graph):
    """Armed past the dispatch boundary, the fault lands inside the
    resumable loop: the SERVER resumes from its checkpoint and the
    client sees a completed, bit-exact reply — no error at all."""
    _, client, _ = server
    ref, _ = served_graph
    FI.arm("device.call", "raise", at=2)     # hit 2 = first chunk
    ranks, _, _ = client.pagerank(graph_key="devres",
                                  max_iterations=ITERS, tol=1e-12)
    np.testing.assert_array_equal(np.asarray(ranks), ref)


def test_dispatch_deadline_exceeded_then_recovers(server, served_graph):
    _, client, _ = server
    ref, _ = served_graph
    FI.arm("device.hang", "delay", arg=0.8, at=1)
    t0 = time.monotonic()
    with pytest.raises(KernelDeadlineExceeded):
        client.pagerank(graph_key="devres", deadline_s=0.15,
                        max_iterations=ITERS, tol=1e-12)
    assert time.monotonic() - t0 < 0.6       # typed failure, not a wedge
    h = client.health()
    assert h["in_flight"] >= 1               # the dispatch is still stuck
    time.sleep(0.9)                          # let the hang drain
    FI.reset()
    ranks, _, _ = client.pagerank(graph_key="devres",
                                  max_iterations=ITERS, tol=1e-12)
    np.testing.assert_array_equal(np.asarray(ranks), ref)


def test_admission_guard_sheds_typed_and_counts(server, served_graph):
    srv, client, _ = server
    _, (src, dst, n) = served_graph
    before = client.health()["counters"].get(
        "kernel_server.admission_rejected_total", 0)
    old_budget = srv.hbm_budget_bytes
    srv.hbm_budget_bytes = 1024
    try:
        with pytest.raises(AdmissionRejected) as ei:
            client.pagerank(src=src, dst=dst, n_nodes=n)
        assert ei.value.outcome == "shed"
        assert not ei.value.retryable
    finally:
        srv.hbm_budget_bytes = old_budget
    h = client.health()
    assert h["counters"]["kernel_server.admission_rejected_total"] \
        == before + 1
    assert h["counters"]["kernel_server.dispatch.shed_total"] >= 1


def test_supervised_client_retries_transient_device_error(server,
                                                          served_graph):
    _, _, sock = server
    ref, _ = served_graph
    FI.arm("device.call", "raise", at=1)     # first attempt fails typed
    sup = SupervisedKernelClient(
        sock, spawn=False, deadline_s=30.0,
        retry=RetryPolicy(base_delay=0.05, max_retries=3,
                          attempt_timeout=30.0))
    try:
        ranks, _, _ = sup.pagerank(graph_key="devres",
                                   max_iterations=ITERS, tol=1e-12)
        np.testing.assert_array_equal(np.asarray(ranks), ref)
    finally:
        sup.close()


def test_supervised_client_does_not_retry_shed_or_oom(server,
                                                      served_graph):
    srv, _, sock = server
    _, (src, dst, n) = served_graph
    sup = SupervisedKernelClient(
        sock, spawn=False,
        retry=RetryPolicy(base_delay=0.05, max_retries=3,
                          attempt_timeout=30.0))
    old_budget = srv.hbm_budget_bytes
    srv.hbm_budget_bytes = 1024
    t0 = time.monotonic()
    try:
        with pytest.raises(AdmissionRejected):
            sup.pagerank(src=src, dst=dst, n_nodes=n)
        assert time.monotonic() - t0 < 1.0   # immediate, not retried
        srv.hbm_budget_bytes = old_budget
        FI.arm("device.oom", "raise")        # persistent oom
        with pytest.raises(KernelOom):
            sup.pagerank(graph_key="devres", max_iterations=ITERS,
                         tol=1e-12)
    finally:
        srv.hbm_budget_bytes = old_budget
        sup.close()


def test_health_reports_wedged_during_overdue_dispatch(server,
                                                       served_graph):
    """wedge_after_s=0.4: a hang longer than that flips health.wedged
    even when the CLIENT asked for no deadline (supervision off)."""
    _, client, sock = server
    FI.arm("device.hang", "delay", arg=1.2, at=1)

    errs = []

    def hung_call():
        c2 = KernelClient(sock, timeout=5)
        try:
            c2.pagerank(graph_key="devres", max_iterations=ITERS,
                        tol=1e-12)
        except Exception as e:  # noqa: BLE001 — recorded for the caller
            errs.append(e)
        finally:
            c2.close()

    t = threading.Thread(target=hung_call, daemon=True)
    t.start()
    time.sleep(0.7)                          # > wedge_after_s, < hang
    h = client.health()
    assert h["wedged"] is True
    assert h["in_flight"] >= 1
    t.join(timeout=10)
    assert not errs                          # it completed, late
    h = client.health()
    assert h["wedged"] is False


def test_wedge_honesty_supervision_disabled_is_detected(server,
                                                        served_graph):
    """CHECKER HONESTY: with supervision disabled (no deadline) a hang
    WEDGES the client — and the harness must detect exactly that (the
    socket-level watchdog trips, health shows the stuck dispatch).
    With supervision enabled the same fault is a typed outcome."""
    _, client, sock = server
    FI.arm("device.hang", "delay", arg=1.0, at=1)
    unsupervised = KernelClient(sock, timeout=0.25)
    wedged = False
    try:
        unsupervised.pagerank(graph_key="devres", max_iterations=ITERS,
                              tol=1e-12)   # NO deadline_s: supervision off
    except OSError:                        # socket timeout = wedged client
        wedged = True
    finally:
        unsupervised.close()
    assert wedged, "supervision-off hang was NOT flagged as a wedge"
    h = client.health()
    assert h["in_flight"] >= 1
    time.sleep(1.1)                        # drain
    FI.reset()
    FI.arm("device.hang", "delay", arg=1.0, at=1)
    with pytest.raises(KernelDeadlineExceeded):   # supervision on: typed
        client.pagerank(graph_key="devres", deadline_s=0.2,
                        max_iterations=ITERS, tol=1e-12)
    time.sleep(1.1)


def test_supervisor_check_once_restarts_wedged(monkeypatch):
    sup = SupervisedKernelClient("/nonexistent.sock", spawn=False)
    restarts = []
    monkeypatch.setattr(sup, "restart_server",
                        lambda reason, pid=None: restarts.append(reason))
    monkeypatch.setattr(sup, "health", lambda timeout=5.0: None)
    assert sup.check_once() == "restarted"
    monkeypatch.setattr(sup, "health",
                        lambda timeout=5.0: {"wedged": True, "pid": 4242})
    assert sup.check_once() == "restarted"
    monkeypatch.setattr(sup, "health",
                        lambda timeout=5.0: {"wedged": False, "pid": 7})
    assert sup.check_once() == "ok"
    assert restarts == ["unreachable", "wedged"]
    sup.close()


def test_probe_op_typed_outcomes(server):
    _, client, _ = server
    assert client.probe()["outcome"] == "completed"
    FI.arm("device.oom", "raise", at=1)
    reply = client.probe()
    assert reply["ok"] is False and reply["outcome"] == "oom"
    FI.reset()
    assert client.probe()["outcome"] == "completed"


def test_health_reply_shape(server):
    _, client, _ = server
    h = client.health()
    for field in ("pid", "uptime_s", "in_flight", "wedged",
                  "graphs_cached", "hbm_budget_bytes", "counters",
                  "platform", "checkpoint_every"):
        assert field in h, field
    assert h["pid"] == os.getpid()           # in-thread daemon


# ==========================================================================
# 3. seeded device-nemesis schedules
# ==========================================================================


def test_device_schedule_byte_identical_per_seed():
    for seed in SWEEP_SEEDS:
        assert device_schedule_text(seed) == device_schedule_text(seed)
    assert device_schedule_text(1) != device_schedule_text(2)


def test_device_schedule_covers_full_matrix():
    """The default schedule enumerates every (op, context) pair — the
    dynamic half of the MG005 device-nemesis coverage contract."""
    for seed in SWEEP_SEEDS:
        pairs = {(op.kind, op.context) for op in device_schedule(seed)}
        want = {(op, ctx) for op in FI.DEVICE_NEMESIS_OPS
                for ctx in DEVICE_CONTEXTS}
        assert pairs == want


def test_device_op_point_mapping():
    for op in FI.DEVICE_NEMESIS_OPS:
        point = FI.device_point_for_op(op)
        assert point in FI.KNOWN_POINTS
    with pytest.raises(ValueError):
        FI.device_point_for_op("device_typo")
    with pytest.raises(ValueError):
        device_schedule(0, ops=("device_call", "typo"))


def test_classify_device_error_classes():
    assert classify_device_error(DeviceOomError("x")) == "oom"
    assert classify_device_error(DeviceLostError("x")) == "device_lost"
    assert classify_device_error(ValueError("x")) is None
    from memgraph_tpu.utils.devicefault import make_device_call_error
    assert classify_device_error(make_device_call_error("y")) \
        == "device_error"
    try:
        from jaxlib.xla_extension import XlaRuntimeError
    except ImportError:
        return
    assert classify_device_error(
        XlaRuntimeError("RESOURCE_EXHAUSTED: out of memory")) == "oom"
    assert classify_device_error(
        XlaRuntimeError("UNAVAILABLE: device lost")) == "device_lost"


def test_probe_device_fault_injectable():
    FI.arm("device.call", "raise", at=1)
    with pytest.raises(Exception) as ei:
        probe_device()
    assert classify_device_error(ei.value) == "device_error"
    FI.reset()
    checksum, platform = probe_device()
    assert checksum == 128.0 * 128 * 128 and platform == "cpu"


# ==========================================================================
# 4. RetryPolicy deadlines
# ==========================================================================


def test_retry_attempts_budget_and_deadline():
    p = RetryPolicy(base_delay=0.01, jitter=0.0, max_retries=3)
    assert list(p.attempts()) == [0, 1, 2, 3]
    p = RetryPolicy(base_delay=10.0, jitter=0.0, max_retries=5,
                    deadline=0.05)
    t0 = time.monotonic()
    assert list(p.attempts()) == [0]         # next backoff would cross
    assert time.monotonic() - t0 < 1.0


def test_retry_attempt_timeout_clips_to_deadline():
    p = RetryPolicy(attempt_timeout=5.0, deadline=1.0)
    t0 = time.monotonic()
    assert p.attempt_timeout_at(t0) <= 1.0
    p2 = RetryPolicy(attempt_timeout=5.0)
    assert p2.attempt_timeout_at(time.monotonic()) == 5.0
    p3 = RetryPolicy()
    assert p3.attempt_timeout_at(time.monotonic()) is None


def test_retry_call_honors_deadline():
    p = RetryPolicy(base_delay=10.0, jitter=0.0, max_retries=5,
                    deadline=0.05)
    calls = []

    def boom():
        calls.append(1)
        raise ConnectionError("nope")

    t0 = time.monotonic()
    with pytest.raises(ConnectionError):
        p.call(boom)
    assert len(calls) == 1                   # no 10s sleep happened
    assert time.monotonic() - t0 < 1.0


# ==========================================================================
# 5. the sweeps (device_chaos marked; run: pytest -m device_chaos)
# ==========================================================================


@pytest.mark.slow
@pytest.mark.device_chaos
@pytest.mark.parametrize("seed", list(SWEEP_SEEDS))
def test_device_nemesis_matrix_sweep(seed):
    """Acceptance: the full (fault x context) matrix per seed — correct
    (bit-exact) analytics results, zero wedged clients, resume ≤ k
    redone iterations, every typed outcome observed."""
    failures, observed = run_device_matrix(seed, echo=lambda *_: None)
    assert not failures, "\n".join(failures)
    for op in FI.DEVICE_NEMESIS_OPS:
        assert observed.get(op), f"{op} produced no observable outcome"


@pytest.mark.slow
@pytest.mark.device_chaos
def test_device_lost_process_kill_supervisor_respawns(tmp_path):
    """The REAL device.lost story: the daemon process dies (SIGKILL —
    what an armed kill action or a lost backend does to it); the
    supervisor detects the loss, respawns, and the retried idempotent
    request completes."""
    from memgraph_tpu.observability.metrics import global_metrics
    from memgraph_tpu.server.kernel_server import ensure_server
    import signal as _signal

    sock = str(tmp_path / "ks.sock")
    env_backup = os.environ.get("JAX_PLATFORMS")
    os.environ["JAX_PLATFORMS"] = "cpu"
    try:
        client = ensure_server(sock, spawn_timeout_s=240,
                               idle_timeout_s=120)
    finally:
        if env_backup is None:
            os.environ.pop("JAX_PLATFORMS", None)
        else:
            os.environ["JAX_PLATFORMS"] = env_backup
    if client is None:
        pytest.skip("kernel server daemon starved during spawn "
                    "(1-core host under full-suite load)")
    h, _ = client.call({"op": "ping"})
    daemon_pid = h["pid"]
    assert daemon_pid != os.getpid()
    client.close()

    sup = SupervisedKernelClient(
        sock, spawn=True, spawn_timeout_s=240, idle_timeout_s=120,
        retry=RetryPolicy(base_delay=0.2, max_retries=3,
                          attempt_timeout=240.0))
    rng = np.random.default_rng(2)
    n, e = 200, 1000
    src, dst = rng.integers(0, n, e), rng.integers(0, n, e)
    try:
        ref, _, _ = sup.pagerank(src=src, dst=dst, n_nodes=n,
                                 graph_key="kill-test")
        os.kill(daemon_pid, _signal.SIGKILL)     # the backend is LOST
        time.sleep(0.3)
        restarts0 = dict((nm, v) for nm, _k, v
                         in global_metrics.snapshot()).get(
            "kernel_server.client.retries_total", 0.0)
        # the graph cache died with the daemon: resend arrays
        ranks, _, _ = sup.pagerank(src=src, dst=dst, n_nodes=n,
                                   graph_key="kill-test")
        np.testing.assert_allclose(np.asarray(ranks), np.asarray(ref),
                                   rtol=1e-6)
        retries1 = dict((nm, v) for nm, _k, v
                        in global_metrics.snapshot()).get(
            "kernel_server.client.retries_total", 0.0)
        assert retries1 > restarts0              # the loss WAS retried
        h2 = sup.health()
        assert h2 is not None and h2["pid"] != daemon_pid
    finally:
        try:
            c = KernelClient(sock, timeout=10)
            c.shutdown()
            c.close()
        except OSError:
            pass
        sup.close()
