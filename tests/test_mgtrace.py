"""mgtrace: span model, retention policy, cross-boundary propagation,
Chrome export, and the disarmed-overhead guard.

The propagation tests are the satellite contract: child spans recorded
on the far side of the kernel-server socket and the mp_executor fork
boundary must carry the parent's trace_id and ship home into ONE
connected trace.
"""

import json
import os
import threading
import time

import numpy as np
import pytest

from memgraph_tpu.observability import trace as T
from memgraph_tpu.query.interpreter import Interpreter, InterpreterContext
from memgraph_tpu.storage import InMemoryStorage


@pytest.fixture
def tracer():
    """Armed tracer with a clean buffer; disarmed + cleared afterwards."""
    T.TRACER.reset()
    T.enable(sample=1.0, slow_ms=250.0)
    yield T.TRACER
    T.disable()
    T.TRACER.reset()


@pytest.fixture
def interp():
    return Interpreter(InterpreterContext(InMemoryStorage()))


def _names(spans):
    return {s["name"] for s in spans}


def _one_connected(spans):
    """Single trace_id, every parent link resolves, exactly one root."""
    assert len({s["trace_id"] for s in spans}) == 1, spans
    ids = {s["span_id"] for s in spans}
    for s in spans:
        if s["parent_id"]:
            assert s["parent_id"] in ids, (s["name"], spans)
    roots = [s for s in spans if not s["parent_id"]]
    assert len(roots) == 1, [s["name"] for s in roots]
    return roots[0]


# --- span model -------------------------------------------------------------


def test_query_yields_one_connected_trace(tracer, interp):
    interp.execute("CREATE (:N {v: 1})")
    traces = T.traces_json()
    assert len(traces) == 1
    spans = traces[0]
    assert {"query", "query.parse", "query.plan", "query.execute",
            "query.commit", "mvcc.begin", "mvcc.commit"} <= _names(spans)
    root = _one_connected(spans)
    assert root["name"] == "query"
    # phase durations ride the root span for the slow-log linkage
    assert "parse_ms" in root["attrs"] and "plan_ms" in root["attrs"]
    # literals are redacted before a query text reaches a trace
    interp.execute("CREATE (:N {s: 'secret-literal'})")
    root2 = _one_connected(T.traces_json()[-1])
    assert "secret-literal" not in root2["attrs"]["query"]


def test_every_product_span_name_is_declared(tracer, interp):
    interp.execute("RETURN 1")
    for spans in T.traces_json():
        for s in spans:
            assert s["name"] in T.SPAN_NAMES, s["name"]


def test_head_sampling_drops_fast_ok_traces(tracer, interp):
    T.enable(sample=0.0)
    interp.execute("RETURN 1")
    assert T.traces_json() == []
    counts = T.TRACER.counts()
    assert counts["dropped"] >= 1 and counts["kept"] == 0


def test_errored_trace_always_kept(tracer, interp):
    T.enable(sample=0.0)
    with pytest.raises(Exception):
        interp.execute("MATCH (n) RETURN n.v + 'x' <<<")
    traces = T.traces_json()
    assert len(traces) == 1
    root = [s for s in traces[0] if s["name"] == "query"][0]
    assert root["status"] == "error"


def test_slow_trace_always_kept(tracer, interp):
    T.enable(sample=0.0, slow_ms=0.0)   # everything counts as slow
    interp.execute("RETURN 1")
    assert len(T.traces_json()) == 1


def test_sampling_decision_is_deterministic_per_trace_id():
    assert T._sample_decision("00000000" + "0" * 24, 0.5)
    assert not T._sample_decision("ffffffff" + "0" * 24, 0.5)
    for rate in (0.0, 0.25, 1.0):
        tid = "8a3b0c1d" + "0" * 24
        assert T._sample_decision(tid, rate) == \
            T._sample_decision(tid, rate)


def _span_counters(name):
    from memgraph_tpu.observability.metrics import global_metrics
    snap = {n: v for n, _k, v in global_metrics.snapshot()}
    return (snap.get(f"span.{name}.seconds_total", 0.0),
            snap.get(f"span.{name}.count", 0.0))


def test_disarmed_api_is_inert():
    """Disarmed, nothing is recorded anywhere a trace could be read: a
    non-phase name is the no-op, a phase name only accounts."""
    T.disable()
    T.TRACER.reset()
    assert T.begin_trace("query") is None
    assert T.inject() is None
    with T.span("query.parse") as sp:
        assert not sp
        sp.set(anything=1)
    with T.span("lane.query") as sp:       # a phase: falsy, set() inert
        assert not sp
        sp.set(refused="small_input")
    _, closes = _span_counters("bolt.run")
    root = T.begin_trace("bolt.run", {"trace_id": "x" * 32})
    assert root is not None and root.ctx is None and root.trace_id is None
    with T.activate(root.ctx):
        T.record_span("bolt.wait", time.time(), 0.001)
    root.finish(status="ok")
    root.finish(status="ok")                # exactly once
    assert _span_counters("bolt.run")[1] == closes + 1
    with T.activate(None):
        pass
    with T.adopt({"trace_id": "x"}):
        pass
    assert T.traces_json() == []
    assert T.TRACER.counts() == {"started": 0, "kept": 0, "dropped": 0}
    assert T.TRACER._active == {}


def test_chrome_export_is_valid(tracer, interp):
    interp.execute("CREATE (:C)")
    doc = json.loads(json.dumps(T.chrome_trace()))
    events = doc["traceEvents"]
    assert events
    for ev in events:
        assert ev["ph"] == "X"
        assert ev["ts"] > 0 and ev["dur"] > 0
        assert ev["cat"] == "mgtrace"
        assert "trace_id" in ev["args"]


def test_slow_query_log_links_trace(tracer, caplog):
    import logging
    ctx = InterpreterContext(InMemoryStorage(),
                             {"log_min_duration_ms": 0.0001})
    interp = Interpreter(ctx)
    with caplog.at_level(logging.INFO,
                         logger="memgraph_tpu.query.interpreter"):
        interp.execute("CREATE (:S {v: 'sekrit'})")
    slow = [r.message for r in caplog.records
            if "slow query" in r.message]
    assert slow, caplog.records
    msg = slow[0]
    assert "trace_id=" in msg
    trace_id = msg.split("trace_id=")[1].split(",")[0]
    assert trace_id != "-"
    # every phase named, literals redacted
    for phase in ("parse=", "plan=", "execute=", "commit="):
        assert phase in msg, msg
    assert "sekrit" not in msg
    # the named trace is retained and retrievable by id
    kept = T.traces_json(trace_id)
    assert kept and kept[0][0]["trace_id"] == trace_id


def test_active_buffer_bounded(tracer):
    for i in range(T.TRACER.MAX_ACTIVE + 50):
        with T.adopt({"trace_id": f"{i:032x}", "span_id": "00",
                      "sampled": True}):
            with T.span("query.parse"):
                pass
    assert len(T.TRACER._active) <= T.TRACER.MAX_ACTIVE


# --- cross-boundary propagation --------------------------------------------


def test_kernel_server_socket_propagation(tracer, tmp_path):
    """Spans recorded on the far side of the kernel-server request
    protocol carry the parent trace_id and ship home on the reply."""
    from memgraph_tpu.server.kernel_server import (KernelClient,
                                                   KernelServer)
    sock = str(tmp_path / "ks.sock")
    server = KernelServer(sock, idle_timeout_s=0.0)
    t = threading.Thread(target=server.serve_forever, daemon=True)
    t.start()
    deadline = time.monotonic() + 120
    client = None
    while time.monotonic() < deadline:
        try:
            client = KernelClient(sock, timeout=60)
            if client.ping():
                break
            client.close()
        except OSError:
            time.sleep(0.05)
    assert client is not None and client.ping()
    try:
        rng = np.random.default_rng(3)
        src = rng.integers(0, 50, 300)
        dst = rng.integers(0, 50, 300)
        handle = T.begin_trace("query")
        with T.activate(handle.ctx):
            ranks, err, iters = client.pagerank(
                src=src, dst=dst, n_nodes=50, max_iterations=5)
        handle.finish()
        assert len(ranks) == 50
        traces = T.traces_json(handle.trace_id)
        assert traces, "traced kernel request was not retained"
        spans = traces[0]
        got = _names(spans)
        assert {"query", "kernel.dispatch", "device.transfer",
                "device.chunk"} <= got, got
        _one_connected(spans)
        dispatch = [s for s in spans if s["name"] == "kernel.dispatch"][0]
        assert dispatch["trace_id"] == handle.trace_id
        # parent chain: kernel.dispatch hangs off the carrier span
        assert dispatch["parent_id"]
    finally:
        client.shutdown()
        client.close()
        t.join(timeout=10)


def test_mp_executor_fork_propagation(tracer, interp):
    """The mp_executor job envelope carries the trace across the fork;
    the worker's spans (its own query trace included) come home in the
    response and join the parent's retained trace."""
    from memgraph_tpu.server.mp_executor import MPReadExecutor
    interp.execute("UNWIND range(1, 5) AS i CREATE (:M {v: i})")
    T.TRACER.reset()   # drop the setup queries' traces
    pool = MPReadExecutor(interp.ctx, n_workers=1)
    try:
        handle = T.begin_trace("query")
        with T.activate(handle.ctx):
            cols, rows = pool.execute("MATCH (m:M) RETURN count(m)")
        handle.finish()
        assert rows == [[5]]
        traces = T.traces_json(handle.trace_id)
        assert traces, "traced mp query was not retained"
        spans = traces[0]
        got = _names(spans)
        assert {"query", "mp.execute", "mp.worker",
                "query.parse"} <= got, got
        _one_connected(spans)
        worker = [s for s in spans if s["name"] == "mp.worker"][0]
        assert worker["trace_id"] == handle.trace_id
        assert worker["pid"] != os.getpid()   # recorded across the fork
    finally:
        pool.close()


def test_replication_system_txn_carries_trace(tracer):
    """The replication wire (JSON system txns) propagates the context;
    the replica-side apply span joins the originating trace."""
    from memgraph_tpu.replication.replica import ReplicaServer
    storage = InMemoryStorage()
    replica = ReplicaServer(storage, port=0)
    replica.start()
    try:
        from memgraph_tpu.replication.main_role import (ReplicaClient,
                                                        ReplicationMode)
        client = ReplicaClient(
            "r1", f"127.0.0.1:{replica.port}", ReplicationMode.SYNC,
            InMemoryStorage(), epoch_fn=lambda: 0)
        client.connect_and_catch_up()
        handle = T.begin_trace("query")
        with T.activate(handle.ctx):
            ok = client.send_system(
                {"seq": 1, "kind": "auth", "data": {}})
        handle.finish()
        assert ok
        # the replica finalized its half locally (retain=True): an
        # adopted repl.apply span under the same trace id
        applied = [spans for spans in T.traces_json()
                   if any(s["name"] == "repl.apply" for s in spans)]
        assert applied, T.traces_json()
        apply_span = [s for s in applied[0]
                      if s["name"] == "repl.apply"][0]
        assert apply_span["trace_id"] == handle.trace_id
        client.close()
    finally:
        replica.stop()


# --- overhead guard ---------------------------------------------------------


def test_disarmed_overhead_under_two_percent(interp):
    """Disarmed tracing must add ≤2% to a tier-1 micro-benchmark.

    Deterministic form of the bound, over both kinds of site:
    (no-op cost x no-op sites per query) + (always-on cost x always-on
    sites per query) must stay under 2% of the measured per-query time.
    The no-op budget (40) is ~4x the real per-query count; the
    always-on budget (4) is one more than the three phases that can lie
    on a point read's or write's path (bolt.wait, bolt.run, and
    mvcc.commit on a write).
    """
    assert not T.armed()
    # a representative OLTP micro-benchmark: a 200-row indexed-label
    # scan with a filter + aggregate (the disarmed overhead is a FIXED
    # number of API calls per query, so the bound is against a real
    # query, not the cheapest statement imaginable)
    interp.execute("UNWIND range(1, 200) AS i CREATE (:B {v: i})")

    # per-call cost of each disarmed path (min over batches)
    def span_batch(name):
        t0 = time.perf_counter()
        for _ in range(2000):
            with T.span(name):
                pass
        return (time.perf_counter() - t0) / 2000

    per_noop = min(span_batch("query.parse") for _ in range(5))
    per_phase = min(span_batch("mvcc.commit") for _ in range(5))
    assert T.span("query.parse") is T._NOOP
    assert "mvcc.commit" in T.PHASES

    # per-query cost of the micro-benchmark (min over runs, against
    # scheduler noise)
    query = "MATCH (b:B) WHERE b.v > 100 RETURN count(b)"
    interp.execute(query)                   # warm plan cache

    def query_batch():
        t0 = time.perf_counter()
        for _ in range(20):
            interp.execute(query)
        return (time.perf_counter() - t0) / 20

    per_query = min(query_batch() for _ in range(3))

    noop_sites, phase_sites = 40, 4
    overhead = per_noop * noop_sites + per_phase * phase_sites
    assert overhead <= 0.02 * per_query, (
        f"disarmed tracing overhead {overhead * 1e6:.2f}µs "
        f"({noop_sites} no-op sites x {per_noop * 1e9:.0f}ns + "
        f"{phase_sites} always-on sites x {per_phase * 1e9:.0f}ns) "
        f"exceeds 2% of the {per_query * 1e6:.1f}µs micro-benchmark "
        f"query")


def test_disarmed_span_is_allocation_free_singleton():
    """Every non-phase name shares the one no-op; a phase allocates its
    own small span (it has a clock to keep), which is still falsy."""
    T.disable()
    for name in T.SPAN_NAMES:
        sp = T.span(name, anything=1)
        if name in T.PHASES:
            assert sp is not T._NOOP and not sp, name
        else:
            assert sp is T._NOOP, name


# --- phases: always accounted ----------------------------------------------


def test_every_phase_is_a_declared_span():
    assert set(T.PHASES) <= set(T.SPAN_NAMES)
    from memgraph_tpu.observability.metrics import STAT_NAMES
    assert "span.*" in STAT_NAMES


def test_phase_span_is_accounted_while_disarmed_and_opens_no_trace():
    T.disable()
    T.TRACER.reset()
    seconds, closes = _span_counters("analytics.export")
    with T.span("analytics.export") as sp:
        time.sleep(0.01)
    assert not sp and sp.seconds >= 0.01
    after_s, after_n = _span_counters("analytics.export")
    assert after_n == closes + 1
    assert after_s - seconds == pytest.approx(sp.seconds)
    # an after-the-fact record is accounted the same way
    T.record_span("analytics.rows", time.time(), 0.25)
    s0, n0 = _span_counters("analytics.rows")
    T.record_span("analytics.rows", time.time(), 0.5)
    s1, n1 = _span_counters("analytics.rows")
    assert n1 == n0 + 1 and s1 - s0 == pytest.approx(0.5)
    # a non-phase name is accounted nowhere
    with T.span("query.parse"):
        pass
    assert _span_counters("query.parse") == (0.0, 0.0)
    assert T.traces_json() == [] and T.TRACER._active == {}
    # GET /metrics carries the family for an operator's scraper
    from memgraph_tpu.observability.metrics import global_metrics
    text = global_metrics.prometheus_text()
    assert "span_analytics_export_seconds_total" in text


def test_armed_phase_span_is_accounted_and_recorded(tracer):
    _, closes = _span_counters("lane.snapshot")
    with T.adopt({"trace_id": "a" * 32, "span_id": "b" * 16,
                  "sampled": True}):
        with T.span("lane.snapshot") as sp:
            assert sp
    assert _span_counters("lane.snapshot")[1] == closes + 1
    assert [s["name"] for s in T.take_trace("a" * 32)] == ["lane.snapshot"]


@pytest.mark.parametrize("armed", [False, True])
def test_phase_span_feeds_the_stage_accumulator_exactly_once(armed):
    from memgraph_tpu.observability import stats as mgstats
    T.TRACER.reset()
    (T.enable if armed else T.disable)()
    try:
        acc = mgstats.StageAccumulator()
        with mgstats.collecting_stages(acc):
            with T.span("device.chunk", backend="segment") as chunk:
                pass
            with T.span("device.chunk"):        # states no backend
                pass
            with T.span("device.route", backend="mesh") as route:
                pass
            with T.span("lane.iterate") as lane:
                pass
            with T.span("analytics.launch", backend="mxu") as launch:
                pass
            with T.span("analytics.device_wait", backend="mxu") as wait:
                pass
            with T.span("analytics.export"):    # maps to no stage
                pass
        snap = acc.snapshot()
    finally:
        T.disable()
        T.TRACER.reset()
    assert set(snap) == {"device_iterate", "semiring_segment",
                         "semiring_mesh", "lane_iterate", "semiring_mxu"}
    assert snap["semiring_segment"] == {
        "seconds": pytest.approx(chunk.seconds), "count": 1}
    assert snap["semiring_mesh"] == {
        "seconds": pytest.approx(route.seconds), "count": 1}
    assert snap["lane_iterate"] == {
        "seconds": pytest.approx(lane.seconds), "count": 1}
    # launch + wait are ONE device_iterate that ends in the wait's block
    assert snap["semiring_mxu"] == {
        "seconds": pytest.approx(launch.seconds + wait.seconds),
        "count": 1}
    assert snap["device_iterate"] == {
        "seconds": pytest.approx(chunk.seconds + launch.seconds
                                 + wait.seconds), "count": 2}
    # outside an accumulator nothing is fed (and nothing raises)
    with T.span("device.chunk", backend="segment"):
        pass


def test_phase_counters_lose_no_close_under_threads():
    """The two counters are one read-modify-write under the registry's
    lock: more threads than cores, a short switch interval, exact count."""
    import sys
    T.disable()
    threads, closes = 8, 1500
    _, before = _span_counters("lane.dispatch")

    def work():
        for _ in range(closes):
            with T.span("lane.dispatch"):
                pass

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        pool = [threading.Thread(target=work) for _ in range(threads)]
        for t in pool:
            t.start()
        for t in pool:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in pool)
    assert _span_counters("lane.dispatch")[1] == before + threads * closes


def test_pagerank_stages_are_fed_once_and_end_in_the_block():
    """No double count with the deleted perf_counter pairs: one CALL is
    one device_iterate + one semiring_<backend>, and the stage now
    holds the device wait (launch alone returns at enqueue)."""
    from memgraph_tpu.observability import stats as mgstats
    from memgraph_tpu.ops import csr, pagerank as pr
    rng = np.random.default_rng(3)
    graph = csr.from_coo(rng.integers(0, 200, 3000),
                         rng.integers(0, 200, 3000), n_nodes=200)
    pr.pagerank(graph)                      # compile outside the reading
    for route, stage in ((lambda: pr.pagerank(graph), "semiring_segment"),
                         (lambda: pr._pagerank_via_mxu(
                             graph, 0.85, 100, 1e-6), "semiring_mxu")):
        route()
        acc = mgstats.StageAccumulator()
        wait0 = _span_counters("analytics.device_wait")[0]
        with mgstats.collecting_stages(acc):
            route()
        waited = _span_counters("analytics.device_wait")[0] - wait0
        snap = acc.snapshot()
        assert set(snap) == {"device_iterate", stage}, snap
        assert snap["device_iterate"]["count"] == 1
        assert snap[stage]["count"] == 1
        assert snap["device_iterate"]["seconds"] == \
            pytest.approx(snap[stage]["seconds"])
        assert snap["device_iterate"]["seconds"] >= waited > 0


def test_phase_span_sits_in_a_live_profiler_session(tmp_path):
    """While a jax.profiler session is live in this process a phase
    span is in the xplane's host plane as mgtrace:<name>, disarmed too;
    with no session there is nothing to enter."""
    import jax
    from jax.profiler import ProfileData
    import glob
    T.disable()
    assert not T._profiler_live()
    with T.span("analytics.export") as sp:
        assert sp._ann is None
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert T._profiler_live()
        with T.span("analytics.export"):
            jax.numpy.ones(8).block_until_ready()
        with T.span("query.parse"):         # not a phase: not bridged
            pass
    finally:
        jax.profiler.stop_trace()
    assert not T._profiler_live()
    found = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                   "*", "*.xplane.pb"))
    assert found
    names = set()
    for plane in ProfileData.from_file(found[-1]).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            names.update(e.name for e in line.events)
    assert "mgtrace:analytics.export" in names
    assert "mgtrace:query.parse" not in names
    assert not hasattr(T.TRACER, "xla_bridge")     # no knob: the session decides


def test_bolt_session_trace_end_to_end(tracer):
    """A Bolt RUN..PULL against a live server yields one connected
    retained trace (session -> interpreter -> storage txn), the client
    carrier in the `extra` metadata field parents the whole thing, and
    the SUCCESS metadata names the trace_id."""
    import socket

    from memgraph_tpu.server.bolt import BoltServer
    from memgraph_tpu.server.client import BoltClient

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    ictx = InterpreterContext(InMemoryStorage())
    srv = BoltServer(ictx, "127.0.0.1", port)
    thread, loop = srv.run_in_thread()
    try:
        client = BoltClient(port=port)
        # drive RUN with a client-side carrier in the extra field
        client_carrier = {"trace_id": "c" * 32, "span_id": "d" * 16,
                          "sampled": True}
        from memgraph_tpu.server.client import (M_PULL, M_RECORD,
                                                M_RUN)
        client._send_message(M_RUN, "CREATE (:T {v: 1}) RETURN 1", {},
                             {"trace": client_carrier})
        run_meta = client._expect_success()
        assert run_meta.get("trace_id") == "c" * 32
        client._send_message(M_PULL, {"n": -1})
        pull_meta = None
        while True:
            msg = client._read_message()
            if msg.tag == M_RECORD:
                continue
            pull_meta = msg.fields[0] if msg.fields else {}
            break
        assert pull_meta.get("trace_id") == "c" * 32
        client.close()
        # the server answers the last PULL and only then closes the
        # bolt.run root (the phase ends with the answer sent), so the
        # client can be here first
        deadline = time.monotonic() + 10.0
        traces = T.traces_json("c" * 32)
        while not traces and time.monotonic() < deadline:
            time.sleep(0.01)
            traces = T.traces_json("c" * 32)
        assert traces, "bolt session trace was not retained"
        spans = traces[0]
        got = _names(spans)
        assert {"bolt.run", "query", "query.parse", "query.execute",
                "query.commit", "mvcc.commit"} <= got, got
        # bolt.run is the local root, parented on the CLIENT's span
        bolt_root = [s_ for s_ in spans if s_["name"] == "bolt.run"][0]
        assert bolt_root["parent_id"] == "d" * 16
        q = [s_ for s_ in spans if s_["name"] == "query"][0]
        assert q["parent_id"] == bolt_root["span_id"]
        # chrome export of exactly this trace parses
        doc = json.loads(json.dumps(T.chrome_trace(traces)))
        assert all(ev["args"]["trace_id"] == "c" * 32
                   for ev in doc["traceEvents"])
    finally:
        srv.stop()
        loop.call_soon_threadsafe(loop.stop)
