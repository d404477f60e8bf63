"""The daemon layout through its normal path: clients speak Bolt to a
server held to the CPU backend, whose ``CALL pagerank.get()`` is routed
to a kernel-server daemon in another process
(``MEMGRAPH_TPU_ANALYTICS_KERNEL_SERVER``). Both processes are the
program's own entry points.

Read-your-write across the process boundary: after every committed
burst the routed CALL equals the in-process answer for the same graph
and a float64 power iteration, no CALL falls back, and the daemon
follows by change-log deltas. A burst that deletes edges warm-starts
like one that adds: PageRank is a contraction (``WARM_START_POLICY``
"always"); the loud cold start is for the monotone algorithms.
"""

import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.request

import numpy as np
import pytest

from memgraph_tpu.query.interpreter import Interpreter, InterpreterContext
from memgraph_tpu.storage import InMemoryStorage

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N, E = 300, 3000
STOP_EPSILON = 1e-5                 # pagerank.get()'s default
TOL = 10 * STOP_EPSILON             # as tests/test_delta.py holds a delta
SOCKET = "ks.sock"                  # relative: a unix path holds 107 bytes

NODES = "UNWIND range(0, $n - 1) AS i CREATE (:User {id: i})"
ADD = ("UNWIND $pairs AS p MATCH (a:User {id: p[0]}), (b:User {id: p[1]}) "
       "CREATE (a)-[:FRIEND]->(b)")
DELETE = ("UNWIND $pairs AS p MATCH (a:User {id: p[0]})-[r:FRIEND]->"
          "(b:User {id: p[1]}) DELETE r")
CALL = ("CALL pagerank.get() YIELD node, rank "
        "RETURN node.id AS id, rank ORDER BY id")
EDGES = "MATCH (a:User)-[:FRIEND]->(b:User) RETURN a.id, b.id"


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _spawn(args, env, cwd, log):
    with open(log, "ab") as f:
        return subprocess.Popen([sys.executable, "-m"] + args, cwd=cwd,
                                env=env, stdout=f, stderr=subprocess.STDOUT,
                                start_new_session=True)


def _stop(proc):
    if proc.poll() is None:
        proc.terminate()
        try:
            proc.wait(20)
        except subprocess.TimeoutExpired:
            pass
    try:
        os.killpg(proc.pid, signal.SIGKILL)
    except (ProcessLookupError, PermissionError):
        pass
    proc.wait(20)


@pytest.fixture(scope="module")
def deployment(tmp_path_factory):
    """(Bolt client, metrics port, daemon client) of a running pair."""
    from memgraph_tpu.server.client import BoltClient
    from memgraph_tpu.server.kernel_server import KernelClient
    work = str(tmp_path_factory.mktemp("daemon_layout"))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    env.pop("MEMGRAPH_TPU_ANALYTICS_KERNEL_SERVER", None)
    env.pop("XLA_FLAGS", None)          # one device each, as deployed
    procs = []
    try:
        daemon = _spawn(["memgraph_tpu.server.kernel_server", "--socket",
                         SOCKET], env, work,
                        os.path.join(work, "daemon.log"))
        procs.append(daemon)
        cwd = os.getcwd()
        deadline = time.monotonic() + 120
        kernel = None
        while kernel is None:
            assert daemon.poll() is None, "the daemon died at start"
            assert time.monotonic() < deadline, "the daemon never served"
            try:
                os.chdir(work)
                kernel = KernelClient(SOCKET, timeout=60.0)
            except OSError:
                time.sleep(0.1)
            finally:
                os.chdir(cwd)
        assert kernel.ping()
        bolt, metrics = _free_port(), _free_port()
        server = _spawn(
            ["memgraph_tpu.main", "--bolt-port", str(bolt),
             "--metrics-port", str(metrics), "--data-directory",
             os.path.join(work, "data"), "--storage-wal-enabled"],
            dict(env, MEMGRAPH_TPU_ANALYTICS_KERNEL_SERVER=SOCKET), work,
            os.path.join(work, "server.log"))
        procs.append(server)
        client = None
        while client is None:
            assert server.poll() is None, "the Bolt server died at start"
            assert time.monotonic() < deadline, "no Bolt server"
            try:
                client = BoltClient(port=bolt, timeout=300.0)
            except OSError:
                time.sleep(0.1)
        yield client, metrics, kernel
        client.close()
        kernel.close()
    finally:
        for proc in reversed(procs):
            _stop(proc)


def _metrics(port):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics?format=json", timeout=30) as r:
        return json.load(r)


def _float64_pagerank(src, dst, n, damping=0.85):
    deg = np.bincount(src, minlength=n).astype(np.float64)
    rank = np.full(n, 1.0 / n)
    for _ in range(1000):
        share = np.where(deg > 0, rank / np.maximum(deg, 1.0), 0.0)
        new = np.bincount(dst, weights=share[src], minlength=n)
        new = (1.0 - damping) / n + damping * (
            new + rank[deg == 0].sum() / n)
        if np.abs(new - rank).sum() < 1e-13:
            return new
        rank = new
    raise AssertionError("the reference did not converge")


def _ranks(rows):
    assert [r[0] for r in rows] == list(range(N))
    return np.asarray([r[1] for r in rows], dtype=np.float64)


def test_routed_call_sees_every_committed_burst(deployment, monkeypatch):
    client, metrics_port, kernel = deployment
    monkeypatch.delenv("MEMGRAPH_TPU_ANALYTICS_KERNEL_SERVER",
                       raising=False)
    local = Interpreter(InterpreterContext(InMemoryStorage()))

    def both(query, params=None):
        _, rows, _ = client.execute(query, params or {})
        _, local_rows, _ = local.execute(query, params or {})
        return rows, local_rows

    rng = np.random.default_rng(29)
    both("CREATE INDEX ON :User(id)")
    both(NODES, {"n": N})
    loaded = np.stack([rng.integers(0, N, E),
                       (rng.random(E) ** 2 * N).astype(np.int64)], axis=1)
    both(ADD, {"pairs": loaded.tolist()})

    def burst(count):
        return np.stack([rng.integers(0, N, count),
                         (rng.random(count) ** 2 * N).astype(np.int64)],
                        axis=1).tolist()

    bursts = [(ADD, burst(16)), (ADD, burst(16)),
              (DELETE, loaded[rng.choice(E, 8, replace=False)].tolist()),
              (ADD, burst(16)), (ADD, burst(1))]
    before = _metrics(metrics_port)
    health0 = kernel.health()["counters"]
    calls, previous = 0, None
    for query, pairs in [(None, None)] + bursts:
        if query is not None:
            both(query, {"pairs": pairs})
        routed, in_process = both(CALL)
        calls += 1
        edges, local_edges = both(EDGES)
        assert sorted(map(tuple, edges)) == sorted(map(tuple, local_edges))
        edges = np.asarray(edges, dtype=np.int64)
        want = _float64_pagerank(edges[:, 0], edges[:, 1], N)
        got = _ranks(routed)
        assert np.abs(got - _ranks(in_process)).max() < TOL, (query, calls)
        assert np.abs(got - want).max() < TOL, (query, calls)
        assert abs(got.sum() - 1.0) < 1e-4
        if previous is not None and len(pairs) >= 8:
            # the burst moved the answer by more than the tolerance: an
            # answer for the graph before it would have failed above
            assert np.abs(previous - want).max() > TOL
        previous = want
    after = _metrics(metrics_port)

    def moved(name):
        return after.get(name, 0.0) - before.get(name, 0.0)

    assert moved("analytics.kernel_routed_total") == calls
    assert moved("analytics.kernel_route_fallback_total") == 0
    assert moved("span.kernel.request.count") == calls
    assert moved("span.analytics.route_meta.count") == calls
    # the daemon followed by deltas: the first CALL shipped the graph,
    # every later one a change-log delta spliced into the one resident
    # generation, and every fixpoint after the first seeded from the
    # last solution, the one after the deleting burst too
    health = kernel.health()
    counters = health["counters"]

    def daemon_moved(name):
        return counters.get(name, 0.0) - health0.get(name, 0.0)

    assert daemon_moved("delta.applied_total") == len(bursts)
    assert daemon_moved("delta.cold_start_total") == 0
    assert daemon_moved("delta.warm_start_total") == len(bursts)
    assert daemon_moved("kernel_server.dispatch.completed_total") == calls
    assert health["graphs_cached"] == 1
    # what the benchmark's per-layer metrics read leaves the daemon
    assert daemon_moved("span.kernel.dispatch.count") == calls
    assert daemon_moved("span.kernel.generation.count") == calls
    assert daemon_moved("device.fixpoint_iterations_total") >= calls
    assert counters["span.kernel.generation.seconds_total"] <= \
        counters["span.kernel.dispatch.seconds_total"]
    assert "jit.compile_total" in counters


def test_the_cells_query_yields_only_its_bound(deployment):
    """The benchmark's query through Bolt and the route: the CALL yields
    the TopK's bound (``query.topk_pushdown_total`` once), its two row
    phases close once, and the 100 rows are the best of the full stream."""
    client, metrics_port, _kernel = deployment
    client.execute("UNWIND range(0, 149) AS i CREATE (:Pushed {id: -1 - i})")
    before = _metrics(metrics_port)
    _, rows, _ = client.execute(
        "CALL pagerank.get() YIELD node, rank "
        "RETURN node.id AS id, rank ORDER BY rank DESC LIMIT 100")
    after = _metrics(metrics_port)
    for name in ("query.topk_pushdown_total", "span.analytics.rows.count",
                 "span.analytics.consume.count",
                 "analytics.kernel_routed_total"):
        assert after.get(name, 0.0) - before.get(name, 0.0) == 1, name
    _, every, _ = client.execute(CALL)
    full = dict(map(tuple, every))
    ranks = [rank for _id, rank in rows]
    assert len(rows) == 100 and ranks == sorted(ranks, reverse=True)
    assert all(abs(full[i] - rank) < TOL for i, rank in rows)
    left_out = set(full) - {i for i, _rank in rows}
    assert max(full[i] for i in left_out) <= ranks[-1] + TOL
