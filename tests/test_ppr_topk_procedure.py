"""``pagerank.personalized(source_nodes, max_iterations, damping_factor,
top_k)`` through the interpreter: with ``top_k`` the answer is the best
k rows of the call without it, best first, on both routes (the resident
kernel server's plane, which takes them on the device, and the
in-process fallback, which cuts its own ranks on the host); without it
every vertex is a row, as before.

Graphs are seeded, with dangling vertices (no out-edge) and a source
list of several vertices: the restart vector is uniform over the list
and dangling mass returns to it.
"""

import threading
import time

import numpy as np
import pytest

from memgraph_tpu.exceptions import QueryException
from memgraph_tpu.observability.metrics import global_metrics
from memgraph_tpu.query.interpreter import Interpreter, InterpreterContext
from memgraph_tpu.storage import InMemoryStorage

SOURCES = [3, 17, 40, 41]
ALL_ROWS = ("UNWIND $ids AS i MATCH (s:User {id: i}) "
            "WITH collect(s) AS sources "
            "CALL pagerank.personalized(sources) YIELD node, rank "
            "RETURN node.id AS id, rank")
TOP = ("UNWIND $ids AS i MATCH (s:User {id: i}) "
       "WITH collect(s) AS sources "
       "CALL pagerank.personalized(sources, 100, 0.85, $k) "
       "YIELD node, rank RETURN node.id AS id, rank")


def _counter(name):
    return dict((n, v) for n, _k, v in global_metrics.snapshot()).get(
        name, 0.0)


@pytest.fixture(scope="module")
def kernel_server(tmp_path_factory):
    """In-thread resident kernel server on a private socket."""
    from memgraph_tpu.server.kernel_server import (KernelClient,
                                                   KernelServer)
    sock = str(tmp_path_factory.mktemp("ks") / "ks.sock")
    server = KernelServer(sock, idle_timeout_s=0.0)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    deadline = time.monotonic() + 120
    while True:
        try:
            client = KernelClient(sock, timeout=60)
            assert client.ping()
            client.close()
            break
        except OSError:
            assert time.monotonic() < deadline, "kernel server never came up"
            time.sleep(0.1)
    yield sock
    server._shutdown.set()


def _loaded(config, seed, n=240, e=1500, dangling=30):
    """An interpreter over a seeded graph whose last `dangling` users
    have no out-edge."""
    interp = Interpreter(InterpreterContext(InMemoryStorage(), config))
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n - dangling, e)
    dst = rng.integers(0, n, e)
    interp.execute("CREATE INDEX ON :User(id)")
    interp.execute("UNWIND range(0, $n - 1) AS i CREATE (:User {id: i})",
                   {"n": n})
    interp.execute(
        "UNWIND $pairs AS p MATCH (a:User {id: p[0]}), (b:User {id: p[1]}) "
        "CREATE (a)-[:FRIEND]->(b)",
        {"pairs": np.stack([src, dst], axis=1).tolist()})
    return interp, n


def _best(rows, k):
    """The best k of a full answer: by rank, ties by the graph's order
    (the order the rows came in)."""
    order = sorted(range(len(rows)), key=lambda i: (-rows[i][1], i))
    return [rows[i] for i in order[:k]]


@pytest.mark.parametrize("route", ["daemon", "in_process"])
@pytest.mark.parametrize("seed", [11, 12])
def test_top_k_is_the_best_k_of_the_full_answer(route, seed, kernel_server):
    config = {"kernel_server_socket": kernel_server} \
        if route == "daemon" else {}
    interp, n = _loaded(config, seed)
    routed = _counter("analytics.kernel_routed_total")
    fell_back = _counter("analytics.kernel_route_fallback_total")
    _, full, _ = interp.execute(ALL_ROWS, {"ids": SOURCES})
    assert len(full) == n and len({r[0] for r in full}) == n
    assert sum(r[1] for r in full) == pytest.approx(1.0, abs=1e-4)
    for k in (1, 5, 20):
        _, top, _ = interp.execute(TOP, {"ids": SOURCES, "k": k})
        assert top == _best(full, k), (route, k)
    # more than there are: every vertex, best first
    _, top, _ = interp.execute(TOP, {"ids": SOURCES, "k": 10 * n})
    assert top == _best(full, n)
    # null is the call without the argument
    _, rows, _ = interp.execute(TOP, {"ids": SOURCES, "k": None})
    assert rows == full
    went = _counter("analytics.kernel_routed_total") - routed
    assert went == (6 if route == "daemon" else 0)
    assert _counter("analytics.kernel_route_fallback_total") == fell_back


def test_the_routes_agree_on_the_top(kernel_server):
    """The plane's device top-k and the host's cut name the same users
    with the same float32 ranks."""
    daemon, _ = _loaded({"kernel_server_socket": kernel_server}, 13)
    local, _ = _loaded({}, 13)
    _, a, _ = daemon.execute(TOP, {"ids": SOURCES, "k": 20})
    _, b, _ = local.execute(TOP, {"ids": SOURCES, "k": 20})
    assert [r[0] for r in a] == [r[0] for r in b]
    np.testing.assert_allclose([r[1] for r in a], [r[1] for r in b],
                               rtol=1e-5)


def test_a_top_k_below_one_is_refused():
    interp, _ = _loaded({}, 14, n=40, e=120, dangling=5)
    with pytest.raises(QueryException, match="top_k"):
        interp.execute(TOP, {"ids": [1, 2], "k": 0})
