"""Multiprocess read executor (server/mp_executor.py): snapshot
semantics, parallel dispatch, error transport, refresh."""

import threading

import pytest

from memgraph_tpu.query import Interpreter
from memgraph_tpu.query.interpreter import InterpreterContext
from memgraph_tpu.server.mp_executor import MPReadExecutor
from memgraph_tpu.storage import InMemoryStorage


@pytest.fixture
def ictx():
    ictx = InterpreterContext(InMemoryStorage())
    Interpreter(ictx).execute(
        "UNWIND range(0, 99) AS i CREATE (:User {id: i, age: i % 50})")
    return ictx


def test_reads_match_in_process(ictx):
    ex = MPReadExecutor(ictx, n_workers=2)
    try:
        cols, rows = ex.execute(
            "MATCH (n:User {id: 7}) RETURN n.age")
        assert rows == [[7]]
        cols, rows = ex.execute("MATCH (n:User) RETURN count(n)")
        assert rows == [[100]]
    finally:
        ex.close()


def test_snapshot_staleness_and_refresh(ictx):
    ex = MPReadExecutor(ictx, n_workers=2)
    try:
        Interpreter(ictx).execute("CREATE (:User {id: 1000, age: 1})")
        # workers still see the fork-time snapshot
        _, rows = ex.execute("MATCH (n:User) RETURN count(n)")
        assert rows == [[100]]
        ex.refresh()
        _, rows = ex.execute("MATCH (n:User) RETURN count(n)")
        assert rows == [[101]]
    finally:
        ex.close()


def test_concurrent_dispatch(ictx):
    ex = MPReadExecutor(ictx, n_workers=4)
    results = []
    errors = []

    def worker():
        try:
            for _ in range(25):
                _, rows = ex.execute(
                    "MATCH (n:User) WHERE n.age > 10 RETURN count(n)")
                results.append(rows[0][0])
        except Exception as e:  # noqa: BLE001
            errors.append(e)
    try:
        threads = [threading.Thread(target=worker) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors
        assert len(results) == 100 and len(set(results)) == 1
    finally:
        ex.close()


def test_worker_error_transport(ictx):
    """Worker-side errors cross the fork boundary TYPED: the parent
    re-raises the typed class the worker named, not a stringly
    RuntimeError."""
    from memgraph_tpu.exceptions import SyntaxException
    ex = MPReadExecutor(ictx, n_workers=1)
    try:
        with pytest.raises(SyntaxException):
            ex.execute("MATCH (n RETURN n")
        # the worker survives the error
        _, rows = ex.execute("RETURN 1")
        assert rows == [[1]]
    finally:
        ex.close()


def test_write_queries_rejected_loudly(ictx):
    """Misrouted writes must fail, not vanish into the forked snapshot."""
    from memgraph_tpu.exceptions import QueryException
    ex = MPReadExecutor(ictx, n_workers=1)
    try:
        with pytest.raises(QueryException, match="read-only"):
            ex.execute("CREATE (:Ghost {id: 1})")
        with pytest.raises(QueryException, match="read-only"):
            ex.execute("MATCH (n:User {id: 1}) SET n.age = 99")
        # non-Cypher statements (auth/DDL) are refused before prepare
        with pytest.raises(QueryException, match="read-only"):
            ex.execute("CREATE INDEX ON :User(id)")
        with pytest.raises(QueryException, match="read-only"):
            ex.execute("CREATE USER ghost IDENTIFIED BY 'pw'")
        # worker still serves reads afterwards
        _, rows = ex.execute("MATCH (n:User) RETURN count(n)")
        assert rows == [[100]]
    finally:
        ex.close()
    # nothing leaked into the parent either
    _, rows, _ = Interpreter(ictx).execute(
        "MATCH (n:Ghost) RETURN count(n)")
    assert rows == [[0]]


def test_worker_crash_respawns_with_typed_retryable_error(ictx):
    """A SIGKILLed worker must not wedge its queue: the in-flight job
    fails with the typed retryable WorkerCrashedError, the worker is
    respawned in place, and the respawn counter moves."""
    import os
    import signal

    from memgraph_tpu.exceptions import WorkerCrashedError
    from memgraph_tpu.observability.metrics import global_metrics

    def metric(name):
        return {n: v for n, _k, v
                in global_metrics.snapshot()}.get(name, 0.0)

    ex = MPReadExecutor(ictx, n_workers=2)
    try:
        assert ex.execute("MATCH (n:User) RETURN count(n)")[1] == [[100]]
        respawns0 = metric("mp_executor.worker_respawn_total")
        for pid, _rq, _rs in list(ex._workers):
            os.kill(pid, signal.SIGKILL)
            os.waitpid(pid, 0)
        crashes = 0
        for _ in range(2):
            try:
                ex.execute("MATCH (n:User) RETURN count(n)")
            except WorkerCrashedError as e:
                # RetryPolicy-compatible: ConnectionError is in the MRO
                assert isinstance(e, ConnectionError)
                crashes += 1
        assert crashes == 2
        assert metric("mp_executor.worker_respawn_total") == \
            respawns0 + 2
        # both workers are fresh and serving again
        for _ in range(4):
            assert ex.execute(
                "MATCH (n:User) RETURN count(n)")[1] == [[100]]
    finally:
        ex.close()


def test_worker_crash_is_retry_policy_compatible(ictx):
    """RetryPolicy.call's default retry_on catches the crash error —
    the dispatch loop heals without special-casing."""
    import os
    import signal

    from memgraph_tpu.utils.retry import RetryPolicy

    ex = MPReadExecutor(ictx, n_workers=1)
    try:
        pid = ex._workers[0][0]
        os.kill(pid, signal.SIGKILL)
        os.waitpid(pid, 0)
        policy = RetryPolicy(base_delay=0.01, max_retries=3)
        _cols, rows = policy.call(
            lambda: ex.execute("MATCH (n:User) RETURN count(n)"))
        assert rows == [[100]]
    finally:
        ex.close()


def test_close_idempotent(ictx):
    ex = MPReadExecutor(ictx, n_workers=1)
    ex.close()
    ex.close()
    with pytest.raises(RuntimeError):
        ex.execute("RETURN 1")
