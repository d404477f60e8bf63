"""Resident kernel server (server/kernel_server.py): spawn, ping,
remote pagerank vs scipy, server-side graph caching, shutdown."""

import os
import sys

import numpy as np
import pytest

from memgraph_tpu.server.kernel_server import (KernelClient, ensure_server)


@pytest.fixture(scope="module")
def server(tmp_path_factory):
    sock = str(tmp_path_factory.mktemp("ks") / "ks.sock")
    env_backup = os.environ.get("JAX_PLATFORMS")
    os.environ["JAX_PLATFORMS"] = "cpu"   # the daemon inherits this
    # generous spawn budget: under a full-suite run this 1-core host
    # makes the daemon's jax import take minutes
    client = ensure_server(sock, spawn_timeout_s=240, idle_timeout_s=300)
    if env_backup is None:
        os.environ.pop("JAX_PLATFORMS", None)
    else:
        os.environ["JAX_PLATFORMS"] = env_backup
    if client is None:
        # 1-core CI contention can starve the daemon's jax import past
        # any reasonable budget; the server itself is covered whenever
        # this file runs standalone (5 passed in ~9s on an idle host)
        pytest.skip("kernel server daemon starved during spawn "
                    "(1-core host under full-suite load)")
    yield client, sock
    client.shutdown()
    client.close()


def _scipy_pagerank(src, dst, n, iters=100, damping=0.85, tol=1e-6):
    import scipy.sparse as sp
    w = np.ones(len(src))
    wsum = np.bincount(src, weights=w, minlength=n)
    inv = np.where(wsum > 0, 1.0 / np.maximum(wsum, 1e-300), 0.0)
    m = sp.csr_matrix((w * inv[src], (dst, src)), shape=(n, n))
    dang = wsum <= 0
    rank = np.full(n, 1.0 / n)
    for _ in range(iters):
        dm = rank[dang].sum()
        new = (1 - damping) / n + damping * (m @ rank + dm / n)
        if np.abs(new - rank).sum() <= tol:
            return new
        rank = new
    return rank


def test_ping(server):
    client, _ = server
    assert client.ping()
    # the daemon is a different process
    h, _ = client.call({"op": "ping"})
    assert h["pid"] != os.getpid()


def test_remote_pagerank_matches_scipy(server):
    client, _ = server
    rng = np.random.default_rng(0)
    n, e = 2000, 12000
    src = rng.integers(0, n, e)
    dst = rng.integers(0, n, e)
    ranks, err, iters = client.pagerank(src=src, dst=dst, n_nodes=n)
    want = _scipy_pagerank(src, dst, n)
    np.testing.assert_allclose(ranks, want, rtol=3e-4, atol=1e-8)


def test_graph_key_caching(server):
    """Second call by key only (no arrays) computes on the cached graph;
    a fresh client sharing the socket sees the same cache."""
    client, sock = server
    rng = np.random.default_rng(1)
    n, e = 1000, 6000
    src = rng.integers(0, n, e)
    dst = rng.integers(0, n, e)
    r1, _, _ = client.pagerank(src=src, dst=dst, n_nodes=n, graph_key="g1")
    r2, _, _ = client.pagerank(graph_key="g1")
    np.testing.assert_allclose(r1, r2, rtol=1e-6)
    c2 = KernelClient(sock)
    r3, _, _ = c2.pagerank(graph_key="g1")
    c2.close()
    np.testing.assert_allclose(r1, r3, rtol=1e-6)


def test_unknown_key_without_arrays_errors(server):
    client, _ = server
    with pytest.raises(RuntimeError):
        client.pagerank(graph_key="never-seen")


def test_error_does_not_kill_server(server):
    client, _ = server
    with pytest.raises(RuntimeError):
        client.pagerank(graph_key="nope")
    assert client.ping()


# --- in-process wire tests (no daemon spawn) --------------------------------


def _in_process_conn(tmp_path):
    """A KernelServer serving ONE socketpair end on a thread — the
    typed-outcome wire is testable without paying the daemon spawn."""
    import socket
    import threading

    from memgraph_tpu.server.kernel_server import KernelServer
    srv = KernelServer(socket_path=str(tmp_path / "ks.sock"))
    ours, theirs = socket.socketpair()
    t = threading.Thread(target=srv._serve_conn, args=(theirs,),
                         daemon=True)
    t.start()
    return srv, ours, t


def test_garbage_header_drops_connection_not_thread(tmp_path):
    """A well-framed envelope whose header is not JSON must sever the
    connection cleanly (no traceback reply, no wedged thread)."""
    import struct

    _srv, conn, t = _in_process_conn(tmp_path)
    try:
        conn.sendall(struct.pack("<I", 8) + b"\xff" * 8)
        conn.settimeout(5)
        assert conn.recv(4096) == b""      # dropped, nothing shipped
        t.join(timeout=5)
        assert not t.is_alive()
    finally:
        conn.close()


def test_typed_outcome_crosses_the_wire(tmp_path):
    """A KernelServerError raised inside dispatch ships its outcome +
    retryable flag, and the client rehydrates the typed class."""
    from memgraph_tpu.server.kernel_server import (AdmissionRejected,
                                                   _raise_for_reply,
                                                   _recv_msg, _send_msg)

    srv, conn, _t = _in_process_conn(tmp_path)

    def shed(header, arrays):
        raise AdmissionRejected("admission budget exhausted")

    srv._ppr.submit = shed
    try:
        conn.settimeout(10)
        _send_msg(conn, {"op": "ppr", "sources": [0]})
        reply, _ = _recv_msg(conn)
        assert reply["ok"] is False
        assert reply["outcome"] == "shed"
        assert reply["retryable"] is False   # shed is not retryable
        with pytest.raises(AdmissionRejected):
            _raise_for_reply(reply)
        # the connection survived the typed failure
        _send_msg(conn, {"op": "ping"})
        reply, _ = _recv_msg(conn)
        assert reply["ok"] is True
    finally:
        conn.close()


# --------------------------------------------------------------------------
# one owner per chip (PR 22)
# --------------------------------------------------------------------------

def test_spawned_daemon_logs_stderr_next_to_its_socket(server):
    _, sock = server
    assert os.path.exists(sock + ".log")


def test_cpu_process_does_not_hold_the_chip():
    import jax
    from memgraph_tpu.utils.devicefault import (process_holds_tpu,
                                                refuse_chip_child)
    jax.devices()                       # backend initialised: the CPU's
    assert process_holds_tpu() is False
    refuse_chip_child("do anything")    # no refusal


@pytest.mark.parametrize("starter", ["ensure_server", "supervised_client",
                                     "mp_executor"])
def test_chip_owner_refuses_a_chip_owning_child(tmp_path, monkeypatch,
                                                starter):
    """A process that has initialised the TPU backend gets a typed
    refusal — not a daemon that dies (or hangs) on the chip's lock."""
    from memgraph_tpu.server import kernel_server as ks
    from memgraph_tpu.utils import devicefault
    monkeypatch.setattr(devicefault, "process_holds_tpu", lambda: True)
    started = []
    monkeypatch.setattr(ks.subprocess, "Popen",
                        lambda *a, **k: started.append(a))
    monkeypatch.setattr(os, "fork", lambda: started.append("fork"))
    sock = str(tmp_path / "none.sock")
    with pytest.raises(devicefault.ChipOwnedError, match="owns the chip"):
        if starter == "ensure_server":
            ensure_server(sock, spawn_timeout_s=5)
        elif starter == "supervised_client":
            # not a connection error: no supervised retry swallows it
            ks.SupervisedKernelClient(
                sock, spawn=True, spawn_timeout_s=5).pagerank(
                src=[0], dst=[0], n_nodes=1)
        else:
            from memgraph_tpu.server.mp_executor import MPReadExecutor
            MPReadExecutor(ictx=None, n_workers=1)
    assert not started
    assert not os.path.exists(sock + ".log")


class _FakeDevice:
    def __init__(self, platform, stats):
        self.platform, self.device_kind, self._stats = \
            platform, f"fake {platform}", stats

    def memory_stats(self):
        return self._stats


@pytest.mark.parametrize("platform,stats,expect", [
    ("tpu", {"bytes_limit": 16 << 30}, 12 << 30),
    ("cpu", None, 4 << 30),
    ("cpu", {}, 4 << 30),
    ("tpu", None, RuntimeError),
    ("tpu", {"bytes_limit": 0}, RuntimeError),
])
def test_hbm_budget_never_guessed_on_a_tpu(monkeypatch, caplog, platform,
                                           stats, expect):
    import logging
    import jax
    from memgraph_tpu.server import kernel_server as ks
    monkeypatch.delenv("MEMGRAPH_TPU_HBM_BUDGET_BYTES", raising=False)
    monkeypatch.setattr(jax, "devices",
                        lambda *a: [_FakeDevice(platform, stats)])
    if expect is RuntimeError:
        with pytest.raises(RuntimeError, match="no bytes_limit"):
            ks._resolve_hbm_budget()
        return
    with caplog.at_level(logging.INFO, logger=ks.log.name):
        assert ks._resolve_hbm_budget() == expect
    assert sum("HBM admission budget" in r.getMessage()
               for r in caplog.records) == 1
