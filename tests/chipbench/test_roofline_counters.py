"""The kernels layer's rooflines count a kernel's iterations by the
program's own counters across the trace slice (``iterations_counter``
in a ``trace_roofline`` metric file), not by the runs of an op whose
name the compiler chose. The reader on made-up traces; the slice's two
snapshots in ``run.run_window``; and for each metric file that names a
counter, a CPU run of its procedure, read as the harness reads it,
moves that counter by the iterations the kernel returned.
"""

import asyncio
import importlib
import json
import os
import socket
import sys
import threading
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmarks", "chipbench")
for _p in (BENCH, os.path.join(REPO, "tests")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import graphalytics_reference as ref  # noqa: E402
import layers  # noqa: E402
import run  # noqa: E402
import seams  # noqa: E402

with open(os.path.join(BENCH, "peaks.json")) as _f:
    PEAK = json.load(_f)["TPU v5 lite"]
#: graph500_s17_inproc's loaded sizes
N_NODES, N_EDGES = 90_162, 1_864_185

BFS = ("MATCH (s:Vertex {id: $root}) CALL bfs.get(s, false) "
       "YIELD node, level RETURN count(*)")
SSSP = ("MATCH (s:Vertex {id: $root}) CALL sssp.get(s, 'weight', false) "
        "YIELD node, distance RETURN count(*)")
#: each metric file that counts by a counter of the Bolt server's, with
#: the query that runs its procedure and the kernel that returns the
#: call's iterations last
IN_PROCESS = {
    "bfs_roofline": (BFS, "memgraph_tpu.ops.traversal.bfs_levels"),
    "sssp_roofline": (SSSP, "memgraph_tpu.ops.traversal.sssp"),
    "wcc_roofline": ("CALL wcc.get() YIELD node RETURN count(*)",
                     "memgraph_tpu.ops.components."
                     "weakly_connected_components"),
    "cdlp_roofline": ("CALL label_propagation.get(10) YIELD node "
                      "RETURN count(*)",
                      "memgraph_tpu.ops.labelprop.label_propagation"),
    "fixpoint_roofline": ("CALL pagerank.get() YIELD node RETURN count(*)",
                          "memgraph_tpu.ops.pagerank.pagerank"),
}
#: and the one that counts by the kernel-server daemon's
DAEMON = "pc_fixpoint_roofline"
COUNTED = list(IN_PROCESS) + [DAEMON]


def spec(name):
    with open(os.path.join(BENCH, "layer_metrics", name + ".json")) as f:
        return json.load(f)


def trace_of(modules: dict, ops: dict) -> dict:
    """A device trace as ``trace_reduce`` summarises one: {name:
    seconds} of programs and {name: runs} of ops."""
    return {"device_planes": ["/device:TPU:0"], "busy_s": 1.0,
            "modules": {name: {"count": 1, "seconds": s}
                        for name, s in modules.items()},
            "ops": {name: {"count": c, "seconds": 1e-3 * c}
                    for name, c in ops.items()}}


def program_of(metric: dict) -> str:
    """A program name the metric's first pattern matches."""
    return metric["params"]["patterns"][0].replace("*", "")


def expected(metric: dict, iterations: float, seconds: float) -> float:
    module = seams.load_module(None, "rooflines",
                               metric["params"]["roofline"])
    least = module.least_seconds(N_NODES, N_EDGES, iterations, PEAK)
    return 100.0 * least["seconds"] / seconds


def ctx_of(trace, before, after, **more) -> dict:
    return dict({"trace": trace, "trace_stats_before": before,
                 "trace_stats_after": after, "n_nodes": N_NODES,
                 "n_edges": N_EDGES, "peak": PEAK}, **more)


# --------------------------------------------------------------------------
# the reader
# --------------------------------------------------------------------------

CDLP_COUNTER = "device/analytics.cdlp.iterations_total"


def test_the_iterations_are_the_counters_delta_across_the_slice():
    """The slice's snapshots count, not the window's, and a counter the
    metric does not name moves nothing."""
    metric = spec("cdlp_roofline")
    trace = trace_of({"jit_fixpoint_labelprop": 3.0}, {"%sort.37": 7})
    ctx = ctx_of(trace,
                 {CDLP_COUNTER: 20.0, "device/jit.compile_total": 1.0},
                 {CDLP_COUNTER: 30.0, "device/jit.compile_total": 9.0},
                 stats_before={CDLP_COUNTER: 0.0},
                 stats_after={CDLP_COUNTER: 60.0})
    assert layers.read(metric, ctx) == pytest.approx(
        expected(metric, 10, 3.0))


@pytest.mark.parametrize("before,after", [
    ({CDLP_COUNTER: 30.0}, {CDLP_COUNTER: 30.0}),
    ({}, {}),
    (None, {CDLP_COUNTER: 30.0}),
    ({CDLP_COUNTER: 20.0}, None),
], ids=["zero_delta", "no_counter", "no_first_snapshot",
        "no_second_snapshot"])
def test_no_counted_iteration_reads_nothing(before, after):
    """Left out of the line, never read as a share of 0."""
    trace = trace_of({"jit_fixpoint_labelprop": 3.0}, {"%sort.37": 10})
    assert layers.read(spec("cdlp_roofline"),
                       ctx_of(trace, before, after)) is None


@pytest.mark.parametrize("name", COUNTED)
def test_renaming_every_op_leaves_the_reading(name):
    """The same work over the same device time reads the same share
    whatever the compiler names the loop body's ops, the op the file
    once counted included."""
    metric = spec(name)
    counter = metric["params"]["iterations_counter"][0]
    old_tick = metric["params"].get("once_per_iteration", ["%sort.37"])[0]
    program = program_of(metric)
    before, after = {counter: 100.0}, {counter: 112.0}
    named = trace_of({program: 0.5}, {old_tick.replace("*", ".2"): 5,
                                      "%fusion.9": 12})
    renamed = trace_of({program: 0.5}, {"%sort.65": 5, "%fusion.70": 12})
    want = expected(metric, 12, 0.5)
    assert layers.read(metric, ctx_of(named, before, after)) == \
        pytest.approx(want)
    assert layers.read(metric, ctx_of(renamed, before, after)) == \
        pytest.approx(want)


def test_a_tick_alone_goes_silent_once_its_op_is_renamed():
    """What the counters cure: counted by an op's name, the reading
    vanishes with a rewrite of the loop body that keeps the work."""
    metric = spec("cdlp_roofline")
    params = dict(metric["params"], once_per_iteration=["%sort.37"])
    del params["iterations_counter"]
    by_tick = dict(metric, params=params)
    ctx = ctx_of(trace_of({"jit_fixpoint_labelprop": 3.0},
                          {"%sort.37": 10}), None, None)
    assert layers.read(by_tick, ctx) == pytest.approx(
        expected(metric, 10, 3.0))
    ctx["trace"] = trace_of({"jit_fixpoint_labelprop": 3.0},
                            {"%sort.33": 10})
    assert layers.read(by_tick, ctx) is None


# --------------------------------------------------------------------------
# the slice's snapshots
# --------------------------------------------------------------------------

class _Owner:
    """Counts one iteration a request; logs what the window asks."""

    def __init__(self):
        self.asked, self.served = [], 0

    def stats(self):
        self.asked.append("stats")
        return {"device/analytics.bfs.iterations_total": float(self.served)}

    def ask(self, op, **_fields):
        self.asked.append(op)
        return {"started_ns": 0} if op == "trace_start" \
            else {"stopped_ns": 10 ** 9}


class _Transport:
    def __init__(self, owner):
        self.owner = owner

    def run(self, req):
        time.sleep(0.002)
        self.owner.served += 1
        return req


def test_the_slice_snapshots_stand_at_its_edges():
    """One snapshot before the profiler starts and before any client,
    one after the slice's cycles and before the profiler stops."""
    owner = _Owner()
    plan = iter(object, None)
    mix = {"schedule": "sequence", "classes": [{}, {}],
           "trace_slice": {"cycles": 3}}
    _t0, outs, trace = run.run_window(mix, [plan], [_Transport(owner)],
                                      0.3, owner, "unused")
    assert owner.asked == ["stats", "trace_start", "stats", "trace_stop"]
    assert trace["stats_before"] == {
        "device/analytics.bfs.iterations_total": 0.0}
    assert trace["cycles"] == 3
    assert 6 <= trace["stats_after"][
        "device/analytics.bfs.iterations_total"] <= len(outs[0])


# --------------------------------------------------------------------------
# each file's counter, moved by a CPU run of its procedure
# --------------------------------------------------------------------------

def _serve_stats(ictx):
    """GET /stats of `ictx` on a thread: (port, stop)."""
    from memgraph_tpu.observability.http import start_monitoring_server
    port = run._free_port()
    loop = asyncio.new_event_loop()
    started = threading.Event()

    def serve():
        asyncio.set_event_loop(loop)
        loop.run_until_complete(
            start_monitoring_server("127.0.0.1", port, ictx))
        started.set()
        loop.run_forever()

    thread = threading.Thread(target=serve, daemon=True)
    thread.start()
    assert started.wait(10)

    def stop():
        loop.call_soon_threadsafe(loop.stop)
        thread.join(10)
    return port, stop


@pytest.fixture(scope="module")
def served():
    """A seeded Kronecker graph at scale 9, loaded through Cypher as
    graph500_s17_inproc loads it, and its server's GET /stats."""
    from memgraph_tpu.query import Interpreter, InterpreterContext
    from memgraph_tpu.storage import InMemoryStorage
    n, src, dst, weights, _ = ref.kronecker(9, 16, 2_147_483_711)
    ictx = InterpreterContext(InMemoryStorage())
    for query, params in (
            ("CREATE INDEX ON :Vertex(id)", {}),
            ("UNWIND range(0, $n - 1) AS i CREATE (:Vertex {id: i})",
             {"n": n}),
            ("UNWIND $edges AS e MATCH (a:Vertex {id: e[0]}), "
             "(b:Vertex {id: e[1]}) CREATE (a)-[:EDGE {weight: e[2]}]->(b)",
             {"edges": [[int(a), int(b), float(w)]
                        for a, b, w in zip(src, dst, weights)]})):
        Interpreter(ictx).execute(query, params)
    port, stop = _serve_stats(ictx)
    yield ictx, port, int(src[0])
    stop()


def watch(monkeypatch, kernel: str) -> list:
    """The iterations each call of `kernel` returns (its last value)."""
    module_name, name = kernel.rsplit(".", 1)
    module = importlib.import_module(module_name)
    inner, returned = getattr(module, name), []

    def watched(*args, **kw):
        out = inner(*args, **kw)
        returned.append(int(out[-1]))
        return out
    monkeypatch.setattr(module, name, watched)
    return returned


@pytest.mark.parametrize("name", list(IN_PROCESS))
def test_a_procedure_moves_its_counter_by_its_iterations(name, served,
                                                         monkeypatch):
    """The file's counter, read through GET /stats by the harness's own
    flat_stats, moves by what the kernel's loop counted, and the reader
    turns that into the share of so many iterations."""
    from memgraph_tpu.query import Interpreter
    ictx, port, root = served
    query, kernel = IN_PROCESS[name]
    returned = watch(monkeypatch, kernel)
    metric = spec(name)
    before = run.flat_stats(port)
    Interpreter(ictx).execute(query, {"root": root})
    after = run.flat_stats(port)
    assert len(returned) == 1 and returned[0] > 0
    trace = trace_of({program_of(metric): 0.25}, {})
    assert layers.read(metric, ctx_of(trace, before, after)) == \
        pytest.approx(expected(metric, returned[0], 0.25))


def test_the_daemons_pagerank_moves_its_counter_by_its_iterations(
        tmp_path):
    """The daemon's pagerank op, served in this process over a socket
    pair, moves the counter its health reply carries by the iterations
    it replies with; the daemon layout lists that counter as
    ``daemon/<name>`` (owners/daemon_server.py ``stats``)."""
    from memgraph_tpu.server.kernel_server import (KernelServer, _recv_msg,
                                                   _send_msg)
    n, src, dst, _, _ = ref.kronecker(9, 16, 2_147_483_712)
    srv = KernelServer(socket_path=str(tmp_path / "ks.sock"))
    ours, theirs = socket.socketpair()
    thread = threading.Thread(target=srv._serve_conn, args=(theirs,),
                              daemon=True)
    thread.start()

    def daemon_stats():
        _send_msg(ours, {"op": "health"})
        health, _ = _recv_msg(ours)
        return {f"daemon/{key}": float(value)
                for key, value in health["counters"].items()
                if isinstance(value, (int, float))}
    try:
        ours.settimeout(120)
        before = daemon_stats()
        _send_msg(ours, {"op": "pagerank", "n_nodes": int(n)},
                  {"src": np.concatenate([src, dst]).astype(np.int64),
                   "dst": np.concatenate([dst, src]).astype(np.int64)})
        reply, _ = _recv_msg(ours)
        after = daemon_stats()
    finally:
        srv._shutdown.set()
        ours.close()
        thread.join(10)
    assert reply["ok"] and reply["iters"] > 0, reply
    metric = spec(DAEMON)
    trace = trace_of({program_of(metric): 0.25}, {})
    assert layers.read(metric, ctx_of(trace, before, after)) == \
        pytest.approx(expected(metric, reply["iters"], 0.25))
