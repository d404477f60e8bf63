"""The host's work under the device's idle gaps, as phases
(observability/trace.py): Python's cyclic collector (``python.gc``,
``python.gc.full``, from one ``gc.callbacks`` entry a process) and the
Bolt server's message work (``bolt.prepare`` and ``bolt.pull`` on the
executor thread, ``bolt.encode`` on the event loop).

Accounted armed or not, and in a live profiler session's xplane as
``mgtrace:<name>``, where ``benchmarks/chipbench/gap_spans.py`` reads
them.
"""

import gc
import importlib
import os
import socket
import sys
import time

import pytest

from memgraph_tpu.observability import metrics as mgmetrics
from memgraph_tpu.observability import trace as T
from memgraph_tpu.observability.metrics import global_metrics
from memgraph_tpu.query.interpreter import InterpreterContext
from memgraph_tpu.server.bolt import BoltServer
from memgraph_tpu.server.client import BoltClient
from memgraph_tpu.storage.storage import InMemoryStorage

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks", "chipbench")

NEW_PHASES = ("bolt.prepare", "bolt.pull", "bolt.encode", "python.gc",
              "python.gc.full")


def counters() -> dict:
    return {name: value for name, _kind, value in global_metrics.snapshot()}


def count(name: str, got: dict | None = None) -> float:
    return (got or counters()).get(f"span.{name}.count", 0.0)


def ours() -> list:
    return [cb for cb in gc.callbacks
            if getattr(cb, "__module__", None) == T.__name__
            and getattr(cb, "__name__", None) == "_on_collect"]


@pytest.fixture
def disarmed():
    was = T.armed()
    T.disable()
    yield
    if was:
        T.enable()


@pytest.fixture
def bolt_server():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    srv = BoltServer(InterpreterContext(InMemoryStorage()), "127.0.0.1",
                     port)
    _thread, loop = srv.run_in_thread()
    try:
        yield port
    finally:
        srv.stop()
        loop.call_soon_threadsafe(loop.stop)


def wait_for(predicate, seconds=10.0):
    """The server closes bolt.encode after the answer is on the wire, so
    the client can read it first."""
    deadline = time.monotonic() + seconds
    while not predicate() and time.monotonic() < deadline:
        time.sleep(0.01)
    return predicate()


# --------------------------------------------------------------------------
# registration
# --------------------------------------------------------------------------

@pytest.mark.parametrize("name", NEW_PHASES)
def test_new_phase_is_a_declared_phase(name):
    assert name in T.SPAN_NAMES
    assert name in T.PHASES
    assert T.PHASES[name] == ()        # feeds no stage


def test_what_the_phases_replace_is_gone():
    assert not hasattr(T, "to_jsonl")
    assert "bolt.prepare_latency_sec" not in mgmetrics.STAT_NAMES


# --------------------------------------------------------------------------
# the collector
# --------------------------------------------------------------------------

@pytest.fixture
def no_automatic_collection():
    """Only the test's own gc.collect() runs between its two reads."""
    gc.disable()
    try:
        yield
    finally:
        gc.enable()


@pytest.mark.parametrize("generation", [0, 1, 2])
def test_a_collection_moves_python_gc(disarmed, no_automatic_collection,
                                      generation):
    before = counters()
    gc.collect(generation)
    after = counters()
    assert count("python.gc", after) - count("python.gc", before) >= 1
    full = count("python.gc.full", after) - count("python.gc.full", before)
    if generation == 2:
        assert full >= 1
        assert after["span.python.gc.full.seconds_total"] > \
            before.get("span.python.gc.full.seconds_total", 0.0)
    else:
        assert full == 0
    assert after["span.python.gc.seconds_total"] > \
        before.get("span.python.gc.seconds_total", 0.0)


def test_the_full_collection_is_the_same_extent():
    """python.gc.full lies inside its python.gc: never longer."""
    before = counters()
    for _ in range(5):
        gc.collect(2)
    after = counters()

    def delta(key):
        return after[key] - before.get(key, 0.0)
    full = delta("span.python.gc.full.seconds_total")
    assert 0 < full <= delta("span.python.gc.seconds_total")


def test_the_pair_is_installed_once():
    assert len(ours()) == 1
    for _ in range(3):
        assert T.install_collector_phases() is False
    folds = list(global_metrics._folds)
    # a reload runs the module's set-up again in the same namespace
    assert importlib.reload(T) is T
    assert T.install_collector_phases() is False
    assert len(ours()) == 1
    assert global_metrics._folds == folds
    before = count("python.gc")
    gc.collect(0)
    assert count("python.gc") - before >= 1


def test_a_callback_that_meets_an_error_does_not_raise(monkeypatch):
    gc.collect()            # other tests' garbage, finalizers and all
    seen = []
    monkeypatch.setattr(sys, "unraisablehook", seen.append)
    # the close cannot find its totals, the open cannot find its keys
    monkeypatch.setattr(T, "_collected", {})
    gc.collect(2)
    monkeypatch.setattr(T, "_PHASE_KEYS", {})
    gc.collect(2)
    # an error in a gc callback would reach the hook with the callback
    assert not [u for u in seen if u.object is T._on_collect], seen
    assert T._collecting == []
    monkeypatch.undo()
    before = count("python.gc")
    gc.collect(0)
    assert count("python.gc") - before >= 1


NO_LOCK_CHILD = """
import gc, threading
from memgraph_tpu.observability import trace as T
from memgraph_tpu.observability.metrics import global_metrics
plain = threading.Lock()
global_metrics._lock = plain
T.enable()
with plain, T.TRACER._lock:
    cycle = []
    cycle.append(cycle)
    del cycle
    gc.collect(2)
T.disable()
assert T._collected["python.gc.full"][1] >= 1
assert not any(s["name"].startswith("python.gc")
               for spans in T.TRACER.finished_traces() for s in spans)
print("ok")
"""


def test_the_collector_takes_no_lock():
    """A collection runs at whatever bytecode its thread had reached:
    inside the metrics registry's lock, or the tracer's, it must not
    wait for the lock its own thread holds (armed, it joins no trace).
    In a child, so that a regression hangs the child and not this
    worker."""
    import subprocess
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", NO_LOCK_CHILD], env=env,
                          capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0 and proc.stdout.strip() == "ok", \
        proc.stderr[-2000:]


def test_a_read_hands_over_what_the_collector_closed():
    gc.collect(0)
    pending = T._collected["python.gc"][1] - T._handed["python.gc"][1]
    assert pending >= 1
    before = global_metrics._counters["span.python.gc.count"]
    global_metrics.snapshot()
    assert global_metrics._counters["span.python.gc.count"] >= \
        before + pending


# --------------------------------------------------------------------------
# the Bolt server's phases
# --------------------------------------------------------------------------

BOLT = ("bolt.prepare", "bolt.pull", "bolt.encode")


def test_run_and_pull_move_each_bolt_phase_once(disarmed, bolt_server):
    client = BoltClient(port=bolt_server)
    try:
        warm = count("bolt.run")
        client.execute("RETURN 1")                    # warm the session
        assert wait_for(lambda: count("bolt.run") >= warm + 1)
        before = counters()
        _cols, rows, _summary = client.execute(
            "UNWIND range(1, 50) AS i RETURN i")
        assert len(rows) == 50
        assert wait_for(
            lambda: count("bolt.run") - count("bolt.run", before) >= 1)
        after = counters()
        for name in BOLT + ("bolt.run",):
            assert count(name, after) - count(name, before) == 1, name
        # bolt.wait ends where each message's phase begins: RUN and PULL
        assert count("bolt.wait", after) - count("bolt.wait", before) == 2
        spent = {name: after[f"span.{name}.seconds_total"]
                 - before[f"span.{name}.seconds_total"]
                 for name in BOLT + ("bolt.run",)}
        assert sum(spent[name] for name in BOLT) <= spent["bolt.run"]
    finally:
        client.close()


def test_discard_moves_bolt_pull(disarmed, bolt_server):
    from memgraph_tpu.server.bolt import M_DISCARD, M_RUN
    client = BoltClient(port=bolt_server)
    try:
        before = counters()
        client._send_message(M_RUN, "UNWIND range(1, 5) AS i RETURN i", {},
                             {})
        client._expect_success()
        client._send_message(M_DISCARD, {"n": -1})
        client._expect_success()
        assert wait_for(
            lambda: count("bolt.run") - count("bolt.run", before) >= 1)
        after = counters()
        assert count("bolt.pull", after) - count("bolt.pull", before) == 1
        assert count("bolt.prepare", after) - \
            count("bolt.prepare", before) == 1
        # nothing was encoded: a DISCARD sends no records
        assert count("bolt.encode", after) == count("bolt.encode", before)
    finally:
        client.close()


def test_armed_the_phases_join_the_session_trace(bolt_server):
    """bolt.prepare / bolt.pull / bolt.encode are children of bolt.run,
    and the interpreter's root stays bolt.run's child beside them."""
    was = T.armed()
    T.enable(sample=1.0)
    T.TRACER.reset()
    client = BoltClient(port=bolt_server)
    try:
        client.execute("UNWIND range(1, 3) AS i RETURN i")
        assert wait_for(lambda: any(
            s["name"] == "bolt.run" for spans in T.traces_json()
            for s in spans))
        spans = next(spans for spans in T.traces_json()
                     if any(s["name"] == "bolt.run" for s in spans))
        by_name = {s["name"]: s for s in spans}
        root = by_name["bolt.run"]
        for name in BOLT + ("query",):
            assert by_name[name]["parent_id"] == root["span_id"], name
        assert len({s["trace_id"] for s in spans}) == 1
    finally:
        client.close()
        if not was:
            T.disable()
        T.TRACER.reset()


# --------------------------------------------------------------------------
# the xplane: what gap_spans.py reads
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def profiled_host_events(tmp_path_factory):
    """One profile on the CPU holding a generation-2 collection and one
    RUN+PULL through an in-process Bolt session."""
    import jax
    import jax.numpy as jnp

    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    import gap_spans

    trace_dir = str(tmp_path_factory.mktemp("xplane"))
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    srv = BoltServer(InterpreterContext(InMemoryStorage()), "127.0.0.1",
                     port)
    _thread, loop = srv.run_in_thread()
    client = BoltClient(port=port)
    try:
        client.execute("RETURN 1")
        jnp.ones(8).sum().block_until_ready()
        jax.profiler.start_trace(trace_dir)
        try:
            gc.collect(2)
            _cols, rows, _summary = client.execute(
                "UNWIND range(1, 20000) AS i RETURN i")
            assert len(rows) == 20000
            assert wait_for(lambda: count("bolt.run") >= 2)
            time.sleep(0.05)       # the loop closes bolt.encode after
        finally:
            jax.profiler.stop_trace()
    finally:
        client.close()
        srv.stop()
        loop.call_soon_threadsafe(loop.stop)
    found = gap_spans.find_xplane(trace_dir)
    assert found is not None
    _planes, host = gap_spans.extract(found)
    return gap_spans, host


@pytest.mark.parametrize("name", NEW_PHASES)
def test_the_phase_sits_in_the_xplane(profiled_host_events, name):
    _gap_spans, host = profiled_host_events
    assert any(event == "mgtrace:" + name for event, _, _ in host), \
        sorted({event for event, _, _ in host})


def test_a_gap_over_a_real_pull_is_the_pull(profiled_host_events):
    """A device gap that spans the real bolt.pull goes to bolt.pull (and
    what lies inside it), not to ``unattributed``."""
    gap_spans, host = profiled_host_events
    pulls = [(start, dur) for event, start, dur in host
             if event == "mgtrace:bolt.pull"]
    start, dur = max(pulls, key=lambda p: p[1])
    planes = {"/device:TPU:0": [["before", start - 1000.0, 1000.0],
                                ["after", start + dur, 1000.0]]}
    rows = gap_spans.attribute(planes, host, top=1)
    assert len(rows) == 1
    parts = dict(rows[0]["parts"])
    assert "unattributed" not in parts, parts
    assert rows[0]["parts"][0][0] == "bolt.pull", parts
    assert abs(rows[0]["seconds"] - dur / 1e9) < 1e-9


# --------------------------------------------------------------------------
# mglint MG005: the collector's open site is trace.py's own
# --------------------------------------------------------------------------

@pytest.mark.parametrize("where", ["trace.py", "user.py"])
def test_mg005_counts_the_collector_phase_only_in_trace_py(tmp_path, where):
    """A name ``_CollectorPhase`` opens in trace.py is a live
    registration; the same call anywhere else opens nothing."""
    import shutil

    from tools.mglint.core import Project, run_rules
    tree = tmp_path / "pkg"
    shutil.copytree(os.path.join(REPO, "tests", "lint_fixtures", "mg005"),
                    tree)
    trace = tree / "observability" / "trace.py"
    trace.write_text(trace.read_text().replace(
        '"dead.span",', '"dead.span",\n    "collector.span",'))
    site = trace if where == "trace.py" else tree / "user.py"
    site.write_text(site.read_text()
                    + '\n\ndef _on(phase, info):\n'
                      '    _CollectorPhase("collector.span")\n')
    result = run_rules(Project([str(tree)], cwd=REPO), {}, only={"MG005"})
    msgs = {f.fingerprint for f in result.findings}
    assert "span-dead:dead.span" in msgs
    assert ("span-dead:collector.span" in msgs) == (where == "user.py")
