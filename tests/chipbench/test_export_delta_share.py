"""``export_delta_share``: how the window's ``GraphCache`` misses were
served (``GET /stats`` section ``delta``: ``delta.export_applied_total``
/ ``delta.export_rebuild_total``), rehearsed without the chip as
test_snapshot_delta_share.py rehearses ``snapshot_delta_share``.
"""

import asyncio
import json
import os
import sys
import threading
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmarks", "chipbench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import bench_pins  # noqa: E402
import layers  # noqa: E402
import run  # noqa: E402

NAME = "export_delta_share"
CELLS = ["pokec_medium.analytics_fresh",
         "pokec_medium_daemon.analytics_fresh"]
#: runs the same GraphCache once a cycle, and inserts a vertex in each:
#: the one cell in which the share can read under 100
RETRIEVAL = "graphrag_medium.retrieve_fresh"

with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    BENCHMARK = json.load(_f)
with open(os.path.join(BENCH, "layer_metrics", NAME + ".json")) as _f:
    SPEC = json.load(_f)


@pytest.fixture(autouse=True)
def _no_stray_children():
    yield
    leaked = list(run._CHILDREN)
    run.stop_all()
    assert not leaked, f"a run left {len(leaked)} process(es) running"


def small(cell_name):
    cell = run.load_cell(cell_name)
    cell["config"] = dict(cell["config"], nodes=2_000, edges=20_000)
    return cell


def drive(cell, tmp_path, seconds):
    return run.run_cell(cell, 2_147_483_929, seconds, True, str(tmp_path),
                        device_check=lambda device, chips: None,
                        t_start=time.perf_counter())


def hold_pins(root=REPO):
    """What this file holds of the BENCHMARK.json under `root`: the
    share is an entry reported in every cell that exports, under any
    name of the span it splits."""
    bench = bench_pins.read(root)
    bench_pins.entry_except_workloads(
        bench_pins.entry(bench["per_layer"], NAME),
        {"name": NAME, "unit": "%", "better": "higher",
         "source": "program_counter", "layer": "CSR export",
         "moves": "fresh_cycle_s", "workloads": CELLS + [RETRIEVAL]})


def test_the_entry_names_the_export_cells_and_its_file_is_data():
    hold_pins()
    entry = bench_pins.entry(BENCHMARK["per_layer"], NAME)
    cells = {w["name"] for w in BENCHMARK["workloads"]}
    moved = next(m for m in BENCHMARK["end_to_end"]
                 if m["name"] == entry["moves"])
    assert set(entry["workloads"]) <= cells & set(moved["workloads"])
    # one layer name, letter for letter, for the export's other metrics
    assert entry["layer"] in {m["layer"] for m in BENCHMARK["per_layer"]
                              if m["name"] == "call_export_ms"}
    assert SPEC["kind"] == "stats_delta" and SPEC["kind"] in layers.READERS
    params = SPEC["params"]
    assert params["scale"] == 100.0
    assert set(params["numerator"]) < set(params["denominator"])
    for cell in entry["workloads"]:
        assert NAME in [m["name"] for m in run.load_cell(cell)["per_layer"]]


def test_a_program_without_the_counters_reports_nothing():
    """The parent commit has neither counter: the share is left out of
    the line, it does not read 0 and does not raise."""
    ctx = {"stats_before": {"delta/delta.plan_applied_total": 3.0,
                            "device/span.analytics.export.count": 2.0},
           "stats_after": {"delta/delta.plan_applied_total": 9.0,
                           "device/span.analytics.export.count": 8.0},
           "cycles": 6}
    assert layers.read(SPEC, ctx) is None
    assert layers.read(SPEC, {}) is None
    applied, = SPEC["params"]["numerator"]
    rebuilt = "delta/delta.export_rebuild_total"
    assert rebuilt in SPEC["params"]["denominator"]
    ctx = {"stats_before": {applied: 2.0, rebuilt: 5.0},
           "stats_after": {applied: 9.0, rebuilt: 6.0}, "cycles": 7}
    assert layers.read(SPEC, ctx) == pytest.approx(87.5)
    # a window of splices alone after a warm-up's full export
    ctx = {"stats_before": {applied: 1.0, rebuilt: 1.0},
           "stats_after": {applied: 9.0, rebuilt: 1.0}, "cycles": 8}
    assert layers.read(SPEC, ctx) == 100.0


def test_counter_names_are_the_ones_get_stats_prints():
    """The file's keys against a live ``GET /stats`` read through
    run.py's own flat_stats, after one full export and one spliced
    insert: the section prefix is part of the name."""
    from memgraph_tpu.observability.http import start_monitoring_server
    from memgraph_tpu.query import Interpreter, InterpreterContext
    from memgraph_tpu.storage import InMemoryStorage

    ictx = InterpreterContext(InMemoryStorage())
    execute = Interpreter(ictx).execute
    execute("UNWIND range(0, 9) AS i CREATE (:User {id: i})")
    execute("MATCH (a:User), (b:User) WHERE b.id = a.id + 1 "
            "CREATE (a)-[:FRIEND]->(b)")
    count = "CALL pagerank.get() YIELD node RETURN count(node)"
    assert execute(count)[1] == [[10]]              # no snapshot yet: full
    execute("MATCH (b:User {id: 0}) CREATE (:User {id: 10})-[:FRIEND]->(b)")
    assert execute(count)[1] == [[11]]              # the vertex joins

    port = run._free_port()
    loop = asyncio.new_event_loop()
    started = threading.Event()

    def serve():
        asyncio.set_event_loop(loop)
        loop.run_until_complete(
            start_monitoring_server("127.0.0.1", port, ictx))
        started.set()
        loop.run_forever()

    threading.Thread(target=serve, daemon=True).start()
    assert started.wait(10)
    try:
        flat = run.flat_stats(port)
    finally:
        loop.call_soon_threadsafe(loop.stop)
    params = SPEC["params"]
    for key in params["numerator"] + params["denominator"]:
        assert flat.get(key, 0.0) >= 1.0, (key, sorted(
            k for k in flat if k.startswith("delta/")))
    # beside the delta plan's pair, in one section
    assert all(k.startswith("delta/delta.") for k in params["denominator"])


def test_every_miss_of_the_rehearsed_window_is_a_splice(tmp_path):
    """The in-process cell's traffic at 2k/20k: every cycle's burst
    writes edges between vertices the snapshot has, so each CALL's miss
    is a splice; the rows still compare."""
    result = drive(small(CELLS[0]), tmp_path, 3.0)
    assert result["correct"] is True, result["compared"]
    assert result["cycles"] >= 2
    got = {name: m["value"] for name, m in result["metrics"].items()}
    assert got[NAME] == 100.0
    assert got["call_export_ms"] > 0


def test_the_daemon_layout_carries_the_bolt_servers_counters(tmp_path):
    """The other layout exports in the Bolt server, whose ``delta``
    section its ``stats()`` passes on under the same names."""
    result = drive(small(CELLS[1]), tmp_path, 2.0)
    assert result["correct"] is True, result["compared"]
    got = {name: m["value"] for name, m in result["metrics"].items()}
    assert got[NAME] == 100.0
    assert got["call_export_ms.daemon"] > 0


def test_the_retrieval_cells_inserted_vertex_is_spliced_too(tmp_path):
    """A cycle of the retrieval cell inserts one vertex with 8 edges
    before its hybrid CALL: the counters, read as the harness reads
    them, say every export of the window followed it."""
    cell = small(RETRIEVAL)
    assert NAME in [m["name"] for m in cell["per_layer"]]
    result = drive(cell, tmp_path, 2.0)
    assert result["correct"] is True, result["compared"]
    assert result["cycles"] >= 2
    got = {name: m["value"] for name, m in result["metrics"].items()}
    assert got[NAME] == 100.0
    assert got["call_export_ms.graphrag"] > 0
