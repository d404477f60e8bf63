"""``snapshot_delta_share``: how the window's columnar-cache misses were
served (``GET /stats`` section ``delta``: ``delta.columnar_applied_total``
/ ``delta.columnar_rebuild_total``), rehearsed without the chip as
test_topk_share.py rehearses ``topk_share``.
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

NAME = "snapshot_delta_share"
CELLS = ["pokec_small.oltp_mixed"]

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


def hold_pins(root=REPO):
    """What this file holds of the BENCHMARK.json under `root`: the
    share is an entry reported in the oltp cells, which stand in this
    order among the cells of their traffic."""
    bench = bench_pins.read(root)
    bench_pins.entry_except_workloads(
        bench_pins.entry(bench["per_layer"], NAME),
        {"name": NAME, "unit": "%", "better": "higher",
         "source": "program_counter", "layer": "Cypher lane",
         "moves": "oltp_queries_per_s", "workloads": CELLS})
    bench_pins.stand_in_order([w for w in bench["workloads"]
                               if w["traffic"] == "oltp_mixed"], CELLS)


def test_the_entry_names_the_oltp_cell_and_its_file_is_data():
    hold_pins()
    entry = bench_pins.entry(BENCHMARK["per_layer"], NAME)
    # one layer name, letter for letter, for the lane's other metrics
    assert entry["layer"] in {m["layer"] for m in BENCHMARK["per_layer"]
                              if m["name"] == "lane_snapshot_ms"}
    assert SPEC["kind"] == "stats_delta" and SPEC["kind"] in layers.READERS
    params = SPEC["params"]
    assert params["scale"] == 100.0
    assert set(params["numerator"]) < set(params["denominator"])


def test_a_program_without_the_counters_reports_nothing():
    """The parent commit has neither counter: the share is left out of
    the line, it does not read 0 and does not raise."""
    ctx = {"stats_before": {"delta/delta.plan_applied_total": 3.0,
                            "device/span.lane.snapshot.count": 2.0},
           "stats_after": {"delta/delta.plan_applied_total": 9.0,
                           "device/span.lane.snapshot.count": 8.0},
           "cycles": 6}
    assert layers.read(SPEC, ctx) is None
    assert layers.read(SPEC, {}) is None
    applied, = SPEC["params"]["numerator"]
    rebuilt = "delta/delta.columnar_rebuild_total"
    assert rebuilt in SPEC["params"]["denominator"]
    ctx = {"stats_before": {applied: 2.0, rebuilt: 5.0},
           "stats_after": {applied: 9.0, rebuilt: 6.0}, "cycles": 7}
    assert layers.read(SPEC, ctx) == pytest.approx(87.5)
    # a window of patches alone after a set-up of sweeps
    ctx = {"stats_before": {applied: 0.0, rebuilt: 4.0},
           "stats_after": {applied: 9.0, rebuilt: 4.0}, "cycles": 7}
    assert layers.read(SPEC, ctx) == 100.0


def test_counter_names_are_the_ones_get_stats_prints():
    """The file's keys against a live ``GET /stats`` read through
    run.py's own flat_stats, after one swept and one patched miss: the
    section prefix is part of the name."""
    from memgraph_tpu.observability.http import start_monitoring_server
    from memgraph_tpu.query import Interpreter, InterpreterContext
    from memgraph_tpu.storage import InMemoryStorage

    ictx = InterpreterContext(InMemoryStorage())
    execute = Interpreter(ictx).execute
    execute("UNWIND range(0, 9) AS i CREATE (:User {id: i, age: i % 4})")
    count = ("MATCH (n:User) USING PARALLEL EXECUTION WHERE n.age > 1 "
             "RETURN count(*)")
    assert execute(count)[1] == [[4]]               # no entry yet: a sweep
    execute("MATCH (n:User {id: 0}) SET n.age = 3")
    assert execute(count)[1] == [[5]]               # one vertex: a patch

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
    # beside the CSR export's pair, in one section
    assert all(k.startswith("delta/delta.") for k in params["denominator"])


def test_every_miss_of_the_rehearsed_window_is_a_patch(tmp_path,
                                                       monkeypatch):
    """The cell's traffic at 2k/20k: writers commit between any two lane
    requests, so each of them misses the cache, and each miss patches
    the entry the request before it left; the counts still compare."""
    # 2,000 rows lie under the lane's floor: lower it, as the small
    # cell's 10,000 rows lie above it (test_program_spans.py)
    monkeypatch.setenv("MEMGRAPH_TPU_LANE_MIN_ROWS", "64")
    cell = run.load_cell(CELLS[0])
    cell["config"] = dict(cell["config"], nodes=2_000, edges=20_000)
    result = run.run_cell(cell, 2_147_483_929, 3.0, True, str(tmp_path),
                          device_check=lambda device, chips: None,
                          t_start=time.perf_counter())
    assert result["correct"] is True, result["compared"]
    got = {name: m["value"] for name, m in result["metrics"].items()}
    assert got[NAME] == 100.0
    assert "lane_snapshot_ms" in got and "lane_hit_share" in got
