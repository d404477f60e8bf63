"""``topk_share``: which operator ran the window's ``ORDER BY``
(``GET /stats`` section ``device``: ``query.topk_total`` /
``query.sort_full_total``), rehearsed without the chip as
test_program_reuse.py rehearses ``program_reuse_share``.
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

NAME = "topk_share"
CELLS = ["pokec_medium.analytics_fresh",
         "pokec_medium_daemon.analytics_fresh"]

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
    share is an entry reported in both analytics cells, which stand in
    this order among the cells of their traffic."""
    bench = bench_pins.read(root)
    bench_pins.entry_except_workloads(
        bench_pins.entry(bench["per_layer"], NAME),
        {"name": NAME, "unit": "%", "better": "higher",
         "source": "program_counter", "layer": "analytics CALL",
         "moves": "fresh_cycle_s", "workloads": CELLS})
    bench_pins.stand_in_order([w for w in bench["workloads"]
                               if w["traffic"] == "analytics_fresh"], CELLS)


def test_the_entry_names_both_analytics_cells_and_its_file_is_data():
    hold_pins()
    entry = bench_pins.entry(BENCHMARK["per_layer"], NAME)
    # one layer name, letter for letter, for the CALL's other metrics
    assert entry["layer"] in {m["layer"] for m in BENCHMARK["per_layer"]
                              if m["name"] == "call_sort_ms"}
    assert SPEC["kind"] == "stats_delta" and SPEC["kind"] in layers.READERS
    params = SPEC["params"]
    assert params["scale"] == 100.0
    assert set(params["numerator"]) < set(params["denominator"])


def test_a_program_without_the_counters_reports_nothing():
    """The parent commit has no query.topk_total: the share is left out
    of the line, it does not read 0 and does not raise."""
    ctx = {"stats_before": {"device/span.query.sort.count": 3.0},
           "stats_after": {"device/span.query.sort.count": 9.0}, "cycles": 6}
    assert layers.read(SPEC, ctx) is None
    assert layers.read(SPEC, {}) is None
    topk, full = SPEC["params"]["numerator"][0], \
        "device/query.sort_full_total"
    assert full in SPEC["params"]["denominator"]
    ctx = {"stats_before": {topk: 2.0, full: 5.0},
           "stats_after": {topk: 9.0, full: 6.0}, "cycles": 7}
    assert layers.read(SPEC, ctx) == pytest.approx(87.5)
    # a window of top-k CALLs alone: the full sort's counter is not
    # there until an OrderBy has run
    ctx = {"stats_before": {topk: 2.0}, "stats_after": {topk: 9.0},
           "cycles": 7}
    assert layers.read(SPEC, ctx) == 100.0


def test_counter_names_are_the_ones_get_stats_prints():
    """The file's keys against a live ``GET /stats`` read through
    run.py's own flat_stats, after one top-k query and one full sort:
    the section prefix is part of the name."""
    from memgraph_tpu.observability.http import start_monitoring_server
    from memgraph_tpu.query import Interpreter, InterpreterContext
    from memgraph_tpu.storage import InMemoryStorage

    ictx = InterpreterContext(InMemoryStorage())
    for tail in ("LIMIT 2", ""):
        rows = Interpreter(ictx).execute(
            "UNWIND [3, 1, 2] AS x RETURN x ORDER BY x DESC " + tail)[1]
        assert rows[:2] == [[3], [2]]

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
            k for k in flat if "query" in k))
    # beside the span the two counters split, in one section
    assert "device/span.query.sort.count" in flat


def test_every_call_of_the_rehearsed_window_is_a_top_k(tmp_path):
    """The cell's traffic at 2k/20k: each cycle's CALL is one TopK
    cursor, the window holds no full sort (the read-back queries, which
    have no LIMIT, come after it), and the rows still compare."""
    cell = run.load_cell(CELLS[0])
    cell["config"] = dict(cell["config"], nodes=2_000, edges=20_000)
    result = run.run_cell(cell, 2_147_483_929, 3.0, True, str(tmp_path),
                          device_check=lambda device, chips: None,
                          t_start=time.perf_counter())
    assert result["correct"] is True, result["compared"]
    assert result["cycles"] >= 2
    got = {name: m["value"] for name, m in result["metrics"].items()}
    assert got[NAME] == 100.0
    assert "call_sort_ms" in got and "call_consume_ms" in got
