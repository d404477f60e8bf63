"""``program_reuse_share``: how often a CALL's MXU kernel found its
fixpoint program already traced (``GET /stats`` section ``device``:
``mxu.program_hit_total`` / ``mxu.program_miss_total``), rehearsed
without the chip as test_program_spans.py rehearses the phase metrics.
"""

import asyncio
import json
import os
import sys
import threading
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmarks", "chipbench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import bench_pins  # noqa: E402
import layers  # noqa: E402
import run  # noqa: E402

N_NODES, N_EDGES = 2_000, 20_000
SEED = 2_147_483_811            # the driver's seeds pass 2**31
MEDIUM = "pokec_medium.analytics_fresh"
NAME = "program_reuse_share"

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
    metric is an entry, read in the analytics cell."""
    bench = bench_pins.read(root)
    bench_pins.entry_except_workloads(
        bench_pins.entry(bench["per_layer"], NAME),
        {"name": NAME, "unit": "%", "better": "higher",
         "source": "program_counter", "layer": "compile",
         "moves": "fresh_cycle_s", "workloads": [MEDIUM]})


def test_metric_is_an_entry_and_its_file_is_data():
    hold_pins()
    # the accepted CPU rehearsal allows no metric named fixpoint*
    assert not NAME.startswith("fixpoint")
    assert SPEC["kind"] == "stats_delta" and SPEC["kind"] in layers.READERS
    params = SPEC["params"]
    assert params["scale"] == 100.0
    assert set(params["numerator"]) < set(params["denominator"])


def test_a_program_without_the_counters_reports_nothing():
    """The parent commit has no mxu.* keys: the share is left out of
    the line, it does not read 0 and does not raise."""
    ctx = {"stats_before": {"device/jit.compile_total": 3.0},
           "stats_after": {"device/jit.compile_total": 9.0}, "cycles": 6}
    assert layers.read(SPEC, ctx) is None
    assert layers.read(SPEC, {}) is None
    hit, miss = SPEC["params"]["numerator"][0], \
        "device/mxu.program_miss_total"
    ctx = {"stats_before": {hit: 1.0, miss: 2.0},
           "stats_after": {hit: 8.0, miss: 3.0}, "cycles": 8}
    assert layers.read(SPEC, ctx) == pytest.approx(87.5)


def test_counter_names_are_the_ones_get_stats_prints():
    """The file's keys against a live ``GET /stats`` read through
    run.py's own flat_stats: the section prefix is part of the name
    (``delta/…`` against ``device/…`` has bitten before)."""
    from memgraph_tpu.observability.http import start_monitoring_server
    from memgraph_tpu.ops import spmv_mxu

    rng = np.random.default_rng(3)
    plan = spmv_mxu.build_plan(rng.integers(0, 300, 3000),
                               rng.integers(0, 300, 3000), None, 300)

    def epilogue(x, acc, env, params):          # a signature of its own
        return spmv_mxu.pagerank_mxu_epilogue(x, acc, env, params)

    spmv_mxu.make_semiring_kernel(plan, epilogue)       # a miss
    spmv_mxu.make_semiring_kernel(plan, epilogue)       # a hit

    port = run._free_port()
    loop = asyncio.new_event_loop()
    started = threading.Event()

    def serve():
        asyncio.set_event_loop(loop)
        loop.run_until_complete(
            start_monitoring_server("127.0.0.1", port, None))
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
            k for k in flat if "mxu" in k or "jit" in k))
    # beside the compile witness, in one section
    assert "device/jit.compile_total" in flat or not any(
        k.startswith("device/jit.") for k in flat)


def test_reuse_on_the_analytics_rehearsal(tmp_path, monkeypatch):
    """The cell's traffic at 2k/20k on the forced MXU route: the first
    CALL and the warm-up cycle meet the two signatures (full plan,
    plan + delta), every CALL of the window reuses the second."""
    monkeypatch.setenv("MEMGRAPH_TPU_FORCE_MXU", "1")
    monkeypatch.setenv("MEMGRAPH_TPU_MXU_MIN_EDGES", "1000")
    cell = run.load_cell(MEDIUM)
    cell["config"] = dict(cell["config"], nodes=N_NODES, edges=N_EDGES)
    result = run.run_cell(cell, SEED, 4.0, True, str(tmp_path),
                          device_check=lambda device, chips: None,
                          t_start=time.perf_counter())
    assert result["correct"] is True, result["compared"]
    assert result["cycles"] >= 2
    got = {name: m["value"] for name, m in result["metrics"].items()}
    assert got[NAME] == 100.0
    assert got["delta_plan_share"] == 100.0
    # no CALL of the window loaded the fixpoint's executable again
    assert got["compiles_per_cycle"] < 1.0
