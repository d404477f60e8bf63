"""The per-layer metrics that read the program's own phase spans and
counters (``GET /stats`` section ``device``: ``span.<name>.seconds_total``
/ ``.count``, ``jit.*``, ``device.*``; section ``delta``), rehearsed
without the chip through run.py's own functions, as test_chipbench.py
rehearses the cells.

The analytics rehearsal forces the MXU route at 2k/20k (the route the
medium cell takes on the chip), so the delta-plan phases and counters
move here too.
"""

import json
import os
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmarks", "chipbench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import bench_pins  # noqa: E402
import gap_spans  # noqa: E402
import layers  # noqa: E402
import run  # noqa: E402

N_NODES, N_EDGES = 2_000, 20_000
SEED = 2_147_483_777            # the driver's seeds pass 2**31

with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    BENCHMARK = json.load(_f)

MEDIUM = "pokec_medium.analytics_fresh"
SMALL = "pokec_small.oltp_mixed"
CALL_PHASES = ["call_export_ms", "call_delta_plan_ms", "call_launch_ms",
               "call_device_wait_ms", "call_rows_ms"]
MEDIUM_NEW = ["call_server_ms"] + CALL_PHASES + [
    "gc_ms_per_cycle", "jit_backend_ms", "true_compiles_per_cycle",
    "iterations_per_call", "delta_plan_share"]
LANE_PHASES = ["lane_snapshot_ms", "lane_stage_ms", "lane_dispatch_ms",
               "lane_iterate_ms"]
SMALL_NEW = ["lane_server_ms"] + LANE_PHASES + ["bolt_wait_ms",
                                               "mvcc_commit_ms"]
PROGRAM_METRICS = MEDIUM_NEW + SMALL_NEW


def small_cell(workload):
    cell = run.load_cell(workload)
    cell["config"] = dict(cell["config"], nodes=N_NODES, edges=N_EDGES)
    return cell


@pytest.fixture(autouse=True)
def _no_stray_children():
    yield
    leaked = list(run._CHILDREN)
    run.stop_all()
    assert not leaked, f"a run left {len(leaked)} process(es) running"


def drive(cell, tmp_path, seconds):
    return run.run_cell(cell, SEED, seconds, True, str(tmp_path),
                        device_check=lambda device, chips: None,
                        t_start=time.perf_counter())


def values(result):
    return {name: m["value"] for name, m in result["metrics"].items()}


# --------------------------------------------------------------------------
# the metric files
# --------------------------------------------------------------------------

def hold_pins(root=REPO):
    """What this file holds of the BENCHMARK.json under `root`: each
    metric is an entry that reads the program and is reported in the
    cells it was written for."""
    bench = bench_pins.read(root)
    for name in PROGRAM_METRICS:
        entry = bench_pins.entry(bench["per_layer"], name)
        assert entry["source"] in ("program_span", "program_counter")
        assert "workloads" in entry
        bench_pins.listed_for(entry, [MEDIUM] if name in MEDIUM_NEW
                              else [SMALL])


@pytest.mark.parametrize("name", PROGRAM_METRICS)
def test_metric_file_is_data_for_a_reader_that_exists(name):
    hold_pins()
    entry = bench_pins.entry(BENCHMARK["per_layer"], name)
    with open(os.path.join(BENCH, "layer_metrics", name + ".json")) as f:
        spec = json.load(f)
    assert spec["kind"] in layers.READERS
    assert spec["kind"] == "stats_delta"
    params = spec["params"]
    # "requests" would have the reader call float() on a list
    assert params["denominator"] == "cycles" or \
        isinstance(params["denominator"], list)
    for key in params["numerator"] + (
            params["denominator"]
            if isinstance(params["denominator"], list) else []):
        # only the sections run.py's flat_stats keeps
        assert key.split("/", 1)[0] in ("device", "delta", "lane", "ppr")
    if entry["source"] == "program_span":
        assert all(k.startswith("device/span.") for k in params["numerator"])


def test_a_program_without_the_spans_reports_nothing_and_does_not_raise():
    """The parent commit has no span.* keys: a metric over a span's own
    count is left out, one over cycles reads 0; neither raises."""
    ctx = {"stats_before": {"device/jit.compile_total": 3.0},
           "stats_after": {"device/jit.compile_total": 9.0}, "cycles": 6}
    for name in PROGRAM_METRICS:
        with open(os.path.join(BENCH, "layer_metrics",
                               name + ".json")) as f:
            spec = json.load(f)
        got = layers.read(spec, ctx)
        assert got is None or got == 0.0, (name, got)


# --------------------------------------------------------------------------
# the analytics rehearsal: every new medium metric, and how they add up
# --------------------------------------------------------------------------

def test_call_phases_on_the_analytics_rehearsal(tmp_path, monkeypatch):
    monkeypatch.setenv("MEMGRAPH_TPU_FORCE_MXU", "1")
    monkeypatch.setenv("MEMGRAPH_TPU_MXU_MIN_EDGES", "1000")
    # a CPU cycle at the forced MXU route is ~3 s: every CALL after a
    # write compiles its delta net, as the chip does on a cold cache
    result = drive(small_cell(MEDIUM), tmp_path, seconds=4.0)
    assert result["correct"] is True, result["compared"]
    assert result["cycles"] >= 2
    got = values(result)
    assert set(MEDIUM_NEW) <= set(got), sorted(set(MEDIUM_NEW) - set(got))
    assert got["call_device_wait_ms"] > 0
    assert got["call_export_ms"] > 0 and got["call_rows_ms"] > 0
    assert got["call_launch_ms"] > 0 and got["call_delta_plan_ms"] > 0
    # the five phases are disjoint children of the CALL's exchange
    assert sum(got[name] for name in CALL_PHASES) <= got["call_server_ms"]
    # nothing served the window's CALLs from a cache, and each was a
    # delta refresh of the warm-up's full plan
    assert got["delta_plan_share"] == 100.0
    assert got["iterations_per_call"] >= 1
    assert got["jit_backend_ms"] >= 0
    assert got["true_compiles_per_cycle"] <= got["compiles_per_cycle"]
    # the old metrics are reported as before, and the server's side of
    # the cycle's two exchanges lies inside the client's
    assert got["call_server_ms"] <= \
        got["rank_call_p50_ms"] + got["burst_write_p50_ms"] \
        or result["cycles"] > 2     # medians, so only roughly


def test_iterations_counter_is_what_the_kernel_returned():
    """device.fixpoint_iterations_total moves by exactly the `iters` of
    every in-process fixpoint, on both routes."""
    import numpy as np

    from memgraph_tpu.observability.metrics import global_metrics
    from memgraph_tpu.ops import csr
    from memgraph_tpu.ops import pagerank as pr

    def total():
        return dict((n, v) for n, _k, v in global_metrics.snapshot()).get(
            "device.fixpoint_iterations_total", 0.0)

    rng = np.random.default_rng(5)
    graph = csr.from_coo(rng.integers(0, 300, 4000),
                         rng.integers(0, 300, 4000), n_nodes=300)
    before = total()
    _, _, iters = pr.pagerank(graph, tol=1e-6)
    assert iters >= 2 and total() - before == iters
    before = total()
    _, _, iters_mxu = pr._pagerank_via_mxu(graph, 0.85, 100, 1e-6)
    assert iters_mxu >= 2 and total() - before == iters_mxu


# --------------------------------------------------------------------------
# the OLTP rehearsal: the lane's phases, the queue, the commit
# --------------------------------------------------------------------------

def test_lane_phases_on_the_oltp_rehearsal(tmp_path, monkeypatch):
    # 2,000 rows lie under the lane's floor: lower it, as the small
    # cell's 10,000 rows lie above it
    monkeypatch.setenv("MEMGRAPH_TPU_LANE_MIN_ROWS", "64")
    result = drive(small_cell(SMALL), tmp_path, seconds=3.0)
    assert result["correct"] is True, result["compared"]
    got = values(result)
    assert set(SMALL_NEW) <= set(got), sorted(set(SMALL_NEW) - set(got))
    assert all(got[name] >= 0 for name in SMALL_NEW)
    assert got["lane_server_ms"] > 0 and got["lane_iterate_ms"] > 0
    # disjoint children of the attempt
    assert sum(got[name] for name in LANE_PHASES) <= got["lane_server_ms"]
    assert got["mvcc_commit_ms"] > 0 and got["bolt_wait_ms"] > 0
    # a commit is a part of a write; the client's clock holds it
    assert got["mvcc_commit_ms"] < got["write_p50_ms"]
    assert "lane_hit_share" in got and "lane_query_p50_ms" in got


# --------------------------------------------------------------------------
# gap_spans.py: arithmetic on a hand-made plane dict
# --------------------------------------------------------------------------

def test_gap_spans_by_hand():
    s = 1e9
    planes = {
        "/device:TPU:0": [["a", 0.0, 1 * s], ["b", 5 * s, 1 * s],
                          ["c", 6.5 * s, 0.5 * s]],
    }
    host = [  # [name, start ns, duration ns]
        ["mgtrace:analytics.export", 0.5 * s, 2.5 * s],     # 1.0..3.0 in gap
        ["mgtrace:analytics.launch", 3.0 * s, 3.0 * s],     # 3.0..5.0 in gap
        ["mgtrace:analytics.edge_diff", 3.2 * s, 0.5 * s],  # inside launch
        ["mgtrace:lane.query", 20 * s, 1 * s],              # after all ops
    ]
    rows = gap_spans.attribute(planes, host, top=10)
    assert [r["plane"] for r in rows] == ["/device:TPU:0"] * 2
    first, second = rows
    assert first["start_ns"] == 1 * s and first["seconds"] == 4.0
    # innermost (shortest event) first; overlap clipped to the gap
    assert first["spans"] == [
        {"name": "analytics.edge_diff", "overlap_s": 0.5},
        {"name": "analytics.export", "overlap_s": 2.0},
        {"name": "analytics.launch", "overlap_s": 2.0}]
    assert first["covered_s"] == 4.0        # the union, not the sum
    # launch ends where the second gap begins: nothing lies under it
    assert second["seconds"] == 0.5 and second["spans"] == []
    assert second["covered_s"] == 0.0


def test_gap_spans_reads_an_xplane(tmp_path):
    """Stage one on a real profile: the host events named mgtrace:*."""
    import jax
    import jax.numpy as jnp

    from memgraph_tpu.observability import trace as mgtrace
    jax.profiler.start_trace(str(tmp_path))
    try:
        with mgtrace.span("analytics.launch"):
            jnp.ones(1024).sum().block_until_ready()
    finally:
        jax.profiler.stop_trace()
    found = gap_spans.find_xplane(str(tmp_path))
    assert found is not None
    planes, host = gap_spans.extract(found)
    assert any(name == "mgtrace:analytics.launch" for name, _, _ in host)
    assert all(name.startswith("mgtrace:") for name, _, _ in host)
