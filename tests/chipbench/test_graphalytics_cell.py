"""The Graph500 cell (``graph500_s17.graphalytics_fresh``) rehearsed
without the chip: the deployment at scale 10 on the CPU, one client, the
device assertion injected. One traced run serves every test that reads a
result; the lost_write control comes out ``correct: false``. The
benchmark's copy of the reference (``semantics/graphalytics.py``) and its
data set (``datasets/graph500_kron.py``) are held to the plain reference
``tests/graphalytics_reference.py``.
"""

import json
import os
import sys
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmarks", "chipbench")
for _p in (BENCH, os.path.join(REPO, "tests")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import bench_pins  # noqa: E402
import graphalytics_reference as ref  # noqa: E402
import layers  # noqa: E402
import run  # noqa: E402
import seams  # noqa: E402
import traffic  # noqa: E402

CELL = "graph500_s17.graphalytics_fresh"
CONFIG = "graph500_s17_inproc"
SCALE = 10
SEED = 2_147_483_707            # run seeds pass 2**31

with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    BENCHMARK = json.load(_f)
TRACE_METRICS = (["device_idle_pct.graphalytics"]
                 + [f"{a}_device_ms" for a in ("bfs", "sssp", "wcc", "cdlp")]
                 + [f"{a}_roofline" for a in ("bfs", "sssp", "wcc", "cdlp")])
PROGRAM_METRICS = ([f"{a}_call_p50_ms" for a in ("bfs", "sssp", "wcc",
                                                  "cdlp")]
                   + ["sssp_iterations_per_call", "wcc_iterations_per_call"])
#: the cell's own per-layer metrics, in the order they were appended
NEW_METRICS = TRACE_METRICS + PROGRAM_METRICS
#: accepted metrics whose readers the cell's counters and spans move
SHARED_METRICS = ["call_rows_ms", "call_export_ms", "export_delta_share",
                  "true_compiles_per_cycle", "pygc_ms_per_cycle"]

sem = seams.load_module(None, "semantics", "graphalytics")
dataset = seams.load_module(None, "datasets", "graph500_kron")


def small_cell():
    cell = run.load_cell(CELL)
    cell["config"] = dict(cell["config"], scale=SCALE)
    return cell


def seen(device, chips):
    """Stands in for require_tpu on a host with no chip."""
    seen.calls.append((device, chips))


seen.calls = []


def drive(cell, tmp_path, seconds=2.0, trace=False, **kw):
    return run.run_cell(cell, SEED, seconds, trace, str(tmp_path),
                        device_check=seen, t_start=time.perf_counter(), **kw)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    return drive(small_cell(), tmp_path_factory.mktemp("graph500"),
                 trace=True)


# --------------------------------------------------------------------------
# the cell end to end
# --------------------------------------------------------------------------

def test_the_cell_traced_is_correct(traced):
    assert traced["correct"] is True, traced["compared"]
    assert traced["cycles"] >= 2 and traced["failed"] == 0
    compared = traced["compared"]
    assert compared["exact_mismatches"] == {"value": 0, "limit": 0,
                                            "ok": True}
    assert compared["exact_reads_compared"]["value"] == 4 * traced["cycles"]
    assert compared["readback_mismatches"]["value"] == 0
    assert traced["device"]["busy_s"] > 0


def test_the_cell_traced_reports_its_program_metrics(traced):
    """Every metric the program feeds reads a number on the CPU; the
    device's are left out of the line there, never read as 0."""
    metrics = traced["metrics"]
    for name in PROGRAM_METRICS + SHARED_METRICS:
        assert metrics[name]["value"] >= 0, name
    assert metrics["sssp_iterations_per_call"]["value"] >= 2
    # warm after every adds-only burst: far fewer than a cold start's
    assert 1 <= metrics["wcc_iterations_per_call"]["value"] <= 4
    assert metrics["export_delta_share"]["value"] == 100.0
    assert not any(name in metrics for name in TRACE_METRICS)


def test_the_lost_write_control_is_not_correct(tmp_path):
    result = drive(small_cell(), tmp_path, control="lost_write")
    assert result["correct"] is False
    assert result["compared"]["readback_mismatches"]["value"] > 0
    assert result["compared"]["exact_mismatches"]["value"] > 0


# --------------------------------------------------------------------------
# the benchmark's reference and data set against the plain reference
# --------------------------------------------------------------------------

def histogram(groups):
    sizes = np.bincount(np.unique(groups, return_inverse=True)[1])
    size, count = np.unique(sizes, return_counts=True)
    return [[int(s), int(c)] for s, c in zip(size[::-1], count[::-1])]


@pytest.mark.parametrize("scale,seed", [(9, 5), (10, 7)])
def test_the_semantics_equal_the_plain_reference(scale, seed):
    config = dict(run.load_cell(CELL)["config"], scale=scale,
                  graph_seed=seed)
    state = dataset.make(config)
    n, src, dst, weights, _ = ref.kronecker(scale, 16, seed)
    assert state.n_loaded == n and np.array_equal(state.src, src)
    assert np.array_equal(state.dst, dst)
    assert np.array_equal(state.weights, weights)
    mix = run.load_cell(CELL)["mix"]
    plan = traffic.Plan(mix, dataset.key_space(config), seed, 0,
                        traffic.Keys(mix["keys"], n, seed), dataset)
    for _ in range(2):
        req = plan.request("burst_write")
        sem.apply("add_weighted_edges", state, req.params)
        s, d, w = state.edge_arrays()
        for root in (int(s[0]), plan.request("bfs_levels").params["root"]):
            levels = ref.bfs_levels(n, s, d, root)
            assert sem.answer("bfs_level_counts", state, {"root": root}) \
                == [[lv, int(c)] for lv, c in
                    enumerate(np.bincount(levels[levels >= 0])) if c]
            dist = ref.sssp(n, s, d, w, root)
            reached = dist[np.isfinite(dist)]
            assert sem.answer("sssp_summary", state, {"root": root}) == \
                [[len(reached), float(reached.sum()), float(reached.max())]]
        assert sem.answer("wcc_sizes", state, {}) == \
            histogram(ref.wcc(n, s, d))
        assert sem.answer("cdlp_sizes", state, {}) == \
            histogram(ref.cdlp(n, s, d, sem.ROUNDS))


def test_a_burst_keeps_the_graph_simple():
    config = dict(run.load_cell(CELL)["config"], scale=9, graph_seed=3)
    state = dataset.make(config)
    mix = run.load_cell(CELL)["mix"]
    plan = traffic.Plan(mix, dataset.key_space(config), SEED, 0, None,
                        dataset)
    have = set(zip(state.src.tolist(), state.dst.tolist()))
    for _ in range(20):
        edges = plan.request("burst_write").params["edges"]
        assert len(edges) == 64
        for a, b, w in edges:
            assert 0 <= a < b < state.n_loaded and (a, b) not in have
            assert w * 1024 == int(w * 1024) and 0 <= w < 1
            have.add((a, b))
    # the same seed draws the same bursts
    again = traffic.Plan(mix, state.n_loaded, SEED, 0, None, dataset)
    first = traffic.Plan(mix, state.n_loaded, SEED, 0, None, dataset)
    assert again.request("burst_write").params == \
        first.request("burst_write").params


def test_the_published_configuration_is_what_the_generator_gives():
    config = run.load_cell(CELL)["config"]
    state = dataset.make(config)
    assert (state.n_loaded, len(state.src)) == (config["nodes"],
                                                 config["edges"])


# --------------------------------------------------------------------------
# rooflines and metric files
# --------------------------------------------------------------------------

def test_the_rooflines_count_the_least_bytes():
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peak = json.load(f)["TPU v5 lite"]
    sweep = seams.load_module(None, "rooflines", "minplus_sweep")
    weighted = seams.load_module(None, "rooflines", "minplus_sweep_weighted")
    labelprop = seams.load_module(None, "rooflines", "labelprop_sort")
    n, e = 90_162, 1_864_185
    assert sweep.per_iteration(n, e)["bytes"] == 24 * e + 8 * n
    assert sweep.per_iteration(n, e, weighted=True)["bytes"] == \
        32 * e + 8 * n
    assert labelprop.per_round(n, e)["bytes"] == 72 * e + 8 * n
    for module, per in ((sweep, 24), (weighted, 32), (labelprop, 72)):
        least = module.least_seconds(n, e, 10, peak)
        assert least["bound"] == "hbm"
        assert least["seconds"] == pytest.approx(
            10 * (per * e + 8 * n) / peak["hbm_bytes_per_s"])


@pytest.mark.parametrize("name", NEW_METRICS)
def test_metric_file_is_data_for_a_reader_that_exists(name):
    with open(os.path.join(BENCH, "layer_metrics", name + ".json")) as f:
        spec = json.load(f)
    entry = bench_pins.entry(BENCHMARK["per_layer"], name)
    assert spec["kind"] in layers.READERS and spec["what"]
    assert spec["kind"].startswith("trace_") == \
        (entry["source"] == "device_trace")
    assert entry["moves"] == "fresh_cycle_s"
    if spec["kind"] == "trace_roofline":
        seams.load_module(None, "rooflines", spec["params"]["roofline"])
        assert entry["unit"] == "%" and entry["layer"] == "kernels"


def test_a_program_without_the_counters_reports_nothing():
    """The parent commit counts no Graphalytics call: the per-call
    iterations are left out of the line, not read as 0."""
    ctx = {"stats_before": {"device/device.fixpoint_iterations_total": 3.0},
           "stats_after": {"device/device.fixpoint_iterations_total": 9.0},
           "cycles": 6}
    for name in ("sssp_iterations_per_call", "wcc_iterations_per_call"):
        with open(os.path.join(BENCH, "layer_metrics",
                               name + ".json")) as f:
            spec = json.load(f)
        assert layers.read(spec, ctx) is None


def hold_pins(root=REPO):
    """What this file holds of the BENCHMARK.json under `root`: the
    deployment, the cell and its metrics are entries in the order they
    were appended; the cell reports them, the end-to-end metrics it has
    and the accepted metrics its procedures move."""
    bench = bench_pins.read(root)
    bench_pins.stand_in_order(bench["configs"], [CONFIG])
    bench_pins.stand_in_order(bench["workloads"], [CELL])
    bench_pins.stand_in_order(bench["per_layer"], NEW_METRICS)
    for name in NEW_METRICS + SHARED_METRICS:
        bench_pins.listed_for(bench_pins.entry(bench["per_layer"], name),
                              [CELL])
    for name in ("fresh_cycle_s", "setup_s"):
        bench_pins.listed_for(bench_pins.entry(bench["end_to_end"], name),
                              [CELL])
    bench_pins.stand_in_order(run.load_cell(CELL, root)["per_layer"],
                              NEW_METRICS)


def test_the_new_entries_stand_in_order_and_name_files():
    hold_pins()
    cell_entry = bench_pins.entry(BENCHMARK["workloads"], CELL)
    assert cell_entry["chips"] == 1 and len(cell_entry["why"]) <= 200
    cell = run.load_cell(CELL)
    layout, data, semantics = run.seams_of(cell)
    assert layout.__file__.endswith("owners/inproc_server.py")
    assert data.__file__.endswith("datasets/graph500_kron.py")
    assert semantics.__file__.endswith("semantics/graphalytics.py")
    assert set(semantics.MODES.values()) == {"write", "exact_in_order"}
    assert cell["limits"] == {}
