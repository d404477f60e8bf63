"""A later change adds a configuration, a cell or a metric to the
benchmark by appending entries to ``BENCHMARK.json`` and adding files.
Every accepted test's pins of that file (``bench_pins.py``, as each test
file's ``hold_pins`` calls it) still hold on a copy with strangers
appended, and still fail on a copy with two of today's entries swapped
or one of today's cells dropped from a metric.
"""

import copy
import importlib
import json
import os
import re
import shutil
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmarks", "chipbench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import bench_pins  # noqa: E402
import run  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
TEST_FILES = sorted(f[:-3] for f in os.listdir(HERE)
                    if f.startswith("test_") and f.endswith(".py"))


def source(name):
    with open(os.path.join(HERE, name + ".py")) as f:
        return f.read()


#: the test files that pin BENCHMARK.json: every one that defines a
#: hold_pins, found, not listed, so that a new one is guarded as it comes
PINNING = [name for name in TEST_FILES
           if re.search(r"^def hold_pins\(", source(name), re.M)]
#: reads the repo's BENCHMARK.json only to hold it to the contract's
#: letter, which every appended entry meets too
CONTRACT_ONLY = {"test_chipbench"}

MEDIUM = "pokec_medium.analytics_fresh"
DAEMON = "pokec_medium_daemon.analytics_fresh"
SMALL_OLTP = "pokec_small.oltp_mixed"
RETRIEVAL = "graphrag_medium.retrieve_fresh"
PPR = "pokec_medium_ppr_serve.ppr_sets"

STRANGER = "stranger_inproc"
STRANGER_CELLS = [STRANGER + ".stranger_mix", STRANGER + ".oltp_mixed",
                  STRANGER + ".analytics_fresh"]
STRANGER_METRIC = "stranger_share"
STRANGER_E2E = "stranger_per_s"


def today():
    return bench_pins.read(REPO)


def with_strangers(bench):
    """`bench` with a configuration, a cell of a new traffic name, a
    second ``oltp_mixed`` and a third ``analytics_fresh`` cell, an
    end-to-end and a per-layer metric appended. The three cells are
    appended to every metric that lists cells, each of today's cells to
    every end-to-end metric that does not list it yet, and the two new
    metrics list every cell."""
    bench = copy.deepcopy(bench)
    today_cells = [w["name"] for w in bench["workloads"]]
    bench["configs"].append({
        "name": STRANGER, "source": "a deployment yet to come",
        "file": f"benchmarks/chipbench/configs/{STRANGER}.json",
        "reduced": [], "why": "appended"})
    for cell in STRANGER_CELLS:
        bench["workloads"].append({
            "name": cell, "config": STRANGER,
            "traffic": cell.split(".", 1)[1], "chips": 1,
            "why": "appended"})
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in metric:
            metric["workloads"].extend(STRANGER_CELLS)
    for metric in bench["end_to_end"]:
        if "workloads" in metric:
            metric["workloads"].extend(
                c for c in today_cells if c not in metric["workloads"])
    every = [w["name"] for w in bench["workloads"]]
    bench["end_to_end"].append({
        "name": STRANGER_E2E, "unit": "1/s", "better": "higher",
        "bound": 0.1, "source": "host_clock", "workloads": every})
    bench["per_layer"].append({
        "name": STRANGER_METRIC, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "a layer yet to come",
        "moves": "setup_s", "workloads": every})
    return bench


def checkout(tmp_path, bench):
    """A root that holds `bench` as its BENCHMARK.json, every
    configuration's file and the stranger's files. Every other data file
    is found in the benchmark's own directory, as ``run.load_cell``
    looks there second."""
    root = tmp_path / "root"
    own = root / "benchmarks" / "chipbench"
    shutil.copytree(os.path.join(BENCH, "configs"), own / "configs")
    with open(own / "configs" / "pokec_small_inproc.json") as f:
        config = dict(json.load(f), name=STRANGER, source="appended")
    files = {
        f"configs/{STRANGER}.json": config,
        f"layer_metrics/{STRANGER_METRIC}.json": {
            "kind": "stats_delta", "what": "appended",
            "params": {"numerator": ["device/stranger_total"],
                       "denominator": "cycles"}},
        "traffic/stranger_mix.json": dict(
            run.load_cell(SMALL_OLTP)["mix"], name="stranger_mix"),
    }
    for rel, data in files.items():
        os.makedirs(own / os.path.dirname(rel), exist_ok=True)
        with open(own / rel, "w") as f:
            json.dump(data, f)
    with open(root / "BENCHMARK.json", "w") as f:
        json.dump(bench, f, indent=1)
    return str(root)


def pinning(name):
    return importlib.import_module(name)


# --------------------------------------------------------------------------
# the pins hold on today's file and on a copy with strangers appended
# --------------------------------------------------------------------------

def test_the_strangers_are_appended_and_load():
    bench = with_strangers(today())
    assert [c["name"] for c in bench["configs"]][-1] == STRANGER
    assert len([w for w in bench["workloads"]
                if w["traffic"] == "oltp_mixed"]) >= 2
    assert len([w for w in bench["workloads"]
                if w["traffic"] == "analytics_fresh"]) >= 3
    assert all(STRANGER_CELLS[0] in m["workloads"]
               for m in bench["end_to_end"] + bench["per_layer"]
               if "workloads" in m)
    # every cell is listed under every end-to-end metric
    for metric in bench["end_to_end"]:
        bench_pins.listed_for(metric, [w["name"] for w in bench["workloads"]])


def test_every_file_that_pins_the_benchmark_is_guarded():
    """A test file that reads the repo's BENCHMARK.json gathers its pins
    in a hold_pins, which the tests below find and run; the one that
    only holds the file to the contract's letter is named."""
    readers = [name for name in TEST_FILES
               if 'join(REPO, "BENCHMARK.json")' in source(name)
               or "bench_pins.read(" in source(name)]
    assert set(PINNING) <= set(readers)
    assert set(readers) - set(PINNING) - {"test_appendable"} \
        == CONTRACT_ONLY
    for name in PINNING:
        assert callable(pinning(name).hold_pins), name


@pytest.mark.parametrize("name", PINNING)
def test_the_pins_hold_on_todays_file(name):
    pinning(name).hold_pins(REPO)


@pytest.mark.parametrize("name", PINNING)
def test_the_pins_hold_with_strangers_appended(name, tmp_path):
    root = checkout(tmp_path, with_strangers(today()))
    cell = run.load_cell(PPR, root)
    assert STRANGER_METRIC in [m["name"] for m in cell["per_layer"]]
    cell = run.load_cell(RETRIEVAL, root)
    assert STRANGER_E2E in [m["name"] for m in cell["end_to_end"]]
    pinning(name).hold_pins(root)


# --------------------------------------------------------------------------
# and still catch what they are there for
# --------------------------------------------------------------------------

def swapped(bench, listing, a, b):
    bench = copy.deepcopy(bench)
    entries = bench[listing]
    at = [i for i, e in enumerate(entries) if e["name"] in (a, b)]
    assert len(at) == 2
    entries[at[0]], entries[at[1]] = entries[at[1]], entries[at[0]]
    return bench


def dropped(bench, listing, metric, cell):
    bench = copy.deepcopy(bench)
    entry = bench_pins.entry(bench[listing], metric)
    entry["workloads"].remove(cell)
    return bench


@pytest.mark.parametrize("name,listing,a,b", [
    ("test_ppr_serve_cell", "per_layer", "ppr_batch_ms", "ppr_queue_ms"),
    ("test_runtime_phase_metrics", "per_layer", "bolt_prepare_ms",
     "bolt_pull_ms"),
    ("test_runtime_phase_metrics", "per_layer", "pygc_ms_per_cycle",
     "pygc_full_ms_per_cycle"),
    ("test_topk_share", "workloads", MEDIUM, DAEMON),
    ("test_graphrag_cell", "per_layer", "knn_device_ms", "knn_roofline"),
], ids=["ppr_metrics", "phase_metrics", "gc_metrics", "analytics_cells",
        "retrieval_metrics"])
def test_two_entries_swapped_fail_the_order_pins(name, listing, a, b,
                                                 tmp_path):
    root = checkout(tmp_path, swapped(with_strangers(today()), listing, a, b))
    with pytest.raises(AssertionError, match="does not stand after"):
        pinning(name).hold_pins(root)


@pytest.mark.parametrize("name,listing,metric,cell", [
    ("test_program_reuse", "per_layer", "program_reuse_share", MEDIUM),
    ("test_ppr_serve_cell", "per_layer", "ppr_reply_ms", PPR),
    ("test_ppr_serve_cell", "end_to_end", "oltp_queries_per_s", PPR),
    ("test_program_spans", "per_layer", "call_rows_ms", MEDIUM),
    ("test_program_spans", "per_layer", "lane_stage_ms", SMALL_OLTP),
    ("test_runtime_phase_metrics", "per_layer", "pygc_ms_per_cycle", DAEMON),
    ("test_runtime_phase_metrics", "per_layer", "bolt_pull_ms", SMALL_OLTP),
    ("test_snapshot_delta_share", "per_layer", "snapshot_delta_share",
     SMALL_OLTP),
    ("test_topk_share", "per_layer", "topk_share", DAEMON),
    ("test_graphrag_cell", "per_layer", "knn_roofline", RETRIEVAL),
    ("test_graphrag_cell", "end_to_end", "fresh_cycle_s", RETRIEVAL),
    ("test_export_delta_share", "per_layer", "export_delta_share",
     RETRIEVAL),
])
def test_a_cell_dropped_fails_the_membership_pins(name, listing, metric,
                                                  cell, tmp_path):
    root = checkout(tmp_path,
                    dropped(with_strangers(today()), listing, metric, cell))
    with pytest.raises(AssertionError, match="does not list"):
        pinning(name).hold_pins(root)


# --------------------------------------------------------------------------
# the helper's own rules, by hand
# --------------------------------------------------------------------------

def test_stand_in_order_allows_others_before_between_and_after():
    listing = [{"name": n} for n in ("x", "a", "y", "b", "c", "z")]
    bench_pins.stand_in_order(listing, ["a", "b", "c"])
    bench_pins.stand_in_order(["x", "a", "y", "b"], ["a", "b"])
    for wrong in (["b", "a"], ["a", "q"], ["a", "a"]):
        with pytest.raises(AssertionError):
            bench_pins.stand_in_order(listing, wrong)


def test_listed_for_and_entry_except_workloads():
    metric = {"name": "m", "unit": "%", "workloads": ["c1", "c2", "c3"]}
    bench_pins.listed_for(metric, ["c3", "c1"])
    bench_pins.listed_for({"name": "setup_s"}, ["any"])    # every cell's
    with pytest.raises(AssertionError):
        bench_pins.listed_for(metric, ["c4"])
    bench_pins.entry_except_workloads(
        metric, {"name": "m", "unit": "%", "workloads": ["c2"]})
    for wrong in ({"name": "m", "unit": "ms", "workloads": ["c2"]},
                  {"name": "m", "unit": "%", "workloads": ["c4"]},
                  {"name": "m", "unit": "%"}):
        with pytest.raises(AssertionError):
            bench_pins.entry_except_workloads(metric, wrong)
    with pytest.raises(AssertionError):
        bench_pins.entry([metric, dict(metric)], "m")
