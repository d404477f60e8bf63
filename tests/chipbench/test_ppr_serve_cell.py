"""The PPR-serving cell (``pokec_medium_ppr_serve.ppr_sets``) rehearsed
without the chip: both processes of the daemon layout on the CPU at
2k/20k, four clients, the device assertion injected. One traced run
serves every test that reads a result; the planted faults are applied to
copies of that run's own window and judged by ``run.compare`` and
``run.judge``, the code that decides ``correct``.
"""

import copy
import json
import os
import sys
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
import seams  # noqa: E402
import traffic  # noqa: E402

CELL = "pokec_medium_ppr_serve.ppr_sets"
CONFIG = "pokec_medium_ppr_serve"
N_NODES, N_EDGES, CATALOGUE, CLIENTS = 2_000, 20_000, 48, 4
SEED = 2_147_483_693            # the driver's seeds pass 2**31

with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    BENCHMARK = json.load(_f)
PROGRAM_METRICS = ["ppr_batch_ms", "ppr_queue_ms", "ppr_reply_ms",
                   "ppr_riders_per_batch", "ppr_cache_hit_share",
                   "route_request_ms.ppr", "bolt_wait_ms.ppr",
                   "true_compiles.ppr", "ppr_query_p50_ms",
                   "daemon_routed_share.ppr"]
TRACE_METRICS = ["device_idle_pct.ppr", "ppr_batch_device_ms",
                 "ppr_topk_device_ms", "ppr_batch_roofline"]
#: the cell's own per-layer metrics, in the order they were appended
NEW_METRICS = TRACE_METRICS + PROGRAM_METRICS

sem = seams.load_module(None, "semantics", "ppr_sets")
dataset = seams.load_module(None, "datasets", "pokec_catalogue")
roofline = seams.load_module(None, "rooflines", "ppr_spmm")


def small_cell():
    cell = run.load_cell(CELL)
    cell["config"] = dict(cell["config"], nodes=N_NODES, edges=N_EDGES,
                          catalogue=CATALOGUE)
    cell["mix"] = dict(cell["mix"], clients=CLIENTS)
    return cell


class Recording:
    """A transport that keeps what it carried."""

    def __init__(self, inner, log):
        self.inner, self.log, self.client = inner, log, inner.client

    def run(self, req):
        self.log.append(self.inner.run(req))
        return self.log[-1]


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """(cell, result, the window's requests by client) of one traced
    run through the daemon layout."""
    cell, logs = small_cell(), []

    def record(transport):
        logs.append([])
        return Recording(transport, logs[-1])

    try:
        result = run.run_cell(
            cell, SEED, 3.0, True, str(tmp_path_factory.mktemp("ppr_cell")),
            device_check=lambda device, chips: None,
            transport_hook=record, t_start=time.perf_counter())
    finally:
        leaked = list(run._CHILDREN)
        run.stop_all()
    assert not leaked, f"the run left {len(leaked)} process(es) running"
    return cell, result, logs


def values(result):
    return {name: m["value"] for name, m in result["metrics"].items()}


# --------------------------------------------------------------------------
# the cell end to end
# --------------------------------------------------------------------------

def test_the_cell_end_to_end_through_the_daemon(served):
    cell, result, logs = served
    assert result["correct"] is True, result["compared"]
    assert result["failed"] == 0 and result["attempted"] >= 2 * CLIENTS
    assert len(logs) == CLIENTS and all(len(log) >= 2 for log in logs)
    compared = result["compared"]
    # every request of every client is held to its own set's answer
    assert compared["rank_calls_compared"]["value"] == result["attempted"]
    assert compared["row_faults"] == {"value": 0, "limit": 0, "ok": True}
    assert compared["rank_dev_max"]["ok"] is True
    assert compared["rank_dev_max"]["value"] < \
        compared["rank_dev_max"]["limit"] / 2
    assert compared["top_gap_max"]["value"] < 1e-6
    assert list(result)[-1] == "compared"
    # the traffic is sets, and nearly all of them differ
    sets = [tuple(r.params["ids"]) for log in logs for r in log]
    assert all(len(set(ids)) == 4 for ids in sets)
    assert len(set(sets)) > len(sets) // 2


def test_the_traced_run_reports_the_planes_metrics(served):
    _cell, result, _logs = served
    got = values(result)
    assert set(PROGRAM_METRICS) <= set(got), \
        sorted(set(NEW_METRICS) - set(got))
    assert set(PROGRAM_METRICS + TRACE_METRICS) == set(NEW_METRICS)
    # the CPU's ops stand in for a device's in the arithmetic and are
    # never reported as a device's time or share
    assert not set(TRACE_METRICS) & set(got)
    assert "oltp_queries_per_s" not in got
    # every request went over the socket and was answered by the plane
    assert got["daemon_routed_share.ppr"] == 100.0
    assert 1.0 <= got["ppr_riders_per_batch"] <= CLIENTS
    assert 0.0 <= got["ppr_cache_hit_share"] < 50.0
    # a request's round trip holds its wait for the batch and the batch
    assert got["route_request_ms.ppr"] >= got["ppr_queue_ms"] > 0
    assert got["ppr_query_p50_ms"] >= got["ppr_batch_ms"] > 0
    assert got["ppr_reply_ms"] > 0 and got["bolt_wait_ms.ppr"] > 0
    assert result["device"]["busy_s"] > 0


def test_the_untraced_line_is_the_end_to_end_metrics():
    cell = run.load_cell(CELL)
    names = [m["name"] for m in cell["end_to_end"]]
    assert "setup_s" in names and "oltp_queries_per_s" in names
    assert set(names) <= {"setup_s", "oltp_queries_per_s",
                          "oltp_query_p95_ms"}


# --------------------------------------------------------------------------
# planted faults, each on a copy of the served window
# --------------------------------------------------------------------------

def swap_riders(outs):
    """Two riders of one round, answered with each other's rows."""
    a, b = outs[0][-1], outs[1][-1]
    assert sorted(a.params["ids"]) != sorted(b.params["ids"])
    a.rows, b.rows = b.rows, a.rows


def alter_rank(outs):
    row = outs[2][-1].rows[7]
    row[1] *= 1.0 + 2e-3            # the order of the rows still holds


def swap_id(outs):
    rows = outs[3][-1].rows
    held = {r[0] for r in rows}
    rows[19][0] = next(i for i in range(N_NODES) if i not in held)


def drop_row(outs):
    outs[1][0].rows.pop()


def judged(cell, outs):
    mix = cell["mix"]
    state = dataset.make(cell["config"])
    numbers = run.compare(mix, state, state.copy(), outs,
                          {"readback": {}, "quiesced": []}, sem)
    rows, correct = run.judge(numbers, mix, cell["limits"])
    return numbers, {name: ok for name, _v, _l, ok in rows}, correct


def test_the_served_window_is_judged_correct_again(served):
    cell, _result, logs = served
    _numbers, ok, correct = judged(cell, copy.deepcopy(logs))
    assert correct is True and ok == {"rank_dev_max": True,
                                      "row_faults": True}


@pytest.mark.parametrize("spoil,fails", [
    (swap_riders, "rank_dev_max"), (alter_rank, "rank_dev_max"),
    (swap_id, "rank_dev_max"), (drop_row, "row_faults")],
    ids=["another_riders_rows", "altered_rank", "swapped_id", "19_rows"])
def test_a_planted_fault_is_not_correct(spoil, fails, served):
    cell, _result, logs = served
    outs = copy.deepcopy(logs)
    spoil(outs)
    numbers, ok, correct = judged(cell, outs)
    assert correct is False
    assert ok[fails] is False, numbers
    if fails == "row_faults":
        assert numbers["row_faults"] == 1


def test_the_reference_in_bfloat16_is_not_correct(served):
    """Every request of the served window answered by the reference's
    own solve with each contribution rounded to bfloat16: not correct
    by the cell's limit, with every row in place."""
    cell, _result, logs = served
    state = dataset.make(cell["config"])
    outs = copy.deepcopy(logs)
    import reference
    for req in (r for out in outs for r in out):
        low = sem.vector("ppr_set_top", state, req.params, precision="bf16")
        order, ranks = reference.top_ranks(low, 20)
        req.rows = [[int(i), float(r)] for i, r in zip(order, ranks)]
    numbers, ok, correct = judged(cell, outs)
    assert correct is False and ok["row_faults"] is True
    assert numbers["rank_dev_max"] > 10 * cell["limits"]["rank_dev_max"]


# --------------------------------------------------------------------------
# the data set, the mix, the roofline, the files
# --------------------------------------------------------------------------

def test_the_catalogue_is_the_key_space():
    config = dict(run.load_cell(CELL)["config"])
    assert config["dataset"] == "pokec_catalogue"
    small = dict(config, nodes=N_NODES, edges=N_EDGES, catalogue=CATALOGUE)
    assert dataset.key_space(small) == CATALOGUE
    members = dataset.current_catalogue()
    assert len(set(members.tolist())) == CATALOGUE
    assert (members == dataset.catalogue(small)).all()     # from the seed
    state = dataset.make(small)
    out = np.bincount(state.edge_arrays()[0], minlength=N_NODES)
    assert (out[members] > 0).all()
    # the graph, its loader and its sizes are the medium cells' own
    default = seams.load_module(None, "datasets", "pokec_synthetic")
    assert dataset.make is default.make and dataset.load is default.load
    assert dataset.sizes is default.sizes
    medium = run.load_cell("pokec_medium_daemon.analytics_fresh")["config"]
    for key in ("nodes", "edges", "graph_seed", "schema", "index", "load"):
        assert config[key] == medium[key], key
    assert config["catalogue"] == 192

    mix = run.load_cell(CELL)["mix"]
    keys = traffic.Keys(mix["keys"], CATALOGUE, SEED)
    plan = traffic.Plan(mix, CATALOGUE, SEED, 0, keys, dataset)
    drawn = [next(plan).params["ids"] for _ in range(300)]
    assert all(len(ids) == 4 == len(set(ids)) for ids in drawn)
    assert all(set(ids) <= set(members.tolist()) for ids in drawn)
    # Zipf over the slots: one member is in far more sets than 4/48
    counts = np.bincount(np.concatenate(drawn), minlength=N_NODES)
    assert counts.max() > 3 * 300 * 4 / CATALOGUE
    # a key space that is not the catalogue's is refused
    other = traffic.Plan(mix, N_NODES, SEED, 0,
                         traffic.Keys(mix["keys"], N_NODES, SEED), dataset)
    with pytest.raises(ValueError, match="catalogue"):
        next(other)


def test_the_mix_and_the_deployment_state_what_the_issue_fixed():
    cell = run.load_cell(CELL)
    mix, config = cell["mix"], cell["config"]
    assert (mix["loop"], mix["clients"], mix["schedule"]) == \
        ("closed", 12, "weighted")
    cls, = mix["classes"]
    assert (cls["kind"], cls["reference"], cls["top"]) == \
        ("read", "ppr_set_top", 20)
    assert "pagerank.personalized(sources, 100, 0.85, 20)" in cls["query"]
    assert "MATCH (s:User {id: i})" in cls["query"]
    assert cls["params"] == {"ids": {"gen": "source_set", "set_size": 4}}
    assert set(mix["compare"]) == {"rank_dev_max", "row_faults"}
    assert mix["readback"] == [] and "controls" in mix
    assert set(cell["limits"]) == {"rank_dev_max"}
    # the shipped defaults: no flag, no environment variable
    owner = config["owner"]
    assert owner["kind"] == "daemon_server"
    assert owner["server_flags"] == ["--storage-wal-enabled"]
    assert owner["env"] == owner["daemon_env"] == {}
    assert owner["daemon_flags"] == [] and "owner" not in config["reduced"]
    assert config["architecture"] is None
    assert config["precision"]["stated"] == "float32"
    assert config["precision"]["controls"] == {}
    assert config["precision"]["controls_why"]
    for key in ("signature", "catalogue", "set_size", "clients", "top"):
        assert config["assumed"][key], key
    assert "every rider of a batch gets its own answer" in \
        config["guarantees"]["answers"]
    assert "fails the run" in config["guarantees"]["routing"]
    assert set(config["reduced_why"]) >= {"nodes, edges", "schema", "device"}


def hold_pins(root=REPO):
    """What this file holds of the BENCHMARK.json under `root`: the
    deployment, the cell and the cell's metrics are entries in the order
    they were appended; the cell reports each of them and the
    end-to-end metrics it has."""
    bench = bench_pins.read(root)
    bench_pins.stand_in_order(bench["configs"], [CONFIG])
    bench_pins.stand_in_order(bench["workloads"], [CELL])
    bench_pins.stand_in_order(bench["per_layer"], NEW_METRICS)
    for name in NEW_METRICS:
        bench_pins.listed_for(bench_pins.entry(bench["per_layer"], name),
                              [CELL])
    for name in ("oltp_queries_per_s", "setup_s"):
        bench_pins.listed_for(bench_pins.entry(bench["end_to_end"], name),
                              [CELL])
    bench_pins.stand_in_order(run.load_cell(CELL, root)["per_layer"],
                              NEW_METRICS)


def test_the_new_entries_stand_in_order_and_name_files():
    hold_pins()
    entry = bench_pins.entry(BENCHMARK["configs"], CONFIG)
    assert len(entry["source"]) <= 200
    cell_entry = bench_pins.entry(BENCHMARK["workloads"], CELL)
    assert cell_entry["chips"] == 1 and len(cell_entry["why"]) <= 200
    for name in NEW_METRICS:
        m = bench_pins.entry(BENCHMARK["per_layer"], name)
        assert m["moves"] == "oltp_queries_per_s"
        with open(os.path.join(BENCH, "layer_metrics",
                               m["name"] + ".json")) as f:
            spec = json.load(f)
        assert spec["kind"] in layers.READERS and spec["what"]
        assert (spec["kind"].startswith("trace_")) == \
            (m["source"] == "device_trace")
    layers_named = {m["layer"] for m in BENCHMARK["per_layer"]
                    if m["name"] in NEW_METRICS}
    assert "PPR plane" in layers_named
    # every seam name against its file
    cell = run.load_cell(CELL)
    layout, data, semantics = run.seams_of(cell)
    assert layout.__file__.endswith("owners/daemon_server.py")
    assert data.__file__.endswith("datasets/pokec_catalogue.py")
    assert semantics.__file__.endswith("semantics/ppr_sets.py")
    assert semantics.MODES == {"ppr_set_top": "vector_top"}
    assert roofline.__file__.endswith("rooflines/ppr_spmm.py")


def test_the_roofline_counts_the_edge_stream_once_and_the_lanes_each():
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peak = json.load(f)["TPU v5 lite"]
    n, e = 100_000, 1_768_515
    assert roofline.per_iteration(n, e, 1) == {
        "bytes": 12 * e + 12 * n, "operations": 2 * e + 6 * n}
    assert roofline.per_iteration(n, e, 16) == {
        "bytes": 12 * e + 12 * n * 16, "operations": 16 * (2 * e + 6 * n)}
    least = roofline.least_seconds(n, e, 88, peak)
    assert least["bound"] == "hbm"
    assert least["seconds"] == pytest.approx(88 * (12 * e + 12 * n) / 819e9)
    # told the lanes, it counts them; untold, the fewest there can be
    assert roofline.least_seconds(n, e, 88, peak, lanes=16)["seconds"] == \
        pytest.approx(88 * (12 * e + 12 * n * 16) / 819e9)
    spec_path = os.path.join(BENCH, "layer_metrics",
                             "ppr_batch_roofline.json")
    with open(spec_path) as f:
        params = json.load(f)["params"]
    # the reader on a hand-made op table: 176 iterations in 1.2 s
    trace = {"device_planes": ["/device:TPU:0"],
             "modules": {"jit_ppr_batch(1)": {"count": 1, "seconds": 0.7},
                         "jit_ppr_batch(2)": {"count": 1, "seconds": 0.5},
                         "jit_ppr_topk(3)": {"count": 2, "seconds": 0.1}},
             "ops": {"%abs_reduce_fusion.2": {"count": 176, "seconds": 0.01},
                     "%fusion.26": {"count": 176, "seconds": 0.9}}}
    ctx = {"trace": trace, "n_nodes": n, "n_edges": e, "peak": peak,
           "dirs": None}
    share = layers.trace_roofline(params, ctx)
    assert share == pytest.approx(
        100.0 * 176 * (12 * e + 12 * n) / 819e9 / 1.2)
    assert 0 < share < 1
    # a program without the op or the program reports nothing
    assert layers.trace_roofline(params, dict(ctx, trace=dict(
        trace, modules={"jit_step(1)": {"count": 1, "seconds": 1.0}}))) is None
