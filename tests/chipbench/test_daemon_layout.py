"""The daemon layout (``owners/daemon_server.py``) rehearsed without the
chip: both processes of ``pokec_medium_daemon`` on the CPU at 2k/20k,
the device assertion injected, ``analytics_fresh`` through Bolt.

What the cell exists to show is shown to fail: a CALL that has not seen
its burst across the process boundary, and a run whose daemon is gone.
"""

import json
import os
import signal
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmarks", "chipbench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import reference  # noqa: E402
import run  # noqa: E402

CELL = "pokec_medium_daemon.analytics_fresh"
N_NODES, N_EDGES = 2_000, 20_000
SEED = 2_147_483_693

ROUTE_METRICS = ("route_request_ms", "route_dispatch_ms",
                 "route_generation_ms", "route_meta_ms",
                 "daemon_routed_share", "compiles_per_cycle.daemon",
                 "true_compiles_per_cycle.daemon",
                 "iterations_per_call.daemon")


def small_cell():
    cell = run.load_cell(CELL)
    cell["config"] = dict(cell["config"], nodes=N_NODES, edges=N_EDGES)
    return cell


@pytest.fixture
def seen():
    """Stands in for require_tpu: records the claim, demands no chip."""
    calls = []

    def record(device, chips):
        calls.append((device, chips))
    record.calls = calls
    return record


@pytest.fixture(autouse=True)
def _no_stray_children():
    yield
    leaked = list(run._CHILDREN)
    run.stop_all()
    assert not leaked, f"a run left {len(leaked)} process(es) running"


def drive(cell, tmp_path, seen, seconds=1.5, trace=False, **kw):
    return run.run_cell(cell, SEED, seconds, trace, str(tmp_path),
                        device_check=seen, t_start=time.perf_counter(), **kw)


def test_the_cell_is_the_inproc_cell_in_another_layout():
    """Same data, mix, semantics and limit file shape; the layout and
    what it states differ."""
    cell = run.load_cell(CELL)
    inproc = run.load_cell("pokec_medium.analytics_fresh")
    assert cell["mix"] == inproc["mix"]
    config, other = cell["config"], inproc["config"]
    for key in ("nodes", "edges", "graph_seed", "schema", "index", "load"):
        assert config[key] == other[key], key
    assert config["owner"]["kind"] == "daemon_server"
    assert "owner" not in config["reduced"]
    assert "across the process boundary" in \
        config["guarantees"]["read_your_write"]
    # no precision lever reaches the daemon's kernel through its
    # environment, and the file says so instead of listing one
    assert config["precision"]["controls"] == {}
    assert config["precision"]["controls_why"]
    # at the shipped default: nothing overrides the matmul precision
    assert "JAX_DEFAULT_MATMUL_PRECISION" not in \
        config["owner"].get("daemon_env", {})
    assert set(cell["limits"]) == {"rank_dev_max"}
    reported = {m["name"] for m in cell["per_layer"]}
    assert set(ROUTE_METRICS) <= reported
    assert {"pc_fixpoint_device_ms", "pc_fixpoint_roofline",
            "device_idle_pct.daemon"} <= reported
    # the MXU plan's metrics are not this cell's: it never runs that plan
    assert not {"fixpoint_roofline", "program_reuse_share",
                "call_delta_plan_ms"} & reported


def test_daemon_layout_end_to_end_traced(tmp_path, seen):
    result = drive(small_cell(), tmp_path, seen, seconds=2.0, trace=True)
    assert result["correct"] is True, result["compared"]
    assert result["cycles"] >= 2 and result["failed"] == 0
    compared = result["compared"]
    assert compared["rank_calls_compared"]["value"] >= 2
    assert compared["stale_calls"] == {"value": 0, "limit": 0, "ok": True}
    assert compared["row_faults"]["value"] == 0
    assert compared["readback_mismatches"]["value"] == 0
    assert compared["stale_sep_min"]["value"] > \
        10 * compared["rank_dev_max"]["value"]
    # the claim judged is the daemon's: one device, and the real
    # assertion refuses a CPU
    assert len(seen.calls) == 1 and seen.calls[0][1] == 1
    assert seen.calls[0][0]["platform"] == "cpu"
    assert set(seen.calls[0][0]) == {"platform", "kind", "count"}
    with pytest.raises(run.RunFailure):
        run.require_tpu(*seen.calls[0])
    # every CALL went over the socket, none fell back, and the route's
    # spans nest: the Bolt server's round trip holds the daemon's
    # dispatch, which holds the generation's refresh
    got = {name: m["value"] for name, m in result["metrics"].items()}
    assert set(ROUTE_METRICS) <= set(got), sorted(got)
    assert got["daemon_routed_share"] == 100.0
    assert got["route_request_ms"] >= got["route_dispatch_ms"] \
        >= got["route_generation_ms"] > 0
    assert got["route_meta_ms"] > 0
    assert got["iterations_per_call.daemon"] >= 1
    assert got["true_compiles_per_cycle.daemon"] <= \
        got["compiles_per_cycle.daemon"]
    # the accepted metric files read the Bolt server's spans as they are
    for name in ("call_server_ms.daemon", "call_export_ms.daemon",
                 "call_rows_ms.daemon", "call_consume_ms", "call_sort_ms",
                 "rank_call_p50_ms", "burst_write_p50_ms"):
        assert got[name] > 0, name
    assert got["call_server_ms.daemon"] >= got["route_request_ms"]
    # no share of a device is reported from the CPU's stand-in
    assert not any(name.startswith(("device_idle", "pc_fixpoint"))
                   for name in got)
    assert result["device"]["busy_s"] > 0


class Spoiled:
    """A transport whose answers pass through `spoil` first."""

    def __init__(self, inner, spoil):
        self.inner, self.spoil = inner, spoil
        self.client = inner.client

    def run(self, req):
        return self.spoil(self.inner.run(req))


def test_a_call_that_missed_its_burst_is_stale(tmp_path, seen):
    """What a route that lost a delta would answer: the first CALL of
    the window gets the float64 reference's own rows for the graph
    without the burst committed just before it. Exact for the wrong
    graph: `stale_calls` has to say so."""
    cell = small_cell()
    _layout, dataset, sem = run.seams_of(cell)
    state = dataset.make(cell["config"])
    held = {"before": None, "calls": 0}

    def spoil(req):
        if req.name == "burst_write" and req.ok:
            held["before"] = state.copy()
            sem.apply("add_edges", state, req.params)
        elif req.name == "rank_call":
            held["calls"] += 1
            if held["calls"] == 3:      # the warm-up holds two
                ranks = sem.vector("pagerank_top", held["before"], {})
                ids, values = reference.top_ranks(ranks, 100)
                req.rows = [[int(i), float(v)]
                            for i, v in zip(ids, values)]
        return req

    result = drive(cell, tmp_path, seen, seconds=1.0,
                   transport_hook=lambda t: Spoiled(t, spoil))
    assert held["calls"] >= 3
    assert result["correct"] is False
    assert result["compared"]["stale_calls"]["value"] >= 1
    assert result["compared"]["stale_calls"]["ok"] is False


def test_a_killed_daemon_gives_no_sound_result(tmp_path, seen):
    """The daemon dies after the load and before the warm-up: the Bolt
    server falls back to its own (CPU) backend and keeps answering, so
    the rows may well be right. The run must not come out as a sound
    reading of this deployment: it fails, or its routed share says so."""
    def kill_the_daemon(transport):
        daemon = run._CHILDREN[0]           # started first
        os.killpg(daemon.pid, signal.SIGKILL)
        daemon.wait(30)
        return transport

    try:
        result = drive(small_cell(), tmp_path, seen, seconds=1.0,
                       trace=True, transport_hook=kill_the_daemon)
    except run.RunFailure as e:
        assert "owner exited" in str(e) or "daemon" in str(e), e
    else:
        share = result["metrics"].get("daemon_routed_share")
        assert not (result["correct"] is True and share
                    and share["value"] == 100.0), result


def test_a_fallback_with_the_daemon_alive_fails_the_run(tmp_path, seen):
    """The daemon serves and refuses: with an admission budget of one
    byte it sheds every CALL, the Bolt server answers each from its own
    (CPU) backend, and the rows are right. Both processes stay alive,
    so only the route's fallback counter can say that the times are
    another deployment's: the layout's `stats()` fails the run on it."""
    cell = small_cell()
    owner = dict(cell["config"]["owner"],
                 daemon_env={"MEMGRAPH_TPU_HBM_BUDGET_BYTES": "1"})
    cell["config"] = dict(cell["config"], owner=owner)
    with pytest.raises(run.RunFailure) as failure:
        drive(cell, tmp_path, seen, seconds=1.0)
    message = str(failure.value)
    assert "fell back" in message and "daemon alive" in message, message
    assert "exceeds HBM budget 1 bytes" in message      # the daemon's log


def test_float32_computed_in_bf16_would_fail_the_cell():
    """The kernel's one lower precision (each edge's contribution
    rounded to bfloat16, summed wider) in the reference's own terms: it
    lies far above the cell's limit, as the bare-client reading on the
    chip does (the cell file's readings.upper.bf16), and the float64
    iteration stopped at the program's tolerance lies under it."""
    cell = run.load_cell(CELL)
    limit = cell["limits"]["rank_dev_max"]
    src, dst = reference.make_graph(int(cell["config"]["graph_seed"]),
                                    N_NODES, N_EDGES)
    want, _ = reference.pagerank(src, dst, N_NODES)
    read = {}
    for precision in ("bf16", "float64"):
        ranks, _ = reference.pagerank(src, dst, N_NODES, tol=1e-5,
                                      max_iterations=100,
                                      precision=precision)
        ids, values = reference.top_ranks(ranks, 100)
        rows = [[int(i), float(v)] for i, v in zip(ids, values)]
        one = run.compare_ranks(rows, want, 100)
        read[precision] = max(one["rel_err"], one["gap"])
    assert read["float64"] < limit < limit * 10 < read["bf16"], read
    with open(os.path.join(BENCH, "cells", CELL + ".json")) as f:
        readings = json.load(f)["readings"]
    assert readings["lower"]["largest"] < limit \
        < readings["upper"]["smallest"] < readings["upper"]["bf16"]["smallest"]
