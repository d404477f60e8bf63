"""The six per-layer metrics over the host's work under the device's
idle gaps (ISSUE 37): Python's cyclic collector (``python.gc``,
``python.gc.full``) and the Bolt server's message work
(``bolt.prepare``, ``bolt.pull``, ``bolt.encode``), read from ``GET
/stats`` section ``device`` by ``stats_delta`` files, rehearsed without
the chip through run.py's own functions at 2k/20k, as
test_program_spans.py rehearses the metrics before them.
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
import layers  # noqa: E402
import run  # noqa: E402

N_NODES, N_EDGES = 2_000, 20_000
SEED = 2_147_483_801            # the driver's seeds pass 2**31

with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    BENCHMARK = json.load(_f)

MEDIUM = "pokec_medium.analytics_fresh"
DAEMON = "pokec_medium_daemon.analytics_fresh"
SMALL = "pokec_small.oltp_mixed"

#: (name, unit, layer, numerator, denominator, moves, workloads): the
#: issue's table, in its order
TABLE = [
    ("pygc_ms_per_cycle", "ms/cycle", "Python runtime",
     "device/span.python.gc.seconds_total", "cycles", "fresh_cycle_s",
     [MEDIUM, DAEMON]),
    ("pygc_full_ms_per_cycle", "ms/cycle", "Python runtime",
     "device/span.python.gc.full.seconds_total", "cycles", "fresh_cycle_s",
     [MEDIUM, DAEMON]),
    ("pygc_ms_per_query", "ms", "Python runtime",
     "device/span.python.gc.seconds_total", ["device/span.bolt.run.count"],
     "oltp_queries_per_s", [SMALL]),
    ("bolt_prepare_ms", "ms", "Bolt front end",
     "device/span.bolt.prepare.seconds_total",
     ["device/span.bolt.prepare.count"], "oltp_queries_per_s", [SMALL]),
    ("bolt_pull_ms", "ms", "Bolt front end",
     "device/span.bolt.pull.seconds_total",
     ["device/span.bolt.pull.count"], "oltp_queries_per_s", [SMALL]),
    ("bolt_encode_ms", "ms", "Bolt front end",
     "device/span.bolt.encode.seconds_total",
     ["device/span.bolt.encode.count"], "oltp_queries_per_s", [SMALL]),
]
NAMES = [row[0] for row in TABLE]
PER_CYCLE = NAMES[:2]
PER_QUERY = NAMES[2:]
BOLT_PHASES = ["bolt_prepare_ms", "bolt_pull_ms", "bolt_encode_ms"]


def spec_of(name):
    with open(os.path.join(BENCH, "layer_metrics", name + ".json")) as f:
        return json.load(f)


def small_cell(workload):
    cell = run.load_cell(workload)
    cell["config"] = dict(cell["config"], nodes=N_NODES, edges=N_EDGES)
    return cell


def drive(workload, tmp_path, seconds):
    """One traced rehearsal; also the reader's context, for the sums."""
    seen = []
    read = layers.read

    def spy(metric, ctx):
        seen.append(ctx)
        return read(metric, ctx)

    layers.read = spy
    try:
        result = run.run_cell(small_cell(workload), SEED, seconds, True,
                              str(tmp_path),
                              device_check=lambda device, chips: None,
                              t_start=time.perf_counter())
    finally:
        layers.read = read
        leaked = list(run._CHILDREN)
        run.stop_all()
    assert not leaked, f"a run left {len(leaked)} process(es) running"
    values = {name: m["value"] for name, m in result["metrics"].items()}
    return result, values, seen[0]


def per_count(ctx, phase):
    """ms of a phase span over its own count in the window."""
    before, after = ctx["stats_before"], ctx["stats_after"]

    def delta(key):
        return after.get(key, 0.0) - before.get(key, 0.0)
    return 1000.0 * delta(f"device/span.{phase}.seconds_total") \
        / delta(f"device/span.{phase}.count")


# --------------------------------------------------------------------------
# the files
# --------------------------------------------------------------------------

def hold_pins(root=REPO):
    """What this file holds of the BENCHMARK.json under `root`: the six
    stand in the order of the table, each is the table's entry, and is
    reported in the table's cells."""
    bench = bench_pins.read(root)
    bench_pins.stand_in_order(bench["per_layer"], NAMES)
    for name, unit, layer, _num, _den, moves, workloads in TABLE:
        bench_pins.entry_except_workloads(
            bench_pins.entry(bench["per_layer"], name),
            {"name": name, "unit": unit, "better": "lower",
             "source": "program_span", "layer": layer,
             "moves": moves, "workloads": workloads})


def test_the_six_are_appended_in_the_order_of_the_table():
    hold_pins()
    assert len({m["name"] for m in BENCHMARK["per_layer"]}) == \
        len(BENCHMARK["per_layer"])


@pytest.mark.parametrize("row", TABLE, ids=NAMES)
def test_entry_and_file_are_the_tables(row):
    name, unit, layer, numerator, denominator, moves, workloads = row
    hold_pins()
    entry = bench_pins.entry(BENCHMARK["per_layer"], name)
    spec = spec_of(name)
    assert spec["kind"] == "stats_delta" and spec["kind"] in layers.READERS
    assert spec["params"] == {"numerator": [numerator],
                              "denominator": denominator, "scale": 1000.0}
    # a cell lists a metric only where it reports what the metric moves
    for cell in entry["workloads"]:
        assert any(m["name"] == moves and cell in m.get("workloads", [cell])
                   for m in BENCHMARK["end_to_end"])


def test_the_layers_are_named_as_the_benchmark_names_them():
    layers_named = {m["layer"] for m in BENCHMARK["per_layer"]
                    if m["name"] not in NAMES}
    assert "Bolt front end" in layers_named
    assert "Python runtime" not in layers_named     # new in this PR


def test_the_six_are_data_only():
    for name in NAMES:
        path = os.path.join(BENCH, "layer_metrics", name + ".json")
        assert path.endswith(".json") and os.path.isfile(path)


def test_a_program_without_the_spans_reports_nothing_and_does_not_raise():
    """The parent commit has no span.python.gc / bolt.prepare|pull|encode:
    a metric over a span's own count is left out, one over cycles or
    bolt.run's count reads 0; none raises."""
    ctx = {"stats_before": {"device/span.bolt.run.count": 10.0},
           "stats_after": {"device/span.bolt.run.count": 50.0},
           "cycles": 6}
    for name in NAMES:
        got = layers.read(spec_of(name), ctx)
        assert got is None or got == 0.0, (name, got)
    for name in BOLT_PHASES:
        assert layers.read(spec_of(name), ctx) is None


# --------------------------------------------------------------------------
# the rehearsals
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def oltp(tmp_path_factory):
    return drive(SMALL, tmp_path_factory.mktemp("oltp"), seconds=3.0)


@pytest.fixture(scope="module")
def analytics(tmp_path_factory):
    os.environ["MEMGRAPH_TPU_FORCE_MXU"] = "1"
    os.environ["MEMGRAPH_TPU_MXU_MIN_EDGES"] = "1000"
    try:
        return drive(MEDIUM, tmp_path_factory.mktemp("fresh"), seconds=4.0)
    finally:
        os.environ.pop("MEMGRAPH_TPU_FORCE_MXU")
        os.environ.pop("MEMGRAPH_TPU_MXU_MIN_EDGES")


def test_oltp_rehearsal_reads_its_four(oltp):
    result, got, _ctx = oltp
    assert result["correct"] is True, result["compared"]
    for name in PER_QUERY:
        assert got.get(name) is not None, name
        assert got[name] >= 0
    assert got["bolt_prepare_ms"] > 0 and got["bolt_pull_ms"] > 0
    assert got["bolt_encode_ms"] > 0
    for name in PER_CYCLE:              # listed for the medium cells only
        assert name not in got


def test_oltp_phases_lie_inside_the_exchange(oltp):
    """bolt.wait ends where bolt.prepare / bolt.pull begin; with
    bolt.encode they lie inside the RUN..PULL exchange."""
    _result, got, ctx = oltp
    run_ms = per_count(ctx, "bolt.run")
    assert got["bolt_wait_ms"] + sum(got[n] for n in BOLT_PHASES) <= run_ms
    # every request is one RUN and one PULL; the server closes bolt.run
    # after its answer is on the wire, so /stats may be read between
    # the two, once for each of the four clients
    before, after = ctx["stats_before"], ctx["stats_after"]

    def moved(phase):
        key = f"device/span.{phase}.count"
        return after[key] - before.get(key, 0.0)
    clients = 4
    assert moved("bolt.prepare") == moved("bolt.pull") > 0
    assert abs(moved("bolt.pull") - moved("bolt.run")) <= clients
    assert abs(moved("bolt.wait") - 2 * moved("bolt.pull")) <= 2 * clients


def test_oltp_collector_per_query(oltp):
    _result, got, ctx = oltp
    before, after = ctx["stats_before"], ctx["stats_after"]
    moved = after["device/span.python.gc.count"] \
        - before.get("device/span.python.gc.count", 0.0)
    assert moved >= 1       # a Bolt server collects under any traffic
    assert got["pygc_ms_per_query"] > 0


def test_analytics_rehearsal_reads_its_two(analytics):
    result, got, ctx = analytics
    assert result["correct"] is True, result["compared"]
    for name in PER_CYCLE:
        assert got.get(name) is not None, name
        assert got[name] >= 0
    assert got["pygc_ms_per_cycle"] > 0
    # the full collections are a part of all of them
    assert got["pygc_full_ms_per_cycle"] <= got["pygc_ms_per_cycle"]
    for name in PER_QUERY:              # listed for the oltp cell only
        assert name not in got
    # a CALL's exchange holds its pull: the procedure's row drain
    assert per_count(ctx, "bolt.pull") > 0


def test_daemon_rehearsal_reads_the_bolt_servers_collector(tmp_path):
    """In the daemon layout the Bolt server still owns storage: the two
    per-cycle metrics read its collector (``device/``), not the
    daemon's (``daemon/span.python.gc.*``, among the counters that
    moved)."""
    result, got, ctx = drive(DAEMON, tmp_path, seconds=2.0)
    assert result["correct"] is True, result["compared"]
    for name in PER_CYCLE:
        assert got.get(name) is not None, name
    assert got["pygc_ms_per_cycle"] > 0
    assert "daemon/span.python.gc.count" in ctx["stats_after"]
