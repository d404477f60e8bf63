"""A deployment that differs from Pokec in layout, data set and
semantics, added by writing files only.

The whole deployment lives in files this test writes under tmp_path: a
benchmark root with its own BENCHMARK.json, one configuration, one mix,
one per-layer metric, and one module for each of run.py's three seams.

  layout     two processes. The program's Bolt server, and beside it a
             holder that stands where a kernel-server daemon would: the
             device report, the answers to trace_start / trace_stop /
             memory and the counters all come from the holder, which
             speaks no Bolt.
  data set   a ledger: :Account and :Branch, (:Account)-[:AT]->(:Branch)
             and a weighted (:Account)-[:PAID {amount}]->(:Account),
             loaded by four statements of its own.
  semantics  one write class (pay) and one read class (paid_out), both
             its own, the read held exact_in_order.

It runs end to end through run.py's own functions to `correct: true`,
and with an answer altered under the timed path to `correct: false`.
Nothing under benchmarks/chipbench differs between these runs and a run
of the accepted cells.
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

import gap_spans  # noqa: E402
import run  # noqa: E402
import seams  # noqa: E402
import traffic  # noqa: E402

SEED = 2_147_483_801            # the driver's seeds pass 2**31
CELL = "ledger.transfers"

# --------------------------------------------------------------------------
# the deployment's files
# --------------------------------------------------------------------------

BENCHMARK = {
    "command": ["python3", "benchmarks/chipbench/run.py"],
    "paths": ["ledgerbench"],
    "run_seconds": 1,
    "configs": [{
        "name": "ledger_beside_holder", "source": "this test",
        "file": "ledgerbench/configs/ledger_beside_holder.json",
        "reduced": [], "why": "a second process holds the device"}],
    "workloads": [{
        "name": CELL, "config": "ledger_beside_holder",
        "traffic": "transfers", "chips": 1,
        "why": "1 client, closed loop: one payment, then the payer's total"}],
    "end_to_end": [
        {"name": "fresh_cycle_s", "unit": "s", "better": "lower",
         "bound": 0.25, "source": "host_clock"},
        {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.25,
         "source": "host_clock"}],
    "per_layer": [
        {"name": "holder_asks", "unit": "count", "better": "lower",
         "source": "program_counter", "layer": "holder",
         "moves": "fresh_cycle_s"}],
}

CONFIG = {
    "name": "ledger_beside_holder",
    "source": "this test",
    "owner": {"kind": "bolt_beside_holder",
              "server_flags": ["--storage-wal-enabled"]},
    "dataset": "ledger",
    "graph_seed": 11,
    "accounts": 60, "branches": 4, "payments": 300,
    "reduced": [],
}

MIX = {
    "name": "transfers",
    "semantics": "ledger",
    "clients": 1,
    "schedule": "sequence",
    "classes": [
        {"name": "pay", "kind": "write", "reference": "pay",
         "query": "MATCH (a:Account {id: $row[0]}), (b:Account {id: $row[1]})"
                  " CREATE (a)-[:PAID {amount: $row[2]}]->(b)",
         "params": {"row": {"gen": "payment"}}},
        {"name": "paid_out", "kind": "read", "reference": "paid_out",
         "query": "MATCH (a:Account {id: $id})-[p:PAID]->() "
                  "RETURN count(p), sum(p.amount)",
         "params": {"id": {"gen": "last_payer"}}},
    ],
    "warmup": {"first": [], "then_cycles": 1},
    "trace_slice": {"cycles": 1},
    "readback": [
        {"name": "totals", "reference": "totals",
         "query": "MATCH (a:Account)-[p:PAID]->() RETURN a.id AS id, "
                  "count(p) AS n, sum(p.amount) AS total ORDER BY id"},
        {"name": "members", "reference": "members",
         "query": "MATCH (a:Account)-[:AT]->(b:Branch) RETURN b.id AS id, "
                  "count(a) AS n ORDER BY id"}],
    "compare": {
        "exact_mismatches": {"limit": 0},
        "readback_mismatches": {"limit": 0}},
}

METRIC = {
    "kind": "stats_delta",
    "what": "requests the harness made of the holder in the window",
    "params": {"numerator": ["holder/asks_total"], "denominator": "cycles"},
}

HOLDER = '''
"""Stands where the process that holds the chip would: answers the
harness over HTTP and speaks no Bolt."""
import json
import sys
import time
from http.server import BaseHTTPRequestHandler, HTTPServer

COUNTS = {"asks_total": 0}


class Handler(BaseHTTPRequestHandler):
    def do_GET(self):
        if self.path == "/device":
            body = {"platform": "cpu", "kind": "a holder of no chip",
                    "count": 1}
        elif self.path == "/stats":
            body = {"holder": dict(COUNTS)}
        else:
            COUNTS["asks_total"] += 1
            op = self.path.rsplit("/", 1)[-1]
            body = {"trace_start": {"started_ns": time.time_ns()},
                    "trace_stop": {"stopped_ns": time.time_ns()},
                    "memory": {"memory_peak_bytes": 4242}}[op]
        raw = json.dumps(body).encode()
        self.send_response(200)
        self.send_header("Content-Length", str(len(raw)))
        self.end_headers()
        self.wfile.write(raw)

    def log_message(self, *args):
        pass


HTTPServer(("127.0.0.1", int(sys.argv[1])), Handler).serve_forever()
'''

LAYOUT = '''
"""Two processes: the program's Bolt server, held to the CPU, and the
holder beside it."""
import json
import os
import sys
import time
import urllib.request

import procs

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class Layout:
    def __init__(self, config, workdir):
        self.bolt, self.http = procs.free_port(), procs.free_port()
        self.logs = [os.path.join(workdir, name + ".log")
                     for name in ("server", "holder")]
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        self.children = [
            procs.spawn([sys.executable, "-m", "memgraph_tpu.main",
                         "--bolt-port", str(self.bolt),
                         "--data-directory", os.path.join(workdir, "data")]
                        + list(config["owner"]["server_flags"]),
                        env, self.logs[0], procs.REPO),
            procs.spawn([sys.executable, os.path.join(HERE, "holder.py"),
                         str(self.http)], env, self.logs[1], workdir)]

    def _get(self, path):
        deadline = time.monotonic() + 60
        while True:
            try:
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{self.http}/{path}",
                        timeout=30) as r:
                    return json.load(r)
            except OSError:
                if not self.alive() or time.monotonic() > deadline:
                    raise procs.RunFailure("the holder did not answer")
                time.sleep(0.05)

    def port(self, client_index):
        return self.bolt

    def alive(self):
        return all(p.poll() is None for p in self.children)

    def device(self, client):
        return self._get("device")      # not the Bolt server's to say

    def ask(self, op, timeout_s=60.0, **fields):
        return self._get("ask/" + op)

    def stats(self):
        return procs.flatten(self._get("stats"))

    def log_tail(self):
        return "\\n".join(procs.tail(path) for path in self.logs)

    def stop(self):
        return [procs.stop_child(p) for p in self.children]


def start(config, chips, workdir, extra_env=None):
    return Layout(config, workdir)
'''

DATASET = '''
"""A ledger: accounts at branches, and weighted payments between
accounts, all from graph_seed."""
import time

import numpy as np


class Ledger:
    def __init__(self, branch_of, payments):
        self.branch_of = list(branch_of)        # by account id
        self.payments = [list(p) for p in payments]     # [a, b, amount]

    def copy(self):
        return Ledger(self.branch_of, self.payments)


def make(config):
    rng = np.random.default_rng(int(config["graph_seed"]))
    n = int(config["accounts"])
    rows = np.stack([rng.integers(0, n, int(config["payments"])),
                     rng.integers(0, n, int(config["payments"])),
                     rng.integers(1, 500, int(config["payments"]))], axis=1)
    return Ledger(rng.integers(0, int(config["branches"]), n).tolist(),
                  rows.tolist())


def key_space(config):
    return int(config["accounts"])


def sizes(state):
    return {"n_nodes": len(state.branch_of), "n_edges": len(state.payments)}


def load(client, config, state):
    t0 = time.perf_counter()
    client.execute("CREATE INDEX ON :Account(id)")
    client.execute("CREATE INDEX ON :Branch(id)")
    client.execute("UNWIND $ids AS i CREATE (:Account {id: i})",
                   {"ids": list(range(len(state.branch_of)))})
    client.execute("UNWIND $ids AS i CREATE (:Branch {id: i})",
                   {"ids": list(range(int(config["branches"])))})
    client.execute(
        "UNWIND $rows AS r MATCH (a:Account {id: r[0]}), (b:Branch {id: r[1]})"
        " CREATE (a)-[:AT]->(b)",
        {"rows": [[a, b] for a, b in enumerate(state.branch_of)]})
    client.execute(
        "UNWIND $rows AS r MATCH (a:Account {id: r[0]}), "
        "(b:Account {id: r[1]}) CREATE (a)-[:PAID {amount: r[2]}]->(b)",
        {"rows": state.payments})
    records = len(state.branch_of) * 2 + int(config["branches"]) \\
        + len(state.payments)
    return time.perf_counter() - t0, records


def payment(plan, spec):
    a, b = (int(v) for v in plan.rng.integers(0, plan.n_ids, 2))
    plan.last_payer = a
    return [a, b, int(plan.rng.integers(1, 500))]


def last_payer(plan, spec):
    return plan.last_payer


GENERATORS = {"payment": payment, "last_payer": last_payer}
'''

SEMANTICS = '''
"""What a payment does to a ledger, and what its reads return."""

MODES = {"pay": "write", "paid_out": "exact_in_order"}


def apply(name, state, params):
    assert name == "pay"
    state.payments.append(list(params["row"]))


def answer(name, state, params):
    assert name == "paid_out"
    mine = [amount for a, _, amount in state.payments if a == params["id"]]
    return [[len(mine), sum(mine)]]


def readback(name, state):
    if name == "members":
        counts = {}
        for branch in state.branch_of:
            counts[branch] = counts.get(branch, 0) + 1
        return sorted([b, n] for b, n in counts.items())
    totals = {}
    for a, _, amount in state.payments:
        n, total = totals.get(a, (0, 0))
        totals[a] = (n + 1, total + amount)
    return sorted([a, n, total] for a, (n, total) in totals.items())
'''


def write_root(root, mix=MIX):
    home = root / "ledgerbench"
    for sub in ("configs", "traffic", "layer_metrics", "owners", "datasets",
                "semantics"):
        (home / sub).mkdir(parents=True)
    (root / "BENCHMARK.json").write_text(json.dumps(BENCHMARK))
    (home / "configs" / "ledger_beside_holder.json").write_text(
        json.dumps(CONFIG))
    (home / "traffic" / "transfers.json").write_text(json.dumps(mix))
    (home / "layer_metrics" / "holder_asks.json").write_text(
        json.dumps(METRIC))
    (home / "holder.py").write_text(HOLDER)
    (home / "owners" / "bolt_beside_holder.py").write_text(LAYOUT)
    (home / "datasets" / "ledger.py").write_text(DATASET)
    (home / "semantics" / "ledger.py").write_text(SEMANTICS)
    return str(root)


@pytest.fixture(autouse=True)
def _no_stray_children():
    yield
    leaked = list(run._CHILDREN)
    run.stop_all()
    assert not leaked, f"a run left {len(leaked)} process(es) running"


class Broken:
    """A transport whose answers pass through `spoil` first."""

    def __init__(self, inner, spoil):
        self.inner, self.spoil = inner, spoil
        self.client = inner.client

    def run(self, req):
        return self.spoil(self.inner.run(req))


def one_unit_more(req):
    """An answer altered where it is produced."""
    if req.name == "paid_out":
        req.rows = [[req.rows[0][0], req.rows[0][1] + 1]]
    return req


def drive(tmp_path, trace, hook=None):
    root = write_root(tmp_path / "root")
    cell = run.load_cell(CELL, root=root)
    seen = []
    work = tmp_path / "work"
    work.mkdir()
    result = run.run_cell(
        cell, SEED, 1.0, trace, str(work),
        device_check=lambda device, chips: seen.append((device, chips)),
        transport_hook=hook, t_start=time.perf_counter())
    return result, seen


# --------------------------------------------------------------------------
# the deployment end to end
# --------------------------------------------------------------------------

def test_a_deployment_of_files_alone_is_correct(tmp_path):
    result, seen = drive(tmp_path, trace=False)
    assert result["correct"] is True, result["compared"]
    assert result["cycles"] >= 3 and result["failed"] == 0
    assert set(result["metrics"]) == {"fresh_cycle_s", "setup_s"}
    compared = result["compared"]
    assert compared["exact_mismatches"] == {"value": 0, "limit": 0,
                                            "ok": True}
    assert compared["exact_reads_compared"]["value"] == result["cycles"]
    assert compared["readback_mismatches"]["value"] == 0
    assert list(result)[-1] == "compared"
    # the claim the device assertion judged is the holder's, not that of
    # the Bolt server (whose SHOW BUILD INFO says kind "cpu"); so is the
    # memory reading
    assert seen == [({"platform": "cpu", "kind": "a holder of no chip",
                      "count": 1}, 1)]
    with pytest.raises(run.RunFailure):
        run.require_tpu(*seen[0])
    assert result["device"]["kind"] == "a holder of no chip"
    assert result["device"]["memory_peak_bytes"] == 4242


def test_its_layer_metric_reads_the_holders_counters(tmp_path):
    """A traced run: trace_start and trace_stop are asked of the holder
    inside the window, and its counter, which no Bolt server keeps, is
    what the per-layer metric reads. The holder wrote no xplane, so the
    line has no device seconds; the run still ends and is correct."""
    result, _ = drive(tmp_path, trace=True)
    assert result["correct"] is True, result["compared"]
    assert set(result["metrics"]) == {"holder_asks"}
    assert result["metrics"]["holder_asks"]["value"] * result["cycles"] \
        == pytest.approx(2.0)
    assert "busy_s" not in result["device"] and "breakdown" not in result


def test_an_altered_answer_is_not_correct(tmp_path):
    result, _ = drive(tmp_path, trace=False,
                      hook=lambda t: Broken(t, one_unit_more))
    assert result["correct"] is False
    assert result["compared"]["exact_mismatches"]["ok"] is False
    assert result["compared"]["exact_mismatches"]["value"] \
        == result["compared"]["exact_reads_compared"]["value"]
    assert result["compared"]["readback_mismatches"]["ok"] is True


# --------------------------------------------------------------------------
# the seams' edges
# --------------------------------------------------------------------------

def test_a_name_with_no_file_fails_before_anything_starts(tmp_path):
    root = write_root(tmp_path / "root")
    cell = run.load_cell(CELL, root=root)
    for where, key, sub in (("config", "dataset", "datasets"),
                            ("mix", "semantics", "semantics")):
        broken = dict(cell, **{where: dict(cell[where], **{key: "nowhere"})})
        with pytest.raises(run.RunFailure, match=f"no {sub}/nowhere.py"):
            run.run_cell(broken, SEED, 1.0, False, str(tmp_path),
                         device_check=lambda device, chips: None)
    broken = dict(cell, config=dict(cell["config"], owner={"kind": "none"}))
    with pytest.raises(run.RunFailure, match="no owners/none.py"):
        run.seams_of(broken)
    with pytest.raises(run.RunFailure, match="no workload"):
        run.load_cell("ledger.nothing", root=root)


def test_the_benchmarks_own_directory_is_searched_first(tmp_path):
    """A benchmark elsewhere finds its own files before the ones here,
    and the ones here where it has none of that name."""
    root = write_root(tmp_path / "root")
    cell = run.load_cell(CELL, root=root)
    assert cell["dirs"] == [os.path.join(root, "ledgerbench"), BENCH]
    layout, dataset, sem = run.seams_of(cell)
    assert layout.__file__.startswith(root) and sem.MODES["pay"] == "write"
    assert run.seams_of(cell)[1] is dataset         # loaded once
    default = dict(cell, config={"owner": {}}, mix={})
    assert [os.path.relpath(m.__file__, BENCH)
            for m in run.seams_of(default)] == [
        "owners/inproc_server.py", "datasets/pokec_synthetic.py",
        "semantics/pokec_graph.py"]
    accepted = run.load_cell("pokec_small.oltp_mixed")
    assert accepted["dirs"] == [BENCH]
    assert seams.find(cell["dirs"], "rooflines", "pagerank_spmv", ".py") \
        == os.path.join(BENCH, "rooflines", "pagerank_spmv.py")


def test_modes_that_follow_an_order_need_one_client(tmp_path):
    root = write_root(tmp_path / "root")
    cell = run.load_cell(CELL, root=root)
    sem = run.seams_of(cell)[2]
    assert run.modes_of(cell["mix"], sem) == {"pay": "write",
                                              "paid_out": "exact_in_order"}
    with pytest.raises(run.RunFailure, match="only one\\s+client"):
        run.modes_of(dict(cell["mix"], clients=2), sem)
    # with no write there is one state, and any number of clients
    reads = dict(cell["mix"], clients=2, classes=cell["mix"]["classes"][1:])
    assert run.modes_of(reads, sem) == {"paid_out": "exact_in_order"}
    unknown = dict(cell["mix"], classes=[dict(cell["mix"]["classes"][1],
                                              reference="balance")])
    with pytest.raises(run.RunFailure, match="have no 'balance'"):
        run.modes_of(unknown, sem)


def test_a_data_sets_generator_comes_before_the_plans_own(tmp_path):
    root = write_root(tmp_path / "root")
    dataset = run.seams_of(run.load_cell(CELL, root=root))[1]
    plan = traffic.Plan(MIX, 60, SEED, 0, None, dataset)
    pay, read = next(plan), next(plan)
    a, b, amount = pay.params["row"]
    assert 0 <= a < 60 and 0 <= b < 60 and 1 <= amount < 500
    assert read.params == {"id": a}

    class Shadow:
        GENERATORS = {"key": lambda plan, spec: -1}
    mix = {"classes": [{"name": "r", "params": {"id": {"gen": "key"}}}],
           "schedule": "sequence"}
    assert traffic.Plan(mix, 60, SEED, 0, None, Shadow).request("r") \
        .params == {"id": -1}
    with pytest.raises(ValueError, match="no parameter generator"):
        traffic.Plan(MIX, 60, SEED, 0, None).request("pay")


# --------------------------------------------------------------------------
# the comparison's modes, on hand-made windows
# --------------------------------------------------------------------------

def _req(cls, params, rows, error=None):
    return traffic.Request(cls, params, 0, rows=rows, error=error)


def test_exact_in_order_follows_the_acknowledged_writes(tmp_path):
    root = write_root(tmp_path / "root")
    cell = run.load_cell(CELL, root=root)
    _, dataset, sem = run.seams_of(cell)
    pay, read = cell["mix"]["classes"]
    state0 = dataset.make(dict(CONFIG, payments=0))
    window = [[
        _req(read, {"id": 3}, [[0, 0]]),
        _req(pay, {"row": [3, 4, 10]}, []),
        _req(read, {"id": 3}, [[1, 10]]),
        _req(pay, {"row": [3, 5, 7]}, None, error="refused"),   # no effect
        _req(read, {"id": 3}, [[1, 10]]),
        _req(read, {"id": 3}, None, error="failed"),    # counted as failed
        _req(pay, {"row": [3, 5, 1]}, []),
        _req(read, {"id": 3}, [[1, 10]]),               # has not seen it
    ]]
    final = state0.copy()
    run.apply_acknowledged(sem, final, window[0])
    assert final.payments == [[3, 4, 10], [3, 5, 1]] and not state0.payments
    collected = {"readback": {"totals": [[3, 2, 11]],
                              "members": sem.readback("members", final)}}
    numbers = run.compare(cell["mix"], state0, final, window, collected, sem)
    assert numbers == {"exact_mismatches": 1, "_exact_reads_compared": 4,
                       "readback_mismatches": 0}
    rows, correct = run.judge(numbers, cell["mix"], {})
    assert not correct and [r[0] for r in rows if not r[3]] == \
        ["exact_mismatches"]
    # a mix that never writes: both clients' reads, one state
    reads = dict(cell["mix"], clients=2, classes=[read], readback=[])
    window = [[_req(read, {"id": 3}, [[2, 11]])],
              [_req(read, {"id": 4}, [[0, 0]]), _req(read, {"id": 3}, [[1, 10]])]]
    assert run.compare(reads, final, final, window, collected, sem) == {
        "exact_mismatches": 1, "_exact_reads_compared": 3,
        "readback_mismatches": 0}


# --------------------------------------------------------------------------
# breakdown.idle_gaps: a gap split among the spans over it
# --------------------------------------------------------------------------

def test_idle_gaps_are_named_by_the_innermost_span():
    s = 1e9
    planes = {"/device:TPU:0": [["a", 0.0, 1 * s], ["b", 5 * s, 1 * s],
                                ["c", 6.5 * s, 0.5 * s]]}
    host = [
        ["mgtrace:analytics.export", 0.5 * s, 2.0 * s],     # 1.0..2.5 in gap
        ["mgtrace:analytics.launch", 3.0 * s, 3.0 * s],     # 3.0..5.0 in gap
        ["mgtrace:analytics.edge_diff", 3.2 * s, 0.5 * s],  # inside launch
        ["mgtrace:analytics.edge_diff", 4.0 * s, 0.25 * s],
    ]
    gaps = gap_spans.attribute(planes, host)
    assert gaps[0]["parts"] == [
        ("analytics.export", pytest.approx(1.5)),
        ("analytics.launch", pytest.approx(1.25)),
        ("analytics.edge_diff", pytest.approx(0.75)),
        ("unattributed", pytest.approx(0.5))]
    assert sum(seconds for _, seconds in gaps[0]["parts"]) == \
        pytest.approx(gaps[0]["seconds"]) == 4.0
    assert gaps[1]["parts"] == [("unattributed", pytest.approx(0.5))]
    trace = {"ops": {"a": {"count": 1, "seconds": 1.0},
                     "b": {"count": 1, "seconds": 1.0}},
             "gaps": json.loads(json.dumps(gaps))}
    assert run.breakdown_of(trace) == {
        "device_ops": [["a", 1.0], ["b", 1.0]],
        "idle_gaps": [["analytics.export", pytest.approx(1.5)],
                      ["analytics.launch", pytest.approx(1.25)],
                      ["analytics.edge_diff", pytest.approx(0.75)],
                      ["unattributed", pytest.approx(0.5)],
                      ["unattributed", pytest.approx(0.5)]]}
    many = {"ops": {}, "gaps": [{"parts": [["x", float(i)]]}
                                for i in range(1, 15)]}
    assert [p[1] for p in run.breakdown_of(many)["idle_gaps"]] == \
        [float(i) for i in range(14, 4, -1)]
