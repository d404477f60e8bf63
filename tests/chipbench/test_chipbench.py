"""benchmarks/chipbench rehearsed without the chip.

Each mix runs end to end through run.py's own functions at 2k nodes /
20k edges against a CPU chip owner (the child inherits JAX_PLATFORMS=cpu
from conftest), with the ONE device assertion injected; the script as
the driver runs it must exit non-zero and print no result on a host
with no TPU. The comparison that decides `correct` is shown to fail:
under each cell's control, and with the timed path broken underneath.
"""

import copy
import json
import os
import re
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmarks", "chipbench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import layers  # noqa: E402
import reference  # noqa: E402
import run  # noqa: E402
import trace_reduce  # noqa: E402
import traffic  # noqa: E402

N_NODES, N_EDGES = 2_000, 20_000
SEED = 2_147_483_659            # the driver's seeds pass 2**31

with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    BENCHMARK = json.load(_f)
CELLS = [w["name"] for w in BENCHMARK["workloads"]]


def small_cell(workload):
    cell = run.load_cell(workload)
    cell["config"] = dict(cell["config"], nodes=N_NODES, edges=N_EDGES)
    return cell


def cell_of_mix(mix):
    return next(w["name"] for w in BENCHMARK["workloads"]
                if w["traffic"] == mix)


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


# --------------------------------------------------------------------------
# each mix end to end
# --------------------------------------------------------------------------

@pytest.mark.skipif("oltp_mixed" not in {w["traffic"] for w in
                                         BENCHMARK["workloads"]},
                    reason="no oltp_mixed cell in BENCHMARK.json")
def test_oltp_mixed_end_to_end(tmp_path, seen):
    cell = small_cell(cell_of_mix("oltp_mixed"))
    result = drive(cell, tmp_path, seen)
    assert result["correct"] is True, result["compared"]
    assert result["attempted"] > 50 and result["failed"] == 0
    # what the cell lists, which is these three and may be more
    assert set(result["metrics"]) == {m["name"] for m in cell["end_to_end"]}
    assert {"oltp_queries_per_s", "oltp_query_p95_ms",
            "setup_s"} <= set(result["metrics"])
    assert all(m["value"] > 0 for m in result["metrics"].values())
    for name in ("reads_out_of_bounds", "quiesced_mismatches",
                 "readback_mismatches"):
        assert result["compared"][name] == {"value": 0, "limit": 0,
                                            "ok": True}
    assert list(result)[-1] == "compared"
    # the device claim was made once, before the load, and the real
    # assertion refuses it
    assert seen.calls == [({"platform": "cpu", "kind": "cpu",
                            "count": seen.calls[0][0]["count"]}, 1)]
    with pytest.raises(run.RunFailure):
        run.require_tpu(*seen.calls[0])


def test_analytics_fresh_end_to_end_traced(tmp_path, seen):
    cell = small_cell(cell_of_mix("analytics_fresh"))
    result = drive(cell, tmp_path, seen, trace=True)
    assert result["correct"] is True, result["compared"]
    assert result["cycles"] >= 2 and result["failed"] == 0
    compared = result["compared"]
    assert compared["rank_calls_compared"]["value"] >= 2
    assert compared["row_faults"]["value"] == 0
    assert compared["stale_calls"] == {"value": 0, "limit": 0, "ok": True}
    # every CALL stood off the reference without its burst
    assert compared["stale_sep_min"]["value"] > \
        10 * compared["rank_dev_max"]["value"]
    assert compared["readback_mismatches"]["value"] == 0
    # float32 on the CPU sits inside the chip's limit
    assert compared["rank_dev_max"]["value"] < 5e-5
    assert compared["rank_dev_max"]["value"] == max(
        compared["rank_rel_err_max"]["value"],
        compared["top_gap_max"]["value"])
    # a traced run reports per-layer metrics only; the CPU's ops stand in
    # for a device's in the arithmetic and are never reported as a share
    assert "fresh_cycle_s" not in result["metrics"]
    assert "compiles_per_cycle" in result["metrics"]
    assert not any(name.startswith(("device_idle", "fixpoint"))
                   for name in result["metrics"])
    assert result["device"]["busy_s"] > 0
    assert result["device"]["window_s"] > result["device"]["busy_s"]
    assert len(result["breakdown"]["device_ops"]) > 0


# --------------------------------------------------------------------------
# `correct` comes out false: the timed path broken underneath
# --------------------------------------------------------------------------

class Broken:
    """A transport whose answers pass through `spoil` first."""

    def __init__(self, inner, spoil):
        self.inner, self.spoil = inner, spoil
        self.client = inner.client
        self.state: dict = {}

    def run(self, req):
        return self.spoil(self.inner.run(req), self.state)


def stale_call(req, state):
    """A step that returns its state unchanged: every CALL of the window
    answers with the rows of the warm-up's CALL."""
    if req.name == "rank_call":
        state.setdefault("rows", req.rows)
        req.rows = state["rows"]
    return req


def altered_rank(req, state):
    """An answer altered where it is produced: one rank 3% high."""
    if req.name == "rank_call":
        rows = [list(r) for r in req.rows]
        rows[40][1] *= 1.03
        req.rows = rows
    return req


def swapped_id(req, state):
    """An answer altered where it is produced: a returned id replaced
    by one far outside the top 100."""
    if req.name == "rank_call":
        rows = [list(r) for r in req.rows]
        rows[99][0] = N_NODES - 1
        req.rows = rows
    return req


def altered_count(req, state):
    if req.name == "one_hop" and req.rows:
        req.rows = [[req.rows[0][0] + 1]]
    return req


def lost_ack(req, state):
    """A write acknowledged to the client that the reference never
    sees applied: the read-back finds an edge nobody is known to have
    written."""
    if req.name == "edge_write":
        state["n"] = state.get("n", 0) + 1
        if state["n"] > 1:              # the first is the warm-up's
            req.error = "spoiled: acknowledgement lost"
    return req


@pytest.mark.parametrize("mix,spoil,fails", [
    ("analytics_fresh", stale_call, "rank_dev_max"),
    ("analytics_fresh", stale_call, "stale_calls"),
    ("analytics_fresh", altered_rank, "rank_dev_max"),
    ("analytics_fresh", swapped_id, "rank_dev_max"),
    ("oltp_mixed", altered_count, "reads_out_of_bounds"),
    ("oltp_mixed", lost_ack, "readback_mismatches"),
])
def test_a_broken_timed_path_is_not_correct(mix, spoil, fails, tmp_path,
                                            seen):
    if mix not in {w["traffic"] for w in BENCHMARK["workloads"]}:
        pytest.skip(f"no {mix} cell in BENCHMARK.json")
    cell = small_cell(cell_of_mix(mix))
    result = drive(cell, tmp_path, seen, seconds=1.0,
                   transport_hook=lambda t: Broken(t, spoil))
    assert result["correct"] is False
    assert result["compared"][fails]["ok"] is False, result["compared"]


def test_the_lost_write_control_is_not_correct(tmp_path, seen):
    if "oltp_mixed" not in {w["traffic"] for w in BENCHMARK["workloads"]}:
        pytest.skip("no oltp_mixed cell in BENCHMARK.json")
    cell = small_cell(cell_of_mix("oltp_mixed"))
    cell["mix"] = copy.deepcopy(cell["mix"])
    cell["mix"]["controls"]["lost_write"]["n"] = 5      # a short window
    result = drive(cell, tmp_path, seen, control="lost_write")
    assert result["correct"] is False
    assert result["compared"]["readback_mismatches"]["value"] > 0


@pytest.mark.parametrize("seed", [3, SEED, 77])
def test_the_bf16_control_is_not_correct(seed):
    """The reference put in the program's place, one precision below
    the stated float32: it must fail the analytics cell's own limit."""
    cell = run.load_cell(cell_of_mix("analytics_fresh"))
    limit = cell["limits"]["rank_dev_max"]
    src, dst = reference.make_graph(seed, N_NODES, N_EDGES)
    want, _ = reference.pagerank(src, dst, N_NODES)
    low, _ = reference.pagerank(src, dst, N_NODES, precision="bf16")
    ids, ranks = reference.top_ranks(low, 100)
    rows = [[int(i), float(r)] for i, r in zip(ids, ranks)]
    got = run.compare_ranks(rows, want, 100)
    assert got["fault"] == 0 and got["rel_err"] > limit
    # and the stated precision passes it
    f32 = want.astype(np.float32).astype(np.float64)
    ids, ranks = reference.top_ranks(f32, 100)
    rows = [[int(i), float(r)] for i, r in zip(ids, ranks)]
    assert run.compare_ranks(rows, want, 100)["rel_err"] < limit / 10


# --------------------------------------------------------------------------
# the script as the driver runs it
# --------------------------------------------------------------------------

def _result_lines(stdout):
    out = []
    for line in stdout.splitlines():
        if line.startswith("{"):
            try:
                out.append(json.loads(line))
            except ValueError:
                pass
    return [o for o in out if "correct" in o]


def test_script_fails_without_a_tpu():
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         cell_of_mix("oltp_mixed"), "--seed", str(SEED), "--seconds", "1",
         "--trace", "0"],
        cwd=REPO, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0, proc.stdout
    assert not _result_lines(proc.stdout)
    assert "FAILED: needs 1 TPU chip(s)" in proc.stderr


def test_script_fails_in_a_bare_directory(tmp_path):
    """BENCHMARK.json and the files under `paths`, and nothing else."""
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    for path in BENCHMARK["paths"]:
        shutil.copytree(os.path.join(REPO, path), tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        BENCHMARK["command"] + ["--workload", cell_of_mix("oltp_mixed"),
                                "--seed", "1", "--seconds", "1",
                                "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert not _result_lines(proc.stdout)


# --------------------------------------------------------------------------
# trace_reduce
# --------------------------------------------------------------------------

def test_trace_reduce_by_hand():
    """Five ops on one device, in ns: a overlaps b, c stands alone, d
    lies inside e."""
    ops = [["a", 0.0, 100.0], ["b", 50.0, 100.0], ["c", 400.0, 50.0],
           ["e", 1000.0, 500.0], ["d", 1100.0, 100.0], ["a", 2000.0, 10.0]]
    summary = trace_reduce.summarize({"/device:TPU:0": ops})
    # union: [0,150] + [400,450] + [1000,1500] + [2000,2010] = 710 ns
    assert summary["busy_s"] == pytest.approx(710e-9)
    assert summary["ops"]["a"] == {"count": 2, "seconds":
                                   pytest.approx(110e-9)}
    assert summary["ops"]["d"]["seconds"] == pytest.approx(100e-9)
    plane = summary["planes"]["/device:TPU:0"]
    assert [g[1] for g in plane["idle_gaps"]] == pytest.approx(
        [550e-9, 500e-9, 250e-9])
    ctx = {"trace": summary, "trace_window_s": 2840e-9}
    assert layers.trace_idle({}, ctx) == pytest.approx(75.0)
    # two chips: busy time and op seconds are averaged over the planes
    two = trace_reduce.summarize({"/device:TPU:0": ops,
                                  "/device:TPU:1": ops[:2]})
    assert two["busy_s"] == pytest.approx((710e-9 + 150e-9) / 2)
    assert two["ops"]["a"]["seconds"] == pytest.approx((110e-9 + 100e-9) / 2)


def test_trace_reduce_on_the_recorded_slice():
    """A slice of the first traced chip run of
    pokec_medium.analytics_fresh (PR 25), as `extract` gave it; the
    expected numbers were worked out by hand from the slice."""
    with open(os.path.join(BENCH, "fixtures", "trace_slice.json")) as f:
        fixture = json.load(f)
    summary = trace_reduce.summarize(fixture["planes"])
    want = fixture["by_hand"]
    assert summary["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    for name, row in want["ops"].items():
        assert summary["ops"][name]["count"] == row["count"]
        assert summary["ops"][name]["seconds"] == pytest.approx(
            row["seconds"], rel=1e-9)
    for name, row in want["modules"].items():
        assert summary["modules"][name] == {
            "count": row["count"],
            "seconds": pytest.approx(row["seconds"], rel=1e-9)}
    ctx = {"trace": summary, "trace_window_s": want["window_s"],
           "traced_cycles": 2}
    assert layers.trace_idle({}, ctx) == pytest.approx(want["idle_pct"],
                                                       rel=1e-9)
    # the fixpoint's metric file finds its program in the slice
    with open(os.path.join(BENCH, "layer_metrics",
                           "fixpoint_device_ms.json")) as f:
        metric = json.load(f)
    per_cycle = sum(row["seconds"] for name, row in want["modules"].items()
                    if name.startswith("jit_run_impl")) * 1000.0 / 2
    assert per_cycle > 0
    assert layers.read(metric, ctx) == pytest.approx(per_cycle, rel=1e-9)


def test_trace_extract_reads_an_xplane(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        for _ in range(3):
            f(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    xplane = trace_reduce.find_xplane(str(tmp_path))
    assert xplane is not None
    planes = trace_reduce.extract(xplane)
    # no device plane on the CPU: the hlo ops of the host threads stand in
    assert list(planes) == ["host-stand-in"]
    summary = trace_reduce.summarize(planes)
    assert summary["stand_in"] and summary["busy_s"] > 0
    assert layers.trace_idle({}, {"trace": summary,
                                  "trace_window_s": 1.0}) is None


# --------------------------------------------------------------------------
# the reference against loops a reader can check by eye
# --------------------------------------------------------------------------

def test_reference_agrees_with_a_plain_loop():
    src = np.array([0, 0, 1, 2, 2, 2, 3, 0, 40], dtype=np.int64)
    dst = np.array([1, 1, 2, 2, 0, 3, 3, 0, 1], dtype=np.int64)
    n = 81
    state = reference.GraphState(n, src, dst)
    edges = list(zip(src.tolist(), dst.tolist()))

    def paths_from(a_ok):
        rows = 0
        for i, (a, b) in enumerate(edges):
            if not a_ok(a):
                continue
            rows += sum(1 for j, (b2, _m) in enumerate(edges)
                        if j != i and b2 == b)
        return rows

    for node in (0, 1, 2, 3, 40, 5):
        assert state.one_hop({"id": node}) == \
            [[sum(1 for a, _ in edges if a == node)]]
        assert state.two_hop({"id": node}) == \
            [[paths_from(lambda a: a == node)]]
    assert state.two_hop_agg() == [[paths_from(lambda a: a % 80 < 2)]]
    over = [i % 80 for i in range(n) if i % 80 > 40]
    assert state.agg_filter() == [[len(over), sum(over), 41, 79]]

    # the three writes, and the bounds of a read between two states
    after = state.copy()
    after.apply("age_increment", {"id": 40})
    after.apply("add_edge", {"a": 1, "b": 0})
    after.apply("add_vertex", {"id": 200})
    after.apply("add_edges", {"pairs": [[3, 0], [3, 0]]})
    assert after.point_read({"id": 40}) == [[41]] and state.age[40] == 40
    assert after.age[200] == 40 and 200 not in state.age
    assert after.one_hop({"id": 3}) == [[3]] and state.one_hop({"id": 3}) == [[1]]
    low, high = reference.read_bounds("one_hop", {"id": 3}, state, after)
    assert (low, high) == ([[1]], [[3]])
    assert reference.within([[2]], low, high)
    assert not reference.within([[4]], low, high)
    assert not reference.within([], low, high)
    assert run.reference_readback("added_edge_rows", after) == \
        [[1, 0, 1], [3, 0, 2]]

    ranks, iterations = reference.pagerank(src, dst, n)
    assert abs(ranks.sum() - 1.0) < 1e-9 and (ranks > 0).all()
    assert iterations < 500
    # power iteration by hand, dense
    want = np.full(n, 1.0 / n)
    deg = np.bincount(src, minlength=n)
    for _ in range(300):
        new = np.full(n, 0.15 / n) + 0.85 * want[deg == 0].sum() / n
        for a, b in edges:
            new[b] += 0.85 * want[a] / deg[a]
        want = new
    assert np.abs(ranks - want).max() < 1e-11
    ids, best = reference.top_ranks(ranks, 3)
    assert list(ids) == list(np.argsort(-want, kind="stable")[:3])
    assert reference.round_bf16(np.array([1.0, 1.00390625, 3.14159]))[:2] \
        .tolist() == [1.0, 1.0]


def test_compare_ranks():
    want = np.linspace(1.0, 2.0, 500)           # id 499 is the best
    ids = list(range(499, 399, -1))
    rows = [[i, float(want[i])] for i in ids]
    assert run.compare_ranks(rows, want, 100) == \
        {"fault": 0, "rel_err": 0.0, "gap": 0.0}
    off = [list(r) for r in rows]
    off[10][1] *= 1.01
    assert run.compare_ranks(off, want, 100)["fault"] == 1  # out of order
    off = [list(r) for r in rows]
    off[99] = [0, off[99][1]]                   # id 0 has rank 1.0
    got = run.compare_ranks(off, want, 100)
    assert got["gap"] == pytest.approx((want[400] - 1.0) / want[400])
    assert run.compare_ranks(rows[:99], want, 100)["fault"] == 1
    assert run.compare_ranks(rows[:99] + [rows[0]], want, 100)["fault"] == 1


# --------------------------------------------------------------------------
# the generator
# --------------------------------------------------------------------------

def _mix(name):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("seed", [1, SEED])
def test_weighted_plan_holds_the_shares_in_every_block(seed):
    mix = _mix("oltp_mixed")
    keys = traffic.Keys(mix["keys"], 10_000, seed)
    plan = traffic.Plan(mix, 10_000, seed, 0, keys)
    shares = {c["name"]: c["share"] for c in mix["classes"]}
    assert sum(shares.values()) == 100
    for _ in range(3):
        block = [next(plan) for _ in range(100)]
        counts = {}
        for req in block:
            counts[req.name] = counts.get(req.name, 0) + 1
        assert counts == shares
    again = traffic.Plan(mix, 10_000, seed, 0, keys)
    first = [(r.name, r.params) for r in (next(again) for _ in range(50))]
    once_more = traffic.Plan(mix, 10_000, seed, 0,
                             traffic.Keys(mix["keys"], 10_000, seed))
    assert first == [(r.name, r.params)
                     for r in (next(once_more) for _ in range(50))]
    other = traffic.Plan(mix, 10_000, seed, 1, keys)
    assert first != [(r.name, r.params)
                     for r in (next(other) for _ in range(50))]


def test_keys_are_skewed_and_new_ids_do_not_collide():
    mix = _mix("oltp_mixed")
    keys = traffic.Keys(mix["keys"], 10_000, 5)
    rng = np.random.default_rng(0)
    drawn = [keys.draw(rng) for _ in range(20_000)]
    assert min(drawn) >= 0 and max(drawn) < 10_000
    top = np.bincount(drawn, minlength=10_000).max() / len(drawn)
    assert 0.07 < top < 0.14            # Zipf 0.99 over 10k: about 10%
    hottest = int(np.bincount(drawn).argmax())
    assert hottest == int(keys.ids[0])  # a permuted id, not id 0
    plans = [traffic.Plan(mix, 10_000, 5, i, keys) for i in range(4)]
    new = [p.request("vertex_write").params["id"]
           for p in plans for _ in range(100)]
    assert len(set(new)) == 400 and min(new) > 10_000


@pytest.mark.parametrize("seeds", [(1, 2), (SEED, 2**31 + 7)])
def test_oltp_work_is_the_same_for_every_seed(seeds):
    mix = _mix("oltp_mixed")
    first, second = (traffic.Keys(mix["keys"], 10_000, s) for s in seeds)
    assert first.ids.tolist() == second.ids.tolist()
    assert first.ids.tolist() != list(range(10_000))    # still permuted
    plans = [traffic.Plan(mix, 10_000, s, 0, k)
             for s, k in zip(seeds, (first, second))]
    drawn = [[(r.name, r.params) for r in (next(p) for _ in range(300))]
             for p in plans]
    assert drawn[0] != drawn[1]                 # another order ...
    for name in {n for n, _ in drawn[0]}:       # ... of the same requests
        assert [p for n, p in drawn[0] if n == name] \
            == [p for n, p in drawn[1] if n == name]
    # without the keys' own seed the run seed draws the hot set and keys
    loose = dict(mix, keys={k: v for k, v in mix["keys"].items()
                            if k != "seed"})
    first, second = (traffic.Keys(loose["keys"], 10_000, s) for s in seeds)
    assert first.ids.tolist() != second.ids.tolist()
    plans = [traffic.Plan(loose, 10_000, s, 0, first) for s in seeds]
    hops = [[r.params for r in (p.request("two_hop") for _ in range(20))]
            for p in plans]
    assert hops[0] != hops[1]


def test_zipf_theta_0_is_uniform():
    keys = traffic.Keys({"distribution": "zipf", "theta": 0.0}, 1_000, 5)
    assert np.allclose(np.diff(keys.cdf), 1e-3)
    assert keys.ids.tolist() == list(range(1_000))      # not permuted
    with pytest.raises(ValueError):
        traffic.Keys({"distribution": "pareto"}, 10, 5)


def test_sequence_plan_is_write_then_call():
    mix = _mix("analytics_fresh")
    plan = traffic.Plan(mix, 100_000, 9, 0, None)
    reqs = [next(plan) for _ in range(6)]
    assert [r.name for r in reqs] == ["burst_write", "rank_call"] * 3
    pairs = reqs[0].params["pairs"]
    assert len(pairs) == 64 and reqs[2].params["pairs"] != pairs
    assert all(isinstance(v, int) and 0 <= v < 100_000
               for p in pairs for v in p)


def test_a_burst_comes_from_the_datasets_generator():
    """Both endpoints as make_graph draws them: uniform sources,
    squared-sample destinations (half of them below a quarter of the
    ids)."""
    mix = _mix("analytics_fresh")
    plan = traffic.Plan(mix, 100_000, SEED, 0, None)
    pairs = np.asarray([p for _ in range(200)
                        for p in plan.request("burst_write").params["pairs"]])
    assert pairs.shape == (12_800, 2)
    assert 0.47 < (pairs[:, 0] < 50_000).mean() < 0.53
    assert 0.47 < (pairs[:, 1] < 25_000).mean() < 0.53
    src, dst = reference.make_graph(SEED, 100_000, 64)
    drawn = reference.draw_edges(np.random.default_rng(SEED), 100_000, 64)
    assert (src == drawn[0]).all() and (dst == drawn[1]).all()


# --------------------------------------------------------------------------
# readers, rooflines, peaks
# --------------------------------------------------------------------------

def test_stats_delta_and_client_class_readers():
    before = {"lane/fingerprints/Q1/hits": 10.0,
              "lane/fingerprints/Q1/fallbacks/mvcc_private": 2.0,
              "device/jit.compile_total": 37.0}
    after = {"lane/fingerprints/Q1/hits": 40.0,
             "lane/fingerprints/Q1/fallbacks/mvcc_private": 12.0,
             "lane/fingerprints/Q2/hits": 5.0,
             "device/jit.compile_total": 41.0}
    ctx = {"stats_before": before, "stats_after": after, "cycles": 8}
    share = layers.stats_delta(
        {"numerator": ["lane/fingerprints/Q1/hits"],
         "denominator": ["lane/fingerprints/Q1/hits",
                         "lane/fingerprints/Q1/fallbacks/*"],
         "scale": 100.0}, ctx)
    assert share == pytest.approx(75.0)
    assert layers.stats_delta({"numerator": ["device/jit.compile_total"],
                               "denominator": "cycles"}, ctx) == 0.5
    # nothing to read: no 0, nothing
    assert layers.stats_delta({"numerator": ["x"], "denominator": ["y"]},
                              ctx) is None

    cls = {"name": "a", "kind": "read"}
    reqs = [traffic.Request(cls, {}, 0, start=0.0, end=t, rows=[])
            for t in (0.001, 0.002, 0.003, 0.004, 0.100)]
    ctx = {"requests": reqs}
    assert layers.client_class({"classes": ["a"]}, ctx) \
        == pytest.approx(3.0)
    assert layers.client_class({"classes": ["b"]}, ctx) is None
    # "requests" is the count of the window's requests, not the list
    ctx = {"stats_before": before, "stats_after": after, "requests": reqs}
    assert layers.stats_delta({"numerator": ["device/jit.compile_total"],
                               "denominator": "requests"}, ctx) == 0.8


def test_roofline_and_peaks():
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f)
    peak = peaks["TPU v5 lite"]
    assert peak["hbm_bytes_per_s"] == 819e9 and peak["source"]
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "pagerank_spmv", os.path.join(BENCH, "rooflines",
                                      "pagerank_spmv.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    assert module.per_iteration(100_000, 1_768_515) == \
        {"bytes": 8 * 1_768_515 + 1_200_000,
         "operations": 2 * 1_768_515 + 400_000}
    least = module.least_seconds(100_000, 1_768_515, 10, peak)
    assert least["bound"] == "hbm"
    assert least["seconds"] == pytest.approx(10 * 15_348_120 / 819e9)


# --------------------------------------------------------------------------
# BENCHMARK.json against the contract's letter
# --------------------------------------------------------------------------

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_benchmark_json_is_well_formed():
    b = BENCHMARK
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert isinstance(b["run_seconds"], int) and 1 <= b["run_seconds"] <= 51
    assert len(json.dumps(b)) < 64 * 1024
    configs = {c["name"]: c for c in b["configs"]}
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and len(c["source"]) <= 200
        assert any(c["file"].startswith(p + "/") for p in b["paths"])
        with open(os.path.join(REPO, c["file"])) as f:
            held = json.load(f)
        assert held["source"] == c["source"]
        assert held["reduced"] == c["reduced"]
        assert held["guarantees"]["durability"].startswith("write-ahead log on")
        assert "--storage-wal-enabled" in held["owner"]["server_flags"]
    assert {w["config"] for w in b["workloads"]} == set(configs)
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in b["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    layer_names = set()
    for m in b["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert os.path.exists(os.path.join(
            BENCH, "layer_metrics", m["name"] + ".json"))
        layer_names.add(m["name"])
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        for w in m.get("workloads", []):
            assert w in CELLS
    assert len(layer_names) + len(e2e) == len(b["per_layer"]) + \
        len(b["end_to_end"])
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        cell = run.load_cell(w["name"])
        reported = [m["name"] for m in cell["end_to_end"]]
        assert "setup_s" in reported and len(reported) >= 2
        assert len(cell["per_layer"]) >= 1
        # every per-layer metric of the cell moves a metric it reports
        for m in b["per_layer"]:
            if w["name"] in m.get("workloads", [w["name"]]):
                assert m["moves"] in reported
        # every compared number has a limit
        for name, spec in cell["mix"]["compare"].items():
            assert cell["limits"].get(name, spec.get("limit")) is not None, \
                (w["name"], name)
        # the deployment's owner layout and data set, the mix's
        # semantics and every generator a class names are files that
        # are there; every class has an answer and a mode
        layout, dataset, sem = run.seams_of(cell)
        assert callable(layout.start)
        for needed in ("make", "load", "key_space", "sizes"):
            assert callable(getattr(dataset, needed)), needed
        for cls in cell["mix"]["classes"]:
            mode = run.mode_of(sem, cls)
            assert mode in ("write", "exact_in_order", "between",
                            "vector_top"), (cls["name"], mode)
            assert (mode == "write") == (cls["kind"] == "write")
            assert callable({"write": sem.apply, "vector_top": getattr(
                sem, "vector", None)}.get(mode, sem.answer))
            if mode == "between":
                assert callable(sem.bounds) and callable(sem.within)
            for spec in cls["params"].values():
                assert spec["gen"] in dataset.GENERATORS or \
                    spec["gen"] in ("key", "new_id", "edge_burst"), spec
        for item in cell["mix"].get("readback", []):
            assert callable(sem.readback)
            if item.get("params"):
                assert callable(sem.readback_params)
        assert run.modes_of(cell["mix"], sem)
