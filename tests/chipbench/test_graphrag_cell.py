"""The hybrid-retrieval cell (``graphrag_medium.retrieve_fresh``),
rehearsed without the chip through run.py's own functions at 2,000 /
20,000 and the deployment's width: end to end, its planted faults, its
reference against plain loops, its data set's generators, its roofline
and its metric files.
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
import reference  # noqa: E402
import run  # noqa: E402
import seams  # noqa: E402
import traffic  # noqa: E402

CELL = "graphrag_medium.retrieve_fresh"
N_NODES, N_EDGES = 2_000, 20_000
SEED = 2_147_483_693            # the driver's seeds pass 2**31

with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    BENCHMARK = json.load(_f)
SPAN_METRICS = ["vector_index_ms", "vector_refresh_ms", "vector_search_ms",
                "hybrid_expand_ms", "hybrid_ppr_ms", "hybrid_rows_ms",
                "call_export_ms.graphrag"]
CLIENT_METRICS = ["knn_query_p50_ms", "knn_fresh_p50_ms",
                  "hybrid_call_p50_ms", "embed_write_p50_ms"]
TRACE_METRICS = ["device_idle_pct.graphrag", "knn_device_ms",
                 "knn_roofline", "ppr_device_ms"]
#: the cell's own per-layer metrics, in the order they were appended
NEW_METRICS = TRACE_METRICS + SPAN_METRICS[:3] + ["vector_delta_share"] \
    + SPAN_METRICS[3:] + CLIENT_METRICS

sem = seams.load_module(None, "semantics", "graphrag")
dataset = seams.load_module(None, "datasets", "pokec_embedded")
roofline = seams.load_module(None, "rooflines", "knn_scores")


def small_cell():
    cell = run.load_cell(CELL)
    cell["config"] = dict(cell["config"], nodes=N_NODES, edges=N_EDGES)
    return cell


@pytest.fixture(autouse=True)
def _no_stray_children():
    yield
    leaked = list(run._CHILDREN)
    run.stop_all()
    assert not leaked, f"a run left {len(leaked)} process(es) running"


def drive(cell, tmp_path, seconds=2.0, trace=False, **kw):
    return run.run_cell(cell, SEED, seconds, trace, str(tmp_path),
                        device_check=lambda device, chips: None,
                        t_start=time.perf_counter(), **kw)


def values(result):
    return {name: m["value"] for name, m in result["metrics"].items()}


# --------------------------------------------------------------------------
# the cell end to end
# --------------------------------------------------------------------------

def test_the_cell_end_to_end(tmp_path):
    result = drive(small_cell(), tmp_path)
    assert result["correct"] is True, result["compared"]
    assert result["cycles"] >= 2 and result["failed"] == 0
    assert result["attempted"] >= 15 * result["cycles"]
    # what the cell lists, which is these two and may be more
    assert set(result["metrics"]) == {
        m["name"] for m in run.load_cell(CELL)["end_to_end"]}
    assert {"fresh_cycle_s", "setup_s"} <= set(result["metrics"])
    compared = result["compared"]
    # 13 reads a cycle are held to the reference, each as of its state
    assert compared["rank_calls_compared"]["value"] >= 13 * result["cycles"]
    for name in ("row_faults", "stale_calls", "readback_mismatches"):
        assert compared[name] == {"value": 0, "limit": 0, "ok": True}
    # float32 products on the CPU sit well inside the chip's limit, and
    # every search after a write stood far off the state before it
    assert compared["rank_dev_max"]["value"] < \
        compared["rank_dev_max"]["limit"] / 3
    assert compared["stale_sep_min"]["value"] > 0.05
    assert list(result)[-1] == "compared"


def test_the_cell_traced_reports_its_program_metrics(tmp_path):
    result = drive(small_cell(), tmp_path, trace=True)
    assert result["correct"] is True, result["compared"]
    got = values(result)
    assert set(SPAN_METRICS + CLIENT_METRICS + ["vector_delta_share"]) \
        <= set(got), sorted(set(NEW_METRICS) - set(got))
    # the CPU's ops stand in for a device's in the arithmetic and are
    # never reported as a device's time or share
    assert not set(TRACE_METRICS) & set(got)
    assert "fresh_cycle_s" not in got
    # every refresh of the window followed the change log
    assert got["vector_delta_share"] == 100.0
    # the refresh is a child of the index lookup; the three hybrid
    # phases and the export lie inside the hybrid CALL
    assert 0 < got["vector_refresh_ms"] <= got["vector_index_ms"]
    assert got["hybrid_expand_ms"] > 0 and got["hybrid_ppr_ms"] > 0
    assert got["hybrid_rows_ms"] > 0 and got["call_export_ms.graphrag"] > 0
    assert got["hybrid_expand_ms"] + got["hybrid_ppr_ms"] \
        + got["hybrid_rows_ms"] + got["call_export_ms.graphrag"] \
        <= 1.5 * got["hybrid_call_p50_ms"]
    assert got["knn_query_p50_ms"] < got["hybrid_call_p50_ms"]
    assert result["device"]["busy_s"] > 0


# --------------------------------------------------------------------------
# `correct` comes out false: the timed path broken underneath
# --------------------------------------------------------------------------

class SearchBeforeWrite:
    """An index that answers before it has taken in the write: the
    re-embedding is acknowledged at once, the search that follows runs
    first, and only then is the write sent. Every write arrives, so the
    read-back holds; the search has not seen its write."""

    def __init__(self, inner):
        self.inner, self.client, self.held = inner, inner.client, None

    def run(self, req):
        if req.name == "embed_update":
            self.held = req
            req.start = req.end = time.perf_counter()
            req.rows = []
            return req
        if req.name == "knn_fresh_update" and self.held is not None:
            out = self.inner.run(req)
            self.inner.run(self.held)
            self.held = None
            return out
        return self.inner.run(req)


class Spoiled:
    def __init__(self, inner, spoil):
        self.inner, self.client, self.spoil = inner, inner.client, spoil

    def run(self, req):
        return self.spoil(self.inner.run(req))


def altered_similarity(req):
    """One similarity a thousandth low (the order of the rows kept)."""
    if req.name == "knn_03":
        rows = [list(r) for r in req.rows]
        rows[-1][1] *= 0.999
        req.rows = rows
    return req


def altered_score(req):
    if req.name == "hybrid_retrieve":
        rows = [list(r) for r in req.rows]
        rows[-1][1] *= 0.999
        req.rows = rows
    return req


def swapped_id(req):
    """A returned id replaced by one that is no neighbour."""
    if req.name == "knn_07":
        rows = [list(r) for r in req.rows]
        taken = {r[0] for r in rows}
        rows[-1][0] = next(i for i in range(N_NODES) if i not in taken)
        req.rows = rows
    return req


@pytest.mark.parametrize("hook,fails", [
    (SearchBeforeWrite, "stale_calls"),
    (lambda t: Spoiled(t, altered_similarity), "rank_dev_max"),
    (lambda t: Spoiled(t, altered_score), "rank_dev_max"),
    (lambda t: Spoiled(t, swapped_id), "rank_dev_max"),
], ids=["search_before_write", "altered_similarity", "altered_score",
        "swapped_id"])
def test_a_broken_timed_path_is_not_correct(hook, fails, tmp_path):
    result = drive(small_cell(), tmp_path, seconds=1.0, transport_hook=hook)
    assert result["correct"] is False
    assert result["compared"][fails]["ok"] is False, result["compared"]
    if fails == "stale_calls":
        # nothing else is wrong with such a run: the write did arrive
        assert result["compared"]["readback_mismatches"]["value"] == 0
        assert result["compared"]["row_faults"]["value"] == 0


def test_the_lost_write_control_is_not_correct(tmp_path):
    result = drive(small_cell(), tmp_path, seconds=1.0, control="lost_write")
    assert result["correct"] is False
    assert result["compared"]["readback_mismatches"]["value"] > 0
    assert result["compared"]["stale_calls"]["value"] > 0


@pytest.mark.parametrize("seed", [3, SEED, 77])
def test_the_bf16_reading_is_not_correct(seed):
    """The reference put in the program's place, one precision below the
    stated float32 (operands rounded to bfloat16, as the kernel stood
    before PR 33): it must fail the cell's own limit, where float32
    operands pass it with room."""
    limit = run.load_cell(CELL)["limits"]["rank_dev_max"]
    state = dataset.make({"nodes": N_NODES, "edges": N_EDGES,
                          "graph_seed": 7})
    rng = np.random.default_rng(seed)
    worst_low = worst_f32 = 0.0
    for q in dataset._CURRENT["mixture"].members(rng, 8):
        want = sem.cosine_all(state, q)
        for rounded, name in ((reference.round_bf16, "low"),
                              (lambda x: np.asarray(x, np.float32)
                               .astype(np.float64), "f32")):
            sims = rounded(state.base) @ rounded(q)
            ids = sem.top_ids(sims, 10)
            rows = [[int(i), float(sims[i])] for i in ids]
            got = run.compare_ranks(rows, want, 10)
            assert got["fault"] == 0
            dev = max(got["rel_err"], got["gap"])
            if name == "low":
                worst_low = max(worst_low, dev)
            else:
                worst_f32 = max(worst_f32, dev)
    assert worst_low > limit
    assert worst_f32 < limit / 10


# --------------------------------------------------------------------------
# the reference against plain loops
# --------------------------------------------------------------------------

def tiny_state(seed=11, n=50, n_edges=160, width=8):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n - 1, n_edges)       # node n-1 has no out-edge
    dst = rng.integers(0, n, n_edges)
    src[:4], dst[:4] = 3, 9                     # parallel edges
    base = rng.standard_normal((n, width))
    state = sem.RagState(n, src, dst, base)
    sem.apply("doc_insert", state, {
        "id": n + 1, "v": rng.standard_normal(width).tolist(),
        "friends": [2, 2, 17, n - 1]})
    sem.apply("embed_update", state, {
        "id": 5, "v": rng.standard_normal(width).tolist()})
    return state, rng.standard_normal(width)


def loops_cosine(state, q):
    out = {}
    for i in sorted(state.age):
        v = state.vector_of(i)
        dot = sum(float(a) * float(b) for a, b in zip(v, q))
        out[i] = dot / (sum(float(a) ** 2 for a in v) ** 0.5
                        * sum(float(b) ** 2 for b in q) ** 0.5)
    return out


def loops_hybrid(state, q, seeds_k, hops, damping=0.85):
    sims = loops_cosine(state, q)
    seeds = sorted(sims, key=lambda i: (-sims[i], i))[:seeds_k]
    src, dst = state.edge_arrays()
    pairs = list(zip(src.tolist(), dst.tolist()))
    reach = set(seeds)
    for _ in range(hops):
        reach |= {b for a, b in pairs if a in reach} \
            | {a for a, b in pairs if b in reach}
    nodes = sorted(state.age)
    out_deg = {i: 0 for i in nodes}
    for a, _ in pairs:
        out_deg[a] += 1
    p = {i: (1.0 / len(seeds) if i in seeds else 0.0) for i in nodes}
    x = dict(p)
    for _ in range(400):
        dangling = sum(x[i] for i in nodes if out_deg[i] == 0)
        acc = {i: 0.0 for i in nodes}
        for a, b in pairs:
            acc[b] += x[a] / out_deg[a]
        x = {i: (1 - damping) * p[i] + damping * (acc[i] + dangling * p[i])
             for i in nodes}
    return seeds, reach, x


def test_cosine_against_plain_loops():
    state, q = tiny_state()
    got = sem.cosine_all(state, q)
    want = loops_cosine(state, q)
    assert len(got) == state.top_id + 1 == 52
    for i, value in want.items():
        assert got[i] == pytest.approx(value, rel=1e-12, abs=1e-15)
    # an id that holds no vertex is below every cosine
    assert got[50] == sem.ABSENT and 50 not in want
    assert list(sem.top_ids(got, 5)) == sorted(
        want, key=lambda i: (-want[i], i))[:5]


@pytest.mark.parametrize("seed", [11, 12, 13])
def test_hybrid_scores_against_plain_loops(seed):
    state, q = tiny_state(seed)
    seeds, reach, rank = loops_hybrid(state, q, 4, 2)
    got = sem.hybrid_scores(state, q, seeds_k=4, hops=2)
    edges = sem._Edges(state)
    assert set(np.flatnonzero(sem.khop_mask(edges, seeds, 2))) == reach
    ppr, rounds = sem.personalized_pagerank(edges, seeds)
    assert rounds < sem.PPR_MAX_ROUNDS
    assert ppr.sum() == pytest.approx(1.0, abs=1e-9)
    for i in sorted(state.age):
        assert ppr[i] == pytest.approx(rank[i], rel=1e-8, abs=1e-12)
        assert got[i] == pytest.approx(rank[i] if i in reach else 0.0,
                                       rel=1e-8, abs=1e-12)
    assert got[50] == 0.0                       # no such vertex


def test_a_copy_of_the_state_is_independent_of_its_source():
    state, q = tiny_state()
    before = sem.cosine_all(state, q).copy()
    scores = sem.hybrid_scores(state, q, seeds_k=4).copy()
    other = state.copy()
    sem.apply("embed_update", other, {"id": 7, "v": q.tolist()})
    sem.apply("doc_insert", other, {"id": 53, "v": q.tolist(),
                                    "friends": [1, 2]})
    assert other.base is state.base            # shared, never written
    assert 7 not in state.rows and 53 not in state.age
    assert state.top_id == 51 and other.top_id == 53
    assert np.array_equal(sem.cosine_all(state, q), before)
    assert np.array_equal(sem.hybrid_scores(state, q, seeds_k=4), scores)
    assert sem.cosine_all(other, q)[7] == pytest.approx(1.0)
    assert sem.readback("embedding_rows", state) != \
        sem.readback("embedding_rows", other)
    assert len(sem.readback("embedding_rows", state)) == 2 * 8


def test_readback_rows_are_exact_and_hashable():
    state, _ = tiny_state()
    rows = sem.readback("embedding_rows", state)
    assert {tuple(r) for r in rows}             # run.compare's set difference
    assert [r[0] for r in rows] == [5] * 8 + [51] * 8
    assert [r[2] for r in rows[:8]] == state.rows[5].tolist()
    assert sem.readback_params("written_ids", state) == {"ids": [5, 51]}
    assert sem.readback("added_edge_rows", state) == [
        [51, 2, 2], [51, 17, 1], [51, 49, 1]]


# --------------------------------------------------------------------------
# the data set and its generators
# --------------------------------------------------------------------------

def test_the_mixture_is_the_stated_one():
    config = run.load_cell(CELL)["config"]
    state = dataset.make(dict(config, nodes=N_NODES, edges=N_EDGES))
    assert state.base.shape == (N_NODES, config["embedding_width"])
    assert config["embedding_width"] == dataset.WIDTH == roofline.WIDTH == 384
    assert np.allclose(np.linalg.norm(state.base, axis=1), 1.0)
    # one data set from graph_seed: the same again, and the medium
    # cells' graph
    again = dataset.make(dict(config, nodes=N_NODES, edges=N_EDGES))
    assert np.array_equal(again.base, state.base)
    src, dst = reference.make_graph(config["graph_seed"], N_NODES, N_EDGES)
    assert np.array_equal(state.edge_arrays()[0], src)
    assert np.array_equal(state.edge_arrays()[1], dst)
    # two members of a topic at cosine 1 / (1 + 0.36), two topics near 0
    centres = dataset._CURRENT["mixture"].centres
    topic = np.argmax(state.base @ centres.T, axis=1)
    first = np.flatnonzero(topic == 0)
    other = np.flatnonzero(topic == 1)
    assert len(first) > len(other) > 20         # Zipf weights
    same = state.base[first[:40]] @ state.base[first[40:80]].T
    cross = state.base[first[:40]] @ state.base[other[:40]].T
    assert same.mean() == pytest.approx(1 / 1.36, abs=0.02)
    assert abs(cross.mean()) < 0.02


def make_plan(margin=None, seed=SEED):
    cell = small_cell()
    mix = copy.deepcopy(cell["mix"])
    if margin is not None:
        mix["classes"][-1]["params"]["q"].update(margin=margin, batch=16)
    state = dataset.make(cell["config"])
    keys = traffic.Keys(mix["keys"], N_NODES, seed)
    return traffic.Plan(mix, N_NODES, seed, 0, keys, dataset), state


def test_the_margin_generator_never_yields_a_query_under_its_margin():
    margin = 5e-3           # wide enough that many candidates are refused
    plan, state = make_plan(margin)
    held = 0
    for cycle in range(12):
        for req in (next(plan) for _ in range(15)):
            if req.cls["kind"] == "write":
                sem.apply(req.cls["reference"], state, req.params)
            if req.name == "hybrid_retrieve":
                # against the state as of the request: loaded and written
                sims = np.sort(sem.cosine_all(state, req.params["q"]))
                assert sims[-10] - sims[-11] >= margin - 1e-12
                held += 1
    drawn, redrawn = plan.margin_seen
    assert held == 12 and redrawn > 0 and drawn == held + redrawn


def test_the_generators_draw_what_the_mix_states():
    plan, state = make_plan()
    reqs = [next(plan) for _ in range(15)]
    names = [r.name for r in reqs]
    assert names[:4] == ["doc_insert", "knn_fresh_doc", "embed_update",
                         "knn_fresh_update"]
    assert names[4:14] == [f"knn_{i:02d}" for i in range(1, 11)]
    assert names[14] == "hybrid_retrieve" and len(set(names)) == 15
    insert, fresh, update, fresh2 = reqs[:4]
    assert insert.params["id"] == N_NODES + 1
    assert len(insert.params["friends"]) == 8
    assert all(0 <= f < N_NODES for f in insert.params["friends"])
    assert 0 <= update.params["id"] < N_NODES
    for written, query in ((insert, fresh), (update, fresh2)):
        v, q = np.asarray(written.params["v"]), np.asarray(query.params["q"])
        assert len(v) == 384 and np.linalg.norm(q) == pytest.approx(1.0)
        # the written vector is the query's nearest: about 0.93, where
        # its topic stands at about 0.69
        assert 0.90 < float(v @ q) < 0.96
        assert float(v @ q) > (state.base @ q).max() + 0.1
    # the same seed draws the same requests
    again, _ = make_plan()
    assert [next(again).params for _ in range(15)] == [r.params for r in reqs]


# --------------------------------------------------------------------------
# the roofline and the metric files
# --------------------------------------------------------------------------

def test_the_roofline_counts_the_work_of_a_search():
    peak = {"hbm_bytes_per_s": 819e9, "flops_per_s_bf16": 197e12}
    work = roofline.per_search(100_000)
    assert work == {"bytes": 100_000 * (384 * 4 + 8),
                    "operations": 2 * 100_000 * 384}
    least = roofline.least_seconds(100_000, 1_768_515, 13, peak)
    assert least["bound"] == "hbm"
    assert least["seconds"] == pytest.approx(13 * work["bytes"] / 819e9)
    with open(os.path.join(BENCH, "layer_metrics", "knn_roofline.json")) as f:
        spec = json.load(f)
    assert spec["params"]["roofline"] == "knn_scores"
    # read through the reader: 26 searches in 40 ms of the kernel
    ctx = {"trace": {"device_planes": ["/device:TPU:0"], "stand_in": False,
                     "modules": {"jit_knn(1)": {"count": 26, "seconds": 0.04}},
                     "ops": {"%sqrt_maximum_fusion": {"count": 26,
                                                      "seconds": 0.001}}},
           "n_nodes": 100_000, "n_edges": 1_768_515, "peak": peak}
    share = layers.read(spec, ctx)
    assert share == pytest.approx(100 * 26 * work["bytes"] / 819e9 / 0.04)
    assert 0 < share < 100


@pytest.mark.parametrize("name", NEW_METRICS)
def test_metric_file_is_data_for_a_reader_that_exists(name):
    entry = next(m for m in BENCHMARK["per_layer"] if m["name"] == name)
    assert entry["moves"] == "fresh_cycle_s"
    with open(os.path.join(BENCH, "layer_metrics", name + ".json")) as f:
        spec = json.load(f)
    assert spec["kind"] in layers.READERS and spec["what"]
    kinds = {"device_trace": ("trace_idle", "trace_ops", "trace_roofline"),
             "program_span": ("stats_delta",),
             "program_counter": ("stats_delta",),
             "host_clock": ("client_class",)}
    assert spec["kind"] in kinds[entry["source"]]
    # no name reads as a device's unless it reads a device trace
    assert name.startswith(("device_idle", "fixpoint")) <= \
        (entry["source"] == "device_trace")
    if spec["kind"] == "stats_delta":
        params = spec["params"]
        keys = params["numerator"] + (
            params["denominator"]
            if isinstance(params["denominator"], list) else [])
        assert params["denominator"] == "cycles" or \
            isinstance(params["denominator"], list)
        for key in keys:
            assert key.split("/", 1)[0] in ("device", "delta", "lane", "ppr")
    if spec["kind"] == "client_class":
        classes = {c["name"] for c in run.load_cell(CELL)["mix"]["classes"]}
        assert set(spec["params"]["classes"]) <= classes


def hold_pins(root=REPO):
    """What this file holds of the BENCHMARK.json under `root`: the
    cell's metrics stand in the order they were appended, each listed
    for the cell, and the cell reports all of them and the end-to-end
    metrics it was written for (and may report more)."""
    bench = bench_pins.read(root)
    bench_pins.stand_in_order(bench["per_layer"], NEW_METRICS)
    for name in NEW_METRICS:
        bench_pins.listed_for(bench_pins.entry(bench["per_layer"], name),
                              [CELL])
    for name in ("fresh_cycle_s", "setup_s"):
        bench_pins.listed_for(bench_pins.entry(bench["end_to_end"], name),
                              [CELL])
    bench_pins.stand_in_order(run.load_cell(CELL, root)["per_layer"],
                              NEW_METRICS)


def test_the_cell_reports_every_metric_the_issue_lists():
    assert sorted(NEW_METRICS) == sorted(
        SPAN_METRICS + CLIENT_METRICS + TRACE_METRICS
        + ["vector_delta_share"])
    hold_pins()


def test_a_program_without_the_spans_reports_nothing_and_does_not_raise():
    """The parent commit has none of the vector or graphrag spans, no
    vector counters, and no program of these names in its trace: each
    reader gives None (or 0 over cycles), and none raises."""
    ctx = {"stats_before": {"device/jit.compile_total": 3.0},
           "stats_after": {"device/jit.compile_total": 9.0}, "cycles": 6,
           "requests": [], "trace_window_s": 2.0, "traced_cycles": 2,
           "n_nodes": 100_000, "n_edges": 1_768_515,
           "peak": {"hbm_bytes_per_s": 819e9, "flops_per_s_bf16": 197e12},
           "trace": {"device_planes": ["/device:TPU:0"], "stand_in": False,
                     "busy_s": 0.5,
                     "modules": {"jit_run(7)": {"count": 2, "seconds": 0.3}},
                     "ops": {"%fusion": {"count": 9, "seconds": 0.2}}}}
    for name in NEW_METRICS:
        with open(os.path.join(BENCH, "layer_metrics",
                               name + ".json")) as f:
            spec = json.load(f)
        got = layers.read(spec, ctx)
        if name == "device_idle_pct.graphrag":
            assert got == pytest.approx(75.0)
        else:
            assert got is None or got == 0.0, (name, got)
