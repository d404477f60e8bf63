"""tools/mgmem: compiled-artifact HBM accounting.

Three layers under test:

* the footprint model (fit/predict, linearity residual) and the fact
  extractor against REAL lowerings of a few cheap manifest kernels;
* the admission cross-checks — the machine-check of the kernel
  server's estimators against the models, including the gate's own
  sensitivity: a deliberately-broken fixture (estimator halved,
  donation dropped) MUST be caught with the offending kernel + bytes;
* the runtime surfacing — the ``kernel_server.hbm_modeled_peak_bytes``
  gauge and the health reply's ``memory`` section.

The full 42-kernel sweep is the dev gate's job (`python -m tools.mgmem
check`, wired into tools/gate.sh); here only a handful of kernels are
lowered so the suite stays tier-1 fast.
"""

import numpy as np
import pytest

from memgraph_tpu.ops.csr import _bucket
from memgraph_tpu.ops import tier as T
from memgraph_tpu.server import kernel_server as ks
from tools.mgmem.admission import (
    CHECK_SHAPES, Estimators, check_ppr, check_resident,
    check_streamed, product_estimators)
from tools.mgmem.check import (
    CheckReport, Violation, _check_envelopes, _check_kernel,
    memory_envelope_from, run_check)
from tools.mgmem.facts import MemFacts
from tools.mgmem.model import FIT_TOLERANCE, FootprintModel, fit


# --- the footprint model (no lowering) --------------------------------------


def _facts(kernel, pts, replicas=1, **over):
    base = dict(kernel=kernel, lanes=1, replicas=replicas,
                output_bytes=0, temp_bytes=0, alias_bytes=0,
                generated_code_bytes=0, donated_aliased=1,
                donation_dropped=0, dropped_bytes=0)
    base.update(over)
    return [MemFacts(n_pad=n, n_edges=e, argument_bytes=a, **base)
            for n, e, a in pts]


def test_fit_is_exact_on_linear_points():
    # peak = 100 + 16n + 32e, synthesized at the real shape points
    fl = _facts("segment:x", [(64, 256, 100 + 16 * 64 + 32 * 256),
                              (128, 256, 100 + 16 * 128 + 32 * 256),
                              (128, 512, 100 + 16 * 128 + 32 * 512)])
    m = fit("segment:x", fl)
    assert m.residual <= 1e-3
    assert (round(m.const), round(m.per_node), round(m.per_edge)) \
        == (100, 16, 32)
    want = 100 + 16 * (1 << 20) + 32 * (1 << 22)
    assert abs(m.predict(1 << 20, 1 << 22) - want) / want < 1e-6


def test_fit_flags_nonlinear_growth():
    # quadratic in n: the residual must blow past FIT_TOLERANCE
    fl = _facts("segment:bad", [(64, 256, 64 * 64),
                                (128, 256, 128 * 128),
                                (256, 256, 256 * 256)])
    m = fit("segment:bad", fl)
    assert m.residual > FIT_TOLERANCE


def test_single_point_model_is_constant():
    fl = _facts("mxu:pagerank", [(64, 256, 13_723_560)])
    m = fit("mxu:pagerank", fl)
    assert m.predict(10, 10) == m.predict(1 << 20, 1 << 25) == 13_723_560


def test_mesh_peak_budgets_whole_request():
    f = _facts("mesh:x", [(64, 256, 1000)], replicas=8)[0]
    assert f.peak_bytes == 8000


# --- estimator padding mirrors (no lowering) --------------------------------


def test_padded_graph_dims_mirror_csr_bucket_exactly():
    for n, e in ((0, 0), (7, 9), (63, 64), (64, 64), (65, 257),
                 (10_000, 80_000), ((1 << 20) + 1, (1 << 22) + 1)):
        assert ks._padded_graph_dims(n, e) \
            == (_bucket(n + 1), _bucket(max(e, 1)))


def test_lane_state_prices_the_power_of_two_bucket():
    n, e = 100_000, 1_500_000
    one = ks._lane_state_bytes(n, e, 1)
    # 33 requested lanes build the 64-wide kernel: same price as 64
    assert ks._lane_state_bytes(n, e, 33) \
        == ks._lane_state_bytes(n, e, 64) == 64 * one
    assert ks._lane_state_bytes(n, e, 65) == 128 * one
    # boundary stays on its own bucket
    assert ks._lane_state_bytes(n, e, 32) == 32 * one


def test_ppr_chunk_lanes_fits_the_budget():
    n, e = 100_000, 1_500_000
    graph = ks._graph_footprint_bytes("ppr", n, e)
    for b in (1, 8, 64):
        budget = graph + ks._lane_state_bytes(n, e, b)
        assert ks._ppr_chunk_lanes(n, e, budget) == b
        # one byte short of the bucket drops to the previous one
        if b > 1:
            assert ks._ppr_chunk_lanes(n, e, budget - 1) < b


def test_estimate_request_bytes_cached_generation_path():
    # a graph_key-only request ships no arrays (the r16 cached-
    # generation sizing path): the estimate is the padded-graph
    # fixpoint footprint alone, not zero
    n, e = 50_000, 400_000
    est = ks._estimate_request_bytes(
        {"algorithm": "pagerank", "n_nodes": n, "n_edges": e}, {})
    assert est == ks._graph_footprint_bytes("pagerank", n, e)
    # with wire arrays the staging copy is priced on top
    src = np.zeros(e, np.int64)
    est_wire = ks._estimate_request_bytes(
        {"algorithm": "pagerank", "n_nodes": n}, {"src": src})
    assert est_wire == src.nbytes + ks._graph_footprint_bytes(
        "pagerank", n, e)


def test_unknown_algorithm_prices_at_column_max():
    n, e = 10_000, 80_000
    worst = max(ks._graph_footprint_bytes(a, n, e)
                for a in ks._ALGO_FOOTPRINT)
    assert ks._graph_footprint_bytes("not-an-algo", n, e) >= worst


# --- real lowerings: facts -> model -> admission matrix ---------------------


@pytest.fixture(scope="module")
def pagerank_model():
    from tools.mgmem.model import fit_kernel
    return fit_kernel("segment:pagerank")


@pytest.fixture(scope="module")
def mesh_pagerank_model():
    from tools.mgmem.model import fit_kernel
    return fit_kernel("mesh:pagerank")


@pytest.fixture(scope="module")
def tier_models():
    from tools.mgmem.model import fit_kernel
    return {k: fit_kernel(k) for k in
            ("tier:wsum", "tier:pagerank_sweep",
             "tier:pagerank_sweep_int8", "tier:pagerank_epilogue")}


def _estimators(**over):
    base = product_estimators()
    return Estimators(**{**{
        "graph_footprint_bytes": base.graph_footprint_bytes,
        "lane_state_bytes": base.lane_state_bytes,
        "streamed_request_bytes": base.streamed_request_bytes,
        "padded_graph_dims": base.padded_graph_dims,
        "lane_buckets": base.lane_buckets}, **over})


def test_model_fits_real_lowering_exactly(pagerank_model):
    m = pagerank_model
    assert m.residual <= FIT_TOLERANCE
    # XLA's buffer assignment for the fixpoint is O(n) + O(e)
    assert m.per_node > 0 and m.per_edge > 0


def test_admission_matrix_product_estimator_bounds(pagerank_model,
                                                   mesh_pagerank_model):
    # both backends the resident route can pick: the estimate must
    # bound the worst of them without exceeding 2x of it
    models = {"segment:pagerank": pagerank_model,
              "mesh:pagerank": mesh_pagerank_model}
    out = check_resident(models, product_estimators(), Violation)
    bad = [v for v in out if v.check.startswith("admission-")]
    assert not bad, "\n".join(v.render() for v in bad)


def test_broken_fixture_halved_estimator_is_caught(pagerank_model):
    models = {"segment:pagerank": pagerank_model}
    halved = _estimators(
        graph_footprint_bytes=lambda a, n, e:
            ks._graph_footprint_bytes(a, n, e) // 2)
    out = check_resident(models, halved, Violation)
    under = [v for v in out if v.check == "admission-underestimate"
             and v.kernel == "segment:pagerank"]
    assert under, "halved estimator escaped the gate"
    # the report names the kernel and quantifies the shortfall
    assert "short" in under[0].snippet and "MB" in under[0].snippet


def test_admission_flip_point_from_fitted_coefficients(pagerank_model):
    # scale the estimator down until it JUST crosses the model at an
    # edge-heavy shape: the gate must flip exactly there
    m = pagerank_model
    n, e = 500_000, 30_000_000
    n_pad, e_pad = ks._padded_graph_dims(n, e)
    floor = ks._graph_footprint_bytes("pagerank", n, e)
    peak = m.predict(n_pad, e_pad)
    assert floor >= peak
    scale_ok = 1.0
    scale_bad = peak / floor * 0.99       # just below the modeled peak
    for scale, expect in ((scale_ok, 0), (scale_bad, 1)):
        est = _estimators(
            graph_footprint_bytes=lambda a, nn, ee, s=scale:
                int(ks._graph_footprint_bytes(a, nn, ee) * s))
        out = check_resident({"segment:pagerank": m}, est, Violation)
        under = [v for v in out
                 if v.check == "admission-underestimate"
                 and v.detail == f"pagerank@({n},{e})"]
        assert len(under) == expect, (scale, [v.render() for v in out])


def test_streamed_estimator_bounds_phases(tier_models):
    out = check_streamed(tier_models, product_estimators(), Violation)
    assert not out, "\n".join(v.render() for v in out)


def test_broken_fixture_halved_streamed_estimator(tier_models):
    halved = _estimators(
        streamed_request_bytes=lambda n, e, p, **kw:
            T.streamed_request_bytes(n, e, p, **kw) // 2)
    out = check_streamed(tier_models, halved, Violation)
    under = [v for v in out if v.check == "admission-underestimate"]
    assert under and under[0].kernel.startswith("tier:")
    assert "short" in under[0].snippet


def test_ppr_pricing_bounds_one_real_bucket():
    from tools.mgmem.model import fit_kernel
    m = fit_kernel("segment:ppr_batch:b4")
    models = {"segment:ppr_batch:b4": m}
    out = check_ppr(models, product_estimators(), Violation)
    assert not out, "\n".join(v.render() for v in out)
    halved = _estimators(
        graph_footprint_bytes=lambda a, n, e:
            ks._graph_footprint_bytes(a, n, e) // 2,
        lane_state_bytes=lambda n, e, b:
            ks._lane_state_bytes(n, e, b) // 2)
    out = check_ppr(models, halved, Violation)
    under = [v for v in out if v.check == "admission-underestimate"]
    assert under and under[0].kernel == "segment:ppr_batch:b4"


def test_admission_verdict_matrix_from_streamed_model():
    # budgets straddling the two estimates flip the verdict exactly:
    # resident -> streamed -> shed
    n, e = 2_000_000, 16_000_000
    res = ks._graph_footprint_bytes("pagerank", n, e)
    stream = T.streamed_request_bytes(n, e, "f32",
                                      algorithm="pagerank")
    assert stream < res
    for budget, want in ((res, "resident"), (res - 1, "streamed"),
                         (stream, "streamed"), (stream - 1, "shed")):
        verdict, est = T.admission_verdict(
            res, budget, n_nodes=n, n_edges=e, algorithm="pagerank")
        assert verdict == want, (budget, verdict)
    # a non-streamable op can only shed past the resident budget
    verdict, _ = T.admission_verdict(res, res - 1, n_nodes=n,
                                     n_edges=e, streamable=False)
    assert verdict == "shed"


# --- the check driver and its envelopes -------------------------------------


def test_run_check_partial_reports_build_violation():
    report = run_check(only={"no:such:kernel"})
    assert not report.ok
    assert report.violations[0].kernel == "no:such:kernel"
    assert report.violations[0].check == "build"


def test_donation_violations_surface_with_bytes(monkeypatch):
    from tools.mgmem import facts as F
    fl = _facts("tier:pagerank_epilogue", [(64, 256, 1024)],
                donation_dropped=1, dropped_bytes=256)
    monkeypatch.setattr(F, "extract_all", lambda kernel: fl)
    report = CheckReport()
    _check_kernel("tier:pagerank_epilogue", report)
    dropped = [v for v in report.violations
               if v.check == "donation-dropped"]
    assert [(v.kernel, v.detail) for v in dropped] \
        == [("tier:pagerank_epilogue", "256B")]


_ENVELOPE = {"max_growth": 0.10,
             "kernels": {"segment:pagerank": 9_676,
                         "tier:pagerank_epilogue": 1_024}}


@pytest.mark.parametrize("peaks,envelope,want", [
    # every kernel at its reference: nothing to report
    ({"segment:pagerank": 9_676, "tier:pagerank_epilogue": 1_024},
     _ENVELOPE, []),
    # a peak 100% past the envelope names the kernel and the growth
    ({"segment:pagerank": 19_352, "tier:pagerank_epilogue": 1_024},
     _ENVELOPE,
     [("segment:pagerank", "peak=19352B>ceiling=10643B", "+100.0%")]),
    # a manifest kernel nobody wrote an envelope for
    ({"segment:pagerank": 9_676, "tier:pagerank_epilogue": 1_024,
      "segment:katz": 512}, _ENVELOPE,
     [("segment:katz", "missing", "512B")]),
    # an envelope for a kernel the manifest no longer has
    ({"segment:pagerank": 9_676}, _ENVELOPE,
     [("tier:pagerank_epilogue", "stale", "envelopes --write")]),
    # no envelope written yet: nothing is checked
    ({"segment:pagerank": 19_352}, None, []),
], ids=["clean", "grown", "missing", "stale", "no-envelope"])
def test_check_envelopes(peaks, envelope, want):
    report = CheckReport()
    for kernel, peak in peaks.items():
        report.facts[kernel] = _facts(kernel, [(64, 256, peak)])
    _check_envelopes(report, envelope)
    assert all(v.check == "envelope" for v in report.violations)
    assert [(v.kernel, v.detail) for v in report.violations] \
        == [(k, d) for k, d, _ in want]
    for v, (_, _, said) in zip(report.violations, want):
        assert said in v.snippet


def test_envelope_roundtrip_shapes():
    report = CheckReport()
    report.facts["segment:pagerank"] = _facts(
        "segment:pagerank", [(64, 256, 9_676)])
    env = memory_envelope_from(report)
    assert env["kernels"] == {"segment:pagerank": 9_676}
    assert 0 < env["max_growth"] < 1


# --- runtime surfacing: the modeled-peak gauge + health memory section ------


def test_kernel_server_surfaces_modeled_memory(tmp_path):
    from memgraph_tpu.observability.metrics import global_metrics
    srv = ks.KernelServer(socket_path=str(tmp_path / "mem.sock"),
                          hbm_budget_bytes=1 << 30)
    src = np.array([0, 1, 2, 3], np.int64)
    dst = np.array([1, 2, 3, 0], np.int64)
    gen = srv._resolve_generation(
        {"graph_key": "g-mem", "graph_version": 1, "n_nodes": 4},
        {"src": src, "dst": dst})
    assert gen is not None
    want = ks._generation_modeled_bytes(gen)
    snap = {name: v for name, _k, v in global_metrics.snapshot()}
    assert snap["kernel_server.hbm_modeled_peak_bytes"] == float(want)
    h = srv._health_reply()
    mem = h["memory"]
    assert mem["hbm_budget_bytes"] == 1 << 30
    assert mem["modeled_peak_bytes"] == want
    assert mem["headroom_bytes"] == (1 << 30) - want
    assert mem["resident_generations"] == {"g-mem": want}
    # the modeled peak is priced at the column-wise worst case
    assert want >= ks._graph_footprint_bytes("pagerank", 4, 4)


def test_stat_names_cover_memory_gauges():
    from memgraph_tpu.observability.metrics import STAT_NAMES
    assert "kernel_server.hbm_modeled_peak_bytes" in STAT_NAMES
    assert "tier.modeled_request_bytes" in STAT_NAMES
