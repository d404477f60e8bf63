"""Multi-chip analytics: partition-centric ShardedCSR + mesh kernels.

Runs on the 8-virtual-device CPU mesh the conftest forces
(--xla_force_host_platform_device_count=8). Covers the ISSUE-6
acceptance criteria:

  * sharded-vs-single numerical equivalence (pagerank/katz/labelprop/
    components/sssp), including an uneven-shard case
    (n_vertices % n_devices != 0) and the mesh-of-1 degeneracy;
  * EXACTLY ONE cross-device collective per power iteration, asserted
    on the compiled HLO;
  * the SPMV_ALGORITHMS registry contract (every sharded target
    resolves; exemptions are justified) — the runtime half of mglint's
    MG005 coverage check;
  * the shard_map version-gate warns once, not per call site.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from memgraph_tpu.ops import csr, SPMV_ALGORITHMS
from memgraph_tpu.ops.pagerank import pagerank
from memgraph_tpu.ops.katz import katz_centrality
from memgraph_tpu.ops.labelprop import label_propagation
from memgraph_tpu.ops.components import weakly_connected_components
from memgraph_tpu.ops.traversal import sssp
from memgraph_tpu.parallel import analytics
from memgraph_tpu.parallel.mesh import (get_mesh_context, resolve_mesh,
                                        resolve_shard_map)

# n % 8 != 0 on purpose: the uneven-shard case is the default here
N, E = 203, 1500


@pytest.fixture(scope="module")
def graph():
    rng = np.random.default_rng(42)
    src = rng.integers(0, N, E)
    dst = rng.integers(0, N, E)
    w = rng.uniform(0.5, 2.0, E).astype(np.float32)
    return csr.from_coo(src, dst, w, n_nodes=N)


@pytest.fixture(scope="module")
def ctx8():
    assert len(jax.devices()) == 8, "conftest must provide 8 devices"
    return get_mesh_context(8)


@pytest.fixture(scope="module")
def ctx1():
    return get_mesh_context(1)


# --------------------------------------------------------------------------
# ShardedCSR layout invariants
# --------------------------------------------------------------------------

def test_sharded_csr_partition_centric_layout(graph, ctx8):
    scsr = csr.shard_csr(graph, ctx8)
    assert scsr.n_shards == 8
    assert scsr.n_pad2 == 8 * scsr.block
    assert scsr.n_pad2 > graph.n_nodes          # sink row exists
    # one row resident per device
    assert len(scsr.src.addressable_shards) == 8
    src = np.asarray(scsr.src)
    dst = np.asarray(scsr.dst)
    w = np.asarray(scsr.weights)
    for p in range(8):
        real = w[p] > 0
        # src-owned: every real edge's src falls in shard p's block
        assert np.all(src[p][real] // scsr.block == p)
        # padding gathers in-bounds locally
        assert np.all(src[p][~real] // scsr.block == p)
        # dst sorted within the shard -> the (p, q) blocks are the
        # contiguous runs block_ptr describes
        assert np.all(np.diff(dst[p]) >= 0)
        bp = scsr.block_ptr[p]
        assert bp[0] == 0 and bp[-1] <= scsr.per
        assert np.all(np.diff(bp) >= 0)
        for q in range(8):
            blk = dst[p][bp[q]:bp[q + 1]]
            assert np.all(blk // scsr.block == q)
    # every true edge appears exactly once
    assert int((w > 0).sum()) == graph.n_edges


def test_sharded_csr_cached_per_mesh(graph, ctx8, ctx1):
    a = csr.shard_csr(graph, ctx8)
    b = csr.shard_csr(graph, ctx8)
    c = csr.shard_csr(graph, ctx1)
    assert a is b
    assert c is not a and c.n_shards == 1


# --------------------------------------------------------------------------
# sharded vs single-chip numerical equivalence (atol 1e-5 criterion)
# --------------------------------------------------------------------------

def test_pagerank_mesh_matches_single_uneven(graph, ctx8):
    single, _, _ = pagerank(graph, tol=1e-10, max_iterations=200)
    sharded, _, _ = analytics.pagerank_mesh(graph, ctx8, tol=1e-10,
                                            max_iterations=200)
    np.testing.assert_allclose(np.asarray(sharded), np.asarray(single),
                               atol=1e-5)


def test_pagerank_mesh_of_1_same_code_path(graph, ctx1):
    single, _, _ = pagerank(graph, tol=1e-10, max_iterations=200)
    sharded, _, _ = analytics.pagerank_mesh(graph, ctx1, tol=1e-10,
                                            max_iterations=200)
    np.testing.assert_allclose(np.asarray(sharded), np.asarray(single),
                               atol=1e-6)


def test_pagerank_mesh_param_routes(graph):
    """ops.pagerank.pagerank(mesh=...) is the user-facing routing."""
    direct, _, _ = pagerank(graph, tol=1e-10, max_iterations=200)
    routed, _, _ = pagerank(graph, tol=1e-10, max_iterations=200, mesh=8)
    np.testing.assert_allclose(np.asarray(routed), np.asarray(direct),
                               atol=1e-5)


def test_pagerank_env_default_routing(graph, monkeypatch):
    """MEMGRAPH_TPU_MESH_DEVICES opts the whole analytics layer in."""
    monkeypatch.setenv("MEMGRAPH_TPU_MESH_DEVICES", "8")
    routed, _, _ = pagerank(graph, tol=1e-10, max_iterations=200)
    monkeypatch.delenv("MEMGRAPH_TPU_MESH_DEVICES")
    single, _, _ = pagerank(graph, tol=1e-10, max_iterations=200)
    np.testing.assert_allclose(np.asarray(routed), np.asarray(single),
                               atol=1e-5)


def test_pagerank_even_division(ctx8):
    """n % n_devices == 0: no padding rows in any block."""
    rng = np.random.default_rng(7)
    n = 256
    g = csr.from_coo(rng.integers(0, n, 2000), rng.integers(0, n, 2000),
                     None, n_nodes=n)
    single, _, _ = pagerank(g, tol=1e-10, max_iterations=200)
    sharded, _, _ = analytics.pagerank_mesh(g, ctx8, tol=1e-10,
                                            max_iterations=200)
    np.testing.assert_allclose(np.asarray(sharded), np.asarray(single),
                               atol=1e-5)


def test_katz_mesh_matches_single(graph, ctx8):
    # alpha chosen convergent for this graph's spectral radius
    single, _, _ = katz_centrality(graph, alpha=0.05, max_iterations=100,
                                   tol=1e-8)
    sharded, _, _ = analytics.katz_mesh(graph, ctx8, alpha=0.05,
                                        max_iterations=100, tol=1e-8)
    np.testing.assert_allclose(np.asarray(sharded), np.asarray(single),
                               atol=1e-5)


def test_katz_mesh_param_and_mesh_of_1(graph, ctx1):
    single, _, _ = katz_centrality(graph, alpha=0.05, max_iterations=100,
                                   tol=1e-8)
    via_param, _, _ = katz_centrality(graph, alpha=0.05,
                                      max_iterations=100, tol=1e-8,
                                      mesh=ctx1)
    np.testing.assert_allclose(np.asarray(via_param), np.asarray(single),
                               atol=1e-6)


def test_labelprop_mesh_matches_single(graph, ctx8):
    single, _ = label_propagation(graph, max_iterations=30)
    sharded, _ = analytics.label_propagation_mesh(graph, ctx8,
                                                  max_iterations=30)
    assert np.array_equal(np.asarray(single), np.asarray(sharded))


def test_labelprop_mesh_param_routes(graph):
    single, _ = label_propagation(graph, max_iterations=30)
    routed, _ = label_propagation(graph, max_iterations=30, mesh=8)
    assert np.array_equal(np.asarray(single), np.asarray(routed))


def test_components_mesh_matches_single(graph, ctx8):
    single, _ = weakly_connected_components(graph)
    sharded, _ = analytics.components_mesh(graph, ctx8)
    assert np.array_equal(np.asarray(single), np.asarray(sharded))


def test_components_mesh_param_routes(graph):
    single, _ = weakly_connected_components(graph)
    routed, _ = weakly_connected_components(graph, mesh=8)
    assert np.array_equal(np.asarray(single), np.asarray(routed))


def test_sssp_mesh_matches_single(graph, ctx8):
    single, _ = sssp(graph, source=0, weighted=True, directed=True)
    sharded, _ = analytics.sssp_mesh(graph, ctx8, source=0)
    np.testing.assert_allclose(np.asarray(sharded), np.asarray(single),
                               atol=1e-4)


def test_bfs_mesh_matches_single_uneven(graph, ctx8):
    """BFS over the GENERIC semiring mesh kernel (r10): level-exact vs
    the single-chip core path on the uneven-shard graph."""
    from memgraph_tpu.ops.traversal import bfs_levels
    single, _ = bfs_levels(graph, 0)
    sharded, _ = analytics.bfs_mesh(graph, ctx8, 0)
    assert np.array_equal(np.asarray(single), np.asarray(sharded))


def test_bfs_mesh_of_1_same_code_path(graph, ctx1):
    from memgraph_tpu.ops.traversal import bfs_levels
    single, _ = bfs_levels(graph, 0)
    sharded, _ = analytics.bfs_mesh(graph, ctx1, 0)
    assert np.array_equal(np.asarray(single), np.asarray(sharded))


# --------------------------------------------------------------------------
# r10 mixed precision on the mesh (8-device uneven + mesh-of-1)
# --------------------------------------------------------------------------

def test_pagerank_mesh_bf16_within_bounds(graph, ctx8, ctx1):
    from memgraph_tpu.ops.semiring import PRECISION_BOUNDS
    f32, _, _ = analytics.pagerank_mesh(graph, ctx8, tol=1e-10,
                                        max_iterations=200)
    for ctx in (ctx8, ctx1):
        b16, _, _ = analytics.pagerank_mesh(graph, ctx, tol=1e-10,
                                            max_iterations=200,
                                            precision="bf16")
        diff = np.abs(np.asarray(b16) - np.asarray(f32))
        assert float(diff.max()) <= PRECISION_BOUNDS["bf16"]["pagerank_linf"]
        assert float(diff.sum()) <= PRECISION_BOUNDS["bf16"]["pagerank_l1"]


def test_pagerank_mesh_f32_bit_exact_across_precision_cache(graph, ctx8):
    """Requesting bf16 must not poison the f32 kernel cache: f32 stays
    bit-identical before and after a bf16 run on the same context."""
    a, _, _ = analytics.pagerank_mesh(graph, ctx8, tol=1e-10,
                                      max_iterations=50)
    analytics.pagerank_mesh(graph, ctx8, tol=1e-10, max_iterations=50,
                            precision="bf16")
    b, _, _ = analytics.pagerank_mesh(graph, ctx8, tol=1e-10,
                                      max_iterations=50)
    assert np.array_equal(np.asarray(a), np.asarray(b))


def test_katz_mesh_bf16_close(graph, ctx8):
    f32, _, _ = analytics.katz_mesh(graph, ctx8, alpha=0.05,
                                    max_iterations=100, tol=1e-8)
    b16, _, _ = analytics.katz_mesh(graph, ctx8, alpha=0.05,
                                    max_iterations=100, tol=1e-8,
                                    precision="bf16")
    np.testing.assert_allclose(np.asarray(b16), np.asarray(f32),
                               atol=5e-2, rtol=2e-2)


def test_mesh_rejects_int8(graph, ctx1):
    with pytest.raises(ValueError):
        analytics.pagerank_mesh(graph, ctx1, max_iterations=5,
                                precision="int8")


# --------------------------------------------------------------------------
# the one-collective-per-iteration invariant (compiled-HLO assertion)
# --------------------------------------------------------------------------
# r17: ONE source of truth — the mgxla contract checker (tools/mgxla)
# abstractly lowers every mesh kernel over the forced 8-device mesh and
# asserts the EXACT collective multiset, its location inside the while
# body, zero f64 ops, zero host callbacks, and donation of the chunk
# carry. These tests assert the checker's verdict instead of carrying
# their own regexes; `python -m tools.mgxla check` runs the same
# contracts over the full manifest in the dev gate.

from tools.mgxla import checker as mgxla_checker


def _assert_contract(kernel: str):
    violations = mgxla_checker.check_kernel_by_id(kernel)
    assert not violations, "\n".join(v.render() for v in violations)


def test_pagerank_exactly_one_collective_per_iteration():
    """The WHOLE compiled CHUNK program contains exactly one
    cross-device collective — the fused psum_scatter inside the while
    body. Setup (out-weights, dangling mask), the convergence check AND
    the r12 chunk-carry plumbing (checkpoint/resume) add none. The
    carry is donated (r17)."""
    _assert_contract("mesh:pagerank")


def test_pagerank_bf16_keeps_the_collective_contract():
    _assert_contract("mesh:pagerank_bf16")


def test_katz_exactly_one_collective_per_iteration():
    _assert_contract("mesh:katz")


def test_labelprop_exactly_one_collective_per_round():
    _assert_contract("mesh:labelprop")


def test_wcc_exactly_one_collective_per_round():
    _assert_contract("mesh:wcc")


def test_generic_semiring_mesh_kernel_contract():
    """The (semiring, x0, epilogue) mesh kernel sssp_mesh/bfs_mesh ride."""
    _assert_contract("mesh:semiring_min_plus")


# --------------------------------------------------------------------------
# registry contract (runtime half of mglint MG005 spmv coverage)
# --------------------------------------------------------------------------

def _resolve(target: str):
    import importlib
    mod, fn = target.split(":")
    return getattr(importlib.import_module(mod), fn)


def test_registry_entries_declare_mesh_story():
    assert SPMV_ALGORITHMS, "registry must not be empty"
    for name, entry in SPMV_ALGORITHMS.items():
        has_sharded = "sharded" in entry
        has_exempt = "exempt" in entry
        assert has_sharded != has_exempt, (
            f"{name}: exactly one of sharded/exempt required")
        if has_exempt:
            assert len(entry["exempt"].strip()) >= 40, (
                f"{name}: exemption needs a real justification")


def test_registry_targets_resolve_and_are_callable():
    for name, entry in SPMV_ALGORITHMS.items():
        for field in ("entry", "sharded"):
            if field in entry:
                fn = _resolve(entry[field])
                assert callable(fn), f"{name}.{field} not callable"


def test_mglint_flags_unregistered_spmv_module(tmp_path):
    """The static half: a new SpMV-shaped ops/ module that skips the
    registry must produce an MG005 finding."""
    from tools.mglint.core import Project
    from tools.mglint.rules.registry_coverage import _check_spmv_registry
    pkg = tmp_path / "pkg" / "ops"
    pkg.mkdir(parents=True)
    (pkg / "__init__.py").write_text("SPMV_ALGORITHMS = {}\n")
    (pkg / "rogue.py").write_text(
        "import jax\n"
        "def run(x, seg):\n"
        "    def body(c):\n"
        "        return jax.ops.segment_sum(c, seg, num_segments=4)\n"
        "    return jax.lax.while_loop(lambda c: True, body, x)\n")
    project = Project([str(tmp_path / "pkg")], cwd=str(tmp_path))
    findings = _check_spmv_registry(project)
    assert any(f.fingerprint == "spmv-uncovered:rogue" for f in findings)


def test_mglint_flags_stub_exemption_and_dangling_target(tmp_path):
    from tools.mglint.core import Project
    from tools.mglint.rules.registry_coverage import _check_spmv_registry
    pkg = tmp_path / "pkg" / "ops"
    pkg.mkdir(parents=True)
    (pkg / "__init__.py").write_text(
        "SPMV_ALGORITHMS = {\n"
        "  'a': {'entry': 'pkg.ops.a:run', 'exempt': 'TODO'},\n"
        "  'b': {'entry': 'pkg.ops.b:run',\n"
        "        'sharded': 'pkg.nowhere:missing'},\n"
        "}\n")
    (pkg / "a.py").write_text("def run():\n    pass\n")
    (pkg / "b.py").write_text("def run():\n    pass\n")
    project = Project([str(tmp_path / "pkg")], cwd=str(tmp_path))
    fps = {f.fingerprint for f in _check_spmv_registry(project)}
    assert "spmv-stub-exemption:a" in fps
    assert "spmv-dangling:b:sharded" in fps


# --------------------------------------------------------------------------
# shard_map resolution
# --------------------------------------------------------------------------

def test_shard_map_resolver_is_the_public_checked_one(caplog):
    import logging
    fn1, fb1 = resolve_shard_map()
    with caplog.at_level(logging.WARNING,
                         logger="memgraph_tpu.parallel.mesh"):
        fn2, fb2 = resolve_shard_map()
    assert fn1 is fn2 is jax.shard_map
    # no fallback exists: replication checking is never switched off
    assert fb1 is False and fb2 is False
    assert not caplog.records


def test_resolve_mesh_accepts_all_spellings(ctx8):
    from memgraph_tpu.parallel.mesh import MeshContext
    assert resolve_mesh(None) is None            # env unset -> no mesh
    assert resolve_mesh(ctx8) is ctx8
    assert resolve_mesh(8).n_shards == 8
    got = resolve_mesh(ctx8.mesh)
    assert isinstance(got, MeshContext) and got.n_shards == 8
    with pytest.raises(TypeError):
        resolve_mesh("everything")
