"""The main path's kernels compile for the chip — without the chip.

The TPU's compiler is installed here and compiles for a DESCRIBED
(not attached) ``v5e:2x2``: whatever it refuses (scoped VMEM, tiling,
device memory, an unpartitionable kernel) is refused here at no chip
time. Shapes are mgbench Pokec medium (100,000 nodes / 1,768,515 edges,
chip_smoke.py's deployment), one chip plus the 4-device mesh compile.
A compile that passes is not a chip run: nothing here executes.

The topology is described inside a module-scoped fixture (never at
import, never in a skipif/parametrize argument): only one process at a
time may load the TPU's library, and every xdist worker imports this
file. Keep these tests in this one file, and compile in the test's own
process. The persistent compilation cache is off around the compiles:
an executable for a described chip is written but cannot be read back.
"""

import os
import re

import numpy as np
import pytest

import chip_smoke


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe: skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def sds(one_chip):
    """ShapeDtypeStruct on the described chip."""
    import jax

    def make(shape, dtype, sharding=one_chip):
        return jax.ShapeDtypeStruct(shape, np.dtype(dtype),
                                    sharding=sharding)
    return make


@pytest.fixture(scope="module")
def pokec():
    """The smoke's graph (host arrays only) and its padded CSR dims."""
    from memgraph_tpu.ops import csr
    n, e = chip_smoke.NODES, chip_smoke.EDGES
    src, dst = chip_smoke.make_graph(7, n, e)
    g = csr.from_coo(src, dst, n_nodes=n)      # host-side, not placed
    return {"src": src, "dst": dst, "n": n, "e": e,
            "n_pad": g.n_pad, "e_pad": g.e_pad}


@pytest.fixture(scope="module")
def mxu_plan(pokec):
    from memgraph_tpu.ops import spmv_mxu
    plan = spmv_mxu.build_plan(pokec["src"], pokec["dst"], None,
                               pokec["n"])
    assert plan.net_log2 >= 21          # past one middle block: 3 passes
    return plan


def _kernel_calls(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


def _edge_arrays(sds, pokec):
    e = (pokec["e_pad"],)
    return {"src": sds(e, "int32"), "dst": sds(e, "int32"),
            "w": sds(e, "float32"), "csr_src": sds(e, "int32"),
            "csr_w": sds(e, "float32")}


def _scalars(sds, **dtypes):
    return {name: sds((), dt) for name, dt in dtypes.items()}


@pytest.mark.parametrize("route", ["float32", "bfloat16"])
def test_mxu_pagerank_fixpoint_compiles(sds, mxu_plan, monkeypatch, route):
    """The whole served fixpoint — expand einsum, Pallas Benes passes,
    MXU reduce, node relabel, PageRank epilogue — in one while_loop:
    the co-scheduling that once hit the scoped-VMEM limit."""
    import jax.numpy as jnp
    from memgraph_tpu.ops import spmv_mxu
    # the kernel picks Pallas from jax.default_backend(), which is the
    # CPU here; the program's own switch steers it, as on the chip
    monkeypatch.setenv("MEMGRAPH_TPU_BENES", "pallas")
    run = spmv_mxu.make_semiring_kernel(
        mxu_plan, spmv_mxu.pagerank_mxu_epilogue,
        route_dtype=jnp.dtype(route))
    compiled = run.jitted_default.lower(
        sds(run.blob.shape, run.blob.dtype),
        {"damping": sds((), "float32")}, 100,
        sds((), "float32")).compile()
    # big net: outer-down + middle + outer-up; node net: one block
    assert _kernel_calls(compiled) >= 4
    assert compiled.memory_analysis().temp_size_in_bytes < 8 << 30


def test_mxu_delta_fixpoint_compiles(sds, mxu_plan, pokec, monkeypatch):
    """The program a CALL after a write runs: the base blob and the
    delta's blob as two arguments, the delta net (2^18 at the floors of
    build_delta_plan) through its own Pallas passes with every stage
    routed. A later burst of the same shapes compiles nothing."""
    from memgraph_tpu.ops import spmv_mxu
    monkeypatch.setenv("MEMGRAPH_TPU_BENES", "pallas")
    rng = np.random.default_rng(3)
    n = pokec["n"]

    def kernel(edges):
        delta = spmv_mxu.build_delta_plan(
            mxu_plan, rng.integers(0, n, edges),
            (rng.random(edges) ** 2 * n).astype(np.int64))
        assert (delta.R_G, delta.C) == (spmv_mxu.SG_ROWS, 2 * mxu_plan.W)
        return spmv_mxu.make_semiring_kernel(
            mxu_plan, spmv_mxu.pagerank_mxu_epilogue, delta=delta)

    run, later = kernel(64), kernel(640)
    assert later.jitted_default is run.jitted_default
    assert later.delta_blob.shape == run.delta_blob.shape
    compiled = run.jitted_default.lower(
        sds(run.blob.shape, run.blob.dtype),
        {"damping": sds((), "float32")}, 100, sds((), "float32"),
        sds(run.delta_blob.shape, run.delta_blob.dtype)).compile()
    # the base's four passes, and the delta net's outer-down + middle +
    # outer-up
    assert _kernel_calls(compiled) >= 7
    assert compiled.memory_analysis().temp_size_in_bytes < 8 << 30


@pytest.mark.parametrize("net_log2,dtype", [(21, "float32"),
                                            (24, "bfloat16")])
def test_benes_pallas_compiles_alone(sds, net_log2, dtype):
    import jax
    from memgraph_tpu.ops.benes import benes_stage_distances
    from memgraph_tpu.ops.benes_pallas import (benes_apply_pallas,
                                               build_pallas_masks)
    n_stages = len(benes_stage_distances(net_log2))
    every_stage_live = np.broadcast_to(
        np.uint8(0xFF), (n_stages, (1 << net_log2) // 8))
    spec, mid, outer = build_pallas_masks(every_stage_live, net_log2)
    assert spec.K == 17 and spec.outer_down and spec.outer_up
    rows = (1 << net_log2) // 128
    compiled = jax.jit(
        lambda x, m, o: benes_apply_pallas(x, m, o, spec)).lower(
        sds((rows, 128), dtype), sds(mid.shape, "int32"),
        sds(outer.shape, "int32")).compile()
    assert _kernel_calls(compiled) == 3


def test_segment_pagerank_fixpoint_compiles(sds, pokec):
    from memgraph_tpu.ops import semiring as S
    from memgraph_tpu.ops.pagerank import (_pagerank_epilogue,
                                           _pagerank_setup)
    fn = S._build_fixpoint(
        S.resolve_semiring("plus_times"), epilogue=_pagerank_epilogue,
        setup=_pagerank_setup, step=None, n_out=pokec["n_pad"],
        max_iterations=100, metric="err", precision="f32", sorted=True,
        sorted_backward=False, direction="fwd")
    fn.lower(_edge_arrays(sds, pokec),
             _scalars(sds, n_nodes="int32", damping="float32",
                      tol="float32"), None).compile()


#: graph500_s17_inproc's padded shapes: 90,162 vertices, 1,864,185
#: relationships (benchmarks/chipbench/configs/graph500_s17_inproc.json)
GRAPH500_N_PAD, GRAPH500_E_PAD = 1 << 17, 1 << 21


def _while_body_ops(compiled) -> list:
    """The names of the instructions of the compiled program's while
    body, as the device trace's XLA Ops line names its events."""
    text = compiled.as_text()
    body = re.search(r"body=%?([\w.\-]+)", text).group(1)
    block = re.search(r"\n%?" + re.escape(body) + r" [^\n]*\{\n(.*?)\n\}",
                      text, re.S).group(1)
    return [re.match(r"\s*(?:ROOT )?(%[\w.\-]+) =", line).group(1)
            for line in block.splitlines() if " = " in line]


@pytest.fixture(scope="module")
def sweeps(sds):
    """The undirected sweeps of graph500_s17.graphalytics_fresh, compiled
    at its shapes: {program: its while body's instruction names}."""
    from memgraph_tpu.ops import components, semiring as S, traversal
    n, e = GRAPH500_N_PAD, GRAPH500_E_PAD
    edges = {"src": sds((e,), "int32"), "dst": sds((e,), "int32")}
    built = {
        "bfs": (S._build_fixpoint(
            S.resolve_semiring("min_plus"),
            epilogue=traversal._bfs_undirected_epilogue, setup=None,
            step=traversal._bfs_undirected_step, n_out=n,
            max_iterations=10_000, metric="changed", precision="f32",
            sorted=False, sorted_backward=False, direction="fwd"),
            edges, sds((n,), "int32")),
        "sssp": (S._build_fixpoint(
            S.resolve_semiring("min_plus"),
            epilogue=traversal._sssp_epilogue, setup=None,
            step=traversal._sssp_step_undirected, n_out=n,
            max_iterations=10_000, metric="changed", precision="f32",
            sorted=False, sorted_backward=False, direction="fwd"),
            dict(edges, w=sds((e,), "float32")), sds((n,), "float32")),
        "wcc": (S._build_fixpoint(
            S.resolve_semiring("min_first"),
            epilogue=components._wcc_epilogue, setup=None, step=None,
            n_out=n, max_iterations=200, metric="changed", precision="f32",
            sorted=False, sorted_backward=False, direction="both"),
            edges, sds((n,), "int32")),
    }
    return {algo: _while_body_ops(fn.lower(arrays, {}, x0).compile())
            for algo, (fn, arrays, x0) in built.items()}


@pytest.mark.parametrize("algo", ["bfs", "sssp", "wcc"])
def test_graphalytics_roofline_counts_one_op_an_iteration(sweeps, algo):
    """The op a roofline counts as one iteration stands once in its
    program's loop body and in no other sweep of the cell's cycle."""
    import json
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "chipbench",
        "layer_metrics", f"{algo}_roofline.json")
    with open(path) as f:
        tick, = json.load(f)["params"]["once_per_iteration"]
    assert sweeps[algo].count(tick) == 1, sweeps[algo]
    for other, ops in sweeps.items():
        if other != algo:
            assert tick not in ops, (other, tick)


def _while_body_lines(compiled) -> list:
    """The instructions of the compiled program's while body and of every
    computation they call (fusions, comparators), as HLO text lines."""
    text = compiled.as_text()
    blocks = dict(re.findall(r"\n%?([\w.\-]+) [^\n]*\{\n(.*?)\n\}", text,
                             re.S))
    todo = [re.search(r"body=%?([\w.\-]+)", text).group(1)]
    seen, lines = set(), []
    while todo:
        name = todo.pop()
        if name in seen:
            continue
        seen.add(name)
        block = blocks[name].splitlines()
        lines += block
        for line in block:
            todo += re.findall(r"(?:calls|to_apply)=%?([\w.\-]+)", line)
    return lines


def test_cdlp_count_election_scatters_nothing(sds):
    """The election by counts (label_propagation.get on a view without
    a weight property) at graph500_s17.graphalytics_fresh's shapes: its
    round is one sort and cumulative passes, no scatter, and the
    program keeps the name cdlp_device_ms and cdlp_roofline match."""
    from memgraph_tpu.ops import labelprop, semiring as S
    n, e2 = GRAPH500_N_PAD, 2 * GRAPH500_E_PAD
    fn = S._build_fixpoint(
        S.resolve_semiring("max_min"), epilogue=labelprop._labelprop_epilogue,
        setup=labelprop._count_setup, step=labelprop._count_step, n_out=n,
        max_iterations=10, metric="changed", precision="f32", sorted=False,
        sorted_backward=False, direction="fwd")
    compiled = fn.lower(
        {"src": sds((e2,), "int32"), "dst": sds((e2,), "int32")},
        _scalars(sds, self_weight="float32"), sds((n,), "int32")).compile()
    assert re.match(r"HloModule jit_fixpoint_labelprop\b", compiled.as_text())
    body = _while_body_lines(compiled)
    assert not [line for line in body if " scatter(" in line]
    assert len([line for line in body if " sort(" in line]) == 1


def test_lane_hop_counts_compiles(sds, pokec):
    """The two-hop filtered aggregate of README §Compiled read lane."""
    from memgraph_tpu.ops import pipeline as pl
    nb, eb = pl._bucket(pokec["n"]), pl._bucket(pokec["e"])
    fn = pl._build_hops_program(2, False, True, True, True, nb)
    fn.lower(sds((eb,), "int32"), sds((eb,), "int32"), sds((eb,), "bool"),
             sds((nb,), "bool"), sds((nb,), "float32"),
             sds((nb,), "float32")).compile()


def test_lane_masked_aggregate_compiles(sds, pokec):
    from memgraph_tpu.ops import pipeline as pl
    nb = pl._bucket(pokec["n"])
    # WHERE u.age < $x RETURN count(*), sum(u.age), min(u.age), max(u.age)
    fn = pl._build_agg_program(
        ((0, "<"),),
        (("count", None), ("sum", 0), ("min", 0), ("max", 0)))
    fn.lower(sds((1, nb), "int32"), sds((1, nb), "bool"),
             sds((nb,), "bool"), sds((1,), "int32")).compile()


def test_batched_ppr_fixpoint_compiles(sds, pokec):
    from memgraph_tpu.ops.pagerank import _build_ppr_batch
    arrays = _edge_arrays(sds, pokec)
    arrays["personalization"] = sds((pokec["n_pad"], 32), "float32")
    _build_ppr_batch(pokec["n_pad"], 100, "f32", False).lower(
        arrays, _scalars(sds, n_nodes="int32", damping="float32",
                         tol="float32"), None).compile()


@pytest.mark.parametrize("n_shards", [1, 4])
def test_partition_centric_pagerank_compiles(topo, pokec, n_shards):
    """The kernel server's `pagerank` op (mesh of 1) and the smoke's
    `--mesh` path (the 4 described devices): one program across chips,
    one reduce-scatter per iteration."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from memgraph_tpu.ops.csr import shard_edges
    from memgraph_tpu.parallel.distributed import _pc_pagerank_build
    from memgraph_tpu.parallel.mesh import MeshContext
    mesh = Mesh(np.array(topo.devices[:n_shards]), ("shard",))
    ctx = MeshContext(
        mesh=mesh, axis="shard", n_shards=n_shards,
        replicated=NamedSharding(mesh, P()),
        edge_blocks=NamedSharding(mesh, P("shard", None)),
        vertex_blocks=NamedSharding(mesh, P("shard")))
    scsr = shard_edges(pokec["src"], pokec["dst"], None, pokec["n"],
                       n_shards)

    def sds(shape, dtype, sharding):
        return jax.ShapeDtypeStruct(shape, np.dtype(dtype),
                                    sharding=sharding)
    edges = (n_shards, scsr.per)
    rep, eb, vb = ctx.replicated, ctx.edge_blocks, ctx.vertex_blocks
    compiled = _pc_pagerank_build(ctx, scsr.block, n_shards).lower(
        sds(edges, "int32", eb), sds(edges, "int32", eb),
        sds(edges, "float32", eb),
        sds((), "int32", rep), sds((), "float32", rep),
        sds((), "float32", rep),
        sds((scsr.n_pad2,), "float32", vb),
        sds((n_shards,), "float32", vb), sds((n_shards,), "float32", vb),
        sds((), "int32", rep), sds((), "int32", rep)).compile()
    # still exactly one collective per iteration (the chip's compiler
    # is free to pick its form: it lowers this psum_scatter to an
    # all-reduce), and none at all on a mesh of one
    collectives = re.findall(
        r"= \S+ (all-reduce|reduce-scatter|all-gather|all-to-all|"
        r"collective-permute)(?:-start)?\(", compiled.as_text())
    assert len(collectives) == (1 if n_shards > 1 else 0), collectives
    # the 16 GB chip holds this many times over
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 1 << 30
