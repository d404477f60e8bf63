"""Sharded whole-graph kernels: edge-partitioned, psum-combined.

Scheme (the scaling-book recipe applied to graphs): pad the edge list to a
multiple of the mesh size, give each device a contiguous edge block
(src/dst/weight shards), replicate the O(n) vertex vectors. Each round every
device computes its local segment reduction into a full-size vertex vector,
then one `psum`/`pmin` over the mesh axis combines them — the collective
rides ICI. Vertex vectors are replicated (fine to ~100M nodes in f32);
2D vertex-sharding is the next scaling step.

Reference contrast: the reference's distributed story is replication +
point-to-point RPC (/root/reference/src/rpc, SURVEY.md §2.4); there is no
data-plane collective to mirror — this layer is designed TPU-first.
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .mesh import MeshContext, shard_map_fn, streaming_device
from ..observability import stats as mgstats
from ..observability.metrics import global_metrics
from ..ops import tier as mgtier
from ..ops.csr import DeviceGraph, ShardedCSR
from ..ops.semiring import (backend_extent, edge_combine, edge_reduce,
                            pagerank_update, resolve_semiring)

shard_map = shard_map_fn()


def _cast_contrib(contrib, precision: str):
    """Reduced-precision streaming on the mesh backend: round each
    per-edge contribution to bf16 before the f32 segment accumulation
    (same contract as the segment backend's bf16 path; int8 streaming
    is a segment-backend feature — the collective lanes stay f32)."""
    if precision == "bf16":
        return contrib.astype(jnp.bfloat16).astype(jnp.float32)
    if precision != "f32":
        raise ValueError(
            f"mesh kernels route f32/bf16 only, got {precision!r}")
    return contrib


@dataclass(frozen=True)
class ShardedGraph:
    """Edge-sharded COO graph on a mesh. Vertex state is replicated."""
    src: object      # (e_pad,) sharded over mesh axis
    dst: object      # (e_pad,)
    weights: object  # (e_pad,)
    n_nodes: int
    n_edges: int     # true edge count; positions >= n_edges are padding
    n_pad: int
    e_pad: int
    mesh: Mesh
    axis: str


def shard_graph(graph: DeviceGraph, mesh: Mesh,
                axis: str | None = None) -> ShardedGraph:
    """Place edge arrays sharded over the mesh; pads edges to a multiple of
    the mesh size (padding edges are inert: weight 0 into the sink row)."""
    axis = axis or mesh.axis_names[0]
    n_shards = mesh.shape[axis]
    e_pad = graph.e_pad
    if e_pad % n_shards:
        new_e = ((e_pad + n_shards - 1) // n_shards) * n_shards
    else:
        new_e = e_pad
    sink = graph.n_nodes

    def pad_to(arr, fill):
        arr = np.asarray(arr)
        if len(arr) < new_e:
            arr = np.concatenate(
                [arr, np.full(new_e - len(arr), fill, dtype=arr.dtype)])
        return arr

    # CSC ((dst, src)-sorted) order: per-shard contiguous blocks stay
    # dst-sorted, so local segment reductions take the fast sorted lowering
    src = pad_to(graph.csc_src, sink)
    dst = pad_to(graph.csc_dst, sink)
    w = pad_to(graph.csc_weights, 0.0)

    sharding = NamedSharding(mesh, P(axis))
    return ShardedGraph(
        src=jax.device_put(src, sharding),
        dst=jax.device_put(dst, sharding),
        weights=jax.device_put(w, sharding),
        n_nodes=graph.n_nodes, n_edges=graph.n_edges,
        n_pad=graph.n_pad, e_pad=new_e,
        mesh=mesh, axis=axis)


def _pagerank_sharded_fn(mesh: Mesh, axis: str, n_pad: int,
                         max_iterations: int):
    """Build the shard_mapped pagerank step for a given mesh/shapes."""

    def step(src_blk, dst_blk, w_blk, n_nodes, damping, tol):
        n_f = n_nodes.astype(jnp.float32)
        valid_f = (jnp.arange(n_pad, dtype=jnp.int32) < n_nodes
                   ).astype(jnp.float32)
        # per-source outgoing weight: local partial + psum = global
        wsum_local = jax.ops.segment_sum(w_blk, src_blk, num_segments=n_pad)
        wsum = jax.lax.psum(wsum_local, axis)
        inv_wsum = jnp.where(wsum > 0, 1.0 / jnp.maximum(wsum, 1e-30), 0.0)
        dangling_f = valid_f * (wsum <= 0)

        rank0 = valid_f / n_f

        edge_mult = w_blk * inv_wsum[src_blk]  # hoisted per-edge multiplier

        def body(carry):
            rank, _, it = carry
            contrib = rank[src_blk] * edge_mult
            acc_local = jax.ops.segment_sum(contrib, dst_blk,
                                            num_segments=n_pad,
                                            indices_are_sorted=True)
            acc = jax.lax.psum(acc_local, axis)          # ← ICI collective
            dangling_mass = jnp.sum(rank * dangling_f)
            new_rank = valid_f * ((1.0 - damping) / n_f
                                  + damping * (acc + dangling_mass / n_f))
            err = jnp.sum(jnp.abs(new_rank - rank))
            return new_rank, err, it + 1

        def cond(carry):
            _, err, it = carry
            return (err > tol) & (it < max_iterations)

        rank, err, iters = jax.lax.while_loop(
            cond, body, (rank0, jnp.float32(jnp.inf), jnp.int32(0)))
        return rank, err, iters

    return shard_map(
        step, mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis), P(), P(), P()),
        out_specs=(P(), P(), P()))


#: compiled legacy sharded kernels keyed by (kind, devices, shapes) —
#: re-jitting the builder closure per call silently retraced + recompiled
#: on EVERY invocation (mglint MG008 recompile-hazard; the partition-
#: centric kernels already cache through _pc_cached)
_SHARDED_JIT_CACHE: dict = {}


def _sharded_jit(kind: str, builder_fn, mesh: Mesh, axis: str,
                 *shape_key, donate: tuple = ()):
    key = (kind, tuple(d.id for d in mesh.devices.flat), axis,
           shape_key, donate)
    fn = _SHARDED_JIT_CACHE.get(key)
    if fn is None:
        fn = _SHARDED_JIT_CACHE[key] = jax.jit(
            builder_fn(mesh, axis, *shape_key), donate_argnums=donate)
    return fn


def pagerank_sharded(sg: ShardedGraph, damping: float = 0.85,
                     max_iterations: int = 100, tol: float = 1e-6):
    """Distributed PageRank over the mesh. Returns (ranks[:n], err, iters)."""
    fn = _sharded_jit("pagerank", _pagerank_sharded_fn, sg.mesh, sg.axis,
                      sg.n_pad, max_iterations)
    rank, err, iters = fn(sg.src, sg.dst, sg.weights,
                          jnp.int32(sg.n_nodes), jnp.float32(damping),
                          jnp.float32(tol))
    return rank[:sg.n_nodes], float(err), int(iters)


def shard_graph_by_src(graph: DeviceGraph, mesh: Mesh,
                       axis: str | None = None) -> ShardedGraph:
    """Partition edges by SOURCE shard (edge e goes to the device owning
    src block floor(src / (n_pad / n_shards))) — the layout the 1.5D
    pagerank needs: every gather rank[src] is then device-local.

    Within each device block edges stay (dst-sorted) for the sorted
    segment reduction.
    """
    import numpy as np
    axis = axis or mesh.axis_names[0]
    n_shards = mesh.shape[axis]
    if graph.n_pad % n_shards:
        raise ValueError("n_pad must divide the mesh size")
    block = graph.n_pad // n_shards
    src = np.asarray(graph.csc_src)[:graph.n_edges]
    dst = np.asarray(graph.csc_dst)[:graph.n_edges]
    w = np.asarray(graph.csc_weights)[:graph.n_edges]
    owner = src // block
    # bucket edges per owner, keep dst order within the bucket (stable)
    order = np.argsort(owner, kind="stable")
    src, dst, w, owner = src[order], dst[order], w[order], owner[order]
    counts = np.bincount(owner, minlength=n_shards)
    per = int(counts.max()) if len(counts) else 1
    per = max(per, 1)
    sink = graph.n_nodes
    e_pad = per * n_shards
    src_full = np.full(e_pad, sink, dtype=np.int32)
    dst_full = np.full(e_pad, sink, dtype=np.int32)
    w_full = np.zeros(e_pad, dtype=np.float32)
    offsets = np.concatenate([[0], np.cumsum(counts)])
    for s in range(n_shards):
        lo, hi = offsets[s], offsets[s + 1]
        src_full[s * per:s * per + (hi - lo)] = src[lo:hi]
        dst_full[s * per:s * per + (hi - lo)] = dst[lo:hi]
        w_full[s * per:s * per + (hi - lo)] = w[lo:hi]
    sharding = NamedSharding(mesh, P(axis))
    return ShardedGraph(
        src=jax.device_put(src_full, sharding),
        dst=jax.device_put(dst_full, sharding),
        weights=jax.device_put(w_full, sharding),
        n_nodes=graph.n_nodes, n_edges=graph.n_edges,
        n_pad=graph.n_pad, e_pad=e_pad, mesh=mesh, axis=axis)


def _pagerank_15d_fn(mesh: Mesh, axis: str, n_pad: int, n_shards: int,
                     max_iterations: int):
    """1.5D pagerank: rank is SHARDED over the mesh (each device holds
    n_pad/n_shards entries); edges are src-sharded so the per-edge rank
    gather is device-local, and partial destination sums combine with ONE
    reduce_scatter per iteration — O(n/p) memory and lower ICI volume than
    the replicated psum scheme (the scaling-book recipe)."""
    block = n_pad // n_shards

    def step(src_blk, dst_blk, w_blk, n_nodes, damping, tol):
        shard_id = jax.lax.axis_index(axis)
        base = shard_id * block
        n_f = n_nodes.astype(jnp.float32)
        local_ids = base + jnp.arange(block, dtype=jnp.int32)
        valid_f = (local_ids < n_nodes).astype(jnp.float32)

        local_src = jnp.clip(src_blk - base, 0, block - 1)
        src_mine = (src_blk >= base) & (src_blk < base + block)
        w_eff = jnp.where(src_mine, w_blk, 0.0)

        # local out-weight per owned node (edges are src-sharded: complete)
        wsum = jax.ops.segment_sum(w_eff, local_src, num_segments=block)
        inv_wsum = jnp.where(wsum > 0, 1.0 / jnp.maximum(wsum, 1e-30), 0.0)
        dangling_f = valid_f * (wsum <= 0)

        rank0 = valid_f / n_f  # local shard of the rank vector

        def body(carry):
            rank, _, it = carry
            contrib = rank[local_src] * w_eff * inv_wsum[local_src]
            # partial sums over ALL destinations, then scatter to owners
            acc_full = jax.ops.segment_sum(contrib, dst_blk,
                                           num_segments=n_pad,
                                           indices_are_sorted=True)
            acc = jax.lax.psum_scatter(
                acc_full.reshape(n_shards, block), axis,
                scatter_dimension=0, tiled=False)
            dangling_mass = jax.lax.psum(jnp.sum(rank * dangling_f), axis)
            new_rank = valid_f * ((1.0 - damping) / n_f
                                  + damping * (acc + dangling_mass / n_f))
            err = jax.lax.psum(jnp.sum(jnp.abs(new_rank - rank)), axis)
            return new_rank, err, it + 1

        def cond(carry):
            _, err, it = carry
            return (err > tol) & (it < max_iterations)

        rank, err, iters = jax.lax.while_loop(
            cond, body, (rank0, jnp.float32(jnp.inf), jnp.int32(0)))
        return rank, err, iters

    return shard_map(
        step, mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis), P(), P(), P()),
        out_specs=(P(axis), P(), P()))


def pagerank_sharded_15d(sg: ShardedGraph, damping: float = 0.85,
                         max_iterations: int = 100, tol: float = 1e-6):
    """Memory-scalable distributed PageRank (use shard_graph_by_src)."""
    n_shards = sg.mesh.shape[sg.axis]
    fn = _sharded_jit("pagerank_15d", _pagerank_15d_fn, sg.mesh, sg.axis,
                      sg.n_pad, n_shards, max_iterations)
    rank, err, iters = fn(sg.src, sg.dst, sg.weights,
                          jnp.int32(sg.n_nodes), jnp.float32(damping),
                          jnp.float32(tol))
    return rank[:sg.n_nodes], float(err), int(iters)


def _min_propagate_sharded_fn(mesh: Mesh, axis: str, n_pad: int,
                              max_iterations: int, undirected: bool,
                              pointer_jump: bool):
    def step(src_blk, dst_blk, w_blk, init):
        def body(carry):
            val, _, it = carry
            # dst_blk is per-block sorted (CSC shards) → sorted lowering;
            # the backward reduction keys on src which is unsorted under CSC
            cand_local = jax.ops.segment_min(val[src_blk] + w_blk, dst_blk,
                                             num_segments=n_pad,
                                             indices_are_sorted=True)
            if undirected:
                back = jax.ops.segment_min(val[dst_blk] + w_blk, src_blk,
                                           num_segments=n_pad)
                cand_local = jnp.minimum(cand_local, back)
            cand = jax.lax.pmin(cand_local, axis)
            new = jnp.minimum(val, cand)
            if pointer_jump:
                new = new[new.astype(jnp.int32)].astype(new.dtype)
            return new, jnp.any(new < val), it + 1

        def cond(carry):
            _, changed, it = carry
            return changed & (it < max_iterations)

        val, _, iters = jax.lax.while_loop(
            cond, body, (init, jnp.bool_(True), jnp.int32(0)))
        return val, iters

    return shard_map(
        step, mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis), P()),
        out_specs=(P(), P()))


_INF = jnp.float32(3.4e38)


def sssp_sharded(sg: ShardedGraph, source: int,
                 max_iterations: int = 10_000):
    """Distributed Bellman-Ford (weighted, directed)."""
    init = jnp.full((sg.n_pad,), _INF, dtype=jnp.float32).at[source].set(0.0)
    # inert padding: padding edges must not relax through the sink
    real = jnp.arange(sg.e_pad) < sg.n_edges
    w = jnp.where(real, sg.weights, _INF)
    w = jax.device_put(w, NamedSharding(sg.mesh, P(sg.axis)))
    # init is freshly built per call: donate it back to the iterate
    fn = _sharded_jit("min_propagate", _min_propagate_sharded_fn,
                      sg.mesh, sg.axis, sg.n_pad, max_iterations,
                      False, False, donate=(3,))
    dist, iters = fn(sg.src, sg.dst, w, init)
    out = dist[:sg.n_nodes]
    return jnp.where(out >= _INF / 2, jnp.inf, out), int(iters)


def _wcc_sharded_fn(mesh: Mesh, axis: str, n_pad: int, max_iterations: int):
    """Integer min-label propagation + pointer jumping (separate from the
    float path: float32 cannot represent node indices >= 2^24)."""

    def step(src_blk, dst_blk, init):
        def body(carry):
            comp, _, it = carry
            fwd = jax.ops.segment_min(comp[src_blk], dst_blk,
                                      num_segments=n_pad,
                                      indices_are_sorted=True)
            bwd = jax.ops.segment_min(comp[dst_blk], src_blk,
                                      num_segments=n_pad)
            cand = jax.lax.pmin(jnp.minimum(fwd, bwd), axis)
            new = jnp.minimum(comp, cand)
            new = new[new]  # pointer jump
            return new, jnp.any(new < comp), it + 1

        def cond(carry):
            _, changed, it = carry
            return changed & (it < max_iterations)

        comp, _, iters = jax.lax.while_loop(
            cond, body, (init, jnp.bool_(True), jnp.int32(0)))
        return comp, iters

    return shard_map(
        step, mesh=mesh,
        in_specs=(P(axis), P(axis), P()),
        out_specs=(P(), P()))


def wcc_sharded(sg: ShardedGraph, max_iterations: int = 200):
    """Distributed weakly-connected components (min-label + pointer jump)."""
    init = jnp.arange(sg.n_pad, dtype=jnp.int32)
    fn = _sharded_jit("wcc", _wcc_sharded_fn, sg.mesh, sg.axis,
                      sg.n_pad, max_iterations, donate=(2,))
    comp, iters = fn(sg.src, sg.dst, init)
    return comp[:sg.n_nodes], int(iters)


# ==========================================================================
# Partition-centric kernels over ShardedCSR (the pjit/NamedSharding story)
# ==========================================================================
#
# Inputs are placed ONCE under the MeshContext's NamedShardings
# (ShardedCSR.to_device); the kernels below are shard_mapped over the
# context's edge axis and keep the ONE-collective-per-iteration invariant:
#
#   pagerank  — rank SHARDED over vertex blocks; per-iteration partials
#               land in the (dst-shard, local) partition-centric layout
#               and ONE fused psum_scatter both scatters them to their
#               owners AND rides the dangling-mass / convergence-error
#               partial sums in two extra lanes (so neither needs its
#               own psum — the 3-collective 1.5D scheme collapses to 1).
#   katz      — x replicated, partial A^T x psum-combined: one psum.
#   labelprop — edges owned by DST shard, labels replicated; each round
#               a device elects labels for its own block only and one
#               psum concatenates the disjoint blocks.
#   wcc       — comp replicated, one pmin per round + pointer jumping.
#
# Convergence checks that need a global reduction are carried one
# iteration behind (the error partial rides the NEXT iteration's
# collective), so tol-based runs execute at most one extra iteration —
# never an extra collective.
#
# Resumability (r12): every kernel is a CHUNK — it takes the loop carry
# (state vector(s), convergence partials, iteration counter) plus an
# `it_stop` bound and runs `while cond & (it < it_stop)`. The entry
# points drive chunks through parallel/checkpoint.run_resumable, which
# copies the carry to host every k iterations and resumes from the last
# checkpoint after a device fault. `checkpoint_every=0` runs ONE chunk
# covering the whole budget: identical device program, no host round
# trips — the fast path is the k=∞ degeneracy, not a separate kernel.

_PC_EXTRA = 2          # piggyback lanes: [dangling_mass, prev_local_err]


def _pc_pagerank_build(ctx: MeshContext, block: int, n_shards: int,
                       precision: str = "f32"):
    axis = ctx.axis
    n_pad2 = n_shards * block

    def step(src_blk, dst_blk, w_blk, n_nodes, damping, tol,
             rank, local_err_v, g_err_v, it, it_stop):
        src_blk, dst_blk, w_blk = src_blk[0], dst_blk[0], w_blk[0]
        # local_err is a genuinely per-shard partial (it rides the next
        # iteration's collective), so it crosses chunk boundaries as a
        # P(axis)-sharded (n_shards,) vector: one lane per device
        local_err = local_err_v[0]
        # the trailing global error is one lane of the psum_scatter
        # result: every shard holds the same VALUE, but shard_map types a
        # scattered row as varying over the axis, so it crosses chunk
        # boundaries per shard too (the host reads lane 0)
        g_err_prev = g_err_v[0]
        shard_id = jax.lax.axis_index(axis)
        base = shard_id * block
        n_f = n_nodes.astype(jnp.float32)
        local_ids = base + jnp.arange(block, dtype=jnp.int32)
        valid_f = (local_ids < n_nodes).astype(jnp.float32)

        # edges are src-owned: every out-edge of an owned vertex is
        # local, so the out-weight sum needs no collective
        local_src = src_blk - base
        wsum = jax.ops.segment_sum(w_blk, local_src, num_segments=block)
        inv_wsum = jnp.where(wsum > 0, 1.0 / jnp.maximum(wsum, 1e-30), 0.0)
        dangling_f = valid_f * (wsum <= 0)
        edge_mult = w_blk * inv_wsum[local_src]

        def body(carry):
            rank, local_err, _, it = carry
            contrib = _cast_contrib(rank[local_src] * edge_mult,
                                    precision)
            # the (dst, src) sort within the shard means this sorted
            # segment-sum fills the (dst-shard, local-dst) blocks of the
            # partition-centric layout contiguously
            acc = edge_reduce("sum", contrib, dst_blk, n_pad2,
                              sorted=True).reshape(n_shards, block)
            dm_local = jnp.sum(rank * dangling_f)
            extras = jnp.broadcast_to(
                jnp.stack([dm_local, local_err]), (n_shards, _PC_EXTRA))
            payload = jnp.concatenate([acc, extras], axis=1)
            # THE collective: row q of the payload sum lands on device q
            got = jax.lax.psum_scatter(payload, axis,
                                       scatter_dimension=0, tiled=False)
            acc_own = got[:block]
            dm = got[block]
            g_err_prev = got[block + 1]
            new_rank = pagerank_update(acc_own, dm, valid_f, n_f, damping)
            new_local_err = jnp.sum(jnp.abs(new_rank - rank))
            return new_rank, new_local_err, g_err_prev, it + 1

        def cond(carry):
            _, _, g_err_prev, it = carry
            return (g_err_prev > tol) & (it < it_stop)

        rank, local_err, g_err, iters = jax.lax.while_loop(
            cond, body, (rank, local_err, g_err_prev, it))
        return rank, local_err.reshape(1), g_err.reshape(1), iters

    Pr = P()
    Pe = P(axis, None)
    Pv = P(axis)
    # the chunk carry (rank, local-err lanes, trailing error, iteration
    # counter) is donated: each chunk consumes the previous chunk's
    # output, so donation halves the iterate's HBM residency and the
    # checkpoint layer's host copies are taken from OUTPUTS, never from
    # donated inputs (parallel/checkpoint.run_resumable)
    return jax.jit(shard_map(
        step, mesh=ctx.mesh,
        in_specs=(Pe, Pe, Pe, Pr, Pr, Pr, Pv, Pv, Pv, Pr, Pr),
        out_specs=(Pv, Pv, Pv, Pr)), donate_argnums=(6, 7, 8, 9))


_PC_KERNEL_CACHE: dict = {}


def _pc_cached(kind: str, builder, ctx: MeshContext, *shape_key):
    key = (kind, ctx.cache_key, shape_key)
    fn = _PC_KERNEL_CACHE.get(key)
    if fn is None:
        fn = _PC_KERNEL_CACHE[key] = builder(ctx, *shape_key)
    return fn


def _run_pc_resumable(*, algo, scsr, ctx, chunk_of, carry0, iter_index,
                      max_iterations, checkpoint_every=0, job=None,
                      store=None, retry=None, chunk_deadline_s=None,
                      report=None):
    """Shared driver: wire a partition-centric chunk kernel into the
    checkpoint layer. `chunk_of(scsr)` binds the (possibly re-placed)
    ShardedCSR into a `chunk(carry, it_stop)` callable; after a
    device_lost the rebuild hook re-places the edge rows and re-binds."""
    from .checkpoint import run_resumable
    holder = {"scsr": scsr}

    def rebuild():
        holder["scsr"] = holder["scsr"].refresh(ctx)
        return chunk_of(holder["scsr"])

    carry = run_resumable(
        algo=algo, chunk=chunk_of(scsr), carry=carry0,
        carry_to_host=lambda c: tuple(np.asarray(x) for x in c),
        carry_from_host=lambda p: p,
        iter_of=lambda c: int(c[iter_index]),
        max_iterations=max_iterations,
        checkpoint_every=checkpoint_every, job=job, store=store,
        retry=retry, rebuild=rebuild, chunk_deadline_s=chunk_deadline_s,
        report=report)
    return carry


def _warm_vertex_vector(x0, scsr: ShardedCSR, dtype, pad_value=None):
    """Pad a warm-start (n_nodes,) solution to the mesh's n_pad2 vertex
    space. ``pad_value=None`` fills padding rows with their own index
    (the label-algorithm convention); a scalar fills directly. The
    returned buffer is FRESH — safe to donate into the chunk carry."""
    if pad_value is None:
        v = np.arange(scsr.n_pad2, dtype=dtype)
    else:
        v = np.full(scsr.n_pad2, pad_value, dtype=dtype)
    x0 = np.asarray(x0)
    n = min(len(x0), scsr.n_nodes)
    v[:n] = x0[:n].astype(dtype, copy=False)
    return v


def pagerank_partition_centric(scsr: ShardedCSR, ctx: MeshContext,
                               damping: float = 0.85,
                               max_iterations: int = 100,
                               tol: float = 1e-6, *,
                               precision: str = "f32",
                               x0=None,
                               checkpoint_every: int = 0,
                               job: str | None = None, store=None,
                               retry=None, chunk_deadline_s=None,
                               report=None):
    """PageRank over a partition-centric ShardedCSR: rank sharded over
    vertex blocks, exactly one collective (a fused psum_scatter) per
    power iteration. Returns (ranks[:n_nodes], err, iters).

    The convergence check trails by one iteration (its global reduction
    rides the next iteration's collective), so tol-based runs may do one
    extra iteration; fixed-iteration runs (tol=0) are unchanged.

    `precision="bf16"` rounds per-edge contributions to bfloat16 before
    the f32 accumulation (semiring.PRECISION_BOUNDS documents the error
    budget); the collective payload stays f32.

    `x0` (optional, (n_nodes,) f32) warm-starts the power iteration from
    a previous solution (ops/delta.py commit-then-CALL): PageRank is a
    contraction with a unique fixpoint, so any seed converges to the
    same answer at the same tol — the seed only changes the iteration
    count. The seed is renormalized to unit mass and rides the SAME
    compiled chunk kernel (x0 is data, not structure: no recompile, the
    carry donation covers it).

    `checkpoint_every=k` (> 0) checkpoints the loop carry to host memory
    every k iterations and resumes from the last checkpoint after a
    device fault — re-executing at most k iterations, bit-exact to an
    unfaulted run (parallel/checkpoint.py). `job` keys the checkpoint in
    `store` so a caller that died mid-run can also resume.
    """
    if scsr.by != "src":
        raise ValueError("pagerank needs a src-owned ShardedCSR")
    fn = _pc_cached("pagerank", _pc_pagerank_build, ctx,
                    scsr.block, scsr.n_shards, precision)
    if x0 is None:
        ids = np.arange(scsr.n_pad2, dtype=np.int64)
        rank0 = (ids < scsr.n_nodes).astype(np.float32) \
            / np.float32(scsr.n_nodes)
    else:
        rank0 = _warm_vertex_vector(x0, scsr, np.float32, pad_value=0.0)
        total = float(rank0.sum())
        if not np.isfinite(total) or total <= 0.0:
            ids = np.arange(scsr.n_pad2, dtype=np.int64)
            rank0 = (ids < scsr.n_nodes).astype(np.float32) \
                / np.float32(scsr.n_nodes)
        else:
            rank0 /= np.float32(total)
    carry0 = (rank0,
              np.full((scsr.n_shards,), np.inf, dtype=np.float32),
              np.full((scsr.n_shards,), np.inf, dtype=np.float32),
              np.int32(0))

    def chunk_of(s):
        def chunk(carry, it_stop):
            return fn(s.src, s.dst, s.weights, jnp.int32(s.n_nodes),
                      jnp.float32(damping), jnp.float32(tol),
                      *carry, jnp.int32(it_stop))
        return chunk

    rank, _, err, iters = _run_pc_resumable(
        algo="pagerank", scsr=scsr, ctx=ctx, chunk_of=chunk_of,
        carry0=carry0, iter_index=3, max_iterations=max_iterations,
        checkpoint_every=checkpoint_every, job=job, store=store,
        retry=retry, chunk_deadline_s=chunk_deadline_s, report=report)
    global_metrics.increment("device.fixpoint_iterations_total",
                             int(iters))
    return rank[:scsr.n_nodes], float(err[0]), int(iters)


def _pc_katz_build(ctx: MeshContext, block: int, n_shards: int,
                   precision: str = "f32"):
    axis = ctx.axis
    n_pad2 = n_shards * block

    def step(src_blk, dst_blk, w_blk, n_nodes, alpha, beta, tol,
             x, err, it, it_stop):
        src_blk, dst_blk, w_blk = src_blk[0], dst_blk[0], w_blk[0]
        valid_f = (jnp.arange(n_pad2, dtype=jnp.int32) < n_nodes
                   ).astype(jnp.float32)

        def body(carry):
            x, _, it = carry
            contrib = _cast_contrib(x[src_blk] * w_blk, precision)
            acc_local = edge_reduce("sum", contrib, dst_blk, n_pad2,
                                    sorted=True)
            acc = jax.lax.psum(acc_local, axis)    # the one collective
            new_x = valid_f * (alpha * acc + beta)
            # x is replicated: every device computes the same error —
            # no collective needed for the convergence check
            err = jnp.max(jnp.abs(new_x - x))
            return new_x, err, it + 1

        def cond(carry):
            _, err, it = carry
            return (err > tol) & (it < it_stop)

        x, err, iters = jax.lax.while_loop(cond, body, (x, err, it))
        return x, err, iters

    Pr = P()
    Pe = P(axis, None)
    # carry (x, err, it) donated — see _pc_pagerank_build
    return jax.jit(shard_map(
        step, mesh=ctx.mesh,
        in_specs=(Pe, Pe, Pe, Pr, Pr, Pr, Pr, Pr, Pr, Pr, Pr),
        out_specs=(Pr, Pr, Pr)), donate_argnums=(7, 8, 9))


def _katz_normalize(x):
    """Final L2 normalization, applied once AFTER the outer chunk loop
    (inside the loop it would have to re-run per chunk and break the
    chunked ≡ monolithic equivalence)."""
    x = jnp.asarray(x)
    norm = jnp.sqrt(jnp.sum(x * x))
    return x / jnp.maximum(norm, 1e-30)


def katz_partition_centric(scsr: ShardedCSR, ctx: MeshContext,
                           alpha: float = 0.2, beta: float = 1.0,
                           max_iterations: int = 100, tol: float = 1e-6,
                           normalized: bool = False, *,
                           precision: str = "f32", x0=None,
                           checkpoint_every: int = 0,
                           job: str | None = None, store=None,
                           retry=None, chunk_deadline_s=None,
                           report=None):
    """Katz centrality over the mesh: x replicated, one psum/iteration.
    `x0` warm-starts from a previous (UN-normalized) solution — the
    Katz iteration is a contraction for alpha < 1/λ_max, so any seed
    reaches the same fixpoint at the same tol (ops/delta.py contract).
    Checkpoint/resume semantics as in `pagerank_partition_centric`."""
    fn = _pc_cached("katz", _pc_katz_build, ctx,
                    scsr.block, scsr.n_shards, precision)
    start = (np.zeros(scsr.n_pad2, dtype=np.float32) if x0 is None
             else _warm_vertex_vector(x0, scsr, np.float32,
                                      pad_value=0.0))
    carry0 = (start,
              np.float32(np.inf), np.int32(0))

    def chunk_of(s):
        def chunk(carry, it_stop):
            return fn(s.src, s.dst, s.weights, jnp.int32(s.n_nodes),
                      jnp.float32(alpha), jnp.float32(beta),
                      jnp.float32(tol), *carry, jnp.int32(it_stop))
        return chunk

    x, err, iters = _run_pc_resumable(
        algo="katz", scsr=scsr, ctx=ctx, chunk_of=chunk_of,
        carry0=carry0, iter_index=2, max_iterations=max_iterations,
        checkpoint_every=checkpoint_every, job=job, store=store,
        retry=retry, chunk_deadline_s=chunk_deadline_s, report=report)
    if normalized:
        x = _katz_normalize(x)
    return x[:scsr.n_nodes], float(err), int(iters)


def _pc_labelprop_build(ctx: MeshContext, block: int, n_shards: int,
                        per: int):
    axis = ctx.axis
    n_pad2 = n_shards * block

    def step(src_blk, dst_blk, w_blk, self_weight,
             labels_in, changed_in, it, it_stop):
        src_blk, dst_blk, w_blk = src_blk[0], dst_blk[0], w_blk[0]
        shard_id = jax.lax.axis_index(axis)
        base = shard_id * block

        def one_round(labels):
            # edges are DST-owned: every incident edge of an owned
            # vertex is local, so run reduction + election are local
            lab_e = labels[src_blk]
            d_s, l_s, w_s = jax.lax.sort((dst_blk, lab_e, w_blk),
                                         num_keys=2)
            first = jnp.concatenate([
                jnp.ones((1,), dtype=jnp.bool_),
                (d_s[1:] != d_s[:-1]) | (l_s[1:] != l_s[:-1])])
            run_id = jnp.cumsum(first.astype(jnp.int32)) - 1
            run_w = jax.ops.segment_sum(w_s, run_id, num_segments=per)
            idx = jnp.arange(per, dtype=jnp.int32)
            first_idx = jax.ops.segment_min(
                jnp.where(first, idx, per), run_id, num_segments=per)
            first_idx = jnp.minimum(first_idx, per - 1)
            run_dst_local = d_s[first_idx] - base
            run_lab = l_s[first_idx]
            valid_run = idx <= run_id[-1]
            # padding edges carry weight 0 into the sink row; runs that
            # fall outside the local block clip to an ignored slot
            in_block = (run_dst_local >= 0) & (run_dst_local < block)
            run_dst_local = jnp.clip(run_dst_local, 0, block - 1)
            run_w = jnp.where(valid_run & in_block, run_w, 0.0)
            best_w = jax.ops.segment_max(run_w, run_dst_local,
                                         num_segments=block)
            is_best = run_w >= best_w[run_dst_local] - 1e-12
            cand = jnp.where(valid_run & in_block & is_best, run_lab,
                             jnp.int32(n_pad2))
            best_lab = jax.ops.segment_min(cand, run_dst_local,
                                           num_segments=block)
            has_nb = best_lab < n_pad2
            own = jax.lax.dynamic_slice(labels, (base,), (block,))
            own_wins = (~has_nb) | (self_weight >= best_w) | \
                       (jnp.isclose(self_weight, best_w)
                        & (own <= best_lab))
            new_local = jnp.where(own_wins, own, best_lab)
            # disjoint block election: one psum concatenates the blocks
            contrib = jax.lax.dynamic_update_slice(
                jnp.zeros(n_pad2, dtype=jnp.int32), new_local, (base,))
            return jax.lax.psum(contrib, axis)

        def body(carry):
            labels, _, it = carry
            new = one_round(labels)
            return new, jnp.any(new != labels), it + 1

        def cond(carry):
            _, changed, it = carry
            return changed & (it < it_stop)

        labels, changed, iters = jax.lax.while_loop(
            cond, body, (labels_in, changed_in, it))
        return labels, changed, iters

    Pr = P()
    Pe = P(axis, None)
    # carry (labels, changed, it) donated — see _pc_pagerank_build
    return jax.jit(shard_map(
        step, mesh=ctx.mesh,
        in_specs=(Pe, Pe, Pe, Pr, Pr, Pr, Pr, Pr),
        out_specs=(Pr, Pr, Pr)), donate_argnums=(4, 5, 6))


def labelprop_partition_centric(scsr: ShardedCSR, ctx: MeshContext,
                                max_iterations: int = 30,
                                self_weight: float = 0.0, *,
                                labels0=None,
                                checkpoint_every: int = 0,
                                job: str | None = None, store=None,
                                retry=None, chunk_deadline_s=None,
                                report=None):
    """Synchronous label propagation over the mesh (dst-owned edges,
    labels replicated, one int psum per round). `scsr` must be built
    with by="dst" (both edge directions already concatenated for the
    undirected variant). Returns (labels[:n_nodes], iters).

    `labels0` warm-starts the election from a previous labeling —
    ONLY valid when the delta since that labeling added edges (the
    monotone gate in ops/delta.py): the election re-runs over a
    superset of neighbors and re-converges; removals must cold-start
    LOUDLY because a community held together by a removed edge would
    never re-elect. Checkpoint/resume semantics as in
    `pagerank_partition_centric`."""
    if scsr.by != "dst":
        raise ValueError("labelprop needs a dst-owned ShardedCSR")
    fn = _pc_cached("labelprop", _pc_labelprop_build, ctx,
                    scsr.block, scsr.n_shards, scsr.per)
    start = (np.arange(scsr.n_pad2, dtype=np.int32) if labels0 is None
             else _warm_vertex_vector(labels0, scsr, np.int32))
    carry0 = (start,
              np.bool_(True), np.int32(0))

    def chunk_of(s):
        def chunk(carry, it_stop):
            return fn(s.src, s.dst, s.weights, jnp.float32(self_weight),
                      *carry, jnp.int32(it_stop))
        return chunk

    labels, _, iters = _run_pc_resumable(
        algo="labelprop", scsr=scsr, ctx=ctx, chunk_of=chunk_of,
        carry0=carry0, iter_index=2, max_iterations=max_iterations,
        checkpoint_every=checkpoint_every, job=job, store=store,
        retry=retry, chunk_deadline_s=chunk_deadline_s, report=report)
    return labels[:scsr.n_nodes], int(iters)


def _pc_wcc_build(ctx: MeshContext, block: int, n_shards: int):
    axis = ctx.axis
    n_pad2 = n_shards * block

    def step(src_blk, dst_blk, comp_in, changed_in, it, it_stop):
        src_blk, dst_blk = src_blk[0], dst_blk[0]

        def body(carry):
            comp, _, it = carry
            fwd = jax.ops.segment_min(comp[src_blk], dst_blk,
                                      num_segments=n_pad2,
                                      indices_are_sorted=True)
            bwd = jax.ops.segment_min(comp[dst_blk], src_blk,
                                      num_segments=n_pad2)
            cand = jax.lax.pmin(jnp.minimum(fwd, bwd), axis)  # the one
            new = jnp.minimum(comp, cand)
            new = new[new]                     # pointer jump, replicated
            return new, jnp.any(new != comp), it + 1

        def cond(carry):
            _, changed, it = carry
            return changed & (it < it_stop)

        comp, changed, iters = jax.lax.while_loop(
            cond, body, (comp_in, changed_in, it))
        return comp, changed, iters

    Pr = P()
    Pe = P(axis, None)
    # carry (comp, changed, it) donated — see _pc_pagerank_build
    return jax.jit(shard_map(
        step, mesh=ctx.mesh,
        in_specs=(Pe, Pe, Pr, Pr, Pr, Pr),
        out_specs=(Pr, Pr, Pr)), donate_argnums=(2, 3, 4))


def wcc_partition_centric(scsr: ShardedCSR, ctx: MeshContext,
                          max_iterations: int = 200, *,
                          comp0=None,
                          checkpoint_every: int = 0,
                          job: str | None = None, store=None,
                          retry=None, chunk_deadline_s=None,
                          report=None):
    """Weakly-connected components over the mesh: comp replicated, one
    pmin per round + pointer jumping. Returns (comp[:n_nodes], iters).

    `comp0` warm-starts from a previous min-label assignment — ONLY
    valid when the delta since it added edges (the monotone gate in
    ops/delta.py): min-label propagation can merge components but never
    split them, so a removal-carrying delta must cold-start LOUDLY.
    Checkpoint/resume semantics as in `pagerank_partition_centric`."""
    fn = _pc_cached("wcc", _pc_wcc_build, ctx,
                    scsr.block, scsr.n_shards)
    start = (np.arange(scsr.n_pad2, dtype=np.int32) if comp0 is None
             else _warm_vertex_vector(comp0, scsr, np.int32))
    carry0 = (start,
              np.bool_(True), np.int32(0))

    def chunk_of(s):
        def chunk(carry, it_stop):
            return fn(s.src, s.dst, *carry, jnp.int32(it_stop))
        return chunk

    comp, _, iters = _run_pc_resumable(
        algo="wcc", scsr=scsr, ctx=ctx, chunk_of=chunk_of,
        carry0=carry0, iter_index=2, max_iterations=max_iterations,
        checkpoint_every=checkpoint_every, job=job, store=store,
        retry=retry, chunk_deadline_s=chunk_deadline_s, report=report)
    return comp[:scsr.n_nodes], int(iters)


# ==========================================================================
# Generic semiring kernel (ops/semiring.py's mesh backend)
# ==========================================================================
#
# A NEW algorithm's mesh story is now a (semiring, x0, epilogue) triple:
# x replicated, per-shard ⊗-combine + local ⊕-reduce, ONE ⊕-matched
# collective per iteration (psum / pmin / pmax), the fused epilogue
# applied replicated — same invariants as the tuned kernels above, and
# checkpoint-resumable through the same r12 chunk machinery.

_PC_COLLECTIVE = {"sum": jax.lax.psum, "min": jax.lax.pmin,
                  "max": jax.lax.pmax, "or": jax.lax.pmax}


def _pc_semiring_build(ctx: MeshContext, block: int, n_shards: int,
                       sr_name: str, epilogue, metric: str,
                       precision: str):
    sr = resolve_semiring(sr_name)
    axis = ctx.axis
    n_pad2 = n_shards * block
    collective = _PC_COLLECTIVE[sr.add]

    def step(src_blk, dst_blk, w_blk, params, x, m, it, it_stop):
        src_blk, dst_blk, w_blk = src_blk[0], dst_blk[0], w_blk[0]

        def body(carry):
            x, _, it = carry
            vals = edge_combine(sr, x[src_blk],
                                None if sr.mul == "first" else w_blk)
            if jnp.issubdtype(vals.dtype, jnp.floating):
                vals = _cast_contrib(vals, precision)
            acc_local = edge_reduce(sr.add, vals, dst_blk, n_pad2,
                                    sorted=True)
            acc = collective(acc_local, axis)      # the one collective
            new_x, new_m = epilogue(x, acc, {}, params)
            return new_x, new_m, it + 1

        if metric == "changed":
            def cond(carry):
                _, m, it = carry
                return m & (it < it_stop)
        else:
            def cond(carry):
                _, m, it = carry
                return (m > params["tol"]) & (it < it_stop)

        return jax.lax.while_loop(cond, body, (x, m, it))

    Pr = P()
    Pe = P(axis, None)
    # carry (x, m, it) donated — see _pc_pagerank_build
    return jax.jit(shard_map(
        step, mesh=ctx.mesh,
        in_specs=(Pe, Pe, Pe, Pr, Pr, Pr, Pr, Pr),
        out_specs=(Pr, Pr, Pr)), donate_argnums=(4, 5, 6))


def semiring_partition_centric(scsr: ShardedCSR, ctx: MeshContext,
                               semiring, x0, epilogue, params=None,
                               max_iterations: int = 100,
                               metric: str = "changed",
                               precision: str = "f32", *,
                               algo: str = "semiring",
                               checkpoint_every: int = 0,
                               job: str | None = None, store=None,
                               retry=None, chunk_deadline_s=None,
                               report=None):
    """Run a (semiring, x0, epilogue) fixpoint over the mesh: exactly
    one collective per iteration, checkpoint-resumable. Returns
    (x[:n_nodes], metric, iters)."""
    sr = resolve_semiring(semiring)
    params = params or {}
    fn = _pc_cached(f"semiring:{sr.name}", _pc_semiring_build, ctx,
                    scsr.block, scsr.n_shards, sr.name, epilogue,
                    metric, precision)
    m0 = np.bool_(True) if metric == "changed" \
        else np.float32(np.inf)
    carry0 = (np.asarray(x0), m0, np.int32(0))

    def chunk_of(s):
        def chunk(carry, it_stop):
            return fn(s.src, s.dst, s.weights, params, *carry,
                      jnp.int32(it_stop))
        return chunk

    x, m, iters = _run_pc_resumable(
        algo=algo, scsr=scsr, ctx=ctx, chunk_of=chunk_of,
        carry0=carry0, iter_index=2, max_iterations=max_iterations,
        checkpoint_every=checkpoint_every, job=job, store=store,
        retry=retry, chunk_deadline_s=chunk_deadline_s, report=report)
    return x[:scsr.n_nodes], m, int(iters)


def _minplus_relax_epilogue(x, acc, env, P):
    """min-plus relaxation epilogue (BFS / SSSP over the mesh)."""
    new = jnp.minimum(x, acc)
    return new, jnp.any(new < x)


# ==========================================================================
# mgtier execution plane: streamed out-of-core fixpoints
# ==========================================================================
#
# The data plane (ops/tier.py) pins the ShardedCSR rows host-side as
# compressed wire blocks; this is the loop that runs a fixpoint over
# them without ever holding the edge set on the device:
#
#   per iteration (one sweep over all P blocks):
#     dispatch device_put(block 0)                      # H2D, async
#     for k in 0..P-1:
#       dispatch device_put(block k+1)                  # next buffer
#       acc = fold(acc, block k)                        # SpMV on k
#     x, metric = epilogue(x, acc)                      # O(n), on-device
#
# JAX's async dispatch turns the two in-flight buffers into the classic
# double-buffer schedule (the pallas-guide DMA pattern applied at the
# host→HBM boundary): block k+1's transfer runs while block k's segment
# reduction executes, so steady-state cost is max(transfer, compute)
# per block instead of the sum. The O(n) iterate/accumulator/env
# vectors stay device-resident across the whole run.
#
# Honest measurement: the FIRST streamed iteration runs the schedule
# serially (put → wait → fold → wait, per block) to price transfer and
# compute separately; later iterations run overlapped and the per-
# iteration wall clock yields `hidden = (T_xfer + T_comp - T_iter) /
# T_xfer` — the fraction of transfer the overlap actually hid (≈0 on a
# CPU host where "transfer" is a memcpy; the perf gate tags that
# degraded rather than asserting a fantasy).
#
# The resident comparator (`resident=True`) pre-places every block and
# runs the IDENTICAL kernels in the identical order — the FLOP schedule
# is shared, only the transfer schedule differs, which is what makes
# the streamed-vs-resident f32 bit-exactness test meaningful.

_TIER_KERNEL_CACHE: dict = {}


def _tier_cached(kind: str, builder, *shape_key):
    key = (kind,) + shape_key
    fn = _TIER_KERNEL_CACHE.get(key)
    if fn is None:
        fn = _TIER_KERNEL_CACHE[key] = builder(*shape_key)
    return fn


def _tier_decode(blk, block: int, per: int, precision: str, u16: bool,
                 need_w: bool = True):
    """Traced half of the ops/tier.py codec: rebuild (src, dst, w) from
    a wire block INSIDE the jitted sweep, so only compressed bytes cross
    the host→device boundary. Index decode is exact (uint16 offsets +
    shard bases); weights dequantize per the tier's precision with f32
    accumulation downstream."""
    if u16:
        src = blk["src_off"].astype(jnp.int32) + blk["base"]
        q = jnp.searchsorted(
            blk["bounds"][1:], jnp.arange(per, dtype=jnp.int32),
            side="right").astype(jnp.int32)
        dst = blk["dst_off"].astype(jnp.int32) + q * block
    else:
        src, dst = blk["src"], blk["dst"]
    if not need_w:
        return src, dst, None
    w = blk["w"]
    if precision == "bf16":
        w = w.astype(jnp.float32)
    elif precision == "int8":
        w = w.astype(jnp.float32) * blk["scale"]
    return src, dst, w


def _tier_wsum_build(block, per, n_pad2, precision, u16):
    def step(acc, blk):
        src, _dst, w = _tier_decode(blk, block, per, precision, u16)
        return acc + jax.ops.segment_sum(w, src, num_segments=n_pad2)
    return jax.jit(step, donate_argnums=(0,))


def _tier_pagerank_sweep_build(block, per, n_pad2, precision, u16):
    def step(acc, x, inv_wsum, blk):
        src, dst, w = _tier_decode(blk, block, per, precision, u16)
        contrib = x[src] * (w * inv_wsum[src])
        contrib = _cast_contrib(contrib,
                                "bf16" if precision == "bf16" else "f32")
        return acc + jax.ops.segment_sum(contrib, dst,
                                         num_segments=n_pad2,
                                         indices_are_sorted=True)
    return jax.jit(step, donate_argnums=(0,))


def _tier_pagerank_epilogue_build(n_pad2):
    def fin(x, acc, dangling_f, valid_f, n_f, damping):
        dm = jnp.sum(x * dangling_f)
        new = pagerank_update(acc, dm, valid_f, n_f, damping)
        err = jnp.sum(jnp.abs(new - x))
        return new, err
    # only ONE O(n) output exists to alias — donating both x and acc
    # makes XLA silently COPY the second (a UserWarning at compile, a
    # full extra iterate on a production device). tools/mgmem gates
    # dropped donations; declare exactly the donation that lands.
    return jax.jit(fin, donate_argnums=(0,))


def _tier_katz_sweep_build(block, per, n_pad2, precision, u16):
    def step(acc, x, blk):
        src, dst, w = _tier_decode(blk, block, per, precision, u16)
        contrib = _cast_contrib(
            x[src] * w, "bf16" if precision == "bf16" else "f32")
        return acc + jax.ops.segment_sum(contrib, dst,
                                         num_segments=n_pad2,
                                         indices_are_sorted=True)
    return jax.jit(step, donate_argnums=(0,))


def _tier_katz_epilogue_build(n_pad2):
    def fin(x, acc, valid_f, alpha, beta):
        new = valid_f * (alpha * acc + beta)
        err = jnp.max(jnp.abs(new - x))
        return new, err
    # one O(n) output slot: donate only the alias that lands (mgmem)
    return jax.jit(fin, donate_argnums=(0,))


def _tier_wcc_sweep_build(block, per, n_pad2, u16):
    def step(cand, comp, blk):
        src, dst, _ = _tier_decode(blk, block, per, "f32", u16,
                                   need_w=False)
        # padding edges carry a REAL local src (the shard base) toward
        # the sink row; weightless min-reductions must mask them or the
        # sink merges unrelated components on the backward pass
        real = jnp.arange(per, dtype=jnp.int32) < blk["rc"]
        ident = jnp.int32(n_pad2)
        fwd = jnp.where(real, comp[src], ident)
        bwd = jnp.where(real, comp[dst], ident)
        cand = jnp.minimum(cand, jax.ops.segment_min(
            fwd, dst, num_segments=n_pad2, indices_are_sorted=True))
        cand = jnp.minimum(cand, jax.ops.segment_min(
            bwd, src, num_segments=n_pad2))
        return cand
    return jax.jit(step, donate_argnums=(0,))


def _tier_wcc_epilogue_build(n_pad2):
    def fin(comp, cand):
        new = jnp.minimum(comp, cand)
        new = new[new]                        # pointer jump
        changed = jnp.any(new != comp)
        return new, changed
    # one O(n) output slot: donate only the alias that lands (mgmem)
    return jax.jit(fin, donate_argnums=(0,))


def _put_block(hb, device):
    return jax.device_put(hb.payload, device)


def _tier_sweep(tier, dev_blocks, fold, acc, device, measure=None):
    """One full pass over the edge blocks: ``acc = fold(acc, blk)``.

    ``dev_blocks`` set → resident comparator (pre-placed, same kernels,
    same order). ``measure`` set → serial timed schedule (prices
    transfer vs compute separately). Otherwise the double-buffered
    streaming schedule: block k+1's put is dispatched before block k's
    fold, so the H2D copy overlaps the segment reduction.
    """
    if dev_blocks is not None:
        for blk in dev_blocks:
            acc = fold(acc, blk)
        return acc
    blocks = tier.blocks
    if measure is not None:
        for hb in blocks:
            t0 = time.perf_counter()
            blk = jax.block_until_ready(_put_block(hb, device))  # mglint: disable=MG009 — the MEASURED serial iteration exists to price transfer vs compute separately; the sync IS the measurement, and it runs exactly once per run
            t1 = time.perf_counter()
            acc = jax.block_until_ready(fold(acc, blk))  # mglint: disable=MG009 — same measured-iteration contract: without the per-block sync the async dispatch would hide exactly the cost being priced
            t2 = time.perf_counter()
            measure["t_xfer"] += t1 - t0
            measure["t_comp"] += t2 - t1
            global_metrics.observe("tier.block_transfer_latency_sec",
                                   t1 - t0)
        return acc
    nxt = _put_block(blocks[0], device)
    for k in range(len(blocks)):
        cur, nxt = nxt, (_put_block(blocks[k + 1], device)
                         if k + 1 < len(blocks) else None)
        acc = fold(acc, cur)
    return acc


def _count_sweep(tier):
    global_metrics.increment("tier.blocks_streamed_total",
                             tier.n_blocks)
    global_metrics.increment("tier.bytes_streamed_total",
                             tier.raw_bytes_per_sweep)
    global_metrics.increment("tier.compressed_bytes_total",
                             tier.wire_bytes_per_sweep)


def _tier_fixpoint(*, algo, tier, env_of, iterate, x0, metric0,
                   keep_going, max_iterations, resident=False,
                   stats=None, checkpoint_every=0, job=None, store=None,
                   retry=None, chunk_deadline_s=None, report=None):
    """Shared streamed-fixpoint driver, wired into the checkpoint layer.

    ``env_of(device, sweep)`` builds the per-run device-resident
    environment (may itself sweep the blocks, e.g. pagerank's wsum
    pass); ``iterate(x, env, sweep)`` runs ONE iteration (sweep +
    epilogue) and returns ``(new_x, metric)`` with a device metric.
    Chunks checkpoint the (x, metric, it) carry to host; a device fault
    resumes from the last chunk boundary, a ``device_lost`` additionally
    drops the env/resident blocks so they re-place on the fresh client.
    """
    from .checkpoint import run_resumable
    device = streaming_device()
    # price the run through the admission estimator the server's
    # verdict used — every device materialization below (block H2D,
    # carry re-place, accumulator/env vectors in the drivers) lives
    # inside this modeled budget, which tools/mgmem machine-checks
    # against XLA's buffer assignment per phase (MG011 accounting root)
    global_metrics.set_gauge(
        "tier.modeled_request_bytes",
        float(mgtier.streamed_request_bytes(
            tier.n_nodes, tier.n_edges, tier.precision,
            algorithm=algo)))
    holder: dict = {}
    measured = {"serial": None, "iters": 0, "hidden_sum": 0.0,
                "overlap_iters": 0, "overlap_wall": 0.0}

    def dev_blocks():
        if not resident:
            return None
        db = holder.get("blocks")
        if db is None:
            db = holder["blocks"] = [_put_block(hb, device)
                                     for hb in tier.blocks]
        return db

    def sweep(fold, acc, measure=None):
        out = _tier_sweep(tier, dev_blocks(), fold, acc, device,
                          measure=measure)
        if not resident:
            _count_sweep(tier)
        return out

    def env():
        e = holder.get("env")
        if e is None:
            e = holder["env"] = env_of(device, sweep)
        return e

    def chunk(carry, it_stop):
        x, metric, it = carry
        x = jax.device_put(x, device)
        while it < it_stop and keep_going(metric):
            measure = None
            if not resident and measured["serial"] is None:
                measure = {"t_xfer": 0.0, "t_comp": 0.0}
            t0 = time.perf_counter()
            x, m_dev = iterate(x, env(),
                               lambda f, a: sweep(f, a, measure))
            metric = np.asarray(m_dev)  # mglint: disable=MG009 — the host drives the per-block streaming loop, so the per-ITERATION convergence read is the sync granularity by construction (the sweep inside the iteration is where overlap lives)
            wall = time.perf_counter() - t0
            if measure is not None:
                measured["serial"] = measure
                mgstats.record_stage("device_transfer",
                                     measure["t_xfer"])
            elif not resident and measured["serial"] is not None:
                s = measured["serial"]
                if s["t_xfer"] > 0:
                    hidden = (s["t_xfer"] + s["t_comp"] - wall) \
                        / s["t_xfer"]
                    hidden = min(max(hidden, 0.0), 1.0)
                    measured["hidden_sum"] += hidden
                    measured["overlap_iters"] += 1
                    measured["overlap_wall"] += wall
                    global_metrics.observe(
                        "tier.transfer_hidden_fraction", hidden)
            measured["iters"] += 1
            it += 1
        return x, metric, it

    def rebuild():
        holder.clear()                        # re-place env + blocks
        return None                           # chunk closure re-reads

    x, metric, iters = run_resumable(
        algo=algo, chunk=chunk, carry=(np.asarray(x0), metric0, 0),
        carry_to_host=lambda c: (np.asarray(c[0]), np.asarray(c[1]),
                                 int(c[2])),
        carry_from_host=lambda p: p, iter_of=lambda c: int(c[2]),
        max_iterations=max_iterations,
        checkpoint_every=checkpoint_every, job=job, store=store,
        retry=retry, rebuild=rebuild, chunk_deadline_s=chunk_deadline_s,
        report=report)

    if stats is not None:
        s = measured["serial"] or {"t_xfer": 0.0, "t_comp": 0.0}
        n_ov = measured["overlap_iters"]
        stats.update({
            "mode": "resident" if resident else "streamed",
            "precision": tier.precision,
            "n_blocks": tier.n_blocks,
            "iterations": int(iters),
            "wire_bytes_per_sweep": tier.wire_bytes_per_sweep,
            "raw_bytes_per_sweep": tier.raw_bytes_per_sweep,
            "serial_transfer_s": s["t_xfer"],
            "serial_compute_s": s["t_comp"],
            "overlap_iters": n_ov,
            "overlap_iter_s_mean": (measured["overlap_wall"] / n_ov)
            if n_ov else None,
            "transfer_hidden_fraction": (measured["hidden_sum"] / n_ov)
            if n_ov else None,
        })
    return x, metric, int(iters)


def pagerank_streamed(tier, damping: float = 0.85,
                      max_iterations: int = 100, tol: float = 1e-6, *,
                      x0=None, resident: bool = False, stats=None,
                      checkpoint_every: int = 0, job: str | None = None,
                      store=None, retry=None, chunk_deadline_s=None,
                      report=None):
    """PageRank over a host-pinned :class:`~..ops.tier.TierCSR` —
    out-of-core: only edge blocks stream, the rank vector stays
    device-resident. Returns ``(ranks[:n], err, iters)``."""
    scsr, n, n_pad2 = tier.scsr, tier.n_nodes, tier.n_pad2
    shape = (tier.block, tier.per, n_pad2, tier.precision, tier.u16)
    wsum_fn = _tier_cached("wsum", _tier_wsum_build, *shape)
    sweep_fn = _tier_cached("pr_sweep", _tier_pagerank_sweep_build,
                            *shape)
    epi_fn = _tier_cached("pr_epi", _tier_pagerank_epilogue_build,
                          n_pad2)
    n_f = np.float32(n)
    damping = np.float32(damping)

    if x0 is None:
        x0v = np.zeros(n_pad2, np.float32)
        x0v[:n] = 1.0 / n
    else:
        x0v = _warm_vertex_vector(x0, scsr, np.float32, pad_value=0.0)

    def env_of(device, sweep):
        valid = np.zeros(n_pad2, np.float32)
        valid[:n] = 1.0
        valid_f = jax.device_put(valid, device)
        wsum = sweep(wsum_fn, jnp.zeros(n_pad2, jnp.float32))
        dangling_f = valid_f * (wsum == 0.0)
        inv_wsum = jnp.where(wsum > 0.0, 1.0 / wsum, 0.0)
        return {"valid_f": valid_f, "dangling_f": dangling_f,
                "inv_wsum": inv_wsum}

    def iterate(x, env, sweep):
        acc = sweep(lambda a, blk: sweep_fn(a, x, env["inv_wsum"], blk),
                    jnp.zeros(n_pad2, jnp.float32))
        return epi_fn(x, acc, env["dangling_f"], env["valid_f"],
                      n_f, damping)

    with backend_extent("streamed"):
        x, err, iters = _tier_fixpoint(
            algo="pagerank", tier=tier, env_of=env_of, iterate=iterate,
            x0=x0v, metric0=np.float32(np.inf),
            keep_going=lambda m: float(m) > tol,
            max_iterations=max_iterations, resident=resident,
            stats=stats, checkpoint_every=checkpoint_every, job=job,
            store=store, retry=retry,
            chunk_deadline_s=chunk_deadline_s, report=report)
    return np.asarray(x)[:n], float(err), iters


def katz_streamed(tier, alpha: float = 0.1, beta: float = 1.0,
                  max_iterations: int = 100, tol: float = 1e-6, *,
                  normalized: bool = True, x0=None,
                  resident: bool = False, stats=None,
                  checkpoint_every: int = 0, job: str | None = None,
                  store=None, retry=None, chunk_deadline_s=None,
                  report=None):
    """Katz centrality over a host-pinned TierCSR. Returns
    ``(scores[:n], err, iters)``."""
    scsr, n, n_pad2 = tier.scsr, tier.n_nodes, tier.n_pad2
    shape = (tier.block, tier.per, n_pad2, tier.precision, tier.u16)
    sweep_fn = _tier_cached("katz_sweep", _tier_katz_sweep_build,
                            *shape)
    epi_fn = _tier_cached("katz_epi", _tier_katz_epilogue_build, n_pad2)
    alpha = np.float32(alpha)
    beta = np.float32(beta)
    x0v = (np.zeros(n_pad2, np.float32) if x0 is None
           else _warm_vertex_vector(x0, scsr, np.float32, pad_value=0.0))

    def env_of(device, sweep):
        valid = np.zeros(n_pad2, np.float32)
        valid[:n] = 1.0
        return {"valid_f": jax.device_put(valid, device)}

    def iterate(x, env, sweep):
        acc = sweep(lambda a, blk: sweep_fn(a, x, blk),
                    jnp.zeros(n_pad2, jnp.float32))
        return epi_fn(x, acc, env["valid_f"], alpha, beta)

    with backend_extent("streamed"):
        x, err, iters = _tier_fixpoint(
            algo="katz", tier=tier, env_of=env_of, iterate=iterate,
            x0=x0v, metric0=np.float32(np.inf),
            keep_going=lambda m: float(m) > tol,
            max_iterations=max_iterations, resident=resident,
            stats=stats, checkpoint_every=checkpoint_every, job=job,
            store=store, retry=retry,
            chunk_deadline_s=chunk_deadline_s, report=report)
    out = np.asarray(x)[:n]
    if normalized:
        nrm = float(np.linalg.norm(out))
        if nrm > 0:
            out = out / nrm
    return out, float(err), iters


def wcc_streamed(tier, max_iterations: int = 200, *, comp0=None,
                 resident: bool = False, stats=None,
                 checkpoint_every: int = 0, job: str | None = None,
                 store=None, retry=None, chunk_deadline_s=None,
                 report=None):
    """Weakly-connected components over a host-pinned TierCSR (min-
    label propagation + pointer jumping). Returns
    ``(labels[:n], changed, iters)``."""
    scsr, n, n_pad2 = tier.scsr, tier.n_nodes, tier.n_pad2
    shape = (tier.block, tier.per, n_pad2, tier.u16)
    sweep_fn = _tier_cached("wcc_sweep", _tier_wcc_sweep_build, *shape)
    epi_fn = _tier_cached("wcc_epi", _tier_wcc_epilogue_build, n_pad2)
    x0v = (np.arange(n_pad2, dtype=np.int32) if comp0 is None
           else _warm_vertex_vector(comp0, scsr, np.int32))

    def env_of(device, sweep):
        return {}

    def iterate(comp, env, sweep):
        cand = sweep(lambda a, blk: sweep_fn(a, comp, blk),
                     jnp.full(n_pad2, n_pad2, jnp.int32))
        return epi_fn(comp, cand)

    with backend_extent("streamed"):
        comp, changed, iters = _tier_fixpoint(
            algo="wcc", tier=tier, env_of=env_of, iterate=iterate,
            x0=x0v, metric0=np.bool_(True),
            keep_going=lambda m: bool(m),
            max_iterations=max_iterations, resident=resident,
            stats=stats, checkpoint_every=checkpoint_every, job=job,
            store=store, retry=retry,
            chunk_deadline_s=chunk_deadline_s, report=report)
    return np.asarray(comp)[:n], bool(changed), iters
