"""Algorithm-level mesh entry points: DeviceGraph in, results out.

The seam between `ops/` (single-chip algorithms over DeviceGraph
snapshots) and `parallel/distributed.py` (partition-centric kernels over
ShardedCSR). Each `*_mesh` function:

  1. blocks the snapshot's edges partition-centrically for the given
     MeshContext (cached on the immutable DeviceGraph, so repeated CALLs
     pay the blocking + device transfer once),
  2. runs the sharded kernel (one collective per iteration), and
  3. returns exactly the same (values[:n_nodes], ...) shape as the
     single-chip entry point it mirrors.

The mesh-of-1 context runs the SAME code path — `psum`/`psum_scatter`
over a 1-device axis compiles to a copy — so single-device is a
degeneracy of the sharded story, not a separate implementation.
`ops/pagerank.py` (and katz/labelprop/components) route here whenever a
mesh is requested (explicit `mesh=` argument or the
MEMGRAPH_TPU_MESH_DEVICES env default; see `parallel/mesh.py`).

Resilience (r12): every iterative entry point accepts
``checkpoint_every=k`` (plus ``job``/``store``/``report``) and routes
through `parallel/checkpoint.run_resumable` — the loop carry is copied
to host memory every k iterations and a device fault resumes from the
last checkpoint, bit-exact, instead of restarting. The
MEMGRAPH_TPU_CHECKPOINT_EVERY env var sets the default k for callers
that do not pass one (0 = single full-budget chunk, no host round
trips); the kernel server passes it explicitly.
"""

from __future__ import annotations

import os

import numpy as np

from .mesh import MeshContext
from ..observability import trace as mgtrace
from ..ops.csr import DeviceGraph, shard_csr


def _shard_traced(graph: DeviceGraph, ctx: MeshContext, by: str = "src",
                  doubled: bool = False):
    """shard_csr under a ``device.transfer`` span: the partition-centric
    blocking + device placement stage of the trace (cache hits show as
    ~zero-duration spans, which is itself useful signal). The same
    extent attributes to the active mgstat stage accumulator, so a
    PROFILE-d query sees transfer seconds even with tracing disarmed."""
    with mgtrace.span("device.transfer") as sp:
        scsr = shard_csr(graph, ctx, by=by, doubled=doubled)
        if sp:
            sp.set(n_shards=ctx.n_shards, by=by,
                   n_nodes=int(graph.n_nodes))
    return scsr


def default_checkpoint_every() -> int:
    """Process-default checkpoint interval for mesh analytics (env
    MEMGRAPH_TPU_CHECKPOINT_EVERY; 0 disables intermediate
    checkpoints — one full-budget chunk, the classic fast path)."""
    try:
        return max(0, int(os.environ.get(
            "MEMGRAPH_TPU_CHECKPOINT_EVERY", "0")))
    except ValueError:
        return 0


def _resume_kw(checkpoint_every, job, store, report, retry):
    if checkpoint_every is None:
        checkpoint_every = default_checkpoint_every()
    return {"checkpoint_every": checkpoint_every, "job": job,
            "store": store, "report": report, "retry": retry}


def pagerank_mesh(graph: DeviceGraph, ctx: MeshContext,
                  damping: float = 0.85, max_iterations: int = 100,
                  tol: float = 1e-6, *, precision: str = "f32",
                  x0=None,
                  checkpoint_every: int | None = None,
                  job: str | None = None, store=None, report=None,
                  retry=None):
    """Sharded PageRank; same contract as ops.pagerank.pagerank.
    ``x0`` warm-starts from a previous solution (ops/delta.py)."""
    from .distributed import pagerank_partition_centric
    scsr = _shard_traced(graph, ctx, by="src")
    return pagerank_partition_centric(
        scsr, ctx, damping=damping, max_iterations=max_iterations,
        tol=tol, precision=precision, x0=x0,
        **_resume_kw(checkpoint_every, job, store, report, retry))


def katz_mesh(graph: DeviceGraph, ctx: MeshContext, alpha: float = 0.2,
              beta: float = 1.0, max_iterations: int = 100,
              tol: float = 1e-6, normalized: bool = False, *,
              precision: str = "f32", x0=None,
              checkpoint_every: int | None = None, job: str | None = None,
              store=None, report=None, retry=None):
    """Sharded Katz centrality; same contract as ops.katz.katz_centrality.
    ``x0`` warm-starts from a previous solution (ops/delta.py)."""
    from .distributed import katz_partition_centric
    scsr = _shard_traced(graph, ctx, by="src")
    return katz_partition_centric(
        scsr, ctx, alpha=alpha, beta=beta,
        max_iterations=max_iterations, tol=tol, normalized=normalized,
        precision=precision, x0=x0,
        **_resume_kw(checkpoint_every, job, store, report, retry))


def label_propagation_mesh(graph: DeviceGraph, ctx: MeshContext,
                           max_iterations: int = 30,
                           self_weight: float = 0.0,
                           directed: bool = False, *,
                           labels0=None,
                           checkpoint_every: int | None = None,
                           job: str | None = None, store=None,
                           report=None, retry=None):
    """Sharded label propagation; same contract as
    ops.labelprop.label_propagation. ``labels0`` warm-starts the
    election (adds-only deltas only — ops/delta.py monotone gate)."""
    from .distributed import labelprop_partition_centric
    scsr = _shard_traced(graph, ctx, by="dst", doubled=not directed)
    labels, iters = labelprop_partition_centric(
        scsr, ctx, max_iterations=max_iterations,
        self_weight=self_weight, labels0=labels0,
        **_resume_kw(checkpoint_every, job, store, report, retry))
    return labels, iters


def components_mesh(graph: DeviceGraph, ctx: MeshContext,
                    max_iterations: int = 200, *,
                    comp0=None,
                    checkpoint_every: int | None = None,
                    job: str | None = None, store=None, report=None,
                    retry=None):
    """Sharded WCC; same contract as
    ops.components.weakly_connected_components. ``comp0`` warm-starts
    the min-label propagation (adds-only deltas only — ops/delta.py
    monotone gate)."""
    from .distributed import wcc_partition_centric
    scsr = _shard_traced(graph, ctx, by="src")
    return wcc_partition_centric(
        scsr, ctx, max_iterations=max_iterations, comp0=comp0,
        **_resume_kw(checkpoint_every, job, store, report, retry))


def sssp_mesh(graph: DeviceGraph, ctx: MeshContext, source: int,
              max_iterations: int = 10_000):
    """Sharded Bellman-Ford over the context's mesh (weighted,
    directed); same result contract as ops.traversal.sssp's weighted
    directed mode. Rides the edge-partition ShardedGraph layout."""
    from .distributed import shard_graph, sssp_sharded
    sg = shard_graph(graph, ctx.mesh, axis=ctx.axis)
    dist, iters = sssp_sharded(sg, source, max_iterations=max_iterations)
    return np.asarray(dist), iters


def bfs_mesh(graph: DeviceGraph, ctx: MeshContext, source: int,
             max_iterations: int = 10_000, *, precision: str = "f32",
             checkpoint_every: int | None = None, job: str | None = None,
             store=None, report=None, retry=None):
    """BFS levels over the mesh via the GENERIC semiring kernel — the
    ~40-line new-algorithm story: a (min_plus, x0, relax-epilogue)
    triple riding semiring_partition_centric (one pmin per level,
    checkpoint-resumable).  Returns (levels[:n_nodes] int32 with -1 for
    unreachable, iterations); same result contract as
    ops.traversal.bfs_levels (directed)."""
    import jax.numpy as jnp
    from .distributed import (_minplus_relax_epilogue,
                              semiring_partition_centric)
    scsr = _shard_traced(graph, ctx, by="src")
    inf = np.float32(3.4e38)
    # unit hop weights; padding edges (dst = sink row n_nodes) stay inert
    unit_w = jnp.where(scsr.dst == scsr.n_nodes, inf,
                       jnp.float32(1.0)).astype(jnp.float32)
    hop_scsr = scsr.__class__(
        src=scsr.src, dst=scsr.dst, weights=unit_w,
        block_ptr=scsr.block_ptr, n_nodes=scsr.n_nodes,
        n_edges=scsr.n_edges, n_shards=scsr.n_shards, block=scsr.block,
        n_pad2=scsr.n_pad2, per=scsr.per, by=scsr.by)
    x0 = np.full(scsr.n_pad2, inf, dtype=np.float32)
    x0[source] = 0.0
    dist, _, iters = semiring_partition_centric(
        hop_scsr, ctx, "min_plus", x0, _minplus_relax_epilogue,
        max_iterations=max_iterations, metric="changed",
        precision=precision, algo="bfs",
        **_resume_kw(checkpoint_every, job, store, report, retry))
    dist = np.asarray(dist)
    levels = np.where(dist >= inf / 2, -1, dist.astype(np.int64))
    return levels.astype(np.int32), iters
