"""Whole-graph traversal kernels on the semiring core: BFS levels,
single-source shortest paths.

Device-side counterparts of the traversal algorithms the reference embeds
in its ExpandVariable operator (BFS/weighted shortest path,
/root/reference/src/query/plan/operator.hpp:1140) for the *analytics*
regime: when the query wants distances/paths from a source over the whole
graph, a min-plus semiring fixpoint (Bellman-Ford: gather + ⊕=min until
fixpoint) beats pull-based expansion by orders of magnitude on TPU.

Directed BFS additionally rides the core's direction-optimizing
push/pull selection (semiring.select_pull, the Beamer/GraphBLAST
heuristic): a sparse frontier relaxes push-style (frontier-masked
contributions), a dense one pulls over every edge — both exact, chosen
per level from the frontier's out-edge mass. Undirected BFS (Graph500
kernel 2) is a program of its own, jit_fixpoint_bfs_undirected: int32
levels, a hop is +1 and no weight is read, both orientations of every
edge an iteration. Every entry point returns host arrays and records
the ``analytics.launch`` / ``analytics.device_wait`` phases.

The point-query regime (short anchored expansions) stays on the host
executor, which walks adjacency directly — same split the reference makes
between operator-embedded traversals and MAGE whole-graph algorithms.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..observability import trace as mgtrace
from . import semiring as S
from .csr import DeviceGraph

INF = jnp.float32(3.4e38)
_UNREACHED = 1.7e38     # INF / 2 as a host number, for arrays read back
#: the int32 level of a vertex the undirected sweep has not reached; a
#: hop added to it stays far below int32's top
_NO_LEVEL = 1 << 30


def _read_back(x, iters, n_nodes: int):
    """The padded iterate and the iteration count in one transfer, cut
    to ``n_nodes`` on the host: a device slice would be a program of its
    own for every vertex count."""
    with mgtrace.span("analytics.device_wait", backend="segment"):
        x_h, iters_h = jax.device_get((x, iters))  # mglint: disable=MG009 — the one fused result transfer of the call
    return np.asarray(x_h)[:n_nodes], int(iters_h)


def _sssp_step_directed(dist, A, env, P, n_out):
    """min-plus relaxation: cand[v] = min over edges (u,v) of d[u]+w."""
    cand = S.spmv("min_plus", dist, A["src"], A["dst"], A["w"],
                  n_out=n_out)
    return jnp.minimum(dist, cand)


def _sssp_step_undirected(dist, A, env, P, n_out):
    """Directed pass then the reverse orientation over the UPDATED
    distances (Gauss-Seidel flavor: halves the round count)."""
    new = _sssp_step_directed(dist, A, env, P, n_out)
    cand_b = S.spmv("min_plus", new, A["dst"], A["src"], A["w"],
                    n_out=n_out)
    return jnp.minimum(new, cand_b)


def _sssp_epilogue(dist, new, env, P):
    return new, jnp.any(new < dist)


def sssp(graph: DeviceGraph, source: int, weighted: bool = True,
         directed: bool = True, max_iterations: int = 10_000):
    """Bellman-Ford SSSP as a min-plus fixpoint. Returns
    (dist[:n_nodes] float32 on the host, iterations); unreachable nodes
    get +inf. With weighted=False computes hop counts (= BFS levels).
    Undirected, an iteration relaxes both orientations of every edge."""
    w = graph.weights if weighted else jnp.where(
        jnp.arange(graph.e_pad) < graph.n_edges, 1.0, INF).astype(jnp.float32)
    if weighted:
        # padding edges have weight 0 into the sink row — force them inert
        w = jnp.where(jnp.arange(graph.e_pad) < graph.n_edges, w, INF)
    dist0 = np.full((graph.n_pad,), float(INF), dtype=np.float32)
    dist0[source] = 0.0
    with mgtrace.span("analytics.launch"):
        dist, _, iters = S.fixpoint(
            "min_plus",
            arrays={"src": graph.src_idx, "dst": graph.col_idx, "w": w},
            x0=jnp.asarray(dist0), n_out=graph.n_pad,
            step=(_sssp_step_directed if directed
                  else _sssp_step_undirected),
            epilogue=_sssp_epilogue, max_iterations=max_iterations,
            metric="changed")
    out, iters = _read_back(dist, iters, graph.n_nodes)
    return np.where(out >= _UNREACHED, np.inf, out), iters


def _bfs_step(x, A, env, P, n_out):
    """Direction-optimizing BFS relaxation: push (frontier-masked
    contributions) while the frontier's out-edge mass is below
    n_edges / alpha, pull (all edges) once it saturates.  Both sides
    are exact for the monotone level recurrence; the selector only
    changes the executed formulation."""
    dist, frontier = x
    pull = S.select_pull(frontier, A["deg"], P["n_edges"])
    new = jax.lax.cond(
        pull,
        lambda d: S.spmv("min_plus", d, A["src"], A["dst"], A["w"],
                         n_out=n_out),
        lambda d: S.spmv("min_plus", d, A["src"], A["dst"], A["w"],
                         n_out=n_out, frontier=frontier),
        dist)
    return jnp.minimum(dist, new)


def _bfs_epilogue(x, new, env, P):
    dist, _frontier = x
    new_frontier = new < dist
    return (new, new_frontier), jnp.any(new_frontier)


def do_bfs(graph: DeviceGraph, source: int, max_iterations: int = 10_000):
    """Direction-optimizing BFS (directed): returns (dist f32 hops with
    +inf for unreachable, on the host; iterations).  Level-exact vs the
    plain min-plus fixpoint — only the push/pull execution strategy
    differs."""
    w = jnp.where(jnp.arange(graph.e_pad) < graph.n_edges, 1.0,
                  INF).astype(jnp.float32)
    dist0 = np.full((graph.n_pad,), float(INF), dtype=np.float32)
    dist0[source] = 0.0
    frontier0 = np.zeros(graph.n_pad, dtype=bool)
    frontier0[source] = True
    with mgtrace.span("analytics.launch"):
        (dist, _), _, iters = S.fixpoint(
            "min_plus",
            arrays={"src": graph.src_idx, "dst": graph.col_idx, "w": w,
                    "deg": graph.out_degree},
            params={"n_edges": np.float32(graph.n_edges)},
            x0=(jnp.asarray(dist0), jnp.asarray(frontier0)),
            n_out=graph.n_pad, step=_bfs_step, epilogue=_bfs_epilogue,
            max_iterations=max_iterations, metric="changed")
    out, iters = _read_back(dist, iters, graph.n_nodes)
    return np.where(out >= _UNREACHED, np.inf, out), iters


def _bfs_undirected_step(level, A, env, P, n_out):
    """A hop is +1 and no weight is read: both orientations of every
    edge, the second over the levels the first just lowered (as
    _sssp_step_undirected)."""
    hop = jnp.int32(1)
    new = jnp.minimum(level, S.spmv("min_plus", level, A["src"], A["dst"],
                                    hop, n_out=n_out))
    back = S.spmv("min_plus", new, A["dst"], A["src"], hop, n_out=n_out)
    return jnp.minimum(new, back)


def _bfs_undirected_epilogue(level, new, env, P):
    return new, jnp.any(new < level)


def _bfs_undirected(graph: DeviceGraph, source: int, max_iterations: int):
    """int32 levels over both orientations of every edge: the program
    jit_fixpoint_bfs_undirected. Padding edges run sink to sink, which
    no source reaches, so they stay inert."""
    level0 = np.full((graph.n_pad,), _NO_LEVEL, dtype=np.int32)
    level0[source] = 0
    with mgtrace.span("analytics.launch"):
        level, _, iters = S.fixpoint(
            "min_plus",
            arrays={"src": graph.src_idx, "dst": graph.col_idx},
            x0=jnp.asarray(level0), n_out=graph.n_pad,
            step=_bfs_undirected_step, epilogue=_bfs_undirected_epilogue,
            max_iterations=max_iterations, metric="changed")
    level, iters = _read_back(level, iters, graph.n_nodes)
    return np.where(level >= _NO_LEVEL, -1, level).astype(np.int32), iters


def bfs_levels(graph: DeviceGraph, source: int, directed: bool = True,
               max_iterations: int = 10_000):
    """BFS levels from source (-1 for unreachable), int32 on the host.
    The directed case rides the direction-optimizing push/pull core
    path; the undirected one is a unit-hop sweep of its own."""
    if not directed:
        return _bfs_undirected(graph, source, max_iterations)
    dist, iters = do_bfs(graph, source, max_iterations=max_iterations)
    return np.where(np.isinf(dist), -1, dist).astype(np.int32), iters


@partial(jax.jit, static_argnames=("n_pad", "max_iterations"))
def _mssp_kernel(src, dst, w, sources, n_pad: int, max_iterations: int):
    """Multi-source SSSP: one distance row per source, vmapped min-plus
    relaxation."""
    def single(source):
        dist0 = jnp.full((n_pad,), INF, dtype=jnp.float32).at[source].set(0.0)

        def body(carry):
            dist, _, it = carry
            cand = S.spmv("min_plus", dist, src, dst, w, n_out=n_pad)
            new = jnp.minimum(dist, cand)
            return new, jnp.any(new < dist), it + 1

        def cond(carry):
            _, changed, it = carry
            return changed & (it < max_iterations)

        dist, _, _ = jax.lax.while_loop(
            cond, body, (dist0, jnp.bool_(True), jnp.int32(0)))
        return dist

    return jax.vmap(single)(sources)


def multi_source_sssp(graph: DeviceGraph, sources, weighted: bool = True,
                      directed: bool = True, max_iterations: int = 10_000):
    """Distances from each of B sources: (B, n_nodes), on the host.
    Feeds betweenness sampling and graph-context retrieval (GraphRAG
    expansions)."""
    w = graph.weights if weighted else jnp.ones_like(graph.weights)
    w = jnp.where(jnp.arange(graph.e_pad) < graph.n_edges, w, INF)
    src, dst = graph.src_idx, graph.col_idx
    if not directed:
        src = jnp.concatenate([graph.src_idx, graph.col_idx])
        dst = jnp.concatenate([graph.col_idx, graph.src_idx])
        w = jnp.concatenate([w, w])
    dist = _mssp_kernel(src, dst, w,
                        jnp.asarray(sources, dtype=jnp.int32),
                        graph.n_pad, max_iterations)
    # the padded rows are read back whole and cut on the host: a device
    # slice to n_nodes is a new program for every vertex count, compiled
    # inside the request that follows an inserted vertex
    out = np.asarray(dist)[:, :graph.n_nodes]
    return np.where(out >= _UNREACHED, np.inf, out)


def khop_neighborhood(graph: DeviceGraph, sources, k: int,
                      directed: bool = False):
    """Boolean mask (n_nodes,), on the host, of nodes within k hops of
    any source — the GraphRAG '2-hop expand' step on the device.

    Each Bellman-Ford round extends reach by ≥1 hop, so k rounds settle
    every node within k hops."""
    levels = multi_source_sssp(graph, sources, weighted=False,
                               directed=directed, max_iterations=k + 1)
    return np.any(levels <= float(k), axis=0)
