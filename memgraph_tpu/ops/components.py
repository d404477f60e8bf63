"""Connected components on the semiring core.

Counterpart of the reference's WCC module
(/root/reference/mage/cpp/connectivity_module/ and query_modules/wcc.py):
WCC is a min-first semiring fixpoint over both edge directions (treating
the graph as undirected) with pointer-jumping (path halving) fused into
the epilogue, which converges in O(log n) rounds instead of O(diameter).
SCC is multi-pivot forward-backward coloring whose propagation rounds are
MASKED min-first matvecs (the masked-SpMV of GraphBLAST: edges with a
settled endpoint contribute the ⊕ identity).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..observability import trace as mgtrace
from . import semiring as S
from .csr import DeviceGraph


def _wcc_epilogue(comp, acc, env, P):
    """Fused WCC epilogue: keep the smaller label, then pointer-jump
    (path halving: comp[v] = comp[comp[v]]) and the changed partial."""
    new_comp = jnp.minimum(comp, acc)
    new_comp = new_comp[new_comp]
    return new_comp, jnp.any(new_comp != comp)


def weakly_connected_components(graph: DeviceGraph,
                                max_iterations: int = 200, mesh=None,
                                comp0=None):
    """Returns (component_id[:n_nodes], iterations). Component ids are the
    minimum dense node index in each component.

    `mesh` (MeshContext | Mesh | int | None) routes through the
    multi-chip layer; see ops.pagerank.pagerank.

    `comp0` warm-starts the min-label propagation from a previous
    assignment — callers must hold the ops/delta.py monotone contract
    (only valid when the delta since that assignment ADDED edges;
    min-labels can merge components but never split them)."""
    backend, ctx = S.route_backend(graph, mesh, semiring="min_first")
    if backend == "mesh":
        from ..parallel.analytics import components_mesh
        with S.backend_extent("mesh"):
            return components_mesh(graph, ctx,
                                   max_iterations=max_iterations,
                                   comp0=comp0)
    start = np.arange(graph.n_pad, dtype=np.int32)
    if comp0 is not None:
        arr = np.asarray(comp0, dtype=np.int32)[:graph.n_nodes]
        start[:len(arr)] = arr
    with mgtrace.span("analytics.launch"):
        comp, _, iters = S.fixpoint(
            "min_first",
            arrays={"src": graph.src_idx, "dst": graph.col_idx},
            x0=jnp.asarray(start), n_out=graph.n_pad,
            epilogue=_wcc_epilogue, max_iterations=max_iterations,
            metric="changed", direction="both")
    # one fused host transfer for the whole result tuple (MG009), cut to
    # n_nodes on the host: a device slice is a program per vertex count
    with mgtrace.span("analytics.device_wait", backend="segment"):
        comp_h, iters_h = jax.device_get((comp, iters))  # mglint: disable=MG009 — results must ship host; this IS the single fused transfer for the whole tuple
    return np.asarray(comp_h)[:graph.n_nodes], int(iters_h)


@partial(jax.jit, static_argnames=("n_pad", "max_iterations"))
def _scc_round(src, dst, comp, n_pad: int, max_iterations: int):
    """One multi-pivot forward-backward coloring round over the unsettled
    subgraph (comp < 0 means unsettled).

    Correctness: with labels = own index on unsettled nodes, after min-label
    propagation fwd(v) = min index that reaches v, bwd(v) = min index v
    reaches (within the unsettled subgraph). fwd(v) == bwd(v) == m implies
    m reaches v and v reaches m ⇒ v is in m's SCC; every such set settled
    this round is exactly one whole SCC. At least the SCC of the minimum
    unsettled index settles each round, so the host outer loop terminates.
    """
    ids = jnp.arange(n_pad, dtype=jnp.int32)
    unsettled = comp < 0
    big = jnp.int32(n_pad)
    lab0 = jnp.where(unsettled, ids, big)
    # propagation only along edges with both endpoints unsettled: the
    # masked min-first matvec (masked edges contribute the sentinel)
    edge_ok = unsettled[src] & unsettled[dst]

    def propagate(a, b):
        def body(carry):
            lab, _, it = carry
            cand = S.spmv("min_first", lab, a, b, n_out=n_pad,
                          mask=edge_ok, mask_fill=big)
            new = jnp.minimum(lab, cand)
            return new, jnp.any(new != lab), it + 1

        def cond(carry):
            _, changed, it = carry
            return changed & (it < max_iterations)

        lab, _, _ = jax.lax.while_loop(
            cond, body, (lab0, jnp.bool_(True), jnp.int32(0)))
        return lab

    fwd = propagate(src, dst)
    bwd = propagate(dst, src)
    settle = unsettled & (fwd == bwd) & (fwd < big)
    return jnp.where(settle, fwd, comp)


@partial(jax.jit, static_argnames=("n_pad", "max_iterations"))
def _scc_trim(src, dst, comp, n_pad: int, max_iterations: int):
    """Trim to fixpoint: unsettled nodes with no unsettled in-neighbors or
    no unsettled out-neighbors are singleton SCCs."""
    def body(carry):
        comp, _, it = carry
        unsettled = comp < 0
        edge_ok = (unsettled[src] & unsettled[dst]).astype(jnp.int32)
        in_deg = S.edge_reduce("sum", edge_ok, dst, n_pad)
        out_deg = S.edge_reduce("sum", edge_ok, src, n_pad)
        trim = unsettled & ((in_deg == 0) | (out_deg == 0))
        new_comp = jnp.where(trim, jnp.arange(n_pad, dtype=jnp.int32), comp)
        return new_comp, jnp.any(trim), it + 1

    def cond(carry):
        _, changed, it = carry
        return changed & (it < max_iterations)

    comp, _, _ = jax.lax.while_loop(
        cond, body, (comp, jnp.bool_(True), jnp.int32(0)))
    return comp


def strongly_connected_components(graph: DeviceGraph,
                                  max_iterations: int = 1 << 30):
    """SCC labels (equal label ⇔ same SCC; label = min dense index in SCC).

    Multi-pivot FW-BW coloring with trimming; the outer loop runs on the
    host, each round jitted on device. Guaranteed ≥1 SCC settles per round.
    max_iterations bounds the *inner* propagation loops; the default is
    effectively unbounded because correctness requires running each
    propagation to its fixpoint (a C-node cycle needs C rounds).
    """
    n_pad = graph.n_pad
    comp = jnp.where(jnp.arange(n_pad, dtype=jnp.int32) < graph.n_nodes,
                     jnp.int32(-1), jnp.arange(n_pad, dtype=jnp.int32))
    while True:
        comp = _scc_trim(graph.src_idx, graph.col_idx, comp, n_pad,
                         max_iterations)
        if not bool(jnp.any(comp < 0)):
            break
        before = comp
        comp = _scc_round(graph.src_idx, graph.col_idx, comp, n_pad,
                          max_iterations)
        if not bool(jnp.any(comp < 0)):
            break
        if bool(jnp.all(comp == before)):  # safety: no progress → stop
            comp = jnp.where(comp < 0, jnp.arange(n_pad, dtype=jnp.int32),
                             comp)
            break
    return np.asarray(comp[:graph.n_nodes])
