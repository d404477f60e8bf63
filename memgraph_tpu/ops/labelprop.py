"""Community detection by synchronous label propagation on the semiring
core.

Counterpart of the reference's community-detection modules
(/root/reference/query_modules/community_detection_module/ — online
label-propagation / LabelRankT — and mage/cpp/community_detection_module/
Louvain): each round every node adopts the label carrying the largest total
incident edge weight among its neighbors (both directions), with
deterministic min-label tie-breaking and a self-weight term for stability.

TPU formulation (no hash tables, static shapes): the election is a custom
semiring-core step — per round,
  1. gather neighbor labels onto edges:     lab_e = label[src_e]
  2. lexicographic sort of (dst_e, lab_e) pairs via `lax.sort` (num_keys=2)
  3. run-length-reduce equal (dst, lab) runs with a sum edge_reduce
  4. max-weight then min-label edge_reduce passes elect each dst's label
Everything is sorts + core ⊕-reductions — the shapes XLA tiles well; the
fused epilogue is the own-label-wins rule + the changed-any convergence
partial.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..observability import trace as mgtrace
from . import semiring as S
from .csr import DeviceGraph


def _labelprop_step(labels, A, env, P, n_out):
    """One election round; returns the proposed labels (the `acc`)."""
    src2, dst2, w2 = A["src"], A["dst"], A["w"]
    e2 = src2.shape[0]
    big_w = jnp.float32(0.0)
    lab_e = labels[src2]
    # lexicographic sort by (dst, neighbor-label)
    d_s, l_s, w_s = jax.lax.sort((dst2, lab_e, w2), num_keys=2)
    first = jnp.concatenate([
        jnp.ones((1,), dtype=jnp.bool_),
        (d_s[1:] != d_s[:-1]) | (l_s[1:] != l_s[:-1])])
    run_id = jnp.cumsum(first.astype(jnp.int32)) - 1  # dense run ids < e2
    run_w = S.edge_reduce("sum", w_s, run_id, e2)
    # representative dst/label of each run (value at its first element)
    idx = jnp.arange(e2, dtype=jnp.int32)
    first_idx = S.edge_reduce("min", jnp.where(first, idx, e2), run_id, e2)
    first_idx = jnp.minimum(first_idx, e2 - 1)
    run_dst = d_s[first_idx]
    run_lab = l_s[first_idx]
    valid_run = idx <= run_id[-1]
    run_w = jnp.where(valid_run, run_w, big_w)
    # add self-weight as an implicit run for the node's own label: handled
    # by comparing the best neighbor run against self_weight below.
    best_w = S.edge_reduce("max", run_w, run_dst, n_out)
    # min label among runs achieving best weight for their dst
    is_best = run_w >= best_w[run_dst] - 1e-12
    cand_lab = jnp.where(valid_run & is_best, run_lab, jnp.int32(n_out))
    best_lab = S.edge_reduce("min", cand_lab, run_dst, n_out)
    has_nb = best_lab < n_out
    self_weight = P["self_weight"]
    # keep own label when it's at least as heavy (self_weight) or no nbrs
    own_wins = (~has_nb) | (self_weight >= best_w) | \
               (jnp.isclose(self_weight, best_w) & (labels <= best_lab))
    return jnp.where(own_wins, labels, best_lab)


def _labelprop_epilogue(labels, proposed, env, P):
    return proposed, jnp.any(proposed != labels)


def label_propagation(graph: DeviceGraph, max_iterations: int = 30,
                      self_weight: float = 0.0, directed: bool = False,
                      mesh=None, labels0=None):
    """Returns (community_label[:n_nodes], iterations).

    Labels are dense node indices (a community's label is one member's id).
    `mesh` (MeshContext | Mesh | int | None) routes through the
    multi-chip layer; see ops.pagerank.pagerank.

    `labels0` seeds the election from a previous labeling. The answer
    then depends on the seed: rounds from the old labels are not the
    same rounds from the ids, so an exact label propagation never seeds
    (ops/delta.py ``WARM_START_POLICY``); the approximate online variant
    that does must hold the monotone contract there (adds-only deltas;
    a removal must cold-start LOUDLY).
    """
    backend, ctx = S.route_backend(graph, mesh, semiring="max_min")
    if backend == "mesh":
        from ..parallel.analytics import label_propagation_mesh
        with S.backend_extent("mesh"):
            return label_propagation_mesh(
                graph, ctx, max_iterations=max_iterations,
                self_weight=self_weight, directed=directed,
                labels0=labels0)
    if directed:
        src2, dst2, w2 = graph.src_idx, graph.col_idx, graph.weights
    else:
        src2 = jnp.concatenate([graph.src_idx, graph.col_idx])
        dst2 = jnp.concatenate([graph.col_idx, graph.src_idx])
        w2 = jnp.concatenate([graph.weights, graph.weights])
    start = np.arange(graph.n_pad, dtype=np.int32)
    if labels0 is not None:
        arr = np.asarray(labels0, dtype=np.int32)[:graph.n_nodes]
        start[:len(arr)] = arr
    with mgtrace.span("analytics.launch"):
        labels, _, iters = S.fixpoint(
            "max_min",
            arrays={"src": src2, "dst": dst2, "w": w2},
            params={"self_weight": np.float32(self_weight)},
            x0=jnp.asarray(start), n_out=graph.n_pad,
            step=_labelprop_step, epilogue=_labelprop_epilogue,
            max_iterations=max_iterations, metric="changed")
    # one fused host transfer for the whole result tuple (MG009), cut to
    # n_nodes on the host: a device slice is a program per vertex count
    with mgtrace.span("analytics.device_wait", backend="segment"):
        labels_h, iters_h = jax.device_get((labels, iters))  # mglint: disable=MG009 — results must ship host; this IS the single fused transfer for the whole tuple
    return np.asarray(labels_h)[:graph.n_nodes], int(iters_h)
