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
  2. lexicographic sort of (dst_e, lab_e) pairs via `lax.sort` (num_keys=2):
     runs of equal (dst, label) lie together, each dst's runs in
     ascending label order, and the sorted dst column is the same in
     every round
  3. elect. A view without a weight property (every edge weighs 1: the
     Graphalytics CDLP that ``label_propagation.get`` asks for) counts
     votes: a run's length is its count (``run_starts``), each dst's
     largest count a running max restarted per dst (``segment_cummax``),
     and the first run to reach it holds the smallest such label;
     cumulative passes over the sorted order, no scatter. A weighted
     view sums each run's weights with a sum edge_reduce, then max-weight
     and min-label edge_reduce passes elect, since an exact float sum
     over a run wants a reduction, not a difference of prefix sums.
The fused epilogue is the own-label-wins rule + the changed-any
convergence partial.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..observability import trace as mgtrace
from ..observability.metrics import global_metrics
from . import semiring as S
from .csr import DeviceGraph


def _sorted_votes(labels, A, *riders):
    """Steps 1-2: every edge's neighbour label, sorted by (dst, label)
    with ``riders`` carried along. Without riders every operand is a
    key, so equal elements are identical and the sort need not be
    stable (a stable one carries an index along)."""
    lab_e = labels[A["src"]]
    return jax.lax.sort((A["dst"], lab_e) + riders, num_keys=2,
                        is_stable=bool(riders))


def _own_label_rule(labels, has_nb, best_w, best_lab, P):
    """Keep the own label when it is at least as heavy (self_weight),
    ties to the smaller label, or when there is no neighbour."""
    self_weight = P["self_weight"]
    own_wins = (~has_nb) | (self_weight >= best_w) | \
               (jnp.isclose(self_weight, best_w) & (labels <= best_lab))
    return jnp.where(own_wins, labels, best_lab)


def _labelprop_step(labels, A, env, P, n_out):
    """One election round by weights; returns the proposed labels (the
    `acc`)."""
    e2 = A["src"].shape[0]
    big_w = jnp.float32(0.0)
    # lexicographic sort by (dst, neighbor-label)
    d_s, l_s, w_s = _sorted_votes(labels, A, A["w"])
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
    return _own_label_rule(labels, best_lab < n_out, best_w, best_lab, P)


def _count_setup(A, P, n_out):
    """Loop invariants of the election by counts, from the sorted dst
    column, which every round's sort reproduces: each position's dst
    segment start, and each vertex's last sorted position."""
    dst = A["dst"]
    d_sorted = jax.lax.sort(dst, is_stable=False)
    seg_start = S.run_starts(jnp.concatenate([
        jnp.ones((1,), dtype=jnp.bool_), d_sorted[1:] != d_sorted[:-1]]))
    degree = S.edge_reduce("sum", jnp.ones_like(dst), dst, n_out)
    return {"seg_start": seg_start, "has_nb": degree > 0,
            "last": jnp.maximum(jnp.cumsum(degree) - 1, 0)}


def _count_step(labels, A, env, P, n_out):
    """One election round by counts (every edge weighs 1); returns the
    proposed labels. Cumulative passes over the sorted order, no
    scatter: only ``labels[src]`` gathers across the edges."""
    seg_start = env["seg_start"]
    idx = jnp.arange(seg_start.shape[0], dtype=jnp.int32)
    _, l_s = _sorted_votes(labels, A)
    seg_first = seg_start == idx
    run_first = seg_first | (l_s != jnp.roll(l_s, 1))
    run_last = jnp.roll(run_first, -1)
    # a (dst, label) run's count, read at its last position
    count = idx - S.run_starts(run_first) + 1
    best = S.segment_cummax(jnp.where(run_last, count, 0), seg_start)
    # a run that outvotes every earlier run of its dst, whose labels are
    # smaller: the dst's last such run is the first to reach its
    # largest count, so it holds the smallest label among those
    beaten = jnp.where(seg_first, 0, jnp.roll(best, 1))
    winner = jax.lax.cummax(
        jnp.where(run_last & (count > beaten), idx, 0))
    last = env["last"]
    return _own_label_rule(labels, env["has_nb"],
                           best[last].astype(jnp.float32),
                           l_s[winner[last]], P)


def _labelprop_epilogue(labels, proposed, env, P):
    return proposed, jnp.any(proposed != labels)


def label_propagation(graph: DeviceGraph, max_iterations: int = 30,
                      self_weight: float = 0.0, directed: bool = False,
                      mesh=None, labels0=None, weighted: bool = True):
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

    `weighted=False` says the view has no weight property, so every
    edge weighs 1 and the election counts votes (`_count_step`, no
    scatter; `analytics.labelprop.count_election_total` moves by one);
    the weights are not read. A weighted view sums them (`_labelprop_step`).
    """
    backend, ctx = S.route_backend(graph, mesh, semiring="max_min")
    if backend == "mesh":
        from ..parallel.analytics import label_propagation_mesh
        with S.backend_extent("mesh"):
            return label_propagation_mesh(
                graph, ctx, max_iterations=max_iterations,
                self_weight=self_weight, directed=directed,
                labels0=labels0)
    fwd = {"src": graph.src_idx, "dst": graph.col_idx}
    if weighted:
        fwd["w"] = graph.weights
        setup, step = None, _labelprop_step
    else:
        setup, step = _count_setup, _count_step
        global_metrics.increment("analytics.labelprop.count_election_total")
    if directed:
        arrays = fwd
    else:
        bwd = dict(fwd, src=graph.col_idx, dst=graph.src_idx)
        arrays = {k: jnp.concatenate([fwd[k], bwd[k]]) for k in fwd}
    start = np.arange(graph.n_pad, dtype=np.int32)
    if labels0 is not None:
        arr = np.asarray(labels0, dtype=np.int32)[:graph.n_nodes]
        start[:len(arr)] = arr
    with mgtrace.span("analytics.launch"):
        labels, _, iters = S.fixpoint(
            "max_min", arrays=arrays,
            params={"self_weight": np.float32(self_weight)},
            x0=jnp.asarray(start), n_out=graph.n_pad, setup=setup,
            step=step, epilogue=_labelprop_epilogue,
            max_iterations=max_iterations, metric="changed")
    # one fused host transfer for the whole result tuple (MG009), cut to
    # n_nodes on the host: a device slice is a program per vertex count
    with mgtrace.span("analytics.device_wait", backend="segment"):
        labels_h, iters_h = jax.device_get((labels, iters))  # mglint: disable=MG009 — results must ship host; this IS the single fused transfer for the whole tuple
    return np.asarray(labels_h)[:graph.n_nodes], int(iters_h)
