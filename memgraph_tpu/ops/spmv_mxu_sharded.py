"""Multi-chip MXU PageRank: the Benes/MXU kernel sharded over the edge
axis of a device mesh.

Decomposition (1D edge partition, scaling-book style):
  - every shard holds ~E/P edges (round-robin assignment, which splits
    each node's edge bundle evenly across shards and so divides the
    per-src-row gather heights — R_G and the Benes net shrink ~P-fold);
  - node LABELINGS (out/in) are global and shared, so every shard's
    extract phase produces a partial accumulator in the SAME in-label
    dense layout (n_drows_p x 128);
  - one `psum` over the 'edges' mesh axis combines the partial
    accumulators — the only per-iteration communication, O(N) floats
    riding ICI;
  - the node-relabel Benes, dangling correction, and damping update run
    replicated on every device (O(N) work, no comms).

Per-iteration cost model: t_iter(P) = t_edge(E/P) + t_allreduce(N) +
t_node(N); not measured on the current machine.

Reference analog: the reference scales pagerank via cuGraph/NCCL
(mage/cpp/cugraph_module/algorithms/pagerank.cu); this is the
TPU-native equivalent — XLA collectives over a jax.sharding.Mesh, not
message passing.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Optional

import numpy as np

from .spmv_mxu import (
    LANES, SG_ROWS, R_C, K_C,
    _benes_apply_rolls, _ceil_to, _edge_perm_masks, _gather_layout,
    _global_labelings, _node_relabel_masks, _scatter_layout,
    _unpack_mask_words,
)


@dataclass
class ShardedMXUPlan:
    n_nodes: int
    n_shards: int
    G: int
    R_G: int                   # uniform across shards (max)
    net_log2: int              # shared net size (max over shards)
    C: int                     # uniform extract chunks (max, padded)
    W: int
    n_drows_p: int
    # --- per-shard, stacked on axis 0 ---
    rowid: np.ndarray          # (P, G, R_G) int16
    mult: np.ndarray           # (P, G, R_G, LANES) f32
    masks_packed: np.ndarray   # (P, stages, N/8) uint8
    run_k: np.ndarray          # (P, C, R_C) int16
    win_oh: np.ndarray         # (P, C, W) f32
    # --- global (replicated) ---
    out_relabel: np.ndarray
    in_relabel: np.ndarray
    valid_out: np.ndarray
    dangling_out: np.ndarray
    node_net_log2: int
    node_masks_packed: np.ndarray


def _assign_shards(src, dst, n_nodes, n_shards):
    """Edge -> shard assignment. MXU-plan padding is governed by each
    128-node row's MAX per-shard degree, so balance matters more than
    randomness: the native balanced bipartite edge coloring (Euler
    splits) gives every node floor(d/P)..ceil(d/P) edges per shard on
    BOTH endpoints; the numpy fallback balances the src side only
    (round-robin within each node's edge bundle)."""
    levels = int(np.log2(n_shards))
    if (1 << levels) == n_shards and levels > 0:
        from .native import balanced_edge_color_native
        try:
            shard = balanced_edge_color_native(src, dst, n_nodes, n_nodes,
                                               levels)
        except Exception:  # noqa: BLE001 — fall back on any native issue
            import logging
            logging.getLogger(__name__).debug(
                "native edge coloring failed; numpy round-robin "
                "fallback", exc_info=True)
            shard = None
        if shard is not None:
            return shard.astype(np.int64)
    # fallback: seq-within-src-bucket round robin
    order = np.argsort(src, kind="stable")
    seq = np.arange(len(src)) - np.concatenate(
        ([0], np.cumsum(np.bincount(src, minlength=n_nodes))))[src[order]]
    shard = np.empty(len(src), dtype=np.int64)
    shard[order] = seq % n_shards
    return shard


def build_sharded_plan(src: np.ndarray, dst: np.ndarray,
                       weights: Optional[np.ndarray], n_nodes: int,
                       n_shards: int) -> ShardedMXUPlan:
    """Per-shard gather/scatter layouts + Benes nets under SHARED global
    node labelings, padded uniform so they stack on a leading shard axis."""
    src = np.asarray(src, dtype=np.int64)
    dst = np.asarray(dst, dtype=np.int64)
    E = len(src)
    w = (np.ones(E, dtype=np.float64) if weights is None
         else np.asarray(weights, dtype=np.float64))

    (G, relab_out, relab_in, inv_wsum, valid_out, dangling_out,
     n_drows_p, _wsum) = _global_labelings(src, dst, w, n_nodes)

    shard_of = _assign_shards(src, dst, n_nodes, n_shards)
    subs = [(src[shard_of == p], dst[shard_of == p], w[shard_of == p])
            for p in range(n_shards)]

    # first pass: per-shard required R_G (gather rows), to fix a uniform
    # R_G before computing positions (positions depend on R_G)
    req_R_G = []
    for s_src, _, _ in subs:
        u = relab_out[s_src]
        deg_l = np.bincount(u, minlength=G * SG_ROWS * LANES)
        H = deg_l.reshape(-1, LANES).max(axis=1)
        req_R_G.append(max(1, int(H.reshape(G, SG_ROWS).sum(axis=1).max())))
    R_G = max(req_R_G)

    gathers = [_gather_layout(s_src, s_w, relab_out, inv_wsum, G,
                              force_R_G=R_G)
               for s_src, _, s_w in subs]
    scatters = [_scatter_layout(s_dst, relab_in, n_drows_p)
                for _, s_dst, _ in subs]

    C = max(sc[0] for sc in scatters)
    W = n_drows_p // K_C
    net = max(G * R_G * LANES,
              max(sc[4] for sc in scatters) * LANES, 2)
    net_log2 = int(np.ceil(np.log2(net)))

    rowid = np.stack([g[1] for g in gathers])
    mult = np.stack([g[2] for g in gathers])
    masks = np.stack([
        _edge_perm_masks(g[3], sc[3], net_log2)
        for g, sc in zip(gathers, scatters)])
    # pad extract chunks to uniform C: padding rows are run_k == -1
    # (never extracted) and all-zero win_oh rows (no window contribution)
    run_k = np.full((n_shards, C, R_C), -1, dtype=np.int16)
    win_oh = np.zeros((n_shards, C, W), dtype=np.float32)
    for p, sc in enumerate(scatters):
        run_k[p, :sc[0]] = sc[1]
        win_oh[p, :sc[0]] = sc[2]

    node_flat = G * SG_ROWS * LANES
    node_net_log2, node_masks_packed = _node_relabel_masks(
        relab_out, relab_in, node_flat, n_drows_p)

    return ShardedMXUPlan(
        n_nodes=n_nodes, n_shards=n_shards, G=G, R_G=R_G,
        net_log2=net_log2, C=C, W=W, n_drows_p=n_drows_p,
        rowid=rowid, mult=mult, masks_packed=masks,
        run_k=run_k, win_oh=win_oh,
        out_relabel=relab_out, in_relabel=relab_in,
        valid_out=valid_out, dangling_out=dangling_out,
        node_net_log2=node_net_log2, node_masks_packed=node_masks_packed)


def make_sharded_pagerank_kernel(plan: ShardedMXUPlan, mesh,
                                 axis_name: str = "edges",
                                 route_dtype=None):
    """Returns jitted fn(rank0_flat, damping, max_iter, tol) ->
    (rank_flat, err, iters), with the edge phase sharded over
    `axis_name` of `mesh` and one psum per iteration.

    rank vectors are replicated, flat in OUT labeling.

    `mesh` may be a jax Mesh (with `axis_name` naming the edge axis) or
    a parallel.mesh.MeshContext (its axis wins)."""
    from ..parallel.mesh import MeshContext
    if isinstance(mesh, MeshContext):
        axis_name = mesh.axis
        mesh = mesh.mesh
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from ..parallel.mesh import shard_map_fn
    shard_map = shard_map_fn()
    from .blob import pack_blob, unblob
    from ..utils.jax_cache import ensure_compile_cache
    ensure_compile_cache()

    if route_dtype is None:
        route_dtype = jnp.bfloat16
    if plan.n_shards != int(mesh.shape[axis_name]):
        raise ValueError(
            f"plan built for {plan.n_shards} shards but mesh axis "
            f"'{axis_name}' has {mesh.shape[axis_name]} devices")

    G, R_G, C, W = plan.G, plan.R_G, plan.C, plan.W
    Pn = plan.n_shards
    N_net = 1 << plan.net_log2
    N_nn = 1 << plan.node_net_log2
    node_flat = G * SG_ROWS * LANES
    n_f = float(plan.n_nodes)

    # per-shard payload: identical segment layout for every shard, so one
    # pack per shard stacks into a (P, words) blob sharded on axis 0
    shard_blobs = []
    segs = None
    for p in range(Pn):
        b, segs = pack_blob({
            "masks": ("bits", plan.masks_packed[p]),
            "mult": plan.mult[p],
            "rowid_i32": plan.rowid[p].astype(np.int32),
            "run_k_i32": plan.run_k[p].astype(np.int32),
            "win_oh": plan.win_oh[p],
        })
        shard_blobs.append(b)
    blob_np = np.stack(shard_blobs)
    gblob_np, gsegs = pack_blob({
        "node_masks": ("bits", plan.node_masks_packed),
        "valid": plan.valid_out,
        "dangling": plan.dangling_out,
    })

    live_big = [bool(plan.masks_packed[:, s].any())
                for s in range(plan.masks_packed.shape[1])]
    live_node = [bool(row.any()) for row in plan.node_masks_packed]

    def edge_phase(rank_flat, dv):
        rank_planes = rank_flat.reshape(G, SG_ROWS, LANES)
        T = jnp.einsum("grw,gwl->grl", dv["oh"], rank_planes,
                       preferred_element_type=jnp.float32)
        contrib = (T * dv["mult"]).astype(route_dtype).reshape(-1, LANES)
        x2 = jnp.zeros((N_net // LANES, LANES), route_dtype
                       ).at[:contrib.shape[0]].set(contrib)
        x2 = _benes_apply_rolls(x2, dv["masks2"], plan.net_log2,
                                live_stages=live_big)
        xc = x2[:C * R_C].reshape(C, R_C, LANES)
        per_chunk = jnp.einsum("cik,cil->ckl", dv["ohe"], xc,
                               preferred_element_type=jnp.float32)
        accw = jnp.einsum("cw,ckl->wkl", dv["win_oh"], per_chunk,
                          preferred_element_type=jnp.float32)
        return accw.reshape(-1, LANES)            # (n_drows_p, 128)

    def node_phase(acc_in2, rank_flat, gdv, d):
        from .semiring import pagerank_update
        xa = jnp.zeros((N_nn // LANES, LANES), jnp.float32
                       ).at[:acc_in2.shape[0]].set(acc_in2)
        acc_out = _benes_apply_rolls(
            xa, gdv["node_masks2"], plan.node_net_log2,
            live_stages=live_node).reshape(-1)[:node_flat]
        dm = jnp.sum(rank_flat * gdv["dangling"])
        # shared damping-update formula (ops/semiring.py): the sharded
        # MXU kernel applies the SAME epilogue as every other backend
        return pagerank_update(acc_out, dm, gdv["valid"], n_f, d)

    def shard_fn(blob_row, gblob, rank0, damping, tol, max_iterations):
        blob = blob_row[0]
        iota_sg = jnp.arange(SG_ROWS, dtype=jnp.int32)
        iota_kc = jnp.arange(K_C, dtype=jnp.int32)
        rowid = unblob(blob, segs, "rowid_i32")
        run_k = unblob(blob, segs, "run_k_i32")
        mwords = unblob(blob, segs, "masks")
        dv = dict(
            oh=(rowid[:, :, None] == iota_sg[None, None, :]
                ).astype(jnp.float32),
            ohe=((run_k[:, :, None] == iota_kc[None, None, :])
                 & (run_k[:, :, None] >= 0)).astype(route_dtype),
            mult=unblob(blob, segs, "mult"),
            win_oh=unblob(blob, segs, "win_oh"),
            masks2=_unpack_mask_words(mwords, plan.net_log2),
        )
        gdv = dict(
            node_masks2=_unpack_mask_words(
                unblob(gblob, gsegs, "node_masks"), plan.node_net_log2),
            valid=unblob(gblob, gsegs, "valid"),
            dangling=unblob(gblob, gsegs, "dangling"),
        )

        def body(carry):
            rank, _, it = carry
            acc_in2 = edge_phase(rank, dv)
            acc_in2 = jax.lax.psum(acc_in2, axis_name)
            new_rank = node_phase(acc_in2, rank, gdv, damping)
            err = jnp.sum(jnp.abs(new_rank - rank))
            return new_rank, err, it + 1

        def cond(carry):
            _, err, it = carry
            return (err > tol) & (it < max_iterations)

        return jax.lax.while_loop(
            cond, body, (rank0, jnp.float32(jnp.inf), jnp.int32(0)))

    Pr = P()
    sharded = shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(axis_name, None), Pr, Pr, Pr, Pr, Pr),
        out_specs=(Pr, Pr, Pr))
    jitted = jax.jit(sharded, static_argnums=(5,))

    blob_dev = jax.device_put(blob_np, NamedSharding(mesh, P(axis_name,
                                                             None)))
    gblob_dev = jax.device_put(gblob_np, NamedSharding(mesh, Pr))

    def run(rank0, damping, max_iterations, tol):
        return jitted(blob_dev, gblob_dev, rank0,
                      jnp.float32(damping), jnp.float32(tol),
                      int(max_iterations))

    return run


def pagerank_mxu_sharded(src, dst, weights, n_nodes, mesh,
                         axis_name: str = "edges", damping=0.85,
                         max_iterations=100, tol=1e-6,
                         plan: ShardedMXUPlan = None, route_dtype=None):
    """End-to-end sharded MXU pagerank over `mesh` (a jax Mesh or a
    MeshContext). Returns ranks in ORIGINAL node ids plus (err, iters)."""
    import jax.numpy as jnp
    from ..parallel.mesh import MeshContext
    if isinstance(mesh, MeshContext):
        axis_name = mesh.axis
        mesh = mesh.mesh
    n_shards = int(mesh.shape[axis_name])
    if plan is None:
        plan = build_sharded_plan(src, dst, weights, n_nodes, n_shards)
    # the compiled kernel caches on the plan: rebuilding it per CALL
    # retraced + recompiled the whole sharded program every invocation
    # (mglint MG008 recompile-hazard)
    cache = getattr(plan, "_kernel_cache", None)
    if cache is None:
        cache = {}
        object.__setattr__(plan, "_kernel_cache", cache)
    key = (axis_name, tuple(d.id for d in mesh.devices.flat),
           None if route_dtype is None else str(route_dtype))
    run = cache.get(key)
    if run is None:
        run = cache[key] = make_sharded_pagerank_kernel(
            plan, mesh, axis_name, route_dtype=route_dtype)
    node_flat = plan.G * SG_ROWS * LANES
    rank0 = np.zeros(node_flat, dtype=np.float32)
    rank0[plan.out_relabel] = 1.0 / plan.n_nodes
    rank, err, iters = run(jnp.asarray(rank0), damping, max_iterations,
                           tol)
    rank = np.asarray(rank)
    return rank[plan.out_relabel], float(err), int(iters)
