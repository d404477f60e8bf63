"""PageRank on the semiring kernel core (ops/semiring.py).

TPU-native counterpart of the reference's PageRank modules
(/root/reference/mage/cpp/pagerank_module/, CUDA analog
mage/cpp/cugraph_module/algorithms/pagerank.cu, online variant
query_modules/pagerank_module/pagerank_online_module.cpp): weighted power
iteration as a plus-times semiring fixpoint — the setup hoists the
per-edge `w / wsum[src]` multipliers, the fused epilogue applies the
damping update (semiring.pagerank_update, shared with every backend) and
the L1 convergence partial inside the matvec body. Dangling-node mass is
redistributed uniformly each round (standard PageRank semantics).

All shapes static; padding edges carry weight 0 into a sink row, so they
contribute nothing.  `precision=` selects the f32 (exact) / bf16 /
int8-streaming variants (semiring.PRECISION_BOUNDS documents the bounds).
"""

from __future__ import annotations

import threading

import jax.numpy as jnp
import numpy as np

from ..observability import trace as mgtrace
from ..observability.metrics import global_metrics
from . import semiring as S
from .csr import DeviceGraph

# back-compat alias; the routing threshold lives with the dispatch now
MXU_MIN_EDGES = S.MXU_MIN_EDGES

# serializes the expensive plan build PER GRAPH so concurrent first CALLs
# on one snapshot don't each run it (~35s host-side at 10M edges), while
# unrelated graphs build in parallel; the registry lock only guards the
# per-graph lock creation
_mxu_locks_guard = threading.Lock()


def _pagerank_setup(A, P, n_out):
    """Loop invariants: hoisted edge multipliers + dangling/valid masks.
    CSR order is src-sorted, so the out-weight sum takes the sorted
    lowering; the per-edge multiplier is gathered ONCE per run."""
    n_nodes = P["n_nodes"]
    n_f = n_nodes.astype(jnp.float32)
    valid = (jnp.arange(n_out, dtype=jnp.int32) < n_nodes)
    valid_f = valid.astype(jnp.float32)
    wsum = S.edge_reduce("sum", A["csr_w"], A["csr_src"], n_out,
                         sorted=True)
    inv_wsum = jnp.where(wsum > 0, 1.0 / jnp.maximum(wsum, 1e-30), 0.0)
    dangling = valid & (wsum <= 0)
    dangling_f = dangling.astype(jnp.float32)
    edge_mult = A["w"] * inv_wsum[A["src"]]  # hoisted: one gather per run
    return {"w": edge_mult, "valid_f": valid_f, "dangling_f": dangling_f,
            "n_f": n_f, "x0": valid_f / n_f}


def _pagerank_epilogue(rank, acc, env, P):
    """FUSED-PAGERANK epilogue: damping update + L1 convergence partial
    computed on the accumulator inside the while body."""
    dangling_mass = jnp.sum(rank * env["dangling_f"])
    new_rank = S.pagerank_update(acc, dangling_mass, env["valid_f"],
                                 env["n_f"], P["damping"])
    err = jnp.sum(jnp.abs(new_rank - rank))
    return new_rank, err


# a delta larger than this fraction of the base edge set triggers a full
# replan (padding inflation + per-iter delta cost outgrow the saving)
DELTA_RECOMPACT_FRACTION = 0.10


def _edge_diff(base_g: DeviceGraph, new_g: DeviceGraph, changed_gids):
    """Multiset edge diff restricted to vertices in changed_gids.
    Returns (added, removed) as (src, dst, w) tuples of host arrays, or
    None when the diff cannot be derived (node set changed, no host
    arrays kept, ...)."""
    if base_g.host_coo is None or new_g.host_coo is None:
        return None
    if base_g.n_nodes != new_g.n_nodes or \
            not np.array_equal(base_g.node_gids, new_g.node_gids):
        return None     # node set changed: dense ids shifted
    bitmap = np.zeros(new_g.n_nodes, dtype=bool)
    for gid in changed_gids:
        idx = new_g.gid_to_idx.get(gid)
        if idx is not None:
            bitmap[idx] = True
    os_, od, ow = base_g.host_coo
    ns_, nd, nw = new_g.host_coo
    o_sel = bitmap[os_]
    n_sel = bitmap[ns_]
    # multiset diff over (src, dst, w) rows: +1 for new, -1 for old
    rows = np.stack([
        np.concatenate([ns_[n_sel].astype(np.int64),
                        os_[o_sel].astype(np.int64)]),
        np.concatenate([nd[n_sel].astype(np.int64),
                        od[o_sel].astype(np.int64)]),
        np.concatenate([nw[n_sel], ow[o_sel]]).view(np.int32).astype(
            np.int64),
    ], axis=1)
    sign = np.concatenate([np.ones(int(n_sel.sum()), dtype=np.int64),
                           -np.ones(int(o_sel.sum()), dtype=np.int64)])
    uniq, inv = np.unique(rows, axis=0, return_inverse=True)
    counts = np.zeros(len(uniq), dtype=np.int64)
    np.add.at(counts, inv, sign)
    add_idx = np.repeat(np.arange(len(uniq)), np.maximum(counts, 0))
    rem_idx = np.repeat(np.arange(len(uniq)), np.maximum(-counts, 0))
    w_back = lambda col: col.astype(np.int32).view(np.float32)  # noqa: E731
    added = (uniq[add_idx, 0], uniq[add_idx, 1], w_back(uniq[add_idx, 2]))
    removed = (uniq[rem_idx, 0], uniq[rem_idx, 1], w_back(uniq[rem_idx, 2]))
    return added, removed


def _try_delta_plan(graph: DeviceGraph):
    """Derive this snapshot's MXU state from a predecessor's full plan
    via an O(changed-edges) DeltaPlan. None -> caller does a full build.
    """
    from . import spmv_mxu
    ctx = getattr(graph, "_delta_ctx", None)
    if ctx is None:
        return None
    base_g, changed_gids = ctx
    base_state = getattr(base_g, "_mxu_state", None)
    if base_state is None or base_state[0].wsum is None:
        return None
    base_plan = base_state[0]
    with mgtrace.span("analytics.edge_diff"):
        diff = _edge_diff(base_g, graph, changed_gids)
    if diff is None:
        return None
    (a_s, a_d, a_w), (r_s, r_d, r_w) = diff
    n_delta = len(a_s) + len(r_s)
    if n_delta == 0:
        return base_state    # property-only bump: plan still exact
    if n_delta > max(DELTA_RECOMPACT_FRACTION * base_g.n_edges, 1024):
        return None          # recompact: full replan is the better deal
    with mgtrace.span("analytics.plan_build", kind="delta"):
        delta = spmv_mxu.build_delta_plan(base_plan, a_s, a_d, a_w,
                                          r_s, r_d, r_w)
    global_metrics.increment("delta.plan_applied_total")
    # the delta's blob alone is packed and uploaded; the base's stays
    # resident with base_plan and the program comes from the table
    with mgtrace.span("analytics.launch"):
        run = spmv_mxu.make_pagerank_kernel(base_plan, delta=delta)
    return (base_plan, run)


def _pagerank_via_mxu(graph: DeviceGraph, damping, max_iterations, tol,
                      precision: str = "f32", x0=None):
    """Large-graph path: gather-free MXU kernel with the plan cached on
    the (immutable) DeviceGraph snapshot. Successor snapshots of a
    mutated graph refresh O(delta) via DeltaPlan side-nets instead of
    replanning (reference analog: pagerank_online_module.cpp keeps
    incremental state for the same reason)."""
    from . import spmv_mxu
    cached = getattr(graph, "_mxu_state", None)
    if cached is None:
        with _mxu_locks_guard:
            lock = getattr(graph, "_mxu_build_lock", None)
            if lock is None:
                lock = threading.Lock()
                object.__setattr__(graph, "_mxu_build_lock", lock)
        with lock:
            cached = getattr(graph, "_mxu_state", None)
            if cached is None:
                cached = _try_delta_plan(graph)
                if cached is not None:
                    object.__setattr__(graph, "_mxu_state", cached)
            if cached is None:
                # true edges only: padding edges sort to the end (sinks)
                src = np.asarray(graph.src_idx)[:graph.n_edges]
                dst = np.asarray(graph.col_idx)[:graph.n_edges]
                w = np.asarray(graph.weights)[:graph.n_edges]
                with mgtrace.span("analytics.plan_build", kind="full"):
                    plan = spmv_mxu.build_plan(src, dst, w, graph.n_nodes)
                global_metrics.increment("delta.plan_rebuild_total")
                with mgtrace.span("analytics.launch"):
                    cached = (plan, spmv_mxu.make_pagerank_kernel(plan))
                # DeviceGraph is frozen; bypass its setattr guard
                object.__setattr__(graph, "_mxu_state", cached)
                # full plans anchor future delta refreshes (GraphCache)
                object.__setattr__(graph, "_mxu_base_self", True)
    plan, run = cached
    if precision == "bf16":
        # bf16 Benes routing halves the dominant HBM traffic; cached
        # separately so the f32 kernel (delta-refresh anchor) survives
        run = getattr(graph, "_mxu_run_bf16", None)
        if run is None:
            run = spmv_mxu.make_pagerank_kernel(
                plan, route_dtype=jnp.bfloat16)
            object.__setattr__(graph, "_mxu_run_bf16", run)
    x0_flat = None
    if x0 is not None:
        # warm seed in the plan's OUT labeling (flat node space); the
        # kernel renormalizes nothing — pass unit mass in
        x0 = np.asarray(x0, dtype=np.float32)[:graph.n_nodes]
        total = float(x0.sum())
        if np.isfinite(total) and total > 0.0:
            x0_flat = np.zeros(len(plan.valid_out), dtype=np.float32)
            x0_flat[plan.out_relabel] = x0 / np.float32(total)
    # launch returns at enqueue (a first-seen program signature traces,
    # lowers and loads or compiles here; any other is a dispatch); the
    # readback is the block. Together they are the device_iterate stage.
    with mgtrace.span("analytics.launch", backend="mxu"):
        # None = uniform start computed on-device (saves a transfer)
        rank, err, iters = run(x0_flat, np.float32(damping),
                               int(max_iterations), np.float32(tol))
    with mgtrace.span("analytics.device_wait", backend="mxu"):
        rank, err, iters = (np.asarray(rank)[plan.out_relabel],
                            float(err), int(iters))
    global_metrics.increment("device.fixpoint_iterations_total", iters)
    return rank, err, iters


def pagerank(graph: DeviceGraph, damping: float = 0.85,
             max_iterations: int = 100, tol: float = 1e-6, mesh=None,
             precision: str = "f32", x0=None):
    """Returns (ranks[:n_nodes], error, iterations).

    `mesh` routes the computation through the multi-chip layer
    (parallel/analytics.py): a MeshContext, a jax Mesh, a device count,
    or None (→ the MEMGRAPH_TPU_MESH_DEVICES env default; unset keeps
    the single-chip kernels). A mesh-of-1 runs the same sharded code
    path as any other size — single-device is a degeneracy, not a fork.

    `precision` — "f32" (exact), "bf16" (contributions rounded, f32
    accumulation) or "int8" (quantized streaming; segment backend only);
    error bounds: semiring.PRECISION_BOUNDS.

    `x0` — optional (n_nodes,) previous solution; warm-starts the
    fixpoint on every backend (ops/delta.py commit-then-CALL contract:
    PageRank is a contraction, any seed converges to the same answer at
    the same tol — the seed only cuts the iteration count).
    """
    from ..utils.jax_cache import ensure_compile_cache
    ensure_compile_cache()
    # MXU_MIN_EDGES read at call time: tests (and operators) tune the
    # threshold by monkeypatching this module attribute
    backend, ctx = S.route_backend(graph, mesh, semiring="plus_times",
                                   precision=precision,
                                   min_edges=MXU_MIN_EDGES)
    if backend == "mesh":
        from ..parallel.analytics import pagerank_mesh
        with S.backend_extent("mesh"):
            return pagerank_mesh(graph, ctx, damping=damping,
                                 max_iterations=max_iterations, tol=tol,
                                 precision=precision, x0=x0)
    if backend == "mxu":
        return _pagerank_via_mxu(graph, damping, max_iterations, tol,
                                 precision, x0=x0)
    x0_pad = None
    if x0 is not None:
        x0 = np.asarray(x0, dtype=np.float32)[:graph.n_nodes]
        total = float(x0.sum())
        if np.isfinite(total) and total > 0.0:
            buf = np.zeros(graph.n_pad, dtype=np.float32)
            buf[:len(x0)] = x0 / np.float32(total)
            x0_pad = jnp.asarray(buf)
    # the fixpoint's own device.chunk (its child) feeds the stages here
    with mgtrace.span("analytics.launch"):
        rank, err, iters = S.fixpoint(
            "plus_times",
            arrays={"src": graph.csc_src, "dst": graph.csc_dst,
                    "w": graph.csc_weights,
                    "csr_src": graph.src_idx, "csr_w": graph.weights},
            params={"n_nodes": np.int32(graph.n_nodes),
                    "damping": np.float32(damping),
                    "tol": np.float32(tol)},
            n_out=graph.n_pad, setup=_pagerank_setup,
            epilogue=_pagerank_epilogue, max_iterations=max_iterations,
            sorted=True, precision=precision, x0=x0_pad)
    with mgtrace.span("analytics.device_wait", backend="segment"):
        rank, err, iters = (np.asarray(rank)[:graph.n_nodes],
                            float(err), int(iters))
    global_metrics.increment("device.fixpoint_iterations_total", iters)
    return rank, err, iters


def _ppr_setup(A, P, n_out):
    """PPR invariants: normalized restart vector + hoisted multipliers."""
    n_nodes = P["n_nodes"]
    valid = (jnp.arange(n_out, dtype=jnp.int32) < n_nodes)
    valid_f = valid.astype(jnp.float32)
    p = A["personalization"] * valid_f
    p = p / jnp.maximum(jnp.sum(p), 1e-30)
    wsum = S.edge_reduce("sum", A["csr_w"], A["csr_src"], n_out,
                         sorted=True)
    inv_wsum = jnp.where(wsum > 0, 1.0 / jnp.maximum(wsum, 1e-30), 0.0)
    dangling_f = (valid & (wsum <= 0)).astype(jnp.float32)
    edge_mult = A["w"] * inv_wsum[A["src"]]
    return {"w": edge_mult, "p": p, "dangling_f": dangling_f, "x0": p}


def _ppr_epilogue(rank, acc, env, P):
    """Fused PPR update: restart mass flows to the personalization
    vector (dangling mass included) instead of uniformly."""
    p = env["p"]
    dangling_mass = jnp.sum(rank * env["dangling_f"])
    new_rank = (1.0 - P["damping"]) * p \
        + P["damping"] * (acc + dangling_mass * p)
    err = jnp.sum(jnp.abs(new_rank - rank))
    return new_rank, err


def personalized_pagerank(graph: DeviceGraph, source_nodes,
                          damping: float = 0.85, max_iterations: int = 100,
                          tol: float = 1e-6, precision: str = "f32",
                          kernel=None, kernel_meta: dict | None = None):
    """PPR with restart mass on `source_nodes` (dense indices).

    Analog of mage/cpp/cugraph_module/algorithms/personalized_pagerank.cu.

    ``kernel`` routes the request through the resident kernel server's
    coalescing PPR plane (a socket path, ``True``/"1" for the default
    socket, or a client object with a ``ppr`` method): concurrent
    requests batch into one multi-source SpMM fixpoint and hit the
    server's change-log-invalidated result cache. ``kernel_meta``
    forwards serving metadata (graph_key / graph_version / delta — see
    server/kernel_server.py). A kernel-plane failure falls back to the
    in-process path LOUDLY.
    """
    if kernel is not None:
        got = _ppr_via_kernel(graph, source_nodes, damping, max_iterations,
                              tol, precision, kernel, kernel_meta)
        if got is not None:
            return got
    p = jnp.zeros(graph.n_pad, dtype=jnp.float32)
    p = p.at[jnp.asarray(source_nodes, dtype=jnp.int32)].set(1.0)
    rank, err, iters = S.fixpoint(
        "plus_times",
        arrays={"src": graph.csc_src, "dst": graph.csc_dst,
                "w": graph.csc_weights,
                "csr_src": graph.src_idx, "csr_w": graph.weights,
                "personalization": p},
        params={"n_nodes": np.int32(graph.n_nodes),
                "damping": np.float32(damping),
                "tol": np.float32(tol)},
        n_out=graph.n_pad, setup=_ppr_setup, epilogue=_ppr_epilogue,
        max_iterations=max_iterations, sorted=True, precision=precision)
    # read back padded and cut on the host, as pagerank() does: a device
    # slice to n_nodes is a new program for every vertex count
    return np.asarray(rank)[:graph.n_nodes], float(err), int(iters)


def _ppr_via_kernel(graph, source_nodes, damping, max_iterations, tol,
                    precision, kernel, kernel_meta):
    """Route one PPR through the resident server's coalescing plane.
    Returns (ranks, err, iters) or None (caller runs in-process)."""
    import logging
    from ..observability.metrics import global_metrics
    from ..server import kernel_server as ks
    meta = dict(kernel_meta or {})
    try:
        if hasattr(kernel, "ppr"):
            client = kernel
        else:
            sock = ks.DEFAULT_SOCKET if kernel in (True, "1", "default") \
                else str(kernel)
            client = ks.shared_client(sock)
        send_graph = meta.pop("send_graph", True)
        meta.pop("top_k", None)    # this entry point returns full ranks
        kwargs = {}
        if send_graph:
            src, dst, w = graph.host_coo if graph.host_coo is not None \
                else (np.asarray(graph.src_idx)[:graph.n_edges],
                      np.asarray(graph.col_idx)[:graph.n_edges],
                      np.asarray(graph.weights)[:graph.n_edges])
            kwargs.update(src=np.asarray(src, dtype=np.int64),
                          dst=np.asarray(dst, dtype=np.int64),
                          weights=np.asarray(w, dtype=np.float32))
        meta.setdefault("graph_key",
                        f"ppr:{id(graph)}:{graph.n_nodes}:{graph.n_edges}")
        h, out = client.ppr(
            sources=np.asarray(source_nodes, dtype=np.int32),
            n_nodes=graph.n_nodes, damping=float(damping),
            max_iterations=int(max_iterations), tol=float(tol),
            precision=precision, **meta, **kwargs)
        global_metrics.increment("analytics.kernel_routed_total")
        return (np.asarray(out["ranks"])[:graph.n_nodes],
                float(h.get("err", 0.0)), int(h.get("iters", 0)))
    except (ks.KernelServerError, ConnectionError, OSError) as e:
        global_metrics.increment("analytics.kernel_route_fallback_total")
        logging.getLogger(__name__).warning(
            "kernel-server PPR route failed (%s: %s); falling back to "
            "the in-process path", type(e).__name__, e)
        return None


# --------------------------------------------------------------------------
# batched multi-source PPR (the serving-plane SpMM fixpoint)
# --------------------------------------------------------------------------
#
# N concurrent personalization vectors are ONE (n, B) SpMM per iteration
# ("Accelerating Personalized PageRank Vector Computation", PAPERS.md):
# the edge gather, ⊗-combine and segment-⊕ run once over B lanes, so the
# dominant memory traffic (the edge stream) is amortized across every
# rider of the batch — the coalescing win the PPR serving plane banks on.
# Lanes are INDEPENDENT fixpoints: a converged column freezes (its value
# is the exact iterate whose L1 step error first dipped under tol, same
# as the sequential loop's stopping state), so batched f32 results are
# BIT-EXACT vs sequential `personalized_pagerank` regardless of how
# long slower batchmates keep iterating (tests/test_ppr_serving.py).

_PPR_BATCH_CACHE: dict = {}
_ppr_batch_cache_lock = threading.Lock()

#: batch lanes are padded up to these bucket widths so a serving
#: workload with jittery batch sizes reuses a handful of compiled
#: programs instead of one per size
_PPR_LANE_BUCKETS = (1, 2, 4, 8, 16, 32, 64, 128)


def _bucket_lanes(b: int) -> int:
    for cap in _PPR_LANE_BUCKETS:
        if b <= cap:
            return cap
    return b


def _build_ppr_batch(n_out: int, max_iterations: int, precision: str,
                     warm: bool):
    import jax

    # the name the program has in a device trace: jit_ppr_batch
    def ppr_batch(A, P, x0):
        # batched analog of _ppr_setup: identical hoisted invariants,
        # personalization columns normalized per lane
        n_nodes = P["n_nodes"]
        valid = (jnp.arange(n_out, dtype=jnp.int32) < n_nodes)
        valid_f = valid.astype(jnp.float32)
        pm = A["personalization"] * valid_f[:, None]
        pm = pm / jnp.maximum(jnp.sum(pm, axis=0), 1e-30)
        wsum = S.edge_reduce("sum", A["csr_w"], A["csr_src"], n_out,
                             sorted=True)
        inv_wsum = jnp.where(wsum > 0, 1.0 / jnp.maximum(wsum, 1e-30), 0.0)
        dangling_f = (valid & (wsum <= 0)).astype(jnp.float32)
        edge_mult = A["w"] * inv_wsum[A["src"]]
        x_init = x0 if warm else pm
        tol = P["tol"]
        n_lanes = pm.shape[1]

        def body(carry):
            x, done, err, iters, it = carry
            acc = S.spmv("plus_times", x, A["src"], A["dst"], edge_mult,
                         n_out=n_out, sorted=True, precision=precision)
            dangling_mass = jnp.sum(x * dangling_f[:, None], axis=0)
            new_x = (1.0 - P["damping"]) * pm \
                + P["damping"] * (acc + dangling_mass[None, :] * pm)
            # once an iteration: what a trace counts iterations by
            with jax.named_scope("ppr_converged"):
                new_err = jnp.sum(jnp.abs(new_x - x), axis=0)
            # freeze converged lanes: their retained iterate is exactly
            # the sequential loop's stopping state
            x = jnp.where(done[None, :], x, new_x)
            err = jnp.where(done, err, new_err)
            iters = jnp.where(done, iters, iters + 1)
            done = done | (err <= tol)
            return x, done, err, iters, it + 1

        def cond(carry):
            _x, done, _err, _iters, it = carry
            return (~jnp.all(done)) & (it < max_iterations)

        carry0 = (x_init, jnp.zeros(n_lanes, dtype=jnp.bool_),
                  jnp.full(n_lanes, jnp.inf, dtype=jnp.float32),
                  jnp.zeros(n_lanes, dtype=jnp.int32), jnp.int32(0))
        x, _done, err, iters, _it = jax.lax.while_loop(cond, body, carry0)
        return x, err, iters

    # the warm-start seed matrix is donated back to the (n_pad, B)
    # iterate — the serving plane builds a fresh x0 per batch, so the
    # seed never needs to outlive the call (cold runs pass x0=None:
    # nothing to donate, pm doubles as the start AND the restart vector)
    return jax.jit(ppr_batch, donate_argnums=(2,))


def personalized_pagerank_batch(graph: DeviceGraph, source_sets,
                                damping: float = 0.85,
                                max_iterations: int = 100,
                                tol: float = 1e-6, precision: str = "f32",
                                x0=None, raw: bool = False):
    """B independent PPR fixpoints as ONE SpMM power iteration.

    ``source_sets`` is a list of dense-index lists (one per lane) or a
    prebuilt (n_pad, B) personalization matrix. ``x0`` optionally seeds
    lanes from cached vectors ((n_pad, B); the serving plane's
    warm-start path — PPR is a contraction, so ANY seed converges to
    the same fixpoint, just in fewer iterations).

    Returns (ranks (B, n_nodes), err (B,), iters (B,)). Lane counts are
    padded up to compile-amortizing buckets; padding lanes restart on
    lane 0's sources and are dropped before returning. ``raw=True``
    instead returns the DEVICE (n_pad, n_lanes) iterate (padding lanes
    included) so the caller can run on-device epilogues (top-k
    extraction) before paying the host transfer.
    """
    from ..utils.jax_cache import ensure_compile_cache
    ensure_compile_cache()
    if getattr(source_sets, "ndim", None) == 2:
        pm = np.asarray(source_sets, dtype=np.float32)
        n_req = pm.shape[1]
    else:
        n_req = len(source_sets)
        pm = np.zeros((graph.n_pad, n_req), dtype=np.float32)
        for lane, sources in enumerate(source_sets):
            pm[np.asarray(sources, dtype=np.int32), lane] = 1.0
    if n_req == 0:
        return (np.zeros((0, graph.n_nodes), dtype=np.float32),
                np.zeros(0, dtype=np.float32), np.zeros(0, dtype=np.int32))
    n_lanes = _bucket_lanes(n_req)
    if n_lanes > n_req:
        pad = np.repeat(pm[:, :1], n_lanes - n_req, axis=1)
        pm = np.concatenate([pm, pad], axis=1)
    warm = x0 is not None
    if warm:
        x0 = np.asarray(x0, dtype=np.float32)
        if x0.shape[1] < n_lanes:
            pad = np.repeat(pm[:, -1:], n_lanes - x0.shape[1], axis=1)
            x0 = np.concatenate([x0, pad], axis=1)
    key = (int(graph.n_pad), int(max_iterations), precision, warm)
    fn = _PPR_BATCH_CACHE.get(key)
    if fn is None:
        with _ppr_batch_cache_lock:
            fn = _PPR_BATCH_CACHE.get(key)
            if fn is None:
                fn = _build_ppr_batch(graph.n_pad, int(max_iterations),
                                      precision, warm)
                _PPR_BATCH_CACHE[key] = fn
    arrays = {"src": graph.csc_src, "dst": graph.csc_dst,
              "w": graph.csc_weights,
              "csr_src": graph.src_idx, "csr_w": graph.weights,
              "personalization": jnp.asarray(pm)}
    with mgtrace.span("device.chunk", backend="segment"):
        x, err, iters = fn(arrays, {"n_nodes": np.int32(graph.n_nodes),
                                    "damping": np.float32(damping),
                                    "tol": np.float32(tol)},
                           jnp.asarray(x0) if warm else None)
    if raw:
        # DEVICE handles (padding lanes included for x): the serving
        # plane fuses its epilogues (top-k) and pays ONE host transfer
        # for the whole batch — err/iters ride that same device_get
        return x, err, iters
    ranks = np.asarray(x)[: graph.n_nodes, :n_req].T
    return (ranks, np.asarray(err)[:n_req], np.asarray(iters)[:n_req])


_PPR_TOPK_CACHE: dict = {}


def _build_ppr_topk(k: int):
    import jax

    def topk(m, n_nodes):
        # columns past n_nodes are padding: masked, not cut, so the
        # program follows the padded shape and not the vertex count
        live = jnp.arange(m.shape[1], dtype=jnp.int32) < n_nodes
        return jax.lax.top_k(jnp.where(live[None, :], m, -jnp.inf), k)

    # the name the program has in a device trace: jit_ppr_topk
    topk.__name__ = "ppr_topk"
    return jax.jit(topk)


def ppr_topk(ranks_matrix, n_nodes: int, k: int, raw: bool = False):
    """Per-lane top-k over a (B, n) rank matrix ON DEVICE — the serving
    plane extracts each request's answer before the reply ships, so a
    top-10 query never pays an O(n) result transfer per rider beyond
    the batch's own cache fill. n may be padded past ``n_nodes``.

    Returns (values (B, k), indices (B, k)) as host arrays, or as
    DEVICE handles with ``raw=True`` so the serving plane can fold them
    into its one fused result transfer per batch (mglint MG009)."""
    k = max(1, min(int(k), int(n_nodes)))
    fn = _PPR_TOPK_CACHE.get(k)
    if fn is None:
        fn = _PPR_TOPK_CACHE[k] = _build_ppr_topk(k)
    vals, idx = fn(jnp.asarray(ranks_matrix), np.int32(n_nodes))
    if raw:
        return vals, idx
    return np.asarray(vals), np.asarray(idx)  # mglint: disable=MG009 — host-array return contract for direct callers; the serving plane passes raw=True and folds these into its one fused device_get per chunk
