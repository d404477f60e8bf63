"""North-star benchmark: PageRank edges/sec on a 10M-edge graph (BASELINE.md).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.
  value       = TPU PageRank throughput in edges/sec (n_edges * iterations /
                wall seconds, compile excluded, fixed iteration count)
  vs_baseline = speedup over the CPU baseline: scipy.sparse CSR power
                iteration on this host — the same sparse-matvec formulation
                the reference's C++ pagerank module implements
                (/root/reference/mage/cpp/pagerank_module), measured on the
                same graph with the same iteration count.

Layout:
  - the orchestrating process never imports jax: the device is probed in
    a SUBPROCESS with a short timeout, and every device stage runs in a
    subprocess of its own (`JAX_PLATFORMS=tpu`) with its own timeout and
    a ladder (MXU kernel @ 10M edges -> segment kernel @ 10M -> @ 1M);
  - no chip, or no device stage that completes, is a NON-ZERO exit: a
    measurement path never falls back to the CPU;
  - the scipy baseline runs first (pure numpy/scipy).

Known fault (ROADMAP S1): the semiring and latency stages reach the chip
through a resident kernel-server daemon that stays up, while the delta
and tier stages reach for the same chip in-process; a chip has one owner
at a time, so on one chip those stages cannot all run. The benchmark PR
replaces this script.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import time

import numpy as np

N_NODES = int(os.environ.get("BENCH_N_NODES", 1_000_000))
N_EDGES = int(os.environ.get("BENCH_N_EDGES", 10_000_000))
ITERATIONS = 50
DAMPING = 0.85

PROBE_TIMEOUT_SEC = 30
STAGE_TIMEOUT_SEC = 300
MASTER_TIMEOUT_SEC = int(os.environ.get("BENCH_MASTER_TIMEOUT", 530))

# best-so-far partial result; the belt-and-braces watchdog prints this, so
# a wedge after the CPU baseline still yields an honest record.
# "degraded" starts True and is only cleared when the headline number came
# from the real accelerator at full size; "backend" stays "none" (and the
# exit code non-zero) until a device stage has completed.
PARTIAL = {
    "metric": "pagerank_edges_per_sec_10M", "value": 0.0, "unit": "edges/s",
    "vs_baseline": 0.0, "degraded": True, "backend": "none",
    "extra": {"error": "bench wedged before any stage"},
}


def log(msg):
    print(msg, file=sys.stderr, flush=True)



def best_timed(once, budget_s=45.0, runs=3):
    """min-of-N wall time, adaptively: stop repeating once the cumulative
    timed spend exceeds budget_s, so a slow environment (fallback rungs,
    loaded host) never triples a stage that barely fit its timeout."""
    best, spent, result = float("inf"), 0.0, None
    for _ in range(runs):
        t0 = time.perf_counter()
        out = once()
        dt = time.perf_counter() - t0
        if dt < best:
            # keep result and time from the SAME run — device reductions
            # are not bit-deterministic across runs
            best, result = dt, out
        spent += dt
        if spent > budget_s:
            break
    return result, best


def generate_graph(n_nodes=N_NODES, n_edges=N_EDGES, seed=7):
    """Skewed random digraph: power-law-ish in-degree via squared sampling
    (supernode skew stresses the segment reductions, SURVEY.md §7)."""
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n_nodes, n_edges, dtype=np.int64)
    # bias destinations toward low ids → heavy-tail in-degree
    dst = (rng.random(n_edges) ** 2 * n_nodes).astype(np.int64)
    return src, dst


def cpu_pagerank(src, dst, n_nodes, iterations=ITERATIONS, damping=DAMPING):
    """Baseline: scipy CSR power iteration (the C++ module's formulation)."""
    import scipy.sparse as sp
    w = np.ones(len(src), dtype=np.float64)
    deg = np.bincount(src, minlength=n_nodes).astype(np.float64)
    inv_deg = np.where(deg > 0, 1.0 / np.maximum(deg, 1), 0.0)
    # column-normalized adjacency: rank flows src -> dst
    mat = sp.csr_matrix((w * inv_deg[src], (dst, src)),
                        shape=(n_nodes, n_nodes))
    dangling = deg == 0
    # best-of-3: single-run wall time swings +-30% on this shared host,
    # which would swing vs_baseline by the same amount for free

    def once():
        rank = np.full(n_nodes, 1.0 / n_nodes)
        for _ in range(iterations):
            dm = rank[dangling].sum()
            rank = (1 - damping) / n_nodes \
                + damping * (mat @ rank + dm / n_nodes)
        return rank
    rank, elapsed = best_timed(once)
    return rank, elapsed


# --------------------------------------------------------------------------
# device-side stages (run in subprocesses; see --stage flags at the bottom)
# --------------------------------------------------------------------------

def stage_probe():
    """Tiny end-to-end device check through the SHARED probe path
    (kernel_server.probe_device — the same compiled-matmul+transfer
    check the resident daemon's health plane runs, fault-injectable via
    the device.* points). Exits 0 iff the device works."""
    import jax
    from memgraph_tpu.server.kernel_server import probe_device
    s, platform = probe_device()
    print(json.dumps({"devices": [str(d) for d in jax.devices()],
                      "platform": platform, "sum": s}))
    if platform != "tpu":
        raise SystemExit(f"probe ran on {platform}, not on a TPU")


def _classify_probe(rc) -> str:
    """Typed outcome for one subprocess probe attempt."""
    if rc == 0:
        return "ok"
    if rc is None:
        return "probe_timeout"
    if rc == 137:
        return "probe_killed"
    return f"probe_error_rc_{rc}"


def _resident_probe(timeout=20.0):
    """Consult the RESIDENT kernel server: its health reply plus its
    typed `probe` op. Returns (health_dict | None, probe_reply | None);
    never spawns a daemon — a probe consult must stay cheap."""
    try:
        from memgraph_tpu.server.kernel_server import (DEFAULT_SOCKET,
                                                       KernelClient)
    except Exception as e:  # noqa: BLE001 — environmental import failure
        log(f"  kernel-server import failed during probe consult: {e}")
        return None, None
    try:
        c = KernelClient(DEFAULT_SOCKET, timeout=timeout)
    except OSError:
        return None, None                # no resident daemon
    try:
        health = c.health()
    except Exception as e:  # noqa: BLE001 — daemon present but sick
        log(f"  resident kernel server health call failed: {e}")
        try:
            c.close()
        except OSError:
            pass
        return None, None
    probe_reply = None
    if not health.get("wedged"):
        try:
            probe_reply = c.probe()
        except Exception as e:  # noqa: BLE001 — typed reply preferred
            log(f"  resident kernel server probe failed: {e}")
    try:
        c.close()
    except OSError:
        pass
    return health, probe_reply


def stage_pagerank_mxu(n_nodes, n_edges, seed, out_path):
    """Gather-free MXU kernel (ops/spmv_mxu.py): plan from cache or fresh,
    run 50 fixed iterations on the device."""
    from memgraph_tpu.ops import spmv_mxu
    from memgraph_tpu.utils.jax_cache import ensure_compile_cache
    import jax
    import jax.numpy as jnp

    ensure_compile_cache()
    src, dst = generate_graph(n_nodes, n_edges, seed)
    cache_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             ".bench_cache")
    os.makedirs(cache_dir, exist_ok=True)
    cache = os.path.join(cache_dir,
                         f"mxu_plan_{n_nodes}_{n_edges}_{seed}.npz")
    t0 = time.perf_counter()
    plan = spmv_mxu.load_plan(cache) if os.path.exists(cache) else None
    plan_cached = plan is not None and plan.n_nodes == n_nodes
    plan_build_s = 0.0
    meta_path = cache + ".meta.json"
    if not plan_cached:
        t1 = time.perf_counter()
        plan = spmv_mxu.build_plan(src, dst, None, n_nodes)
        plan_build_s = time.perf_counter() - t1
        try:
            spmv_mxu.save_plan(plan, cache)
            with open(meta_path, "w") as f:
                json.dump({"plan_build_fresh_s": plan_build_s}, f)
        except OSError:
            pass
    plan_s = time.perf_counter() - t0
    # the fresh-build cost is a real number even when this run hit the
    # cache: report the persisted measurement from the run that built it
    plan_build_fresh_s = plan_build_s
    if plan_cached and os.path.exists(meta_path):
        try:
            with open(meta_path) as f:
                plan_build_fresh_s = float(
                    json.load(f)["plan_build_fresh_s"])
        except (OSError, ValueError, KeyError):
            pass

    # O(delta) refresh cost: the side-plan for a 100k-edge topology
    # change (the streaming-ingest path; full replan no longer needed —
    # ops/pagerank._try_delta_plan, tests/test_plan_delta_e2e.py)
    t1 = time.perf_counter()
    drng = np.random.default_rng(1)
    spmv_mxu.build_delta_plan(
        plan, drng.integers(0, n_nodes, 100_000),
        (drng.random(100_000) ** 2 * n_nodes).astype(np.int64))
    plan_delta_build_s = time.perf_counter() - t1

    t0 = time.perf_counter()
    # bf16 routing through the Benes (f32 accumulation): validated to
    # preserve exact top-100 order on this graph; the overlap check below
    # re-verifies every run
    run = spmv_mxu.make_pagerank_kernel(plan, route_dtype=jnp.bfloat16)
    transfer_s = time.perf_counter() - t0  # blob pack + device_put
    t0 = time.perf_counter()
    # uniform start computed on-device (None): saves one 33MB transfer
    # compile + warm (excluded); 1-element host transfer forces completion
    rank, err, iters = run(None, jnp.float32(DAMPING), ITERATIONS,
                           jnp.float32(0.0))
    _ = float(rank[0])
    warm_s = time.perf_counter() - t0

    def once():
        out = run(None, jnp.float32(DAMPING), ITERATIONS, jnp.float32(0.0))
        _ = float(out[0][0])
        return out
    # best-of-3 mirrors the CPU baseline's timing
    (rank, err, iters), elapsed = best_timed(once)
    assert int(iters) == ITERATIONS, f"expected {ITERATIONS}, ran {int(iters)}"
    ranks = np.asarray(rank)[plan.out_relabel]
    np.savez(out_path, ranks=ranks, elapsed=elapsed,
             export_s=plan_s + transfer_s + warm_s,
             build_s=plan_s, transfer_s=transfer_s,
             plan_build_s=plan_build_s, plan_cached=plan_cached,
             plan_build_fresh_s=plan_build_fresh_s,
             plan_delta_build_s=plan_delta_build_s,
             warm_s=warm_s,
             platform=jax.devices()[0].platform)


def stage_pagerank(n_nodes, n_edges, seed, out_path):
    """CSR export + device PageRank via the RESUMABLE partition-centric
    entry point (mesh-of-1 degeneracy of the sharded path): the loop
    carry checkpoints to host every BENCH_CHECKPOINT_EVERY iterations,
    so a device fault mid-stage resumes instead of restarting — the
    same path the kernel server serves. Writes ranks + timings."""
    from memgraph_tpu.ops import csr
    from memgraph_tpu.parallel import analytics
    from memgraph_tpu.parallel.mesh import get_mesh_context
    import jax

    ckpt_every = int(os.environ.get("BENCH_CHECKPOINT_EVERY", "25"))
    src, dst = generate_graph(n_nodes, n_edges, seed)
    t0 = time.perf_counter()
    graph = csr.from_coo(src, dst, n_nodes=n_nodes)
    build_s = time.perf_counter() - t0
    ctx = get_mesh_context(1)
    t0 = time.perf_counter()
    # partition-centric blocking + device placement (cached on the graph)
    csr.shard_csr(graph, ctx, by="src")
    transfer_s = time.perf_counter() - t0
    export_s = build_s + transfer_s

    def run():
        # tol=-1 pins the run to exactly ITERATIONS iterations (f32 err
        # can legitimately reach 0.0, so tol=0 could stop early)
        return analytics.pagerank_mesh(
            graph, ctx, damping=DAMPING, max_iterations=ITERATIONS,
            tol=-1.0, checkpoint_every=ckpt_every)

    # compile + warm up (excluded from timing); the host transfer
    # forces completion
    # mgstat (r14): the stage accumulator rides the whole device extent,
    # so the record carries the SAME per-stage attribution PROFILE shows
    # (transfer / compile-fold / iterate), measured by the product hooks
    # rather than by bench-side stopwatches alone.
    from memgraph_tpu.observability import stats as mgstats
    acc = mgstats.StageAccumulator()
    with mgstats.collecting_stages(acc):
        t0 = time.perf_counter()
        rank, err, iters = run()
        _ = float(rank[0])
        warm_s = time.perf_counter() - t0

        def once():
            out = run()
            _ = float(out[0][0])  # host sync
            return out
        (rank, err, iters), elapsed = best_timed(once)
    assert int(iters) == ITERATIONS, f"expected {ITERATIONS}, ran {int(iters)}"
    np.savez(out_path, ranks=np.asarray(rank[:n_nodes]),
             elapsed=elapsed, export_s=export_s,
             build_s=build_s, transfer_s=transfer_s, warm_s=warm_s,
             mgstat_stages=json.dumps(acc.snapshot()),
             platform=jax.devices()[0].platform)


SEMIRING_ITERATIONS = 20


def stage_semiring(n_nodes, n_edges, seed, out_path):
    """Semiring-core sweep (r10): pagerank through ops/semiring.py at
    f32 AND bf16 (same dispatch the product serves), plus BFS via the
    min-plus generic mesh kernel — routed through the RESIDENT kernel
    server's `semiring` op when a daemon is reachable (the graph ships
    once under a graph_key; timed calls pay socket + device only), else
    in-process.  Writes per-precision timings + top-100 f32/bf16
    overlap so the record carries rank-order-preservation evidence."""
    import jax
    src, dst = generate_graph(n_nodes, n_edges, seed)
    client = None
    resident = False
    try:
        from memgraph_tpu.server.kernel_server import ensure_server
        client = ensure_server()
        resident = True
    except Exception as e:  # noqa: BLE001 — environmental: fall back
        log(f"  resident kernel server unavailable for semiring "
            f"sweep ({e}); running in-process")
    results = {}
    if client is not None:
        key = f"sem_{n_nodes}_{n_edges}_{seed}"
        # warm: ship the graph + compile (excluded from timing)
        client.semiring("pagerank", src=src, dst=dst, n_nodes=n_nodes,
                        graph_key=key, max_iterations=2, tol=-1.0)
        for prec in ("f32", "bf16"):
            def once(prec=prec):
                _h, out = client.semiring(
                    "pagerank", graph_key=key, precision=prec,
                    max_iterations=SEMIRING_ITERATIONS, tol=-1.0)
                return out["ranks"]
            ranks, elapsed = best_timed(once, budget_s=40.0)
            results[prec] = (np.asarray(ranks), elapsed)

        def bfs_once():
            h, _out = client.semiring("bfs", graph_key=key, source=0)
            return h["iters"]
        _, bfs_elapsed = best_timed(bfs_once, budget_s=20.0)
        # the daemon owns the chip: ask it, never this process's jax
        platform = client.health()["platform"]
        client.close()
    else:
        from memgraph_tpu.ops import csr
        from memgraph_tpu.ops.pagerank import pagerank
        from memgraph_tpu.parallel import analytics
        from memgraph_tpu.parallel.mesh import get_mesh_context
        graph = csr.from_coo(src, dst, n_nodes=n_nodes)
        for prec in ("f32", "bf16"):
            pagerank(graph, max_iterations=2, tol=-1.0, precision=prec)

            def once(prec=prec):
                out = pagerank(graph, max_iterations=SEMIRING_ITERATIONS,
                               tol=-1.0, precision=prec)
                _ = float(np.asarray(out[0])[0])
                return np.asarray(out[0])
            ranks, elapsed = best_timed(once, budget_s=40.0)
            results[prec] = (ranks, elapsed)
        ctx1 = get_mesh_context(1)
        analytics.bfs_mesh(graph, ctx1, 0)          # warm

        def bfs_once():
            return analytics.bfs_mesh(graph, ctx1, 0)[1]
        _, bfs_elapsed = best_timed(bfs_once, budget_s=20.0)
        platform = jax.devices()[0].platform
    f32_ranks, f32_s = results["f32"]
    bf16_ranks, bf16_s = results["bf16"]
    top100 = lambda r: set(np.argsort(-r)[:100].tolist())  # noqa: E731
    overlap = len(top100(f32_ranks[:n_nodes]) & top100(bf16_ranks[:n_nodes]))
    np.savez(out_path, f32_s=f32_s, bf16_s=bf16_s, bfs_s=bfs_elapsed,
             overlap=overlap, platform=platform, resident=resident)


#: fixed sweep count for the tier stage — convergence is the smoke's
#: and the test suite's territory; the bench wants a stable edges/s +
#: overlap measurement over a known number of full-graph sweeps
TIER_ITERATIONS = 20


def stage_tier(n_nodes, n_edges, seed, out_path):
    """Out-of-core streamed tier (r21 mgtier): PageRank over a
    host-pinned TierCSR — compressed edge blocks stream H2D
    double-buffered against the previous block's SpMV fold while the
    rank vector stays device-resident. Records the measured serial
    transfer/compute split (first iteration runs the blocks serially
    to price both sides), the overlapped-iteration wall time and the
    hidden-transfer fraction the BASELINE.json tier_overlap envelope
    defends, plus the bf16/int8 wire-compression ratios vs raw COO."""
    import jax
    from memgraph_tpu.ops import tier as mgtier
    from memgraph_tpu.parallel.distributed import pagerank_streamed
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n_nodes, n_edges).astype(np.int64)
    dst = rng.integers(0, n_nodes, n_edges).astype(np.int64)
    w = (rng.random(n_edges) + 0.1).astype(np.float32)
    # enough blocks that the double-buffer schedule has real work to
    # hide even when the bench graph fits the default 32 MiB budget
    n_blocks = max(8, mgtier.plan_blocks(n_nodes, n_edges, "f32",
                                         mgtier.block_bytes_budget()))
    tier = mgtier.plan_tier(src, dst, w, n_nodes, precision="f32",
                            n_blocks=n_blocks)
    pagerank_streamed(tier, max_iterations=2, tol=-1.0)       # warm
    stats = {}
    t0 = time.perf_counter()
    ranks, _err, iters = pagerank_streamed(
        tier, max_iterations=TIER_ITERATIONS, tol=-1.0, stats=stats)
    elapsed = time.perf_counter() - t0
    _ = float(np.asarray(ranks)[0])
    ratios = {}
    for prec in ("bf16", "int8"):
        tp = mgtier.plan_tier(src, dst, w, n_nodes, precision=prec,
                              n_blocks=n_blocks)
        ratios[prec] = (sum(b.raw_nbytes for b in tp.blocks)
                        / sum(b.nbytes for b in tp.blocks))
    np.savez(out_path, platform=jax.devices()[0].platform,
             elapsed=elapsed, iters=iters, n_blocks=tier.n_blocks,
             serial_transfer_s=stats.get("serial_transfer_s") or 0.0,
             serial_compute_s=stats.get("serial_compute_s") or 0.0,
             hidden=stats.get("transfer_hidden_fraction") or 0.0,
             overlap_iter_s=stats.get("overlap_iter_s_mean") or 0.0,
             wire_bytes=stats.get("wire_bytes_per_sweep", 0),
             raw_bytes=stats.get("raw_bytes_per_sweep", 0),
             ratio_bf16=ratios["bf16"], ratio_int8=ratios["int8"])


#: churn fraction for the delta stage — 0.5% of the edge set in ONE
#: committed remove+add transaction (half the envelope's ≤1% ceiling;
#: representative of a heavy OLTP burst between two CALLs)
DELTA_CHURN = float(os.environ.get("BENCH_DELTA_CHURN", "0.005"))


def stage_delta(n_nodes, n_edges, seed, out_path):
    """mgdelta (r19): commit-to-fresh-result vs cold full rebuild, plus
    the streaming-ingest-while-querying scenario the bench never
    covered.

    Part 1 — resident delta speedup at full size: a ResidentGraph holds
    the graph device-side with a converged pagerank solution; a ≤1%
    edge churn then goes through BOTH paths:
      cold  = from_coo (native CSR build) + shard_edges (global
              lexsort) + device placement + cold fixpoint — the
              CONSERVATIVE cold baseline (the real product cold path
              additionally pays the Python MVCC export walk);
      delta = change-log diff (diff_changed_coo) + EdgeDelta splice of
              the resident layout (O(delta + affected rows)) + re-place
              + warm-started fixpoint at the SAME tol.
    delta_speedup = cold_s / delta_s feeds the BASELINE.json
    ``delta_speedup`` envelope (perf_gate.check_delta).

    Part 2 — streaming ingest while querying (small scale): a writer
    thread feeds edge batches through the storage bulk lane while a
    query loop serves commit-then-CALL pagerank through GraphCache +
    LocalWarmPool; records fresh-result latency percentiles and
    delta-apply throughput.
    """
    import jax
    from memgraph_tpu.ops import delta as D
    from memgraph_tpu.ops.csr import export_csr, shard_edges
    from memgraph_tpu.parallel.distributed import \
        pagerank_partition_centric
    from memgraph_tpu.parallel.mesh import get_mesh_context
    from memgraph_tpu.storage import InMemoryStorage

    tol = 1e-6
    ctx = get_mesh_context(1)
    rng = np.random.default_rng(seed + 1)

    # real storage at full size (setup, untimed): the cold path below
    # is the PRODUCT's commit-then-CALL — MVCC export walk + CSR build
    # + partition blocking + cold fixpoint — not a synthetic stand-in
    big = InMemoryStorage()
    acc = big.access()
    verts, _ = acc.batch_insert(
        vertices=[((), {}) for _ in range(n_nodes)])
    et_big = big.edge_type_mapper.name_to_id("E")
    B = 500_000
    for lo in range(0, n_edges, B):
        hi = min(lo + B, n_edges)
        a = rng.integers(0, n_nodes, hi - lo)
        b = (rng.random(hi - lo) ** 2 * n_nodes).astype(np.int64)
        acc.batch_insert(edges=[
            (et_big, verts[int(x)], verts[int(y)], None)
            for x, y in zip(a, b)])
    acc.commit()
    log(f"  delta stage: storage built ({n_nodes:,} nodes, "
        f"{n_edges:,} edges)")

    # resident generation at v0 (setup, untimed): export + sharded
    # variant + a converged solution to warm-start from
    acc0 = big.access()
    v0 = acc0.topology_snapshot
    g0 = export_csr(acc0, to_device=False)
    acc0.abort()
    gen = D.ResidentGraph("bench", v0, g0)
    scsr0 = gen.ensure_sharded(ctx, by="src")
    r0, _, it_cold0 = pagerank_partition_centric(scsr0, ctx, tol=tol)
    gen.note_solution("pagerank", ("p",), np.asarray(r0))

    # the ≤1% churn, ONE committed transaction: half removals of
    # existing edges, half fresh adds between existing vertices
    k = max(1, int(n_edges * DELTA_CHURN / 2))
    wacc = big.access()
    edge_gids = list(big._edges.keys())
    for gid in rng.choice(len(edge_gids), k, replace=False):
        ea = wacc.find_edge(edge_gids[int(gid)])
        if ea is not None:
            wacc.delete_edge(ea)
    a = rng.integers(0, n_nodes, k)
    b = (rng.random(k) ** 2 * n_nodes).astype(np.int64)
    wacc.batch_insert(edges=[
        (et_big, verts[int(x)], verts[int(y)], None)
        for x, y in zip(a, b)])
    wacc.commit()
    v1 = big.topology_version

    # COLD commit-then-CALL (timed end to end): the pre-mgdelta path
    t0 = time.perf_counter()
    acc_c = big.access()
    g_c = export_csr(acc_c, to_device=False)
    acc_c.abort()
    scsr_cold = shard_edges(*g_c.host_coo, n_nodes, ctx.n_shards,
                            by="src").to_device(ctx)
    rc_ranks, _, it_cold = pagerank_partition_centric(scsr_cold, ctx,
                                                      tol=tol)
    cold_s = time.perf_counter() - t0

    # DELTA commit-then-CALL (timed end to end): change log -> O(delta)
    # incident read -> diff -> resident splice -> warm-started fixpoint
    t0 = time.perf_counter()
    acc_d = big.access()
    changed = big.changes_between(v0, v1)
    assert isinstance(changed, frozenset), changed
    inc = D.incident_from_storage(acc_d, gen.gid_to_idx, changed)
    changed_idx = [gen.gid_to_idx[g] for g in changed
                   if g in gen.gid_to_idx]
    d = D.diff_incident(gen.coo, changed_idx, inc[0], inc[1], inc[2],
                        gen.n_nodes, v0, v1)
    acc_d.abort()
    t_diff = time.perf_counter() - t0
    applied = gen.apply(d, ctx)
    t_apply = time.perf_counter() - t0 - t_diff
    x0, _ = gen.warm_x0("pagerank", ("p",))
    scsr_new = gen.ensure_sharded(ctx, by="src")
    rw_ranks, _, it_warm = pagerank_partition_centric(
        scsr_new, ctx, tol=tol, x0=x0)
    delta_s = time.perf_counter() - t0
    # freshness contract: same tol, residual-equivalent result
    linf = float(np.abs(np.asarray(rc_ranks)
                        - np.asarray(rw_ranks)).max())
    del big, verts, g_c, g0, scsr_cold

    # part 2: streaming ingest while querying (bulk lane feeding
    # commits while commit-then-CALL pagerank serves fresh results)
    import threading as _threading
    from memgraph_tpu.ops.csr import GLOBAL_GRAPH_CACHE
    st = InMemoryStorage()
    sn, se = 20_000, 80_000
    acc = st.access()
    et = st.edge_type_mapper.name_to_id("E")
    verts, _ = acc.batch_insert(vertices=[((), {}) for _ in range(sn)])
    srng = np.random.default_rng(seed + 2)
    acc.batch_insert(edges=[
        (et, verts[a], verts[b], None)
        for a, b in zip(srng.integers(0, sn, se),
                        srng.integers(0, sn, se))])
    acc.commit()
    pool = D.LocalWarmPool()
    stop = _threading.Event()
    ingested = [0]

    def writer():
        while not stop.is_set():
            w_acc = st.access()
            batch = [(et, verts[int(a)], verts[int(b)], None)
                     for a, b in zip(srng.integers(0, sn, 50),
                                     srng.integers(0, sn, 50))]
            w_acc.batch_insert(edges=batch)
            w_acc.commit()
            ingested[0] += len(batch)
            time.sleep(0.02)

    wt = _threading.Thread(target=writer, daemon=True)
    latencies = []
    warm_iters = []
    t_stream = time.perf_counter()
    wt.start()
    try:
        from memgraph_tpu.ops.pagerank import pagerank as _pr
        while time.perf_counter() - t_stream < 6.0:
            q0 = time.perf_counter()
            q_acc = st.access()
            try:
                g = GLOBAL_GRAPH_CACHE.get(q_acc)
                v = q_acc.topology_snapshot
                cached, x0s = pool.prepare(st, g, v, "pagerank",
                                           ("p",))
                if cached is None:
                    ranks, _, its = _pr(g, tol=1e-5, x0=x0s)
                    pool.store(st, g, v, "pagerank", ("p",),
                               np.asarray(ranks))
            finally:
                q_acc.abort()
            latencies.append(time.perf_counter() - q0)
            if cached is None and x0s is not None:
                warm_iters.append(int(its))
    finally:
        stop.set()
        wt.join(timeout=5)
    stream_s = time.perf_counter() - t_stream
    lat = np.asarray(sorted(latencies))

    np.savez(
        out_path, cold_s=cold_s, delta_s=delta_s, diff_s=t_diff,
        apply_s=t_apply, applied=bool(applied),
        delta_edges=d.n_delta, it_cold=it_cold, it_warm=it_warm,
        it_cold0=it_cold0, linf=linf,
        stream_queries=len(latencies),
        stream_commits_edges=ingested[0],
        stream_seconds=stream_s,
        fresh_latency_p50_ms=float(lat[len(lat) // 2] * 1e3)
        if len(lat) else 0.0,
        fresh_latency_p95_ms=float(lat[int(len(lat) * 0.95)] * 1e3)
        if len(lat) else 0.0,
        warm_queries=len(warm_iters),
        warm_iters_mean=float(np.mean(warm_iters))
        if warm_iters else 0.0,
        platform=jax.devices()[0].platform)


def stage_stream(n_records, batch_size, seed, out_path):
    """mgstream (r17): sustained exactly-once streaming ingestion.

    Host-side (no device): the whole stage measures the transactional
    ingest path — FILE source poll → transform → per-batch transaction
    carrying the WAL OP_STREAM_OFFSET record → consumer ack. Three
    phases:

      A  backlog drain: n_records pre-written JSONL lines through one
         stream -> sustained records/s end-to-end (the headline floor
         BASELINE.json ``stream_ingest`` enforces on every host);
      B  always-fresh reads under live ingest: a producer appends at a
         fixed rate while a reader loop times count() queries against
         the same storage -> fresh-read latency percentiles (reads must
         stay cheap and monotone while the consumer commits);
      C  consumer kill + cold restart mid-ingest: records appended
         while dead must drain after restart with ZERO duplicates (the
         recovered offset dedups) — exactly_once feeds the gate.
    """
    import shutil
    import tempfile
    import threading as _threading

    from memgraph_tpu.query import streams as S
    from memgraph_tpu.query.interpreter import (Interpreter,
                                                InterpreterContext)
    from memgraph_tpu.storage import InMemoryStorage, StorageConfig
    from memgraph_tpu.storage.durability.recovery import (recover,
                                                          wire_durability)
    from memgraph_tpu.storage.kvstore import KVStore

    workdir = tempfile.mkdtemp(prefix="bench-stream-")
    feed = os.path.join(workdir, "feed.jsonl")
    storage = InMemoryStorage(StorageConfig(
        durability_dir=os.path.join(workdir, "data"), wal_enabled=True))
    recover(storage)
    wal = wire_durability(storage)
    ictx = InterpreterContext(storage)
    ictx.kvstore = KVStore(os.path.join(workdir, "kv.db"))
    interp = Interpreter(ictx, system=True)

    def transform(batch):
        return [{"query": "CREATE (:Ev {id: $id})",
                 "parameters": {"id": json.loads(
                     m.payload_str())["id"]}}
                for m in batch]

    S.TRANSFORMATIONS["bench_stream"] = transform

    def count():
        _c, rows, _s = interp.execute("MATCH (e:Ev) RETURN count(e)")
        return rows[0][0]

    def produce(ids):
        with open(feed, "a", encoding="utf-8") as f:
            for i in ids:
                f.write(json.dumps({"id": int(i)}) + "\n")

    def wait_count(target, timeout=120.0):
        deadline = time.time() + timeout
        while time.time() < deadline and count() < target:
            time.sleep(0.02)
        return count() >= target

    try:
        spec = S.StreamSpec(
            name="bench", kind="file", topics=[feed],
            transform="bench_stream", batch_size=batch_size,
            batch_interval_sec=0.02)
        # phase A: drain a pre-written backlog, timed end to end
        produce(range(n_records))
        stream = S.Stream(spec, ictx)
        t0 = time.perf_counter()
        stream.start()
        drained = wait_count(n_records)
        drain_s = time.perf_counter() - t0
        if not drained:
            raise RuntimeError(
                f"backlog never drained: {count()}/{n_records}")

        # phase B: fresh reads while a producer keeps appending
        stop = _threading.Event()
        produced_b = [0]

        def producer():
            i = n_records
            while not stop.is_set():
                produce([i])
                i += 1
                produced_b[0] += 1
                time.sleep(0.005)

        pt = _threading.Thread(target=producer, daemon=True)
        read_lat = []
        last = -1
        monotone = True
        pt.start()
        t_b = time.perf_counter()
        try:
            while time.perf_counter() - t_b < 4.0:
                q0 = time.perf_counter()
                c = count()
                read_lat.append(time.perf_counter() - q0)
                if c < last:
                    monotone = False
                last = c
        finally:
            stop.set()
            pt.join(timeout=5)

        # phase C: kill mid-ingest, append while dead, cold restart
        total_b = n_records + produced_b[0]
        stream.kill()
        produce(range(total_b, total_b + batch_size * 3))
        total = total_b + batch_size * 3
        stream2 = S.Stream(spec, ictx)
        t_r = time.perf_counter()
        stream2.start()
        recovered = wait_count(total)
        recovery_s = time.perf_counter() - t_r
        stream2.stop()
        # exactly-once: every id exactly once, nothing extra
        _c, rows, _s = interp.execute(
            "MATCH (e:Ev) WITH e.id AS i, count(*) AS c "
            "WHERE c > 1 RETURN count(*)")
        dups = rows[0][0]
        exactly_once = recovered and dups == 0 and count() == total

        lat = np.asarray(sorted(read_lat))
        np.savez(
            out_path,
            records_per_sec=n_records / max(drain_s, 1e-9),
            drain_s=drain_s, n_records=n_records,
            batch_size=batch_size,
            fresh_reads=len(read_lat),
            fresh_read_p50_ms=float(lat[len(lat) // 2] * 1e3)
            if len(lat) else 0.0,
            fresh_read_p95_ms=float(lat[int(len(lat) * 0.95)] * 1e3)
            if len(lat) else 0.0,
            reads_monotone=monotone,
            live_ingested=produced_b[0],
            recovery_drain_s=recovery_s,
            duplicates=int(dups), total=total,
            exactly_once=bool(exactly_once),
            wal_offset=int(storage.stream_offsets.get("bench", 0)),
            platform="host")
    finally:
        S.TRANSFORMATIONS.pop("bench_stream", None)
        wal.close()
        shutil.rmtree(workdir, ignore_errors=True)


def stage_latency(out_path):
    """CALL-to-first-record latency through the module/CSR-cache path.

    Cold = a FRESH client process's first CALL on a new graph. With the
    resident kernel server (memgraph_tpu/server/kernel_server.py) the
    client does not pay the per-process runtime start — the daemon holds
    the runtime, the client pays export + one socket round-trip + device
    compute."""
    from memgraph_tpu.storage import InMemoryStorage, StorageConfig, StorageMode
    from memgraph_tpu.ops.csr import GraphCache, export_csr
    from memgraph_tpu.ops.pagerank import pagerank

    storage = InMemoryStorage(StorageConfig(
        storage_mode=StorageMode.IN_MEMORY_ANALYTICAL))
    rng = np.random.default_rng(3)
    n, e = 20_000, 100_000
    acc = storage.access()
    et = storage.edge_type_mapper.name_to_id("E")
    vs = [acc.create_vertex() for _ in range(n)]
    for s, d in zip(rng.integers(0, n, e), rng.integers(0, n, e)):
        acc.create_edge(vs[s], vs[d], et)
    acc.commit()

    resident = False
    client = None
    try:
        from memgraph_tpu.server.kernel_server import ensure_server, \
            KernelClient
    except Exception:  # noqa: BLE001 — environmental -> quiet fallback
        ensure_server = None
    if ensure_server is not None:
        # reuse the resident daemon when it is already up; one retry on
        # failure — a transient spawn race must not demote the whole
        # latency stage to the non-resident fallback. Timing rides the
        # shared RetryPolicy (no ad-hoc sleep constants).
        from memgraph_tpu.utils.retry import RetryPolicy
        for attempt in RetryPolicy(base_delay=2.0, factor=1.0,
                                   jitter=0.0, max_retries=1).attempts():
            try:
                client = ensure_server()
                break
            except RuntimeError as e:
                # daemon died during init: a real regression — say so
                # loudly (the bench still falls back so a number is
                # always produced)
                log(f"  RESIDENT KERNEL SERVER DIED DURING INIT "
                    f"(attempt {attempt + 1}): {e}")
            except Exception as e:  # noqa: BLE001 — environmental
                log(f"  resident kernel server unavailable "
                    f"(attempt {attempt + 1}): {e}")
    if client is not None:
        # steady-state server: shape-bucket kernels already compiled
        # (a production daemon has served before); measure a NEW graph
        wsrc = rng.integers(0, n, e)
        wdst = rng.integers(0, n, e)
        client.pagerank(src=wsrc, dst=wdst, n_nodes=n, graph_key="warmup",
                        max_iterations=100, tol=1e-6)
        sock = client.socket_path
        client.close()

        acc2 = storage.access()
        t0 = time.perf_counter()
        c2 = KernelClient(sock)                      # fresh client
        g = export_csr(acc2, to_device=False)        # host-side export
        ranks, _, _ = c2.pagerank(
            src=g.host_coo[0], dst=g.host_coo[1], n_nodes=g.n_nodes,
            graph_key="bench", max_iterations=100, tol=1e-6)
        _ = (int(g.node_gids[0]), float(ranks[0]))
        cold = time.perf_counter() - t0
        t0 = time.perf_counter()
        ranks, _, _ = c2.pagerank(graph_key="bench",
                                  max_iterations=100, tol=1e-6)
        _ = float(ranks[0])
        warm = time.perf_counter() - t0
        c2.close()
        acc2.abort()
        resident = True
    else:
        cache = GraphCache()
        acc = storage.access()
        t0 = time.perf_counter()
        g = cache.get(acc)
        ranks, _, _ = pagerank(g, max_iterations=100, tol=1e-6)
        _ = (int(g.node_gids[0]), float(ranks[0]))
        cold = time.perf_counter() - t0
        t0 = time.perf_counter()
        g = cache.get(acc)
        ranks, _, _ = pagerank(g, max_iterations=100, tol=1e-6)
        _ = float(ranks[0])
        warm = time.perf_counter() - t0
        acc.abort()
    np.savez(out_path, cold=cold, warm=warm, resident=resident)


# --------------------------------------------------------------------------
# orchestrator
# --------------------------------------------------------------------------

# the stage subprocess currently in flight, so the watchdog can kill it
# before emitting (an orphan would keep holding the chip)
_CURRENT_CHILD = None


def _emit_and_exit():
    """Print the record and exit: 0 only when the headline came from the
    chip at full size; a record without a device measurement still says
    why, but the exit code is 1."""
    child = _CURRENT_CHILD
    if child is not None and child.poll() is None:
        try:
            child.kill()
        except OSError:
            pass
    print(json.dumps(PARTIAL))
    sys.stdout.flush()
    os._exit(1 if PARTIAL["backend"] == "none" else 0)


def _arm_watchdog(seconds=MASTER_TIMEOUT_SEC):
    import signal

    def on_alarm(signum, frame):
        PARTIAL["extra"].setdefault(
            "error", "bench watchdog fired (partial result)")
        PARTIAL["extra"]["watchdog_fired_after_s"] = seconds
        _emit_and_exit()

    signal.signal(signal.SIGALRM, on_alarm)
    signal.alarm(seconds)


def _run_stage(args, env, timeout):
    """Run this script as a subprocess stage. Returns (rc, stdout) or
    (None, None) on timeout (the child is killed)."""
    global _CURRENT_CHILD
    cmd = [sys.executable, os.path.abspath(__file__)] + args
    p = subprocess.Popen(cmd, env=env, stdout=subprocess.PIPE,
                         stderr=sys.stderr)
    _CURRENT_CHILD = p
    try:
        out, _ = p.communicate(timeout=timeout)
        return p.returncode, out
    except subprocess.TimeoutExpired:
        p.kill()
        try:
            p.communicate(timeout=10)
        except subprocess.TimeoutExpired:
            pass
        return None, None
    finally:
        _CURRENT_CHILD = None


def _stage_env(platform=None):
    env = dict(os.environ)
    if platform is not None:
        env["JAX_PLATFORMS"] = platform
    return env


def main():
    _arm_watchdog()
    t_bench = time.perf_counter()

    log(f"generating {N_EDGES:,}-edge graph ...")
    src, dst = generate_graph()

    log("CPU baseline (scipy CSR power iteration) ...")
    cpu_ranks, cpu_time = cpu_pagerank(src, dst, N_NODES)
    cpu_eps = N_EDGES * ITERATIONS / cpu_time
    log(f"  {cpu_time:.3f}s -> {cpu_eps:,.0f} edges/s")
    PARTIAL["extra"] = {"cpu_seconds_50iter": round(cpu_time, 4),
                        "error": "device stages did not complete"}

    log("probing device (subprocess) ...")
    t_probe = time.perf_counter()
    device_ok = False
    probe_server_health = None
    probe_outcome = "probe_never_ran"
    for attempt in range(2):
        rc, out = _run_stage(["--stage", "probe"], _stage_env("tpu"),
                             PROBE_TIMEOUT_SEC)
        device_ok = rc == 0
        probe_outcome = _classify_probe(rc)
        log(f"  probe attempt {attempt + 1}: rc={rc} ok={device_ok} "
            f"{(out or b'').decode(errors='replace').strip()}")
        if device_ok:
            break
        # one flaky probe must not cost the run: a single retry after
        # a short pause is cheap insurance
        time.sleep(3)
    if not device_ok:
        # second opinion from the resident kernel server's health plane:
        # the daemon holds a live device runtime, so its typed probe is
        # authoritative — a flaky subprocess probe must not fail a run
        # while the resident daemon demonstrably holds a working chip
        health, probe_reply = _resident_probe()
        if health is None:
            probe_outcome += "+no_resident_server"
        elif health.get("wedged"):
            probe_outcome += "+resident_server_wedged"
        elif probe_reply is None:
            probe_outcome += "+resident_probe_unanswered"
        elif probe_reply.get("ok") \
                and probe_reply.get("platform") == "tpu":
            device_ok = True
            probe_outcome += "+resident_probe_ok"
            log("  subprocess probe failed but the RESIDENT kernel "
                "server's device probe completed — using the device "
                f"ladder (platform={probe_reply.get('platform')})")
        else:
            probe_outcome += \
                f"+resident_probe_{probe_reply.get('outcome', 'failed')}"
        if health is not None:
            probe_server_health = {
                "wedged": bool(health.get("wedged")),
                "in_flight": health.get("in_flight"),
                "uptime_s": health.get("uptime_s"),
                "platform": health.get("platform"),
            }
            PARTIAL["extra"]["probe_server_health"] = probe_server_health
    PARTIAL["extra"]["probe_outcome"] = probe_outcome
    probe_s = time.perf_counter() - t_probe

    if not device_ok:
        PARTIAL["extra"]["error"] = (
            f"no TPU ({probe_outcome}); cpu baseline only")
        _emit_and_exit()

    # ladder: the chip at full size (MXU kernel, then segment kernel),
    # then at 1M edges; every rung asks for the TPU — there is no CPU rung
    ladder = [
        ("pagerank_mxu", N_NODES, N_EDGES, STAGE_TIMEOUT_SEC),
        ("pagerank", N_NODES, N_EDGES, STAGE_TIMEOUT_SEC),
        ("pagerank", N_NODES // 10, N_EDGES // 10, 120),
    ]

    result = None
    for stage, n_nodes, n_edges, budget in ladder:
        remaining = MASTER_TIMEOUT_SEC - (time.perf_counter() - t_bench) - 15
        if remaining < 35:
            log("  out of time budget; stopping the ladder")
            break
        budget = min(budget, int(remaining))
        log(f"{stage} stage: edges={n_edges:,} budget={budget}s ...")
        with tempfile.NamedTemporaryFile(suffix=".npz") as tf:
            rc, _ = _run_stage(
                ["--stage", stage, str(n_nodes), str(n_edges), "7",
                 tf.name], _stage_env("tpu"), budget)
            if rc != 0:
                log(f"  stage failed (rc={rc}); next rung")
                continue
            data = np.load(tf.name)
            result = {
                "platform": str(data["platform"]), "kernel": stage,
                "n_nodes": n_nodes, "n_edges": n_edges,
                "ranks": data["ranks"], "elapsed": float(data["elapsed"]),
                "export_s": float(data["export_s"]),
            }
            for key in ("plan_build_s", "plan_cached", "warm_s",
                        "plan_build_fresh_s", "plan_delta_build_s",
                        "build_s", "transfer_s"):
                if key in data.files:
                    result[key] = float(data[key])
            if "mgstat_stages" in data.files:
                try:
                    result["mgstat_stages"] = json.loads(
                        str(data["mgstat_stages"]))
                except (ValueError, TypeError):
                    pass
        break

    if result is None:
        PARTIAL["extra"]["error"] = ("all device stages failed/timed out; "
                                     "cpu baseline only")
        _emit_and_exit()

    eps = result["n_edges"] * ITERATIONS / result["elapsed"]
    log(f"  {result['elapsed']:.3f}s for {ITERATIONS} iterations "
        f"-> {eps:,.0f} edges/s on {result['platform']}")

    # acceptance: top-100 rank parity vs scipy on the same graph
    if result["n_edges"] == N_EDGES:
        base_ranks = cpu_ranks
        base_eps = cpu_eps
    else:  # fallback size: recompute baseline at that size for parity
        s2, d2 = generate_graph(result["n_nodes"], result["n_edges"], 7)
        base_ranks, base_time = cpu_pagerank(s2, d2, result["n_nodes"])
        base_eps = result["n_edges"] * ITERATIONS / base_time
    top_dev = set(np.argsort(-result["ranks"])[:100].tolist())
    top_cpu = set(np.argsort(-base_ranks)[:100].tolist())
    overlap = len(top_dev & top_cpu)
    log(f"top-100 overlap: {overlap}/100")

    # honesty contract (ROADMAP open item 5): the headline is only
    # non-degraded when it came from the real accelerator at full size.
    # A shrunken graph still yields a number, but one every consumer
    # (and tools/perf_gate.py) can see is not comparable.
    degraded = (result["platform"] != "tpu"
                or result["n_edges"] < N_EDGES)
    if degraded:
        log(f"  DEGRADED RUN: backend={result['platform']} "
            f"edges={result['n_edges']:,} — not a headline measurement")
    PARTIAL.update({
        "value": round(eps, 1),
        "vs_baseline": round(eps / base_eps, 3),
        "degraded": degraded,
        "backend": result["platform"],
    })
    PARTIAL["extra"] = {
        "device_platform": result["platform"],
        "kernel": result["kernel"],
        "bench_edges": result["n_edges"],
        "device_seconds_50iter": round(result["elapsed"], 4),
        "cpu_seconds_50iter": round(cpu_time, 4),
        "csr_export_transfer_s": round(result["export_s"], 2),
        "top100_overlap": overlap,
        "device_probe_ok": device_ok,
        # typed probe failure reason (ISSUE 7): a degraded record now
        # says WHY the device path was not used
        "probe_outcome": probe_outcome,
        # per-stage timings: where the wall clock actually went
        "stages": {
            "probe_s": round(probe_s, 2),
            "baseline_s": round(cpu_time, 2),
            "build_s": round(result.get("build_s", 0.0), 2),
            "transfer_s": round(result.get("transfer_s", 0.0), 2),
            "compile_warm_s": round(result.get("warm_s", 0.0), 2),
            "iterate_s": round(result["elapsed"], 4),
            # mgstat device attribution, measured by the product's own
            # stage hooks (the same numbers PROFILE shows): per stage
            # {"seconds", "count"} over the whole warm+timed extent
            "mgstat": result.get("mgstat_stages"),
        },
    }
    if probe_server_health is not None:
        PARTIAL["extra"]["probe_server_health"] = probe_server_health
    if "plan_build_s" in result:
        PARTIAL["extra"]["plan_build_s"] = round(result["plan_build_s"], 2)
        PARTIAL["extra"]["plan_cached"] = bool(result["plan_cached"])
        PARTIAL["extra"]["compile_warm_s"] = round(result["warm_s"], 2)
    for key in ("plan_build_fresh_s", "plan_delta_build_s"):
        if key in result:
            PARTIAL["extra"][key] = round(result[key], 2)

    # bulk-write fast lane: storage-level batch_insert throughput (r6).
    # Best-effort and cheap; the OLTP-grade end-to-end number lives in
    # benchmarks/mgbench.py (OLTP_r06.json load_records_per_sec).
    try:
        from memgraph_tpu.storage import InMemoryStorage as _IMS
        _st = _IMS()
        _lid = _st.label_mapper.name_to_id("U")
        _pid = _st.property_mapper.name_to_id("id")
        _t0 = time.perf_counter()
        _total = 0
        while time.perf_counter() - _t0 < 2.0:
            _acc = _st.access()
            _acc.batch_insert(vertices=[
                ((_lid,), {_pid: _total + i}) for i in range(10_000)])
            _acc.commit()
            _total += 10_000
        _rate = _total / (time.perf_counter() - _t0)
        PARTIAL["extra"]["bulk_insert_vertices_per_s"] = round(_rate, 1)
        log(f"bulk ingest (batch_insert): {_rate:,.0f} vertices/s")
    except Exception as _e:  # noqa: BLE001 — never block the north star
        log(f"bulk ingest stage skipped: {_e}")

    # semiring-core sweep (r10): pagerank via the core at f32/bf16 + BFS
    # via min-plus, honest per-sweep backend/degraded tagging; the perf
    # gate reads extra.semiring against the BASELINE.json ratio envelopes
    sem_nodes, sem_edges = N_NODES // 10, N_EDGES // 10
    remaining = MASTER_TIMEOUT_SEC - (time.perf_counter() - t_bench) - 10
    if remaining > 60:
        with tempfile.NamedTemporaryFile(suffix=".npz") as tf:
            rc, _ = _run_stage(
                ["--stage", "semiring", str(sem_nodes), str(sem_edges),
                 "7", tf.name],
                _stage_env("tpu"),
                min(150, int(remaining)))
            if rc == 0:
                d = np.load(tf.name)
                f32_s = float(d["f32_s"])
                bf16_s = float(d["bf16_s"])
                sem_platform = str(d["platform"])
                PARTIAL["extra"]["semiring"] = {
                    "backend": sem_platform,
                    # the sweep's OWN honesty tag: a CPU run can never
                    # satisfy the on-device ratio envelopes
                    "degraded": sem_platform == "cpu",
                    "bench_edges": sem_edges,
                    "iterations": SEMIRING_ITERATIONS,
                    "f32_eps": round(
                        sem_edges * SEMIRING_ITERATIONS / f32_s, 1),
                    "bf16_eps": round(
                        sem_edges * SEMIRING_ITERATIONS / bf16_s, 1),
                    "bf16_speedup": round(f32_s / bf16_s, 3),
                    "bfs_minplus_s": round(float(d["bfs_s"]), 4),
                    "top100_overlap_f32_bf16": int(d["overlap"]),
                    "resident_kernel_server": bool(d["resident"]),
                }
                log(f"semiring sweep: f32 {f32_s:.3f}s bf16 {bf16_s:.3f}s "
                    f"(speedup {f32_s / bf16_s:.2f}x) on {sem_platform}")
            else:
                log(f"semiring sweep stage failed (rc={rc}); record "
                    "carries no extra.semiring")

    # mgdelta (r19): commit-to-fresh-result speedup + the
    # streaming-ingest-while-querying stage; feeds the BASELINE.json
    # delta_speedup envelope (perf_gate.check_delta). Honest per-stage
    # backend/degraded tagging like the semiring sweep.
    delta_nodes = int(os.environ.get("BENCH_DELTA_N_NODES", N_NODES))
    delta_edges = int(os.environ.get("BENCH_DELTA_N_EDGES", 3_000_000))
    remaining = MASTER_TIMEOUT_SEC - (time.perf_counter() - t_bench) - 10
    # the stage builds a REAL 1M-node storage through the bulk lane
    # (~90s) before it measures anything — with less than ~6 minutes
    # left it cannot finish, so skip LOUDLY instead of burning the
    # remaining budget on a record-less timeout (raise
    # BENCH_MASTER_TIMEOUT to include it in a default run)
    if remaining > 360:
        with tempfile.NamedTemporaryFile(suffix=".npz") as tf:
            rc, _ = _run_stage(
                ["--stage", "delta", str(delta_nodes),
                 str(delta_edges), "7", tf.name],
                _stage_env("tpu"),
                min(420, int(remaining)))
            if rc == 0:
                d = np.load(tf.name)
                delta_platform = str(d["platform"])
                cold_s = float(d["cold_s"])
                delta_s = float(d["delta_s"])
                PARTIAL["extra"]["delta"] = {
                    "backend": delta_platform,
                    # own honesty tag, same contract as the semiring
                    # sweep: a CPU run can never satisfy the on-device
                    # delta_speedup envelope
                    "degraded": delta_platform == "cpu",
                    "n_nodes": delta_nodes,
                    "n_edges": delta_edges,
                    "churn": DELTA_CHURN,
                    "cold_rebuild_s": round(cold_s, 4),
                    "delta_refresh_s": round(delta_s, 4),
                    "delta_speedup": round(cold_s / max(delta_s, 1e-9),
                                           3),
                    "diff_s": round(float(d["diff_s"]), 4),
                    "apply_s": round(float(d["apply_s"]), 4),
                    "delta_edges": int(d["delta_edges"]),
                    "iters_cold": int(d["it_cold"]),
                    "iters_warm": int(d["it_warm"]),
                    "residual_linf": float(d["linf"]),
                    "streaming": {
                        "queries": int(d["stream_queries"]),
                        "ingested_edges": int(d["stream_commits_edges"]),
                        "seconds": round(float(d["stream_seconds"]), 2),
                        "fresh_latency_p50_ms": round(
                            float(d["fresh_latency_p50_ms"]), 2),
                        "fresh_latency_p95_ms": round(
                            float(d["fresh_latency_p95_ms"]), 2),
                        "warm_queries": int(d["warm_queries"]),
                        "warm_iters_mean": round(
                            float(d["warm_iters_mean"]), 2),
                    },
                }
                log(f"delta stage: cold {cold_s:.3f}s vs delta "
                    f"{delta_s:.3f}s (speedup "
                    f"{cold_s / max(delta_s, 1e-9):.2f}x) on "
                    f"{delta_platform}; streaming "
                    f"{int(d['stream_queries'])} fresh queries over "
                    f"{int(d['stream_commits_edges'])} ingested edges")
            else:
                log(f"delta stage failed (rc={rc}); record carries "
                    "no extra.delta")
    else:
        log(f"delta stage SKIPPED ({remaining:.0f}s left < 360s it "
            "needs); record carries no extra.delta")

    # mgtier (r21): out-of-core streamed edge blocks — the
    # double-buffered H2D-vs-SpMV overlap fraction plus the wire
    # compression ratios; feeds the BASELINE.json tier_overlap
    # envelope (perf_gate.check_tier)
    tier_nodes = int(os.environ.get("BENCH_TIER_N_NODES", N_NODES // 10))
    tier_edges = int(os.environ.get("BENCH_TIER_N_EDGES", N_EDGES // 10))
    remaining = MASTER_TIMEOUT_SEC - (time.perf_counter() - t_bench) - 10
    if remaining > 75:
        with tempfile.NamedTemporaryFile(suffix=".npz") as tf:
            rc, _ = _run_stage(
                ["--stage", "tier", str(tier_nodes), str(tier_edges),
                 "7", tf.name], _stage_env("tpu"),
                min(180, int(remaining)))
            if rc == 0:
                d = np.load(tf.name)
                tier_platform = str(d["platform"])
                hidden = float(d["hidden"])
                PARTIAL["extra"]["tier"] = {
                    "backend": tier_platform,
                    # own honesty tag, same contract as the semiring /
                    # delta sweeps: a CPU host has no real H2D lane —
                    # its "overlap" is host-memcpy arithmetic and can
                    # never satisfy the on-device envelope
                    "degraded": tier_platform == "cpu",
                    "n_nodes": tier_nodes,
                    "n_edges": tier_edges,
                    "n_blocks": int(d["n_blocks"]),
                    "iterations": int(d["iters"]),
                    "streamed_s": round(float(d["elapsed"]), 4),
                    "eps": round(tier_edges * int(d["iters"])
                                 / max(float(d["elapsed"]), 1e-9), 1),
                    "serial_transfer_s": round(
                        float(d["serial_transfer_s"]), 4),
                    "serial_compute_s": round(
                        float(d["serial_compute_s"]), 4),
                    "overlap_iter_s_mean": round(
                        float(d["overlap_iter_s"]), 4),
                    "transfer_hidden_fraction": round(hidden, 4),
                    "wire_bytes_per_sweep": int(d["wire_bytes"]),
                    "raw_bytes_per_sweep": int(d["raw_bytes"]),
                    "wire_ratio_bf16": round(float(d["ratio_bf16"]), 3),
                    "wire_ratio_int8": round(float(d["ratio_int8"]), 3),
                }
                log(f"tier stage: {int(d['n_blocks'])} blocks, "
                    f"{hidden:.0%} of transfer hidden, wire bf16 "
                    f"{float(d['ratio_bf16']):.2f}x / int8 "
                    f"{float(d['ratio_int8']):.2f}x on {tier_platform}")
            else:
                log(f"tier stage failed (rc={rc}); record carries no "
                    "extra.tier")
    else:
        log(f"tier stage SKIPPED ({remaining:.0f}s left < 75s it "
            "needs); record carries no extra.tier")

    # mgstream (r17): sustained streaming ingestion — the supervised
    # FILE-stream consumer drains a pre-written backlog, serves fresh
    # reads under live ingest, then survives a mid-stream kill with
    # zero duplicates; feeds the BASELINE.json stream_ingest envelope
    # (perf_gate.check_stream). Host-side by construction (the plane is
    # the Cypher/WAL path, not a kernel) so it runs on every box.
    stream_records = int(os.environ.get("BENCH_STREAM_RECORDS", 2000))
    remaining = MASTER_TIMEOUT_SEC - (time.perf_counter() - t_bench) - 10
    if remaining > 40:
        with tempfile.NamedTemporaryFile(suffix=".npz") as tf:
            rc, _ = _run_stage(
                ["--stage", "stream", str(stream_records), "64", "7",
                 tf.name], _stage_env("cpu"), min(120, int(remaining)))
            if rc == 0:
                d = np.load(tf.name)
                PARTIAL["extra"]["stream_ingest"] = {
                    "backend": "host",
                    "n_records": int(d["n_records"]),
                    "batch_size": int(d["batch_size"]),
                    "records_per_sec": round(
                        float(d["records_per_sec"]), 1),
                    "drain_s": round(float(d["drain_s"]), 4),
                    "fresh_reads": int(d["fresh_reads"]),
                    "fresh_read_p50_ms": round(
                        float(d["fresh_read_p50_ms"]), 3),
                    "fresh_read_p95_ms": round(
                        float(d["fresh_read_p95_ms"]), 3),
                    "reads_monotone": bool(d["reads_monotone"]),
                    "live_ingested": int(d["live_ingested"]),
                    "recovery_drain_s": round(
                        float(d["recovery_drain_s"]), 4),
                    "duplicates": int(d["duplicates"]),
                    "total_ingested": int(d["total"]),
                    "exactly_once": bool(d["exactly_once"]),
                    "wal_offset": int(d["wal_offset"]),
                }
                log(f"stream stage: {float(d['records_per_sec']):.0f} "
                    f"records/s sustained, fresh-read p95 "
                    f"{float(d['fresh_read_p95_ms']):.2f}ms, kill+"
                    f"restart exactly_once={bool(d['exactly_once'])} "
                    f"({int(d['duplicates'])} dups)")
            else:
                log(f"stream stage failed (rc={rc}); record carries "
                    "no extra.stream_ingest")
    else:
        log(f"stream stage SKIPPED ({remaining:.0f}s left < 40s it "
            "needs); record carries no extra.stream_ingest")

    # CALL-to-first-record latency (best-effort; never blocks the result)
    remaining = MASTER_TIMEOUT_SEC - (time.perf_counter() - t_bench) - 10
    if remaining > 45:
        with tempfile.NamedTemporaryFile(suffix=".npz") as tf:
            rc, _ = _run_stage(
                ["--stage", "latency", tf.name],
                _stage_env("tpu"),
                min(120, int(remaining)))
            if rc == 0:
                data = np.load(tf.name)
                PARTIAL["extra"]["call_to_first_record_cold_ms"] = round(
                    float(data["cold"]) * 1e3, 1)
                PARTIAL["extra"]["call_to_first_record_warm_ms"] = round(
                    float(data["warm"]) * 1e3, 1)
                if "resident" in data.files:
                    PARTIAL["extra"]["resident_kernel_server"] = bool(
                        data["resident"])

    _emit_and_exit()


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "--stage":
        stage = sys.argv[2]
        if stage == "probe":
            stage_probe()
        elif stage == "pagerank":
            stage_pagerank(int(sys.argv[3]), int(sys.argv[4]),
                           int(sys.argv[5]), sys.argv[6])
        elif stage == "pagerank_mxu":
            stage_pagerank_mxu(int(sys.argv[3]), int(sys.argv[4]),
                               int(sys.argv[5]), sys.argv[6])
        elif stage == "semiring":
            stage_semiring(int(sys.argv[3]), int(sys.argv[4]),
                           int(sys.argv[5]), sys.argv[6])
        elif stage == "delta":
            stage_delta(int(sys.argv[3]), int(sys.argv[4]),
                        int(sys.argv[5]), sys.argv[6])
        elif stage == "tier":
            stage_tier(int(sys.argv[3]), int(sys.argv[4]),
                       int(sys.argv[5]), sys.argv[6])
        elif stage == "stream":
            stage_stream(int(sys.argv[3]), int(sys.argv[4]),
                         int(sys.argv[5]), sys.argv[6])
        elif stage == "latency":
            stage_latency(sys.argv[3])
        else:
            raise SystemExit(f"unknown stage {stage}")
    else:
        main()
