"""Gate smoke for the mgtier out-of-core streamed tier (r21): spawn
the kernel server under an HBM budget the graph's RESIDENT estimate
exceeds (but the streamed working set fits), assert the admission
guard flips the request onto the streamed path automatically, and that
the streamed result is bit-identical to the resident comparator (same
kernels, same fold order) and matches the in-process reference. Then:
WCC rides the streamed path too (partition-equivalent labels), a
non-streamable algorithm against the same oversized graph sheds with
the typed non-retryable verdict instead of lying, and the compressed
wire formats actually compress (bf16/int8 >= 1.8x vs raw COO bytes).

Sized for the dev gate (~seconds, CPU-safe): this proves out-of-core
execution WORKS on every host; it measures no overlap or throughput.

Usage: python -m tools.tier_smoke
"""

from __future__ import annotations

import os
import sys
import tempfile
import threading
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# small per-buffer budget so the smoke graph splits into real blocks
os.environ.setdefault("MEMGRAPH_TPU_TIER_BLOCK_BYTES", str(1 << 15))

import numpy as np  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

N, E = 2000, 16000
#: fits the streamed working set (~130 KiB) but NOT the resident
#: estimate (~830 KiB): admission must pick "streamed", not "shed"
BUDGET = 300_000


def log(msg: str) -> None:
    print(f"tier-smoke: {msg}", flush=True)


def fail(msg: str) -> int:
    log(f"FAIL: {msg}")
    return 1


def _metric(name):
    from memgraph_tpu.observability.metrics import global_metrics
    return dict((n, v) for n, _k, v in global_metrics.snapshot()).get(
        name, 0.0)


def _same_partition(a, b) -> bool:
    pairs = set(zip(a.tolist(), b.tolist()))
    return len(pairs) == len(set(a.tolist())) == len(set(b.tolist()))


def main() -> int:
    from memgraph_tpu.ops import tier as mgtier
    from memgraph_tpu.ops.components import weakly_connected_components
    from memgraph_tpu.ops.csr import from_coo
    from memgraph_tpu.ops.pagerank import pagerank
    from memgraph_tpu.parallel.distributed import pagerank_streamed
    from memgraph_tpu.server.kernel_server import (AdmissionRejected,
                                                   KernelClient,
                                                   KernelServer)

    sock = os.path.join(tempfile.mkdtemp(prefix="tiersmoke"), "ks.sock")
    srv = KernelServer(sock, wedge_after_s=60,
                       hbm_budget_bytes=BUDGET)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    client = None
    deadline = time.monotonic() + 120
    while time.monotonic() < deadline:
        try:
            client = KernelClient(sock, timeout=120)
            break
        except OSError:
            time.sleep(0.05)
    if client is None:
        return fail("kernel server never came up")

    rng = np.random.default_rng(21)
    src = rng.integers(0, N, E)
    dst = rng.integers(0, N, E)
    w = (rng.random(E) + 0.1).astype(np.float32)
    tol = 1e-8

    # 1. oversized pagerank: admission flips it onto the streamed path
    streamed0 = _metric("tier.admission_streamed_total")
    h, out = client.semiring(algorithm="pagerank", src=src, dst=dst,
                             weights=w, n_nodes=N, graph_key="smoke",
                             graph_version=1, tol=tol)
    if h.get("tier") != "streamed":
        return fail(f"oversized pagerank was not streamed "
                    f"(tier={h.get('tier')!r}, budget {BUDGET})")
    if _metric("tier.admission_streamed_total") <= streamed0:
        return fail("streamed verdict was not counted")
    if _metric("tier.blocks_streamed_total") <= 0:
        return fail("no edge blocks actually streamed")
    ranks = np.asarray(out["ranks"])[:N]

    # 2. bit-identical to the resident comparator (same kernels, same
    #    fold order, whole graph pre-placed) and close to the classic
    #    segment-backend reference
    t = mgtier.tier_from_scsr(
        __import__("memgraph_tpu.ops.csr", fromlist=["shard_edges"])
        .shard_edges(src.astype(np.int64), dst.astype(np.int64), w,
                     N, mgtier.plan_blocks(N, E, "f32",
                                           mgtier.block_bytes_budget()),
                     by="src"))
    res, _err, _it = pagerank_streamed(t, tol=tol, resident=True)
    if not np.array_equal(ranks, res):
        gap = float(np.abs(ranks - res).max())
        return fail(f"streamed != resident comparator (Linf {gap:.2e})")
    ref, _, _ = pagerank(from_coo(src, dst, weights=w, n_nodes=N),
                         tol=tol)
    gap = float(np.abs(np.asarray(ref)[:N] - ranks).max())
    if gap > 1e-5:
        return fail(f"streamed result diverges from reference "
                    f"(Linf {gap})")
    log(f"pagerank streamed: bit-identical to resident comparator, "
        f"Linf {gap:.2e} vs segment reference")

    # 3. WCC rides the streamed path too
    h2, out2 = client.semiring(algorithm="wcc", graph_key="smoke",
                               n_nodes=N, graph_version=1)
    if h2.get("tier") != "streamed":
        return fail(f"oversized WCC was not streamed "
                    f"(tier={h2.get('tier')!r})")
    ref_c, _ = weakly_connected_components(from_coo(src, dst, n_nodes=N))
    if not _same_partition(np.asarray(ref_c)[:N],
                           np.asarray(out2["components"])[:N]):
        return fail("streamed WCC labels are not partition-equivalent "
                    "to the reference")
    log("WCC streamed: partition-equivalent to reference")

    # 4. a non-streamable algorithm against the same oversized graph
    #    must SHED (typed, non-retryable) — never silently go resident
    shed0 = _metric("tier.admission_shed_total")
    try:
        client.semiring(algorithm="labelprop", graph_key="smoke",
                        n_nodes=N, graph_version=1)
        return fail("non-streamable oversized labelprop was admitted")
    except AdmissionRejected as e:
        if e.retryable:
            return fail("shed verdict claims to be retryable")
    if _metric("tier.admission_shed_total") <= shed0:
        return fail("shed verdict was not counted")
    log("non-streamable labelprop shed with the typed verdict")

    # 5. the wire actually compresses: bf16/int8 blocks vs raw COO
    for precision, floor in (("bf16", 1.8), ("int8", 1.8)):
        tp = mgtier.plan_tier(src.astype(np.int64), dst.astype(np.int64),
                              w, N, precision=precision)
        ratio = (sum(b.raw_nbytes for b in tp.blocks)
                 / sum(b.nbytes for b in tp.blocks))
        if ratio < floor:
            return fail(f"{precision} wire ratio {ratio:.2f} "
                        f"< {floor}")
        log(f"{precision} wire compression {ratio:.2f}x vs raw COO")

    try:
        client.shutdown()
        client.close()
    except OSError:
        pass
    log("OK: out-of-core tier end-to-end (auto-streamed admission, "
        "bit-exact vs resident, typed shed, compressed wire)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
