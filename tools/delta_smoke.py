"""Gate smoke for the mgdelta incremental-analytics plane (r19): spawn
the kernel server, import a graph at v1, ship a delta-only request at
v2 (changed indices + incident edges, NO full edge arrays), assert the
resident generation refreshed O(delta) and the reply matches a cold
reference; then assert the warm-start contracts — pagerank warm on
repeat, WCC warm on an adds-only delta, the LOUD typed cold after a
removal — and the change-log-wrap typed fallback.

Sized for the dev gate (~seconds, CPU-safe): this proves the delta
plane WORKS on every host; it measures no speed.

Usage: python -m tools.delta_smoke
"""

from __future__ import annotations

import os
import sys
import tempfile
import threading
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

N, E = 600, 4000


def log(msg: str) -> None:
    print(f"delta-smoke: {msg}", flush=True)


def fail(msg: str) -> int:
    log(f"FAIL: {msg}")
    return 1


def _metric(name):
    from memgraph_tpu.observability.metrics import global_metrics
    return dict((n, v) for n, _k, v in global_metrics.snapshot()).get(
        name, 0.0)


def _incident(src, dst, changed, n):
    bitmap = np.zeros(n, dtype=bool)
    bitmap[np.asarray(changed, dtype=np.int64)] = True
    sel = bitmap[src] | bitmap[dst]
    return (src[sel].astype(np.int64), dst[sel].astype(np.int64),
            np.ones(int(sel.sum()), dtype=np.float32))


def main() -> int:
    from memgraph_tpu.ops.components import weakly_connected_components
    from memgraph_tpu.ops.csr import from_coo
    from memgraph_tpu.parallel.analytics import pagerank_mesh
    from memgraph_tpu.parallel.mesh import get_mesh_context
    from memgraph_tpu.server.kernel_server import (KernelClient,
                                                   KernelServer)
    from memgraph_tpu.storage.storage import (ChangeLogUnknowable,
                                              InMemoryStorage)

    sock = os.path.join(tempfile.mkdtemp(prefix="deltasmoke"), "ks.sock")
    srv = KernelServer(sock, wedge_after_s=60)
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

    rng = np.random.default_rng(0)
    src = rng.integers(0, N, E)
    dst = rng.integers(0, N, E)
    tol = 1e-6
    client.pagerank(src=src, dst=dst, n_nodes=N, graph_key="smoke",
                    graph_version=1, tol=tol)
    log("v1 imported + cold pagerank served")

    # commit: ship ONLY the delta payload at v2
    add_src = rng.integers(0, N, 12)
    add_dst = rng.integers(0, N, 12)
    src2 = np.concatenate([src, add_src])
    dst2 = np.concatenate([dst, add_dst])
    changed = np.unique(np.concatenate([add_src,
                                        add_dst])).astype(np.int32)
    inc_src, inc_dst, inc_w = _incident(src2, dst2, changed, N)
    applied0 = _metric("delta.applied_total")
    ranks, err, iters = client.pagerank(
        n_nodes=N, graph_key="smoke", graph_version=2, base_version=1,
        changed=changed, inc_src=inc_src, inc_dst=inc_dst, inc_w=inc_w,
        tol=tol)
    if _metric("delta.applied_total") <= applied0:
        return fail("delta request did not ride the O(delta) apply")
    if err > tol:
        return fail(f"warm reply err {err} above tol {tol}")
    ref, _, it_ref = pagerank_mesh(from_coo(src2, dst2, n_nodes=N),
                                   get_mesh_context(1), tol=tol)
    gap = float(np.abs(np.asarray(ref) - np.asarray(ranks)[:N]).max())
    if gap > 10 * tol:
        return fail(f"delta-refreshed result diverges from cold "
                    f"reference (Linf {gap})")
    if iters > it_ref:
        return fail(f"warm start took MORE iterations than cold "
                    f"({iters} > {it_ref})")
    log(f"delta-only request served fresh result (Linf {gap:.2e}, "
        f"warm {iters} vs cold {it_ref} iters)")

    # WCC monotone gate: warm on adds-only, LOUD cold after a removal
    h1, out1 = client.semiring(algorithm="wcc", graph_key="smoke",
                               n_nodes=N, graph_version=2)
    h2, out2 = client.semiring(algorithm="wcc", graph_key="smoke",
                               n_nodes=N, graph_version=2)
    if not h2.get("warm_started"):
        return fail("repeat WCC did not warm-start")
    src3, dst3 = np.delete(src2, [0]), np.delete(dst2, [0])
    ch3 = np.unique(np.concatenate([src2[:1], dst2[:1]])).astype(
        np.int32)
    i3 = _incident(src3, dst3, ch3, N)
    cold0 = _metric("delta.cold_start_total")
    h3, out3 = client.semiring(
        algorithm="wcc", graph_key="smoke", n_nodes=N, graph_version=3,
        base_version=2, changed=ch3, inc_src=i3[0], inc_dst=i3[1],
        inc_w=i3[2])
    if h3.get("warm_started"):
        return fail("removal delta warm-started WCC (monotone-unsafe)")
    if _metric("delta.cold_start_total") <= cold0:
        return fail("monotone-unsafe cold start was not counted")
    ref_c, _ = weakly_connected_components(from_coo(src3, dst3,
                                                    n_nodes=N))
    if not np.array_equal(np.asarray(ref_c), out3["components"][:N]):
        return fail("post-removal WCC does not match cold reference")
    log("WCC monotone gate held (warm on repeat, LOUD cold on removal)")

    # change-log wrap: the typed verdict forces the full-export path
    st = InMemoryStorage()
    for i in range(1100):
        st._bump_topology({i})
    verdict = st.changes_between(0, st.topology_version)
    if not isinstance(verdict, ChangeLogUnknowable) or verdict:
        return fail("wrapped change log did not return the typed falsy "
                    "ChangeLogUnknowable")
    log(f"change-log wrap verdict: {verdict!r}")

    try:
        client.shutdown()
        client.close()
    except OSError:
        pass
    log("OK: delta plane end-to-end (O(delta) refresh, warm contracts, "
        "typed wrap fallback)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
