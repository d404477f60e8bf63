"""Gate smoke for the PPR serving plane: spawn the kernel server, fire
64 concurrent requests from threads, assert the coalescing ratio beats
1 (requests actually shared batches), assert a repeat request hits the
result cache, and shut down cleanly.

Sized for the dev gate (~seconds, CPU-safe): this proves the serving
plane WORKS on every host; it measures no speed.

Usage: python -m tools.ppr_smoke
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

CLIENTS = 64
N, E = 2000, 12000


def log(msg: str) -> None:
    print(f"ppr-smoke: {msg}", flush=True)


def fail(msg: str) -> "int":
    log(f"FAIL: {msg}")
    return 1


def _metric(name):
    from memgraph_tpu.observability.metrics import global_metrics
    return dict((n, v) for n, _k, v in global_metrics.snapshot()).get(
        name, 0.0)


def main() -> int:
    from memgraph_tpu.server.kernel_server import KernelClient, KernelServer

    sock = os.path.join(tempfile.mkdtemp(prefix="pprsmoke"), "ks.sock")
    srv = KernelServer(sock, wedge_after_s=60)
    srv._ppr.window_s = 0.02        # wide window: 64 threads must ride
    server_thread = threading.Thread(target=srv.serve_forever,
                                     daemon=True)
    server_thread.start()
    client = None
    deadline = time.monotonic() + 120
    while time.monotonic() < deadline:
        try:
            client = KernelClient(sock, timeout=120)
            break
        except OSError:
            time.sleep(0.05)
    if client is None:
        return fail("kernel server never bound its socket")

    rng = np.random.default_rng(0)
    src = rng.integers(0, N, E)
    dst = rng.integers(0, N, E)
    client.ppr([0], src=src, dst=dst, n_nodes=N, graph_key="smoke",
               graph_version=1, tol=1e-6)
    log(f"graph staged ({N} nodes, {E} edges)")

    req_before = _metric("ppr.requests_total")
    batch_before = _metric("ppr.batches_total")
    results: dict = {}
    errors: list = []
    barrier = threading.Barrier(CLIENTS)

    def worker(i):
        try:
            for attempt in range(50):
                try:
                    c = KernelClient(sock, timeout=120)
                    break
                except OSError:
                    time.sleep(0.05)
            barrier.wait(timeout=60)
            h, out = c.ppr([i % N], graph_key="smoke", graph_version=1,
                           n_nodes=N, tol=1e-6, top_k=5)
            results[i] = h
            c.close()
        except Exception as e:  # noqa: BLE001 — smoke reports, not raises
            errors.append((i, repr(e)))

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(CLIENTS)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=180)
    if errors:
        return fail(f"{len(errors)} of {CLIENTS} concurrent requests "
                    f"errored; first: {errors[0]}")
    if len(results) != CLIENTS:
        return fail(f"only {len(results)} of {CLIENTS} requests "
                    "completed")
    req_delta = _metric("ppr.requests_total") - req_before
    batch_delta = max(_metric("ppr.batches_total") - batch_before, 1.0)
    ratio = req_delta / batch_delta
    max_batch = max(h["batch_size"] for h in results.values())
    log(f"{CLIENTS} concurrent requests -> {int(batch_delta)} batches "
        f"(coalescing ratio {ratio:.1f}, widest batch {max_batch})")
    if ratio <= 1.0:
        return fail(f"coalescing ratio {ratio:.2f} <= 1 — requests "
                    "never shared a batch")

    # repeat request must ride the result cache, not the device
    h, _ = client.ppr([1], graph_key="smoke", graph_version=1, n_nodes=N,
                      tol=1e-6, top_k=5)
    if h.get("cache") != "hit":
        return fail(f"repeat request missed the cache "
                    f"(cache={h.get('cache')!r})")
    log("repeat request: cache hit")

    client.shutdown()
    client.close()
    server_thread.join(timeout=30)
    if server_thread.is_alive():
        return fail("server did not shut down cleanly")
    log("clean shutdown")
    log("PASS")
    return 0


if __name__ == "__main__":
    sys.exit(main())
