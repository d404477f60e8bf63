"""Betweenness at scale: sampled Brandes on a
1M-node / 10M-edge graph with the autotuned (B, n_pad) chunking,
correctness-anchored by exact parity at small scale.

Usage: python benchmarks/bench_betweenness.py [--nodes N] [--edges E]
       [--samples 64] [--out BETWEENNESS_r05.json]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=1_000_000)
    ap.add_argument("--edges", type=int, default=10_000_000)
    ap.add_argument("--samples", type=int, default=64)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    from memgraph_tpu.utils.jax_cache import ensure_compile_cache
    ensure_compile_cache()
    import jax
    from memgraph_tpu.ops.betweenness import (autotune_chunk,
                                              betweenness_centrality)
    from memgraph_tpu.ops.csr import from_coo
    import bench as B

    report = {"nodes": args.nodes, "edges": args.edges,
              "samples": args.samples,
              "platform": jax.devices()[0].platform}

    # correctness anchor: exact parity vs networkx at small scale
    import networkx as nx
    rng = np.random.default_rng(0)
    sn, se = 300, 1500
    s_small = rng.integers(0, sn, se)
    d_small = rng.integers(0, sn, se)
    g_small = from_coo(s_small, d_small, n_nodes=sn)
    got = np.asarray(betweenness_centrality(g_small, directed=True))
    G = nx.DiGraph()
    G.add_nodes_from(range(sn))
    G.add_edges_from(zip(s_small.tolist(), d_small.tolist()))
    want = np.array([nx.betweenness_centrality(G)[i] for i in range(sn)])
    parity = bool(np.allclose(got, want, atol=1e-6))
    report["small_scale_exact_parity"] = parity
    print(f"small-scale parity vs networkx: {parity}", file=sys.stderr)

    # scale run
    src, dst = B.generate_graph(args.nodes, args.edges, seed=7)
    graph = from_coo(src, dst, n_nodes=args.nodes)
    chunk = autotune_chunk(args.edges, graph.n_pad)
    report["autotuned_chunk"] = chunk
    print(f"autotuned chunk at {args.edges:,} edges: B={chunk}",
          file=sys.stderr)
    t0 = time.perf_counter()
    bc = betweenness_centrality(graph, directed=True,
                                samples=args.samples, chunk=chunk,
                                max_levels=64)
    top = np.argsort(-np.asarray(bc))[:10]
    _ = float(np.asarray(bc)[0])
    elapsed = time.perf_counter() - t0
    report["seconds"] = round(elapsed, 2)
    report["sources_per_sec"] = round(args.samples / elapsed, 2)
    report["top10_nodes"] = [int(x) for x in top]
    report["ok"] = parity and elapsed > 0
    print(f"{args.samples} sources in {elapsed:.1f}s "
          f"({args.samples / elapsed:.2f} src/s)", file=sys.stderr)
    out = json.dumps(report)
    if args.out:
        with open(args.out, "w") as f:
            f.write(out + "\n")
    print(out)


if __name__ == "__main__":
    main()
