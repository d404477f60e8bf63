"""Workload benchmark driver (the reference's tests/mgbench analog).

Measures the host query engine over a live Bolt server with
Pokec-flavored workloads (/root/reference/tests/mgbench/workloads/pokec.py
methodology: isolated query groups, latency percentiles + throughput):

  point_read        MATCH (n:User {id: $id}) RETURN n.age
  one_hop           MATCH (n:User {id: $id})-[:FRIEND]->(m) RETURN count(m)
  two_hop           ... -[:FRIEND*2..2]-> ...
  property_update   SET on a matched vertex
  aggregate         global count/avg
  analytical        CALL pagerank.get() (device path)

Round 5 additions: a supernode-skew workload
(/root/reference/tests/mgbench/workloads/supernode.py — one hub node
with CARDINALITY in-edges), a multiprocess read-executor group
(server/mp_executor.py), and `--out OLTP_rN.json` so every round ships
a tracked OLTP artifact, not prose.

Usage: python benchmarks/mgbench.py [--nodes 10000] [--edges 50000]
                                    [--supernode 20000] [--out FILE]
Prints a JSON report; the driver-tracked artifact is OLTP_r{N}.json.
"""

from __future__ import annotations

import argparse
import json
import random
import statistics
import sys
import time


def percentiles(samples):
    s = sorted(samples)

    def pct(p):
        return s[min(int(p * len(s)), len(s) - 1)] * 1000

    return {"p50_ms": round(pct(0.50), 3), "p90_ms": round(pct(0.90), 3),
            "p99_ms": round(pct(0.99), 3),
            "mean_ms": round(statistics.mean(samples) * 1000, 3)}


def run_group(client, name, query, param_fn, iterations, warmup=0):
    """Fault-isolated: an error (e.g. unreachable device) yields an error
    entry instead of discarding the whole report."""
    try:
        for _ in range(warmup):  # discarded (JIT compilation etc.)
            client.execute(query, param_fn() if param_fn else None)
        samples = []
        for _ in range(iterations):
            params = param_fn() if param_fn else None
            t0 = time.perf_counter()
            client.execute(query, params)
            samples.append(time.perf_counter() - t0)
    except Exception as e:
        return {"name": name, "error": f"{type(e).__name__}: {e}"}
    total = sum(samples)
    return {"name": name, "iterations": iterations,
            "throughput_qps": round(iterations / total, 1),
            **percentiles(samples)}


def _loader_worker(port, n_nodes, n_edges, batch, queue):
    """Dataset loader in its OWN process: parameter generation and
    packstream encoding run on a separate GIL, so the measured load rate
    reflects the server's ingest path, not the bench client's CPU
    stealing the server process's GIL."""
    import os
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import numpy as np
    from memgraph_tpu.server.client import BoltClient
    client = BoltClient(port=port, timeout=600.0)
    try:
        client.execute("CREATE INDEX ON :User(id)")
        t0 = time.perf_counter()
        for start in range(0, n_nodes, batch):
            ids = list(range(start, min(start + batch, n_nodes)))
            client.execute(
                "UNWIND $ids AS i CREATE (:User {id: i, age: i % 80})",
                {"ids": ids})
        nodes_s = time.perf_counter() - t0
        nprng = np.random.default_rng(7)
        t0 = time.perf_counter()
        for start in range(0, n_edges, batch):
            pairs = nprng.integers(
                0, n_nodes,
                size=(min(batch, n_edges - start), 2)).tolist()
            client.execute(
                "UNWIND $pairs AS p "
                "MATCH (a:User {id: p[0]}), (b:User {id: p[1]}) "
                "CREATE (a)-[:FRIEND]->(b)", {"pairs": pairs})
        edges_s = time.perf_counter() - t0
        queue.put((nodes_s, edges_s))
    finally:
        client.close()


def _client_worker(port, n_iter, n_nodes, barrier, queue):
    """Point-read loop in a separate process (own GIL). Waits on the
    barrier after import+connect+warmup so measured time excludes
    process startup, then reports its own (start, end) window."""
    import os
    import random as _random
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from memgraph_tpu.server.client import BoltClient
    c = BoltClient(port=port)
    try:
        local = _random.Random()
        for _ in range(20):   # warmup
            c.execute("MATCH (n:User {id: $id}) RETURN n.age",
                      {"id": local.randrange(n_nodes)})
        barrier.wait()
        t0 = time.perf_counter()
        for _ in range(n_iter):
            c.execute("MATCH (n:User {id: $id}) RETURN n.age",
                      {"id": local.randrange(n_nodes)})
        queue.put((t0, time.perf_counter(), n_iter))
    finally:
        c.close()


def _shard_plane_groups(args, groups):
    """The mgshard groups: sharded bulk load, threaded point reads,
    routed updates, cross-shard 2PC with an oracle check."""
    import threading
    from collections import defaultdict

    from memgraph_tpu.sharding import ShardPlane, ShardedClient
    from memgraph_tpu.sharding.partition import shard_for_key

    out = []
    n = args.shards
    print(f"loading {args.nodes} users into {n} shard workers ...",
          file=sys.stderr)
    plane = ShardPlane(n_shards=n).start()
    try:
        client = ShardedClient(plane)
        client.ddl("CREATE INDEX ON :User(id)")
        client.ddl("CREATE INDEX ON :Acct(id)")
        batch = 10_000
        t0 = time.perf_counter()
        for start in range(0, args.nodes, batch):
            per_shard = defaultdict(list)
            for i in range(start, min(start + batch, args.nodes)):
                per_shard[shard_for_key(i, n)].append(i)
            for _sid, ids in per_shard.items():
                client.write(
                    "UNWIND $ids AS i "
                    "CREATE (:User {id: i, age: i % 80})",
                    {"ids": ids}, key=ids[0])
        load_s = time.perf_counter() - t0
        out.append({"name": f"shard_load_{n}w", "workers": n,
                    "records_per_sec": round(args.nodes / load_s, 1)})

        rng = random.Random(11)
        for _ in range(50):    # warmup (parse/plan caches per worker)
            i = rng.randrange(args.nodes)
            client.read("MATCH (n:User {id: $id}) RETURN n.age",
                        {"id": i}, key=i)

        def pump(fn, per_thread, threads_n):
            t0 = time.perf_counter()

            def worker():
                local = random.Random()
                c = ShardedClient(plane)
                for _ in range(per_thread):
                    fn(c, local.randrange(args.nodes))
            threads = [threading.Thread(target=worker)
                       for _ in range(threads_n)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            return per_thread * threads_n / (time.perf_counter() - t0)

        per_thread = max(args.iterations // 2, 50)
        qps = pump(lambda c, i: c.read(
            "MATCH (n:User {id: $id}) RETURN n.age", {"id": i}, key=i),
            per_thread, n)
        read_group = {"name": f"point_read_sharded_{n}w", "workers": n,
                      "aggregate_qps": round(qps, 1)}
        one = next((g for g in groups
                    if g["name"] == "point_read_1_clients"
                    and "aggregate_qps" in g), None)
        if one:
            read_group["speedup_vs_single_process"] = round(
                qps / one["aggregate_qps"], 2)
        out.append(read_group)

        qps = pump(lambda c, i: c.write(
            "MATCH (n:User {id: $id}) SET n.age = n.age + 1",
            {"id": i}, key=i), max(per_thread // 2, 25), n)
        out.append({"name": f"property_update_sharded_{n}w",
                    "workers": n, "aggregate_qps": round(qps, 1)})

        # cross-shard 2PC: transfer pairs between accounts on distinct
        # shards; the oracle is arithmetic — total balance conserved,
        # every per-account balance equal to the locally-computed value
        accts = list(range(64))
        for a in accts:
            client.write("CREATE (:Acct {id: $id, bal: 100})",
                         {"id": a}, key=a)
        expected = {a: 100 for a in accts}
        iters = max(args.iterations // 3, 30)
        samples = []
        for k in range(iters):
            a, b = rng.sample(accts, 2)
            t0 = time.perf_counter()
            client.write_multi([
                (a, "MATCH (x:Acct {id: $id}) SET x.bal = x.bal - 1",
                 {"id": a}),
                (b, "MATCH (x:Acct {id: $id}) SET x.bal = x.bal + 1",
                 {"id": b}),
            ])
            samples.append(time.perf_counter() - t0)
            expected[a] -= 1
            expected[b] += 1
        _cols, rows = client.read("MATCH (x:Acct) RETURN sum(x.bal)")
        oracle_match = rows == [[100 * len(accts)]]
        for a in rng.sample(accts, 8):
            _c, r = client.read(
                "MATCH (x:Acct {id: $id}) RETURN x.bal", {"id": a},
                key=a)
            oracle_match = oracle_match and r == [[expected[a]]]
        out.append({"name": "cross_shard_write_2pc",
                    "iterations": iters,
                    "oracle_match": bool(oracle_match),
                    **percentiles(samples)})
    finally:
        plane.close()
    return out


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--nodes", type=int, default=10_000)
    p.add_argument("--edges", type=int, default=50_000)
    p.add_argument("--iterations", type=int, default=300)
    p.add_argument("--port", type=int, default=0,
                   help="existing server port (0 = spawn in-process)")
    p.add_argument("--clients", type=int, default=8,
                   help="connections for the multi-client scaling group")
    p.add_argument("--supernode", type=int, default=20_000,
                   help="in-degree of the supernode hub (0 = skip)")
    p.add_argument("--mp-workers", type=int, default=4,
                   help="processes for the mp-executor group (0 = skip)")
    p.add_argument("--shards", type=int, default=4,
                   help="shard workers for the mgshard plane group "
                        "(0 = skip)")
    p.add_argument("--out", default=None,
                   help="also write the JSON report to this file")
    args = p.parse_args()

    import os
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from memgraph_tpu.query.interpreter import InterpreterContext
    from memgraph_tpu.server.bolt import BoltServer
    from memgraph_tpu.server.client import BoltClient
    from memgraph_tpu.storage import InMemoryStorage

    if args.port:
        port = args.port
    else:
        import socket
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        server = BoltServer(InterpreterContext(InMemoryStorage()),
                            "127.0.0.1", port)
        server.run_in_thread()

    # wide timeout: load batches at 1M+ nodes can stall on GC/index
    # growth well past the 30s default
    client = BoltClient(port=port, timeout=600.0)
    rng = random.Random(7)

    print(f"loading {args.nodes} users / {args.edges} friendships ...",
          file=sys.stderr)
    # 10k-row batches: the bulk-write fast lane amortizes per-batch costs
    # (gid reservation, WAL record, index merge), so bigger batches are
    # strictly better until packstream frames dominate client memory.
    # The loader runs in its own process (own GIL) — see _loader_worker.
    batch = 10_000
    import multiprocessing as _mp
    _mp_ctx = _mp.get_context("spawn")
    _loader_q = _mp_ctx.Queue()
    loader = _mp_ctx.Process(target=_loader_worker,
                             args=(port, args.nodes, args.edges, batch,
                                   _loader_q))
    loader.start()
    nodes_s, edges_s = _loader_q.get()
    loader.join()
    load_s = nodes_s + edges_s
    print(f"  loaded in {load_s:.1f}s "
          f"({(args.nodes + args.edges) / load_s:,.0f} records/s; "
          f"nodes {args.nodes / nodes_s:,.0f}/s, "
          f"edges {args.edges / max(edges_s, 1e-9):,.0f}/s)",
          file=sys.stderr)

    rand_id = lambda: {"id": rng.randrange(args.nodes)}
    groups = [
        run_group(client, "point_read",
                  "MATCH (n:User {id: $id}) RETURN n.age", rand_id,
                  args.iterations),
        run_group(client, "one_hop",
                  "MATCH (n:User {id: $id})-[:FRIEND]->(m) RETURN count(m)",
                  rand_id, args.iterations),
        run_group(client, "two_hop",
                  "MATCH (n:User {id: $id})-[:FRIEND*2..2]->(m) "
                  "RETURN count(m)", rand_id, max(args.iterations // 3, 10)),
        run_group(client, "property_update",
                  "MATCH (n:User {id: $id}) SET n.age = n.age + 1", rand_id,
                  args.iterations),
        run_group(client, "aggregate",
                  "MATCH (n:User) RETURN count(n), avg(n.age)", None,
                  max(args.iterations // 10, 5)),
        # intra-query parallel execution (columnar scan+filter+aggregate)
        # vs the same work through the serial Volcano path (`n.age + 0`
        # makes the filter ineligible for the columnar rewrite)
        run_group(client, "scan_aggregate_parallel",
                  "MATCH (n:User) WHERE n.age > 40 "
                  "RETURN count(*), sum(n.age)", None,
                  max(args.iterations // 10, 5), warmup=1),
        run_group(client, "scan_aggregate_serial",
                  "MATCH (n:User) WHERE n.age + 0 > 40 "
                  "RETURN count(*), sum(n.age)", None,
                  max(args.iterations // 30, 3)),
    ]
    par = next((g for g in groups if g["name"] == "scan_aggregate_parallel"
                and "mean_ms" in g), None)
    ser = next((g for g in groups if g["name"] == "scan_aggregate_serial"
                and "mean_ms" in g), None)
    if par and ser:
        par["speedup_vs_serial"] = round(ser["mean_ms"] / par["mean_ms"], 1)

    # compiled read lane (r20 mglane): the two groups the lane exists
    # for — a filtered aggregate tail and a set-oriented two-hop count —
    # measured lane-ON (compiled device program) vs lane-OFF (the
    # serial row-at-a-time interpreter). The env toggles change PLAN
    # shape, so plans are invalidated between modes; this needs the
    # in-process server (an external --port server keeps its own env).
    lane_report = None
    if not args.port:
        import jax

        from memgraph_tpu.ops import pipeline as lane_pl

        LANE_AGG_Q = ("MATCH (n:User) WHERE n.age > 40 "
                      "RETURN count(*), sum(n.age), min(n.age), "
                      "max(n.age)")
        LANE_HOP_Q = ("MATCH (a:User)-[:FRIEND]->(b)-[:FRIEND]->(m) "
                      "WHERE a.age < 2 RETURN count(m)")

        def _lane_mode(off: bool) -> None:
            for k in ("MEMGRAPH_TPU_DISABLE_LANE",
                      "MEMGRAPH_TPU_DISABLE_PARALLEL"):
                if off:
                    os.environ[k] = "1"
                else:
                    os.environ.pop(k, None)
            server.ictx.invalidate_plans()

        def _m(name):
            from memgraph_tpu.observability.metrics import global_metrics
            return {n: v for n, _k, v
                    in global_metrics.snapshot()}.get(name, 0.0)

        print("compiled-lane groups (lane on/off) ...", file=sys.stderr)
        _lane_mode(False)
        hits0 = _m("lane.hit_total")
        groups.append(run_group(client, "aggregate_lane_on", LANE_AGG_Q,
                                None, max(args.iterations // 10, 5),
                                warmup=1))
        groups.append(run_group(client, "two_hop_lane_on", LANE_HOP_Q,
                                None, max(args.iterations // 30, 5),
                                warmup=1))
        lane_served = _m("lane.hit_total") > hits0
        resident_after_on = lane_pl.resident_programs()
        _lane_mode(True)
        groups.append(run_group(client, "aggregate_lane_off",
                                LANE_AGG_Q, None, 3))
        groups.append(run_group(client, "two_hop_lane_off", LANE_HOP_Q,
                                None, 3))
        _lane_mode(False)
        for on_name, off_name in (("aggregate_lane_on",
                                   "aggregate_lane_off"),
                                  ("two_hop_lane_on",
                                   "two_hop_lane_off")):
            on = next((g for g in groups if g["name"] == on_name
                       and "p99_ms" in g), None)
            off = next((g for g in groups if g["name"] == off_name
                        and "p99_ms" in g), None)
            if on and off:
                on["p99_speedup_vs_serial"] = round(
                    off["p99_ms"] / max(on["p99_ms"], 1e-9), 1)
        backend = jax.default_backend()
        lane_report = {
            "backend": backend,
            # honesty: a CPU-host lane number is a machinery proof, not
            # the accelerator headline
            "degraded": backend == "cpu",
            "lane_served": bool(lane_served),
            "resident_programs": resident_after_on,
        }

    # multi-client scaling: N concurrent connections hammering point
    # reads. Clients run as separate PROCESSES so their encode/decode CPU
    # doesn't share the server's GIL; server-side execution runs on the
    # Bolt worker pool.
    import multiprocessing as mp

    mp_ctx = mp.get_context("spawn")
    for n_clients in (1, args.clients):
        barrier = mp_ctx.Barrier(n_clients)
        queue = mp_ctx.Queue()
        procs = [mp_ctx.Process(
            target=_client_worker,
            args=(port, args.iterations, args.nodes, barrier, queue))
            for _ in range(n_clients)]
        for t in procs:
            t.start()
        try:
            spans = [queue.get(timeout=120) for _ in range(n_clients)]
        except Exception as e:   # a dead worker must not hang the bench
            for t in procs:
                t.terminate()
            groups.append({
                "name": f"point_read_{n_clients}_clients",
                "clients": n_clients,
                "error": f"{type(e).__name__}: worker died or timed out"})
            continue
        finally:
            for t in procs:
                t.join(timeout=10)
        total = sum(s[2] for s in spans)
        wall = max(s[1] for s in spans) - min(s[0] for s in spans)
        groups.append({
            "name": f"point_read_{n_clients}_clients",
            "clients": n_clients,
            "aggregate_qps": round(total / wall, 1),
        })
    one = next((g for g in groups
                if g["name"] == "point_read_1_clients"
                and "aggregate_qps" in g), None)
    many = next((g for g in groups
                 if g["name"] == f"point_read_{args.clients}_clients"
                 and "aggregate_qps" in g), None)
    if one and many:
        many["scaling_vs_1_client"] = round(
            many["aggregate_qps"] / one["aggregate_qps"], 2)
    # supernode skew (reference workload: one hub, CARDINALITY spokes):
    # expansion over the hub, hub-touching writes, MERGE over the hub
    if args.supernode:
        print(f"loading supernode hub with {args.supernode} spokes ...",
              file=sys.stderr)
        client.execute("CREATE INDEX ON :SNode(id)")
        client.execute("CREATE INDEX ON :Supernode")
        client.execute("CREATE INDEX ON :Supernode(id)")
        client.execute("CREATE (:Supernode {id: 0})")
        for start in range(0, args.supernode, batch):
            ids = list(range(start, min(start + batch, args.supernode)))
            client.execute(
                "MATCH (s:Supernode {id: 0}) UNWIND $ids AS i "
                "CREATE (s)<-[:EDGE]-(:SNode {id: i})", {"ids": ids})
        groups += [
            run_group(client, "supernode_expand_count",
                      "MATCH (s:Supernode {id: 0})<-[:EDGE]-(n) "
                      "RETURN count(n)", None,
                      max(args.iterations // 10, 5), warmup=1),
            run_group(client, "supernode_two_hop",
                      "MATCH (n:SNode {id: $id})-[:EDGE]->(s)"
                      "<-[:EDGE]-(m) RETURN count(m)",
                      lambda: {"id": rng.randrange(args.supernode)},
                      max(args.iterations // 30, 3)),
            run_group(client, "supernode_unwind_writes",
                      f"UNWIND range(1, {args.supernode}) AS x "
                      "MATCH (s:Supernode {id: 0}) SET s.prop = x", None,
                      max(args.iterations // 30, 3)),
            run_group(client, "supernode_merge_edges",
                      "MATCH (s:Supernode {id: 0}), (n:SNode {id: $id}) "
                      "MERGE (s)<-[:EDGE]-(n)",
                      lambda: {"id": rng.randrange(args.supernode)},
                      max(args.iterations // 3, 10)),
        ]

    # multiprocess read executor (server/mp_executor.py): same point
    # reads dispatched over N forked workers with independent GILs —
    # the architectural answer to the GIL ceiling (1-core hosts show ~1x)
    if args.mp_workers and not args.port:
        import threading as _threading
        from memgraph_tpu.server.mp_executor import MPReadExecutor
        ex = MPReadExecutor(server.ictx, n_workers=args.mp_workers)
        try:
            for _ in range(20):
                ex.execute("MATCH (n:User {id: $id}) RETURN n.age",
                           {"id": rng.randrange(args.nodes)})
            per_thread = max(args.iterations // 2, 50)
            t0 = time.perf_counter()

            def _pump():
                local = random.Random()
                for _ in range(per_thread):
                    ex.execute("MATCH (n:User {id: $id}) RETURN n.age",
                               {"id": local.randrange(args.nodes)})
            threads = [_threading.Thread(target=_pump)
                       for _ in range(args.mp_workers)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            wall = time.perf_counter() - t0
            groups.append({
                "name": f"point_read_mp_executor_{args.mp_workers}w",
                "workers": args.mp_workers,
                "aggregate_qps": round(per_thread * args.mp_workers / wall,
                                       1)})
        except Exception as e:  # noqa: BLE001
            groups.append({"name": "point_read_mp_executor",
                           "error": f"{type(e).__name__}: {e}"})
        finally:
            ex.close()

    # sharded OLTP execution plane (r18, mgshard): the same dataset
    # hash-sharded across N worker PROCESSES (each its own storage +
    # WAL + GIL), point reads/writes routed by key, plus the
    # cross-shard 2PC write group with an arithmetic oracle check.
    # The honest comparison target is the single-process 1-client Bolt
    # aggregate (point_read_1_clients) — the number the plane exists
    # to multiply past the GIL.
    if args.shards:
        groups += _shard_plane_groups(args, groups)

    client.close()
    # the analytical group gets its own client with a wide timeout (first
    # CALL pays XLA compilation) and one discarded warm-up run
    analytical = BoltClient(port=port, timeout=600.0)
    groups.append(run_group(
        analytical, "analytical_pagerank",
        "CALL pagerank.get() YIELD rank RETURN max(rank)", None, 3,
        warmup=1))
    analytical.close()
    # honesty tags (the r06 lesson, applied to OLTP): shard scaling on
    # fewer cores than workers measures contention, not the
    # architecture — such a record is DEGRADED and the perf gate must
    # never accept it as the scaling headline
    cores = os.cpu_count() or 1
    report = {"workload": "pokec-flavored+supernode", "nodes": args.nodes,
              "edges": args.edges, "supernode_degree": args.supernode,
              "cores": cores,
              "shard_workers": args.shards,
              "degraded": bool(args.shards and cores < args.shards),
              "load_records_per_sec":
              round((args.nodes + args.edges) / load_s, 1),
              "groups": groups}
    if lane_report is not None:
        report["lane"] = lane_report
    if report["degraded"]:
        report["degraded_reason"] = (
            f"host has {cores} core(s) for {args.shards} shard "
            "workers; scaling numbers are contention-bound")
    if args.out:
        with open(args.out, "w") as f:
            json.dump(report, f, indent=2)
    print(json.dumps(report, indent=2))


if __name__ == "__main__":
    main()
