"""Chip smoke: the served path, once, on one TPU chip.

    python chip_smoke.py            # one chip: phases 1 and 2
    python chip_smoke.py --mesh     # four chips: the mesh phase only

The quickest proof that the system still starts on the chip. It drives
the main path through the entry points a user calls, checks every answer
against an oracle computed here with numpy/scipy from the same seed, and
fails (non-zero exit, no result line) when any phase fails or when the
device is not a TPU. A smoke result is not a benchmark: the times it
prints are single readings.

Deployment: the reference's own mgbench Pokec *medium* shape (`:User
{id, age}` nodes, `:FRIEND` edges, index on `:User(id)`), generated from
``--seed`` with the skewed destinations of bench.generate_graph.

One process per chip. This parent never imports jax; each phase starts
exactly one chip owner, and waits for it to exit before the next:

  phase 1  `python -m memgraph_tpu.main` with a data directory and WAL:
           load over Bolt, point read, one-hop count, the two-hop
           filtered aggregate of the compiled lane, `CALL pagerank.get()`
           cold and warm, `pagerank.personalized`, a committed write and
           the CALL again, `PROFILE CALL`, `SHOW BUILD INFO`, `/stats`.
  phase 2  `python -m memgraph_tpu.server.kernel_server` as the chip's
           owner: one `pagerank` and one coalesced `ppr` batch from this
           jax-free client, `platform` from its `health`.
  --mesh   this process itself (no child) runs
           `pagerank_partition_centric` over a 4-device mesh and over a
           mesh of one, and compares them.

The last line of stdout is the result:
{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time
import urllib.request

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))

#: mgbench Pokec medium (BASELINE.md §Datasets)
NODES = 100_000
EDGES = 1_768_515
REDUCED = ("Pokec medium (100,000 / 1,768,515), not Pokec large "
           "(1.63M / 30.6M): Bolt ingest runs at some 30-40k records/s, "
           "so large would spend about 15 minutes loading")

BATCH = 10_000
DAMPING = 0.85
YOUNG = 30                  # the lane query's `a.age < 30`
PPR_SOURCES = ([3, 7, 11], [42], [1000, 2000])
WRITE_EDGES = 64            # the committed write: hubs -> one quiet node
MESH_ATOL = 1e-5            # tests/test_sharded_analytics.py's f32 criterion
MASTER_TIMEOUT_S = 1150     # under the driver's 1200


class SmokeFailure(Exception):
    """A phase's check did not hold."""


def say(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)
    say(f"  ok: {what}")


# --------------------------------------------------------------------------
# data and oracles (numpy / scipy only)
# --------------------------------------------------------------------------

def make_graph(seed: int, n_nodes: int, n_edges: int):
    """bench.generate_graph's skewed digraph: uniform sources, squared
    sampling of destinations (heavy-tail in-degree toward low ids)."""
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n_nodes, n_edges, dtype=np.int64)
    dst = (rng.random(n_edges) ** 2 * n_nodes).astype(np.int64)
    return src, dst


def oracle_pagerank(src, dst, n_nodes, personalization=None,
                    damping=DAMPING, tol=1e-12, max_iterations=500):
    """float64 scipy power iteration. Restart and dangling mass go to
    `personalization` (uniform when None), as the kernels' epilogues do."""
    import scipy.sparse as sp
    deg = np.bincount(src, minlength=n_nodes).astype(np.float64)
    inv = np.where(deg > 0, 1.0 / np.maximum(deg, 1.0), 0.0)
    mat = sp.csr_matrix((inv[src], (dst, src)), shape=(n_nodes, n_nodes))
    p = np.full(n_nodes, 1.0 / n_nodes)
    if personalization is not None:
        p = np.zeros(n_nodes)
        p[np.asarray(personalization)] = 1.0 / len(personalization)
    dangling = deg == 0
    rank = p.copy()
    for _ in range(max_iterations):
        new = (1.0 - damping) * p \
            + damping * (mat @ rank + rank[dangling].sum() * p)
        err = np.abs(new - rank).sum()
        rank = new
        if err <= tol:
            break
    return rank


def oracle_two_hop(src, dst, n_nodes):
    """count(m), count(DISTINCT m) of
    (a:User)-[:FRIEND]->(b)-[:FRIEND]->(m) WHERE a.age < 30, with
    Cypher's rule that one path never uses the same edge twice."""
    young = (np.arange(n_nodes) % 80) < YOUNG
    young_in = np.bincount(dst[young[src]], minlength=n_nodes)
    # e2 = (b, m) pairs with every e1 = (a, b), a young, except itself
    firsts = young_in[src] - ((src == dst) & young[src])
    return int(firsts.sum()), int(len(np.unique(dst[firsts > 0])))


def compare_ranks(got, want, what: str, top: int = 100) -> None:
    got = np.asarray(got, dtype=np.float64)
    check(got.shape == want.shape and bool(np.isfinite(got).all()),
          f"{what}: {got.shape[0]} finite ranks")
    linf = float(np.abs(got - want).max())
    top = min(top, len(want))
    overlap = len(set(np.argsort(-got)[:top].tolist())
                  & set(np.argsort(-want)[:top].tolist()))
    check(linf < 1e-4, f"{what}: L-inf {linf:.3e} < 1e-4 vs the oracle")
    check(overlap == top, f"{what}: top-{top} overlap {overlap}/{top}")


# --------------------------------------------------------------------------
# the one device assertion
# --------------------------------------------------------------------------

def require_tpu(device: dict, count: int = 1, backends=None) -> None:
    """Every claim that a phase ran on the chip goes through here: the
    chip owner's own report of its device and, once PROFILE and the
    server's log have named the backends, that a 1.77M-edge PageRank
    took the MXU plan with the Pallas Benes passes."""
    if device.get("platform") != "tpu" or device.get("count") != count:
        raise SmokeFailure(
            f"needs {count} TPU chip(s); the chip owner reports {device}")
    for needed in () if backends is None else ("semiring_mxu",
                                               "benes_pallas"):
        if needed not in backends:
            raise SmokeFailure(
                f"the served CALL ran on {backends or 'no backend'}, "
                f"not on {needed}")


# --------------------------------------------------------------------------
# child processes
# --------------------------------------------------------------------------

_CHILDREN: list = []


def _spawn(args, log_path: str):
    """Start one chip owner. Its environment is the one given (JAX picks
    its default backend, and the compile cache goes where
    JAX_COMPILATION_CACHE_DIR says), minus the switch that would route
    the server's analytics to a daemon: one owner per chip. A session of
    its own, so the whole group can be stopped at the end."""
    env = dict(os.environ)
    env.pop("MEMGRAPH_TPU_ANALYTICS_KERNEL_SERVER", None)
    with open(log_path, "ab") as log:
        p = subprocess.Popen([sys.executable] + args, cwd=REPO, env=env,
                             stdout=log, stderr=subprocess.STDOUT,
                             start_new_session=True)
    _CHILDREN.append(p)
    return p


def _connect(make_client, child, what: str, timeout_s: float = 180.0):
    """Retry `make_client()` until the child accepts connections."""
    deadline = time.monotonic() + timeout_s
    while True:
        try:
            return make_client()
        except OSError:
            if child.poll() is not None or time.monotonic() > deadline:
                raise SmokeFailure(f"{what} did not come up")
            time.sleep(0.2)


def _stop(p, grace_s: float = 60.0) -> int:
    """SIGTERM, wait, then kill the group; returns the exit code."""
    if p.poll() is None:
        p.terminate()
        try:
            p.wait(grace_s)
        except subprocess.TimeoutExpired:
            pass
    try:
        os.killpg(p.pid, signal.SIGKILL)    # stragglers of the group
    except (ProcessLookupError, PermissionError):
        pass
    rc = p.wait(30)
    if p in _CHILDREN:
        _CHILDREN.remove(p)
    return rc


def _stop_all() -> None:
    for p in list(_CHILDREN):
        try:
            _stop(p, grace_s=5.0)
        except (OSError, subprocess.TimeoutExpired):
            pass


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _tail(path: str, n: int = 3000) -> str:
    try:
        with open(path, "rb") as f:
            return f.read()[-n:].decode(errors="replace")
    except OSError:
        return ""


def _cache_entries(path: str) -> int:
    try:
        return sum(name.endswith("-cache") for name in os.listdir(path))
    except FileNotFoundError:
        return 0


# --------------------------------------------------------------------------
# phase 1: the served path
# --------------------------------------------------------------------------

def _build_info(client) -> dict:
    _, rows, _ = client.execute("SHOW BUILD INFO")
    return {k: v for k, v in rows}


def _device_of(info: dict) -> dict:
    return {"platform": info.get("device_platform"),
            "kind": info.get("device_kind"),
            "count": info.get("device_count")}


def _compile_total(metrics_port: int) -> int:
    with urllib.request.urlopen(
            f"http://127.0.0.1:{metrics_port}/stats", timeout=60) as r:
        stats = json.load(r)
    return int(stats["device"].get("jit.compile_total", 0))


PAGERANK_Q = "CALL pagerank.get() YIELD node, rank RETURN node.id AS id, rank"
PPR_Q = ("MATCH (s:User) WHERE s.id IN $ids WITH collect(s) AS srcs "
         "CALL pagerank.personalized(srcs) YIELD node, rank "
         "RETURN node.id AS id, rank")
EDGE_Q = ("UNWIND $pairs AS p "
          "MATCH (a:User {id: p[0]}), (b:User {id: p[1]}) "
          "CREATE (a)-[:FRIEND]->(b)")


def _ranks_of(rows, n_nodes: int) -> np.ndarray:
    ranks = np.full(n_nodes, np.nan)
    for node_id, rank in rows:
        ranks[node_id] = rank
    return ranks


def phase_served(src, dst, n_nodes: int, workdir: str,
                 device_check=require_tpu):
    """Phase 1. Returns the report dict; raises SmokeFailure."""
    from memgraph_tpu.server.client import BoltClient
    say(f"phase 1: served path, {n_nodes:,} nodes / {len(src):,} edges")
    bolt, metrics = _free_port(), _free_port()
    log_path = os.path.join(workdir, "server.log")
    server = _spawn(
        ["-m", "memgraph_tpu.main", "--bolt-port", str(bolt),
         "--metrics-port", str(metrics),
         "--data-directory", os.path.join(workdir, "data"),
         "--storage-wal-enabled"], log_path)
    report = {}
    client = None
    try:
        client = _connect(lambda: BoltClient(port=bolt, timeout=900.0),
                          server, "the Bolt server")

        # the device, from the one process that owns it — before the load,
        # so a host without a chip fails in seconds
        info = _build_info(client)
        report["device"] = _device_of(info)
        report["cache_dir"] = info["compile_cache_dir"]
        report["cache_entries_before"] = _cache_entries(report["cache_dir"])
        say(f"  SHOW BUILD INFO: {info}")
        say(f"  compile cache: {report['cache_dir']}, "
            f"{report['cache_entries_before']} entries")
        device_check(report["device"])

        t0 = time.perf_counter()
        client.execute("CREATE INDEX ON :User(id)")
        for start in range(0, n_nodes, BATCH):
            client.execute(
                "UNWIND $ids AS i CREATE (:User {id: i, age: i % 80})",
                {"ids": list(range(start, min(start + BATCH, n_nodes)))})
        pairs = np.stack([src, dst], axis=1)
        for start in range(0, len(pairs), BATCH):
            client.execute(EDGE_Q,
                           {"pairs": pairs[start:start + BATCH].tolist()})
        load_s = time.perf_counter() - t0
        report["load_records_per_s"] = (n_nodes + len(src)) / load_s
        say(f"  loaded over Bolt in {load_s:.1f} s: "
            f"{report['load_records_per_s']:,.0f} records/s")

        probe = int(np.argmax(np.bincount(src, minlength=n_nodes)))
        _, rows, _ = client.execute(
            "MATCH (u:User {id: $id}) RETURN u.id, u.age", {"id": probe})
        check(rows == [[probe, probe % 80]], f"point read of id {probe}")
        _, rows, _ = client.execute(
            "MATCH (u:User {id: $id})-[:FRIEND]->(v) RETURN count(v)",
            {"id": probe})
        check(rows == [[int((src == probe).sum())]],
              f"one-hop count from id {probe} = {rows[0][0]}")
        _, rows, _ = client.execute(
            "MATCH (a:User)-[:FRIEND]->(b)-[:FRIEND]->(m) "
            f"WHERE a.age < {YOUNG} RETURN count(m), count(DISTINCT m)")
        check(rows == [list(oracle_two_hop(src, dst, n_nodes))],
              f"two-hop filtered aggregate = {rows[0]}")

        want = oracle_pagerank(src, dst, n_nodes)
        t0 = time.perf_counter()
        _, rows, _ = client.execute(PAGERANK_Q)
        report["cold_call_s"] = time.perf_counter() - t0
        compiles_cold = _compile_total(metrics)
        compare_ranks(_ranks_of(rows, n_nodes), want, "CALL pagerank.get()")
        check(compiles_cold > 0,
              f"jit.compile_total = {compiles_cold} after the first CALL")
        t0 = time.perf_counter()
        _, rows2, _ = client.execute(PAGERANK_Q)
        report["warm_call_s"] = time.perf_counter() - t0
        report["compile_total"] = _compile_total(metrics)
        check(report["compile_total"] == compiles_cold,
              "an identical CALL on the unchanged graph compiles nothing")
        check(rows2 == rows, "and returns the same ranks")
        say(f"  CALL to last record: cold {report['cold_call_s']:.3f} s, "
            f"warm {report['warm_call_s']:.3f} s")

        for sources in PPR_SOURCES:
            sources = [s % n_nodes for s in sources]
            _, rows, _ = client.execute(PPR_Q, {"ids": sources})
            compare_ranks(_ranks_of(rows, n_nodes),
                          oracle_pagerank(src, dst, n_nodes, sources),
                          f"pagerank.personalized({sources})", top=10)

        # one committed write: the top hubs all befriend the quietest
        # node, whose rank the second CALL must then show
        quiet = int(np.argmin(want))
        hubs = np.argsort(-want)[:WRITE_EDGES]
        client.execute(EDGE_Q, {"pairs": [[int(h), quiet] for h in hubs]})
        src2 = np.concatenate([src, hubs])
        dst2 = np.concatenate([dst, np.full(len(hubs), quiet)])
        want2 = oracle_pagerank(src2, dst2, n_nodes)
        t0 = time.perf_counter()
        _, rows, _ = client.execute(PAGERANK_Q)
        report["after_write_call_s"] = time.perf_counter() - t0
        got2 = _ranks_of(rows, n_nodes)
        compare_ranks(got2, want2, "CALL pagerank.get() after the write")
        check(got2[quiet] > 10 * want[quiet]
              and abs(got2[quiet] - want2[quiet]) < 0.05 * want2[quiet],
              f"the acknowledged write is visible: rank of id {quiet} "
              f"{want[quiet]:.3e} -> {got2[quiet]:.3e}")
        say(f"  CALL after the write: {report['after_write_call_s']:.3f} s")

        _, rows, _ = client.execute("PROFILE " + PAGERANK_Q)
        for row in rows:
            say(f"  PROFILE: {row[0]:<32} {row[3]:>12} {row[4]:>16}")
        report["backends"] = sorted(
            r[0].split(": ", 1)[1] for r in rows
            if r[0].startswith(">> device: semiring_"))
        # which Benes formulation the MXU kernels were built with
        for line in _tail(log_path, 1 << 20).splitlines():
            if "Benes backend" in line:
                say(f"  server log: {line.split(': ', 1)[-1]}")
                benes = "benes_" + line.split("Benes backend ")[1].split()[0]
                if benes not in report["backends"]:
                    report["backends"].append(benes)
        device_check(report["device"], backends=report["backends"])

        info = _build_info(client)
        report["native_builder"] = info.get("native_builder")
        report["compile_total_end"] = _compile_total(metrics)
        say(f"  native builder: {report['native_builder']}; "
            f"jit.compile_total at the end: {report['compile_total_end']}")
        check(report["native_builder"] == "loaded",
              "the native CSR/Benes builder was built and loaded")
        check(_device_of(info) == report["device"],
              "SHOW BUILD INFO still reports the same device")
    except SmokeFailure:
        raise
    except Exception as e:
        raise SmokeFailure(
            f"phase 1: {type(e).__name__}: {e}\n--- server log ---\n"
            f"{_tail(log_path)}") from e
    finally:
        if client is not None:
            client.close()
        rc = _stop(server)
        say(f"  server exited with code {rc}")
    return report


# --------------------------------------------------------------------------
# phase 2: the kernel-server daemon as the chip's owner
# --------------------------------------------------------------------------

def phase_daemon(src, dst, n_nodes: int, workdir: str,
                 device_check=require_tpu):
    from memgraph_tpu.server.kernel_server import KernelClient
    say("phase 2: kernel-server daemon")
    sock = os.path.join(workdir, "k.sock")
    log_path = sock + ".log"
    daemon = _spawn(["-m", "memgraph_tpu.server.kernel_server",
                     "--socket", sock, "--idle-timeout", "600"],
                    log_path)
    report = {}
    client = None
    try:
        client = _connect(lambda: KernelClient(sock, timeout=900.0),
                          daemon, "the kernel-server daemon")
        health = client.health()
        report["platform"] = health["platform"]
        say(f"  health: platform {health['platform']}, HBM admission "
            f"budget {health['hbm_budget_bytes']:,} bytes")
        # health carries the platform only; one owner, one chip
        device_check({"platform": health["platform"], "count": 1})

        t0 = time.perf_counter()
        ranks, _err, iters = client.pagerank(
            src=src, dst=dst, n_nodes=n_nodes, graph_key="smoke",
            max_iterations=100, tol=1e-6)
        report["pagerank_s"] = time.perf_counter() - t0
        say(f"  pagerank: {iters} iterations, "
            f"{report['pagerank_s']:.3f} s with the graph shipped")
        compare_ranks(np.asarray(ranks)[:n_nodes],
                      oracle_pagerank(src, dst, n_nodes),
                      "KernelClient.pagerank")

        # one batch: concurrent requests against the resident graph
        # coalesce into one multi-source SpMM fixpoint
        sets = [[s % n_nodes for s in sources] for sources in PPR_SOURCES]
        replies: list = [None] * len(sets)

        def ask(i):
            c = KernelClient(sock, timeout=900.0)
            try:
                replies[i] = c.ppr(sets[i], n_nodes=n_nodes,
                                   graph_key="smoke")
            except Exception as e:  # noqa: BLE001 — reported below
                replies[i] = e
            finally:
                c.close()

        threads = [threading.Thread(target=ask, args=(i,))
                   for i in range(len(sets))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(900)
        for sources, reply in zip(sets, replies):
            if isinstance(reply, Exception) or reply is None:
                raise SmokeFailure(f"ppr({sources}) failed: {reply!r}")
            _h, out = reply
            compare_ranks(np.asarray(out["ranks"])[:n_nodes],
                          oracle_pagerank(src, dst, n_nodes, sources),
                          f"KernelClient.ppr({sources})", top=10)
        ppr = {k: v for k, v in client.health()["counters"].items()
               if k.startswith("ppr.batch")}
        say(f"  ppr plane: {ppr}")
        client.shutdown()
        try:
            daemon.wait(60)
        except subprocess.TimeoutExpired:
            raise SmokeFailure("the daemon ignored its shutdown op")
    except SmokeFailure:
        raise
    except Exception as e:
        raise SmokeFailure(
            f"phase 2: {type(e).__name__}: {e}\n--- daemon log ---\n"
            f"{_tail(log_path)}") from e
    finally:
        if client is not None:
            client.close()
        rc = _stop(daemon)
        say(f"  daemon exited with code {rc}")
    return report


# --------------------------------------------------------------------------
# --mesh: one program across four chips, in this process
# --------------------------------------------------------------------------

def phase_mesh(src, dst, n_nodes: int, n_devices: int = 4,
               device_check=require_tpu):
    say(f"mesh phase: pagerank_partition_centric over {n_devices} devices")
    import jax
    from memgraph_tpu.ops.csr import shard_edges
    from memgraph_tpu.parallel.distributed import pagerank_partition_centric
    from memgraph_tpu.parallel.mesh import get_mesh_context
    from memgraph_tpu.utils.jax_cache import ensure_compile_cache

    device = {"platform": jax.devices()[0].platform,
              "kind": jax.devices()[0].device_kind,
              "count": len(jax.devices())}
    say(f"  jax.devices(): {device}")
    device_check(device, n_devices)
    ensure_compile_cache()
    cache_dir = jax.config.jax_compilation_cache_dir or "off"
    report = {"device": device, "cache_dir": cache_dir,
              "cache_entries_before": _cache_entries(cache_dir)}

    def run(n):
        ctx = get_mesh_context(n)
        scsr = shard_edges(src, dst, None, n_nodes, n).to_device(ctx)
        holders = {s.device for s in scsr.src.addressable_shards}
        check(len(holders) == n,
              f"edge blocks placed on {len(holders)} of {n} device(s)")
        t0 = time.perf_counter()
        ranks, err, iters = pagerank_partition_centric(
            scsr, ctx, damping=DAMPING, max_iterations=100, tol=1e-6)
        ranks = np.asarray(ranks)
        say(f"  mesh of {n}: {iters} iterations, err {err:.3e}, "
            f"{time.perf_counter() - t0:.3f} s with compile")
        return ranks

    many, one = run(n_devices), run(1)
    linf = float(np.abs(many - one).max())
    check(linf <= MESH_ATOL, f"mesh of {n_devices} vs one chip: "
          f"L-inf {linf:.3e} <= {MESH_ATOL:g}")
    compare_ranks(many, oracle_pagerank(src, dst, n_nodes),
                  f"mesh of {n_devices}")
    return report


# --------------------------------------------------------------------------
# entry
# --------------------------------------------------------------------------

def _arm_watchdog() -> None:
    def on_alarm(signum, frame):
        say(f"FAILED: no result within {MASTER_TIMEOUT_S} s")
        _stop_all()
        os._exit(3)
    signal.signal(signal.SIGALRM, on_alarm)
    signal.alarm(MASTER_TIMEOUT_S)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--mesh", action="store_true",
                    help="four chips: the mesh phase and nothing else")
    args = ap.parse_args(argv)
    _arm_watchdog()

    say(f"deployment: mgbench Pokec medium, seed {args.seed}; "
        f"reduced: {REDUCED}")
    last_path = os.path.join(REPO, "chiprun_out", "chip_smoke_last.json")
    previous = {}
    if os.path.exists(last_path):
        with open(last_path) as f:
            previous = json.load(f)

    src, dst = make_graph(args.seed, NODES, EDGES)
    workdir = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        if args.mesh:
            report = phase_mesh(src, dst, NODES)
        else:
            report = phase_served(src, dst, NODES, workdir)
            phase_daemon(src, dst, NODES, workdir)
            if "cold_call_s" in previous:
                say(f"cold CALL {report['cold_call_s']:.3f} s from "
                    f"{report['cache_entries_before']} cache entries; "
                    f"the run before: {previous['cold_call_s']:.3f} s "
                    f"from {previous['cache_entries_before']}")
            os.makedirs(os.path.dirname(last_path), exist_ok=True)
            with open(last_path, "w") as f:
                json.dump(report, f)
    except SmokeFailure as e:
        say(f"FAILED: {e}")
        return 1
    finally:
        _stop_all()
        shutil.rmtree(workdir, ignore_errors=True)
    entries = _cache_entries(report["cache_dir"])
    say(f"compile cache: {report['cache_dir']}, {entries} entries, "
        f"{entries - report['cache_entries_before']} added by this run")
    default_dir = os.path.join(REPO, ".jax_cache")
    if report["cache_dir"] != default_dir:
        say(f"  and nowhere else: {default_dir} holds "
            f"{_cache_entries(default_dir)} entries")
    print(json.dumps({"ok": True, "device": report["device"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
