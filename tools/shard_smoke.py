"""Gate smoke for the sharded OLTP execution plane (r18, mgshard):
spawn 4 shard workers, drive routed point reads/writes, one
scatter-gather read, one cross-shard 2PC transaction, one LIVE
shard-move under the same data, a worker kill + typed-error respawn,
and a clean shutdown.

Sized for the dev gate (~seconds, fork-safe on any host): this proves
the plane WORKS everywhere; it measures no scaling.

Usage: python -m tools.shard_smoke
"""

from __future__ import annotations

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

N_SHARDS = 4
N_USERS = 100


def log(msg: str) -> None:
    print(f"shard-smoke: {msg}", flush=True)


def fail(msg: str) -> int:
    log(f"FAIL: {msg}")
    return 1


def main() -> int:
    from memgraph_tpu.exceptions import WorkerCrashedError
    from memgraph_tpu.sharding import ShardPlane, ShardedClient

    plane = ShardPlane(n_shards=N_SHARDS).start()
    try:
        client = ShardedClient(plane)
        log(f"{N_SHARDS} shard workers up, map epoch "
            f"{plane.map.epoch}: {plane.map.owners}")
        client.ddl("CREATE INDEX ON :User(id)")

        # routed writes + point reads
        for i in range(N_USERS):
            client.write("CREATE (:User {id: $id, age: $age})",
                         {"id": i, "age": i % 40}, key=i)
        for i in (0, 17, 63, 99):
            _c, rows = client.read(
                "MATCH (n:User {id: $id}) RETURN n.age", {"id": i},
                key=i)
            if rows != [[i % 40]]:
                return fail(f"point read {i} returned {rows}")
        log(f"routed {N_USERS} writes + point reads OK")

        # scatter-gather with merge
        _c, rows = client.read(
            "MATCH (n:User) RETURN count(n), sum(n.age)")
        expected_sum = sum(i % 40 for i in range(N_USERS))
        if rows != [[N_USERS, expected_sum]]:
            return fail(f"scatter-gather merged {rows}, expected "
                        f"[[{N_USERS}, {expected_sum}]]")
        log(f"scatter-gather count/sum OK: {rows[0]}")

        # cross-shard 2PC
        k1 = 0
        k2 = next(k for k in range(1, 64)
                  if client.shard_for(k) != client.shard_for(k1))
        out = client.write_multi([
            (k1, "MATCH (n:User {id: $id}) SET n.flag = true",
             {"id": k1}),
            (k2, "MATCH (n:User {id: $id}) SET n.flag = true",
             {"id": k2}),
        ])
        if len(out["shards"]) != 2:
            return fail(f"2PC touched {out['shards']}, expected 2 "
                        "shards")
        _c, rows = client.read(
            "MATCH (n:User) WHERE n.flag RETURN count(n)")
        if rows != [[2]]:
            return fail(f"cross-shard txn visible rows: {rows}")
        log(f"cross-shard 2PC across shards {out['shards']} OK "
            f"(txn {out['txn_id']})")

        # live shard-move: epoch bumps, data survives, stale client
        # bounces then lands
        epoch0 = plane.map.epoch
        moved = client.shard_for(k1)
        new_owner = plane.shard_move(moved)
        if plane.map.epoch <= epoch0:
            return fail("shard-move did not mint a new epoch")
        _c, rows, ack = client.write(
            "MATCH (n:User {id: $id}) SET n.moved = true", {"id": k1},
            key=k1)
        if ack["epoch"] != plane.map.epoch:
            return fail(f"post-move ack epoch {ack['epoch']} != map "
                        f"epoch {plane.map.epoch}")
        _c, rows = client.read("MATCH (n:User) RETURN count(n)")
        if rows != [[N_USERS]]:
            return fail(f"data lost in move: {rows}")
        log(f"shard {moved} moved to {new_owner} (epoch {epoch0} -> "
            f"{plane.map.epoch}), data intact, stale write re-routed")

        # worker kill: typed retryable error + per-shard WAL recovery
        victim = client.shard_for(17)
        plane.kill_worker(victim)
        try:
            plane.request(victim, "read",
                          {"query": "MATCH (n) RETURN count(n)",
                           "params": {}, "epoch": plane.map.epoch})
            return fail("dead worker did not raise the typed error")
        except WorkerCrashedError:
            pass
        _c, rows = client.read(
            "MATCH (n:User {id: 17}) RETURN n.age", key=17)
        if rows != [[17 % 40]]:
            return fail(f"post-respawn recovery lost data: {rows}")
        log(f"shard {victim} kill -> typed error -> respawn + WAL "
            "recovery OK")
    finally:
        plane.close()
    log("clean shutdown — PASS")
    return 0


if __name__ == "__main__":
    sys.exit(main())
