"""Gate smoke for the streaming ingestion plane (r17, mgstream): a
WAL-backed FILE stream driven end-to-end through the Cypher surface —
CREATE/START STREAM, transactional-offset ingest, a consumer kill +
cold restart resuming from the durable offset (exactly-once), a poison
batch quarantined to the dead-letter buffer with the loop alive, an
AFTER-COMMIT trigger firing on ingested batches, the backpressure
probe, and the stream_lag health check flipping /health.

Sized for the dev gate (~seconds, any host): this proves the plane
WORKS; it measures no throughput.

Usage: python -m tools.stream_smoke
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import tempfile
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

N_FIRST = 8      # ingested before the kill
N_WHILE_DEAD = 5  # appended while the consumer is down


def log(msg: str) -> None:
    print(f"stream-smoke: {msg}", flush=True)


def fail(msg: str) -> int:
    log(f"FAIL: {msg}")
    return 1


def _produce(path: str, ids) -> None:
    with open(path, "a", encoding="utf-8") as f:
        for i in ids:
            f.write(json.dumps({
                "query": "CREATE (:Ev {id: $id})",
                "parameters": {"id": i}}) + "\n")


def _wait(pred, timeout: float = 15.0) -> bool:
    deadline = time.time() + timeout
    while time.time() < deadline:
        if pred():
            return True
        time.sleep(0.05)
    return False


def main() -> int:
    from memgraph_tpu.observability import stats as mgstats
    from memgraph_tpu.observability.metrics import global_metrics
    from memgraph_tpu.query.interpreter import (Interpreter,
                                                InterpreterContext)
    from memgraph_tpu.query.streams import streams_of
    from memgraph_tpu.storage import InMemoryStorage, StorageConfig
    from memgraph_tpu.storage.durability.recovery import (recover,
                                                          wire_durability)
    from memgraph_tpu.storage.kvstore import KVStore

    workdir = tempfile.mkdtemp(prefix="stream-smoke-")
    feed = os.path.join(workdir, "feed.jsonl")
    open(feed, "w").close()
    storage = InMemoryStorage(StorageConfig(
        durability_dir=os.path.join(workdir, "data"), wal_enabled=True))
    recover(storage)
    wal = wire_durability(storage)
    ictx = InterpreterContext(storage)
    ictx.kvstore = KVStore(os.path.join(workdir, "kv.db"))
    interp = Interpreter(ictx, system=True)

    def count() -> int:
        _c, rows, _s = interp.execute("MATCH (e:Ev) RETURN count(e)")
        return rows[0][0]

    try:
        # AFTER-COMMIT trigger riding the ingest path (satellite: its
        # failures are counted+logged, its firings metered)
        interp.execute(
            "CREATE TRIGGER audit ON CREATE AFTER COMMIT "
            "EXECUTE MERGE (c:Audit) SET c.n = coalesce(c.n, 0) + 1")
        interp.execute(
            f"CREATE FILE STREAM smoke TOPICS '{feed}' "
            f"TRANSFORM transform.cypher BATCH_SIZE 4 BATCH_INTERVAL 50")
        interp.execute("START STREAM smoke")
        _produce(feed, range(N_FIRST))
        if not _wait(lambda: count() >= N_FIRST):
            return fail(f"initial ingest stalled at {count()}/{N_FIRST}")
        log(f"{N_FIRST} records ingested through the FILE stream")

        if not storage.stream_offsets.get("smoke"):
            return fail("no transactional offset in storage.stream_offsets")
        if storage.stream_offsets["smoke"] != os.path.getsize(feed):
            return fail(
                f"WAL offset {storage.stream_offsets['smoke']} != file "
                f"size {os.path.getsize(feed)}")
        log(f"WAL offset record exact: {storage.stream_offsets['smoke']} "
            "bytes (rides the ingest commit)")

        # consumer kill mid-stream (the chaos hook: no graceful ack),
        # records appended while dead, cold restart resumes from the
        # durable offset — exactly-once
        stream = streams_of(ictx)._get("smoke")
        stream.kill()
        _produce(feed, range(N_FIRST, N_FIRST + N_WHILE_DEAD))
        interp.execute("START STREAM smoke")
        total = N_FIRST + N_WHILE_DEAD
        if not _wait(lambda: count() >= total):
            return fail(f"post-restart ingest stalled at {count()}/{total}")
        _c, rows, _s = interp.execute(
            "MATCH (e:Ev) RETURN e.id, count(*) ORDER BY e.id")
        ids = {r[0]: r[1] for r in rows}
        if ids != {i: 1 for i in range(total)}:
            return fail(f"exactly-once broken across kill/restart: {ids}")
        log(f"consumer kill -> cold restart -> {total} ids exactly once")

        # trigger fired on ingested batches, meters live
        _c, rows, _s = interp.execute("MATCH (c:Audit) RETURN c.n")
        if not rows or not rows[0][0]:
            return fail("AFTER COMMIT trigger never fired on ingest")
        snap = {n: v for n, _k, v in global_metrics.snapshot()}
        if not snap.get("trigger.fired_total"):
            return fail("trigger.fired_total not counted")
        if not snap.get("stream.batches_total"):
            return fail("stream.batches_total not counted")
        log(f"trigger fired {rows[0][0]}x on ingest; stream metrics live "
            f"(batches={snap['stream.batches_total']})")

        # poison batch: quarantined to the dead-letter buffer, offset
        # advanced, loop ALIVE — then a good record still ingests
        with open(feed, "a", encoding="utf-8") as f:
            f.write(json.dumps({"query": "THIS IS NOT CYPHER"}) + "\n")
        if not _wait(lambda: len(stream.dead_letter) >= 1):
            return fail("poison batch never quarantined")
        if not stream.running:
            return fail("stream wedged/stopped by the poison batch")
        _produce(feed, [total])
        if not _wait(lambda: count() >= total + 1):
            return fail("ingest after quarantine stalled")
        log("poison batch dead-lettered, offset advanced, loop alive")

        # backpressure probe + the stream_lag health check
        plane = mgstats.global_saturation
        if plane.ingest_pressure() is not None:
            return fail("ingest_pressure tripped on an idle plane")
        global_metrics.set_gauge("replication.replica_lag.smoketest",
                                 plane.max_replica_lag + 1)
        if plane.ingest_pressure() != "replication_lag":
            return fail("backpressure probe missed replication lag")
        global_metrics.set_gauge("replication.replica_lag.smoketest", 0.0)
        global_metrics.set_gauge("stream.lag.smoke",
                                 plane.max_stream_lag + 1)
        verdict = plane.evaluate(ictx)
        if verdict["ready"] or not any(
                "stream_lag" in r.get("check", "")
                for r in verdict["reasons"]):
            return fail(f"stream_lag did not flip /health: {verdict}")
        global_metrics.set_gauge("stream.lag.smoke", 0.0)
        if not plane.evaluate(ictx)["ready"]:
            return fail("health did not recover after lag cleared")
        log("backpressure probe + stream_lag health flip OK")

        interp.execute("STOP STREAM smoke")
        interp.execute("DROP STREAM smoke")
        interp.execute("DROP TRIGGER audit")
    finally:
        try:
            streams_of(ictx).stop_all()
        finally:
            wal.close()
            shutil.rmtree(workdir, ignore_errors=True)
    log("clean shutdown — PASS")
    return 0


if __name__ == "__main__":
    sys.exit(main())
