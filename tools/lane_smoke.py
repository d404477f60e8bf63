"""mglane smoke: compiled hit + loud typed fallback + schema-change
invalidation round trip, end to end through the interpreter.

    python -m tools.lane_smoke

Functional on every host (CPU jax included); it measures no speed.
This gate proves the MACHINERY: a lane-eligible query compiles once and
serves from the compiled program, refusal shapes fall back loudly with their
typed reason while answering identically, and index DDL drops every
compiled lane (stale lanes never serve) with results bit-identical to
the serial interpreter before and after.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def log(msg: str) -> None:
    print(f"lane-smoke: {msg}", flush=True)


def fail(msg: str) -> "None":
    log(f"FAIL: {msg}")
    sys.exit(1)


def metric(name: str) -> float:
    from memgraph_tpu.observability.metrics import global_metrics
    return {n: v for n, _k, v in global_metrics.snapshot()}.get(name, 0.0)


def main() -> None:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import numpy as np

    from memgraph_tpu.ops import pipeline as pl
    from memgraph_tpu.query.interpreter import (Interpreter,
                                                InterpreterContext)
    from memgraph_tpu.storage import (InMemoryStorage, StorageConfig,
                                      StorageMode)

    storage = InMemoryStorage(StorageConfig(
        storage_mode=StorageMode.IN_MEMORY_TRANSACTIONAL))
    ctx = InterpreterContext(storage)
    acc = storage.access()
    lid = storage.label_mapper.name_to_id("U")
    page = storage.property_mapper.name_to_id("age")
    rng = np.random.default_rng(5)
    n_nodes = 6000
    vs = []
    for i in range(n_nodes):
        v = acc.create_vertex()
        v.add_label(lid)
        v.set_property(page, int(i % 80))
        vs.append(v)
    te = storage.edge_type_mapper.name_to_id("F")
    for _ in range(24000):
        a, b = rng.integers(0, n_nodes, 2)
        acc.create_edge(vs[a], vs[b], te)
    acc.commit()
    interp = Interpreter(ctx)

    def run(q):
        _, rows, _ = interp.execute(q)
        return rows

    def serial(q):
        os.environ["MEMGRAPH_TPU_DISABLE_PARALLEL"] = "1"
        ctx.invalidate_plans()
        try:
            return run(q)
        finally:
            os.environ.pop("MEMGRAPH_TPU_DISABLE_PARALLEL", None)
            ctx.invalidate_plans()

    agg_q = ("MATCH (n:U) WHERE n.age > 40 RETURN count(*) AS c, "
             "sum(n.age) AS s, min(n.age) AS mn, max(n.age) AS mx")
    hop_q = ("MATCH (a:U)-[:F]->(b)-[:F]->(m) WHERE a.age < 2 "
             "RETURN count(m) AS c")

    # 1. compiled hit: first run compiles, second serves from the cache
    c0, h0 = metric("lane.compiled_total"), metric("lane.hit_total")
    first = run(agg_q)
    if metric("lane.compiled_total") <= c0:
        fail("no lane program compiled for the aggregate tail")
    if metric("lane.hit_total") <= h0:
        fail("aggregate tail did not serve from the lane")
    c1 = metric("lane.compiled_total")
    second = run(agg_q)
    if metric("lane.compiled_total") != c1:
        fail("repeat query recompiled — fingerprint cache broken")
    if first != second:
        fail(f"repeat query changed answers: {first} vs {second}")
    log(f"compiled hit OK: {first[0]} (1 compile, repeat = cache hit)")

    # 2. hop lane parity vs the serial interpreter
    lane_rows = run(hop_q)
    ser_rows = serial(hop_q)
    if lane_rows != ser_rows:
        fail(f"two-hop lane diverges: {lane_rows} vs {ser_rows}")
    log(f"two-hop lane OK: count={lane_rows[0][0]} == serial")

    # 3. loud typed fallback: avg is a refusal shape — identical
    #    answers, reason counted
    avg_q = "MATCH (n:U) RETURN count(*) AS c, avg(n.age) AS a"
    f0 = metric("lane.fallback_total.agg_avg")
    lane_rows = run(avg_q)
    if metric("lane.fallback_total.agg_avg") <= f0:
        fail("avg refusal not counted under lane.fallback_total.agg_avg")
    ser_rows = serial(avg_q)
    if lane_rows != ser_rows:
        fail(f"avg fallback diverges: {lane_rows} vs {ser_rows}")
    log("loud fallback OK: agg_avg counted, results identical")

    # 4. schema-change invalidation round trip
    run(agg_q)
    if pl.resident_programs() == 0:
        fail("expected resident lane programs before DDL")
    run("CREATE INDEX ON :U(age)")
    if pl.resident_programs() != 0:
        fail("CREATE INDEX left compiled lanes resident (stale-lane "
             "hazard)")
    after = run(agg_q)
    oracle = serial(agg_q)
    if after != oracle:
        fail(f"post-DDL lane diverges from interpreter: {after} vs "
             f"{oracle}")
    log("schema invalidation OK: DDL dropped lanes, results identical")

    log("ALL CHECKS PASSED")


if __name__ == "__main__":
    main()
