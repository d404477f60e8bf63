"""Streaming-insert vector index micro-bench: O(delta) refresh vs full
rebuild.

Run: python benchmarks/bench_vector_delta.py [n_vectors] [dim]
"""

import sys
import time

import numpy as np

sys.path.insert(0, ".")

from memgraph_tpu.procedures import vector_search as vs
from memgraph_tpu.query.interpreter import Interpreter, InterpreterContext
from memgraph_tpu.storage import InMemoryStorage


def main(n=20_000, dim=64):
    db = InterpreterContext(InMemoryStorage())
    interp = Interpreter(db)
    rng = np.random.default_rng(0)

    t0 = time.perf_counter()
    acc = db.storage.access()
    pid = db.storage.property_mapper.name_to_id("emb")
    lid = db.storage.label_mapper.name_to_id("V")
    for i in range(n):
        v = acc.create_vertex()
        v.add_label(lid)
        v.set_property(pid, [float(x) for x in rng.random(dim)])
    acc.commit()
    print(f"seeded {n} x {dim} in {time.perf_counter()-t0:.2f}s")

    q = [1.0] + [0.0] * (dim - 1)

    def search():
        _, rows, _ = interp.execute(
            "CALL vector_search.search('emb', $q, 10) YIELD node, similarity "
            "RETURN count(node)", {"q": q})
        return rows

    t0 = time.perf_counter()
    search()
    full_s = time.perf_counter() - t0
    print(f"cold search (full build): {full_s:.3f}s")

    # streaming inserts: one commit + search per batch
    deltas = []
    for i in range(20):
        interp.execute("CREATE (:V {emb: $e})",
                       {"e": [float(x) for x in rng.random(dim)]})
        t0 = time.perf_counter()
        search()
        deltas.append(time.perf_counter() - t0)
    delta_s = sorted(deltas)[len(deltas) // 2]
    print(f"streaming search (delta refresh, median of 20): {delta_s:.3f}s")
    print(f"stats: {vs.STATS}")
    print(f"speedup vs full rebuild per insert: {full_s / delta_s:.1f}x")


if __name__ == "__main__":
    main(*(int(a) for a in sys.argv[1:]))
