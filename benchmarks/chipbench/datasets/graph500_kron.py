"""The Graph500 data set: a Kronecker graph cleaned as LDBC Graphalytics
cleans its graph500-N sets, with a dyadic weight on every relationship.

Graph500 v3.0's generator from the deployment's ``graph_seed``: 2^scale
labels, ``edgefactor`` x 2^scale edges, each placed bit by bit in a
quadrant of the initiator (A, B, C, D) = (0.57, 0.19, 0.19, 0.05), the
labels then randomly permuted. Graphalytics's cleaning: undirected, no
self-loop, no pair twice, no isolated vertex. The vertices that remain
are renumbered 0..n-1 in ascending order of their permuted label, so
every tie-break by the smallest id is the label order's; a relationship
runs from the lower id to the higher. Weights are Graph500's U[0, 1)
quantised to 10 bits, k / 1024 with k uniform in 0..1023: a float32 sum
of up to 2^14 of them is exact, so distances can be held exactly.

Loaded over Bolt by the deployment's own statements: the index, the
vertices in ascending id (so a vertex's dense index on the device is its
id), then the relationships, in ``UNWIND`` batches on one connection.

``GENERATORS`` (a plan looks here before its own three):

  kron_burst   ``edges`` new relationships, each a Kronecker-drawn pair
               (the same initiator and permutation) mapped onto loaded
               vertices, with a dyadic weight. A pair is redrawn where an
               endpoint's label was dropped as isolated, where it is a
               self-loop, a pair of the loaded graph or a pair this
               plan's earlier bursts drew: the graph stays simple

The generator gets a plan and a spec and nothing else, so the graph it
draws against is that of the last ``make(config)`` of this process:
``run_cell`` makes the data set before it makes a plan.
"""

from __future__ import annotations

import time

import numpy as np

#: Graph500 v3.0's initiator (A, B, C; D = 1 - A - B - C)
INITIATOR = (0.57, 0.19, 0.19)
WEIGHT_STEPS = 1024

_GRAPH: dict = {}       # the last make(config)'s generator state


def kronecker_pairs(rng, scale: int, count: int):
    """``count`` Kronecker edges over 2^scale labels, not yet permuted."""
    a, b, c = INITIATOR
    c_norm, a_norm = c / (1.0 - (a + b)), a / (a + b)
    i = np.zeros(count, dtype=np.int64)
    j = np.zeros(count, dtype=np.int64)
    for bit in range(scale):
        i_bit = rng.random(count) > a + b
        j_bit = rng.random(count) > np.where(i_bit, c_norm, a_norm)
        i += i_bit.astype(np.int64) << bit
        j += j_bit.astype(np.int64) << bit
    return i, j


def dyadic_weights(rng, count: int) -> np.ndarray:
    return rng.integers(0, WEIGHT_STEPS, count) / WEIGHT_STEPS


class KronState:
    """The loaded graph (shared by every copy) and the relationships
    written since, in order: ``[low id, high id, weight]``."""

    def __init__(self, n: int, src, dst, weights):
        self.n_loaded = int(n)
        self.src, self.dst, self.weights = src, dst, weights
        self.added: list = []

    def copy(self) -> "KronState":
        other = KronState(self.n_loaded, self.src, self.dst, self.weights)
        other.added = list(self.added)
        return other

    def edge_arrays(self):
        """(src, dst, weights) of every relationship, loaded then added."""
        if not self.added:
            return self.src, self.dst, self.weights
        added = np.asarray(self.added, dtype=np.float64)
        return (np.concatenate([self.src, added[:, 0].astype(np.int64)]),
                np.concatenate([self.dst, added[:, 1].astype(np.int64)]),
                np.concatenate([self.weights, added[:, 2]]))


def generate(scale: int, edgefactor: int, seed: int) -> dict:
    """The cleaned graph and what a burst needs to draw more of it."""
    rng = np.random.default_rng([seed, 0x6500])
    n_labels = 1 << scale
    i, j = kronecker_pairs(rng, scale, edgefactor * n_labels)
    perm = rng.permutation(n_labels)
    i, j = perm[i], perm[j]
    lo, hi = np.minimum(i, j), np.maximum(i, j)
    codes = np.unique((lo * n_labels + hi)[lo != hi])
    lo, hi = codes // n_labels, codes % n_labels
    labels = np.unique(np.concatenate([lo, hi]))
    id_of = np.full(n_labels, -1, dtype=np.int64)
    id_of[labels] = np.arange(len(labels))
    src, dst = id_of[lo], id_of[hi]
    n = len(labels)
    return {"key": (scale, edgefactor, seed), "scale": scale, "n": n,
            "src": src, "dst": dst,
            "weights": dyadic_weights(rng, len(src)),
            "perm": perm, "id_of": id_of,
            "codes": set((src * n + dst).tolist())}


def _graph(config: dict) -> dict:
    key = (int(config["scale"]), int(config["edgefactor"]),
           int(config["graph_seed"]))
    if _GRAPH.get("key") != key:
        _GRAPH.clear()
        _GRAPH.update(generate(*key))
    return _GRAPH


def make(config: dict) -> KronState:
    g = _graph(config)
    return KronState(g["n"], g["src"], g["dst"], g["weights"])


def key_space(config: dict) -> int:
    return _graph(config)["n"]


def sizes(state: KronState) -> dict:
    return {"n_nodes": state.n_loaded,
            "n_edges": len(state.src) + len(state.added)}


def load(client, config: dict, state: KronState):
    """Index, vertices in ascending id, then the relationships, in
    ``UNWIND`` batches on one connection."""
    spec, n = config["load"], state.n_loaded
    batch = int(spec["batch"])
    t0 = time.perf_counter()
    client.execute(config["index"])
    for start in range(0, n, batch):
        client.execute(spec["nodes_query"],
                       {"ids": list(range(start, min(start + batch, n)))})
    rows = [[a, b, w] for a, b, w in zip(state.src.tolist(),
                                         state.dst.tolist(),
                                         state.weights.tolist())]
    for start in range(0, len(rows), batch):
        client.execute(spec["edges_query"],
                       {"edges": rows[start:start + batch]})
    return time.perf_counter() - t0, n + len(rows)


def kron_burst(plan, spec: dict) -> list:
    g = _GRAPH
    if not g or plan.n_ids != g["n"]:
        raise ValueError("kron_burst: no graph of the plan's key space; "
                         "make(config) comes first")
    n, want = g["n"], int(spec["edges"])
    drawn = plan.__dict__.setdefault("_kron_drawn", set())
    out: list = []
    while len(out) < want:
        i, j = kronecker_pairs(plan.rng, g["scale"], 4 * want)
        a, b = g["id_of"][g["perm"][i]], g["id_of"][g["perm"][j]]
        weights = dyadic_weights(plan.rng, len(a))
        for x, y, w in zip(a.tolist(), b.tolist(), weights.tolist()):
            lo, hi = min(x, y), max(x, y)
            code = lo * n + hi
            if lo < 0 or lo == hi or code in g["codes"] or code in drawn:
                continue
            drawn.add(code)
            out.append([lo, hi, w])
            if len(out) == want:
                break
    return out


GENERATORS = {"kron_burst": kron_burst}
