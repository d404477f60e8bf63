"""The plain reference: data from the seed, and the same semantics in
numpy/scipy float64 and Python integers.

Imports nothing of the program. The generator is
``bench.generate_graph``'s (copied, so a later PR cannot move the
yardstick): uniform sources, squared-sample destinations, which gives
a heavy-tailed in-degree toward low ids. ``age = id % 80``.

Cypher's rule that one path never uses the same relationship twice is
applied wherever a pattern has two hops.
"""

from __future__ import annotations

import numpy as np

DAMPING = 0.85


def draw_edges(rng, n_nodes: int, n_edges: int):
    """The dataset's generator: the loaded graph and every edge a mix
    writes later come from these two draws."""
    src = rng.integers(0, n_nodes, n_edges, dtype=np.int64)
    dst = (rng.random(n_edges) ** 2 * n_nodes).astype(np.int64)
    return src, dst


def make_graph(seed: int, n_nodes: int, n_edges: int):
    return draw_edges(np.random.default_rng(seed), n_nodes, n_edges)


def round_bf16(x: np.ndarray) -> np.ndarray:
    """Round float values to bfloat16 (nearest even), returned as float64."""
    bits = np.asarray(x, dtype=np.float32).view(np.uint32).astype(np.uint64)
    bits = (bits + 0x7FFF + ((bits >> 16) & 1)) & 0xFFFF0000
    return bits.astype(np.uint32).view(np.float32).astype(np.float64)


def pagerank(src, dst, n_nodes: int, x0=None, damping: float = DAMPING,
             tol: float = 1e-12, max_iterations: int = 500,
             precision: str = "float64"):
    """Power iteration; restart and dangling mass spread uniformly, as
    the program's epilogue does. Returns (ranks, iterations).

    ``precision="bf16"`` is the low-precision control: every edge's
    contribution ``rank[src] / outdeg[src]`` is rounded to bfloat16
    before it is summed in float64 — the one rounding the program's
    bf16 route makes."""
    import scipy.sparse as sp
    deg = np.bincount(src, minlength=n_nodes).astype(np.float64)
    inv = np.where(deg > 0, 1.0 / np.maximum(deg, 1.0), 0.0)
    ones = np.ones(len(src))
    mat = sp.csr_matrix((ones, (dst, src)), shape=(n_nodes, n_nodes))
    dangling = deg == 0
    p = 1.0 / n_nodes
    rank = np.full(n_nodes, p) if x0 is None else np.asarray(x0, np.float64)
    it = 0
    for it in range(1, max_iterations + 1):
        contrib = rank * inv
        if precision == "bf16":
            contrib = round_bf16(contrib)
        new = (1.0 - damping) * p \
            + damping * (mat @ contrib + rank[dangling].sum() * p)
        err = np.abs(new - rank).sum()
        rank = new
        if err <= tol:
            break
    return rank, it


def top_ranks(ranks: np.ndarray, k: int):
    """(ids, ranks) of the k best, best first; ties by lower id."""
    order = np.lexsort((np.arange(len(ranks)), -ranks))[:k]
    return order, ranks[order]


class GraphState:
    """The deployment's logical state: ages by id, and the edge list.

    Writes are the mixes' three: an increment, an inserted edge, an
    inserted vertex. All commute, so the state after a set of
    acknowledged writes does not depend on their order."""

    def __init__(self, n_nodes: int, src, dst):
        self.n_loaded = int(n_nodes)
        self.age = {i: i % 80 for i in range(n_nodes)}
        self._src0 = np.asarray(src, dtype=np.int64)    # as loaded, shared
        self._dst0 = np.asarray(dst, dtype=np.int64)
        self.added: list = []                           # [a, b] pairs since
        self._out = None

    def copy(self) -> "GraphState":
        other = GraphState.__new__(GraphState)
        other.n_loaded = self.n_loaded
        other.age = dict(self.age)
        other._src0, other._dst0 = self._src0, self._dst0
        other.added = list(self.added)
        other._out = None
        return other

    # --- writes -----------------------------------------------------------

    def apply(self, kind: str, params: dict) -> None:
        if kind == "age_increment":
            self.age[params["id"]] += 1
        elif kind == "add_edge":
            self.added.append([params["a"], params["b"]])
            self._out = None
        elif kind == "add_edges":
            self.added.extend([a, b] for a, b in params["pairs"])
            self._out = None
        elif kind == "add_vertex":
            if params["id"] in self.age:
                raise ValueError(f"vertex {params['id']} exists")
            self.age[params["id"]] = params["id"] % 80
        else:
            raise ValueError(f"no write semantics named {kind!r}")

    # --- reads ------------------------------------------------------------

    def out(self) -> dict:
        if self._out is None:
            out: dict = {}
            src, dst = self.edge_arrays()
            for s, d in zip(src.tolist(), dst.tolist()):
                out.setdefault(s, []).append(d)
            self._out = out
        return self._out

    def point_read(self, params):
        return [[self.age[params["id"]]]]

    def one_hop(self, params):
        return [[len(self.out().get(params["id"], ()))]]

    def two_hop(self, params):
        """count(m) of (n)-[:FRIEND*2..2]->(m): paths of two distinct
        relationships. The second equals the first only where the
        first is a self-loop, once each."""
        out = self.out()
        n = params["id"]
        firsts = out.get(n, ())
        total = sum(len(out.get(x, ())) for x in firsts)
        return [[total - sum(1 for x in firsts if x == n)]]

    def agg_filter(self, params=None, over: int = 40):
        ages = [a for a in self.age.values() if a > over]
        if not ages:
            return [[0, None, None, None]]
        return [[len(ages), sum(ages), min(ages), max(ages)]]

    def two_hop_agg(self, params=None, below: int = 2, ages=None):
        """count(m) of (a)-[:FRIEND]->(b)-[:FRIEND]->(m) WHERE a.age <
        below. ``ages`` lets a bound be taken with another state's ages."""
        age = self.age if ages is None else ages
        out = self.out()
        total = 0
        for a, firsts in out.items():
            if age[a] < below:
                for b in firsts:
                    total += len(out.get(b, ())) - (1 if a == b else 0)
        return [[total]]

    def out_degree_rows(self):
        return sorted([a, len(ds)] for a, ds in self.out().items())

    def age_rows(self):
        return sorted([i, a] for i, a in self.age.items())

    def edge_arrays(self):
        """(src, dst) as loaded plus every edge written since."""
        if not self.added:
            return self._src0, self._dst0
        added = np.asarray(self.added, dtype=np.int64)
        return (np.concatenate([self._src0, added[:, 0]]),
                np.concatenate([self._dst0, added[:, 1]]))


def read_bounds(kind: str, params, before: GraphState, after: GraphState):
    """[low, high] rows that a snapshot read taken between two states
    may return, where only the mixes' three writes ran in between. Ages
    and edges only grow, so every read but one is monotone; the
    filtered two-hop count falls with ages and grows with edges."""
    if kind == "two_hop_agg":
        low = before.two_hop_agg(params, ages=after.age)
        high = after.two_hop_agg(params, ages=before.age)
        return low, high
    low, high = getattr(before, kind)(params), getattr(after, kind)(params)
    if kind == "agg_filter":
        # min(age | age > 40) is not monotone; it stays at 41 or above
        low[0][2] = 41
        high[0][2] = max(low[0][3], high[0][3])
    return low, high


def within(rows, low, high) -> bool:
    """Every cell of the one returned row lies in [low, high]."""
    if len(rows) != 1 or len(rows[0]) != len(low[0]):
        return False
    return all(v is not None and lo <= v <= hi
               for v, lo, hi in zip(rows[0], low[0], high[0]))
