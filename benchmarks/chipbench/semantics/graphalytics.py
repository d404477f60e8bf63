"""The semantics of the Graphalytics/Graph500 kernels over the data set's
``KronState`` (``datasets/graph500_kron.py``), in numpy/scipy float64.

Imports nothing of the program. The benchmark's copy of the plain
reference ``tests/graphalytics_reference.py``, vectorised for the
published scale (``tests/chipbench/test_graphalytics_cell.py`` holds the
two equal): every relationship is undirected, a graph is the loaded
relationships plus every acknowledged write.

The names a class may give as its ``reference``:

  add_weighted_edges  write: ``edges``, each ``[low id, high id, weight]``
  bfs_level_counts    exact_in_order: Graph500 kernel 2 from ``root``;
                      rows ``[level, vertices at that level]`` by level
  sssp_summary        exact_in_order: Graph500 kernel 3 from ``root``
                      (Dijkstra); one row ``[vertices reached, sum of
                      their distances, the largest]``
  wcc_sizes           exact_in_order: Graphalytics WCC; rows ``[component
                      size, components of that size]``, largest first
  cdlp_sizes          exact_in_order: Graphalytics CDLP, ``ROUNDS``
                      synchronous rounds from label = id, the most
                      frequent neighbour label, ties to the smallest, a
                      vertex without neighbours keeping its own; rows as
                      wcc_sizes' over the communities

Each answer is a label-free histogram or exact sums: distances are sums
of dyadic weights, exact in float32 and float64 alike, and so is their
sum in any order. Departures from the specifications are the
reference's: dyadic weights. The graph is simple, as the data set and
its bursts keep it; a pair related twice is refused.
"""

from __future__ import annotations

import numpy as np
from scipy.sparse import csr_matrix
from scipy.sparse.csgraph import connected_components, dijkstra

MODES = {"add_weighted_edges": "write", "bfs_level_counts": "exact_in_order",
         "sssp_summary": "exact_in_order", "wcc_sizes": "exact_in_order",
         "cdlp_sizes": "exact_in_order"}

#: CDLP's T, as the mix's query asks for it
ROUNDS = 10


def apply(name: str, state, params: dict) -> None:
    if name != "add_weighted_edges":
        raise ValueError(f"no write semantics named {name!r}")
    state.added.extend([int(a), int(b), float(w)] for a, b, w
                       in params["edges"])


class _Views:
    """Both orientations of every relationship of one state, built once
    per state and number of writes: the four reads of a cycle share it."""

    def __init__(self, state):
        n = state.n_loaded
        src, dst, w = state.edge_arrays()
        self.n = n
        self.src = np.concatenate([src, dst])
        self.dst = np.concatenate([dst, src])
        self.hops = csr_matrix((np.ones(len(self.src)),
                                (self.src, self.dst)), shape=(n, n))
        if self.hops.nnz != len(self.src):
            # csr_matrix would sum the weights of a pair given twice
            raise ValueError("a pair of vertices is related twice; the "
                             "data set and its bursts keep the graph simple")
        self.weighted = csr_matrix((np.concatenate([w, w]),
                                    (self.src, self.dst)), shape=(n, n))


def _views(state) -> _Views:
    held = getattr(state, "_views", None)
    if held is None or held[0] != len(state.added):
        held = (len(state.added), _Views(state))
        state._views = held
    return held[1]


def _histogram(groups: np.ndarray) -> list:
    """[[size, how many groups of that size]], largest size first."""
    sizes = np.bincount(np.unique(groups, return_inverse=True)[1])
    size, count = np.unique(sizes, return_counts=True)
    return [[int(s), int(c)] for s, c in zip(size[::-1], count[::-1])]


def cdlp(n: int, src: np.ndarray, dst: np.ndarray,
         rounds: int = ROUNDS) -> np.ndarray:
    """Labels after ``rounds`` synchronous rounds; ``src``/``dst`` hold
    both orientations of every relationship (a vertex's neighbour list,
    one entry per relationship)."""
    label = np.arange(n, dtype=np.int64)
    for _ in range(rounds):
        keys = np.sort(dst * n + label[src])
        start = np.flatnonzero(np.r_[True, keys[1:] != keys[:-1]])
        count = np.diff(np.r_[start, len(keys)])
        vertex, lab = keys[start] // n, keys[start] % n
        # runs are ordered by (vertex, label): per vertex, the largest
        # count, then the first (smallest) label that has it
        first = np.flatnonzero(np.r_[True, vertex[1:] != vertex[:-1]])
        top = np.maximum.reduceat(count, first)
        span = np.diff(np.r_[first, len(vertex)])
        best = np.where(count == np.repeat(top, span), lab, n)
        new = label.copy()
        new[vertex[first]] = np.minimum.reduceat(best, first)
        label = new
    return label


def answer(name: str, state, params: dict):
    views = _views(state)
    if name == "bfs_level_counts":
        level = dijkstra(views.hops, indices=int(params["root"]),
                         unweighted=True)
        reached = level[np.isfinite(level)].astype(np.int64)
        at = np.bincount(reached)
        return [[int(lv), int(c)] for lv, c in enumerate(at) if c]
    if name == "sssp_summary":
        dist = dijkstra(views.weighted, indices=int(params["root"]))
        reached = dist[np.isfinite(dist)]
        return [[int(len(reached)), float(reached.sum()),
                 float(reached.max())]]
    if name == "wcc_sizes":
        _, comp = connected_components(views.hops, directed=False)
        return _histogram(comp)
    if name == "cdlp_sizes":
        return _histogram(cdlp(views.n, views.src, views.dst))
    raise ValueError(f"no read semantics named {name!r}")


def added_pairs(state) -> list:
    return sorted([a, b] for a, b, _ in state.added)


def readback_params(name: str, state) -> dict:
    if name == "added_pairs":
        return {"pairs": added_pairs(state)}
    raise ValueError(f"no read-back parameters named {name!r}")


def readback(name: str, state) -> list:
    """``written_edges``: every written relationship with its weight, by
    (low id, high id)."""
    if name == "written_edges":
        return sorted([int(a), int(b), float(w)] for a, b, w in state.added)
    raise ValueError(f"no read-back reference named {name!r}")
