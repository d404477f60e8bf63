"""A plain reference for the four Graphalytics/Graph500 kernels, straight
from the two specifications, and the Graph500 graph they run on.

Imports nothing of the program. A graph is ``n`` vertices with ids
0..n-1 and an undirected multigraph of relationships given as id pairs
(``src``, ``dst``) and, for SSSP, float64 weights. Every answer is per
vertex, indexed by id.

  kronecker(scale, edgefactor, seed)   Graph500 v3.0's generator, then
                                       Graphalytics's graph500-N cleaning
  bfs_levels(n, src, dst, root)        Graph500 kernel 2: hops from root,
                                       -1 where unreached
  sssp(n, src, dst, weights, root)     Graph500 kernel 3: Dijkstra,
                                       inf where unreached
  wcc(n, src, dst)                     Graphalytics WCC: each vertex's
                                       component, named by its least id
  cdlp(n, src, dst, rounds)            Graphalytics CDLP

Departures from the specifications, each on purpose:

* weights are dyadic, k / 1024 with k uniform in 0..1023: Graph500's
  U[0, 1) quantised to 10 bits, so that a float32 sum of up to 2^14 of
  them is exact and a device's distances can be held to these exactly;
* a relationship given twice counts twice in CDLP, as a vertex's
  neighbour list has it twice on the device (the Graphalytics data sets
  are simple, so there it never arises); in SSSP the lighter one wins;
* vertices are renumbered 0..n-1 in ascending order of their permuted
  Graph500 label, isolated ones dropped, which keeps the relative order
  of ids and so every tie-break by the smallest id.
"""

from __future__ import annotations

import heapq
from collections import Counter, deque

import numpy as np

#: Graph500 v3.0's initiator matrix (A, B, C; D = 1 - A - B - C)
INITIATOR = (0.57, 0.19, 0.19)
#: the denominator of a dyadic weight
WEIGHT_STEPS = 1024


def kronecker_pairs(rng, scale: int, count: int):
    """``count`` edges of the Kronecker generator over 2^scale labels,
    before the labels are permuted: per bit, the quadrant by A, B, C, D."""
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


def kronecker(scale: int, edgefactor: int, seed: int):
    """(n, src, dst, weights, label_of): the Graph500 graph at ``scale``
    cleaned as Graphalytics's graph500-N are, undirected and simple (no
    self-loop, no pair twice, no isolated vertex), one relationship per
    pair from the lower id to the higher; ``label_of[id]`` is the
    vertex's permuted Graph500 label."""
    rng = np.random.default_rng([seed, 0x6500])
    n_labels = 1 << scale
    i, j = kronecker_pairs(rng, scale, edgefactor * n_labels)
    perm = rng.permutation(n_labels)
    i, j = perm[i], perm[j]
    lo, hi = np.minimum(i, j), np.maximum(i, j)
    codes = np.unique((lo * n_labels + hi)[lo != hi])
    lo, hi = codes // n_labels, codes % n_labels
    label_of = np.unique(np.concatenate([lo, hi]))
    src = np.searchsorted(label_of, lo)
    dst = np.searchsorted(label_of, hi)
    weights = rng.integers(0, WEIGHT_STEPS, len(src)) / WEIGHT_STEPS
    return len(label_of), src, dst, weights, label_of


def _neighbours(n, src, dst, weights=None):
    """Each vertex's (neighbour, weight) list over both orientations of
    every relationship, one entry per relationship."""
    adj = [[] for _ in range(n)]
    w = np.ones(len(src)) if weights is None else weights
    for a, b, x in zip(np.asarray(src).tolist(), np.asarray(dst).tolist(),
                       np.asarray(w, dtype=np.float64).tolist()):
        adj[a].append((b, x))
        adj[b].append((a, x))
    return adj


def bfs_levels(n, src, dst, root: int) -> np.ndarray:
    level = np.full(n, -1, dtype=np.int64)
    level[root] = 0
    queue = deque([root])
    adj = _neighbours(n, src, dst)
    while queue:
        v = queue.popleft()
        for u, _ in adj[v]:
            if level[u] < 0:
                level[u] = level[v] + 1
                queue.append(u)
    return level


def sssp(n, src, dst, weights, root: int) -> np.ndarray:
    dist = np.full(n, np.inf)
    dist[root] = 0.0
    done = np.zeros(n, dtype=bool)
    heap = [(0.0, root)]
    adj = _neighbours(n, src, dst, weights)
    while heap:
        d, v = heapq.heappop(heap)
        if done[v]:
            continue
        done[v] = True
        for u, w in adj[v]:
            if d + w < dist[u]:
                dist[u] = d + w
                heapq.heappush(heap, (dist[u], u))
    return dist


def wcc(n, src, dst) -> np.ndarray:
    comp = np.full(n, -1, dtype=np.int64)
    adj = _neighbours(n, src, dst)
    for start in range(n):          # ascending: a component's least id
        if comp[start] >= 0:
            continue
        comp[start] = start
        queue = deque([start])
        while queue:
            v = queue.popleft()
            for u, _ in adj[v]:
                if comp[u] < 0:
                    comp[u] = start
                    queue.append(u)
    return comp


def cdlp(n, src, dst, rounds: int = 10) -> np.ndarray:
    """``rounds`` synchronous rounds from label = id: each vertex takes
    the label most frequent among its neighbours, ties to the smallest;
    a vertex without neighbours keeps its label."""
    label = np.arange(n, dtype=np.int64)
    adj = _neighbours(n, src, dst)
    for _ in range(rounds):
        new = label.copy()
        for v in range(n):
            if not adj[v]:
                continue
            counts = Counter(int(label[u]) for u, _ in adj[v])
            top = max(counts.values())
            new[v] = min(lab for lab, c in counts.items() if c == top)
        label = new
    return label
