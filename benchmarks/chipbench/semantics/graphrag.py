"""The semantics of hybrid retrieval: exact cosine kNN over every
``:User`` that holds an embedding, and kNN -> 2-hop expand ->
personalized-PageRank rerank, in numpy/scipy float64.

Imports nothing of the program. The state is ``reference.GraphState``
(ids, ages, the edge list) plus the embeddings: a float64 base matrix
that every copy shares, and a dict of the rows overwritten or inserted
since, so that ``copy()`` costs what has changed.

The names a class may give as its ``reference``:

  doc_insert       write: a new ``:User`` with its embedding and its
                   out-edges to ``friends``
  embed_update     write: one ``:User``'s embedding replaced
  knn              vector_top: the cosine similarity of ``$q`` with
                   every id. The vector is indexed by id and as long as
                   the highest id the state holds; an id with no vertex
                   or no embedding reads ``ABSENT``
  hybrid_retrieve  vector_top: the score of every id, as below

``hybrid_retrieve`` (``graphrag.retrieve(property, $q, 10, 2, 20)``):

  seeds    the 10 ids with the highest cosine similarity to ``$q``
  mask     ids within 2 hops of a seed, edges taken in both directions
           (the seeds themselves included)
  rank     personalized PageRank, as ``ops/pagerank.py``'s
           ``_ppr_setup`` / ``_ppr_epilogue`` state it. With S the seed
           set, d = 0.85, out(u) the number of edges leaving u (parallel
           edges counted as often as they occur):

             p[v]   = 1/|S| for v in S, else 0         (restart)
             D      = {u : out(u) = 0}                 (dangling)
             x_0    = p
             x_t+1  = (1 - d) p
                      + d (sum over edges u->v of x_t[u] / out(u)
                           + p[v] * sum over u in D of x_t[u])

           so restart mass and dangling mass both return to the seeds,
           and the ranks sum to 1 with no normalisation at the end.
  score    rank where masked, else 0.

Departures from the program, each on the side of exactness: float64
where the program computes float32; the iteration runs until the L1
change is under 1e-10 (at most 1000 rounds), where the program stops
under ``tol`` 1e-6 or after 100 (with d = 0.85 it needs about 90); the
seeds are taken from the float64 similarities, so a request whose 10th
and 11th best lie closer than float32 resolves would differ by whole
nodes — the data set's query generator leaves a margin there
(``datasets/pokec_embedded.py``). No screening pass: every similarity
is the float64 product.
"""

from __future__ import annotations

import numpy as np

import reference
import seams

_pokec = seams.load_module(None, "semantics", "pokec_graph")

MODES = {"doc_insert": "write", "embed_update": "write",
         "knn": "vector_top", "hybrid_retrieve": "vector_top"}

SEEDS, HOPS, DAMPING = 10, 2, 0.85
PPR_TOL, PPR_MAX_ROUNDS = 1e-10, 1000

#: what ``knn`` reads for an id that holds no embedding: under every cosine
ABSENT = -2.0


class RagState(reference.GraphState):
    """``GraphState`` and the embeddings by id."""

    def __init__(self, n_nodes: int, src, dst, base: np.ndarray):
        super().__init__(n_nodes, src, dst)
        self.base = np.asarray(base, dtype=np.float64)     # shared, as loaded
        self.base_norm = np.linalg.norm(self.base, axis=1)  # shared
        self.rows: dict = {}        # id -> vector written since the load
        self.top_id = int(n_nodes) - 1
        self.shared: dict = {}      # what is built once from the loaded edges

    def copy(self) -> "RagState":
        other = super().copy()
        other.__class__ = RagState
        other.base, other.base_norm = self.base, self.base_norm
        other.rows = dict(self.rows)
        other.top_id = self.top_id
        other.shared = self.shared
        return other

    def vector_of(self, i: int):
        if i in self.rows:
            return self.rows[i]
        return self.base[i] if 0 <= i < self.n_loaded else None


def apply(name: str, state: RagState, params: dict) -> None:
    if name == "doc_insert":
        new = int(params["id"])
        state.apply("add_vertex", {"id": new})
        state.apply("add_edges",
                    {"pairs": [[new, int(f)] for f in params["friends"]]})
        state.rows[new] = np.asarray(params["v"], dtype=np.float64)
        state.top_id = max(state.top_id, new)
    elif name == "embed_update":
        if int(params["id"]) not in state.age:
            raise ValueError(f"vertex {params['id']} does not exist")
        state.rows[int(params["id"])] = np.asarray(params["v"],
                                                   dtype=np.float64)
    else:
        raise ValueError(f"no write semantics named {name!r}")


def answer(name: str, state, params: dict):
    """No class of these semantics is held exactly, row for row."""
    raise ValueError(f"no exact read semantics named {name!r}")


def cosine_all(state: RagState, q) -> np.ndarray:
    """The cosine similarity of `q` with every id up to the highest."""
    q = np.asarray(q, dtype=np.float64)
    q = q / max(float(np.linalg.norm(q)), 1e-300)
    out = np.full(state.top_id + 1, ABSENT)
    out[:state.n_loaded] = (state.base @ q) \
        / np.maximum(state.base_norm, 1e-300)
    for i, v in state.rows.items():
        out[i] = float(v @ q) / max(float(np.linalg.norm(v)), 1e-300)
    return out


def top_ids(values: np.ndarray, k: int) -> np.ndarray:
    """The ids of the k best, best first; ties by lower id."""
    return reference.top_ranks(values, k)[0]


def _loaded_matrix(state: RagState):
    """dst x src over the loaded edges, parallel edges summed; built
    once and shared by every copy."""
    if "mat" not in state.shared:
        import scipy.sparse as sp
        n = state.n_loaded
        state.shared["mat"] = sp.csr_matrix(
            (np.ones(len(state._src0)), (state._dst0, state._src0)),
            shape=(n, n))
        state.shared["mat_t"] = state.shared["mat"].T.tocsr()
        state.shared["out"] = np.bincount(
            state._src0, minlength=n).astype(np.float64)
    return state.shared["mat"], state.shared["mat_t"], state.shared["out"]


class _Edges:
    """The state's edges as two products: along the edges (`forward`,
    y[v] = sum of x[u] over u->v) and against them (`backward`), the
    loaded edges from the shared matrix and the written ones beside."""

    def __init__(self, state: RagState):
        import scipy.sparse as sp
        self.n0, self.n = state.n_loaded, state.top_id + 1
        self.mat, self.mat_t, out0 = _loaded_matrix(state)
        added = np.asarray(state.added, dtype=np.int64).reshape(-1, 2)
        self.extra = sp.csr_matrix(
            (np.ones(len(added)), (added[:, 1], added[:, 0])),
            shape=(self.n, self.n))
        self.extra_t = self.extra.T.tocsr()
        self.out = np.zeros(self.n)
        self.out[:self.n0] = out0
        self.out += np.bincount(added[:, 0], minlength=self.n)

    def forward(self, x: np.ndarray) -> np.ndarray:
        y = self.extra @ x
        y[:self.n0] += self.mat @ x[:self.n0]
        return y

    def backward(self, x: np.ndarray) -> np.ndarray:
        y = self.extra_t @ x
        y[:self.n0] += self.mat_t @ x[:self.n0]
        return y


def khop_mask(edges: _Edges, seeds, hops: int) -> np.ndarray:
    reach = np.zeros(edges.n)
    reach[np.asarray(seeds)] = 1.0
    for _ in range(hops):
        reach = ((reach + edges.forward(reach) + edges.backward(reach))
                 > 0).astype(np.float64)
    return reach > 0


def personalized_pagerank(edges: _Edges, seeds, damping: float = DAMPING,
                          tol: float = PPR_TOL,
                          max_rounds: int = PPR_MAX_ROUNDS):
    """(ranks, rounds): the module docstring's equations."""
    p = np.zeros(edges.n)
    p[np.asarray(seeds)] = 1.0
    p /= p.sum()
    inv = np.where(edges.out > 0, 1.0 / np.maximum(edges.out, 1.0), 0.0)
    dangling = edges.out == 0
    x = p.copy()
    rounds = 0
    for rounds in range(1, max_rounds + 1):
        new = (1.0 - damping) * p + damping * (
            edges.forward(x * inv) + x[dangling].sum() * p)
        change = np.abs(new - x).sum()
        x = new
        if change < tol:
            break
    return x, rounds


def hybrid_scores(state: RagState, q, seeds_k: int = SEEDS,
                  hops: int = HOPS) -> np.ndarray:
    seeds = top_ids(cosine_all(state, q), seeds_k)
    edges = _Edges(state)
    rank, _ = personalized_pagerank(edges, seeds)
    return np.where(khop_mask(edges, seeds, hops), rank, 0.0)


def vector(name: str, state: RagState, params: dict, x0=None):
    if name == "knn":
        return cosine_all(state, params["q"])
    if name == "hybrid_retrieve":
        return hybrid_scores(state, params["q"])
    raise ValueError(f"no vector semantics named {name!r}")


def readback_params(name: str, state: RagState) -> dict:
    if name == "written_ids":
        return {"ids": sorted(state.rows)}
    return _pokec.readback_params(name, state)


def readback(name: str, state: RagState) -> list:
    """``embedding_rows``: one row [id, position, value] per component
    of every embedding written since the load (a double survives
    PackStream and the property store bit for bit, so the rows are
    held exactly). The written edges are the default semantics'."""
    if name == "embedding_rows":
        return [[i, j, float(x)] for i in sorted(state.rows)
                for j, x in enumerate(state.rows[i])]
    return _pokec.readback(name, state)
