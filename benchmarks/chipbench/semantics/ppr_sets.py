"""The semantics of personalized-PageRank serving: the rank of every
user for a request's *set* of restart users, in numpy/scipy float64.

Imports nothing of the program. The state is ``reference.GraphState``.

The one name a class may give as its ``reference``:

  ppr_set_top   vector_top: ``params["ids"]`` is the restart set S (user
                ids; a repeated id counts once). With d = 0.85, out(u)
                the number of edges leaving u (parallel edges counted
                as often as they occur), as ``ops/pagerank.py``'s
                ``_build_ppr_batch`` states it:

                  p[v]   = 1/|S| for v in S, else 0       (restart)
                  D      = {u : out(u) = 0}               (dangling)
                  x_0    = p
                  x_t+1  = (1 - d) p
                           + d (sum over edges u->v of x_t[u] / out(u)
                                + p[v] * sum over u in D of x_t[u])

                so restart mass and dangling mass both return to S, and
                the ranks sum to 1.

``ppr_set_direct`` is the plain reference: those equations, as loops.
It costs a solve per request, and at the published size a window holds
more distinct sets than a run has time to solve. ``vector`` is the
shortcut the comparison uses, and a test holds it to the plain one
(``tests/chipbench/test_ppr_sets_semantics.py``): the fixed point
satisfies ``(I - d M) x = c p`` with M the edge sum above and c the
scalar ``(1 - d) + d * (dangling mass of x)``, so x is ``y / sum(y)``
with ``y = (I - d M)^-1 p``, and y is linear in p. With ``y_s`` the
solution for the restart vector ``e_s`` of one user,

    x(S) = mean(y_s for s in S) / sum(mean(y_s for s in S)).

Each ``y_s`` is solved once (``y <- e_s + d M y`` until the L1 change is
under 1e-12, ``BLOCK`` columns at a time), kept for as long as the
state's edges stay what they were, and the data set's whole catalogue
(``datasets/pokec_catalogue.py``) is solved at the first request.

Departures from the program, each on the side of exactness: float64
where the program computes float32; every column iterated to 1e-12 where
the program stops a lane at an L1 change under ``tol`` 1e-6 or after 100
rounds.
"""

from __future__ import annotations

import os

import numpy as np

import reference
import seams

_data = seams.load_module(None, "datasets", "pokec_catalogue")

MODES = {"ppr_set_top": "vector_top"}

DAMPING = 0.85
SOLVE_TOL, SOLVE_MAX_ROUNDS = 1e-12, 1000
BLOCK = 32

#: what is solved for one version of one graph: ((the loaded edges'
#: identity, edges written since, precision), the edges' operator,
#: {id: y_s})
_SOLVED: tuple = (None, None, {})


def apply(name: str, state, params: dict) -> None:
    raise ValueError(f"no write semantics named {name!r}")


def answer(name: str, state, params: dict):
    """No class of these semantics is held exactly, row for row."""
    raise ValueError(f"no exact read semantics named {name!r}")


def members(params: dict) -> list:
    """The restart set: the request's ids, each once."""
    return sorted({int(i) for i in params["ids"]})


# --------------------------------------------------------------------------
# the plain reference
# --------------------------------------------------------------------------

def ppr_set_direct(state, ids, damping: float = DAMPING,
                   tol: float = 1e-15, max_rounds: int = 2000):
    """(ranks, rounds): the module docstring's equations on the set's
    own restart vector, edge by edge."""
    src, dst = state.edge_arrays()
    n = state.n_loaded
    edges = list(zip(src.tolist(), dst.tolist()))
    out = [0] * n
    for u, _v in edges:
        out[u] += 1
    restart = sorted({int(i) for i in ids})
    p = [0.0] * n
    for s in restart:
        p[s] = 1.0 / len(restart)
    x = list(p)
    rounds = 0
    for rounds in range(1, max_rounds + 1):
        dangling = sum(x[u] for u in range(n) if out[u] == 0)
        summed = [0.0] * n
        for u, v in edges:
            summed[v] += x[u] / out[u]
        new = [(1.0 - damping) * p[v]
               + damping * (summed[v] + p[v] * dangling) for v in range(n)]
        change = sum(abs(a - b) for a, b in zip(new, x))
        x = new
        if change < tol:
            break
    return np.asarray(x), rounds


# --------------------------------------------------------------------------
# the shortcut: one solve per restart user
# --------------------------------------------------------------------------

def _operator(state):
    """(dst x src matrix of the edges, parallel edges summed; 1/out)."""
    import scipy.sparse as sp
    src, dst = state.edge_arrays()
    n = state.n_loaded
    out = np.bincount(src, minlength=n).astype(np.float64)
    inv = np.where(out > 0, 1.0 / np.maximum(out, 1.0), 0.0)
    mat = sp.csr_matrix((np.ones(len(src)), (dst, src)), shape=(n, n))
    return mat, inv


def solve_columns(state, ids, precision: str = "float64",
                  damping: float = DAMPING, operator=None) -> np.ndarray:
    """``y_s = (I - d M)^-1 e_s`` for every s of `ids`, as the columns
    of one (n, len(ids)) array. ``precision="bf16"`` is the
    low-precision reading: every edge's contribution ``y[u] / out(u)``
    is rounded to bfloat16 before it is summed in float64, in every
    round, as ``reference.pagerank`` rounds it."""
    mat, inv = operator or _operator(state)
    ids = [int(i) for i in ids]
    unit = np.zeros((state.n_loaded, len(ids)))
    unit[ids, np.arange(len(ids))] = 1.0
    y = unit.copy()
    for _ in range(SOLVE_MAX_ROUNDS):
        contrib = y * inv[:, None]
        if precision == "bf16":
            contrib = reference.round_bf16(contrib)
        new = unit + damping * (mat @ contrib)
        change = np.abs(new - y).sum(axis=0).max()
        y = new
        if change < SOLVE_TOL:
            break
    return y


def solutions(state, ids, precision: str = "float64") -> dict:
    """{id: y_s} for every id asked for. What is solved stays solved
    while the state's edges are what they were; the first call solves
    the data set's catalogue with it, where the ids belong to it. The
    blocks are solved side by side on the host's cores (scipy's sparse
    product releases the interpreter's lock): by now the deployment's
    processes have exited."""
    global _SOLVED
    version = (id(state._src0), len(state.added), precision)
    if _SOLVED[0] != version:
        _SOLVED = (version, _operator(state), {})
    _, operator, held = _SOLVED
    want = [i for i in dict.fromkeys(int(i) for i in ids) if i not in held]
    if want and not held:
        known = _data.current_catalogue()
        if known is not None and set(want) <= set(known.tolist()) \
                and int(known.max()) < state.n_loaded:
            want = [int(i) for i in known]
    blocks = [want[start:start + BLOCK]
              for start in range(0, len(want), BLOCK)]
    if blocks:
        from concurrent.futures import ThreadPoolExecutor
        workers = max(1, min(len(blocks), (os.cpu_count() or 2) - 1))
        with ThreadPoolExecutor(workers) as pool:
            solved = pool.map(
                lambda block: solve_columns(state, block, precision,
                                            operator=operator),
                blocks)
            for block, columns in zip(blocks, solved):
                for j, i in enumerate(block):
                    held[i] = columns[:, j]
    return held


def vector(name: str, state, params: dict, x0=None,
           precision: str = "float64") -> np.ndarray:
    if name != "ppr_set_top":
        raise ValueError(f"no vector semantics named {name!r}")
    restart = members(params)
    held = solutions(state, restart, precision)
    mean = sum(held[s] for s in restart) / len(restart)
    return mean / mean.sum()
