"""The default semantics: the ten names of ``reference.py`` over its
``GraphState``.

A semantics module says, for each name a class may give as its
``reference``, what the right answer is and how the harness holds a
response to it (``MODES``):

  write            ``apply(name, state, params)`` is its effect
  exact_in_order   one client (any number where the mix never
                   writes): rows == ``answer`` on the state as of that
                   request
  between          many clients: a read of the window lies ``within``
                   the ``bounds`` of the window's first and last state,
                   and equals ``answer`` once quiesced
  vector_top       one client: float rows [id, value], best first,
                   against ``vector`` on the state as of that request,
                   under the cell's limit, and against the vector
                   without the last write (the stale-read test)

and, for a mix's ``readback`` items, ``readback(name, state)`` (the
rows the final state should give) and ``readback_params(name, state)``
(the parameters of the item's query).
"""

from __future__ import annotations

import numpy as np

import reference
from reference import within  # noqa: F401 — the between mode's test

MODES = {
    "point_read": "between", "one_hop": "between", "two_hop": "between",
    "agg_filter": "between", "two_hop_agg": "between",
    "pagerank_top": "vector_top",
    "age_increment": "write", "add_edge": "write", "add_edges": "write",
    "add_vertex": "write",
}


def apply(name: str, state, params: dict) -> None:
    state.apply(name, params)


def answer(name: str, state, params: dict):
    if MODES.get(name) not in ("between", "exact_in_order"):
        raise ValueError(f"no read semantics named {name!r}")
    return getattr(state, name)(params)


def bounds(name: str, params: dict, before, after):
    return reference.read_bounds(name, params, before, after)


def vector(name: str, state, params: dict, x0=None):
    if name != "pagerank_top":
        raise ValueError(f"no vector semantics named {name!r}")
    rank, _ = reference.pagerank(*state.edge_arrays(), state.n_loaded, x0=x0)
    return rank


def added_pairs(state) -> list:
    return sorted({(a, b) for a, b in state.added})


def readback_params(name: str, state) -> dict:
    if name == "added_pairs":
        return {"pairs": [list(p) for p in added_pairs(state)]}
    raise ValueError(f"no read-back parameters named {name!r}")


def readback(name: str, state) -> list:
    if name == "age_rows":
        return state.age_rows()
    if name == "out_degree_rows":
        return state.out_degree_rows()
    if name == "added_edge_rows":
        pairs = added_pairs(state)
        if not pairs:
            return []
        src, dst = state.edge_arrays()
        big = int(max(src.max(), dst.max())) + 1
        codes = src * big + dst
        wanted = np.asarray([a * big + b for a, b in pairs], dtype=np.int64)
        hit = codes[np.isin(codes, wanted)]
        uniq, counts = np.unique(hit, return_counts=True)
        return [[int(c // big), int(c % big), int(n)]
                for c, n in zip(uniq, counts)]
    raise ValueError(f"no read-back reference named {name!r}")
