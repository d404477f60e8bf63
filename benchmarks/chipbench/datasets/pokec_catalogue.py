"""Pokec with a catalogue: the default data set's graph, index, loader
and sizes (imported, so the graph is the one the medium cells load),
and a fixed set of ``config["catalogue"]`` users that every request's
restart vertices are drawn from.

Why a catalogue: a personalized-PageRank answer is held against a
float64 reference at the published graph size, and one float64 solve at
1.77M edges costs a third of a second. Personalized PageRank is linear
in its restart vector up to one scalar (``semantics/ppr_sets.py``), so
the reference solves each catalogue member once and then checks any
number of distinct *sets* of members in a millisecond each. The
catalogue bounds the reference's cost, not the traffic's variety: 192
members give 54 million sets of 4.

The catalogue is ``config["catalogue"]`` user ids drawn without
replacement from ``graph_seed`` among the users with at least one
out-edge (a restart set of dangling users alone would keep all its
mass: an answer, but not the one a recommender asks for).

``key_space(config)`` is the catalogue's length: a mix's ``keys`` draw
a *slot* of it with their Zipf skew, so a few members are in most
requests, as a few users are in most sessions.

``GENERATORS`` (a plan looks here before its own three):

  source_set   ``set_size`` distinct catalogue members, each drawn by
               the plan's Zipf key (redrawn while it repeats a member
               already in the set), as user ids in the order drawn

The generator gets a plan and a spec and nothing else, so the catalogue
it maps slots through is that of the last ``key_space(config)`` of this
process: ``run_cell`` asks for the key space before it makes a plan.
"""

from __future__ import annotations

import numpy as np

import reference
import seams

_pokec = seams.load_module(None, "datasets", "pokec_synthetic")

make = _pokec.make
load = _pokec.load
sizes = _pokec.sizes

_CATALOGUE: np.ndarray | None = None


def catalogue(config: dict) -> np.ndarray:
    """The catalogue's user ids, from the deployment's sizes and
    ``graph_seed`` alone."""
    n_nodes = int(config["nodes"])
    src, _dst = reference.make_graph(int(config["graph_seed"]), n_nodes,
                                     int(config["edges"]))
    with_out = np.flatnonzero(np.bincount(src, minlength=n_nodes) > 0)
    rng = np.random.default_rng([int(config["graph_seed"]), 0xCA7A])
    return np.sort(rng.choice(with_out, size=int(config["catalogue"]),
                              replace=False)).astype(np.int64)


def key_space(config: dict) -> int:
    global _CATALOGUE
    _CATALOGUE = catalogue(config)
    return len(_CATALOGUE)


def current_catalogue():
    """The catalogue of the last ``key_space(config)``, or None."""
    return _CATALOGUE


def source_set(plan, spec: dict) -> list:
    size = int(spec["set_size"])
    if _CATALOGUE is None or plan.n_ids != len(_CATALOGUE):
        raise ValueError("source_set: no catalogue of the plan's key space; "
                         "key_space(config) comes first")
    if size > plan.n_ids:
        raise ValueError(f"a set of {size} from a catalogue of "
                         f"{plan.n_ids}")
    slots: list = []
    while len(slots) < size:
        slot = plan.keys.draw(plan.rng)
        if slot not in slots:
            slots.append(slot)
    return [int(_CATALOGUE[slot]) for slot in slots]


GENERATORS = dict(_pokec.GENERATORS, source_set=source_set)
