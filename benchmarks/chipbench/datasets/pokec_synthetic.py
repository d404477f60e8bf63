"""The default data set: a Pokec-shaped graph at the deployment's sizes.

One label (``:User {id, age}``), one edge type (``:FRIEND``), drawn by
``reference.make_graph`` from the deployment's ``graph_seed``: one graph
for every ``--seed``, as the data set is one file. Loaded over Bolt by
the deployment's own statements: the index, then ``UNWIND`` batches on
one connection.

A data set is a module with

  make(config)                 the reference's state of the loaded data:
                               any object with ``copy()`` that the mix's
                               semantics know how to read and write
  load(client, config, state)  loads it over the served path; returns
                               (seconds, records loaded)
  key_space(config)            how many ids a mix's ``keys`` draw over
  sizes(state)                 {"n_nodes", "n_edges"} for the log and
                               for a roofline's shapes
  GENERATORS                   {name: f(plan, spec)} for the parameter
                               generators a mix may name in ``gen``; a
                               plan looks here before its own three
"""

from __future__ import annotations

import time

import numpy as np

import reference

GENERATORS: dict = {}


def make(config: dict):
    n_nodes = int(config["nodes"])
    src, dst = reference.make_graph(int(config["graph_seed"]), n_nodes,
                                    int(config["edges"]))
    return reference.GraphState(n_nodes, src, dst)


def key_space(config: dict) -> int:
    return int(config["nodes"])


def sizes(state) -> dict:
    return {"n_nodes": state.n_loaded,
            "n_edges": len(state.edge_arrays()[0])}


def load(client, config: dict, state):
    """Index, then UNWIND batches on one connection, as the smoke loads."""
    load, n_nodes = config["load"], state.n_loaded
    batch = int(load["batch"])
    src, dst = state.edge_arrays()
    t0 = time.perf_counter()
    client.execute(config["index"])
    for start in range(0, n_nodes, batch):
        client.execute(load["nodes_query"],
                       {"ids": list(range(start,
                                          min(start + batch, n_nodes)))})
    pairs = np.stack([src, dst], axis=1)
    for start in range(0, len(pairs), batch):
        client.execute(load["edges_query"],
                       {"pairs": pairs[start:start + batch].tolist()})
    return time.perf_counter() - t0, n_nodes + len(pairs)
