"""Pokec with an embedding on every ``:User``: the default data set's
graph, index and loader (imported, so the graph is the one the medium
cells load), and beside them ``embedding``, ``WIDTH`` doubles a node,
made from the deployment's ``graph_seed`` alone.

The embeddings are a mixture of ``TOPICS`` topics on the unit sphere,
because real text embeddings cluster by topic and uniform directions
would make every neighbour equally far: a topic's centre is a uniform
direction; a member is the centre plus N(0, sigma^2) noise per
component with sigma = ``SPREAD`` / sqrt(WIDTH), normalised, so that two
members of a topic stand at cosine 1 / (1 + SPREAD^2) = 0.74 and two
topics near 0; a node's topic is drawn with Zipf ``TOPIC_THETA`` weights.

They are loaded over Bolt by the deployment's own statement
(``load.embeddings_query``) in ``UNWIND`` batches of
``load.embedding_batch`` rows on the same connection, after
``pokec_synthetic.load`` has loaded the graph: that function is one
piece (index, nodes, edges), and calling it whole keeps the graph load
the medium cells' own. ``load`` returns the graph's seconds and records
as the default does; the embedding load's seconds go to the log and, as
everything before the window, to ``setup_s``.

``GENERATORS`` (a plan looks here before its own three):

  fresh_vector  a fresh member of a topic drawn by the topic weights;
                remembered on the plan as the vector last written
  query_vector  by default the same draw: a query near existing
                documents, not a copy of one. ``{"near": "last_written",
                "noise": s}``: the vector last written plus N(0, s^2)
                per component, normalised. ``{"margin": m, "seeds": k}``:
                a draw whose k-th and (k+1)-th best cosine similarities
                lie at least m apart, so that no float32 rounding
                decides a hybrid request's seed set; candidates are
                scored against the loaded corpus in float64, ``batch``
                at a time (the first batch at the plan's first such
                request, in the warm-up), and against what the plan has
                written since when one is handed out
  friends       ``count`` ids from the data set's own destination draw

The generators get a plan and a spec and nothing else, so the mixture
and the corpus they draw from are those of the last ``make(config)`` of
this process: ``run_cell`` makes the data set before it makes a plan.
"""

from __future__ import annotations

import time

import numpy as np

import reference
import seams

_pokec = seams.load_module(None, "datasets", "pokec_synthetic")
_sem = seams.load_module(None, "semantics", "graphrag")

WIDTH = 384
TOPICS = 256
TOPIC_THETA = 0.99
SPREAD = 0.6

key_space = _pokec.key_space
sizes = _pokec.sizes


class Mixture:
    def __init__(self, graph_seed: int, width: int = WIDTH):
        rng = np.random.default_rng([int(graph_seed), 0xE3BED])
        centres = rng.standard_normal((TOPICS, width))
        self.centres = centres / np.linalg.norm(centres, axis=1,
                                                keepdims=True)
        weight = 1.0 / np.arange(1, TOPICS + 1) ** TOPIC_THETA
        self.cdf = np.cumsum(weight) / weight.sum()
        self.width = width
        self._rng = rng

    def members(self, rng, count: int) -> np.ndarray:
        """`count` fresh members, each of a topic drawn by the weights."""
        topic = np.minimum(np.searchsorted(self.cdf, rng.random(count)),
                           TOPICS - 1)
        noise = rng.standard_normal((count, self.width)) \
            * (SPREAD / np.sqrt(self.width))
        rows = self.centres[topic] + noise
        return rows / np.linalg.norm(rows, axis=1, keepdims=True)

    def corpus(self, n_nodes: int) -> np.ndarray:
        return self.members(self._rng, n_nodes)


#: the mixture and the loaded corpus of the last make(): what the
#: generators draw from
_CURRENT: dict = {}


def make(config: dict):
    n_nodes = int(config["nodes"])
    src, dst = reference.make_graph(int(config["graph_seed"]), n_nodes,
                                    int(config["edges"]))
    mixture = Mixture(int(config["graph_seed"]),
                      int(config.get("embedding_width", WIDTH)))
    state = _sem.RagState(n_nodes, src, dst, mixture.corpus(n_nodes))
    _CURRENT.update(mixture=mixture, base=state.base)
    return state


def load(client, config: dict, state):
    """The graph as the default data set loads it, then the embeddings."""
    seconds, records = _pokec.load(client, config, state)
    batch = int(config["load"]["embedding_batch"])
    query = config["load"]["embeddings_query"]
    t0 = time.perf_counter()
    for start in range(0, state.n_loaded, batch):
        rows = [{"id": start + i, "v": v} for i, v in
                enumerate(state.base[start:start + batch].tolist())]
        client.execute(query, {"rows": rows})
    took = time.perf_counter() - t0
    n, width = state.base.shape
    print(f"embeddings loaded over Bolt in {took:.3f} s: {n / took:,.0f} "
          f"records/s, {8 * n * width / took / 1e6:.2f} MB/s of doubles "
          f"({n} x {width})", flush=True)
    return seconds, records


# --------------------------------------------------------------------------
# parameter generators
# --------------------------------------------------------------------------

def _unit(v: np.ndarray) -> np.ndarray:
    return v / np.linalg.norm(v)


def _written(plan) -> list:
    """The vectors the plan has handed out to be written, oldest first."""
    return plan.__dict__.setdefault("written_vectors", [])


def fresh_vector(plan, spec: dict) -> list:
    v = _CURRENT["mixture"].members(plan.rng, 1)[0]
    _written(plan).append(v)
    return v.tolist()


def margin_candidates(base: np.ndarray, queries: np.ndarray, k: int):
    """For each query the k-th and (k+1)-th best cosine similarities
    over the rows of `base` (unit rows), float64."""
    sims = queries @ base.T
    best = -np.partition(-sims, k, axis=1)[:, :k + 1]
    best.sort(axis=1)
    return best[:, 1], best[:, 0]          # k-th best, (k+1)-th best


def _with_margin(plan, spec: dict) -> np.ndarray:
    """The next candidate that holds the margin against the loaded
    corpus and against every vector the plan has written."""
    margin, k = float(spec["margin"]), int(spec["seeds"])
    stock = plan.__dict__.setdefault("margin_stock", [])
    seen = plan.__dict__.setdefault("margin_seen", [0, 0])  # drawn, redrawn
    while True:
        if not stock:
            batch = int(spec.get("batch", 64))
            queries = _CURRENT["mixture"].members(plan.rng, batch)
            kth, nxt = margin_candidates(_CURRENT["base"], queries, k)
            print(f"margin generator (client {plan.client}): "
                  f"{int((kth - nxt < margin).sum())} of {batch} candidates "
                  f"under the margin {margin:g} against the loaded corpus; "
                  f"{seen[1]} of {seen[0]} handed out so far were redrawn",
                  flush=True)
            stock.extend(zip(queries, kth, nxt))
            stock.reverse()
        q, kth, nxt = stock.pop()
        seen[0] += 1
        near = max((float(w @ q) for w in _written(plan)), default=-1.0)
        if kth - nxt >= margin and near <= kth - margin:
            return q
        seen[1] += 1


def query_vector(plan, spec: dict) -> list:
    if spec.get("near") == "last_written":
        last = _written(plan)[-1]
        noise = plan.rng.standard_normal(len(last)) * float(spec["noise"])
        return _unit(last + noise).tolist()
    if "margin" in spec:
        return _with_margin(plan, spec).tolist()
    return _CURRENT["mixture"].members(plan.rng, 1)[0].tolist()


def friends(plan, spec: dict) -> list:
    _, dst = reference.draw_edges(plan.rng, plan.n_ids, int(spec["count"]))
    return dst.tolist()


GENERATORS = {"fresh_vector": fresh_vector, "query_vector": query_vector,
              "friends": friends}
