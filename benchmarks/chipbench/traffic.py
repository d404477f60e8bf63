"""The one general traffic generator: a mix file's parameters and a seed
in, each client's endless plan of requests out; and the closed loop
that drives a plan over one Bolt connection.

A mix (``traffic/<mix>.json``) names its clients, how a client picks
its next class (``weighted``: every block of sum-of-shares requests
holds each class exactly ``share`` times, shuffled by the seed;
``sequence``: the classes in order, one pass being one cycle), the key
distribution (with a ``seed`` of its own every run seed offers the same
work in another order), and for each class its Cypher text, what draws each parameter (a generator of
the deployment's data set, ``datasets/<name>.py`` ``GENERATORS``, or
one of the three here), and the name of its semantics
(``semantics/<name>.py``).
"""

from __future__ import annotations

import time
from dataclasses import dataclass

import numpy as np

import reference

#: new vertex ids: above the loaded range, disjoint between clients
NEW_ID_STRIDE = 10_000_000


@dataclass
class Request:
    cls: dict
    params: dict
    client: int
    start: float = 0.0
    end: float = 0.0
    rows: list | None = None
    tries: int = 0
    error: str | None = None
    dropped: bool = False       # a control's lost write: acknowledged, unsent

    @property
    def name(self) -> str:
        return self.cls["name"]

    @property
    def ok(self) -> bool:
        return self.error is None and self.rows is not None


class Keys:
    """Ids drawn with the mix's Zipf skew (theta 0 is uniform) over a
    seeded permutation. A mix whose keys name a ``seed`` keeps one hot
    set for every run seed, as the graph is one (and ``Plan`` one stream
    of parameters for each class)."""

    def __init__(self, spec: dict, n_ids: int, seed: int):
        if spec["distribution"] != "zipf":
            raise ValueError(f"no key distribution {spec['distribution']!r}")
        rng = np.random.default_rng([spec.get("seed", seed), 0xC0FFEE])
        self.ids = rng.permutation(n_ids) if spec.get("permuted") \
            else np.arange(n_ids)
        weight = 1.0 / np.arange(1, n_ids + 1) ** float(spec["theta"])
        self.cdf = np.cumsum(weight) / weight.sum()

    def draw(self, rng) -> int:
        slot = min(int(np.searchsorted(self.cdf, rng.random())),
                   len(self.ids) - 1)
        return int(self.ids[slot])


class Plan:
    """One client's requests, drawn from (seed, client index) alone, or
    their parameters from (the keys' seed, client index, class)."""

    def __init__(self, mix: dict, n_ids: int, seed: int, client: int,
                 keys: Keys | None, dataset=None):
        self.mix, self.n_ids, self.client, self.keys = mix, n_ids, client, keys
        self.generators = getattr(dataset, "GENERATORS", {})
        self.order = self.rng = np.random.default_rng([seed, 1 + client])
        # a mix whose keys name a ``seed`` draws each class's parameters
        # from a stream of its own, the same for every run seed: the run's
        # seed then orders the classes inside each block and nothing more
        fixed = mix.get("keys", {}).get("seed")
        self.streams = {} if fixed is None else {
            c["name"]: np.random.default_rng([int(fixed), 1 + client, i])
            for i, c in enumerate(mix["classes"])}
        self.by_name = {c["name"]: c for c in mix["classes"]}
        self._new_ids = 0
        self._block: list = []

    def _param(self, spec: dict):
        gen = spec["gen"]
        if gen in self.generators:
            return self.generators[gen](self, spec)
        if gen == "key":
            return self.keys.draw(self.rng)
        if gen == "new_id":
            self._new_ids += 1
            return (self.n_ids + self.client * NEW_ID_STRIDE
                    + self._new_ids)
        if gen == "edge_burst":
            src, dst = reference.draw_edges(self.rng, self.n_ids,
                                            int(spec["edges"]))
            return np.stack([src, dst], axis=1).tolist()
        raise ValueError(f"no parameter generator {gen!r}")

    def request(self, name: str) -> Request:
        cls = self.by_name[name]
        self.rng = self.streams.get(name, self.order)
        params = {k: self._param(v) for k, v in cls["params"].items()}
        return Request(cls, params, self.client)

    def __iter__(self):
        return self

    def __next__(self) -> Request:
        if not self._block:
            classes = self.mix["classes"]
            if self.mix["schedule"] == "sequence":
                self._block = [c["name"] for c in reversed(classes)]
            elif self.mix["schedule"] == "weighted":
                names = [c["name"] for c in classes
                         for _ in range(int(c["share"]))]
                self._block = [names[i]
                               for i in self.order.permutation(len(names))]
            else:
                raise ValueError(f"no schedule {self.mix['schedule']!r}")
        return self.request(self._block.pop())


@dataclass
class Transport:
    """One Bolt connection, with the retry the mix allows inside a
    request's time. ``drop_every`` is a control's fault: every n-th
    write is acknowledged here and never sent."""
    client: object
    retries: int = 0
    transient: type = Exception
    drop_every: int = 0
    writes: int = 0

    def run(self, req: Request) -> Request:
        req.start = time.perf_counter()
        if req.cls["kind"] == "write" and self.drop_every:
            self.writes += 1
            if self.writes % self.drop_every == 0:
                req.rows, req.dropped = [], True
                req.end = time.perf_counter()
                return req
        for attempt in range(self.retries + 1):
            req.tries = attempt + 1
            try:
                _, req.rows, _ = self.client.execute(req.cls["query"],
                                                     req.params)
                req.error = None
                break
            except self.transient as e:
                req.error = f"{type(e).__name__}: {e}"
                self.client.reset()     # the session is in its failed state
                if "TransientError" not in req.error:
                    break
        req.end = time.perf_counter()
        return req


def closed_loop(transport: Transport, plan: Plan, deadline: float,
                out: list, per_cycle: int) -> None:
    """Send the plan's requests back to back until the deadline. A cycle
    that has begun is finished, so the state the reference follows is
    whole; what ended after the deadline is told apart by its time."""
    while time.perf_counter() < deadline:
        for _ in range(per_cycle):
            out.append(transport.run(next(plan)))
