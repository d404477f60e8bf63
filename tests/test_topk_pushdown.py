"""A CALL under ``ORDER BY … [SKIP s] LIMIT k`` yields the rows that can
reach the result, not one per vertex.

``planner.topk_rewrite`` hands a CALL right below a TopK the TopK's
bound (``planner.call_bound``); the rank procedures' ``_rank_results``
then yield the ``s + k`` best visible vertices and every one tied with
the last of them, in index order. Every case here runs one query through
that plan and through the same plan without the bound, and wants the
same rows in the same order.
"""

import numpy as np
import pytest

from memgraph_tpu.exceptions import TypeException
from memgraph_tpu.observability.metrics import global_metrics
from memgraph_tpu.ops.delta import GLOBAL_WARM_POOL
from memgraph_tpu.procedures import graph_algorithms
from memgraph_tpu.query import Interpreter, InterpreterContext
from memgraph_tpu.query.plan import planner
from memgraph_tpu.query.procedures.registry import RowBound
from memgraph_tpu.storage import InMemoryStorage

N_LINKED, N_SOURCES = 150, 30       # the sources have no in-edge: ties
SOURCES = ("MATCH (s:U) WHERE s.id IN [1, 5, 9] "
           "WITH collect(s) AS sources ")
#: (prefix, procedure call, field)
PROCEDURES = {
    "pagerank": ("", "pagerank.get()", "rank"),
    "katz": ("", "katz_centrality.get()", "rank"),
    "degree": ("", "degree_centrality.get('in')", "degree"),
    "personalized": (SOURCES, "pagerank.personalized(sources)", "rank"),
}
#: (query after YIELD, parameters, the bound engages)
CASES = {
    "desc": ("RETURN node.id AS id, {f} ORDER BY {f} DESC LIMIT 7",
             None, True),
    "asc": ("RETURN node.id AS id, {f} ORDER BY {f} ASC LIMIT 7",
            None, True),
    "skip": ("RETURN node.id AS id, {f} ORDER BY {f} DESC SKIP 4 LIMIT 5",
             None, True),
    "limit_parameter": ("RETURN node.id AS id, {f} ORDER BY {f} DESC "
                        "SKIP $s LIMIT $l", {"s": 2, "l": 6}, True),
    "ties_and_a_second_key": ("RETURN node.id AS id, {f} "
                              "ORDER BY {f}, node.id DESC LIMIT 5",
                              None, True),
    "second_key_desc": ("RETURN node.id AS id, {f} "
                        "ORDER BY {f} DESC, node.id LIMIT 9", None, True),
    "renamed_field": ("RETURN node AS n, {f} AS value "
                      "ORDER BY value DESC LIMIT 4", None, True),
    "bound_at_least_n": ("RETURN node.id AS id, {f} ORDER BY {f} DESC "
                         "SKIP 170 LIMIT 20", None, False),
    "limit_zero": ("RETURN node.id AS id, {f} ORDER BY {f} DESC LIMIT 0",
                   None, False),
}


@pytest.fixture(scope="module")
def storage():
    """180 vertices; the last 30 only point out, so they tie on every
    measure; in-degrees tie everywhere."""
    storage = InMemoryStorage()
    rng = np.random.default_rng(38)
    n = N_LINKED + N_SOURCES
    src = rng.integers(0, n, 600)
    dst = rng.integers(0, N_LINKED, 600)
    keep = src != dst
    ictx = InterpreterContext(storage)
    execute(ictx, "UNWIND range(0, $n - 1) AS i CREATE (:U {id: i})",
            {"n": n})
    execute(ictx, "UNWIND $pairs AS p MATCH (a:U {id: p[0]}), "
                  "(b:U {id: p[1]}) CREATE (a)-[:E]->(b)",
            {"pairs": np.stack([src[keep], dst[keep]], 1).tolist()})
    return storage


def execute(ictx, query, params=None):
    return Interpreter(ictx).execute(query, params)[1]


def counter(name):
    return {n: v for n, _k, v in global_metrics.snapshot()}.get(name, 0.0)


def unbounded(monkeypatch):
    """The test hook: plans made after it give no CALL a bound."""
    monkeypatch.setattr(planner, "call_bound", lambda topk: None)


def both_ways(storage, statements, params=None):
    """(rows, pushdowns) of the last statement, run after the others on
    one interpreter, with the bound and then without it; each from a
    cold start (a warm-started fixpoint moves the low bits)"""
    def run():
        GLOBAL_WARM_POOL.clear()
        interpreter = Interpreter(InterpreterContext(storage))
        for statement in statements[:-1]:
            interpreter.execute(statement)
        before = counter("query.topk_pushdown_total")
        rows = interpreter.execute(statements[-1], params)[1]
        if statements[0] == "BEGIN":
            interpreter.execute("ROLLBACK")
        return rows, counter("query.topk_pushdown_total") - before

    got = run()
    with pytest.MonkeyPatch.context() as patch:
        unbounded(patch)
        want = run()
    assert want[1] == 0
    return got, want[0]


def query(procedure, tail):
    prefix, call, field = PROCEDURES[procedure]
    return (f"{prefix}CALL {call} YIELD node, {field} "
            + tail.replace("{f}", field))


@pytest.mark.parametrize("case", sorted(CASES) + [
    "unwind_two_calls", "deleted_earlier_in_the_transaction"])
@pytest.mark.parametrize("procedure", sorted(PROCEDURES))
def test_same_rows_with_and_without_the_bound(storage, procedure, case):
    params, engages, calls = None, True, 1
    if case in CASES:
        tail, params, engages = CASES[case]
        statements = [query(procedure, tail)]
    elif case == "unwind_two_calls":
        prefix, call, field = PROCEDURES[procedure]
        statements = [f"{prefix}UNWIND [1, 2] AS x CALL {call} "
                      f"YIELD node, {field} RETURN x, node.id AS id, "
                      f"{field} ORDER BY {field} DESC LIMIT 6"]
        calls = 2
    else:
        # the best vertex goes, in the same transaction as the CALL
        top = execute(InterpreterContext(storage), query(
            procedure, "RETURN node.id ORDER BY {f} DESC LIMIT 1"))[0][0]
        statements = ["BEGIN", f"MATCH (n:U {{id: {top}}}) DETACH DELETE n",
                      query(procedure, "RETURN node.id AS id, {f} "
                                       "ORDER BY {f} DESC LIMIT 6")]
    (rows, pushdowns), want = both_ways(storage, statements, params)
    assert rows == want
    assert pushdowns == (calls if engages else 0)
    if case == "limit_zero":
        assert rows == []
    if case == "ties_and_a_second_key":
        # the fifth and sixth best tie: the second key chose among them
        every = execute(InterpreterContext(storage), query(
            procedure, "RETURN {f} ORDER BY {f} LIMIT 6"))
        assert every[4] == every[5]
    if case == "deleted_earlier_in_the_transaction":
        assert top not in [row[0] for row in rows]


#: the cell's query, then shapes in which a dropped row could have
#: changed the result or no bound can be read off the TopK, and a
#: procedure that takes its own top k and leaves the bound unread
ENGAGEMENT = {
    "the_cells_query":
        ("CALL pagerank.get() YIELD node, rank "
         "RETURN node.id AS id, rank ORDER BY rank DESC LIMIT 100", 1),
    "with_where_between":
        ("CALL pagerank.get() YIELD node, rank WITH node, rank "
         "WHERE rank > 0 RETURN node.id AS id, rank "
         "ORDER BY rank DESC LIMIT 5", 0),
    "an_item_that_can_raise":
        ("CALL pagerank.get() YIELD node, rank "
         "RETURN node.id AS id, rank.x AS x ORDER BY rank DESC LIMIT 5", 0),
    "an_item_evaluated_for_every_row":
        ("CALL pagerank.get() YIELD node, rank "
         "RETURN node.id % 7 AS id, rank ORDER BY rank DESC LIMIT 5", 0),
    "aggregating_return":
        ("CALL pagerank.get() YIELD node, rank RETURN node.id % 3 AS g, "
         "max(rank) AS rank ORDER BY rank DESC LIMIT 2", 0),
    "expression_over_the_field":
        ("CALL pagerank.get() YIELD node, rank "
         "RETURN node.id AS id, rank ORDER BY rank * 2 DESC LIMIT 5", 0),
    "personalized_with_its_own_top_k":
        (SOURCES + "CALL pagerank.personalized(sources, 100, 0.85, 20) "
         "YIELD node, rank RETURN node.id AS id, rank "
         "ORDER BY rank DESC LIMIT 20", 0),
    "feeds_a_write":
        ("CALL pagerank.get() YIELD node, rank WITH node, rank "
         "ORDER BY rank DESC LIMIT 3 SET node.top = true "
         "RETURN node.id AS id", 0),
}


@pytest.mark.parametrize("case", sorted(ENGAGEMENT))
def test_which_plans_push_the_bound(storage, case):
    statement, pushdowns = ENGAGEMENT[case]
    statements = [statement]
    if case == "feeds_a_write":     # (its writes are rolled back)
        statements = ["BEGIN", statement]
    if case == "an_item_that_can_raise":
        # (`rank.x` raises on every row here: whether the bound was
        # given is in the counter, read where the first row raised)
        before = counter("query.topk_pushdown_total")
        with pytest.raises(TypeException):
            execute(InterpreterContext(storage), statement)
        assert counter("query.topk_pushdown_total") == before
        return
    (rows, moved), want = both_ways(storage, statements)
    assert moved == pushdowns
    assert rows == want


def test_rows_and_consume_close_once_a_call(storage):
    names = ("span.analytics.rows.count", "span.analytics.consume.count",
             "query.topk_pushdown_total")
    before = [counter(name) for name in names]
    execute(InterpreterContext(storage),
            "UNWIND [1, 2] AS x CALL pagerank.get() YIELD node, rank "
            "RETURN node.id AS id, rank ORDER BY rank DESC LIMIT 3")
    assert [counter(name) - b for name, b in zip(names, before)] == \
        [2.0, 2.0, 2.0]


class _Graph:
    def __init__(self, n):
        self.n_nodes = n
        self.node_gids = np.arange(n)


class _Context:
    """vertex_by_index over a set of deleted indices"""
    def __init__(self, deleted):
        self.deleted = deleted

    def vertex_by_index(self, graph, i):
        return None if i in self.deleted else i


@pytest.mark.parametrize("seed", range(6))
def test_bounded_rows_against_a_full_sort(seed):
    """The chosen indices are exactly those whose key is at most the
    count-th best visible key, deleted vertices among the best included
    (the search widens past them), in index order."""
    rng = np.random.default_rng(seed)
    n = 200
    values = rng.integers(0, 12, n).astype(np.float32)
    deleted = set(np.argsort(-values, kind="stable")[:rng.integers(0, 40)]
                  .tolist())
    visible = np.array([i for i in range(n) if i not in deleted])
    for count in (1, 5, 17, 60):
        for descending in (True, False):
            keys = -values if descending else values
            edge = np.sort(keys[visible])[count - 1]
            got = graph_algorithms._bounded_rows(
                _Context(deleted), _Graph(n), values,
                RowBound("rank", descending, count))
            assert got == np.flatnonzero(keys <= edge).tolist()
    nan = values.copy()
    nan[3] = np.nan
    assert graph_algorithms._bounded_rows(
        _Context(set()), _Graph(n), nan, RowBound("rank", True, 5)) is None
