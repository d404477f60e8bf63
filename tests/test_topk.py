"""``ORDER BY … LIMIT k`` as one bounded selection (Op.TopK).

The planner's pass turns ``Limit(Skip?(OrderBy(x)))`` into ``TopK`` and
folds the projection's ``Produce`` into it. Every case here runs one
query both ways, through that plan and through the plain chain the
planner builds without the pass, and wants the same rows in the same
order, or the same exception.
"""

import asyncio
import json
import socket
import threading
import urllib.request

import numpy as np
import pytest

from memgraph_tpu.exceptions import TypeException
from memgraph_tpu.observability.metrics import global_metrics
from memgraph_tpu.query import Interpreter, InterpreterContext
from memgraph_tpu.query.plan import planner
from memgraph_tpu.storage import InMemoryStorage
from memgraph_tpu.utils.memory_tracker import MemoryLimitException

NAN = float("nan")

#: id, age (ties), k (null, NaN, int, float, string), name
PEOPLE = [
    (0, 30, 2, "c"), (1, 25, None, "a"), (2, 30, "x", "b"),
    (3, 25, 1.5, "a"), (4, 41, NAN, "c"), (5, 30, 2, "b"),
    (6, 25, None, "d"), (7, 41, "a", "a"), (8, 30, -7, "c"),
    (9, 19, NAN, "b"), (10, 30, 2.0, "a"), (11, 25, "x", "d"),
]


@pytest.fixture
def storage(monkeypatch):
    # the columnar rewrite claims scan-shaped ORDER BY before the pass
    # sees it (test_the_scan_shape_is_still_the_columnar_operators):
    # these cases are about the row operator
    monkeypatch.setenv("MEMGRAPH_TPU_DISABLE_PARALLEL", "1")
    return people()


def people():
    storage = InMemoryStorage()
    execute(InterpreterContext(storage),
            "UNWIND $rows AS r CREATE (:P {id: r[0], age: r[1], k: r[2], "
            "name: r[3]})", {"rows": [list(p) for p in PEOPLE]})
    execute(InterpreterContext(storage),
            "MATCH (a:P), (b:P) WHERE b.id = (a.id * 5 + 1) % 12 "
            "AND a.id % 3 <> 0 CREATE (a)-[:E]->(b)")
    return storage


def execute(ictx, query, params=None):
    return Interpreter(ictx).execute(query, params)[1]


def outcome(ictx, query, params):
    try:
        return ("rows", normal(execute(ictx, query, params)))
    except Exception as e:  # noqa: BLE001 — the exception IS the outcome
        return ("raises", type(e).__name__, str(e))


def normal(value):
    """NaN equal to NaN, so that rows compare."""
    if isinstance(value, float) and value != value:
        return "NaN"
    if isinstance(value, list):
        return [normal(v) for v in value]
    return value


def plan_names(ictx, query):
    return [r[0].strip("|* ").split(" ")[0]
            for r in execute(ictx, "EXPLAIN " + query)]


def both_ways(storage, query, params=None, plain_storage=None):
    """(outcome through TopK, outcome through Limit(Skip(OrderBy(…)))),
    the second on ``plain_storage`` where the query writes"""
    topk = InterpreterContext(storage)
    assert "TopK" in plan_names(topk, query)
    got = outcome(topk, query, params)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(planner, "topk_rewrite", lambda plan: plan)
        plain = InterpreterContext(plain_storage or storage)
        names = plan_names(plain, query)
        assert "TopK" not in names and "OrderBy" in names \
            and "Limit" in names
        want = outcome(plain, query, params)
    return got, want


CASES = {
    "ties_keep_arrival_order":
        ("MATCH (n:P) RETURN n.id AS id, n.age AS age "
         "ORDER BY age LIMIT 7", None),
    "ties_desc":
        ("MATCH (n:P) RETURN n.id AS id ORDER BY n.age DESC LIMIT 6", None),
    "nulls_last_asc":
        ("MATCH (n:P) WHERE n.id < 7 RETURN n.id AS id, n.k AS k "
         "ORDER BY k ASC LIMIT 6", None),
    "nulls_first_desc":
        ("MATCH (n:P) WHERE n.id < 7 RETURN n.id AS id, n.k AS k "
         "ORDER BY k DESC LIMIT 3", None),
    "nan_after_numbers":
        ("MATCH (n:P) WHERE n.id IN [0, 3, 4, 8, 9, 10] "
         "RETURN n.id AS id, n.k AS k ORDER BY k DESC LIMIT 4", None),
    "mixed_numbers_and_strings":
        ("MATCH (n:P) RETURN n.id AS id, n.k AS k ORDER BY k LIMIT 9",
         None),
    "two_keys_mixed_directions":
        ("MATCH (n:P) RETURN n.id AS id, n.age AS age, n.name AS name "
         "ORDER BY age DESC, name ASC LIMIT 8", None),
    "three_keys":
        ("MATCH (n:P) RETURN n.id AS id ORDER BY n.name DESC, n.age, "
         "n.id DESC LIMIT 5", None),
    "skip_with_limit":
        ("MATCH (n:P) RETURN n.id AS id, n.age AS age "
         "ORDER BY age DESC SKIP 3 LIMIT 4", None),
    "skip_past_the_end":
        ("MATCH (n:P) RETURN n.id AS id ORDER BY n.age SKIP 40 LIMIT 4",
         None),
    "bound_one_below_n":
        ("MATCH (n:P) RETURN n.id AS id, n.k AS k ORDER BY k DESC, id "
         "SKIP 8 LIMIT 3", None),
    "bound_equal_to_n":
        ("MATCH (n:P) RETURN n.id AS id ORDER BY n.age DESC SKIP 9 LIMIT 3",
         None),
    "limit_zero":
        ("MATCH (n:P) RETURN n.id AS id ORDER BY n.age LIMIT 0", None),
    "limit_zero_reads_no_skip":
        ("MATCH (n:P) RETURN n.id AS id ORDER BY n.age SKIP $s LIMIT 0",
         {"s": "x"}),
    "k_above_n":
        ("MATCH (n:P) RETURN n.id AS id ORDER BY n.age LIMIT 500", None),
    "negative_limit_parameter_is_clamped":
        ("MATCH (n:P) RETURN n.id AS id ORDER BY n.age LIMIT $l",
         {"l": -3}),
    "limit_parameter":
        ("MATCH (n:P) RETURN n.id AS id ORDER BY n.age SKIP $s LIMIT $l",
         {"s": 1, "l": 4}),
    "limit_not_an_integer":
        ("MATCH (n:P) RETURN n.id AS id ORDER BY n.age LIMIT $l",
         {"l": 2.5}),
    "limit_a_boolean":
        ("MATCH (n:P) RETURN n.id AS id ORDER BY n.age LIMIT $l",
         {"l": True}),
    "skip_not_an_integer":
        ("MATCH (n:P) RETURN n.id AS id ORDER BY n.age SKIP $s LIMIT 3",
         {"s": "two"}),
    "skip_negative":
        ("MATCH (n:P) RETURN n.id AS id ORDER BY n.age SKIP $s LIMIT 3",
         {"s": -1}),
    "both_wrong_limit_speaks_first":
        ("MATCH (n:P) RETURN n.id AS id ORDER BY n.age SKIP $s LIMIT $l",
         {"s": "x", "l": "y"}),
    "projected_alias":
        ("MATCH (n:P) RETURN n.id AS id, n.age * 2 AS twice "
         "ORDER BY twice DESC, id LIMIT 5", None),
    "alias_shadows_a_variable":
        ("MATCH (n:P) RETURN n.age AS n ORDER BY n LIMIT 2", None),
    "computed_alias_shadows_a_variable_another_item_reads":
        ("MATCH (n:P) RETURN n.age + 1 AS n, n.name AS name "
         "ORDER BY name DESC, n LIMIT 5", None),
    "expression_equal_to_a_projected_item":
        ("MATCH (n:P) RETURN n.id AS id, n.age + n.id AS s "
         "ORDER BY n.age + n.id DESC LIMIT 4", None),
    "expression_over_an_alias":
        ("MATCH (n:P) RETURN n.id AS id, n.age AS age "
         "ORDER BY age % 7, id DESC LIMIT 6", None),
    "variable_that_is_not_returned":
        ("MATCH (n:P) RETURN n.name AS name ORDER BY n.id DESC LIMIT 4",
         None),
    "comprehension_in_the_sort_item":
        ("MATCH (n:P) RETURN n.id AS id, n.age AS x "
         "ORDER BY [x IN [n.age, 1] | x * id][0] DESC LIMIT 4", None),
    "case_in_the_sort_item":
        ("MATCH (n:P) RETURN n.id AS id, n.age AS age ORDER BY "
         "CASE WHEN age > 26 THEN 0 ELSE 1 END, id DESC LIMIT 5", None),
    "literal_and_parameter_items":
        ("MATCH (n:P) RETURN 7 AS seven, $p AS p, n.id AS id "
         "ORDER BY n.age DESC LIMIT 3", {"p": "q"}),
    "parameter_that_was_not_given":
        ("MATCH (n:P) RETURN $nope AS p, n.id AS id "
         "ORDER BY n.age DESC LIMIT 3", None),
    "property_of_a_parameter":
        ("MATCH (n:P) RETURN $m.a AS a, n.id AS id ORDER BY id DESC "
         "LIMIT 2", {"m": {"a": 1}}),
    "property_of_a_parameter_that_has_none":
        ("MATCH (n:P) RETURN $m.a AS a, n.id AS id ORDER BY id DESC "
         "SKIP 20 LIMIT 2", {"m": 3}),
    "return_distinct":
        ("MATCH (n:P) RETURN DISTINCT n.age AS age, n.name AS name "
         "ORDER BY age DESC, name LIMIT 5", None),
    "aggregating_projection":
        ("MATCH (n:P) RETURN n.age AS age, count(*) AS c, max(n.id) AS m "
         "ORDER BY c DESC, age LIMIT 3", None),
    "aggregate_in_the_sort_item":
        ("MATCH (n:P) RETURN n.name AS name, count(*) AS c "
         "ORDER BY count(*) DESC, name LIMIT 2", None),
    "with_in_the_middle":
        ("MATCH (n:P) WITH n, n.age AS age ORDER BY age DESC, n.id "
         "LIMIT 5 MATCH (n)-[:E]->(m) RETURN n.id, m.id, age "
         "ORDER BY n.id, m.id", None),
    "with_where_after_the_limit":
        ("MATCH (n:P) WITH n.id AS id, n.age AS age ORDER BY age "
         "SKIP 1 LIMIT 6 WHERE id % 2 = 0 RETURN id, age", None),
    "optional_match_nulls":
        ("MATCH (n:P) OPTIONAL MATCH (n)-[:E]->(m) "
         "RETURN n.id AS id, m.name AS name, m.age AS age "
         "ORDER BY age DESC, id LIMIT 6", None),
    "edge_property_item":
        ("MATCH (a:P)-[e:E]->(b:P) RETURN e.w AS w, a.id AS a, b.id AS b "
         "ORDER BY b DESC, a LIMIT 4", None),
    "map_property_item":
        ("MATCH (n:P) WITH {v: n.age, i: n.id} AS m "
         "RETURN m.v AS v, m.i AS i ORDER BY v, i DESC LIMIT 5", None),
    "after_an_update":
        ("MATCH (n:P) SET n.seen = n.id RETURN n.seen AS s, n.name AS name "
         "ORDER BY name, s DESC LIMIT 4", None),
    "unwind_of_mixed_values":
        ("UNWIND [3, 'a', null, 2.5, [1, 2], true, {a: 1}, [1], false, "
         "-1, 'B'] AS v RETURN v ORDER BY v DESC LIMIT 8", None),
    "in_a_subquery":
        ("MATCH (n:P) WHERE n.id < 3 CALL { WITH n MATCH (m:P) "
         "WHERE m.age >= n.age RETURN m.id AS mid ORDER BY m.age, mid DESC "
         "LIMIT 2 } RETURN n.id, mid ORDER BY n.id, mid", None),
    "under_a_union":
        ("MATCH (n:P) RETURN n.id AS id ORDER BY n.age LIMIT 2 "
         "UNION MATCH (n:P) RETURN n.id AS id ORDER BY n.age DESC LIMIT 2",
         None),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_same_rows_in_the_same_order(storage, case):
    query, params = CASES[case]
    got, want = both_ways(storage, query, params)
    assert got == want
    if case == "ties_keep_arrival_order":
        assert got == ("rows", [[9, 19], [1, 25], [3, 25], [6, 25],
                                [11, 25], [0, 30], [2, 30]])
    if case == "alias_shadows_a_variable":
        assert got == ("rows", [[19], [25]])
    if case in ("limit_not_an_integer", "limit_a_boolean",
                "both_wrong_limit_speaks_first"):
        assert got == ("raises", "TypeException",
                       "LIMIT must be a non-negative integer")
    if case in ("skip_not_an_integer", "skip_negative"):
        assert got == ("raises", "TypeException",
                       "SKIP must be a non-negative integer")
    if case in ("limit_zero", "limit_zero_reads_no_skip",
                "negative_limit_parameter_is_clamped"):
        assert got == ("rows", [])


#: a writer ABOVE the operator: no Eager stands between a WITH … ORDER BY
#: … LIMIT and a writing CALL {}, and Apply streams. OrderBy had drained
#: Produce by then, so every item was read before the first write.
WRITER_ABOVE = {
    "set_of_the_property_read":
        "MATCH (n:P) WITH n.age AS v, n.id AS id ORDER BY v DESC, id "
        "LIMIT 5 CALL { MATCH (m:P) SET m.age = 0, m.id = -1 } RETURN v, id",
    "delete_of_the_vertices_read":
        "MATCH (n:P) WITH n.id AS id, n.name AS name ORDER BY n.age, id "
        "SKIP 1 LIMIT 5 CALL { MATCH (m:P) DETACH DELETE m } "
        "RETURN id, name",
    "in_transactions_of_one_row":
        "MATCH (n:P) WITH n.id AS id, n.age AS v ORDER BY v, id LIMIT 4 "
        "CALL { WITH id MATCH (m:P) WHERE m.id >= id SET m.age = -1, "
        "m.id = m.id + 100 } IN TRANSACTIONS OF 1 ROWS RETURN id, v",
    "in_transactions_with_a_graph_value":
        "MATCH (n:P) WITH n, n.age AS v ORDER BY v LIMIT 4 "
        "CALL { WITH n SET n.age = 0 } IN TRANSACTIONS OF 1 ROWS RETURN v",
}


@pytest.mark.parametrize("case", sorted(WRITER_ABOVE))
def test_a_writer_above_reads_nothing_late(storage, case):
    got, want = both_ways(storage, WRITER_ABOVE[case],
                          plain_storage=people())
    assert got == want
    if case == "set_of_the_property_read":
        assert got == ("rows", [[41, 4], [41, 7], [30, 0], [30, 2], [30, 5]])
    if case == "in_transactions_of_one_row":
        assert got == ("rows", [[9, 19], [1, 25], [3, 25], [6, 25]])
    if case == "in_transactions_with_a_graph_value":
        assert got[:2] == ("raises", "QueryException")


def test_pagerank_top_100_against_a_stable_argsort(storage):
    """The benchmark's query on a small graph with many tied ranks."""
    ictx = InterpreterContext(storage)
    execute(ictx, "UNWIND range(100, 399) AS i CREATE (:P {id: i})")
    every = execute(ictx, "CALL pagerank.get() YIELD node, rank "
                          "RETURN node.id AS id, rank")
    ranks = np.array([r[1] for r in every])
    assert len(every) == 312 and len(set(ranks)) < 200      # ties
    order = np.argsort(-ranks, kind="stable")[:100]
    query = ("CALL pagerank.get() YIELD node, rank "
             "RETURN node.id AS id, rank ORDER BY rank DESC LIMIT 100")
    got, want = both_ways(storage, query)
    assert got == want
    assert got[1] == [[every[i][0], every[i][1]] for i in order]


def test_an_item_that_raises_on_a_dropped_row_still_raises(storage):
    """`rank.id` is deferred where `rank` is a map; the second row's is
    a number, and the row would not have survived the top 1."""
    query = ("UNWIND [[1, {id: 7}], [2, 3]] AS p "
             "WITH p[0] AS k, p[1] AS rank "
             "RETURN rank.id AS x, k ORDER BY k LIMIT 1")
    got, want = both_ways(storage, query)
    assert got == want
    assert got[:2] == ("raises", "TypeException")
    fine = query.replace("[2, 3]", "[2, null]")
    assert both_ways(storage, fine)[0] == ("rows", [[7, 1]])


def test_memory_limit_counts_the_rows_admitted(storage):
    """About 100 + 100 ln(500) rows enter the heap of a top 100 over
    50k rows in scattered order; 50k frames would not fit."""
    query = ("UNWIND range(0, 49999) AS x WITH (x * 7919) % 50000 AS y "
             "RETURN y ORDER BY y DESC LIMIT 100 QUERY MEMORY LIMIT 2 MB")
    got, want = both_ways(storage, query)
    assert got == ("rows", [[y] for y in range(49999, 49899, -1)])
    assert want[:2] == ("raises", MemoryLimitException.__name__)


def _counter(name):
    return {n: v for n, _k, v in global_metrics.snapshot()}.get(name, 0.0)


def test_counters_move_and_show_in_stats(storage):
    ictx = InterpreterContext(storage)
    topk, full = _counter("query.topk_total"), \
        _counter("query.sort_full_total")
    execute(ictx, "MATCH (n:P) RETURN n.id ORDER BY n.age LIMIT 3")
    assert (_counter("query.topk_total"),
            _counter("query.sort_full_total")) == (topk + 1, full)
    execute(ictx, "MATCH (n:P) RETURN n.id ORDER BY n.age")
    execute(ictx, "MATCH (n:P) RETURN n.id ORDER BY n.age SKIP 2")
    assert (_counter("query.topk_total"),
            _counter("query.sort_full_total")) == (topk + 1, full + 2)

    from memgraph_tpu.observability.http import start_monitoring_server
    with socket.socket() as probe:
        probe.bind(("127.0.0.1", 0))
        port = probe.getsockname()[1]
    loop = asyncio.new_event_loop()
    started = threading.Event()

    def serve():
        asyncio.set_event_loop(loop)
        loop.run_until_complete(
            start_monitoring_server("127.0.0.1", port, ictx))
        started.set()
        loop.run_forever()

    threading.Thread(target=serve, daemon=True).start()
    assert started.wait(10)
    try:
        doc = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/stats", timeout=5).read())
    finally:
        loop.call_soon_threadsafe(loop.stop)
    assert doc["device"]["query.topk_total"] >= topk + 1
    assert doc["device"]["query.sort_full_total"] >= full + 2
    assert "span.query.sort.count" in doc["device"]
    # the section lists the two counters, not the query.* family
    assert "query.prepared" not in doc["device"]


def test_the_scan_shape_is_still_the_columnar_operators(monkeypatch):
    """parallel_rewrite and lane_rewrite claim `OrderBy <- Produce <-
    Filter* <- ScanAll*` first; the pass finds no OrderBy there, and
    leaves their fallback subplans as planned."""
    monkeypatch.delenv("MEMGRAPH_TPU_DISABLE_PARALLEL", raising=False)
    ictx = InterpreterContext(InMemoryStorage())
    execute(ictx, "UNWIND range(0, 9) AS i CREATE (:P {id: i, age: i % 4})")
    scan = "MATCH (n:P) RETURN n.id AS id ORDER BY n.age DESC LIMIT 3"
    assert plan_names(ictx, scan) == [
        "Limit", "Produce", "ParallelOrderedScanLane", "Once"]
    assert plan_names(ictx, scan.replace("LIMIT", "SKIP 1 LIMIT")) == [
        "Limit", "Skip", "Produce", "ParallelOrderedScanLane", "Once"]
    plan = ictx.cached_plan(scan, ictx.cached_parse(scan))[0]
    fallback = plan.input.input.fallback
    assert type(fallback).__name__ == "OrderBy"
    assert execute(ictx, scan) == [[3], [7], [2]]
    # no LIMIT: the full sort, as it was
    assert plan_names(ictx, "UNWIND [2, 1] AS x RETURN x ORDER BY x") == [
        "OrderBy", "Produce", "Unwind", "Once"]
    assert plan_names(
        ictx, "CALL pagerank.get() YIELD node, rank RETURN node.id AS id, "
              "rank ORDER BY rank DESC LIMIT 100") == [
        "TopK", "CallProcedureOp", "Once"]


def _deferred(ictx, query):
    """{column: deferred?} of the first TopK in the query's plan."""
    def first(op):
        if type(op).__name__ == "TopK":
            return op
        for child in op.children():
            found = first(child)
            if found is not None:
                return found
        return first(op.subplan) if hasattr(op, "subplan") else None

    plan = ictx.cached_plan(query, ictx.cached_parse(query))[0]
    return {name: slot is None for _expr, name, slot in first(plan).projection}


def test_which_items_are_deferred(storage):
    """Identifiers, literals and parameters always; a property of one
    only where nothing below the projection writes, and the rows do not
    come from outside the plan (a subquery's Argument); anything else
    is evaluated for every row as Produce did."""
    ictx = InterpreterContext(storage)
    assert _deferred(
        ictx, "MATCH (n:P) OPTIONAL MATCH (n)-[e:E]->(m) RETURN n.id AS id, "
              "m, e.w AS w, $p AS p, $p.q AS q, 1 AS one, n.age + 1 AS a, "
              "{k: 1}.k AS lit ORDER BY a LIMIT 2") == {
        "id": True, "m": True, "w": True, "p": True, "q": True, "one": True,
        "a": False, "lit": False}
    assert _deferred(
        ictx, "MATCH (n:P) SET n.seen = 1 RETURN n.name AS name, n AS n, "
              "1 AS one ORDER BY name LIMIT 2") == {
        "name": False, "n": True, "one": True}
    assert _deferred(
        ictx, "MATCH (n:P) CALL { WITH n MATCH (m:P) RETURN m.id AS mid, "
              "m AS m ORDER BY mid LIMIT 2 } RETURN n.id, mid") == {
        "mid": False, "m": True}
    assert _deferred(
        ictx, "CALL pagerank.get() YIELD node, rank RETURN node.id AS id, "
              "rank ORDER BY rank DESC LIMIT 100") == {
        "id": True, "rank": True}
    with pytest.raises(TypeException):
        execute(ictx, "MATCH (n:P) RETURN n.id AS id ORDER BY id LIMIT $l",
                {"l": "1"})
