"""mgshard (r18): shard-per-process OLTP execution plane.

Tier-1 coverage:
  * stable hash partitioner (cross-process routing determinism)
  * routed point reads/writes + per-shard WAL directories
  * scatter-gather merge correctness vs a single-process oracle
    (count/sum/min/max, grouped, ORDER BY + LIMIT, DISTINCT) and the
    loud-refusal contract for unmergeable shapes
  * fencing: epoch-monotonic map refresh, stale-map writes bounced by
    the owner's grant epoch then retried against the new owner, a
    deposed (fenced) owner refusing writes outright
  * cross-shard 2PC: atomic commit, presumed abort on prepare failure,
    and atomicity with a worker SIGKILLed between prepare and commit
    (the durable pending journal replays the vote after recovery)
  * shard-move: data preserved, writes during the move not lost
  * worker crash -> typed retryable error -> respawn with per-shard
    WAL recovery
  * coordinator-owned placement: epochs minted inside the replicated
    apply, shard map on the ROUTE table, RoutedClient learning it
  * checker: <= 1 acking owner per (epoch, shard)
  * saturation plane: per-shard queue-depth check trips and recovers

The 10-seed shard chaos sweep (shard_move + shard_worker_kill under
register traffic) is slow-marked: ``pytest -m chaos``.
"""

import os
import threading
import time

import pytest

from memgraph_tpu.exceptions import (MemgraphTpuError, StaleShardEpoch,
                                     WorkerCrashedError)
from memgraph_tpu.observability.metrics import global_metrics
from memgraph_tpu.query import Interpreter
from memgraph_tpu.query.interpreter import InterpreterContext
from memgraph_tpu.sharding import (MergeError, ShardedClient, ShardMap,
                                   ShardPlane, shard_for_key)
from memgraph_tpu.sharding.router import merge_rows, plan_merge
from memgraph_tpu.storage import InMemoryStorage

SWEEP_SEEDS = list(range(10))


def _metric(name: str) -> float:
    return {n: v for n, _k, v in global_metrics.snapshot()}.get(name,
                                                                0.0)


@pytest.fixture
def plane():
    p = ShardPlane(n_shards=4).start()
    yield p
    p.close()


@pytest.fixture(scope="module")
def loaded():
    """A module-shared plane + client with 60 users and a
    single-process oracle with the identical dataset — the
    scatter-gather tests only READ it, so one build serves them all."""
    p = ShardPlane(n_shards=4).start()
    client = ShardedClient(p)
    oracle_ictx = InterpreterContext(InMemoryStorage())
    oracle = Interpreter(oracle_ictx)
    for i in range(60):
        q = "CREATE (:User {id: $id, age: $age, grp: $grp})"
        params = {"id": i, "age": (i * 7) % 50, "grp": i % 3}
        client.write(q, params, key=i)
        oracle.execute(q, params)
    yield client, oracle
    p.close()


# --------------------------------------------------------------------------
# partitioner
# --------------------------------------------------------------------------


def test_partitioner_stable_and_typed():
    for key in (0, 7, "user-9", 3.0, b"k", None, True):
        assert shard_for_key(key, 4) == shard_for_key(key, 4)
    # int/float that compare equal route identically (Cypher equality)
    assert shard_for_key(7, 8) == shard_for_key(7.0, 8)
    counts = [0] * 4
    for i in range(1000):
        counts[shard_for_key(i, 4)] += 1
    assert min(counts) > 100, f"pathological skew: {counts}"
    with pytest.raises(TypeError):
        shard_for_key(object(), 4)
    with pytest.raises(ValueError):
        shard_for_key(1, 0)


# --------------------------------------------------------------------------
# routed point path + per-shard WAL
# --------------------------------------------------------------------------


def test_point_reads_writes_route_and_per_shard_wal(plane):
    client = ShardedClient(plane)
    for i in range(12):
        _c, _r, ack = client.write(
            "CREATE (:User {id: $id})", {"id": i}, key=i)
        assert ack["epoch"] == client.map.epoch
        assert ack["shard"] == client.shard_for(i)
    for i in range(12):
        _c, rows = client.read(
            "MATCH (n:User {id: $id}) RETURN n.id", {"id": i}, key=i)
        assert rows == [[i]]
    # every shard owns its own durability directory with a live WAL
    wal_dirs = [d for d in os.listdir(plane.base_dir)
                if d.startswith("shard_")]
    assert len(wal_dirs) == 4
    for d in wal_dirs:
        assert any(f.endswith(".wal") or "wal" in f.lower()
                   for f in os.listdir(os.path.join(plane.base_dir, d)))
    # routed ops surfaced in the shard.* metric family
    assert _metric("shard.requests_total") > 0
    assert _metric("shard.map_epoch") == float(plane.map.epoch)


# --------------------------------------------------------------------------
# scatter-gather merge vs the single-process oracle
# --------------------------------------------------------------------------


@pytest.mark.parametrize("query", [
    "MATCH (n:User) RETURN count(n)",
    "MATCH (n:User) RETURN sum(n.age)",
    "MATCH (n:User) RETURN min(n.age), max(n.age), count(n)",
    "MATCH (n:User) WHERE n.age > 20 RETURN count(n), sum(n.age)",
    "MATCH (n:User) RETURN n.grp, count(n), sum(n.age)",
])
def test_scatter_aggregate_matches_oracle(loaded, query):
    client, oracle = loaded
    _cols, rows = client.read(query)
    _ocols, orows, _ = oracle.execute(query)
    assert sorted(map(tuple, rows)) == sorted(map(tuple, orows))


def test_scatter_order_by_limit_matches_oracle(loaded):
    client, oracle = loaded
    q = ("MATCH (n:User) RETURN n.id, n.age "
         "ORDER BY n.age DESC, n.id ASC LIMIT 10")
    _cols, rows = client.read(q)
    _ocols, orows, _ = oracle.execute(q)
    assert rows == orows
    q2 = "MATCH (n:User) RETURN DISTINCT n.grp ORDER BY n.grp"
    _cols, rows = client.read(q2)
    _ocols, orows, _ = oracle.execute(q2)
    assert rows == orows
    assert _metric("shard.scatter_gather_total") > 0


def test_scatter_refuses_unmergeable_shapes(loaded):
    client, _oracle = loaded
    for q in (
        "MATCH (n:User) RETURN avg(n.age)",
        "MATCH (n:User) RETURN count(DISTINCT n.grp)",
        "MATCH (n:User) RETURN count(n) + 1",
        "MATCH (n:User) RETURN n.id ORDER BY n.id SKIP 5 LIMIT 5",
        "MATCH (n:User) WITH count(n) AS c RETURN c",
        "MATCH (n:User) RETURN n.grp, count(n) LIMIT 2",
        "MATCH (n:User) RETURN *",
    ):
        with pytest.raises(MergeError):
            client.read(q)


def test_merge_rows_unit():
    plan = plan_merge("MATCH (n) RETURN n.g, count(n), sum(n.v)")
    merged = merge_rows(plan, [[["a", 2, 10], ["b", 1, 5]],
                               [["a", 3, 7]]])
    assert sorted(map(tuple, merged)) == [("a", 5, 17), ("b", 1, 5)]
    plan = plan_merge("MATCH (n) RETURN n.v ORDER BY n.v LIMIT 3")
    merged = merge_rows(plan, [[[5], [1]], [[3], [2]]])
    assert merged == [[1], [2], [3]]


def test_merge_rows_mixed_type_order_by_is_total():
    """A heterogeneous ORDER BY column across shards must sort by the
    Cypher type rank (strings < numbers, NULL last ascending), not
    raise TypeError out of list.sort."""
    plan = plan_merge("MATCH (n) RETURN n.v ORDER BY n.v")
    merged = merge_rows(plan, [[[3], ["b"], [None]], [[1], ["a"]]])
    assert merged == [["a"], ["b"], [1], [3], [None]]
    plan = plan_merge("MATCH (n) RETURN n.v ORDER BY n.v DESC")
    merged = merge_rows(plan, [[[True], [2.5]], [["x"], [None]]])
    assert merged == [[None], [2.5], [True], ["x"]]


# --------------------------------------------------------------------------
# fencing: epoch-monotonic refresh + stale-map bounce
# --------------------------------------------------------------------------


def test_epoch_monotonic_map_refresh(plane):
    client = ShardedClient(plane)
    epoch0 = client.map.epoch
    # a lower-epoch "authority view" must be refused
    stale = ShardMap(epoch=epoch0 - 1, n_shards=4,
                     owners=dict(plane.map.owners))

    class _StaleAuthority:
        def current(self):
            return stale

    real_placement = plane.placement
    plane.placement = _StaleAuthority()
    try:
        assert client.refresh_map() is False
        assert client.map.epoch == epoch0
    finally:
        plane.placement = real_placement
    plane.shard_move(0)
    assert client.refresh_map() is True
    assert client.map.epoch > epoch0


def test_stale_map_write_bounced_by_fencing_then_retried(plane):
    fresh = ShardedClient(plane)
    stale = ShardedClient(plane)
    fresh.write("CREATE (:User {id: $id})", {"id": 1}, key=1)
    shard = stale.shard_for(1)
    epoch_before = stale.map.epoch
    plane.shard_move(shard)             # stale's map is now behind
    bounces0 = _metric("shard.stale_epoch_bounces_total")
    _c, _r, ack = stale.write(
        "MATCH (n:User {id: 1}) SET n.touched = true", key=1)
    # the write landed on the NEW owner at the NEW epoch after a bounce
    assert ack["epoch"] > epoch_before
    assert stale.map.epoch == plane.map.epoch
    assert _metric("shard.stale_epoch_bounces_total") > bounces0
    _c, rows = fresh.read(
        "MATCH (n:User {id: 1}) RETURN n.touched", key=1)
    assert rows == [[True]]


def test_deposed_owner_is_fenced(plane):
    """The raw worker-level proof: after end_move the old owner refuses
    writes with a typed fenced status, whatever epoch the client
    claims."""
    client = ShardedClient(plane)
    client.write("CREATE (:User {id: $id})", {"id": 5}, key=5)
    shard = client.shard_for(5)
    source = plane.owner(shard)
    _status, _ = plane._direct(source, "begin_move", {})
    _status, _ = plane._direct(source, "end_move",
                               {"epoch": plane.map.epoch + 1})
    status, body = plane._direct(
        source, "write", {"query": "MATCH (n:User {id: 5}) "
                                   "SET n.x = 1",
                          "epoch": plane.map.epoch + 1})
    assert status == "fenced"


# --------------------------------------------------------------------------
# cross-shard 2PC
# --------------------------------------------------------------------------


def _two_keys_on_distinct_shards(client):
    k1 = 0
    s1 = client.shard_for(k1)
    k2 = next(k for k in range(1, 64) if client.shard_for(k) != s1)
    return k1, k2


def test_2pc_cross_shard_commit_atomic(plane):
    client = ShardedClient(plane)
    k1, k2 = _two_keys_on_distinct_shards(client)
    out = client.write_multi([
        (k1, "CREATE (:Acct {id: $id, bal: 10})", {"id": k1}),
        (k2, "CREATE (:Acct {id: $id, bal: 20})", {"id": k2}),
    ])
    assert len(out["shards"]) == 2
    for k, bal in ((k1, 10), (k2, 20)):
        _c, rows = client.read(
            "MATCH (a:Acct {id: $id}) RETURN a.bal", {"id": k}, key=k)
        assert rows == [[bal]]
    assert _metric("shard.twopc_total") > 0


def test_2pc_prepare_failure_presumed_abort(plane):
    client = ShardedClient(plane)
    k1, k2 = _two_keys_on_distinct_shards(client)
    aborts0 = _metric("shard.twopc_aborts_total")
    with pytest.raises(MemgraphTpuError):
        client.write_multi([
            (k1, "CREATE (:Acct {id: $id, bal: 1})", {"id": k1}),
            (k2, "THIS IS NOT CYPHER", None),
        ])
    assert _metric("shard.twopc_aborts_total") > aborts0
    # nothing committed anywhere (atomic abort)
    _c, rows = client.read("MATCH (a:Acct) RETURN count(a)")
    assert rows == [[0]]


def test_2pc_worker_killed_between_prepare_and_commit(plane):
    """The satellite case: participant B dies after voting yes. The
    commit decision re-drives against the respawned worker, whose
    durable pending journal replays the vote — both shards commit."""
    client = ShardedClient(plane)
    k1, k2 = _two_keys_on_distinct_shards(client)
    s1, s2 = client.shard_for(k1), client.shard_for(k2)
    txn_id = "xs-test-kill"
    for shard, k in ((s1, k1), (s2, k2)):
        status, body = plane.request(
            shard, "prepare",
            {"txn_id": txn_id, "epoch": client.map.epoch,
             "statements": [{"query": "CREATE (:Acct {id: $id})",
                             "params": {"id": k}}]})
        assert body["vote"] == "yes"
    plane.kill_worker(s2)               # dies holding the prepared txn
    client._decide_one(s1, txn_id, "commit")
    client._decide_one(s2, txn_id, "commit")   # retries + journal replay
    for k in (k1, k2):
        _c, rows = client.read(
            "MATCH (a:Acct {id: $id}) RETURN count(a)", {"id": k},
            key=k)
        assert rows == [[1]], f"key {k} lost its voted write"
    # the replayed entry left the journal only AFTER its commit — and
    # it did leave, on both the live path (s1) and the replay path (s2)
    health = plane.health()
    assert health[s1]["pending_2pc"] == []
    assert health[s2]["pending_2pc"] == []


def test_2pc_abort_prunes_crashed_participants_journal(plane):
    """A participant that journaled its vote then died must not keep
    the pending entry past the abort decision (presumed-abort journal
    GC): a later buggy commit for the txn_id must find nothing to
    replay, and health output must not accumulate dead entries."""
    client = ShardedClient(plane)
    _k1, k2 = _two_keys_on_distinct_shards(client)
    s2 = client.shard_for(k2)
    txn_id = "xs-test-prune"
    plane.request(s2, "prepare",
                  {"txn_id": txn_id, "epoch": client.map.epoch,
                   "statements": [{"query": "CREATE (:Acct {id: $id})",
                                   "params": {"id": k2}}]})
    plane.kill_worker(s2)
    # the respawned worker recovers the journal entry...
    client._decide_one(s2, txn_id, "abort", best_effort=True)
    # ...and the abort prunes it, durably
    assert plane.health()[s2]["pending_2pc"] == []
    status, _body = plane.request(s2, "decide",
                                  {"txn_id": txn_id,
                                   "decision": "commit"},
                                  raise_typed=False)
    assert status == "unknown_txn"
    _c, rows = client.read("MATCH (a:Acct) RETURN count(a)")
    assert rows == [[0]]


def test_2pc_killed_before_decision_aborts_clean(plane):
    client = ShardedClient(plane)
    k1, k2 = _two_keys_on_distinct_shards(client)
    s1, s2 = client.shard_for(k1), client.shard_for(k2)
    txn_id = "xs-test-abort"
    for shard, k in ((s1, k1), (s2, k2)):
        plane.request(shard, "prepare",
                      {"txn_id": txn_id, "epoch": client.map.epoch,
                       "statements": [{"query":
                                       "CREATE (:Acct {id: $id})",
                                       "params": {"id": k}}]})
    plane.kill_worker(s2)
    client._decide_one(s1, txn_id, "abort", best_effort=True)
    client._decide_one(s2, txn_id, "abort", best_effort=True)
    _c, rows = client.read("MATCH (a:Acct) RETURN count(a)")
    assert rows == [[0]]


# --------------------------------------------------------------------------
# shard-move + crash recovery
# --------------------------------------------------------------------------


def test_shard_move_preserves_data_and_live_writes(plane):
    client = ShardedClient(plane)
    for i in range(30):
        client.write("CREATE (:User {id: $id})", {"id": i}, key=i)
    moved_shard = 0
    acked = []
    halt = threading.Event()

    def writer():
        w = ShardedClient(plane)
        i = 1000
        while not halt.is_set():
            key = next(k for k in range(i, i + 64)
                       if w.shard_for(k) == moved_shard)
            try:
                w.write("CREATE (:User {id: $id})", {"id": key},
                        key=key)
                acked.append(key)
            except MemgraphTpuError:
                pass   # indeterminate during cutover; not acked
            i = key + 1

    t = threading.Thread(target=writer, daemon=True)
    t.start()
    time.sleep(0.1)
    old_owner = plane.map.owners[moved_shard]
    new_owner = plane.shard_move(moved_shard)
    time.sleep(0.1)
    halt.set()
    t.join(timeout=10)
    assert new_owner != old_owner
    client.refresh_map()
    # pre-move data survived the snapshot ship
    _c, rows = client.read("MATCH (n:User) WHERE n.id < 30 "
                           "RETURN count(n)")
    assert rows == [[30]]
    # every write acked during the move survived the delta catch-up
    for key in acked:
        _c, rows = client.read(
            "MATCH (n:User {id: $id}) RETURN count(n)", {"id": key},
            key=key)
        assert rows == [[1]], f"acked write {key} lost in the move"
    assert _metric("shard.moves_total") > 0


def test_shard_move_failure_after_epoch_bump_restores_source(plane):
    """If the move dies AFTER the placement epoch moved to the target,
    the source must be re-assigned (fresh epoch) and re-granted —
    otherwise it stale-bounces every write at the new map epoch forever
    and the shard is permanently write-unavailable."""
    client = ShardedClient(plane)
    client.write("CREATE (:User {id: $id})", {"id": 1}, key=1)
    shard = client.shard_for(1)
    real_direct = plane._direct

    def flaky(worker, op, payload):
        if op == "end_move":
            raise MemgraphTpuError("injected cutover failure")
        return real_direct(worker, op, payload)

    plane._direct = flaky
    try:
        with pytest.raises(MemgraphTpuError, match="injected"):
            plane.shard_move(shard)
    finally:
        plane._direct = real_direct
    # ownership came back to the source at a fresh epoch: routed
    # writes succeed after a refresh instead of bouncing forever
    _c, _r, ack = client.write(
        "MATCH (n:User {id: 1}) SET n.x = 1", key=1)
    assert ack["epoch"] == plane.map.epoch
    _c, rows = client.read("MATCH (n:User {id: 1}) RETURN n.x", key=1)
    assert rows == [[1]]


def test_worker_crash_typed_error_and_wal_recovery(plane):
    client = ShardedClient(plane)
    for i in range(10):
        client.write("CREATE (:User {id: $id})", {"id": i}, key=i)
    victim = client.shard_for(3)
    respawns0 = _metric("shard.worker_respawn_total")
    plane.kill_worker(victim)
    with pytest.raises(WorkerCrashedError):
        plane.request(victim, "read",
                      {"query": "MATCH (n) RETURN count(n)",
                       "params": {}, "epoch": client.map.epoch})
    assert _metric("shard.worker_respawn_total") > respawns0
    # the routed client rides the typed retryable error transparently
    _c, rows = client.read(
        "MATCH (n:User {id: 3}) RETURN n.id", key=3)
    assert rows == [[3]], "per-shard WAL recovery lost a committed row"


def test_write_in_doubt_surfaces_typed_instead_of_blind_resend(
        plane, monkeypatch):
    """An owner that dies AFTER the write hit the wire may already
    have it in the shard WAL — the router must NOT re-send a
    non-idempotent write; it surfaces WriteInDoubtError typed."""
    from memgraph_tpu.exceptions import WriteInDoubtError
    client = ShardedClient(plane)
    client.write("CREATE (:User {id: 1})", key=1)

    def died_mid_request(shard_id, op, payload, raise_typed=True):
        raise WorkerCrashedError(
            f"shard {shard_id} worker died mid-request", in_doubt=True)

    monkeypatch.setattr(client.plane, "request", died_mid_request)
    in_doubt0 = _metric("shard.write_in_doubt_total")
    with pytest.raises(WriteInDoubtError):
        client.write("CREATE (:User {id: 2})", key=2)
    assert _metric("shard.write_in_doubt_total") == in_doubt0 + 1


def test_pre_send_crash_still_retries_transparently(
        plane, monkeypatch):
    """The other crash window — the owner was replaced BEFORE the
    request was sent (in_doubt=False) — is definitely-not-applied, so
    the routed write keeps healing itself."""
    client = ShardedClient(plane)
    real_request = client.plane.request
    calls = {"n": 0}

    def replaced_once(shard_id, op, payload, raise_typed=True):
        calls["n"] += 1
        if calls["n"] == 1:
            raise WorkerCrashedError(
                "replaced while this request queued", in_doubt=False)
        return real_request(shard_id, op, payload,
                            raise_typed=raise_typed)

    monkeypatch.setattr(client.plane, "request", replaced_once)
    _c, _r, ack = client.write("CREATE (:User {id: 9})", key=9)
    assert ack["shard"] == client.shard_for(9)
    assert calls["n"] >= 2
    assert client.read(
        "MATCH (n:User {id: 9}) RETURN n.id", key=9)[1] == [[9]]


def test_worker_errors_decode_typed_across_the_shard_wire(plane):
    """Worker-side classification errors cross the process boundary TYPED:
    the plane re-raises the class the worker named instead of a
    stringly MemgraphTpuError."""
    from memgraph_tpu.exceptions import SyntaxException
    client = ShardedClient(plane)
    client.write("CREATE (:User {id: 1})", key=1)
    with pytest.raises(SyntaxException):
        client.read("MATCH (n RETURN n", key=1)
    # the worker survived the error and keeps serving
    assert client.read(
        "MATCH (n:User {id: 1}) RETURN n.id", key=1)[1] == [[1]]


def test_garbage_frame_on_request_pipe_respawns_worker(plane):
    """A corrupt frame on a shard's request pipe must not wedge the
    plane: the worker drops it and exits, the next routed request
    respawns the shard with per-shard WAL recovery."""
    import struct as structlib

    client = ShardedClient(plane)
    for i in range(8):
        client.write("CREATE (:User {id: $id})", {"id": i}, key=i)
    victim = client.shard_for(5)
    worker = plane.owner(victim)
    respawns0 = _metric("shard.worker_respawn_total")
    # a well-framed envelope whose body is not a pickle at all
    os.write(worker.req_fd,
             structlib.pack("<I", 4) + b"\xff\xff\xff\xff")
    _c, rows = client.read(
        "MATCH (n:User {id: 5}) RETURN n.id", key=5)
    assert rows == [[5]], "WAL recovery lost a committed row"
    assert _metric("shard.worker_respawn_total") > respawns0


# --------------------------------------------------------------------------
# coordinator-owned placement
# --------------------------------------------------------------------------


def test_coordinator_mints_shard_epochs_in_replicated_apply():
    from memgraph_tpu.coordination.coordinator import CoordinatorInstance
    from memgraph_tpu.server.bolt import BoltServer
    from memgraph_tpu.server.client import BoltClient
    from memgraph_tpu.sharding.plane import CoordinatorPlacement
    from tools.mgchaos.cluster import free_ports, wait_for

    raft_port, bolt_port = free_ports(2)
    coord = CoordinatorInstance("c1", "127.0.0.1", raft_port, {},
                                routers=[f"127.0.0.1:{bolt_port}"])
    coord_ictx = InterpreterContext(
        InMemoryStorage(),
        {"advertised_address": f"127.0.0.1:{bolt_port}"})
    coord_ictx.coordinator = coord
    bolt = BoltServer(coord_ictx, "127.0.0.1", bolt_port)
    _t, loop = bolt.run_in_thread()
    coord.start()
    try:
        assert wait_for(lambda: coord.raft.is_leader(), timeout=15)
        epoch0 = coord.epoch
        assert coord.assign_shard(0, "s0g0")
        assert coord.assign_shard(1, "s1g0")
        view = coord.shard_map_view()
        assert view["owners"] == {0: "s0g0", 1: "s1g0"}
        assert view["epoch"] == epoch0 + 2     # minted per assignment
        assert coord.assign_shard(0, "s0g1")   # a move bumps again
        assert coord.shard_map_view()["epoch"] == epoch0 + 3
        # the placement adapter exposes the replicated map to a plane
        placement = CoordinatorPlacement(coord, n_shards=2)
        m = placement.current()
        assert m.owners == {0: "s0g1", 1: "s1g0"}
        assert m.epoch == epoch0 + 3
        # ... and the ROUTE table ships shards under the same epoch,
        # which RoutedClient-style clients read off the Bolt wire
        bc = BoltClient(port=bolt_port)
        rt = bc.route()
        bc.close()
        assert rt["epoch"] == epoch0 + 3
        assert rt["shards"] == {"0": "s0g1", "1": "s1g0"}
        # raft snapshot round-trips the shard map
        snap = coord._snapshot()
        coord._restore(snap)
        assert coord.shard_map_view()["owners"] == {0: "s0g1",
                                                    1: "s1g0"}
    finally:
        coord.stop()
        bolt.stop()
        loop.call_soon_threadsafe(loop.stop)


def test_routed_client_adopts_shard_table_epoch_monotonically():
    from memgraph_tpu.server.client import RoutedClient
    rc = RoutedClient.__new__(RoutedClient)
    rc.known_epoch = 5
    rc.shard_table = {0: "s0g1"}
    # simulate the refresh guard: a lower-epoch table must be ignored
    # (refresh_route_table skips tables below known_epoch before ever
    # touching shard_table — replicate its guard here)
    for epoch, shards, expect in (
            (4, {"0": "old"}, {0: "s0g1"}),
            (6, {"0": "new", "1": "n1"}, {0: "new", 1: "n1"})):
        if epoch >= rc.known_epoch:
            rc.known_epoch = max(rc.known_epoch, epoch)
            rc.shard_table = {int(k): v for k, v in shards.items()}
        assert rc.shard_table == expect


# --------------------------------------------------------------------------
# checker: per-(epoch, shard) ownership
# --------------------------------------------------------------------------


def test_checker_allows_one_owner_per_shard_per_epoch():
    from tools.mgchaos.checker import check_cluster_history
    violations = check_cluster_history([
        {"e": "invoke", "op": 1, "client": 0, "key": "a", "value": 1},
        {"e": "ok", "op": 1, "node": "s0g0", "epoch": 4, "shard": 0},
        {"e": "invoke", "op": 2, "client": 1, "key": "b", "value": 1},
        {"e": "ok", "op": 2, "node": "s1g0", "epoch": 4, "shard": 1},
        {"e": "final", "node": "plane", "epoch": 4,
         "state": {"a": 1, "b": 1}},
    ])
    assert violations == []


def test_checker_flags_two_owners_same_shard_same_epoch():
    from tools.mgchaos.checker import check_cluster_history
    violations = check_cluster_history([
        {"e": "invoke", "op": 1, "client": 0, "key": "a", "value": 1},
        {"e": "ok", "op": 1, "node": "s0g0", "epoch": 4, "shard": 0},
        {"e": "invoke", "op": 2, "client": 1, "key": "b", "value": 1},
        {"e": "ok", "op": 2, "node": "s0g1", "epoch": 4, "shard": 0},
        {"e": "final", "node": "plane", "epoch": 4,
         "state": {"a": 1, "b": 1}},
    ])
    assert any("split-brain" in v and "shard 0" in v
               for v in violations), violations


# --------------------------------------------------------------------------
# saturation plane: per-shard queue depth
# --------------------------------------------------------------------------


def test_saturation_shard_queue_trips_and_recovers():
    from memgraph_tpu.observability.stats import SaturationPlane
    plane = SaturationPlane()
    global_metrics.set_gauge("shard.queue_depth.2",
                             plane.max_shard_queue + 5)
    try:
        verdict = plane.evaluate()
        assert verdict["checks"]["shard_queue"] == "saturated"
        assert any(r["check"] == "shard_queue"
                   for r in verdict["reasons"])
    finally:
        global_metrics.set_gauge("shard.queue_depth.2", 0.0)
    verdict = plane.evaluate()
    assert verdict["checks"]["shard_queue"] == "ok"


# --------------------------------------------------------------------------
# shard chaos: tier-1 smoke + the -m chaos sweep
# --------------------------------------------------------------------------


def test_shard_chaos_smoke():
    from tools.mgchaos.shard import run_shard_chaos
    _hist, violations, stats = run_shard_chaos(
        0, rounds=2, n_shards=2, n_clients=2,
        dwell=(0.2, 0.4), recover=(0.2, 0.3))
    assert violations == [], (violations, stats)
    assert stats["converged"]
    assert stats["acked"] > 0


def test_shard_nemesis_ops_registered_and_scheduled():
    from memgraph_tpu.utils import faultinject as FI
    from tools.mgchaos.nemesis import schedule
    assert "shard_move" in FI.NEMESIS_OPS
    assert "shard_worker_kill" in FI.NEMESIS_OPS
    seen = set()
    for seed in SWEEP_SEEDS:
        for op in schedule(seed, ["0", "1"], ["0", "1"], rounds=4,
                           ops=("shard_move", "shard_worker_kill"),
                           shards=["0", "1"]):
            seen.add(op.kind)
            assert op.targets[0] in ("0", "1")
    assert seen == {"shard_move", "shard_worker_kill"}


@pytest.mark.chaos
@pytest.mark.slow
@pytest.mark.parametrize("seed", SWEEP_SEEDS)
def test_seeded_shard_chaos_sweep(seed):
    """The acceptance sweep: 10 seeds mixing live shard moves and owner
    kills under register traffic — zero acked-write loss, at most one
    acking owner per (epoch, shard), bounded post-heal liveness."""
    from tools.mgchaos.shard import run_shard_chaos
    _hist, violations, stats = run_shard_chaos(seed, rounds=4)
    assert violations == [], \
        f"seed {seed} UNSAFE: {violations}\nstats={stats}"
    assert stats["converged"], f"seed {seed} never converged: {stats}"
