"""Exception-flow contracts for the serving planes (mgflow, r24).

Every long-lived dispatch loop and RPC handler in the framework is a
**serving root**: a function whose uncaught exceptions kill a daemon,
wedge a session, or silently drop a request. The registry below is the
machine-checked ground truth for what each root is ALLOWED to let
escape — ``python -m tools.mgflow check`` computes the interprocedural
escape set of every root (raise sites + known-raising calls, narrowed
by except clauses, re-raises and RetryPolicy wrappers) and fails the
gate when an escape is not covered by the root's ``raises`` contract.

The same file declares the typed-outcome **wires**: every outcome
string a server emits on the kernel/mp/2PC protocols must have a
client-side decoder, and every decoder must correspond to an outcome a
server can actually emit (both directions, MG005-style). Drift in
either direction is a gate failure, not a code review hope.

This module is product code (the registries ARE the contract surface,
exported at runtime through ``GET /stats``); the analyzers in
``tools/mgflow`` read it via AST so fixtures can declare their own
miniature registries.
"""

from __future__ import annotations

from dataclasses import dataclass, field


@dataclass(frozen=True)
class ServingRoot:
    """One serving loop/handler + its declared escape contract.

    ``path`` is a repo-relative file suffix; ``qualname`` the dotted
    function path inside it. ``raises`` lists exception type names that
    MAY propagate out of the root — subclasses are covered by their
    bases, so ``("MemgraphTpuError",)`` admits the whole typed
    classification. An empty contract means the root must be total: every
    exception is handled inside the loop (the supervised-daemon shape).
    """

    root_id: str
    path: str
    qualname: str
    raises: tuple = ()
    why: str = ""


@dataclass(frozen=True)
class WireSide:
    """Where one side of a typed-outcome protocol lives and how to read
    its vocabulary out of the source (directives interpreted by
    tools/mgflow/protocol.py):

      ("dict_value", K)    constants under key K in dict literals
      ("dict_keys", N)     constant keys of the module-level dict N
      ("tuple_const", N)   members of the module-level tuple N
      ("send_tuple0", F)   constant first elements of tuple literals
                           passed to calls of F (wire envelopes)
      ("return_tuple0","") constant first elements of returned tuples
      ("compare", V)       constants compared against variable V
                           ("[0]" matches any x[0] subscript)
    """

    path: str
    scope: tuple = ()        # qualname prefixes; () = whole file
    extract: tuple = ()


@dataclass(frozen=True)
class Wire:
    """One server↔client typed-outcome protocol. ``declared`` names a
    module-level tuple that is the canonical vocabulary (falls back to
    the emitted set); ``handled_inline`` lists values consumed
    structurally rather than by literal comparison (e.g. the success
    value behind an ``if reply["ok"]`` check)."""

    wire_id: str
    server: tuple = ()       # WireSide(s)
    client: tuple = ()       # WireSide(s)
    declared: tuple | None = None    # (path, symbol)
    handled_inline: tuple = ()


#: Serving roots and their escape contracts. Keep ``why`` honest: it is
#: printed by ``python -m tools.mgflow list`` and is the reviewer-facing
#: justification for every non-empty contract.
SERVING_ROOTS = (
    ServingRoot(
        root_id="bolt.session",
        path="server/bolt.py",
        qualname="BoltSession.run",
        raises=(),
        why="a Bolt session must die clean: protocol errors map to "
            "FAILURE records, transport errors end the session, and "
            "the terminal catch-all logs anything else",
    ),
    ServingRoot(
        root_id="kernel.dispatch",
        path="server/kernel_server.py",
        qualname="KernelServer._serve_conn",
        raises=(),
        why="the kernel daemon's per-connection loop replies a typed "
            "outcome for every failure; an escape here kills the "
            "connection thread with the client still waiting",
    ),
    ServingRoot(
        root_id="ppr.plane",
        path="server/kernel_server.py",
        qualname="PprServingPlane._run",
        raises=(),
        why="the coalescing batcher thread serves every rider; it must "
            "survive any single batch failing (riders get typed "
            "replies, the thread lives on)",
    ),
    ServingRoot(
        root_id="mp.worker",
        path="server/mp_executor.py",
        qualname="MPReadExecutor._worker_loop",
        raises=(),
        why="the forked read worker ships every error back on the "
            "(err, type, message) envelope; an escape is a silent "
            "worker death the parent only sees as a broken pipe",
    ),
    ServingRoot(
        root_id="shard.worker",
        path="sharding/worker.py",
        qualname="shard_worker_main",
        raises=(),
        why="the shard worker's envelope loop ships errors back typed; "
            "an escape kills the shard until the plane respawns it",
    ),
    ServingRoot(
        root_id="twopc.prepare",
        path="sharding/router.py",
        qualname="ShardedClient._prepare_one",
        raises=("MemgraphTpuError",),
        why="prepare surfaces only the typed classification: vote-no, bounce "
            "exhaustion and worker death all land in MemgraphTpuError "
            "subclasses the 2PC driver's presumed-abort path handles",
    ),
    ServingRoot(
        root_id="twopc.decide",
        path="sharding/router.py",
        qualname="ShardedClient._decide_one",
        raises=("MemgraphTpuError",),
        why="decide re-drives through the durable journal; what it "
            "raises (undeliverable decision, in-doubt loss) is typed "
            "so write_multi can account the abort",
    ),
    ServingRoot(
        root_id="replication.apply",
        path="replication/replica.py",
        qualname="ReplicaServer._serve_main",
        raises=(),
        why="the replica's apply loop must survive any frame: a "
            "corrupt or refused frame drops the connection (the main "
            "reconnects and catches up), it never kills the server",
    ),
    ServingRoot(
        root_id="raft.rpc",
        path="coordination/raft.py",
        qualname="RaftNode._handle",
        raises=(),
        why="a raft RPC handler that raises drops the peer's request "
            "on the floor mid-election; every path must answer",
    ),
    ServingRoot(
        root_id="stream.consumer",
        path="query/streams.py",
        qualname="Stream._loop",
        raises=(),
        why="the consumer loop owns exactly-once ingestion: poll "
            "errors reconnect, poison batches quarantine, stop is the "
            "typed _StreamStopped — nothing else may kill the thread",
    ),
    ServingRoot(
        root_id="http.monitoring",
        path="observability/http.py",
        qualname="start_monitoring_server.handle",
        raises=(),
        why="the monitoring endpoint is the thing operators check "
            "when everything else is broken; it answers or closes, "
            "it does not crash the event loop",
    ),
)


#: Typed-outcome wires (server-emitted ↔ client-decoded, both ways).
WIRES = (
    Wire(
        wire_id="kernel",
        server=(
            WireSide(path="server/kernel_server.py",
                     scope=("KernelServer", "PprServingPlane"),
                     extract=(("dict_value", "outcome"),)),
        ),
        client=(
            WireSide(path="server/kernel_server.py",
                     scope=("KernelClient", "SupervisedKernelClient",
                            "_raise_for_reply", "_OUTCOME_ERRORS"),
                     extract=(("dict_keys", "_OUTCOME_ERRORS"),
                              ("compare", "outcome"))),
        ),
        declared=("server/kernel_server.py", "DISPATCH_OUTCOMES"),
        # "completed" is the ok-path (header["ok"] is checked
        # structurally); "invalid" is the generic-KernelServerError
        # fall-through in _raise_for_reply, which carries the outcome
        handled_inline=("completed", "invalid"),
    ),
    Wire(
        wire_id="mp_executor",
        server=(
            WireSide(path="server/mp_executor.py",
                     scope=("MPReadExecutor._worker_loop",),
                     extract=(("send_tuple0", "_send"),)),
        ),
        client=(
            WireSide(path="server/mp_executor.py",
                     scope=("MPReadExecutor.execute",),
                     extract=(("compare", "[0]"),)),
        ),
        # "ok" is decoded structurally: everything that is not "err"
        # unpacks as (ok, columns, rows, spans)
        handled_inline=("ok",),
    ),
    Wire(
        wire_id="twopc",
        server=(
            WireSide(path="sharding/worker.py",
                     scope=("_handle", "shard_worker_main"),
                     extract=(("return_tuple0", ""),
                              ("send_tuple0", "_send"))),
        ),
        client=(
            WireSide(path="sharding/plane.py",
                     scope=("ShardPlane.request", "ShardPlane._direct"),
                     extract=(("compare", "status"),)),
            WireSide(path="sharding/router.py",
                     scope=("ShardedClient._decide_one",),
                     extract=(("compare", "status"),)),
        ),
        # "ok" falls through request() as the success status
        handled_inline=("ok",),
    ),
)


def flow_stats() -> dict:
    """The runtime-visible contract surface (GET /stats `flow` section):
    how many roots are under contract and how many escape types the
    contracts admit in total. Static by construction — these gauges
    move only when the registry itself changes, which is exactly what
    an operator diffing two deployments wants to see."""
    return {
        "contract_roots": len(SERVING_ROOTS),
        "escapes_total": sum(len(r.raises) for r in SERVING_ROOTS),
        "wires": [w.wire_id for w in WIRES],
        "roots": {r.root_id: list(r.raises) for r in SERVING_ROOTS},
    }
