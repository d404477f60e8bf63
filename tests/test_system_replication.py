"""System-state replication: auth + multi-database DDL survive failover.

Reference contract (/root/reference/src/system/transaction.cpp +
single-writer gate interpreter.cpp:9908-9917): non-graph state changes on
MAIN — users, roles, privileges, CREATE/DROP DATABASE — replicate to
replicas as ordered system transactions, so a promoted replica serves the
same users and databases.
"""

import socket

import pytest

from memgraph_tpu.auth.auth import Auth
from memgraph_tpu.dbms.dbms import DbmsHandler
from memgraph_tpu.query.interpreter import Interpreter, InterpreterContext
from memgraph_tpu.storage import InMemoryStorage


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _rows(interp, q):
    _, rows, _ = interp.execute(q)
    return rows


@pytest.fixture
def cluster(tmp_path):
    def make(name):
        dbms = DbmsHandler(recover_on_startup=False)
        ictx = dbms.get("memgraph")
        ictx.auth_store = Auth()
        interp = Interpreter(ictx)
        # first user gets all privileges; run the session as it so RBAC
        # does not reject the test's admin DDL
        ictx.auth_store.create_user("root", "rootpw")
        interp.username = "root"
        return ictx, interp

    main_ictx, main = make("main")
    rep_ictx, rep = make("replica")
    port = _free_port()
    rep.execute(f"SET REPLICATION ROLE TO REPLICA WITH PORT {port}")
    yield main, rep, main_ictx, rep_ictx, port
    if getattr(rep_ictx, "replication", None) and \
            rep_ictx.replication.replica_server:
        rep_ictx.replication.replica_server.stop()
    if getattr(main_ictx, "replication", None):
        for c in main_ictx.replication.replicas.values():
            c.close()


def test_auth_and_ddl_replicate_live(cluster):
    main, rep, main_ictx, rep_ictx, port = cluster
    main.execute(f"REGISTER REPLICA r1 SYNC TO '127.0.0.1:{port}'")

    main.execute("CREATE USER ada IDENTIFIED BY 'pw1'")
    main.execute("CREATE ROLE admin")
    main.execute("GRANT MATCH, CREATE TO admin")
    main.execute("SET ROLE FOR ada TO admin")
    main.execute("CREATE DATABASE analytics")

    # replica has the same users/roles/databases
    assert "ada" in rep_ictx.auth_store.users()
    assert "admin" in rep_ictx.auth_store.roles()
    assert rep_ictx.auth_store.user_roles("ada") == ["admin"]
    assert rep_ictx.auth_store.authenticate("ada", "pw1")
    assert "analytics" in rep_ictx.dbms.names()

    # drops replicate too
    main.execute("DROP DATABASE analytics")
    main.execute("DROP USER ada")
    assert "ada" not in rep_ictx.auth_store.users()
    assert "analytics" not in rep_ictx.dbms.names()


def test_system_state_in_catchup(cluster):
    """State created BEFORE registration reaches the replica via the
    full-state system catch-up at registration."""
    main, rep, main_ictx, rep_ictx, port = cluster
    main.execute("CREATE USER grace IDENTIFIED BY 's3cret'")
    main.execute("CREATE DATABASE ml")
    main.execute(f"REGISTER REPLICA r1 SYNC TO '127.0.0.1:{port}'")

    assert "grace" in rep_ictx.auth_store.users()
    assert rep_ictx.auth_store.authenticate("grace", "s3cret")
    assert "ml" in rep_ictx.dbms.names()


def test_failover_preserves_system_state(cluster):
    """The e2e: create user + database on MAIN, fail over, both
    exist on the new MAIN."""
    main, rep, main_ictx, rep_ictx, port = cluster
    main.execute(f"REGISTER REPLICA r1 SYNC TO '127.0.0.1:{port}'")
    main.execute("CREATE USER oncall IDENTIFIED BY 'page'")
    main.execute("GRANT MATCH TO oncall")
    main.execute("CREATE DATABASE prod")
    main.execute("CREATE (:Doc {id: 1})")

    # MAIN dies; promote the replica
    for c in main_ictx.replication.replicas.values():
        c.close()
    rep.execute("SET REPLICATION ROLE TO MAIN")

    # graph data AND system state are present on the new MAIN
    assert _rows(rep, "MATCH (n:Doc) RETURN n.id") == [[1]]
    assert "oncall" in rep_ictx.auth_store.users()
    assert rep_ictx.auth_store.authenticate("oncall", "page")
    assert rep_ictx.auth_store.has_privilege("oncall", "MATCH")
    assert "prod" in rep_ictx.dbms.names()
    # and the new MAIN can keep evolving system state
    rep.execute("CREATE USER next IDENTIFIED BY 'x'")
    assert "next" in rep_ictx.auth_store.users()
