"""mglint: tier-1 gate + per-rule fixture tests + lock-order witness.

The gate test runs the analyzer over memgraph_tpu/ exactly like
`python -m tools.mglint memgraph_tpu/` and fails on any unbaselined
finding — so a new lock inversion, swallowed exception, impure kernel,
or unwired WAL opcode/fault point fails CI the commit it appears.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, "tests", "lint_fixtures")

sys.path.insert(0, REPO) if REPO not in sys.path else None

from tools.mglint.core import Project, load_baseline, run_rules  # noqa: E402


def _run(paths, baseline=None, only=None):
    project = Project([os.path.join(REPO, p) for p in paths], cwd=REPO)
    return run_rules(project, baseline or {}, only=only)


def _hits(result, rule):
    return [(f.path.split("/")[-1], f.line) for f in result.findings
            if f.rule == rule]


# --- the gate ---------------------------------------------------------------


def test_package_has_no_unbaselined_findings():
    result = _run(["memgraph_tpu"], baseline=load_baseline())
    assert not result.parse_errors, result.parse_errors
    assert not result.findings, \
        "unbaselined mglint findings:\n" + "\n".join(
            f.render() for f in result.findings)


def test_baseline_is_fully_used_and_justified():
    baseline = load_baseline()   # raises on missing justifications
    for key, justification in baseline.items():
        assert len(justification) >= 25, \
            f"baseline justification for {key} is too thin to mean much"
    result = _run(["memgraph_tpu"], baseline=baseline)
    assert not result.unused_baseline, \
        f"stale baseline entries (fixed or drifted): " \
        f"{result.unused_baseline}"


def test_cli_exits_zero_on_package():
    proc = subprocess.run(
        [sys.executable, "-m", "tools.mglint", "memgraph_tpu/",
         "--json"],
        cwd=REPO, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    doc = json.loads(proc.stdout)
    assert doc["findings"] == []
    assert doc["files_scanned"] > 100


def test_cli_nonzero_on_fixtures():
    proc = subprocess.run(
        [sys.executable, "-m", "tools.mglint", "tests/lint_fixtures",
         "--no-baseline"],
        cwd=REPO, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 1
    assert "MG001" in proc.stdout and "MG005" in proc.stdout
    assert "MG006" in proc.stdout and "MG007" in proc.stdout


# --- per-rule fixtures ------------------------------------------------------


def test_mg001_fires_on_inversion_only():
    result = _run(["tests/lint_fixtures"], only={"MG001"})
    hits = _hits(result, "MG001")
    assert ("mg001_lock_order.py", 13) in hits
    assert ("mg001_lock_order.py", 18) in hits
    # the consistently-ordered decoy class stays silent
    assert all(line in (13, 18) for _p, line in hits), hits


def test_mg002_fires_under_lock_only():
    result = _run(["tests/lint_fixtures"], only={"MG002"})
    hits = _hits(result, "MG002")
    assert ("mg002_blocking.py", 14) in hits            # fsync under lock
    # r12: device dispatches under a server lock are the wedge class
    # the kernel-server supervision contains — both the raw device_put
    # and the compiled-call fault boundary fire; the decoy that ships
    # the dispatch outside the lock stays silent
    assert ("mg002_device_dispatch.py", 18) in hits     # jax.device_put
    assert ("mg002_device_dispatch.py", 22) in hits     # fault boundary
    assert len(hits) == 3, hits


def test_mg003_fires_on_silent_swallow_only():
    result = _run(["tests/lint_fixtures"], only={"MG003"})
    hits = _hits(result, "MG003")
    # one silent swallow; the logging / exception-using handlers and the
    # suppressed one stay silent
    assert hits == [("mg003_swallowed.py", 11)], hits
    assert result.suppressed_count == 1


def test_mg004_fires_on_impurity_only():
    result = _run(["tests/lint_fixtures"], only={"MG004"})
    hits = _hits(result, "MG004")
    assert ("mg004_purity.py", 12) in hits   # print
    assert ("mg004_purity.py", 13) in hits   # np on traced arg
    assert ("mg004_purity.py", 26) in hits   # sleep via reachability
    assert len(hits) == 3, hits              # clean_kernel is silent


def test_mg005_fires_on_coverage_gaps_only():
    result = _run(["tests/lint_fixtures"], only={"MG005"})
    msgs = {f.fingerprint for f in result.findings}
    assert "wal-op:OP_ORPHAN" in msgs
    assert "fault-unregistered:wired.typo" in msgs
    assert "fault-dead:dead.point" in msgs
    # r12 device-nemesis wiring: an op without a fault point and a
    # device point no op can schedule both fire; the fully-wired
    # device_wired/device.wired pair stays silent
    assert "device-nemesis-dead:device_ghost" in msgs
    assert "device-point-unscheduled:device.orphan" in msgs
    # r13 span-registry wiring: an undeclared opened name, a declared
    # never-opened name, and a manual _begin_span call all fire; the
    # wired.span open sites (span + record_span) stay silent
    assert "span-unregistered:unregistered.span" in msgs
    assert "span-dead:dead.span" in msgs
    assert "span-manual:_begin_span" in msgs
    # r14 stat-registry wiring: an unregistered literal, an unmatched
    # dynamic prefix, a dead exact name, a dead family, and a duplicate
    # declaration all fire; wired.stat / wired.family.* stay silent
    assert "stat-unregistered:unregistered.stat" in msgs
    assert "stat-dynamic-unregistered:ghost.family." in msgs
    assert "stat-dead:dead.stat" in msgs
    assert "stat-dead-family:dead.family.*" in msgs
    assert "stat-duplicate:dup.stat" in msgs
    # PR 26 phase mark: a PHASES key that is no declared span fires; the
    # span.* family is emitted by the phase registry itself, so it is
    # not a dead family, and the wired phase stays silent
    assert "phase-undeclared:ghost.phase" in msgs
    assert "stat-dead-family:span.*" not in msgs
    assert "phase-undeclared:wired.span" not in msgs
    assert len(msgs) == 14, msgs             # OP_WIRED is fully covered


def test_mg005_span_family_is_dead_without_a_phase_registry(tmp_path):
    """span.* counts as emitted only while trace.py marks phases: with
    no PHASES it is a dead family like any other."""
    import shutil
    tree = tmp_path / "pkg"
    shutil.copytree(os.path.join(REPO, "tests", "lint_fixtures", "mg005"),
                    tree)
    trace = tree / "observability" / "trace.py"
    text = trace.read_text()
    head, _, rest = text.partition("PHASES = {")
    trace.write_text(head + rest.split("}\n", 1)[1])
    result = _run([str(tree)], only={"MG005"})
    msgs = {f.fingerprint for f in result.findings}
    assert "stat-dead-family:span.*" in msgs
    assert not any(m.startswith("phase-undeclared") for m in msgs)


def test_mg006_fires_on_unguarded_access_only():
    result = _run(["tests/lint_fixtures"], only={"MG006"})
    hits = _hits(result, "MG006")
    assert ("mg006_shared_field.py", 25) in hits   # unguarded write
    assert ("mg006_shared_field.py", 28) in hits   # unguarded read
    assert ("mg006_shared_field.py", 31) in hits   # mutator call = write
    # construction + the lock-guarded decoy stay silent
    assert len([h for h in hits
                if h[0] == "mg006_shared_field.py"]) == 3, hits
    # the dynamic race fixtures agree with the static view: the
    # unguarded one is flagged, the TrackedLock-guarded one is clean
    assert ("race_unguarded.py", 18) in hits
    assert ("race_unguarded.py", 22) in hits
    assert all(p != "race_guarded.py" for p, _l in hits), hits
    assert result.suppressed_count == 1   # Hot.suppressed


def test_mg007_fires_on_split_regions_only():
    result = _run(["tests/lint_fixtures"], only={"MG007"})
    hits = _hits(result, "MG007")
    # atomic + revalidated decoys silent; only the split check-then-act
    assert hits == [("mg007_check_then_act.py", 36)], hits
    assert result.suppressed_count == 1   # Registry.suppressed_split


def test_mg008_fires_on_recompile_hazards_only():
    result = _run(["tests/lint_fixtures"], only={"MG008"})
    hits = _hits(result, "MG008")
    assert ("mg008_recompile.py", 19) in hits   # per-call jit
    assert ("mg008_recompile.py", 37) in hits   # traced branch
    assert ("mg008_recompile.py", 52) in hits   # unhashable static
    assert ("mg008_recompile.py", 67) in hits   # memo on a snapshot
    # the cached builder, structural branches (is None / .ndim), the
    # hashable static and the table-keyed program stay silent; the
    # suppressed rebuild counts
    assert len([h for h in hits
                if h[0] == "mg008_recompile.py"]) == 4, hits
    assert all(p == "mg008_recompile.py" for p, _l in hits), hits


def test_mg008_tells_an_object_memo_from_a_keyed_table():
    """jit-per-object: the shape ops/spmv_mxu.make_semiring_kernel had
    before PR 27 (a closure jitted per kernel, memoised on the graph
    snapshot) is a finding of its own kind; the same builder behind a
    module-level table keyed by its statics is not."""
    result = _run(["tests/lint_fixtures"], only={"MG008"})
    kinds = {f.fingerprint for f in result.findings if f.rule == "MG008"}
    assert "jit-per-object@make_kernel.run_impl" in kinds, kinds
    assert not any("_build_program" in k for k in kinds), kinds
    # and the package's own fixpoint builder goes through its table
    package = _run(["memgraph_tpu"], only={"MG008"})
    flagged = {f.symbol for f in package.findings
               if f.path.endswith("ops/spmv_mxu.py")}
    assert not flagged, flagged


def test_mg009_fires_on_hot_path_syncs_only():
    result = _run(["tests/lint_fixtures"], only={"MG009"})
    hits = _hits(result, "MG009")
    assert ("mg009_host_sync.py", 17) in hits   # np.asarray on device
    assert ("mg009_host_sync.py", 18) in hits   # .item() sync
    # wire bytes, the post-sync host value, the non-hot cold_path and
    # the suppressed reply transfer stay silent
    assert len(hits) == 2, hits
    assert result.suppressed_count == 1


def test_mg010_fires_on_missing_donation_only():
    result = _run(["tests/lint_fixtures"], only={"MG010"})
    hits = _hits(result, "MG010")
    assert ("mg010_donation.py", 21) in hits    # decorator form
    assert ("mg010_donation.py", 40) in hits    # wrapper call form
    # donated variants, the loop-free jit and the suppressed one silent
    assert len(hits) == 2, hits
    assert result.suppressed_count == 1


def test_mg011_fires_on_unaccounted_allocations_only():
    result = _run(["tests/lint_fixtures"], only={"MG011"})
    hits = _hits(result, "MG011")
    assert ("mg011_device_alloc.py", 41) in hits  # jnp.ones, unpriced
    assert ("mg011_device_alloc.py", 42) in hits  # device_put, unpriced
    # the deliberately dead exemption entry is reported at line 1
    assert ("mg011_device_alloc.py", 1) in hits
    # the admission-guarded dispatch (device_put under the verdict, the
    # forward-closure helper), the table-exempted staging, the non-root
    # cold path and the suppressed placement all stay silent
    assert len(hits) == 3, hits
    assert result.suppressed_count == 1
    dead = [f for f in result.findings
            if f.fingerprint.startswith("unused-exemption:")]
    assert len(dead) == 1 and "gone_function" in dead[0].fingerprint


def test_mg011_package_serving_paths_are_accounted():
    # the real tree must be MG011-clean WITHOUT baseline help: every
    # serving-path allocation is either inside an estimator-routed
    # scope or carries a justified EXEMPTIONS entry
    result = _run(["memgraph_tpu"], only={"MG011"})
    assert not result.findings, "\n".join(
        f.render() for f in result.findings)


def test_mg012_fires_on_contract_escapes_only():
    result = _run(["tests/lint_fixtures"], only={"MG012"})
    hits = _hits(result, "MG012")
    # witness lines: the known-raising json.loads in the helper and the
    # undeclared raise — NOT the root function's def line
    assert ("mg012_escape.py", 44) in hits
    assert ("mg012_escape.py", 55) in hits
    prints = {f.fingerprint for f in result.findings}
    assert "escape:fixture.serve:ValueError" in prints
    assert "escape:fixture.serve:CrashError" in prints
    # dead registry entry reported at its own declaration
    assert "dead-root:fixture.dead" in prints
    # the declared AppError narrowing and the total decoy stay silent
    assert len(hits) == 3, hits


def test_mg012_package_roots_hold_their_contracts():
    # the real tree's serving roots must be clean modulo the justified
    # mgflow baseline (shared keys live in tools/mglint/baseline.json)
    result = _run(["memgraph_tpu"], baseline=load_baseline(),
                  only={"MG012"})
    assert not result.findings, "\n".join(
        f.render() for f in result.findings)


def test_mg013_fires_on_unsafe_retries_only():
    result = _run(["tests/lint_fixtures"], only={"MG013"})
    hits = _hits(result, "MG013")
    assert ("mg013_unsafe_retry.py", 48) in hits   # blind-retry
    assert ("mg013_unsafe_retry.py", 50) in hits   # unsafe class
    assert ("mg013_unsafe_retry.py", 61) in hits   # unclassified loop
    assert ("mg013_unsafe_retry.py", 22) in hits   # dead registration
    prints = {f.fingerprint for f in result.findings}
    assert "blind-retry:Client.send_write:TransportError" in prints
    assert "retry-unsafe-class:Client.send_write:ShedError" in prints
    assert "unclassified:Client.unregistered_spin" in prints
    assert "idem-unused:Client.ghost_op" in prints
    # the retryable fetch loop swallowing a retryable class is silent
    assert len(hits) == 4, hits


def test_mg013_package_retries_respect_idempotency():
    result = _run(["memgraph_tpu"], baseline=load_baseline(),
                  only={"MG013"})
    assert not result.findings, "\n".join(
        f.render() for f in result.findings)


def test_new_rules_are_registered_in_catalog():
    from tools.mglint import rules as _rules  # noqa: F401
    from tools.mglint.registry import RULES
    for rule_id in ("MG008", "MG009", "MG010", "MG011", "MG012",
                    "MG013"):
        assert rule_id in RULES
    assert RULES["MG008"].name == "recompile-hazard"
    assert RULES["MG009"].name == "host-sync-in-hot-path"
    assert RULES["MG010"].name == "missing-donation"
    assert RULES["MG011"].name == "unaccounted-device-allocation"
    assert RULES["MG012"].name == "undeclared-escape"
    assert RULES["MG013"].name == "unsafe-retry"


def test_suppression_comment_scopes_to_one_handler():
    # remove the suppression and the second handler must fire too
    path = os.path.join(FIXTURES, "mg003_swallowed.py")
    with open(path) as f:
        text = f.read()
    stripped = text.replace(
        "  # mglint: disable=MG003 — fixture: deliberate", "")
    import tempfile
    with tempfile.TemporaryDirectory() as tmp:
        alt = os.path.join(tmp, "mg003_swallowed.py")
        with open(alt, "w") as f:
            f.write(stripped)
        project = Project([alt], cwd=tmp)
        result = run_rules(project, {}, only={"MG003"})
        assert len([f for f in result.findings
                    if f.rule == "MG003"]) == 2


def test_finding_keys_are_line_stable():
    """Baseline keys must not change when code above a finding moves."""
    import tempfile
    src = ("def f():\n    try:\n        pass\n"
           "    except Exception:\n        pass\n")
    shifted = "import os\n\n\n" + src
    keys = []
    for body in (src, shifted):
        with tempfile.TemporaryDirectory() as tmp:
            p = os.path.join(tmp, "m.py")
            with open(p, "w") as f:
                f.write(body)
            result = run_rules(Project([p], cwd=tmp), {},
                               only={"MG003"})
            assert len(result.findings) == 1
            keys.append(result.findings[0].key)
    assert keys[0] == keys[1]


# --- runtime witness (TrackedLock) ------------------------------------------


def test_tracked_lock_witnesses_cycle():
    from memgraph_tpu.utils import locks
    with locks.isolated_witness():
        a = locks.TrackedLock("Fix.A")
        b = locks.TrackedLock("Fix.B")
        with a:
            with b:
                pass
        with b:
            with a:
                pass
        assert len(locks.violations()) == 1
        with pytest.raises(locks.LockOrderViolation) as exc:
            locks.assert_acyclic()
        assert "Fix.A" in str(exc.value) and "Fix.B" in str(exc.value)
    # the surrounding session's witness state is restored
    assert all("Fix.A" not in f for f, _t in locks.edges())


def test_tracked_lock_consistent_order_is_clean():
    from memgraph_tpu.utils import locks
    with locks.isolated_witness():
        a = locks.TrackedLock("Fix.C")
        b = locks.TrackedLock("Fix.D")
        for _ in range(3):
            with a:
                with b:
                    pass
        locks.assert_acyclic()
        assert ("Fix.C", "Fix.D") in locks.edges()


def test_tracked_rlock_reentry_records_no_self_edge():
    from memgraph_tpu.utils import locks
    with locks.isolated_witness():
        r = locks.TrackedLock("Fix.R", reentrant=True)
        with r:
            with r:
                pass
        assert locks.edges() == {}
        locks.assert_acyclic()


def test_factory_unarmed_returns_plain_lock(monkeypatch):
    import threading
    from memgraph_tpu.utils import locks
    monkeypatch.setenv(locks.ENV_VAR, "0")
    lk = locks.tracked_lock("X.Y")
    assert isinstance(lk, type(threading.Lock()))
    monkeypatch.setenv(locks.ENV_VAR, "1")
    assert isinstance(locks.tracked_lock("X.Y"), locks.TrackedLock)


def test_suite_witness_is_armed_and_recording():
    """conftest arms MG_TRACK_LOCKS for the tier-1 run; storage commits
    must actually produce witnessed edges."""
    from memgraph_tpu.utils import locks
    if not locks.armed():
        pytest.skip("witness disarmed via MG_TRACK_LOCKS=0")
    from memgraph_tpu.storage import InMemoryStorage
    storage = InMemoryStorage()
    acc = storage.access()
    v = acc.create_vertex()
    v.add_label(1)
    acc.commit()
    edges = locks.edges()
    assert any(frm.startswith("Storage.") for frm, _to in edges), edges
    locks.assert_acyclic()
