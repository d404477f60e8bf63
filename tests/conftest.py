"""Test configuration: force JAX onto a virtual 8-device CPU mesh.

The tests never need a chip: every sharding/multi-chip test runs against
8 virtual CPU devices, and the environment alone (set here, before jax is
first imported) selects the backend.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"

# Arm the runtime lock-order witness (memgraph_tpu/utils/locks.py) for the
# whole suite: every lock the package creates becomes a TrackedLock, the
# actual acquisition graph is recorded, and the session fails if any cycle
# was witnessed (the dynamic validation of mglint's static MG001 rule).
# Must happen BEFORE any memgraph_tpu import creates a lock; opt out with
# MG_TRACK_LOCKS=0.
os.environ.setdefault("MG_TRACK_LOCKS", "1")
prev = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in prev:
    os.environ["XLA_FLAGS"] = (prev + " --xla_force_host_platform_device_count=8").strip()

import sys  # noqa: E402

import pytest  # noqa: E402

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def pytest_configure(config):
    """MG_SAN=1: arm the vector-clock race detector for the whole suite.

    Every TrackedLock acquire/release and every shared_field annotation
    feeds the process-global detector; the session fails if any access
    pair is unordered by happens-before. Tests that arm their own
    detector via `mgsan.detecting()` stack on top and restore this one
    on exit."""
    from memgraph_tpu.utils import sanitize
    if not sanitize.armed():
        return
    if _REPO not in sys.path:
        sys.path.insert(0, _REPO)
    from tools.mgsan import racedetect
    config._mgsan_detector = racedetect.arm()


@pytest.fixture
def storage():
    from memgraph_tpu.storage import InMemoryStorage
    return InMemoryStorage()


def pytest_terminal_summary(terminalreporter, exitstatus, config):
    """Lock-order witness verdict for the whole session."""
    from memgraph_tpu.utils import locks
    if not locks.armed():
        return
    edges = locks.edges()
    bad = locks.violations()
    terminalreporter.write_line(
        f"lock-order witness: {len(edges)} edge(s) recorded, "
        f"{len(bad)} cycle(s)"
        + (" — ACYCLIC" if not bad else " — VIOLATIONS BELOW"))
    for cycle, site in bad:
        terminalreporter.write_line(
            f"  CYCLE {' -> '.join(cycle)} closed at {site}", red=True)
    det = getattr(config, "_mgsan_detector", None)
    if det is not None:
        terminalreporter.write_line(
            f"mgsan race detector: {len(det.races)} race(s)"
            + (" — CLEAN" if not det.races else " — RACES BELOW"))
        for race in det.races:
            terminalreporter.write_line(f"  {race.render()}", red=True)


def pytest_sessionfinish(session, exitstatus):
    """Fail the run on witnessed lock-order cycles or data races."""
    from memgraph_tpu.utils import locks
    if locks.armed() and locks.violations():
        session.exitstatus = 1
    det = getattr(session.config, "_mgsan_detector", None)
    if det is not None and det.races:
        session.exitstatus = 1
