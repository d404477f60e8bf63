"""chip_smoke.py rehearsed without the chip.

The phase functions run here at 2k nodes / 20k edges against CPU chip
owners (the child processes inherit JAX_PLATFORMS=cpu from conftest):
every oracle check must hold, and the ONE device assertion must fail.
The script as the driver runs it must exit non-zero and print no result
on a host with no TPU — it has no option that lets it pass without one.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import chip_smoke

N_NODES, N_EDGES = 2_000, 20_000
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def graph():
    return chip_smoke.make_graph(7, N_NODES, N_EDGES)


@pytest.fixture
def seen():
    """Stands in for require_tpu: records what each phase claims about
    its device instead of demanding a chip."""
    calls = []

    def record(device, count=1, backends=None):
        calls.append({"device": device, "count": count,
                      "backends": backends})
    record.calls = calls
    return record


@pytest.fixture(autouse=True)
def _no_stray_children():
    yield
    leaked = list(chip_smoke._CHILDREN)
    chip_smoke._stop_all()
    assert not leaked, f"a phase left {len(leaked)} process(es) running"


def test_phase_served(graph, tmp_path, seen):
    src, dst = graph
    report = chip_smoke.phase_served(src, dst, N_NODES, str(tmp_path),
                                     device_check=seen)
    # every step of the phase ran (a failed check raises SmokeFailure)
    for key in ("load_records_per_s", "cold_call_s", "warm_call_s",
                "after_write_call_s", "compile_total", "backends",
                "native_builder", "cache_dir"):
        assert key in report, key
    assert report["device"]["platform"] == "cpu"
    assert report["compile_total"] > 0
    assert report["native_builder"] == "loaded"
    # a 20k-edge graph on the CPU rides the segment backend; the device
    # claim was made twice (before the load, after PROFILE) with it
    assert report["backends"] == ["semiring_segment"]
    assert [c["backends"] for c in seen.calls] == \
        [None, ["semiring_segment"]]
    # ... and the real assertion refuses exactly that
    for call in seen.calls:
        with pytest.raises(chip_smoke.SmokeFailure):
            chip_smoke.require_tpu(call["device"], call["count"],
                                   call["backends"])


def test_phase_daemon(graph, tmp_path, seen):
    src, dst = graph
    report = chip_smoke.phase_daemon(src, dst, N_NODES, str(tmp_path),
                                     device_check=seen)
    assert report["platform"] == "cpu" and report["pagerank_s"] > 0
    assert len(seen.calls) == 1
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.require_tpu(seen.calls[0]["device"])
    # the daemon's stderr went to a log next to its socket
    assert os.path.exists(os.path.join(str(tmp_path), "k.sock.log"))


def test_phase_mesh_on_four_virtual_devices(graph, seen):
    """`--mesh` rehearsed on 4 of conftest's virtual CPU devices."""
    src, dst = graph
    report = chip_smoke.phase_mesh(src, dst, N_NODES, n_devices=4,
                                   device_check=seen)
    assert report["device"]["platform"] == "cpu"
    assert seen.calls[0]["count"] == 4
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.require_tpu(report["device"], 4)


@pytest.mark.parametrize("device,count,backends,ok", [
    ({"platform": "tpu", "kind": "TPU v5 lite", "count": 1}, 1, None, True),
    ({"platform": "tpu", "kind": "TPU v5 lite", "count": 1}, 1,
     ["semiring_mxu", "benes_pallas"], True),
    ({"platform": "tpu", "kind": "TPU v5 lite", "count": 1}, 1,
     ["semiring_mxu", "benes_rolls"], False),
    ({"platform": "tpu", "kind": "TPU v5 lite", "count": 1}, 1,
     ["semiring_mxu"], False),
    ({"platform": "tpu", "kind": "TPU v5 lite", "count": 4}, 4, None, True),
    ({"platform": "cpu", "kind": "cpu", "count": 1}, 1, None, False),
    ({"platform": "tpu", "kind": "TPU v5 lite", "count": 1}, 4, None,
     False),
    ({"platform": "tpu", "kind": "TPU v5 lite", "count": 1}, 1,
     ["semiring_segment"], False),
    ({"platform": "tpu", "kind": "TPU v5 lite", "count": 1}, 1, [], False),
])
def test_require_tpu(device, count, backends, ok):
    if ok:
        chip_smoke.require_tpu(device, count, backends)
    else:
        with pytest.raises(chip_smoke.SmokeFailure):
            chip_smoke.require_tpu(device, count, backends)


def test_oracles_agree_with_a_plain_loop():
    """The smoke's own references, checked against loops a reader can
    verify by eye (self-loops and duplicate edges included)."""
    src = np.array([0, 0, 1, 2, 2, 2, 3, 0, 40], dtype=np.int64)
    dst = np.array([1, 1, 2, 2, 0, 3, 3, 0, 1], dtype=np.int64)
    n = 41
    rows, distinct = 0, set()
    for e1 in range(len(src)):
        if src[e1] % 80 >= chip_smoke.YOUNG:
            continue
        for e2 in range(len(src)):
            if e2 != e1 and src[e2] == dst[e1]:
                rows += 1
                distinct.add(int(dst[e2]))
    assert chip_smoke.oracle_two_hop(src, dst, n) == (rows, len(distinct))
    ranks = chip_smoke.oracle_pagerank(src, dst, n)
    assert abs(ranks.sum() - 1.0) < 1e-9 and (ranks > 0).all()
    # node 40 has no in-edge: only the uniform restart reaches it
    ppr = chip_smoke.oracle_pagerank(src, dst, n, [0])
    assert abs(ppr.sum() - 1.0) < 1e-9 and ppr[40] == 0 and ppr[3] > 0


@pytest.mark.parametrize("args", [[], ["--mesh"]])
def test_script_fails_without_a_tpu(args):
    """As the driver runs it: non-zero exit, no result line."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")] + args,
        cwd=REPO, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0, proc.stdout
    assert '"ok": true' not in proc.stdout
    assert "FAILED: needs" in proc.stdout
    for line in proc.stdout.splitlines():
        if line.startswith("{"):
            assert not json.loads(line).get("ok")


def test_script_fails_alone_in_an_empty_directory(tmp_path):
    """`chip_smoke.py` and nothing else of the repo: it must not pass."""
    import shutil
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
