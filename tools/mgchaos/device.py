"""Seeded device-plane nemesis: byte-replayable accelerator fault
schedules driven through the supervised kernel plane.

The cluster nemesis (nemesis.py) faults LINKS; this module faults the
ACCELERATOR. Each round arms one scalar ``device.*`` fault point
(``faultinject.DEVICE_NEMESIS_OPS`` — the MG005-checked registry) at a
seeded dispatch hit, in one of three injection contexts:

    pagerank        mid-flight in a checkpoint-resumable mesh pagerank
                    (parallel/checkpoint.py) — must resume from the last
                    checkpoint and produce a BIT-EXACT result
    kernel_request  mid-flight in a supervised kernel-server request —
                    the client must get either a correct result (after
                    typed retries) and never wedge
    probe           during the kernel server's device probe — the
                    failure must classify to its typed outcome

A schedule is a pure function of the seed (``device_schedule_text``
renders it canonically, so determinism is testable as byte identity),
and the default schedule enumerates every (op, context) pair — coverage
of the whole matrix by construction, which is what the gate's
``device-smoke`` stage and the 10-seed sweep in
tests/test_device_resilience.py replay.
"""

from __future__ import annotations

import logging
import random
import time
from dataclasses import dataclass

import numpy as np

from memgraph_tpu.utils import faultinject as FI

log = logging.getLogger(__name__)

DEVICE_CONTEXTS = ("pagerank", "kernel_request", "probe")

#: resumable-loop checkpoint interval the smoke rounds run with
SMOKE_K = 4
#: fixed iteration budget (tol=-1 pins the run to exactly this many)
SMOKE_ITERS = 16


@dataclass(frozen=True)
class DeviceOp:
    round: int
    kind: str        # one of faultinject.DEVICE_NEMESIS_OPS
    context: str     # one of DEVICE_CONTEXTS
    hit: int         # 1-based dispatch hit at which the fault fires
    arg: float       # hang delay seconds (0 when unused)

    def render(self) -> str:
        return (f"r{self.round:02d} {self.kind}@{self.context}"
                f" hit={self.hit} arg={self.arg:.3f}")


def device_schedule(seed: int, rounds: int | None = None,
                    ops: tuple[str, ...] = FI.DEVICE_NEMESIS_OPS,
                    contexts: tuple[str, ...] = DEVICE_CONTEXTS,
                    max_hit: int = 3) -> list[DeviceOp]:
    """Derive a deterministic device fault schedule from ``seed``.

    The default (rounds=None) enumerates every (op, context) pair once,
    in seeded order — full matrix coverage per seed. An explicit
    ``rounds`` truncates (smoke) or extends by seeded resampling."""
    for op in ops:
        if op not in FI.DEVICE_NEMESIS_OPS:
            raise ValueError(f"unknown device nemesis op {op!r}")
    for ctx in contexts:
        if ctx not in DEVICE_CONTEXTS:
            raise ValueError(f"unknown device context {ctx!r}")
    rng = random.Random(seed)
    pairs = [(op, ctx) for op in ops for ctx in contexts]
    rng.shuffle(pairs)
    if rounds is not None:
        while len(pairs) < rounds:
            pairs.append(pairs[rng.randrange(len(pairs))])
        pairs = pairs[:rounds]
    out = []
    for i, (op, ctx) in enumerate(pairs):
        arg = round(0.25 + rng.random() * 0.25, 3) \
            if op == "device_hang" else 0.0
        out.append(DeviceOp(round=i, kind=op, context=ctx,
                            hit=rng.randint(1, max_hit), arg=arg))
    return out


def device_schedule_text(seed: int, rounds: int | None = None,
                         **kw) -> str:
    """Canonical one-op-per-line rendering; same seed ⇒ identical bytes."""
    ops = device_schedule(seed, rounds, **kw)
    lines = [f"device-nemesis seed={seed} rounds={len(ops)}"]
    lines += [op.render() for op in ops]
    return "\n".join(lines) + "\n"


def _arm(op: DeviceOp) -> None:
    point = FI.device_point_for_op(op.kind)
    if op.kind == "device_hang":
        FI.arm(point, "delay", arg=op.arg, at=op.hit)
    else:
        # in-process rounds arm "raise" even for device_lost — the
        # process-kill variant needs a real daemon subprocess and lives
        # in the device_chaos-marked test tier
        FI.arm(point, "raise", at=op.hit)


def _counters() -> dict[str, float]:
    from memgraph_tpu.observability.metrics import global_metrics
    return {name: value for name, _k, value in global_metrics.snapshot()
            if name.startswith(("kernel_server.", "analytics."))}


class DeviceSmokeEnv:
    """Shared state for a device nemesis campaign: a tiny graph, the
    mesh context, an in-thread supervised kernel server, and unfaulted
    reference results every round is compared against bit-exactly."""

    N, E = 200, 1200

    def __init__(self, tmpdir: str):
        import os
        import threading
        from memgraph_tpu.ops import csr
        from memgraph_tpu.parallel.mesh import get_mesh_context
        from memgraph_tpu.server.kernel_server import (
            KernelClient, KernelServer, SupervisedKernelClient)
        from memgraph_tpu.utils.retry import RetryPolicy

        rng = np.random.default_rng(7)
        self.src = rng.integers(0, self.N, self.E)
        self.dst = rng.integers(0, self.N, self.E)
        self.graph = csr.from_coo(self.src, self.dst, n_nodes=self.N)
        self.ctx = get_mesh_context(min(2, _device_count()))
        self.ref_ranks = self._pagerank()           # unfaulted reference

        self.sock = os.path.join(tmpdir, "device_smoke.sock")
        self.server = KernelServer(self.sock, wedge_after_s=30.0,
                                   checkpoint_every=SMOKE_K)
        self._thread = threading.Thread(target=self.server.serve_forever,
                                        daemon=True)
        self._thread.start()
        deadline = time.monotonic() + 60
        probe = None
        while time.monotonic() < deadline:
            try:
                probe = KernelClient(self.sock, timeout=10)
                break
            except OSError:
                time.sleep(0.05)
        if probe is None:
            raise RuntimeError("in-thread kernel server never came up")
        probe.close()
        self.client = SupervisedKernelClient(
            self.sock, spawn=False, deadline_s=5.0,
            retry=RetryPolicy(base_delay=0.1, max_delay=0.5,
                              max_retries=4, attempt_timeout=30.0))
        self.ref_server = self._kernel_request()    # unfaulted reference

    def _pagerank(self, report=None):
        from memgraph_tpu.parallel import analytics
        ranks, _err, _it = analytics.pagerank_mesh(
            self.graph, self.ctx, max_iterations=SMOKE_ITERS, tol=-1.0,
            checkpoint_every=SMOKE_K, report=report)
        return np.asarray(ranks)

    def _pagerank_deadline(self, report):
        """The chunk-deadline variant used for hang rounds."""
        from memgraph_tpu.ops.csr import shard_csr
        from memgraph_tpu.parallel.distributed import \
            pagerank_partition_centric
        scsr = shard_csr(self.graph, self.ctx, by="src")
        ranks, _e, _i = pagerank_partition_centric(
            scsr, self.ctx, max_iterations=SMOKE_ITERS, tol=-1.0,
            checkpoint_every=SMOKE_K, chunk_deadline_s=0.05,
            report=report)
        return np.asarray(ranks)

    def _kernel_request(self):
        ranks, _err, _it = self.client.pagerank(
            src=self.src, dst=self.dst, n_nodes=self.N,
            graph_key="smoke", max_iterations=SMOKE_ITERS, tol=1e-12)
        return np.asarray(ranks)

    def close(self):
        try:
            self.client.close()
        except OSError as e:
            log.debug("closing smoke client: %s", e)
        try:
            from memgraph_tpu.server.kernel_server import KernelClient
            c = KernelClient(self.sock, timeout=5)
            c.shutdown()
            c.close()
        except OSError as e:
            log.debug("shutting down smoke server: %s", e)


def _device_count() -> int:
    import jax
    return len(jax.devices())


def run_device_round(env: DeviceSmokeEnv, op: DeviceOp) -> tuple[list, set]:
    """Execute one schedule round. Returns (failures, observed outcomes)."""
    from memgraph_tpu.parallel.checkpoint import RunReport
    from memgraph_tpu.server.kernel_server import probe_device
    from memgraph_tpu.utils.devicefault import classify_device_error

    failures: list[str] = []
    observed: set[str] = set()
    FI.reset()
    _arm(op)
    before = _counters()
    t0 = time.monotonic()
    try:
        if op.context == "pagerank":
            report = RunReport()
            ranks = env._pagerank_deadline(report) \
                if op.kind == "device_hang" else env._pagerank(report)
            if not np.array_equal(ranks, env.ref_ranks):
                failures.append(f"{op.render()}: pagerank result is not "
                                "bit-exact vs the unfaulted run")
            observed.update(report.faults)
            if report.slow_chunks:
                observed.add("deadline_exceeded")
            if report.lost_spans and max(report.lost_spans) > SMOKE_K:
                failures.append(f"{op.render()}: resume redid "
                                f"{max(report.lost_spans)} iterations "
                                f"(> k={SMOKE_K})")
            if op.kind != "device_hang" and not report.resumes:
                failures.append(f"{op.render()}: armed fault never "
                                "produced a resume")
        elif op.context == "kernel_request":
            from memgraph_tpu.server.kernel_server import KernelOom
            # hang rounds get a deadline BELOW the hang delay so the
            # dispatch must come back as a typed deadline_exceeded
            # (everything is warm by now; a healthy dispatch is ms)
            deadline = 0.12 if op.kind == "device_hang" else None
            try:
                ranks, _e, _i = env.client.pagerank(
                    graph_key="smoke", max_iterations=SMOKE_ITERS,
                    tol=1e-12, deadline_s=deadline)
            except KernelOom:
                if op.kind != "device_oom":
                    raise
                # oom at the dispatch boundary is typed and deliberately
                # NOT retried (deterministic against this budget) —
                # the typed propagation IS the contract
                observed.add("oom")
            else:
                if not np.array_equal(np.asarray(ranks), env.ref_server):
                    failures.append(f"{op.render()}: kernel request "
                                    "result is not bit-exact vs the "
                                    "unfaulted run")
        elif op.context == "probe":
            # the armed hit counts probe DISPATCHES: probe until it fires
            fired = None
            for _ in range(op.hit):
                t_p = time.monotonic()
                try:
                    probe_device()
                except Exception as e:  # noqa: BLE001 — classified below
                    kind = classify_device_error(e)
                    if kind is None:
                        raise
                    fired = kind
                    observed.add(kind)
                    break
                if op.kind == "device_hang" and \
                        time.monotonic() - t_p >= op.arg:
                    fired = "deadline_exceeded"
                    observed.add("deadline_exceeded")
                    break
            if fired is None:
                failures.append(f"{op.render()}: probe fault never "
                                "fired")
    except Exception as e:  # noqa: BLE001 — a round must not kill the run
        failures.append(f"{op.render()}: unexpected escape "
                        f"{type(e).__name__}: {e}")
    finally:
        FI.reset()
    elapsed = time.monotonic() - t0
    if elapsed > 30.0:
        failures.append(f"{op.render()}: round took {elapsed:.1f}s — "
                        "a client wedged")
    after = _counters()
    for name, value in after.items():
        if value > before.get(name, 0.0):
            for outcome in ("deadline_exceeded", "device_error", "oom",
                            "shed", "device_lost"):
                if outcome in name:
                    observed.add(outcome)
            if "device_fault" in name:
                observed.add(name.split(".")[-1].replace("_total", ""))
    return failures, observed


#: what each op must have visibly produced somewhere across its rounds
_EXPECT = {
    "device_call": {"device_error"},
    "device_oom": {"oom"},
    "device_hang": {"deadline_exceeded"},
    "device_lost": {"device_lost", "device_error"},
}


def run_device_matrix(seed: int, rounds: int | None = None,
                      tmpdir: str | None = None, echo=print):
    """One seeded campaign over the (op × context) matrix. Returns
    (failures, observed_by_op)."""
    import tempfile
    sched = device_schedule(seed, rounds)
    failures: list[str] = []
    observed_by_op: dict[str, set] = {}
    with tempfile.TemporaryDirectory() as td:
        env = DeviceSmokeEnv(tmpdir or td)
        try:
            for op in sched:
                f, obs = run_device_round(env, op)
                failures.extend(f)
                observed_by_op.setdefault(op.kind, set()).update(obs)
                echo(f"  {op.render()}: "
                     f"{'FAIL' if f else 'ok'} observed={sorted(obs)}")
        finally:
            env.close()
    for op_kind, wanted in _EXPECT.items():
        if op_kind not in observed_by_op:
            continue   # not scheduled (truncated smoke)
        if not (observed_by_op[op_kind] & wanted):
            failures.append(
                f"op {op_kind}: none of the expected typed outcomes "
                f"{sorted(wanted)} was ever observed "
                f"(got {sorted(observed_by_op[op_kind])})")
    return failures, observed_by_op
