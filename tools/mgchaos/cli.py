"""mgchaos command line: `python -m tools.mgchaos <cmd>`.

    run          one seeded chaos campaign (cluster + nemesis + checker)
    sweep        N seeded campaigns; any violation fails the sweep
    schedule     print a seed's nemesis schedule (byte-replayable)
    check        offline-check a previously dumped history JSONL
    device-smoke one seeded DEVICE nemesis round (accelerator faults
                 through the supervised kernel plane; gate stage)
    device-schedule  print a seed's device nemesis schedule
    shard        one seeded SHARD-plane campaign (shard_move +
                 shard_worker_kill against a live ShardPlane; r18)

Exit codes: 0 safe, 1 violations found, 2 bad invocation.
"""

from __future__ import annotations

import argparse
import os
import sys


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="python -m tools.mgchaos",
        description="memgraph_tpu Jepsen-style cluster chaos harness")
    sub = p.add_subparsers(dest="cmd", required=True)

    rn = sub.add_parser("run", help="one seeded chaos campaign")
    rn.add_argument("--seed", type=int, default=0)
    rn.add_argument("--rounds", type=int, default=4)
    rn.add_argument("--clients", type=int, default=3)
    rn.add_argument("--no-fencing", action="store_true",
                    help="deliberately unsafe SYNC cluster without "
                         "fencing (the checker MUST flag it)")
    rn.add_argument("--expect-unsafe", action="store_true",
                    help="invert the exit code: succeed only when the "
                         "checker FOUND violations (honesty check)")
    rn.add_argument("--dump", metavar="PATH",
                    help="write the history JSONL to PATH")

    sw = sub.add_parser("sweep", help="N seeded campaigns")
    sw.add_argument("--seeds", type=int, default=10)
    sw.add_argument("--seed-base", type=int, default=0)
    sw.add_argument("--rounds", type=int, default=4)

    sub.add_parser(
        "honesty",
        help="checker-honesty gate: the scripted split-brain scenario "
             "must be FLAGGED without fencing and CLEAN with it")

    sc = sub.add_parser("schedule", help="print a seed's nemesis schedule")
    sc.add_argument("--seed", type=int, default=0)
    sc.add_argument("--rounds", type=int, default=4)
    sc.add_argument("--coords", type=int, default=3)
    sc.add_argument("--data", type=int, default=3)

    ck = sub.add_parser("check", help="offline-check a history JSONL")
    ck.add_argument("history", help="path to a chaos history .jsonl")

    ds = sub.add_parser(
        "device-smoke",
        help="seeded device nemesis round: accelerator faults "
             "(call/oom/hang/lost) injected mid-pagerank, mid-kernel-"
             "request and during probe, through the supervised plane")
    ds.add_argument("--seed", type=int, default=0)
    ds.add_argument("--rounds", type=int, default=None,
                    help="truncate the (op x context) matrix "
                         "(default: full matrix)")

    dsch = sub.add_parser("device-schedule",
                          help="print a seed's device nemesis schedule")
    dsch.add_argument("--seed", type=int, default=0)
    dsch.add_argument("--rounds", type=int, default=None)

    sh = sub.add_parser(
        "shard",
        help="one seeded shard-plane campaign: live shard moves + "
             "owner kills under register traffic, offline-checked")
    sh.add_argument("--seed", type=int, default=0)
    sh.add_argument("--rounds", type=int, default=4)
    sh.add_argument("--shards", type=int, default=4)
    sh.add_argument("--clients", type=int, default=4)
    sh.add_argument("--dump", metavar="PATH",
                    help="write the history JSONL to PATH")
    return p


def _force_cpu_backend() -> None:
    """Device-smoke runs on the CPU backend unless the operator opts a
    real accelerator in: the stage validates the resilience machinery
    deterministically, and the dev-gate must not take the chip from
    whatever owns it. Must run before jax is first imported."""
    platform = os.environ.get("MGCHAOS_DEVICE_PLATFORM", "cpu")
    os.environ["JAX_PLATFORMS"] = platform
    flags = os.environ.get("XLA_FLAGS", "")
    if platform == "cpu" and \
            "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = \
            (flags + " --xla_force_host_platform_device_count=2").strip()


def _report(seed: int, violations: list[str], stats: dict) -> None:
    verdict = "SAFE" if not violations else "UNSAFE"
    print(f"seed {seed}: {verdict} — {stats['acked']} acked / "
          f"{stats['ops']} ops, main={stats['main']} "
          f"epoch={stats['epoch']} converged={stats['converged']}")
    for v in violations:
        print(f"  VIOLATION: {v}")


def _cmd_run(args) -> int:
    from .runner import run_chaos
    history, violations, stats = run_chaos(
        args.seed, rounds=args.rounds, n_clients=args.clients,
        fencing=not args.no_fencing)
    _report(args.seed, violations, stats)
    if args.dump:
        history.dump(args.dump)
        print(f"history written to {args.dump}")
    if args.expect_unsafe:
        if violations:
            print("checker-honesty: violations found, as expected")
            return 0
        print("checker-honesty FAILED: the unsafe run was NOT flagged",
              file=sys.stderr)
        return 1
    return 1 if violations else 0


def _cmd_sweep(args) -> int:
    from .runner import run_chaos
    bad = 0
    for i in range(args.seeds):
        seed = args.seed_base + i
        _, violations, stats = run_chaos(seed, rounds=args.rounds)
        _report(seed, violations, stats)
        bad += bool(violations)
    print(f"sweep: {args.seeds - bad}/{args.seeds} seeds safe")
    return 1 if bad else 0


def _cmd_honesty(_args) -> int:
    from .runner import run_split_brain_scenario
    _, unsafe_violations, _ = run_split_brain_scenario(fencing=False)
    _, safe_violations, _ = run_split_brain_scenario(fencing=True)
    ok = bool(unsafe_violations) and not safe_violations
    print(f"checker-honesty: fencing-off flagged={bool(unsafe_violations)}"
          f" ({len(unsafe_violations)} violation(s)), "
          f"fencing-on clean={not safe_violations}")
    for v in unsafe_violations:
        print(f"  [expected] {v}")
    for v in safe_violations:
        print(f"  [UNEXPECTED] {v}", file=sys.stderr)
    return 0 if ok else 1


def _cmd_schedule(args) -> int:
    from .nemesis import schedule_text
    coords = [f"c{i + 1}" for i in range(args.coords)]
    data = [f"i{i + 1}" for i in range(args.data)]
    sys.stdout.write(schedule_text(args.seed, sorted(coords) + sorted(data),
                                   sorted(data), rounds=args.rounds))
    return 0


def _cmd_device_smoke(args) -> int:
    _force_cpu_backend()
    from .device import run_device_matrix
    print(f"device nemesis smoke: seed={args.seed}")
    failures, observed = run_device_matrix(args.seed, rounds=args.rounds)
    for f in failures:
        print(f"  FAILURE: {f}", file=sys.stderr)
    ops = ", ".join(f"{k}→{sorted(v)}" for k, v in sorted(observed.items()))
    print(f"device-smoke: {'UNSAFE' if failures else 'SAFE'} — "
          f"{len(failures)} failure(s); outcomes: {ops}")
    return 1 if failures else 0


def _cmd_device_schedule(args) -> int:
    from .device import device_schedule_text
    sys.stdout.write(device_schedule_text(args.seed, args.rounds))
    return 0


def _cmd_shard(args) -> int:
    from .shard import run_shard_chaos
    history, violations, stats = run_shard_chaos(
        args.seed, rounds=args.rounds, n_shards=args.shards,
        n_clients=args.clients)
    verdict = "SAFE" if not violations else "UNSAFE"
    print(f"shard seed {args.seed}: {verdict} — {stats['acked']} acked "
          f"/ {stats['ops']} ops, epoch={stats['epoch']} "
          f"converged={stats['converged']}")
    for v in violations:
        print(f"  VIOLATION: {v}")
    if args.dump:
        history.dump(args.dump)
        print(f"history written to {args.dump}")
    return 1 if violations else 0


def _cmd_check(args) -> int:
    from .checker import HistoryLog, check_cluster_history
    violations = check_cluster_history(HistoryLog.load(args.history))
    for v in violations:
        print(f"VIOLATION: {v}")
    print(f"{len(violations)} violation(s)")
    return 1 if violations else 0


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    return {"run": _cmd_run, "sweep": _cmd_sweep, "honesty": _cmd_honesty,
            "schedule": _cmd_schedule, "check": _cmd_check,
            "device-smoke": _cmd_device_smoke,
            "device-schedule": _cmd_device_schedule,
            "shard": _cmd_shard}[args.cmd](args)
