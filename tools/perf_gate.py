"""Perf-regression gate: bench output vs BASELINE.json envelopes.

A silent CPU fallback once stood in for the headline number: a perf
number nobody can trust is not a perf number. This gate makes the
trajectory enforceable:

  * no accelerator present      -> LOUD skip, exit 0 (a CPU-only dev
                                   box must not fail the gate — but it
                                   must SAY it measured nothing);
  * bench record is degraded    -> FAIL (a degraded run can never
                                   stand in for the headline metric);
  * value under the envelope    -> FAIL on > max_regression (15%)
                                   against BASELINE.json's reference;
  * otherwise                   -> PASS with the measured margin.

Usage:
    python -m tools.perf_gate                 # probe; run bench.py; check
    python -m tools.perf_gate --json F.json   # check an existing record
    python -m tools.perf_gate --latest        # check newest BENCH_r*.json

`tools/gate.sh` runs `--latest` so the dev gate validates the freshest
recorded measurement without re-running the 9-minute bench; CI on real
hardware runs the bare form to measure fresh.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BASELINE_PATH = os.path.join(REPO, "BASELINE.json")
PROBE_TIMEOUT_SEC = 30
BENCH_TIMEOUT_SEC = 700

_PROBE_SNIPPET = (
    "import jax, sys; "
    "b = jax.default_backend(); "
    "print(b); "
    "sys.exit(0 if b != 'cpu' else 3)"
)


def log(msg: str) -> None:
    print(f"perf-gate: {msg}", flush=True)


def accelerator_present() -> bool:
    """Probe in a subprocess (a hung device runtime must not hang the
    gate); exit 3 from the child means 'jax is up but CPU-only'."""
    try:
        proc = subprocess.run(
            [sys.executable, "-c", _PROBE_SNIPPET],
            capture_output=True, timeout=PROBE_TIMEOUT_SEC, text=True,
            env={k: v for k, v in os.environ.items()
                 if k != "JAX_PLATFORMS"})
        log(f"probe backend: {proc.stdout.strip() or '?'} "
            f"(rc={proc.returncode})")
        return proc.returncode == 0
    except (subprocess.TimeoutExpired, OSError) as e:
        log(f"probe failed: {e}")
        return False


def run_bench() -> dict | None:
    """Run bench.py and parse its single JSON stdout line."""
    log("running bench.py for a fresh measurement ...")
    try:
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "bench.py")],
            stdout=subprocess.PIPE, timeout=BENCH_TIMEOUT_SEC)
    except (subprocess.TimeoutExpired, OSError) as e:
        log(f"bench.py did not complete: {e}")
        return None
    for line in reversed(proc.stdout.decode(errors="replace")
                         .strip().splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                return json.loads(line)
            except ValueError:
                continue
    log("bench.py produced no JSON record")
    return None


def latest_bench_json() -> str | None:
    records = sorted(glob.glob(os.path.join(REPO, "BENCH_r*.json")))
    return records[-1] if records else None


def latest_ppr_json() -> str | None:
    records = sorted(glob.glob(os.path.join(REPO, "BENCH_ppr_r*.json")))
    return records[-1] if records else None


def latest_oltp_json() -> str | None:
    records = sorted(glob.glob(os.path.join(REPO, "OLTP_r*.json")))
    return records[-1] if records else None


def latest_mem_json() -> str | None:
    records = sorted(glob.glob(os.path.join(REPO, "MEM_r*.json")))
    return records[-1] if records else None


def check_memory(record: dict | None, envelopes: dict) -> int:
    """mgmem memory-regression gate over the newest MEM_r*.json record:
    per-kernel canonical-point peak bytes vs the BASELINE.json memory
    envelope, plus the donation-effectiveness floor (zero silently
    copied donations). Buffer assignment is DETERMINISTIC — the record
    lowers on the forced CPU mesh — so unlike every perf envelope this
    check runs with or without an accelerator: a refactor that doubles
    a fixpoint's temp footprint or breaks a donated carry fails CI the
    way a 15% perf regression already does."""
    env = envelopes.get("memory")
    if env is None:
        return 0
    if record is None:
        log("FAIL: BASELINE.json declares a memory envelope but no "
            "MEM_r*.json record exists — run `python -m tools.mgmem "
            "check --record MEM_rN.json`")
        return 1
    kernels = env.get("kernels") or {}
    max_growth = float(env.get("max_growth", 0.10))
    got = record.get("kernels") or {}
    rc = 0
    worst = 1.0
    for kernel, ref in sorted(kernels.items()):
        entry = got.get(kernel)
        if entry is None:
            log(f"FAIL: memory record has no entry for {kernel} — "
                "regenerate with the current manifest")
            rc = 1
            continue
        peak = float(entry.get("peak_bytes", 0))
        ceiling = ref * (1.0 + max_growth)
        if peak > ceiling:
            log(f"FAIL: {kernel} canonical peak {peak:,.0f}B grew "
                f"{(peak / ref - 1) * 100:+.1f}% past the envelope "
                f"{ref:,.0f}B (allowed +{max_growth * 100:.0f}%)")
            rc = 1
        if ref:
            worst = max(worst, peak / ref)
        if int(entry.get("donation_dropped", 0)) > 0:
            log(f"FAIL: {kernel} has {entry['donation_dropped']} "
                f"dropped donation(s) — "
                f"{entry.get('dropped_bytes', '?')}B silently copied "
                "instead of aliased")
            rc = 1
    unenveloped = sorted(set(got) - set(kernels))
    if unenveloped:
        log(f"FAIL: kernels without a memory envelope: {unenveloped} "
            "— add them via `python -m tools.mgmem envelopes --write`")
        rc = 1
    if rc == 0:
        log(f"PASS: memory — {len(kernels)} kernel peaks within "
            f"+{max_growth * 100:.0f}% of envelope (worst "
            f"{(worst - 1) * 100:+.1f}%), 0 dropped donations")
    return rc


def check(record: dict, baseline: dict) -> int:
    envelopes = baseline.get("envelopes") or {}
    metric = record.get("metric", "")
    env = envelopes.get(metric)
    if env is None:
        log(f"NO ENVELOPE for metric {metric!r} in BASELINE.json — "
            "add one; gate cannot pass what it cannot compare")
        return 1
    if "degraded" not in record:
        log("FAIL: record predates the degraded-tagging format "
            "(pre-r06) — an untagged number cannot be trusted; "
            "regenerate with the current bench.py")
        return 1
    if record["degraded"]:
        log(f"FAIL: record is degraded (backend="
            f"{record.get('backend', '?')}); a degraded run can never "
            "stand in for the headline metric")
        return 1
    value = float(record.get("value", 0.0))
    ref = float(env["value"])
    max_reg = float(env.get("max_regression", 0.15))
    floor = ref * (1.0 - max_reg)
    if value < floor:
        log(f"FAIL: {metric} = {value:,.0f} is "
            f"{(1 - value / ref) * 100:.1f}% below the envelope "
            f"reference {ref:,.0f} (allowed regression "
            f"{max_reg * 100:.0f}%, floor {floor:,.0f})")
        return 1
    log(f"PASS: {metric} = {value:,.0f} vs envelope {ref:,.0f} "
        f"(margin {(value / ref - 1) * 100:+.1f}%, floor {floor:,.0f})")
    return check_semiring(record, envelopes, ref)


def check_semiring(record: dict, envelopes: dict, headline_ref: float) -> int:
    """r10 semiring-core ratio envelopes over the record's
    extra.semiring sweep.  Runs only for records whose main metric
    already passed (i.e. non-degraded, on-device): the sweep must be
    present, honestly tagged, and inside the f32-parity / bf16-speedup
    envelopes."""
    f32p = envelopes.get("semiring_pagerank_f32_parity")
    spd = envelopes.get("semiring_bf16_speedup")
    if not f32p and not spd:
        return 0
    sem = (record.get("extra") or {}).get("semiring")
    if sem is None:
        log("FAIL: BASELINE.json declares semiring envelopes but the "
            "record carries no extra.semiring sweep — regenerate with "
            "the current bench.py")
        return 1
    if sem.get("backend") == "cpu" and not sem.get("degraded"):
        log("FAIL: semiring sweep ran on cpu but is not tagged "
            "degraded — an untagged CPU fallback cannot stand in for "
            "the on-device core measurement")
        return 1
    if sem.get("degraded"):
        log("FAIL: the main metric is on-device but the semiring sweep "
            f"is degraded (backend={sem.get('backend', '?')}) — the "
            "core sweep must ride the same accelerator")
        return 1
    rc = 0
    if f32p:
        frac = float(f32p["min_fraction_of_headline"])
        f32_eps = float(sem.get("f32_eps", 0.0))
        floor = frac * headline_ref
        if f32_eps < floor:
            log(f"FAIL: semiring f32 pagerank = {f32_eps:,.0f} e/s is "
                f"below the parity floor {floor:,.0f} "
                f"({frac:.0%} of the headline envelope)")
            rc = 1
        else:
            log(f"PASS: semiring f32 parity {f32_eps:,.0f} e/s "
                f"(floor {floor:,.0f})")
    if spd:
        need = float(spd["min"])
        got = float(sem.get("bf16_speedup", 0.0))
        if got < need:
            log(f"FAIL: semiring bf16 speedup {got:.3f}x < required "
                f"{need:.2f}x — the reduced-precision path stopped "
                "paying for its rounding")
            rc = 1
        else:
            log(f"PASS: semiring bf16 speedup {got:.3f}x "
                f"(>= {need:.2f}x)")
    return rc


def check_ppr(record: dict, envelopes: dict) -> int:
    """r16 PPR-serving envelope over a BENCH_ppr_r*.json record: the
    coalescing plane's sustained QPS must beat the sequential baseline
    by the declared factor with a real coalescing ratio, and a
    degraded/untagged record can never stand in for the headline —
    exactly the honesty contract the main metric carries."""
    env = envelopes.get("ppr_qps")
    if env is None:
        return 0
    if record is None:
        log("FAIL: BASELINE.json declares a ppr_qps envelope but no "
            "BENCH_ppr_r*.json record exists — run "
            "benchmarks/ppr_serving_bench.py")
        return 1
    if "degraded" not in record:
        log("FAIL: ppr record carries no degraded tag — an untagged "
            "number cannot be trusted; regenerate with the current "
            "ppr_serving_bench.py")
        return 1
    if record["degraded"]:
        log(f"FAIL: ppr record is degraded (backend="
            f"{record.get('backend', '?')}); a degraded run can never "
            "stand in for the serving headline")
        return 1
    extra = record.get("extra") or {}
    rc = 0
    speedup = float(extra.get("speedup_vs_sequential", 0.0))
    need_speedup = float(env.get("min_speedup_vs_sequential", 5.0))
    if speedup < need_speedup:
        log(f"FAIL: ppr speedup {speedup:.2f}x over the sequential "
            f"baseline < required {need_speedup:.1f}x — coalescing "
            "stopped paying")
        rc = 1
    else:
        log(f"PASS: ppr speedup {speedup:.2f}x (>= {need_speedup:.1f}x)")
    ratio = float(extra.get("coalescing_ratio", 0.0))
    need_ratio = float(env.get("min_coalescing_ratio", 4.0))
    if ratio < need_ratio:
        log(f"FAIL: coalescing ratio {ratio:.2f} < required "
            f"{need_ratio:.1f} — requests are not sharing batches")
        rc = 1
    else:
        log(f"PASS: coalescing ratio {ratio:.2f} "
            f"(>= {need_ratio:.1f})")
    if not extra.get("f32_bit_exact_vs_sequential", False):
        log("FAIL: batched f32 results are not bit-exact vs sequential "
            "personalized_pagerank — the batch changed the answers")
        rc = 1
    return rc


def check_delta(record: dict, envelopes: dict) -> int:
    """r19 mgdelta envelope over the record's ``extra.delta`` stage:
    commit-then-CALL pagerank after a ≤1% edge churn on the resident
    graph must beat the cold full-rebuild path by the declared factor,
    at the same tol (residual-equivalent, the stage records the Linf
    gap), with warm iterations never exceeding cold. Same honesty
    contract as the other sweeps: a CPU (degraded) sub-record can never
    satisfy the on-device envelope, an untagged one FAILS."""
    env = envelopes.get("delta_speedup")
    if env is None:
        return 0
    delta = (record.get("extra") or {}).get("delta")
    if delta is None:
        log("FAIL: BASELINE.json declares a delta_speedup envelope but "
            "the record carries no extra.delta stage — regenerate with "
            "the current bench.py")
        return 1
    if "degraded" not in delta:
        log("FAIL: delta stage carries no degraded tag — an untagged "
            "number cannot be trusted")
        return 1
    if delta.get("backend") == "cpu" and not delta.get("degraded"):
        log("FAIL: delta stage ran on cpu but is not tagged degraded")
        return 1
    if delta["degraded"]:
        log(f"FAIL: delta stage is degraded (backend="
            f"{delta.get('backend', '?')}) — a CPU commit-then-CALL "
            "curve cannot stand in for the resident-graph headline")
        return 1
    rc = 0
    got = float(delta.get("delta_speedup", 0.0))
    need = float(env.get("min_speedup", 10.0))
    if got < need:
        log(f"FAIL: delta speedup {got:.2f}x < required {need:.1f}x — "
            "the incremental path stopped paying for its bookkeeping")
        rc = 1
    else:
        log(f"PASS: delta speedup {got:.2f}x (>= {need:.1f}x)")
    max_churn = float(env.get("max_churn", 0.01))
    if float(delta.get("churn", 1.0)) > max_churn:
        log(f"FAIL: delta stage churn {delta.get('churn')} exceeds the "
            f"envelope's ≤{max_churn:.0%} contract")
        rc = 1
    if int(delta.get("iters_warm", 1 << 30)) > int(
            delta.get("iters_cold", 0)):
        log("FAIL: warm-started fixpoint took MORE iterations than "
            "cold — the seed is hurting, not helping")
        rc = 1
    tol_linf = float(env.get("max_residual_linf", 1e-5))
    if float(delta.get("residual_linf", 1.0)) > tol_linf:
        log(f"FAIL: warm result diverges from cold by Linf "
            f"{delta.get('residual_linf')} > {tol_linf} — warm start "
            "is not residual-equivalent")
        rc = 1
    return rc


def check_tier(record: dict, envelopes: dict) -> int:
    """r21 mgtier envelope over the record's ``extra.tier`` stage: the
    double-buffered block schedule must actually HIDE the declared
    fraction of the H2D transfer behind the SpMV folds (else streaming
    degenerates to serial page-in and out-of-core stops paying), and
    the compressed wire formats must keep their byte-reduction floor.
    Same honesty contract as the other sweeps: a CPU host has no real
    H2D lane, so its sub-record carries ``degraded: true`` and can
    never stand in for the on-device overlap headline; untagged
    records FAIL."""
    env = envelopes.get("tier_overlap")
    if env is None:
        return 0
    tier = (record.get("extra") or {}).get("tier")
    if tier is None:
        log("FAIL: BASELINE.json declares a tier_overlap envelope but "
            "the record carries no extra.tier stage — regenerate with "
            "the current bench.py")
        return 1
    if "degraded" not in tier:
        log("FAIL: tier stage carries no degraded tag — an untagged "
            "number cannot be trusted")
        return 1
    if tier.get("backend") == "cpu" and not tier.get("degraded"):
        log("FAIL: tier stage ran on cpu but is not tagged degraded")
        return 1
    rc = 0
    # the wire codec is host-side and deterministic: its compression
    # floor holds on EVERY host, degraded or not
    ratio_floor = float(env.get("min_wire_ratio", 1.8))
    for prec in ("bf16", "int8"):
        got = float(tier.get(f"wire_ratio_{prec}", 0.0))
        if got < ratio_floor:
            log(f"FAIL: {prec} wire compression {got:.2f}x < required "
                f"{ratio_floor:.1f}x — the block codec stopped "
                "shrinking the transfer")
            rc = 1
        else:
            log(f"PASS: {prec} wire compression {got:.2f}x "
                f"(>= {ratio_floor:.1f}x)")
    if tier["degraded"]:
        log(f"FAIL: tier stage is degraded (backend="
            f"{tier.get('backend', '?')}) — a host-memcpy overlap "
            "curve cannot stand in for the H2D-hiding headline")
        return 1
    got = float(tier.get("transfer_hidden_fraction", 0.0))
    need = float(env.get("min_hidden_fraction", 0.6))
    if int(tier.get("n_blocks", 0)) < 2:
        log("FAIL: tier stage ran with fewer than 2 blocks — nothing "
            "was actually streamed")
        rc = 1
    if got < need:
        log(f"FAIL: hidden-transfer fraction {got:.0%} < required "
            f"{need:.0%} — the double-buffer schedule stopped "
            "overlapping")
        rc = 1
    else:
        log(f"PASS: hidden-transfer fraction {got:.0%} "
            f"(>= {need:.0%})")
    return rc


def check_stream(record: dict, envelopes: dict) -> int:
    """r17 mgstream envelope over the record's ``extra.stream_ingest``
    stage: the supervised FILE-stream consumer must sustain the
    declared ingest rate, keep fresh analytics reads under the latency
    ceiling while ingest runs, and — non-negotiably — survive the
    mid-stream consumer kill with ZERO duplicates and zero loss
    (``exactly_once``). The whole stage is host-side (the plane is the
    Cypher/WAL path, not a kernel), so like the tier wire-ratio floor
    it is deterministic and enforced on EVERY host — there is no
    degraded escape hatch for a broken exactly-once guarantee."""
    env = envelopes.get("stream_ingest")
    if env is None:
        return 0
    stream = (record.get("extra") or {}).get("stream_ingest")
    if stream is None:
        log("FAIL: BASELINE.json declares a stream_ingest envelope but "
            "the record carries no extra.stream_ingest stage — "
            "regenerate with the current bench.py")
        return 1
    rc = 0
    # correctness floors first: these are absolute, not envelopes
    if not stream.get("exactly_once"):
        log(f"FAIL: stream stage is not exactly-once across the "
            f"consumer kill ({int(stream.get('duplicates', -1))} "
            "duplicates) — the transactional-offset protocol is broken")
        rc = 1
    else:
        log(f"PASS: kill+cold-restart exactly-once "
            f"({int(stream.get('total_ingested', 0))} records, 0 dups)")
    if not stream.get("reads_monotone", False):
        log("FAIL: fresh reads regressed during live ingest — "
            "committed ingestion became invisible")
        rc = 1
    rate_floor = float(env.get("min_records_per_sec", 500.0))
    got = float(stream.get("records_per_sec", 0.0))
    if got < rate_floor:
        log(f"FAIL: sustained ingest {got:.0f} records/s < required "
            f"{rate_floor:.0f} — the supervised consumer loop "
            "stopped keeping up")
        rc = 1
    else:
        log(f"PASS: sustained ingest {got:.0f} records/s "
            f"(>= {rate_floor:.0f})")
    p95_ceiling = float(env.get("max_fresh_read_p95_ms", 50.0))
    got = float(stream.get("fresh_read_p95_ms", float("inf")))
    if got > p95_ceiling:
        log(f"FAIL: fresh-read p95 {got:.2f}ms under live ingest > "
            f"ceiling {p95_ceiling:.0f}ms — analytics stopped being "
            "always-fresh")
        rc = 1
    else:
        log(f"PASS: fresh-read p95 {got:.2f}ms under live ingest "
            f"(<= {p95_ceiling:.0f}ms)")
    return rc


def check_sharding(record: dict | None, envelopes: dict) -> int:
    """r18 shard-scaling envelope over the newest OLTP_r*.json record:
    the sharded point-read group must beat the single-process aggregate
    by the declared factor at the declared worker count, the
    cross-shard 2PC group must match its arithmetic oracle, and an
    untagged or degraded record can never stand as the scaling
    headline (a 1-core host's contention-bound curve carries
    ``degraded: true`` + its core count, and fails here exactly like a
    CPU-fallback device record would)."""
    env = envelopes.get("shard_scaling")
    if env is None:
        return 0
    if record is None:
        log("FAIL: BASELINE.json declares a shard_scaling envelope but "
            "no OLTP_r*.json record exists — run benchmarks/mgbench.py "
            "--out OLTP_rN.json")
        return 1
    if "degraded" not in record or "cores" not in record:
        log("FAIL: OLTP record predates the degraded/cores tagging — "
            "an untagged scaling number cannot be trusted; regenerate "
            "with the current mgbench.py")
        return 1
    if record["degraded"]:
        log(f"FAIL: OLTP record is degraded "
            f"({record.get('degraded_reason', 'no reason recorded')}); "
            "a contention-bound curve can never stand in for the "
            "shard-scaling headline")
        return 1
    workers = int(env.get("workers", 4))
    group = next((g for g in record.get("groups", [])
                  if g.get("name") == f"point_read_sharded_{workers}w"),
                 None)
    rc = 0
    if group is None or "speedup_vs_single_process" not in group:
        log(f"FAIL: record has no point_read_sharded_{workers}w group "
            "with a speedup_vs_single_process measurement")
        rc = 1
    else:
        got = float(group["speedup_vs_single_process"])
        need = float(env.get("min_speedup", 3.0))
        if got < need:
            log(f"FAIL: sharded point-read speedup {got:.2f}x at "
                f"{workers} workers < required {need:.1f}x — the "
                "plane stopped scaling")
            rc = 1
        else:
            log(f"PASS: sharded point-read speedup {got:.2f}x at "
                f"{workers} workers (>= {need:.1f}x)")
    twopc = next((g for g in record.get("groups", [])
                  if g.get("name") == "cross_shard_write_2pc"), None)
    if twopc is None or not twopc.get("oracle_match"):
        log("FAIL: cross_shard_write_2pc group missing or its "
            "arithmetic oracle did not match — cross-shard atomicity "
            "is broken or unmeasured")
        rc = 1
    else:
        log("PASS: cross-shard 2PC group matches its oracle")
    return rc


def check_lane(record: dict | None, envelopes: dict) -> int:
    """r20 mglane envelope over the newest OLTP_r*.json record: the
    compiled read lane must serve the aggregate and two-hop groups with
    the declared p99 reduction vs the serial interpreter path, on a
    non-degraded lane sub-record (a CPU lane curve carries
    ``lane.degraded: true`` and fails here exactly like every other
    CPU stand-in — the CPU record still documents the machinery, the
    gate defends the accelerator headline)."""
    env = envelopes.get("columnar_lane")
    if env is None:
        return 0
    if record is None:
        log("FAIL: BASELINE.json declares a columnar_lane envelope but "
            "no OLTP_r*.json record exists — run benchmarks/mgbench.py "
            "--out OLTP_rN.json")
        return 1
    lane = record.get("lane")
    if lane is None:
        log("FAIL: OLTP record carries no lane sub-record — regenerate "
            "with the current mgbench.py")
        return 1
    if "degraded" not in lane:
        log("FAIL: lane sub-record carries no degraded tag — an "
            "untagged number cannot be trusted")
        return 1
    if lane.get("backend") == "cpu" and not lane.get("degraded"):
        log("FAIL: lane groups ran on cpu but are not tagged degraded")
        return 1
    if lane["degraded"]:
        log(f"FAIL: lane sub-record is degraded (backend="
            f"{lane.get('backend', '?')}); a CPU lane curve can never "
            "stand in for the compiled-lane headline")
        return 1
    rc = 0
    if not lane.get("lane_served"):
        log("FAIL: lane groups did not actually serve from the "
            "compiled lane (lane.hit_total never moved)")
        rc = 1
    need = float(env.get("min_p99_speedup", 10.0))
    for group_name in env.get("groups", ("aggregate_lane_on",
                                         "two_hop_lane_on")):
        group = next((g for g in record.get("groups", [])
                      if g.get("name") == group_name), None)
        if group is None or "p99_speedup_vs_serial" not in group:
            log(f"FAIL: record has no {group_name} group with a "
                "p99_speedup_vs_serial measurement")
            rc = 1
            continue
        got = float(group["p99_speedup_vs_serial"])
        if got < need:
            log(f"FAIL: {group_name} p99 speedup {got:.1f}x < required "
                f"{need:.1f}x — the compiled lane stopped paying")
            rc = 1
        else:
            log(f"PASS: {group_name} p99 speedup {got:.1f}x "
                f"(>= {need:.1f}x)")
    return rc


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="perf_gate")
    ap.add_argument("--json", help="check an existing bench JSON record")
    ap.add_argument("--latest", action="store_true",
                    help="check the newest BENCH_r*.json in the repo")
    ap.add_argument("--baseline", default=BASELINE_PATH)
    args = ap.parse_args(argv)

    with open(args.baseline) as f:
        baseline = json.load(f)

    # the memory gate is deterministic (forced CPU-mesh lowering), so
    # it runs BEFORE the accelerator probe can skip anything
    mem_path = latest_mem_json()
    mem_record = None
    if mem_path is not None:
        log(f"checking newest memory record "
            f"{os.path.basename(mem_path)}")
        with open(mem_path) as f:
            mem_record = json.load(f)
    rc_mem = check_memory(mem_record, baseline.get("envelopes") or {})

    if not accelerator_present():
        log("=" * 62)
        log("SKIPPED: no accelerator present — nothing was measured")
        log("(the deterministic memory gate above still ran).")
        log("This gate only defends the perf trajectory on real")
        log("hardware; do NOT read this skip as a pass.")
        log("=" * 62)
        return rc_mem

    if args.json:
        path = args.json
    elif args.latest:
        path = latest_bench_json()
        if path is None:
            log("no BENCH_r*.json records found")
            return 1
        log(f"checking newest record {os.path.basename(path)}")
    else:
        record = run_bench()
        if record is None:
            log("FAIL: could not obtain a bench measurement")
            return 1
        return (rc_mem
                or check(record, baseline)
                or check_delta(record, baseline.get("envelopes") or {})
                or check_tier(record, baseline.get("envelopes") or {})
                or check_stream(record, baseline.get("envelopes") or {}))

    with open(path) as f:
        record = json.load(f)
    rc = rc_mem or check(record, baseline)
    rc = rc or check_delta(record, baseline.get("envelopes") or {})
    rc = rc or check_tier(record, baseline.get("envelopes") or {})
    rc = rc or check_stream(record, baseline.get("envelopes") or {})
    if args.latest:
        # the serving-plane record rides the same --latest gate run
        ppr_path = latest_ppr_json()
        ppr_record = None
        if ppr_path is not None:
            log(f"checking newest ppr record "
                f"{os.path.basename(ppr_path)}")
            with open(ppr_path) as f:
                ppr_record = json.load(f)
        rc = rc or check_ppr(ppr_record,
                             baseline.get("envelopes") or {})
        # the OLTP shard-scaling record rides the same --latest run
        oltp_path = latest_oltp_json()
        oltp_record = None
        if oltp_path is not None:
            log(f"checking newest OLTP record "
                f"{os.path.basename(oltp_path)}")
            with open(oltp_path) as f:
                oltp_record = json.load(f)
        rc = rc or check_sharding(oltp_record,
                                  baseline.get("envelopes") or {})
        rc = rc or check_lane(oltp_record,
                              baseline.get("envelopes") or {})
    return rc


if __name__ == "__main__":
    sys.exit(main())
