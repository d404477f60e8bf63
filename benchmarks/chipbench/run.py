"""One run of one cell of BENCHMARK.json, on the chip, through Bolt.

    python benchmarks/chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

This parent never imports jax. It reads the cell's files (deployment,
traffic mix, per-layer metrics: all data, found by the names in
BENCHMARK.json) and the three modules those files name (``seams_of``:
the deployment's owner layout and data set, the mix's semantics), makes
the deployment's data (its ``graph_seed``: one data set for every run)
and the traffic (``--seed``), has the layout start the deployment's
processes, refuses to go on unless the one that holds the chip reports
a TPU with the cell's chip count, loads over Bolt, warms what the
window will use, measures for ``--seconds``, stops the processes, and
only then computes the plain reference (the semantics on the data
set's state) and compares. The last line of stdout is the result;
without a chip there is none and the exit code is not 0.

``--trace 0`` reports the cell's end-to-end metrics with no profiler
started. ``--trace 1`` has the owner trace a short slice at the start
of the window and reports the per-layer metrics.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import signal  # noqa: E402
import statistics  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402

import numpy as np  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
for _p in (HERE, REPO):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import layers  # noqa: E402
import procs  # noqa: E402
import seams  # noqa: E402
import traffic as traffic_mod  # noqa: E402
from procs import RunFailure, connect, flat_stats, say, stop_all  # noqa: E402,F401

MASTER_TIMEOUT_S = 350          # the driver allows a run 360
TRACE_STOP_TIMEOUT_S = 120

_CHILDREN = procs.CHILDREN      # what the layouts have started
_free_port = procs.free_port


# --------------------------------------------------------------------------
# the cell's files
# --------------------------------------------------------------------------

def _load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(workload: str, root: str = REPO) -> dict:
    """Everything BENCHMARK.json and the data files say about one cell.
    `root` holds the BENCHMARK.json; its first `paths` entry is the
    benchmark's directory, searched before this one."""
    bench = _load_json(root, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise RunFailure(f"BENCHMARK.json has no workload {workload!r}; "
                         f"it has {sorted(cells)}")
    cell = cells[workload]
    config_entry = next(c for c in bench["configs"]
                        if c["name"] == cell["config"])
    dirs = list(dict.fromkeys(
        [os.path.abspath(os.path.join(root, bench["paths"][0])), HERE]))

    def data(sub, name, needed=True):
        path = seams.find(dirs, sub, name, ".json")
        if path is None:
            if needed:
                raise RunFailure(f"no {sub}/{name}.json under {dirs}")
            return None
        return _load_json(path)

    def of_cell(metric):
        return workload in metric.get("workloads", [workload])

    layer_metrics = []
    for metric in bench["per_layer"]:
        if of_cell(metric):
            layer_metrics.append(dict(
                data("layer_metrics", metric["name"]), name=metric["name"],
                unit=metric["unit"]))
    return {
        "name": workload,
        "chips": cell["chips"],
        "dirs": dirs,
        "config": _load_json(root, config_entry["file"]),
        "mix": data("traffic", cell["traffic"]),
        "end_to_end": [m for m in bench["end_to_end"] if of_cell(m)],
        "per_layer": layer_metrics,
        "limits": (data("cells", workload, needed=False)
                   or {"limits": {}})["limits"],
    }


def seams_of(cell: dict):
    """(owner layout, data set, semantics): the three modules that the
    deployment's and the mix's files name, each ``<kind>/<name>.py`` of
    the benchmark's directory. A file that names none gets today's."""
    config, mix = cell["config"], cell["mix"]
    names = {"owners": config.get("owner", {}).get("kind"),
             "datasets": config.get("dataset"),
             "semantics": mix.get("semantics")}
    try:
        return tuple(seams.load_module(
            cell.get("dirs"), sub, names[sub] or seams.DEFAULTS[sub])
            for sub in ("owners", "datasets", "semantics"))
    except LookupError as e:
        raise RunFailure(str(e)) from e


def require_tpu(device: dict, chips: int) -> None:
    """The one device assertion: the chip owner's own report."""
    if device.get("platform") != "tpu" or device.get("count") != chips:
        raise RunFailure(f"needs {chips} TPU chip(s); the chip owner "
                         f"reports {device}")


# --------------------------------------------------------------------------
# set-up: warm (the load is the data set's)
# --------------------------------------------------------------------------

def per_cycle_of(mix: dict) -> int:
    """Requests in one cycle: a pass over a sequence, else one request."""
    return len(mix["classes"]) if mix["schedule"] == "sequence" else 1


def apply_acknowledged(sem, state, requests) -> None:
    for req in requests:
        if req.cls["kind"] == "write" and req.ok:
            sem.apply(req.cls["reference"], state, req.params)


def warm_up(mix: dict, plan, transport, state, sem) -> list:
    """Every shape the window will use, once, on the first connection.
    The reference's state follows the writes."""
    done = []
    for step in mix["warmup"]["first"]:
        for _ in range(int(step["times"])):
            done.append(transport.run(plan.request(step["class"])))
    for _ in range(int(mix["warmup"].get("then_cycles", 0))
                   * per_cycle_of(mix)):
        done.append(transport.run(next(plan)))
    for req in done:
        if not req.ok:
            raise RunFailure(f"warm-up request {req.name} failed: "
                             f"{req.error}")
    apply_acknowledged(sem, state, done)
    return done


# --------------------------------------------------------------------------
# the window
# --------------------------------------------------------------------------

def run_window(mix: dict, plans, transports, seconds: float, owner,
               trace_dir: str | None):
    """Closed loops for `seconds`; with a trace directory, the owner's
    profiler covers a slice at the start. Returns (t0, requests by
    client, trace info).

    The trace info holds the owner's counters at the slice's two edges
    (``stats_before``, ``stats_after``), for the readers that count the
    traced work by the program's own counters. The first is taken before
    the profiler starts, when no client runs yet, so it adds no idle
    time to the slice; the second once the slice's last cycle has
    returned. In the one-client mixes whose rooflines read counters
    (``analytics_fresh``, ``graphalytics_fresh``) the next request is
    the next cycle's burst write, which runs no analytics program, so
    no counted program straddles an edge."""
    per_cycle = per_cycle_of(mix)
    outs = [[] for _ in plans]
    trace = None
    if trace_dir is not None:
        stats_before = owner.stats()
        started = owner.ask("trace_start", dir=trace_dir)
        trace = {"started_ns": started["started_ns"],
                 "stats_before": stats_before}
    t0 = time.perf_counter()
    deadline = t0 + seconds
    threads = [threading.Thread(
        target=traffic_mod.closed_loop,
        args=(transport, plan, deadline, out, per_cycle), daemon=True)
        for transport, plan, out in zip(transports, plans, outs)]
    for t in threads:
        t.start()
    if trace is not None:
        want = mix["trace_slice"]
        if "cycles" in want:
            need = int(want["cycles"]) * per_cycle
            while len(outs[0]) < need and time.perf_counter() < deadline \
                    and threads[0].is_alive():
                time.sleep(0.005)
            trace["cycles"] = min(len(outs[0]), need) // per_cycle
        else:
            time.sleep(min(float(want["seconds"]), seconds))
        trace["requests_in_slice"] = sum(len(o) for o in outs)
        trace["stats_after"] = owner.stats()
        stopped = owner.ask("trace_stop", timeout_s=TRACE_STOP_TIMEOUT_S)
        trace["window_s"] = (stopped["stopped_ns"]
                             - trace["started_ns"]) / 1e9
    for t in threads:
        t.join()
    return t0, outs, trace


# --------------------------------------------------------------------------
# after the window: read back, then compare with the reference
# --------------------------------------------------------------------------

def mode_of(sem, cls: dict) -> str:
    """How a class's responses are held to the reference: its semantics'
    word for the name the class gives as its `reference`."""
    try:
        return sem.MODES[cls["reference"]]
    except KeyError:
        raise RunFailure(f"class {cls['name']!r}: the semantics have no "
                         f"{cls['reference']!r}; they have "
                         f"{sorted(sem.MODES)}") from None


def read_back(mix: dict, client, plan, final_state, sem) -> dict:
    """Quiesced, after the window: the rows the comparison will hold
    against the reference's final state. Only collected here; the
    reference runs once the owner is gone."""
    got = {"readback": {}, "quiesced": []}
    for item in mix.get("readback", []):
        params = sem.readback_params(item["params"], final_state) \
            if item.get("params") else {}
        _, rows, _ = client.execute(item["query"], params)
        got["readback"][item["name"]] = rows
    per_class = int(mix.get("quiesced_reads", {}).get("per_class", 0))
    for cls in mix["classes"]:
        if mode_of(sem, cls) != "between":
            continue
        for _ in range(per_class if cls["params"] else min(per_class, 1)):
            req = plan.request(cls["name"])
            _, req.rows, _ = client.execute(cls["query"], req.params)
            got["quiesced"].append(req)
    return got


def reference_readback(name: str, state, sem=None) -> list:
    """The rows a read-back item should give on `state`, by the given
    semantics or the default ones."""
    if sem is None:
        sem = seams.load_module(None, "semantics",
                                seams.DEFAULTS["semantics"])
    return sem.readback(name, state)


def compare_ranks(rows, want: np.ndarray, top: int) -> dict:
    """One CALL's rows against the reference's vector for that state:
    `rel_err`, the widest |rank - reference| / reference over the
    returned ids, and `gap`, the widest share by which a returned id's
    reference rank lies below the reference's top-th best (0 where the
    ids are the reference's own top, whatever their order among ties)."""
    ids = [r[0] for r in rows]
    got = np.asarray([r[1] for r in rows], dtype=np.float64)
    fault = (len(rows) != top or len(set(ids)) != top
             or not bool(np.isfinite(got).all())
             or bool((np.diff(got) > 0).any())
             or any(not isinstance(i, int) or not 0 <= i < len(want)
                    for i in ids))
    if fault:
        return {"fault": 1, "rel_err": float("inf"), "gap": float("inf")}
    ref = want[np.asarray(ids)]
    cut = np.sort(want)[-top]
    return {"fault": 0,
            "rel_err": float((np.abs(got - ref) / ref).max()),
            "gap": float(max(0.0, (cut - ref.min()) / cut))}


def modes_of(mix: dict, sem) -> dict:
    """{class name: mode}. The modes that hold a read to the state as
    of that request need a mix of one client, or one that never writes."""
    modes = {c["name"]: mode_of(sem, c) for c in mix["classes"]}
    ordered = sorted(n for n, m in modes.items()
                     if m in ("exact_in_order", "vector_top"))
    if ordered and int(mix["clients"]) != 1 and "write" in modes.values():
        raise RunFailure(f"classes {ordered} are held to the state as of "
                         f"each request, which under writes only one "
                         f"client's order gives; the mix has "
                         f"{mix['clients']} clients")
    return modes


def compare(mix: dict, state0, final, window: list, collected: dict,
            sem) -> dict:
    """The numbers that decide `correct`, each to be held to its limit.

    `window` is every request of the window, by client; `state0` and
    `final` are the reference's state before and after it; `sem` gives
    each class's answer and the mode it is held by. With one client
    the order is known and every read has one right answer
    (`exact_in_order`, `vector_top`); with more, a read of the window is
    held between the window's first and last state, and exactness is for
    the quiesced reads after it (`between`); where no class writes,
    every client's reads are held to the one state there is."""
    numbers: dict = {}
    modes = modes_of(mix, sem)

    if {"exact_in_order", "vector_top"} & set(modes.values()):
        # the one client's requests in order (every client's, where
        # nothing writes), the state following every acknowledged
        # write. A vector read is held against the
        # reference for the state as of that read, and against the one
        # for the state before the writes since the last vector read: a
        # read at least as near to that one has not seen its write
        worst = {"fault": 0, "rel_err": 0.0, "gap": 0.0}
        compared = stale = exact_compared = exact_wrong = 0
        stale_sep = float("inf")
        state, version = state0.copy(), 0
        before = None           # (state, version) ahead of those writes
        newest: dict = {}       # vector key -> (version, vector)

        def vector_at(key, req, at_state, at_version):
            held = newest.get(key)
            if held is None or held[0] != at_version:
                held = (at_version, sem.vector(
                    req.cls["reference"], at_state, req.params,
                    x0=None if held is None else held[1]))
                newest[key] = held
            return held[1]

        for req in (r for out in window for r in out):
            mode = modes[req.name]
            if mode == "write":
                if req.ok:
                    if before is None:
                        before = (state.copy(), version)
                    apply_acknowledged(sem, state, [req])
                    version += 1
                continue
            if not req.ok:
                continue
            if mode == "exact_in_order":
                exact_compared += 1
                exact_wrong += req.rows != sem.answer(
                    req.cls["reference"], state, req.params)
            elif mode == "vector_top":
                key = (req.cls["reference"],
                       json.dumps(req.params, sort_keys=True))
                without = None if before is None \
                    else vector_at(key, req, *before)
                rank = vector_at(key, req, state, version)
                top = int(req.cls["top"])
                one = compare_ranks(req.rows, rank, top)
                worst = {k: max(worst[k], one[k]) if k != "fault"
                         else worst[k] + one[k] for k in worst}
                compared += 1
                if without is not None and not one["fault"]:
                    old = compare_ranks(req.rows, without, top)
                    sep = max(old["rel_err"], old["gap"])
                    stale += sep <= max(one["rel_err"], one["gap"])
                    stale_sep = min(stale_sep, sep)
                before = None
        if compared:
            # one number: right values for the ids returned, and the
            # right ids; the parts are shown beside it
            numbers["rank_dev_max"] = max(worst["rel_err"], worst["gap"])
            numbers["_rank_rel_err_max"] = worst["rel_err"]
            numbers["_top_gap_max"] = worst["gap"]
            numbers["row_faults"] = worst["fault"]
            numbers["stale_calls"] = stale
            numbers["_stale_sep_min"] = stale_sep
            numbers["_rank_calls_compared"] = compared
        if exact_compared:
            numbers["exact_mismatches"] = exact_wrong
            numbers["_exact_reads_compared"] = exact_compared

    if "between" in modes.values():
        outside = 0
        bounds: dict = {}
        for out in window:
            for req in out:
                if modes[req.name] != "between" or not req.ok:
                    continue
                key = (req.name, tuple(sorted(req.params.items())))
                if key not in bounds:
                    bounds[key] = sem.bounds(
                        req.cls["reference"], req.params, state0, final)
                outside += not sem.within(req.rows, *bounds[key])
        numbers["reads_out_of_bounds"] = outside
        numbers["quiesced_mismatches"] = sum(
            req.rows != sem.answer(req.cls["reference"], final, req.params)
            for req in collected["quiesced"])

    mismatches = 0
    for item in mix.get("readback", []):
        want = sem.readback(item["reference"], final)
        got = [list(r) for r in collected["readback"][item["name"]]]
        if got != want:
            as_set = {tuple(r) for r in got}
            mismatches += len(as_set ^ {tuple(r) for r in want}) or 1
    numbers["readback_mismatches"] = mismatches
    return numbers


def judge(numbers: dict, mix: dict, limits: dict):
    """[(name, value, limit, ok)], and whether all held. A number the
    mix names with no limit in the mix or the cell's file fails: a
    comparison without a limit decides nothing."""
    rows = []
    for name, spec in mix["compare"].items():
        if name not in numbers:
            continue
        limit = limits.get(name, spec.get("limit"))
        value = numbers[name]
        ok = limit is not None and value <= limit
        rows.append((name, value, limit, bool(ok)))
    return rows, all(r[3] for r in rows) and bool(rows)


# --------------------------------------------------------------------------
# metrics
# --------------------------------------------------------------------------

def end_to_end(name: str, ctx: dict):
    reqs, t0, seconds = ctx["requests"], ctx["t0"], ctx["seconds"]
    if name == "setup_s":
        return ctx["setup_s"]
    if name == "ingest_records_per_s":
        return ctx["records"] / ctx["load_s"]
    if name == "fresh_cycle_s":
        per_cycle = ctx["per_cycle"]
        done = ctx["cycles"]
        if not done:
            return None
        return (reqs[done * per_cycle - 1].end - t0) / done
    if name == "oltp_queries_per_s":
        return sum(r.ok and r.end <= t0 + seconds for r in reqs) / seconds
    if name == "oltp_query_p95_ms":
        # a failed request missed every limit: it sits beyond the tail
        times = sorted((r.end - r.start) if r.ok else float("inf")
                       for r in reqs)
        if len(times) < 20:
            return None
        value = times[min(len(times) - 1, int(0.95 * len(times)))]
        return 1000.0 * value if value != float("inf") else None
    raise ValueError(f"no end-to-end metric named {name!r}")


def completed_cycles(reqs: list, per_cycle: int) -> int:
    """Whole cycles, every request of which succeeded, from the start."""
    done = 0
    while (done + 1) * per_cycle <= len(reqs) and all(
            r.ok for r in reqs[done * per_cycle:(done + 1) * per_cycle]):
        done += 1
    return done


def reduce_trace(trace_dir: str, workdir: str) -> dict | None:
    """gap_spans.py in a process of its own, held to the CPU, after the
    chip's holder has exited: trace_reduce.py's summary of the device
    planes, and under "gaps" the host spans beneath their idle gaps."""
    out_path = os.path.join(workdir, "trace_summary.json")
    gaps_path = os.path.join(workdir, "trace_gaps.json")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "gap_spans.py"), trace_dir,
         gaps_path, out_path], env=env, cwd=REPO, capture_output=True,
        text=True, timeout=200)
    if proc.returncode != 0:
        say(f"gap_spans failed: {proc.stderr[-2000:]}")
        return None
    return dict(_load_json(out_path), gaps=_load_json(gaps_path)["gaps"])


def breakdown_of(trace: dict) -> dict:
    """The ten device ops that took most time, and the ten longest
    stretches of the idle gaps by the program's span over them (a gap
    is split among the innermost spans; `unattributed` is what no span
    covers)."""
    ops = sorted(trace["ops"].items(), key=lambda kv: -kv[1]["seconds"])
    parts = [[name, seconds] for gap in trace["gaps"]
             for name, seconds in gap["parts"]]
    parts.sort(key=lambda part: -part[1])
    return {"device_ops": [[name, row["seconds"]] for name, row in ops[:10]],
            "idle_gaps": parts[:10]}


# --------------------------------------------------------------------------
# one run
# --------------------------------------------------------------------------

def run_cell(cell: dict, seed: int, seconds: float, trace: bool,
             workdir: str, control: str | None = None,
             device_check=require_tpu, transport_hook=None, t_start: float = T_PROCESS_START) -> dict:
    """Returns the result object. Raises RunFailure where there is none.

    `device_check` and `transport_hook` are for the tests: the first
    stands in for the device assertion on a host with no chip, the
    second plants a fault under the timed path. `setup_s` runs from
    `t_start`, which is the start of this process."""
    from memgraph_tpu.server.client import BoltClientError
    config, mix = cell["config"], cell["mix"]
    layout, dataset, sem = seams_of(cell)
    modes_of(mix, sem)          # a mix its semantics cannot hold: now
    say(f"cell {cell['name']}: {config['name']} under {mix['name']}, "
        f"seed {seed}, {seconds:g} s, trace {int(trace)}"
        + (f", CONTROL {control}" if control else ""))

    extra_env, drop_every = {}, 0
    if control:
        controls = dict(config.get("precision", {}).get("controls", {}))
        controls.update(mix.get("controls", {}))
        if control not in controls:
            raise RunFailure(f"no control {control!r}; the cell has "
                             f"{sorted(controls)}")
        spec = controls[control]
        extra_env = spec.get("owner_env", {})
        if spec.get("harness") == "drop_every_nth_write":
            drop_every = int(spec["n"])

    # the data set is one, as Pokec is one file: every seed shares it
    # (and so the shapes of the programs compiled for it), and draws
    # its own traffic
    state0 = dataset.make(config)
    say(f"data set: {dataset.sizes(state0)}")
    n_ids = dataset.key_space(config)
    keys = traffic_mod.Keys(mix["keys"], n_ids, seed) \
        if "keys" in mix else None
    plans = [traffic_mod.Plan(mix, n_ids, seed, i, keys, dataset)
             for i in range(int(mix["clients"]))]

    owner = layout.start(config, cell["chips"], workdir, extra_env)
    clients = []
    try:
        clients.append(connect(owner.port(0), owner.alive))
        device = owner.device(clients[0])
        device_check(device, cell["chips"])

        load_s, records = dataset.load(clients[0], config, state0)
        say(f"loaded over Bolt in {load_s:.3f} s: "
            f"{records / load_s:,.0f} records/s")
        for i in range(1, len(plans)):
            clients.append(connect(owner.port(i), owner.alive))
        transports = [traffic_mod.Transport(
            c, int(mix.get("retries", 0)), BoltClientError, drop_every)
            for c in clients]
        if transport_hook is not None:
            transports = [transport_hook(t) for t in transports]
        t_warm = time.perf_counter()
        warm = warm_up(mix, plans[0], transports[0], state0, sem)
        for t in transports[1:]:
            t.client.execute("RETURN 1")
        say(f"warmed {len(warm)} requests in "
            f"{time.perf_counter() - t_warm:.3f} s: "
            + ", ".join(f"{r.name} {r.end - r.start:.3f}" for r in warm[:12]))

        stats_before = owner.stats()
        trace_dir = os.path.join(workdir, "trace") if trace else None
        setup_s = time.perf_counter() - t_start
        t0, outs, trace_info = run_window(mix, plans, transports, seconds,
                                          owner, trace_dir)
        t_end = time.perf_counter()
        stats_after = owner.stats()
        memory = owner.ask("memory")
        # quiesced: the window's clients have all returned
        final_state = state0.copy()
        for out in outs:
            apply_acknowledged(sem, final_state, out)
        check_plan = traffic_mod.Plan(mix, n_ids, seed, len(plans), keys,
                                      dataset)
        collected = read_back(mix, clients[0], check_plan, final_state, sem)
        t_readback = time.perf_counter()
    except RunFailure:
        raise
    except Exception as e:
        raise RunFailure(
            f"{type(e).__name__}: {e}\n--- owner log ---\n"
            f"{owner.log_tail()}") from e
    finally:
        for c in clients:
            try:
                c.close()
            except OSError:
                pass
        say(f"owner exited with code "
            f"{', '.join(str(rc) for rc in owner.stop())}")

    # the window has closed, the peak is read, the program is gone:
    # now the reference
    per_cycle = per_cycle_of(mix)
    # a loop begins a cycle only before the deadline, and finishes it
    reqs = sorted((r for out in outs for r in out), key=lambda r: r.start)
    cycles = completed_cycles(outs[0], per_cycle) \
        if mix["schedule"] == "sequence" else sum(r.ok for r in reqs)
    t_ref = time.perf_counter()
    numbers = compare(mix, state0, final_state, outs, collected, sem)
    rows, correct = judge(numbers, mix, cell["limits"])
    ref_s = time.perf_counter() - t_ref

    trace_summary = None
    if trace:
        trace_summary = reduce_trace(trace_dir, workdir)

    peaks = _load_json(HERE, "peaks.json")
    if device["platform"] == "tpu" and device["kind"] not in peaks:
        raise RunFailure(f"peaks.json has no device kind "
                         f"{device['kind']!r}")
    ctx = {
        "requests": reqs, "t0": t0, "seconds": seconds, "setup_s": setup_s,
        "load_s": load_s, "records": records, "cycles": cycles,
        "per_cycle": per_cycle, "dirs": cell.get("dirs"),
        **dataset.sizes(final_state),
        "stats_before": stats_before, "stats_after": stats_after,
        "trace": trace_summary, "peak": peaks.get(device["kind"]),
        "trace_window_s": (trace_info or {}).get("window_s"),
        "traced_cycles": (trace_info or {}).get("cycles"),
        "trace_stats_before": (trace_info or {}).get("stats_before"),
        "trace_stats_after": (trace_info or {}).get("stats_after"),
    }
    by_class: dict = {}
    for r in reqs:
        by_class.setdefault(r.name, []).append(r.end - r.start)
    say(f"window {seconds:g} s (+{t_end - t0 - seconds:.3f} s to finish "
        f"what had begun): {len(reqs)} requests, {cycles} cycles, "
        f"{sum(r.tries > 1 for r in reqs)} retried, "
        f"{sum(not r.ok for r in reqs)} failed; read-back "
        f"{t_readback - t_end:.3f} s; reference {ref_s:.3f} s")
    for r in [r for r in reqs if not r.ok][:5]:
        say(f"  failed {r.name} (client {r.client}, {r.tries} tries): "
            f"{r.error}")
    for name, times in sorted(by_class.items()):
        say(f"  {name:<18} n {len(times):>6}  p50 "
            f"{1000 * statistics.median(times):9.3f} ms  max "
            f"{1000 * max(times):9.3f} ms")
    if mix["schedule"] == "sequence":
        ends = [r.end for r in outs[0][per_cycle - 1::per_cycle]]
        say("  cycle seconds: " + " ".join(
            f"{b - a:.3f}" for a, b in zip([t0] + ends, ends)))
    moved = {k: stats_after[k] - stats_before.get(k, 0.0)
             for k in stats_after if stats_after[k] != stats_before.get(k, 0.0)}
    say(f"  /stats counters that moved in the window: {moved}")

    metrics_out: dict = {}
    for metric in cell["per_layer"] if trace else cell["end_to_end"]:
        value = layers.read(metric, ctx) if trace \
            else end_to_end(metric["name"], ctx)
        if value is not None:       # nothing to read: left out of the line
            metrics_out[metric["name"]] = {"value": value,
                                           "unit": metric["unit"]}

    device_out = dict(device,
                      memory_peak_bytes=memory["memory_peak_bytes"])
    result = {"correct": correct, "attempted": len(reqs),
              "failed": sum(not r.ok for r in reqs),
              "metrics": metrics_out, "device": device_out}
    if trace and trace_summary is not None:
        device_out["busy_s"] = trace_summary["busy_s"]
        device_out["window_s"] = ctx["trace_window_s"]
        result["breakdown"] = breakdown_of(trace_summary)
    result["cycles"] = cycles
    result["control"] = control
    result["compared"] = {
        name: {"value": min(value, 1e300), "limit": limit, "ok": ok}
        for name, value, limit, ok in rows}
    for name, value in numbers.items():     # shown, not judged
        if name.startswith("_"):
            result["compared"][name[1:]] = {"value": min(value, 1e300)}
    return result


# --------------------------------------------------------------------------
# entry
# --------------------------------------------------------------------------

def _arm_watchdog(timeout_s: int) -> None:
    def on_alarm(signum, frame):
        print(f"FAILED: no result within {timeout_s} s",
              file=sys.stderr, flush=True)
        stop_all()
        os._exit(3)
    signal.signal(signal.SIGALRM, on_alarm)
    signal.alarm(timeout_s)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", default=None,
                    help="run the cell's named control instead (never "
                         "part of a measured run)")
    args = ap.parse_args(argv)
    _arm_watchdog(MASTER_TIMEOUT_S)
    workdir = tempfile.mkdtemp(prefix="chipbench_")
    try:
        cell = load_cell(args.workload)
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          workdir, control=args.control)
    except RunFailure as e:
        print(f"FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    finally:
        stop_all()
        shutil.rmtree(workdir, ignore_errors=True)
    say(json.dumps(result))                 # "compared" comes last
    sys.stdout.flush()
    for name, row in result["compared"].items():
        print(f"compared {name}: {row.get('value')!r} limit "
              f"{row.get('limit')!r}"
              + ("" if row.get("ok", True) else "  <-- FAILS"),
              file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
