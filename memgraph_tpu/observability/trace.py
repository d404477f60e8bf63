"""mgtrace: low-overhead, always-compiled-in query tracing.

One Cypher query yields ONE connected trace — session → parse → plan →
execute → storage txn (MVCC begin/commit) → kernel-server dispatch →
device stages → replication acks — across every process boundary the
deployment has: Bolt frames (``extra`` metadata field), the
kernel-server request protocol, ``mp_executor`` job envelopes, and the
replication/raft wire.

Design rules:

* **One boundary, one span, four sinks.** A span is the single
  instrument at a layer boundary. A name marked as a *phase*
  (:data:`PHASES`) does this when it closes, armed or not: (1) adds its
  seconds and 1 to ``span.<name>.seconds_total`` / ``span.<name>.count``
  in ``global_metrics`` (``GET /stats`` section ``device``, and
  ``/metrics``); (2) while a ``jax.profiler`` session is live in this
  process, sits in the xplane as ``mgtrace:<name>`` on the clock of the
  device's ops (one attribute read otherwise); (3) under ``PROFILE`` /
  an active ``StageAccumulator`` feeds the stages :data:`PHASES` maps
  it to; (4) when armed, is recorded as below. No site keeps a
  ``perf_counter`` pair of its own beside a span.

* **Disarmed costs ~nothing.** Tracing is compiled in everywhere but
  armed only via ``MEMGRAPH_TPU_TRACE=1`` (or programmatically,
  ``enable()``). Every public entry point starts with one attribute
  read; disarmed, ``span()`` of a non-phase name returns a shared no-op
  context manager and ``inject()``/``activate()`` return ``None``/
  no-ops. A phase costs two clock reads and one locked add; six close
  on a point read's path (``bolt.run``, ``bolt.wait`` for its RUN and
  its PULL, ``bolt.prepare``, ``bolt.pull``, ``bolt.encode``), seven on
  a write's (``mvcc.commit``). The overhead-guard test
  (tests/test_mgtrace.py) enforces the ≤2% budget on a tier-1
  micro-benchmark over both kinds of site.

* **Spans open only through this module's context-manager API** —
  ``span()`` for synchronous extents, ``record_span()`` for atomic
  after-the-fact records (phases whose start/end straddle generator
  boundaries), ``begin_trace()`` for the one sanctioned long-lived root
  per query (finished in exactly one place by its owner). The raw
  ``_begin_span``/``_end_span`` primitives are private to this file;
  mglint's MG005 span-registry check rejects product code that touches
  them, and requires every literal span name to be declared in
  :data:`SPAN_NAMES`.

* **Head-based sampling, slow/error always kept.** The keep/drop
  decision is taken once, at the trace root, from a deterministic hash
  of the trace id against ``MEMGRAPH_TPU_TRACE_SAMPLE`` — and travels
  in the carrier so every process agrees. Regardless of the sample
  verdict, a trace whose root ran ≥ ``MEMGRAPH_TPU_TRACE_SLOW_MS`` or
  that contains an errored span is retained.

* **Cross-process spans ship home.** A kernel-server dispatch or
  mp_executor worker records its spans locally under the propagated
  trace id, then ``take_trace()`` pops them into the reply envelope and
  the caller ``adopt_spans()``-s them — so the retained trace in the
  querying process is the whole connected picture, not a stub.

* **Python's cyclic collector is a phase too.** One ``gc.callbacks``
  entry a process (installed when this module is first imported:
  the Bolt server and the kernel-server daemon both import it) opens
  ``python.gc`` on every collection, and ``python.gc.full`` over the
  same extent of a generation-2 one, on the thread the collection
  interrupted. See :func:`_on_collect` for what it does differently.

Exports: ``traces_json()`` (the /traces endpoint) and
``chrome_trace()`` — Chrome trace-event JSON loadable in Perfetto /
chrome://tracing.
"""

from __future__ import annotations

import gc
import logging
import os
import sys
import threading
import time

from . import stats as mgstats
from .metrics import global_metrics

log = logging.getLogger(__name__)

ENV_ARM = "MEMGRAPH_TPU_TRACE"
ENV_SAMPLE = "MEMGRAPH_TPU_TRACE_SAMPLE"
ENV_SLOW_MS = "MEMGRAPH_TPU_TRACE_SLOW_MS"
ENV_RING = "MEMGRAPH_TPU_TRACE_RING"

#: Every span name product code may open. mglint MG005 (span-registry)
#: statically enforces that (a) every literal name passed to span()/
#: record_span()/begin_trace() in memgraph_tpu/ appears here, and
#: (b) every name here has at least one live open site — a dead
#: registration means dashboards "cover" a span that can never fire.
SPAN_NAMES = (
    "bolt.run",            # RUN received -> last PULL answered (session root)
    "bolt.wait",           # message decoded -> executor thread starts on it
    "bolt.prepare",        # RUN on its worker thread: parse, plan, prepare
    "bolt.pull",           # PULL/DISCARD on its worker thread: execute,
    #                        the rows, the operators above them, commit
    "bolt.encode",         # the records' PackStream and SUCCESS, on the loop
    "python.gc",           # one pass of Python's cyclic collector, any
    #                        generation, on the thread it interrupted
    "python.gc.full",      # the same extent, of a generation-2 collection
    "query",               # interpreter root: prepare -> summary
    "query.parse",         # text -> AST (cache-aware)
    "query.plan",          # AST -> operator tree (cache-aware)
    "query.execute",       # stream drain: first pull -> exhaustion
    "query.commit",        # autocommit finalization (interpreter side)
    "query.sort",          # ORDER BY's sort: of every row (OrderBy), or
    #                        of the rows its bounded selection held (TopK)
    "mvcc.begin",          # storage transaction begin
    "mvcc.commit",         # storage engine commit of a writing txn
    #                        (durability: WAL append/fsync, + repl)
    "mvcc.release",        # end of a read-only txn: nothing to make durable
    "storage.gc",          # one collect_garbage tick
    "storage.gc.sweep",    # its O(V+E) delta-chain sweep (not the thaw)
    "analytics.export",    # MVCC -> CSR/COO device snapshot (GraphCache)
    "analytics.edge_diff",   # multiset edge diff against the base snapshot
    "analytics.plan_build",  # MXU plan: delta side-net or full build
    "analytics.launch",    # kernel closure + jitted call until it returns
    "analytics.device_wait",  # readback of rank/err/iters: ends in a block
    "analytics.rows",      # summed time in the procedure's row generator
    "analytics.consume",   # summed time of the operators above it, per row
    "vector.index",        # the embedding index for a reader's snapshot
    #                        (kind: hit, alias, delta, full)
    "vector.refresh",      # its change-log refresh: O(changed) reads, scatter
    "vector.build",        # its full build: every vertex's vector read
    "vector.search",       # query upload, knn dispatch, readback of the top k
    "graphrag.expand",     # hybrid retrieval: k-hop frontier and its readback
    "graphrag.ppr",        # its personalized-PageRank fixpoint and readback
    "graphrag.rows",       # its ranking of the masked scores into vertices
    "lane.query",          # one compiled-lane attempt, refusals included
    "lane.snapshot",       # columnar snapshot fetch (rebuilt after a write)
    "lane.stage",          # argsort/endpoints/masks + edge upload
    "lane.compile",        # one lane program build
    "lane.dispatch",       # padding + program lookup
    "lane.iterate",        # program call + readback: ends in a block
    "kernel.request",      # client->kernel-server round trip
    "kernel.dispatch",     # server-side supervised dispatch
    "kernel.generation",   # its resident generation: delta decode, splice
    "ppr.queue",           # PPR plane: a rider enqueued -> its batch starts
    "ppr.batch",           # one batch: pack, upload, fixpoint, top-k, readback
    "ppr.reply",           # its cache fill and the riders' reply arrays
    "analytics.route_meta",  # change log -> what a routed CALL sends
    "device.transfer",     # partition-centric blocking + device_put
    "device.chunk",        # one compiled chunk of device iterations
    "device.route",        # one mesh/streamed dispatch (chunks inside)
    "mp.execute",          # parent->mp-worker round trip
    "mp.worker",           # worker-side prepare+pull
    "shard.request",       # router->shard-owner round trip (r18)
    "shard.worker",        # shard-worker-side statement execution
    "repl.ship",           # one WAL frame ship + ack, per replica
    "repl.apply",          # replica-side system-txn application
    "raft.rpc",            # outbound raft RPC (request + response)
    "raft.handle",         # inbound raft RPC application
)

#: The phase mark. A name listed here is accounted on every close,
#: armed or not (module docstring); every other name keeps the disarmed
#: no-op. The value names the mgstat stages (``stats.STAGE_NAMES`` plus
#: the ``semiring_<backend>`` family) the phase feeds while a
#: ``StageAccumulator`` collects on its thread. ``{backend}`` is filled
#: from the attribute the site opens the span with; a site that states
#: none feeds no stage (its child does — the segment route's launch —
#: or its runner records for itself — the checkpoint's chunks). A
#: leading ``+`` adds seconds without a count: the phase continues an
#: extent another phase began (launch + wait are ONE ``device_iterate``
#: that ends in a block). mglint MG005 requires every key to be a
#: declared span name.
PHASES = {
    "bolt.run": (),
    "bolt.wait": (),
    "bolt.prepare": (),
    "bolt.pull": (),
    "bolt.encode": (),
    "python.gc": (),
    "python.gc.full": (),
    "mvcc.commit": (),
    "query.sort": (),
    "storage.gc": (),
    "storage.gc.sweep": (),
    "analytics.export": (),
    "analytics.edge_diff": (),
    "analytics.plan_build": (),
    "analytics.launch": ("device_iterate", "semiring_{backend}"),
    "analytics.device_wait": ("+device_iterate", "+semiring_{backend}"),
    "analytics.rows": (),
    "analytics.consume": (),
    "vector.index": (),
    "vector.refresh": (),
    "vector.build": (),
    "vector.search": (),
    "graphrag.expand": (),
    "graphrag.ppr": (),
    "graphrag.rows": (),
    "lane.query": (),
    "lane.snapshot": (),
    "lane.stage": (),
    "lane.compile": ("lane_compile",),
    "lane.dispatch": ("lane_dispatch",),
    "lane.iterate": ("lane_iterate",),
    "kernel.request": (),
    "kernel.dispatch": (),
    "kernel.generation": (),
    "ppr.queue": (),
    "ppr.batch": (),
    "ppr.reply": (),
    "analytics.route_meta": (),
    "device.transfer": ("device_transfer",),
    "device.chunk": ("device_iterate", "semiring_{backend}"),
    "device.route": ("semiring_{backend}",),
}

#: name -> (seconds counter, count counter, ((stage template, counted),))
_PHASE_KEYS = {
    name: (f"span.{name}.seconds_total", f"span.{name}.count",
           tuple((t.lstrip("+"), not t.startswith("+")) for t in stages))
    for name, stages in PHASES.items()}

def _env_flag(name: str) -> bool:
    return os.environ.get(name, "") not in ("", "0")


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, "") or default)
    except ValueError:
        return default


def _new_id(nbytes: int = 8) -> str:
    return os.urandom(nbytes).hex()


def _sample_decision(trace_id: str, rate: float) -> bool:
    """Deterministic head-sampling verdict from the trace id: every
    process that sees the id would agree even without the carrier."""
    if rate >= 1.0:
        return True
    if rate <= 0.0:
        return False
    return int(trace_id[:8], 16) / 0xFFFFFFFF < rate


class TraceContext:
    """The propagated identity: (trace_id, span_id, sampled)."""

    __slots__ = ("trace_id", "span_id", "sampled")

    def __init__(self, trace_id: str, span_id: str, sampled: bool) -> None:
        self.trace_id = trace_id
        self.span_id = span_id
        self.sampled = sampled

    def carrier(self) -> dict:
        return {"trace_id": self.trace_id, "span_id": self.span_id,
                "sampled": self.sampled}


class _NoopSpan:
    """Shared disarmed-path context manager: one allocation per process."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        return False

    def __bool__(self):
        return False

    def set(self, **attrs) -> None:
        pass


_NOOP = _NoopSpan()

#: jax's profiler state, bound on first sight of the imported module: a
#: process that never imported jax can have no session to sit in
_profile_state = None


def _profiler_live() -> bool:
    """True while a ``jax.profiler`` session is live in this process."""
    global _profile_state
    state = _profile_state
    if state is None:
        mod = sys.modules.get("jax._src.profiler")
        state = getattr(mod, "_profile_state", None)
        if state is None:
            return False
        _profile_state = state
    return state.profile_session is not None


def _enter_annotation(name: str):
    """``mgtrace:<name>`` in the live profiler session's host plane."""
    try:
        from jax.profiler import TraceAnnotation
        ann = TraceAnnotation("mgtrace:" + name)
        ann.__enter__()
        return ann
    except Exception as e:  # noqa: BLE001 — profiling never breaks serving
        log.debug("profiler trace-annotation unavailable: %s", e)
        return None


def _exit_annotation(ann) -> None:
    try:
        ann.__exit__(None, None, None)
    except Exception as e:  # noqa: BLE001 — profiling never breaks serving
        log.debug("profiler trace-annotation exit failed: %s", e)


def _account(keys: tuple, seconds: float, attrs) -> None:
    """A closed phase's always-on sinks: the two counters, and the
    stages it maps to where an accumulator collects on this thread."""
    global_metrics.add_seconds(keys[0], keys[1], seconds)
    stages = keys[2]
    if stages and mgstats.stages_active():
        try:
            named = [(t.format_map(attrs), counted) for t, counted in stages]
        except KeyError:        # the site states no backend: no stage
            return
        for stage, counted in named:
            mgstats.record_stage(stage, seconds, 1 if counted else 0)


class _PhaseSpan:
    """A phase span while disarmed: accounted, in no trace. Falsy, like
    the no-op, so ``if sp:`` still guards attr computation."""

    __slots__ = ("_keys", "_name", "_attrs", "_t0", "_ann", "seconds")

    def __init__(self, name: str, keys: tuple, attrs: dict) -> None:
        self._name = name
        self._keys = keys
        self._attrs = attrs
        self.seconds = 0.0      # the closed extent, for the site's use

    def __bool__(self):
        return False

    def set(self, **attrs) -> None:
        pass

    def __enter__(self):
        self._ann = _enter_annotation(self._name) \
            if _profiler_live() else None
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        self.seconds = seconds = time.perf_counter() - self._t0
        if self._ann is not None:
            _exit_annotation(self._ann)
        _account(self._keys, seconds, self._attrs)
        return False


class _NullActivation:
    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, exc_type, exc, tb):
        return False


_NULL_ACTIVATION = _NullActivation()


def _clean_attrs(attrs: dict) -> dict:
    """Attrs must survive JSON serialization across process boundaries."""
    out = {}
    for k, v in attrs.items():
        if v is None or isinstance(v, (str, int, float, bool)):
            out[k] = v
        else:
            out[k] = str(v)
    return out


class _LiveSpan:
    """An open span; created only while armed, via span()."""

    __slots__ = ("_tracer", "name", "trace_id", "span_id", "parent_id",
                 "_t0_wall", "_t0_perf", "attrs", "status", "error",
                 "_prev_ctx", "_ann", "seconds")

    def __init__(self, tracer: "Tracer", name: str, ctx_parent, attrs):
        self._tracer = tracer
        self.name = name
        if ctx_parent is not None:
            self.trace_id = ctx_parent.trace_id
            self.parent_id = ctx_parent.span_id
            sampled = ctx_parent.sampled
        else:
            self.trace_id = _new_id(16)
            self.parent_id = None
            sampled = _sample_decision(self.trace_id, tracer.sample_rate)
        self.span_id = _new_id()
        self.attrs = _clean_attrs(attrs) if attrs else {}
        self.status = "ok"
        self.error = None
        self._prev_ctx = None
        self._ann = None
        self.seconds = 0.0
        self._t0_wall = time.time()
        self._t0_perf = time.perf_counter()
        # children opened inside this extent hang off this span
        self._prev_ctx = tracer._swap_current(
            TraceContext(self.trace_id, self.span_id, sampled))
        if _profiler_live():
            self._ann = _enter_annotation(name)

    def __bool__(self):
        return True

    def set(self, **attrs) -> None:
        self.attrs.update(_clean_attrs(attrs))

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        if exc_type is not None:
            # a typed decline (the lane's LaneRefused names its own
            # ``span_status``) is control flow: recorded, not an error
            # that force-keeps the trace
            self.status = getattr(exc_type, "span_status", "error")
            self.error = f"{exc_type.__name__}: {exc}"
        t = self._tracer
        self.seconds = dur = time.perf_counter() - self._t0_perf
        if self._ann is not None:
            _exit_annotation(self._ann)
        keys = _PHASE_KEYS.get(self.name)
        if keys is not None:
            _account(keys, dur, self.attrs)
        t._swap_current(self._prev_ctx)
        t._record(self.trace_id, {
            "trace_id": self.trace_id, "span_id": self.span_id,
            "parent_id": self.parent_id, "name": self.name,
            "ts": self._t0_wall, "dur_s": dur, "status": self.status,
            "error": self.error, "attrs": self.attrs,
            "pid": os.getpid(), "tid": threading.get_ident()})
        return False


class _Activation:
    __slots__ = ("_tracer", "_ctx", "_prev")

    def __init__(self, tracer: "Tracer", ctx: TraceContext) -> None:
        self._tracer = tracer
        self._ctx = ctx
        self._prev = None

    def __enter__(self):
        self._prev = self._tracer._swap_current(self._ctx)
        return self._ctx

    def __exit__(self, exc_type, exc, tb):
        self._tracer._swap_current(self._prev)
        return False


class _Adoption(_Activation):
    """Activation of a REMOTE parent context; with retain=True the trace
    is finalized locally on scope exit (for one-way hops whose spans
    cannot ship back — raft/replication appliers)."""

    __slots__ = ("_retain",)

    def __init__(self, tracer, ctx, retain: bool) -> None:
        super().__init__(tracer, ctx)
        self._retain = retain

    def __exit__(self, exc_type, exc, tb):
        super().__exit__(exc_type, exc, tb)
        if self._retain:
            self._tracer._finalize(self._ctx.trace_id, self._ctx.sampled,
                                   root_dur_s=None)
        return False


class TraceHandle:
    """The one sanctioned long-lived root span (a query's lifetime spans
    multiple protocol messages, so its root cannot be a ``with`` block).
    Mint with begin_trace(); the owner calls finish() exactly once.
    Disarmed, a phase name's handle has no ``ctx``: finish() accounts
    its seconds and records nothing."""

    __slots__ = ("_tracer", "name", "ctx", "parent_id", "t0_wall",
                 "t0_perf", "_done", "_owns_finalize")

    def __init__(self, tracer: "Tracer", name: str,
                 ctx: TraceContext | None, parent_id: str | None,
                 owns_finalize: bool) -> None:
        self._tracer = tracer
        self.name = name
        self.ctx = ctx
        self.parent_id = parent_id
        self.t0_wall = time.time()
        self.t0_perf = time.perf_counter()
        self._done = False
        # finalization ownership: only the OUTERMOST local handle (a
        # true root, or the process-edge adopter of an external
        # client's carrier) moves the trace to the retained ring — an
        # inner handle (the interpreter's "query" under a Bolt session,
        # or inside an mp/kernel worker whose spans ship home via
        # take_trace) must leave the buffer alone
        self._owns_finalize = owns_finalize

    @property
    def trace_id(self) -> str | None:
        return self.ctx.trace_id if self.ctx is not None else None

    def finish(self, status: str = "ok", error: str | None = None,
               force_keep: bool = False, **attrs) -> None:
        if self._done:
            return
        self._done = True
        dur = time.perf_counter() - self.t0_perf
        keys = _PHASE_KEYS.get(self.name)
        if keys is not None:
            _account(keys, dur, attrs)
        if self.ctx is None:
            return
        t = self._tracer
        t._record(self.ctx.trace_id, {
            "trace_id": self.ctx.trace_id, "span_id": self.ctx.span_id,
            "parent_id": self.parent_id, "name": self.name,
            "ts": self.t0_wall, "dur_s": dur, "status": status,
            "error": error, "attrs": _clean_attrs(attrs),
            "pid": os.getpid(), "tid": threading.get_ident()})
        if self._owns_finalize:
            t._finalize(self.ctx.trace_id, self.ctx.sampled,
                        root_dur_s=dur, force=force_keep)
        elif force_keep:
            # not the retention owner (e.g. the interpreter under a Bolt
            # session root): sticky-mark the trace so the owner keeps it
            t.force_keep(self.ctx.trace_id)


class Tracer:
    """Process-wide tracer: current-context registry + span buffers."""

    #: open (unfinalized) traces the buffer tolerates before evicting
    #: the oldest — orphans (a deadline-exceeded dispatch whose spans
    #: were never taken) must not leak unboundedly
    MAX_ACTIVE = 512

    def __init__(self) -> None:
        self._armed = _env_flag(ENV_ARM)
        self.sample_rate = _env_float(ENV_SAMPLE, 1.0)
        self.slow_ms = _env_float(ENV_SLOW_MS, 250.0)
        self.ring_cap = int(_env_float(ENV_RING, 256))
        self._tls = threading.local()
        self._lock = threading.Lock()
        #: trace_id -> {"spans": [dict], "error": bool}
        self._active: dict[str, dict] = {}
        #: finalized, retained traces (each a list of span dicts)
        self._finished: list[list[dict]] = []
        self._counts = {"started": 0, "kept": 0, "dropped": 0}

    # --- arming ------------------------------------------------------------

    def enable(self, sample: float | None = None,
               slow_ms: float | None = None) -> None:
        if sample is not None:
            self.sample_rate = sample
        if slow_ms is not None:
            self.slow_ms = slow_ms
        self._armed = True

    def disable(self) -> None:
        self._armed = False

    def reset(self) -> None:
        with self._lock:
            self._active.clear()
            self._finished.clear()
            self._counts = {"started": 0, "kept": 0, "dropped": 0}

    # --- current context ----------------------------------------------------

    def _swap_current(self, ctx):
        prev = getattr(self._tls, "ctx", None)
        self._tls.ctx = ctx
        return prev

    def current(self) -> TraceContext | None:
        if not self._armed:
            return None
        return getattr(self._tls, "ctx", None)

    # --- span recording -----------------------------------------------------

    def _record(self, trace_id: str, span: dict) -> None:
        with self._lock:
            entry = self._active.get(trace_id)
            if entry is None:
                entry = {"spans": [], "error": False}
                self._active[trace_id] = entry
                self._counts["started"] += 1
                while len(self._active) > self.MAX_ACTIVE:
                    victim = next(iter(self._active))
                    del self._active[victim]
                    self._counts["dropped"] += 1
            entry["spans"].append(span)
            if span.get("status") == "error":
                entry["error"] = True

    def force_keep(self, trace_id: str) -> None:
        """Sticky keep-mark on a still-open trace (slow-query linkage)."""
        with self._lock:
            entry = self._active.get(trace_id)
            if entry is not None:
                entry["force"] = True

    def _finalize(self, trace_id: str, sampled: bool,
                  root_dur_s: float | None, force: bool = False) -> None:
        with self._lock:
            entry = self._active.pop(trace_id, None)
            if entry is None:
                return
            slow = root_dur_s is not None and \
                root_dur_s * 1000.0 >= self.slow_ms
            if not (force or entry.get("force") or sampled or slow
                    or entry["error"]):
                self._counts["dropped"] += 1
                return
            self._finished.append(entry["spans"])
            self._counts["kept"] += 1
            while len(self._finished) > self.ring_cap:
                self._finished.pop(0)

    def take_trace(self, trace_id: str) -> list[dict]:
        """Pop the spans accumulated for an ADOPTED trace, for shipping
        back to the process that owns the root."""
        with self._lock:
            entry = self._active.pop(trace_id, None)
        return entry["spans"] if entry else []

    def adopt_spans(self, spans) -> None:
        """Merge spans a remote process shipped back into their (still
        open) local trace."""
        if not self._armed or not spans:
            return
        for span in spans:
            tid = span.get("trace_id")
            if tid:
                self._record(tid, dict(span))

    # --- snapshots / exporters ---------------------------------------------

    def finished_traces(self) -> list[list[dict]]:
        with self._lock:
            return [list(spans) for spans in self._finished]

    def counts(self) -> dict:
        with self._lock:
            return dict(self._counts)


TRACER = Tracer()


# --------------------------------------------------------------------------
# module-level API (what product code calls)
# --------------------------------------------------------------------------


def armed() -> bool:
    return TRACER._armed


def enable(sample: float | None = None, slow_ms: float | None = None) -> None:
    TRACER.enable(sample=sample, slow_ms=slow_ms)


def disable() -> None:
    TRACER.disable()


def span(name: str, **attrs):
    """Open a child span of the current context (context manager).

    Disarmed: a phase (:data:`PHASES`) is accounted and recorded in no
    trace; any other name returns the shared no-op (one attribute read,
    one dict miss). The span object is truthy only when armed, so hot
    paths can guard attr computation with ``if sp:`` — an attribute a
    phase's stage template needs (``backend``) is given here, at open.
    """
    t = TRACER
    if t._armed:
        return _LiveSpan(t, name, t.current(), attrs)
    keys = _PHASE_KEYS.get(name)
    if keys is None:
        return _NOOP
    return _PhaseSpan(name, keys, attrs)


def record_span(name: str, start_wall: float, duration_s: float,
                span_id: str | None = None, status: str = "ok",
                **attrs) -> None:
    """Atomically record a completed span under the current context —
    for extents whose start and end straddle protocol messages (e.g.
    query.execute across PULL batches). No begin/end imbalance is
    possible: one call, one span. A phase is accounted armed or not; an
    after-the-fact record cannot sit in a profiler session."""
    keys = _PHASE_KEYS.get(name)
    if keys is not None:
        _account(keys, duration_s, attrs)
    t = TRACER
    if not t._armed:
        return
    ctx = t.current()
    if ctx is None:
        return
    t._record(ctx.trace_id, {
        "trace_id": ctx.trace_id, "span_id": span_id or _new_id(),
        "parent_id": ctx.span_id, "name": name, "ts": start_wall,
        "dur_s": duration_s, "status": status, "error": None,
        "attrs": _clean_attrs(attrs), "pid": os.getpid(),
        "tid": threading.get_ident()})


def begin_trace(name: str, carrier: dict | None = None):
    """Mint the root of a locally-owned trace. Returns a TraceHandle;
    the owner must call ``handle.finish()`` exactly once. Disarmed it
    returns None, or for a phase a handle with no ``ctx`` that only
    accounts. If a remote ``carrier`` (or an ambient local context)
    exists, the new root joins that trace as a child."""
    t = TRACER
    if not t._armed:
        if name in _PHASE_KEYS:
            return TraceHandle(t, name, None, None, owns_finalize=False)
        return None
    parent = None
    edge = False
    if carrier and carrier.get("trace_id"):
        # a process-edge adoption (e.g. a Bolt client's carrier): this
        # handle is the local retention owner
        parent = TraceContext(str(carrier["trace_id"]),
                              str(carrier.get("span_id") or ""),
                              bool(carrier.get("sampled", True)))
        edge = True
    if parent is None:
        parent = t.current()
    if parent is not None:
        trace_id, sampled = parent.trace_id, parent.sampled
        parent_id = parent.span_id or None
    else:
        trace_id = _new_id(16)
        sampled = _sample_decision(trace_id, t.sample_rate)
        parent_id = None
    ctx = TraceContext(trace_id, _new_id(), sampled)
    return TraceHandle(t, name, ctx, parent_id,
                       owns_finalize=edge or parent_id is None)


def activate(ctx):
    """Make ``ctx`` (a TraceContext, e.g. ``handle.ctx``) current for
    the extent — the cross-thread continuation primitive. None → no-op."""
    if ctx is None or not TRACER._armed:
        return _NULL_ACTIVATION
    return _Activation(TRACER, ctx)


def adopt(carrier: dict | None, retain: bool = False):
    """Activate a REMOTE parent context from a wire carrier. Spans
    opened inside join the remote trace. retain=True finalizes the
    trace locally on exit (one-way hops); retain=False leaves the spans
    for take_trace() to ship back."""
    t = TRACER
    if not t._armed or not carrier or not carrier.get("trace_id"):
        return _NULL_ACTIVATION
    ctx = TraceContext(str(carrier["trace_id"]),
                       str(carrier.get("span_id") or ""),
                       bool(carrier.get("sampled", True)))
    return _Adoption(t, ctx, retain)


def inject() -> dict | None:
    """The wire carrier for the current context, or None."""
    ctx = TRACER.current()
    return ctx.carrier() if ctx is not None else None


def current_trace_id() -> str | None:
    ctx = TRACER.current()
    return ctx.trace_id if ctx is not None else None


def take_trace(trace_id: str) -> list[dict]:
    return TRACER.take_trace(trace_id)


def adopt_spans(spans) -> None:
    TRACER.adopt_spans(spans)


# --------------------------------------------------------------------------
# exporters
# --------------------------------------------------------------------------


def traces_json(trace_id: str | None = None) -> list[list[dict]]:
    """Retained traces (newest last), optionally filtered by id."""
    traces = TRACER.finished_traces()
    if trace_id:
        traces = [t for t in traces
                  if t and t[0].get("trace_id") == trace_id]
    return traces


def chrome_trace(traces=None) -> dict:
    """Chrome trace-event JSON (load in Perfetto / chrome://tracing).

    Complete ("X") events in microseconds; pid/tid preserved so a
    cross-process trace renders as lanes per process."""
    traces = TRACER.finished_traces() if traces is None else traces
    events = []
    for spans in traces:
        for s in spans:
            args = {"trace_id": s.get("trace_id"),
                    "span_id": s.get("span_id"),
                    "parent_id": s.get("parent_id"),
                    "status": s.get("status")}
            args.update(s.get("attrs") or {})
            if s.get("error"):
                args["error"] = s["error"]
            events.append({
                "name": s.get("name", "?"), "cat": "mgtrace", "ph": "X",
                "ts": float(s.get("ts", 0.0)) * 1e6,
                "dur": max(float(s.get("dur_s", 0.0)) * 1e6, 0.001),
                "pid": s.get("pid", 0), "tid": s.get("tid", 0),
                "args": args})
    return {"traceEvents": events, "displayTimeUnit": "ms"}


# --------------------------------------------------------------------------
# Python's cyclic collector
# --------------------------------------------------------------------------


class _CollectorPhase(_PhaseSpan):
    """A phase the collector's callback opens. Its close adds to
    :data:`_collected` and takes no lock; :func:`_hand_over` moves that
    into the phase's counters before ``global_metrics`` is read."""

    __slots__ = ()

    def __init__(self, name: str) -> None:
        super().__init__(name, _PHASE_KEYS[name], {})

    def __exit__(self, exc_type, exc, tb):
        self.seconds = seconds = time.perf_counter() - self._t0
        if self._ann is not None:
            _exit_annotation(self._ann)
        total, count = _collected[self._name]
        _collected[self._name] = (total + seconds, count + 1)
        return False


#: phase name -> (seconds, closes) the collector has closed, ever. Only
#: the callback writes it (collections never nest), one whole tuple at a
#: time into a key that is already there
_collected = {"python.gc": (0.0, 0), "python.gc.full": (0.0, 0)}
#: what _hand_over has moved into global_metrics of it
_handed = dict(_collected)
_hand_lock = threading.Lock()
#: the collection in progress: its open phases, innermost last
_collecting: list = []


def _on_collect(phase: str, info: dict) -> None:
    """``gc.callbacks``: Python's cyclic collector as ``python.gc`` (every
    collection) and ``python.gc.full`` (the same extent, generation 2:
    the whole heap, what a ``gc.unfreeze`` thawed included). A
    collection's start and stop come on the thread it interrupted.

    Accounted and in a live profiler session's xplane like any phase,
    armed or not; unlike one, a collection joins **no trace** and its
    close takes **no lock**: it runs at whatever bytecode the thread
    had reached, which may lie inside the tracer's lock, the metrics
    registry's, or the lock witness's own, and a recorded span or a
    locked add would wait there for itself. Never raises into the
    collector."""
    try:
        if phase == "start":
            _collecting.append(_CollectorPhase("python.gc").__enter__())
            if info.get("generation") == 2:
                _collecting.append(
                    _CollectorPhase("python.gc.full").__enter__())
        else:
            while _collecting:
                _collecting.pop().__exit__(None, None, None)
    except Exception:  # mglint: disable=MG003 — the collector must never see one, and a log line could wait on a lock the interrupted thread holds
        _collecting.clear()


def _hand_over() -> None:
    """The collector's closes since the last read, into
    ``span.python.gc*.seconds_total`` / ``.count`` (``global_metrics``
    runs this before every read)."""
    with _hand_lock:
        now = dict(_collected)
        moved = [(name, total - _handed[name][0], count - _handed[name][1])
                 for name, (total, count) in now.items()
                 if count != _handed[name][1]]
        _handed.update(now)
    for name, seconds, count in moved:
        keys = _PHASE_KEYS[name]
        global_metrics.increment(keys[0], seconds)
        global_metrics.increment(keys[1], count)


def install_collector_phases() -> bool:
    """Install :func:`_on_collect` once a process, however often this
    module is set up (a reload included). True if this call did."""
    if any(getattr(cb, "__module__", None) == __name__
           and getattr(cb, "__name__", None) == "_on_collect"
           for cb in gc.callbacks):
        return False
    gc.callbacks.append(_on_collect)
    global_metrics.add_fold(_hand_over)
    return True


install_collector_phases()
