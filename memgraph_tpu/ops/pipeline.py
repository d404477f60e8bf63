"""mglane device kernels: whole read pipelines compiled onto the
semiring core.

The columnar lane (query/plan/parallel.py) already collapses an
eligible ``filter -> [expand] -> aggregate`` tail into whole-column
host-numpy kernels. This module is the DEVICE half of the same lane:
each recognized pipeline *shape* is compiled ONCE (per plan-cache
fingerprint, see query/plan/lane.py) into a single jitted XLA program
in which the predicate masks, the expansion and the aggregate epilogue
are fused — masks are applied with ``where(mask, v, identity)`` inside
the reduction (GraphBLAST's masked-SpMV formulation), never as a
gather-then-filter materialization.

Three program families:

  * ``masked_aggregate`` — columnar predicate masks over stacked int32
    property columns + fused count/sum/min/max epilogues. Used by both
    the scan tail and the one-hop edge-table tail (an edge snapshot is
    just another column set).
  * ``hop_counts`` — 1–2 hop expansion counts from a masked source
    frontier: ``x1 = A^T ⊕.⊗ s`` over the **plus_first** semiring
    (path multiplicities), chained for the second hop, with the
    self-loop edge-uniqueness correction and an optional **or_and**
    style distinct-target epilogue (``count(DISTINCT m)`` is a
    reachability popcount). Rides :func:`ops.semiring.spmv`.
  * ``masked_topk`` — ORDER BY <int key> LIMIT k as one fused
    mask + stable argsort program (nulls ranked per openCypher:
    last ascending, first descending).

Exactness discipline (this jax build keeps x64 disabled): columns are
admitted only when every value fits int32; predicate compares run in
int32 (bit-exact vs the row path); count/sum epilogues accumulate in
int32 with an f32 absolute-mass shadow — the host refuses the result
(typed ``precision_overflow`` fallback) unless the shadow proves no
int32 partial could have wrapped (mass < 2^30; path-count chains
additionally prove every per-node multiplicity stayed under f32's 2^24
integer range). Anything the discipline cannot prove falls back to the
host columnar path, which is exact by construction.

Shapes are padded to power-of-two buckets before dispatch, so the
compile count is O(shapes x log(size)) — the same bounded-bucket
contract the PPR serving lanes carry, checked statically by
tools/mgxla (``segment:lane_*`` contracts: zero collectives, no f64,
no host callbacks).
"""

from __future__ import annotations

import os
import threading

import numpy as np

from ..observability import trace as mgtrace
from ..utils.locks import tracked_lock
from ..utils.sanitize import shared_field, shared_read, shared_write

#: device dispatch pays off only past this row/edge count (below it the
#: host columnar sweep wins); USING PARALLEL EXECUTION forces through
LANE_MIN_ROWS = int(os.environ.get("MEMGRAPH_TPU_LANE_MIN_ROWS", 4096))

#: f32 integer-exactness ceiling for per-node path multiplicities
_F24 = float(1 << 24)
#: int32 no-partial-wrap ceiling for the f32 mass shadows
_I30 = float(1 << 30)

#: predicate opcodes (static program structure; rhs stays traced)
_OPS = ("=", "<>", "<", "<=", ">", ">=", "present")

#: int32 identities for masked min/max
_I32_MAX = np.int32(2**31 - 1)
_I32_MIN = np.int32(-(2**31) + 1)


class LaneRefused(Exception):
    """Typed device-lane refusal; ``reason`` feeds
    ``lane.fallback_total.<reason>`` and the per-fingerprint registry."""

    span_status = "refused"     # mgtrace: a decline, not a span error

    def __init__(self, reason: str, detail: str = "") -> None:
        super().__init__(detail or reason)
        self.reason = reason


def _bucket(n: int, floor: int = 1024) -> int:
    """Power-of-two padding bucket: bounded distinct compiled shapes."""
    b = floor
    while b < n:
        b <<= 1
    return b


def _pad(arr: np.ndarray, size: int, fill) -> np.ndarray:
    if len(arr) == size:
        return arr
    out = np.full(size, fill, dtype=arr.dtype)
    out[: len(arr)] = arr
    return out


# --------------------------------------------------------------------------
# program cache (fingerprint-keyed bookkeeping lives in LaneRegistry;
# programs themselves are keyed structurally so identical shapes from
# different fingerprints share one executable)
# --------------------------------------------------------------------------

_PROGRAM_CACHE: dict = {}
_program_lock = threading.Lock()


def _get_program(key, build, *build_args):
    """MG008-shaped memo: get-then-build-then-store under one lock, with
    compile accounting (lane.compiled_total / compile-latency histogram
    / the ``lane_compile`` PROFILE stage)."""
    fn = _PROGRAM_CACHE.get(key)
    if fn is not None:
        return fn
    from ..observability.metrics import global_metrics
    from ..utils.jax_cache import ensure_compile_cache
    ensure_compile_cache()
    with _program_lock:
        fn = _PROGRAM_CACHE.get(key)
        if fn is None:
            with mgtrace.span("lane.compile") as sp:
                fn = build(*build_args)
            _PROGRAM_CACHE[key] = fn
            global_metrics.increment("lane.compiled_total")
            global_metrics.observe("lane.compile_latency_sec", sp.seconds)
            global_metrics.set_gauge("lane.resident",
                                     float(len(_PROGRAM_CACHE)))
    return fn


def resident_programs() -> int:
    return len(_PROGRAM_CACHE)


def drop_programs() -> None:
    """Schema-change invalidation: drop every compiled lane program
    (query/plan/lane.py calls this from the plan-cache invalidation
    hook — a lane compiled under dropped DDL must never serve)."""
    from ..observability.metrics import global_metrics
    with _program_lock:
        _PROGRAM_CACHE.clear()
    global_metrics.set_gauge("lane.resident", 0.0)


# --------------------------------------------------------------------------
# per-fingerprint lane registry (compiles / hits / typed fallbacks)
# --------------------------------------------------------------------------


class LaneRegistry:
    """Per-plan-cache-fingerprint lane accounting, surfaced as the
    ``lane`` section of ``GET /stats``. Plan-time refusals (shape never
    compiled) land under the ``"<plan>"`` pseudo-fingerprint."""

    def __init__(self) -> None:
        self._lock = tracked_lock("LaneRegistry._lock")
        self._by_fp: dict[str, dict] = {}
        shared_field(self, "_by_fp")

    def _entry(self, fp: str | None) -> dict:
        key = fp or "<plan>"
        # mglint: disable=MG006,MG007 — every caller holds self._lock
        # around this helper (leaf lock; intraprocedural analysis
        # cannot see the caller's lock region)
        e = self._by_fp.get(key)
        if e is None:
            e = self._by_fp[key] = {"compiled": 0, "hits": 0,  # mglint: disable=MG006,MG007 — under caller's self._lock
                                    "fallbacks": {}}
        return e

    def note_compiled(self, fp: str | None) -> None:
        with self._lock:
            shared_write(self, "_by_fp")
            self._entry(fp)["compiled"] += 1

    def note_hit(self, fp: str | None) -> None:
        from ..observability.metrics import global_metrics
        global_metrics.increment("lane.hit_total")
        with self._lock:
            shared_write(self, "_by_fp")
            self._entry(fp)["hits"] += 1

    def note_fallback(self, fp: str | None, reason: str) -> None:
        from ..observability.metrics import global_metrics
        global_metrics.increment(f"lane.fallback_total.{reason}")
        with self._lock:
            shared_write(self, "_by_fp")
            fb = self._entry(fp)["fallbacks"]
            fb[reason] = fb.get(reason, 0) + 1

    def compiles_for(self, fp: str | None) -> int:
        with self._lock:
            shared_read(self, "_by_fp")
            return self._entry(fp)["compiled"]

    def reset(self) -> None:
        with self._lock:
            shared_write(self, "_by_fp")
            self._by_fp.clear()

    def snapshot(self) -> dict:
        with self._lock:
            shared_read(self, "_by_fp")
            return {fp: {"compiled": e["compiled"], "hits": e["hits"],
                         "fallbacks": dict(e["fallbacks"])}
                    for fp, e in self._by_fp.items()}


LANE_REGISTRY = LaneRegistry()


def lane_stats() -> dict:
    """The ``lane`` section of ``GET /stats``."""
    return {"resident_programs": resident_programs(),
            "fingerprints": LANE_REGISTRY.snapshot()}


# --------------------------------------------------------------------------
# masked aggregate program (scan tail + one-hop edge tail)
# --------------------------------------------------------------------------


def _compare(v, r, op):
    import jax.numpy as jnp
    if op == "=":
        return v == r
    if op == "<>":
        return v != r
    if op == "<":
        return v < r
    if op == "<=":
        return v <= r
    if op == ">":
        return v > r
    if op == ">=":
        return v >= r
    return jnp.ones_like(v, dtype=bool)       # "present": presence only


def _build_agg_program(preds: tuple, aggs: tuple):
    """One fused program: predicate masks AND-folded into every
    aggregate's reduction via where(mask, v, identity) — never a
    gathered intermediate. Returns a flat tuple of int32/f32 scalars
    laid out per _AGG_WIDTH."""
    import jax
    import jax.numpy as jnp

    def run(vals, present, base, rhs):
        mask = base
        for i, (ci, op) in enumerate(preds):
            m = _compare(vals[ci], rhs[i], op)
            mask = mask & m & present[ci]
        outs = []
        mask_i = mask.astype(jnp.int32)
        for kind, ci in aggs:
            if ci is None:                    # count(*) / count(sym)
                outs.append(jnp.sum(mask_i))
                continue
            sel = mask & present[ci]
            v = vals[ci]
            if kind == "count":
                outs.append(jnp.sum(sel.astype(jnp.int32)))
            elif kind == "sum":
                sv = jnp.where(sel, v, 0)
                outs.append(jnp.sum(sv))
                outs.append(jnp.sum(jnp.where(
                    sel, jnp.abs(v.astype(jnp.float32)), 0.0)))
            elif kind == "min":
                outs.append(jnp.min(jnp.where(sel, v, _I32_MAX)))
                outs.append(jnp.sum(sel.astype(jnp.int32)))
            else:                             # max
                outs.append(jnp.max(jnp.where(sel, v, _I32_MIN)))
                outs.append(jnp.sum(sel.astype(jnp.int32)))
        return tuple(outs)

    return jax.jit(run)


def masked_aggregate(preds: tuple, aggs: tuple, vals: np.ndarray,
                     present: np.ndarray, base: np.ndarray,
                     rhs: list, fingerprint: str | None = None) -> list:
    """Dispatch one compiled scan/expand aggregate.

    ``vals``/``present`` are (C, n) int32 / bool stacks; ``preds`` is a
    static tuple of (col_idx, op); ``aggs`` a static tuple of
    (kind, col_idx|None); ``rhs`` the traced per-predicate int32
    right-hand sides. Returns python aggregate values in ``aggs``
    order; raises :class:`LaneRefused` when the exactness witness
    cannot prove the int32 accumulation safe.
    """
    with mgtrace.span("lane.dispatch"):     # lookup (or build) + padding
        n = vals.shape[1] if vals.size else len(base)
        nb = _bucket(max(n, 1))
        key = ("agg", preds, aggs, vals.shape[0], nb)
        was = key in _PROGRAM_CACHE
        fn = _get_program(key, _build_agg_program, preds, aggs)
        if not was:
            LANE_REGISTRY.note_compiled(fingerprint)
        if n != nb:
            vals = np.concatenate(
                [vals, np.zeros((vals.shape[0], nb - n), np.int32)],
                axis=1)
            present = np.concatenate(
                [present, np.zeros((present.shape[0], nb - n), bool)],
                axis=1)
            base = _pad(base, nb, False)
        rhs_arr = np.asarray(rhs, dtype=np.int32) if rhs else \
            np.zeros(0, dtype=np.int32)
    with mgtrace.span("lane.iterate"):      # call + readback: a block
        raw = [np.asarray(x) for x in fn(vals, present, base, rhs_arr)]

    out = []
    i = 0
    for kind, ci in aggs:
        if ci is None or kind == "count":
            out.append(int(raw[i]))
            i += 1
        elif kind == "sum":
            total, mass = int(raw[i]), float(raw[i + 1])
            i += 2
            if mass >= _I30:
                raise LaneRefused("precision_overflow",
                                  f"sum mass {mass:.3g} >= 2^30")
            out.append(total)
        else:                                  # min / max
            val, cnt = int(raw[i]), int(raw[i + 1])
            i += 2
            out.append(val if cnt else None)
    return out


# --------------------------------------------------------------------------
# hop-count program (1–2 hop expansion from a masked frontier)
# --------------------------------------------------------------------------


def _build_hops_program(hops: int, include_lower: bool, edge_unique: bool,
                        need_rows: bool, need_distinct: bool, n_out: int):
    """Masked plus_first SpMV chain over the semiring core. All masks
    arrive as traced (n,)/(e,) arrays so one program serves every
    predicate/parameter combination of the shape."""
    import jax
    import jax.numpy as jnp

    from . import semiring as S

    def run(src, dst, emask, smask, midmask, tmask):
        x0 = smask.astype(jnp.float32)
        x1 = S.spmv("plus_first", x0, src, dst, n_out=n_out, mask=emask)
        p = jnp.zeros(n_out, dtype=jnp.float32)
        max1 = jnp.max(x1)
        if hops == 2:
            x1m = x1 * midmask
            x2 = S.spmv("plus_first", x1m, src, dst, n_out=n_out,
                        mask=emask)
            p2 = x2 * tmask
            if edge_unique:
                # the ONLY length-2 path reusing its edge is a source
                # self-loop traversed twice: subtract one per such edge
                w = x0 * midmask
                sl = S.spmv("plus_first", w, src, dst, n_out=n_out,
                            mask=emask & (src == dst))
                p2 = p2 - sl * tmask
            p = p + p2
            max2 = jnp.max(x2)
        else:
            max2 = jnp.float32(0.0)
        if hops == 1 or include_lower:
            p = p + x1 * tmask
        outs = [max1, max2, jnp.sum(p)]
        if need_rows:
            outs.append(jnp.sum(p.astype(jnp.int32)))
        if need_distinct:
            outs.append(jnp.sum((p > 0.5).astype(jnp.int32)))
        return tuple(outs)

    return jax.jit(run)


def stage_edges(src: np.ndarray, dst: np.ndarray,
                emask: np.ndarray) -> tuple:
    """Pad the edge arrays to their bucket and ship them to the device
    ONCE. Callers cache the staged tuple per (topology version, edge
    types, direction) — the per-query hop dispatch then moves only the
    O(n) node masks, which is what makes the lane's per-query export
    cost zero on an unchanged graph (the PR 14 residency contract)."""
    import jax
    e = len(src)
    eb = _bucket(max(e, 1))
    return (jax.device_put(_pad(np.asarray(src, np.int32), eb, 0)),
            jax.device_put(_pad(np.asarray(dst, np.int32), eb, 0)),
            jax.device_put(_pad(np.asarray(emask, bool), eb, False)),
            eb)


def hop_counts(src, dst, emask, smask: np.ndarray,
               midmask: np.ndarray, tmask: np.ndarray, n_nodes: int, *,
               hops: int, include_lower: bool = False,
               edge_unique: bool = True, need_rows: bool = True,
               need_distinct: bool = False,
               fingerprint: str | None = None) -> dict:
    """Run a compiled 1–2 hop count. ``src``/``dst``/``emask`` may be a
    :func:`stage_edges` result (already padded + device-resident) or
    raw host arrays. Returns {"rows": int, "distinct": int} (keys per
    request); raises :class:`LaneRefused` when the f32 multiplicity
    witness trips."""
    with mgtrace.span("lane.dispatch"):     # padding + lookup (or build)
        n = int(n_nodes)
        nb = _bucket(max(n, 1))
        if isinstance(src, np.ndarray):
            src, dst, emask, eb = stage_edges(src, dst, emask)
        else:
            eb = len(src)
        smask = _pad(np.asarray(smask, bool), nb, False)
        midmask = _pad(np.asarray(midmask, np.float32), nb, 0.0)
        tmask = _pad(np.asarray(tmask, np.float32), nb, 0.0)
        key = ("hops", hops, include_lower, edge_unique, need_rows,
               need_distinct, eb, nb)
        was = key in _PROGRAM_CACHE
        fn = _get_program(key, _build_hops_program, hops, include_lower,
                          edge_unique, need_rows, need_distinct, nb)
        if not was:
            LANE_REGISTRY.note_compiled(fingerprint)
    with mgtrace.span("lane.iterate"):      # call + readback: a block
        raw = [np.asarray(x) for x in
               fn(src, dst, emask, smask, midmask, tmask)]
    max1, max2, total_f = float(raw[0]), float(raw[1]), float(raw[2])
    if max1 >= _F24 or max2 >= _F24:
        raise LaneRefused("precision_overflow",
                          "per-node path multiplicity >= 2^24")
    if total_f >= _I30:
        raise LaneRefused("precision_overflow",
                          f"path total {total_f:.3g} >= 2^30")
    out: dict = {}
    i = 3
    if need_rows:
        out["rows"] = int(raw[i])
        i += 1
    if need_distinct:
        out["distinct"] = int(raw[i])
    return out


# --------------------------------------------------------------------------
# top-k ORDER BY program
# --------------------------------------------------------------------------

#: null ordering sentinels — finite so they sort between real keys
#: (|v| < 2^24 admitted) and the +inf "predicate excluded" sentinel
_NULL_LAST = np.float32(3.0e38)
_NULL_FIRST = np.float32(-3.0e38)


def _build_topk_program(preds: tuple, ascending: bool):
    """Fused mask + stable ascending argsort. Nulls rank last under ASC
    and first under DESC (openCypher orderability); rows excluded by a
    predicate sort to the very end, past every included row."""
    import jax
    import jax.numpy as jnp

    def run(vals, present, keyv, keyp, rhs):
        mask = jnp.ones_like(keyp)
        for i, (ci, op) in enumerate(preds):
            m = _compare(vals[ci], rhs[i], op)
            mask = mask & m & present[ci]
        kf = keyv.astype(jnp.float32)
        if not ascending:
            kf = -kf
        null_rank = _NULL_LAST if ascending else _NULL_FIRST
        kf = jnp.where(keyp, kf, null_rank)
        kf = jnp.where(mask, kf, jnp.float32(np.inf))
        order = jnp.argsort(kf)                # stable: ties keep row order
        return order, jnp.sum(mask.astype(jnp.int32))

    return jax.jit(run)


def masked_topk(preds: tuple, ascending: bool, vals: np.ndarray,
                present: np.ndarray, keyv: np.ndarray, keyp: np.ndarray,
                rhs: list, fingerprint: str | None = None):
    """Returns (order, n_included): row indices in final ORDER BY order
    (callers take the first min(k, n_included))."""
    with mgtrace.span("lane.dispatch"):     # lookup (or build) + padding
        n = len(keyv)
        nb = _bucket(max(n, 1))
        key = ("topk", preds, ascending, vals.shape[0], nb)
        was = key in _PROGRAM_CACHE
        fn = _get_program(key, _build_topk_program, preds, ascending)
        if not was:
            LANE_REGISTRY.note_compiled(fingerprint)
        if n != nb:
            vals = np.concatenate(
                [vals, np.zeros((vals.shape[0], nb - n), np.int32)],
                axis=1)
            present = np.concatenate(
                [present, np.zeros((present.shape[0], nb - n), bool)],
                axis=1)
            keyv = _pad(keyv, nb, np.int32(0))
            keyp = _pad(keyp, nb, False)
        rhs_arr = np.asarray(rhs, dtype=np.int32) if rhs else \
            np.zeros(0, dtype=np.int32)
    with mgtrace.span("lane.iterate"):      # call + readback: a block
        order, count = fn(vals, present, keyv, keyp, rhs_arr)
        order = np.asarray(order)
        count = int(count)
    return order, count


# --------------------------------------------------------------------------
# host-side column admission (exactness gate) + device staging
# --------------------------------------------------------------------------


def i32_column(col) -> np.ndarray | None:
    """An ops/columnar.py Column as an int32 value array, or None when
    the lane's exactness discipline cannot admit it (float columns,
    ints beyond int32, "other" kinds). The verdict is cached on the
    column — snapshots live per topology version, so this runs once per
    (version, column)."""
    cached = getattr(col, "_lane_i32", False)
    if cached is not False:
        return cached
    out = None
    if col.kind in ("int", "bool", "str") and col.values is not None:
        if col.kind == "int":
            v = col.values
            sel = v[col.present] if col.present.any() else v[:0]
            if sel.size == 0 or (int(sel.min()) > -(2**31)
                                 and int(sel.max()) < 2**31):
                out = v.astype(np.int32)
        else:
            out = col.values.astype(np.int32)
    try:
        col._lane_i32 = out
    except AttributeError:
        pass
    return out
