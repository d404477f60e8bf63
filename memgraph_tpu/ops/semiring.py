"""GraphBLAST-style semiring SpMV/SpMM core — the ONE linear-algebra seam
every SpMV-shaped algorithm in `ops/` rides.

Before r10 each algorithm (pagerank, katz, labelprop, wcc/scc, sssp/bfs,
betweenness, gnn) hand-rolled its own `jax.ops.segment_*`-inside-
`lax.while_loop` pipeline — 31 call sites across 8 files — and none of
them inherited the MXU fast path or the mesh story unless someone wired
it by hand. This module collapses all of them onto one algebra
(GraphBLAST, PAPERS.md): a graph algorithm is

    y = A ⊕.⊗ x          over a (⊕, ⊗) semiring,

iterated to a fixpoint with the rank-update and the convergence check
FUSED into the matvec body (FUSED-PAGERANK, PAPERS.md — the epilogue
runs on the accumulator while it is still in registers/VMEM, removing a
full HBM round trip per iteration).

Three backends sit behind one dispatch (`route_backend`):

  * ``segment``  — the reference path: per-edge gather + ⊗-combine +
    sorted segment-⊕ reduction, jitted with the epilogue fused into the
    `while_loop` body.  Runs everywhere (CPU tests, mesh-of-1).
  * ``mxu``      — the gather-free pallas/Benes MXU plan
    (`ops/spmv_mxu.py`), generalized from pagerank-only to
    semiring-parameterized kernels.  Only ⊕ = sum rides it (the
    reduce/extract phase is a one-hot matmul, i.e. a sum).
  * ``mesh``     — the partition-centric `ShardedCSR` kernels
    (`parallel/distributed.py`): exactly ONE collective per iteration,
    checkpoint-resumable through the r12 chunk machinery.

Mixed precision (`precision=`): ``f32`` is the exact path; ``bf16``
rounds each per-edge contribution to bfloat16 before the f32
accumulation (halves the routed HBM traffic on the MXU backend);
``int8`` quantizes the streamed vector symmetrically to int8 per
iteration and dequantizes after the gather (the reduced-precision
streaming SpMV of PAPERS.md).  The documented error bounds live in
:data:`PRECISION_BOUNDS` and are enforced by tests/test_semiring.py.

Direction optimization: :func:`select_pull` implements the
Beamer/GraphBLAST push/pull heuristic — pull (reduce over all edges)
when the frontier's out-edge mass exceeds ``n_edges / DIRECTION_ALPHA``,
push (frontier-masked contributions) when it is sparse.  Both sides are
exact; the selector only changes which formulation the device executes.

Adding a new algorithm is a ~50-line (semiring, setup, epilogue)
definition — see docs/architecture.md §Semiring kernel core.

The seam also serves NON-iterating consumers: the compiled Cypher read
lane (r20 mglane, ops/pipeline.py) lowers 1–2 hop expansions onto
fixed-depth masked :func:`spmv` chains over the ``plus_first`` /
``or_and`` rows of the table — same masks, same backends, same stage
attribution, no while_loop.
"""

from __future__ import annotations

import os
import threading
from dataclasses import dataclass
from functools import partial

import numpy as np

# --------------------------------------------------------------------------
# semiring algebra
# --------------------------------------------------------------------------

#: ⊕ kinds understood by :func:`edge_reduce`
_ADD_KINDS = ("sum", "min", "max", "or")
#: ⊗ kinds understood by :func:`edge_combine`
_MUL_KINDS = ("times", "plus", "first", "min", "and")


@dataclass(frozen=True)
class Semiring:
    """A (⊕, ⊗) pair: ``y[j] = ⊕_{(i,j) ∈ E} (x[i] ⊗ w[i,j])``."""
    name: str
    add: str            # one of _ADD_KINDS
    mul: str            # one of _MUL_KINDS

    def __post_init__(self):
        if self.add not in _ADD_KINDS:
            raise ValueError(f"unknown ⊕ {self.add!r}")
        if self.mul not in _MUL_KINDS:
            raise ValueError(f"unknown ⊗ {self.mul!r}")


#: the semiring table (GraphBLAST's classics + the two degenerate ⊗=first
#: forms the label/component kernels use). mglint MG005 validates every
#: SPMV_ALGORITHMS "core" declaration against these keys.
SEMIRINGS = {
    "plus_times": Semiring("plus_times", "sum", "times"),   # pagerank/katz
    "min_plus": Semiring("min_plus", "min", "plus"),        # sssp/bfs
    "max_min": Semiring("max_min", "max", "min"),           # bottleneck path
    "or_and": Semiring("or_and", "or", "and"),              # reachability
    "plus_first": Semiring("plus_first", "sum", "first"),   # sigma/gnn agg
    "min_first": Semiring("min_first", "min", "first"),     # wcc/scc labels
}


def resolve_semiring(sr) -> Semiring:
    if isinstance(sr, Semiring):
        return sr
    got = SEMIRINGS.get(sr)
    if got is None:
        raise KeyError(f"unknown semiring {sr!r}; have {sorted(SEMIRINGS)}")
    return got


# --------------------------------------------------------------------------
# mixed precision
# --------------------------------------------------------------------------

#: Documented, test-enforced error bounds (tests/test_semiring.py asserts
#: converged pagerank on the seeded 300-node/3k-edge graph stays inside
#: these vs the f32 reference; docs/architecture.md §Semiring kernel core
#: carries the same table).  Derivation sketch:
#:   bf16 — each contribution carries one rounding of relative size
#:          2^-9..2^-8; with damping d the fixpoint error is bounded by
#:          d/(1-d) · 2^-8 · max(rank) per component.  Budgeted 4x.
#:   int8 — symmetric per-iteration quantization of the streamed vector:
#:          |x - dq(x)| ≤ max|x|/254 per element, amplified d/(1-d) at
#:          the fixpoint.  Budgeted 4x.
PRECISION_BOUNDS = {
    "bf16": {"pagerank_linf": 4 * (0.85 / 0.15) * 2.0 ** -8 * 0.05,
             "pagerank_l1": 2.5e-2, "topk_order": 5},
    "int8": {"pagerank_linf": 4 * (0.85 / 0.15) * (0.05 / 254.0),
             "pagerank_l1": 2.5e-2, "topk_order": 5},
}

_PRECISIONS = ("f32", "bf16", "int8")


def _check_precision(precision: str) -> str:
    if precision not in _PRECISIONS:
        raise ValueError(
            f"precision must be one of {_PRECISIONS}, got {precision!r}")
    return precision


def quantize_int8(x):
    """Symmetric per-vector int8 quantization: (q int8, scale f32) with
    x ≈ q * scale, |x - q·scale| ≤ max|x|/254 per element."""
    import jax.numpy as jnp
    scale = jnp.maximum(jnp.max(jnp.abs(x)) / 127.0, 1e-30)
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


# --------------------------------------------------------------------------
# building blocks (traceable: usable inside any jitted kernel)
# --------------------------------------------------------------------------


def edge_combine(sr, xe, w=None):
    """Per-edge ⊗: combine the gathered vector entries with edge values.

    SpMM lanes: when ``xe`` carries feature columns (e, d) and ``w`` is
    the per-edge (e,) vector, the edge values broadcast across every
    lane — one weight per edge, applied to all d fixpoints at once (the
    batched multi-source PPR formulation)."""
    import jax.numpy as jnp
    sr = resolve_semiring(sr)
    if sr.mul == "first":
        return xe
    if w is None:
        raise ValueError(f"⊗ = {sr.mul!r} needs edge values")
    if getattr(xe, "ndim", 1) > 1 and getattr(w, "ndim", 1) == 1:
        w = w[(...,) + (None,) * (xe.ndim - 1)]
    if sr.mul == "times":
        return xe * w
    if sr.mul == "plus":
        return xe + w
    if sr.mul == "min":
        return jnp.minimum(xe, w)
    # "and": boolean conjunction
    return jnp.logical_and(xe, w)


def edge_reduce(kind, vals, ids, num_segments: int, sorted: bool = False):
    """⊕ segment reduction — THE routing point for every segment-shaped
    reduction in ops/ (mglint MG005 flags residual direct
    ``jax.ops.segment_*`` pipelines outside this module)."""
    import jax
    import jax.numpy as jnp
    if kind == "sum":
        return jax.ops.segment_sum(vals, ids, num_segments=num_segments,
                                   indices_are_sorted=sorted)
    if kind == "min":
        return jax.ops.segment_min(vals, ids, num_segments=num_segments,
                                   indices_are_sorted=sorted)
    if kind == "max":
        return jax.ops.segment_max(vals, ids, num_segments=num_segments,
                                   indices_are_sorted=sorted)
    if kind == "or":
        got = jax.ops.segment_max(vals.astype(jnp.int32), ids,
                                  num_segments=num_segments,
                                  indices_are_sorted=sorted)
        return got > 0
    raise ValueError(f"unknown ⊕ {kind!r}")


def run_starts(first):
    """At every position of a sorted key column, the first position of
    its run of equal keys (``first`` marks where a run starts; position
    0 must). ``idx - run_starts(first) + 1`` counts the run so far, an
    exact int32 that is the run's length at its last position: the
    sorted-run form of ``edge_reduce("sum", 1, run_id)``, with no
    scatter."""
    import jax
    import jax.numpy as jnp
    idx = jnp.arange(first.shape[0], dtype=jnp.int32)
    return jax.lax.cummax(jnp.where(first, idx, 0))


def segment_cummax(vals, seg_start):
    """Running max of int32 ``vals`` that restarts at every segment of a
    sorted layout (``seg_start``: each position's segment's first
    position, from :func:`run_starts`): one global cummax over keys
    offset by the segment's start, with no scatter. Exact where every
    value lies in [0, its segment's length]: an earlier segment's keys
    then never pass a later segment's start."""
    import jax
    return jax.lax.cummax(seg_start + vals) - seg_start


def reduce_identity(sr, dtype):
    """The ⊕ identity (what masked-out edges must contribute)."""
    import jax.numpy as jnp
    sr = resolve_semiring(sr)
    if sr.add == "sum":
        return jnp.zeros((), dtype=dtype)
    if sr.add == "or":
        return jnp.zeros((), dtype=jnp.bool_)
    info = (jnp.iinfo(dtype) if jnp.issubdtype(dtype, jnp.integer)
            else jnp.finfo(dtype))
    return jnp.array(info.max if sr.add == "min" else info.min,
                     dtype=dtype)


def combine_accumulators(sr, a, b):
    """⊕-combine two partial accumulators (e.g. fwd + bwd direction)."""
    import jax.numpy as jnp
    sr = resolve_semiring(sr)
    if sr.add == "sum":
        return a + b
    if sr.add == "min":
        return jnp.minimum(a, b)
    if sr.add == "max":
        return jnp.maximum(a, b)
    return jnp.logical_or(a, b)


def spmv(sr, x, src, dst, w=None, *, n_out: int, sorted: bool = False,
         mask=None, mask_fill=None, precision: str = "f32",
         frontier=None):
    """One semiring matvec ``y = A^T ⊕.⊗ x`` over COO edge arrays.

    Traceable — usable standalone or inside a jitted loop body.

      sr         semiring name or Semiring
      x          (n,) or (n, d) vector/matrix (SpMM: d feature lanes)
      src, dst   (e,) gather / reduce-key edge endpoints
      w          (e,) edge values (required unless ⊗ = first)
      sorted     dst is non-decreasing (CSC shards) → sorted lowering
      mask       (e,) bool — edges where False contribute the ⊕ identity
                 (or `mask_fill` when given: the masked-SpMV of
                 GraphBLAST, used by the SCC coloring rounds)
      precision  f32 | bf16 (contributions rounded, f32 accumulate) |
                 int8 (x quantized before the gather — the streamed
                 read is 1/4 the bytes — dequantized after)
      frontier   (n,) bool — push-mode source masking: only edges whose
                 src is in the frontier contribute (exact for monotone
                 iterations; see select_pull)
    """
    import jax.numpy as jnp
    sr = resolve_semiring(sr)
    _check_precision(precision)
    if precision == "int8":
        q, scale = quantize_int8(x)
        xe = q[src].astype(x.dtype) * scale
    else:
        xe = x[src]
    vals = edge_combine(sr, xe, w)
    if precision == "bf16":
        vals = vals.astype(jnp.bfloat16).astype(jnp.float32)
    sel = None
    if mask is not None:
        sel = mask
    if frontier is not None:
        fsel = frontier[src]
        sel = fsel if sel is None else (sel & fsel)
    if sel is not None:
        fill = (mask_fill if mask_fill is not None
                else reduce_identity(sr, vals.dtype))
        if vals.ndim > 1:
            sel = sel[(...,) + (None,) * (vals.ndim - 1)]
        vals = jnp.where(sel, vals, fill)
    return edge_reduce(sr.add, vals, dst, n_out, sorted=sorted)


# --------------------------------------------------------------------------
# direction-optimizing push/pull
# --------------------------------------------------------------------------

#: Beamer's alpha: pull once the frontier's out-edge mass exceeds
#: n_edges / alpha (the classic DO-BFS threshold; env-overridable)
DIRECTION_ALPHA = float(os.environ.get("MEMGRAPH_TPU_DO_ALPHA", 14.0))


def select_pull(frontier, out_degree, n_edges, alpha: float | None = None):
    """Traced push/pull decision from frontier density.

    Returns a traced bool: True → pull (reduce over every edge), False →
    push (frontier-masked contributions).  `frontier` is the (n,) bool
    active-vertex mask, `out_degree` the (n,) f32 out-degrees — the
    frontier's out-edge mass m_f is compared against m/alpha exactly as
    in direction-optimizing BFS (Beamer; GraphBLAST's switch)."""
    import jax.numpy as jnp
    a = DIRECTION_ALPHA if alpha is None else alpha
    m_f = jnp.sum(jnp.where(frontier, out_degree, 0.0))
    return m_f > (n_edges / a)


# --------------------------------------------------------------------------
# the fused fixpoint loop (segment backend)
# --------------------------------------------------------------------------
#
# One jitted program per (algorithm, shapes):   env = setup(A, P)
#   while cond:  acc = step(x);  x, metric = epilogue(x, acc, env, P)
# The epilogue — the algorithm's update rule AND its convergence partial
# — runs inside the while body, on the accumulator the matvec just
# produced (FUSED-PAGERANK): no extra HBM round trip, no second kernel.

_FIXPOINT_CACHE: dict = {}
_fixpoint_cache_lock = threading.Lock()


def _default_step(sr, A, env, x, P, *, n_out, sorted, sorted_backward,
                  direction, precision):
    w = env.get("w", A.get("w"))
    acc = spmv(sr, x, A["src"], A["dst"], w, n_out=n_out, sorted=sorted,
               precision=precision)
    if direction == "both":
        acc_b = spmv(sr, x, A["dst"], A["src"], w, n_out=n_out,
                     sorted=sorted_backward, precision=precision)
        acc = combine_accumulators(sr, acc, acc_b)
    return acc


def _build_fixpoint(sr, *, epilogue, setup, step, n_out, max_iterations,
                    metric, precision, sorted, sorted_backward, direction):
    import jax
    import jax.numpy as jnp

    def run(A, P, x0):
        env = dict(setup(A, P, n_out)) if setup is not None else {}
        x = env.pop("x0") if x0 is None else x0
        tol = P.get("tol")

        def body(carry):
            x, _, it = carry
            if step is not None:
                acc = step(x, A, env, P, n_out)
            else:
                acc = _default_step(
                    sr, A, env, x, P, n_out=n_out, sorted=sorted,
                    sorted_backward=sorted_backward, direction=direction,
                    precision=precision)
            new_x, m = epilogue(x, acc, env, P)
            return new_x, m, it + 1

        if metric == "changed":
            def cond(carry):
                _, m, it = carry
                return m & (it < max_iterations)
            m0 = jnp.bool_(True)
        else:
            def cond(carry):
                _, m, it = carry
                return (m > tol) & (it < max_iterations)
            m0 = jnp.float32(jnp.inf)

        return jax.lax.while_loop(cond, body, (x, m0, jnp.int32(0)))

    # the program's name in a device trace is the algorithm's, from its
    # epilogue hook (jit_fixpoint_ppr, jit_fixpoint_pagerank, ...)
    hook = getattr(epilogue, "__name__", "").strip("_")
    run.__name__ = "fixpoint_" + (hook.removesuffix("_epilogue") or "run")
    # the x0 seed is donated back to the iterate: callers pass freshly
    # built start vectors (or None, which donates nothing), so the
    # fixpoint carry never holds two live copies of the O(n) state
    return jax.jit(run, donate_argnums=(2,))


def fixpoint(sr, *, arrays, params=None, x0=None, n_out: int, epilogue,
             setup=None, step=None, max_iterations: int, metric="err",
             precision: str = "f32", sorted: bool = False,
             sorted_backward: bool = False, direction: str = "fwd"):
    """Run a fused semiring fixpoint on the segment backend.

    ``arrays``/``params`` are dicts of traced edge arrays / scalars;
    ``setup(A, P, n_out) -> env`` precomputes loop invariants (and may
    provide ``env["x0"]`` when `x0` is None); ``step(x, A, env, P,
    n_out) -> acc`` overrides the default matvec (multi-matvec bodies
    like HITS or labelprop's election); ``epilogue(x, acc, env, P) ->
    (new_x, metric)`` is the fused update + convergence partial.
    ``metric="err"`` iterates while ``metric > P["tol"]``;
    ``metric="changed"`` while the bool metric holds.

    Returns (x, metric, iterations).  Compiled programs are cached per
    (algorithm hooks, shapes) — repeated calls pay tracing once.
    """
    from ..utils.jax_cache import ensure_compile_cache
    from ..observability import trace as mgtrace
    ensure_compile_cache()
    sr = resolve_semiring(sr)
    _check_precision(precision)
    params = params or {}
    key = (sr.name, epilogue, setup, step, int(n_out),
           int(max_iterations), metric, precision, bool(sorted),
           bool(sorted_backward), direction, tuple(sorted_keys(arrays)),
           tuple(sorted_keys(params)), x0 is None)
    fn = _FIXPOINT_CACHE.get(key)
    if fn is None:
        with _fixpoint_cache_lock:
            fn = _FIXPOINT_CACHE.get(key)
            if fn is None:
                fn = _build_fixpoint(
                    sr, epilogue=epilogue, setup=setup, step=step,
                    n_out=n_out, max_iterations=max_iterations,
                    metric=metric, precision=precision, sorted=sorted,
                    sorted_backward=sorted_backward, direction=direction)
                _FIXPOINT_CACHE[key] = fn
    # the jitted call returns at enqueue: the caller's readback blocks
    with mgtrace.span("device.chunk", backend="segment") as sp:
        out = fn(arrays, params, x0)
        if sp:
            sp.set(semiring=sr.name, precision=precision)
    return out


def sorted_keys(d):
    return sorted(d) if d else ()


# --------------------------------------------------------------------------
# shared update rules (one definition; every backend folds onto it)
# --------------------------------------------------------------------------


def pagerank_update(acc, dangling_mass, valid, n_f, damping):
    """THE PageRank damping update — shared by the segment kernel, the
    MXU kernel (spmv_mxu), the sharded MXU kernel (spmv_mxu_sharded)
    and the partition-centric mesh kernel (parallel/distributed), so
    the formula exists exactly once in the tree."""
    return valid * ((1.0 - damping) / n_f
                    + damping * (acc + dangling_mass / n_f))


# --------------------------------------------------------------------------
# backend routing
# --------------------------------------------------------------------------

#: Above this edge count the gather-free MXU formulation (ops/spmv_mxu.py)
#: wins despite its host-side plan build; below it the segment kernel's
#: zero setup cost wins. Plan+kernel are cached on the DeviceGraph
#: snapshot, so repeated CALLs on an unchanged graph pay the build once.
MXU_MIN_EDGES = int(os.environ.get("MEMGRAPH_TPU_MXU_MIN_EDGES", 500_000))


def route_backend(graph, mesh=None, *, semiring="plus_times",
                  precision: str = "f32", min_edges: int | None = None):
    """Resolve which backend a core-routed algorithm runs on.

    Returns ("mesh", MeshContext) | ("mxu", None) | ("segment", None).
    The MXU plan's reduce/extract phase is a one-hot matmul — a SUM —
    so only ⊕ = sum semirings ride it; int8 streaming stays on the
    segment backend (the Benes route dtype is f32/bf16).
    """
    import jax
    from ..parallel.mesh import resolve_mesh
    _check_precision(precision)
    ctx = resolve_mesh(mesh)
    if ctx is not None:
        return "mesh", ctx
    sr = resolve_semiring(semiring)
    if min_edges is None:
        min_edges = MXU_MIN_EDGES
    if (sr.add == "sum" and precision != "int8"
            and graph.n_edges >= min_edges
            and (jax.default_backend() != "cpu"
                 or os.environ.get("MEMGRAPH_TPU_FORCE_MXU"))):
        return "mxu", None
    return "segment", None


def backend_extent(backend: str):
    """One mesh / streamed backend dispatch as a ``device.route`` span:
    PROFILE of a core-routed query shows time per backend
    (``semiring_mesh`` / ``semiring_streamed``; the chunks inside are
    the checkpoint runner's, which records compile and iterate itself).
    The segment and MXU dispatches are ``device.chunk`` spans at their
    own sites."""
    from ..observability import trace as mgtrace
    return mgtrace.span("device.route", backend=backend)


# --------------------------------------------------------------------------
# generalized MXU backend (semiring-parameterized plan cache)
# --------------------------------------------------------------------------

_mxu_plan_guard = threading.Lock()


def mxu_fixpoint(graph, *, epilogue, params, max_iterations, tol,
                 normalize: bool = True, precision: str = "f32",
                 cache_tag: str = "generic", x0_default: str = "zeros",
                 x0=None):
    """Run a ⊕ = sum fixpoint on the gather-free MXU backend.

    Builds (or reuses, cached on the immutable DeviceGraph snapshot) a
    `spmv_mxu` plan with ``normalize=True`` baking w/out-weight-sum
    multipliers (the stochastic matrix pagerank iterates) or plain w
    (katz's A^T), then runs `make_semiring_kernel` with the given fused
    epilogue.  Returns (x_original_ids, err, iters).

    ``x0`` — optional (n_nodes,) warm-start seed in ORIGINAL node ids
    (ops/delta.py commit-then-CALL); mapped into the plan's OUT
    labeling before dispatch. None keeps the on-device default start
    (``x0_default``), which saves the host->device transfer."""
    import jax.numpy as jnp
    from . import spmv_mxu
    _check_precision(precision)
    if precision == "int8":
        raise ValueError("the MXU backend routes f32/bf16 only; int8 "
                         "streaming rides the segment backend")
    key = (cache_tag, bool(normalize), precision, epilogue, x0_default)
    cache = getattr(graph, "_mxu_semiring", None)
    if cache is None or key not in cache:
        with _mxu_plan_guard:
            cache = getattr(graph, "_mxu_semiring", None)
            if cache is None:
                cache = {}
                object.__setattr__(graph, "_mxu_semiring", cache)
            if key not in cache:
                plan_key = ("plan", cache_tag, bool(normalize))
                plan = cache.get(plan_key)
                if plan is None:
                    src = np.asarray(graph.src_idx)[:graph.n_edges]
                    dst = np.asarray(graph.col_idx)[:graph.n_edges]
                    w = np.asarray(graph.weights)[:graph.n_edges]
                    plan = spmv_mxu.build_plan(src, dst, w,
                                               graph.n_nodes,
                                               normalize=normalize)
                    cache[plan_key] = plan
                route_dtype = (jnp.bfloat16 if precision == "bf16"
                               else jnp.float32)
                cache[key] = (plan, spmv_mxu.make_semiring_kernel(
                    plan, epilogue=epilogue, route_dtype=route_dtype,
                    x0_default=x0_default))
    plan, run = cache[key]
    x0_flat = None
    if x0 is not None:
        x0_flat = np.zeros(len(plan.valid_out), dtype=np.float32)
        x0_flat[plan.out_relabel] = \
            np.asarray(x0, dtype=np.float32)[:graph.n_nodes]
    from ..observability import trace as mgtrace
    # the readback is inside: the chunk ends in a block
    with mgtrace.span("device.chunk", backend="mxu"):
        x, err, iters = run(x0_flat, params, int(max_iterations),
                            np.float32(tol))
        return np.asarray(x)[plan.out_relabel], float(err), int(iters)
