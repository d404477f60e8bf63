"""The mgxla compiled-artifact contract checker.

For every kernel in :data:`tools.mgxla.manifest.MANIFEST` a registered
``@builder`` abstractly lowers the REAL product builder —
``jax.jit(...).lower(...)`` on ``ShapeDtypeStruct``s over a forced
8-device mesh; nothing executes — and the post-optimization HLO is
verified against the kernel's contract:

  * exact collective multiset, and (for iterating kernels) every
    collective located inside the while body — the generalization of
    the regex assertions tests/test_sharded_analytics.py carried
    before r17 (those tests now call this module as a library);
  * zero f64/c128 ops (nothing silently upcasts out of the
    mixed-precision streaming envelope);
  * zero host callbacks / infeed / outfeed (no host round trip hides
    inside a compiled hot path);
  * input-output aliasing of fixpoint carries (``min_donated``);
  * the PPR lane-bucket compile budget: batch widths 1..128 must fold
    onto exactly the declared bucket set (same bucket ⇒ cache hit — a
    silent recompile per width would melt the serving plane's latency).

Violations carry the offending HLO snippet. Deliberate exceptions go in
``tools/mgxla/baseline.json`` with a justification (mglint's format);
unused or unexplained entries fail, so the baseline only shrinks
honestly. The static budget's runtime witness is the
``jit.compile_total`` counter (utils/jax_cache.py) exported in
``GET /stats``.
"""

from __future__ import annotations

import os
import sys
import threading
from contextlib import contextmanager
from dataclasses import dataclass, field

from . import hlo
from .manifest import (BACKENDS, MANIFEST, PPR_LANE_BUCKETS,
                       KernelContract, load_baseline,
                       manifest_registry_keys)

#: the forced virtual mesh width every mesh contract lowers against
N_SHARDS = 8
#: abstract graph shapes (values never matter — nothing executes).
#: These are module globals ON PURPOSE: builders read them at call
#: time, so :func:`build_compiled` can rebind them per shape point and
#: the same builder registry serves both the contract checker (one
#: canonical point) and tools/mgmem's footprint-model fitter (several).
N_PAD = 64
N_EDGES = 256
BLOCK = N_PAD // N_SHARDS
PER = 32            # edges per shard in the partition-centric layout


@dataclass(frozen=True)
class Dims:
    """One abstract lowering shape point. ``n_pad`` must be a multiple
    of the forced mesh width (block = n_pad // N_SHARDS); ``per`` is
    the per-shard edge capacity (defaults to n_edges / N_SHARDS)."""

    n_pad: int = 64
    n_edges: int = 256
    per: int = 0

    def __post_init__(self):
        if self.n_pad % N_SHARDS:
            raise ValueError(f"n_pad={self.n_pad} must be a multiple "
                             f"of the {N_SHARDS}-wide mesh")
        if not self.per:
            object.__setattr__(self, "per",
                               max(8, self.n_edges // N_SHARDS))


DEFAULT_DIMS = Dims()

_dims_lock = threading.Lock()


@contextmanager
def _shape_dims(dims: Dims):
    """Rebind the module shape globals for one builder call."""
    global N_PAD, N_EDGES, BLOCK, PER
    old = (N_PAD, N_EDGES, BLOCK, PER)
    N_PAD, N_EDGES, PER = dims.n_pad, dims.n_edges, dims.per
    BLOCK = N_PAD // N_SHARDS
    try:
        yield
    finally:
        N_PAD, N_EDGES, BLOCK, PER = old


class CheckerEnvironmentError(RuntimeError):
    """The process cannot host the forced multi-device mesh."""


@dataclass(frozen=True)
class Violation:
    kernel: str
    check: str          # collectives|while-collectives|f64|host-callback|
    #                     donation|coverage|lane-buckets|build
    detail: str
    snippet: str = ""

    @property
    def key(self) -> str:
        return f"{self.kernel}:{self.check}:{self.detail}"

    def render(self) -> str:
        out = f"{self.kernel}: {self.check}: {self.detail}"
        if self.snippet:
            out += "\n    | " + self.snippet.replace("\n", "\n    | ")
        return out


@dataclass
class CheckReport:
    violations: list = field(default_factory=list)    # unbaselined
    baselined: list = field(default_factory=list)
    unused_baseline: list = field(default_factory=list)
    kernels_checked: int = 0

    @property
    def ok(self) -> bool:
        return not self.violations and not self.unused_baseline

    def render(self) -> str:
        lines = [f"mgxla: {self.kernels_checked} kernels checked"]
        for v in self.violations:
            lines.append("VIOLATION " + v.render())
        for v in self.baselined:
            lines.append("baselined " + v.render().splitlines()[0])
        for key in self.unused_baseline:
            lines.append(f"UNUSED baseline entry (fixed or drifted): "
                         f"{key}")
        lines.append("mgxla: " + ("OK" if self.ok else "FAILED"))
        return "\n".join(lines)


# --------------------------------------------------------------------------
# builders: kernel id -> compiled executable (abstract lowering only)
# --------------------------------------------------------------------------

BUILDERS: dict = {}


def builder(*kernels):
    def deco(fn):
        for k in kernels:
            BUILDERS[k] = fn
        return fn
    return deco


def _jax():
    import jax
    return jax


def _sds(shape, dtype):
    import jax
    import jax.numpy as jnp
    return jax.ShapeDtypeStruct(shape, getattr(jnp, dtype))


def _ctx():
    import jax
    if len(jax.devices()) < N_SHARDS:
        raise CheckerEnvironmentError(
            f"mgxla needs {N_SHARDS} devices for the forced mesh; "
            f"this process has {len(jax.devices())}. Run via "
            "`python -m tools.mgxla` (it sets "
            "--xla_force_host_platform_device_count before jax loads) "
            "or export XLA_FLAGS yourself.")
    from memgraph_tpu.parallel.mesh import get_mesh_context
    return get_mesh_context(N_SHARDS)


def _compiled(lowered):
    """Compile an abstract lowering. Returns the COMPILED executable —
    ``as_text()`` feeds the contract checks, ``memory_analysis()``
    feeds tools/mgmem's footprint model; both read the same artifact."""
    return lowered.compile()


def build_compiled(kernel: str, dims: Dims | None = None):
    """Compiled executable for one manifest kernel at abstract `dims`.

    ``dims=None`` lowers at the canonical contract-checker shapes.
    mxu:* kernels carry a fixed internal plan and ignore `dims`.
    Raises KeyError for kernels without a registered builder."""
    build = BUILDERS[kernel]
    if dims is None or dims == DEFAULT_DIMS:
        return build(kernel)
    with _dims_lock, _shape_dims(dims):
        return build(kernel)


# ---- partition-centric mesh kernels ---------------------------------------


def _mesh_pagerank(precision: str):
    from memgraph_tpu.parallel.distributed import _pc_pagerank_build
    fn = _pc_pagerank_build(_ctx(), BLOCK, N_SHARDS, precision)
    ep, vp = (N_SHARDS, PER), (N_SHARDS * BLOCK,)
    return _compiled(fn.lower(
        _sds(ep, "int32"), _sds(ep, "int32"), _sds(ep, "float32"),
        _sds((), "int32"), _sds((), "float32"), _sds((), "float32"),
        _sds(vp, "float32"), _sds((N_SHARDS,), "float32"),
        _sds((N_SHARDS,), "float32"), _sds((), "int32"), _sds((), "int32")))


@builder("mesh:pagerank")
def _b_mesh_pagerank(kernel):
    return _mesh_pagerank("f32")


@builder("mesh:pagerank_bf16")
def _b_mesh_pagerank_bf16(kernel):
    return _mesh_pagerank("bf16")


@builder("mesh:katz")
def _b_mesh_katz(kernel):
    from memgraph_tpu.parallel.distributed import _pc_katz_build
    fn = _pc_katz_build(_ctx(), BLOCK, N_SHARDS)
    ep = (N_SHARDS, PER)
    return _compiled(fn.lower(
        _sds(ep, "int32"), _sds(ep, "int32"), _sds(ep, "float32"),
        _sds((), "int32"), _sds((), "float32"), _sds((), "float32"),
        _sds((), "float32"), _sds((N_SHARDS * BLOCK,), "float32"),
        _sds((), "float32"), _sds((), "int32"), _sds((), "int32")))


@builder("mesh:labelprop")
def _b_mesh_labelprop(kernel):
    from memgraph_tpu.parallel.distributed import _pc_labelprop_build
    fn = _pc_labelprop_build(_ctx(), BLOCK, N_SHARDS, PER)
    ep = (N_SHARDS, PER)
    return _compiled(fn.lower(
        _sds(ep, "int32"), _sds(ep, "int32"), _sds(ep, "float32"),
        _sds((), "float32"), _sds((N_SHARDS * BLOCK,), "int32"),
        _sds((), "bool_"), _sds((), "int32"), _sds((), "int32")))


@builder("mesh:wcc")
def _b_mesh_wcc(kernel):
    from memgraph_tpu.parallel.distributed import _pc_wcc_build
    fn = _pc_wcc_build(_ctx(), BLOCK, N_SHARDS)
    ep = (N_SHARDS, PER)
    return _compiled(fn.lower(
        _sds(ep, "int32"), _sds(ep, "int32"),
        _sds((N_SHARDS * BLOCK,), "int32"), _sds((), "bool_"),
        _sds((), "int32"), _sds((), "int32")))


@builder("mesh:semiring_min_plus")
def _b_mesh_semiring(kernel):
    from memgraph_tpu.parallel.distributed import (
        _minplus_relax_epilogue, _pc_semiring_build)
    fn = _pc_semiring_build(_ctx(), BLOCK, N_SHARDS, "min_plus",
                            _minplus_relax_epilogue, "changed", "f32")
    ep = (N_SHARDS, PER)
    return _compiled(fn.lower(
        _sds(ep, "int32"), _sds(ep, "int32"), _sds(ep, "float32"),
        {}, _sds((N_SHARDS * BLOCK,), "float32"), _sds((), "bool_"),
        _sds((), "int32"), _sds((), "int32")))


# ---- out-of-core streamed tier (r21, mgtier) -------------------------------


def _tier_block(precision: str = "f32"):
    """Abstract wire block: the u16-compressed payload pack_block ships
    (ops/tier.py). P = N_SHARDS blocks of BLOCK rows, PER edges each."""
    wdt = {"f32": "float32", "bf16": "bfloat16", "int8": "int8"}
    out = {"rc": _sds((), "int32"),
           "src_off": _sds((PER,), "uint16"),
           "dst_off": _sds((PER,), "uint16"),
           "bounds": _sds((N_SHARDS + 1,), "int32"),
           "base": _sds((), "int32"),
           "w": _sds((PER,), wdt[precision])}
    if precision == "int8":
        out["scale"] = _sds((), "float32")
    return out


def _tier_v(dtype: str = "float32"):
    return _sds((N_PAD,), dtype)


@builder("tier:wsum")
def _b_tier_wsum(kernel):
    from memgraph_tpu.parallel.distributed import _tier_wsum_build
    fn = _tier_wsum_build(BLOCK, PER, N_PAD, "f32", True)
    return _compiled(fn.lower(_tier_v(), _tier_block()))


def _tier_pr_sweep(precision: str):
    from memgraph_tpu.parallel.distributed import (
        _tier_pagerank_sweep_build)
    fn = _tier_pagerank_sweep_build(BLOCK, PER, N_PAD, precision, True)
    return _compiled(fn.lower(
        _tier_v(), _tier_v(), _tier_v(), _tier_block(precision)))


@builder("tier:pagerank_sweep")
def _b_tier_pr_sweep(kernel):
    return _tier_pr_sweep("f32")


@builder("tier:pagerank_sweep_int8")
def _b_tier_pr_sweep_int8(kernel):
    return _tier_pr_sweep("int8")


@builder("tier:pagerank_epilogue")
def _b_tier_pr_epi(kernel):
    from memgraph_tpu.parallel.distributed import (
        _tier_pagerank_epilogue_build)
    fn = _tier_pagerank_epilogue_build(N_PAD)
    return _compiled(fn.lower(
        _tier_v(), _tier_v(), _tier_v(), _tier_v(),
        _sds((), "float32"), _sds((), "float32")))


@builder("tier:katz_sweep")
def _b_tier_katz_sweep(kernel):
    from memgraph_tpu.parallel.distributed import _tier_katz_sweep_build
    fn = _tier_katz_sweep_build(BLOCK, PER, N_PAD, "f32", True)
    return _compiled(fn.lower(_tier_v(), _tier_v(), _tier_block()))


@builder("tier:katz_epilogue")
def _b_tier_katz_epi(kernel):
    from memgraph_tpu.parallel.distributed import (
        _tier_katz_epilogue_build)
    fn = _tier_katz_epilogue_build(N_PAD)
    return _compiled(fn.lower(
        _tier_v(), _tier_v(), _tier_v(),
        _sds((), "float32"), _sds((), "float32")))


@builder("tier:wcc_sweep")
def _b_tier_wcc_sweep(kernel):
    from memgraph_tpu.parallel.distributed import _tier_wcc_sweep_build
    fn = _tier_wcc_sweep_build(BLOCK, PER, N_PAD, True)
    return _compiled(fn.lower(
        _tier_v("int32"), _tier_v("int32"), _tier_block()))


@builder("tier:wcc_epilogue")
def _b_tier_wcc_epi(kernel):
    from memgraph_tpu.parallel.distributed import (
        _tier_wcc_epilogue_build)
    fn = _tier_wcc_epilogue_build(N_PAD)
    return _compiled(fn.lower(_tier_v("int32"), _tier_v("int32")))


# ---- segment backend -------------------------------------------------------


def _segment_fixpoint(sr, *, arrays, params, x0, epilogue, setup=None,
                      step=None, metric="err", sorted=False,
                      sorted_backward=False, direction="fwd"):
    from memgraph_tpu.ops import semiring as S
    fn = S._build_fixpoint(
        S.resolve_semiring(sr), epilogue=epilogue, setup=setup, step=step,
        n_out=N_PAD, max_iterations=8, metric=metric, precision="f32",
        sorted=sorted, sorted_backward=sorted_backward,
        direction=direction)
    return _compiled(fn.lower(arrays, params, x0))


def _edge_arrays(w: bool = True, csr: bool = False):
    out = {"src": _sds((N_EDGES,), "int32"),
           "dst": _sds((N_EDGES,), "int32")}
    if w:
        out["w"] = _sds((N_EDGES,), "float32")
    if csr:
        out["csr_src"] = _sds((N_EDGES,), "int32")
        out["csr_w"] = _sds((N_EDGES,), "float32")
    return out


@builder("segment:pagerank")
def _b_seg_pagerank(kernel):
    from memgraph_tpu.ops.pagerank import (_pagerank_epilogue,
                                           _pagerank_setup)
    return _segment_fixpoint(
        "plus_times", arrays=_edge_arrays(csr=True),
        params={"n_nodes": _sds((), "int32"),
                "damping": _sds((), "float32"),
                "tol": _sds((), "float32")},
        x0=None, setup=_pagerank_setup, epilogue=_pagerank_epilogue,
        sorted=True)


@builder("segment:pagerank_warm")
def _b_seg_pagerank_warm(kernel):
    # r19 mgdelta: the commit-then-CALL warm start — identical program
    # modulo the donated x0 seed argument
    from memgraph_tpu.ops.pagerank import (_pagerank_epilogue,
                                           _pagerank_setup)
    return _segment_fixpoint(
        "plus_times", arrays=_edge_arrays(csr=True),
        params={"n_nodes": _sds((), "int32"),
                "damping": _sds((), "float32"),
                "tol": _sds((), "float32")},
        x0=_sds((N_PAD,), "float32"), setup=_pagerank_setup,
        epilogue=_pagerank_epilogue, sorted=True)


@builder("segment:katz_warm")
def _b_seg_katz_warm(kernel):
    from memgraph_tpu.ops.katz import _katz_epilogue, _katz_setup
    return _segment_fixpoint(
        "plus_times", arrays=_edge_arrays(),
        params={"n_nodes": _sds((), "int32"),
                "alpha": _sds((), "float32"),
                "beta": _sds((), "float32"),
                "tol": _sds((), "float32")},
        x0=_sds((N_PAD,), "float32"), setup=_katz_setup,
        epilogue=_katz_epilogue, sorted=True)


@builder("segment:ppr")
def _b_seg_ppr(kernel):
    from memgraph_tpu.ops.pagerank import _ppr_epilogue, _ppr_setup
    arrays = _edge_arrays(csr=True)
    arrays["personalization"] = _sds((N_PAD,), "float32")
    return _segment_fixpoint(
        "plus_times", arrays=arrays,
        params={"n_nodes": _sds((), "int32"),
                "damping": _sds((), "float32"),
                "tol": _sds((), "float32")},
        x0=None, setup=_ppr_setup, epilogue=_ppr_epilogue, sorted=True)


@builder("segment:katz")
def _b_seg_katz(kernel):
    from memgraph_tpu.ops.katz import _katz_epilogue, _katz_setup
    return _segment_fixpoint(
        "plus_times", arrays=_edge_arrays(),
        params={"n_nodes": _sds((), "int32"),
                "alpha": _sds((), "float32"),
                "beta": _sds((), "float32"),
                "tol": _sds((), "float32")},
        x0=None, setup=_katz_setup, epilogue=_katz_epilogue, sorted=True)


@builder("segment:hits")
def _b_seg_hits(kernel):
    from memgraph_tpu.ops.katz import (_hits_epilogue, _hits_setup,
                                       _hits_step)
    arrays = _edge_arrays()
    arrays.update(csrc=_sds((N_EDGES,), "int32"),
                  cdst=_sds((N_EDGES,), "int32"),
                  cw=_sds((N_EDGES,), "float32"))
    return _segment_fixpoint(
        "plus_times", arrays=arrays,
        params={"n_nodes": _sds((), "int32"),
                "tol": _sds((), "float32")},
        x0=None, setup=_hits_setup, step=_hits_step,
        epilogue=_hits_epilogue)


@builder("segment:labelprop")
def _b_seg_labelprop(kernel):
    from memgraph_tpu.ops.labelprop import (_labelprop_epilogue,
                                            _labelprop_step)
    return _segment_fixpoint(
        "max_min", arrays=_edge_arrays(),
        params={"self_weight": _sds((), "float32")},
        x0=_sds((N_PAD,), "int32"), step=_labelprop_step,
        epilogue=_labelprop_epilogue, metric="changed")


@builder("segment:wcc")
def _b_seg_wcc(kernel):
    from memgraph_tpu.ops.components import _wcc_epilogue
    return _segment_fixpoint(
        "min_first", arrays=_edge_arrays(w=False), params={},
        x0=_sds((N_PAD,), "int32"), epilogue=_wcc_epilogue,
        metric="changed", direction="both")


@builder("segment:sssp")
def _b_seg_sssp(kernel):
    from memgraph_tpu.ops.traversal import (_sssp_epilogue,
                                            _sssp_step_directed)
    return _segment_fixpoint(
        "min_plus", arrays=_edge_arrays(),
        params={}, x0=_sds((N_PAD,), "float32"),
        step=_sssp_step_directed, epilogue=_sssp_epilogue,
        metric="changed")


@builder("segment:bfs")
def _b_seg_bfs(kernel):
    from memgraph_tpu.ops.traversal import _bfs_epilogue, _bfs_step
    arrays = _edge_arrays()
    arrays["deg"] = _sds((N_PAD,), "float32")
    return _segment_fixpoint(
        "min_plus", arrays=arrays,
        params={"n_edges": _sds((), "float32")},
        x0=(_sds((N_PAD,), "float32"), _sds((N_PAD,), "bool_")),
        step=_bfs_step, epilogue=_bfs_epilogue, metric="changed")


@builder("segment:scc")
def _b_seg_scc(kernel):
    from memgraph_tpu.ops.components import _scc_round
    return _compiled(_scc_round.lower(
        _sds((N_EDGES,), "int32"), _sds((N_EDGES,), "int32"),
        _sds((N_PAD,), "int32"), n_pad=N_PAD, max_iterations=8))


@builder("segment:betweenness")
def _b_seg_betweenness(kernel):
    from memgraph_tpu.ops.betweenness import _brandes_chunk
    return _compiled(_brandes_chunk.lower(
        _sds((N_EDGES,), "int32"), _sds((N_EDGES,), "int32"),
        _sds((N_EDGES,), "bool_"), _sds((4,), "int32"),
        _sds((4,), "float32"), n_pad=N_PAD, max_levels=8))


@builder("segment:gnn")
def _b_seg_gnn(kernel):
    import jax
    from memgraph_tpu.ops.gnn import init_sage_params, sage_forward
    params = init_sage_params(jax.random.PRNGKey(0), 8, 16, 8)
    psds = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params)
    fn = jax.jit(sage_forward, static_argnames=("n_pad",))
    return _compiled(fn.lower(
        psds, _sds((N_PAD, 8), "float32"), _sds((N_EDGES,), "int32"),
        _sds((N_EDGES,), "int32"), n_pad=N_PAD))


# ---- MXU backend -----------------------------------------------------------


def _mxu_plan():
    import numpy as np
    from memgraph_tpu.ops import spmv_mxu
    rng = np.random.default_rng(7)
    n, e = 48, 160
    src = rng.integers(0, n, e).astype(np.int64)
    dst = rng.integers(0, n, e).astype(np.int64)
    w = rng.random(e).astype(np.float32)
    return spmv_mxu.build_plan(src, dst, w, n)


def _mxu_lower(run, params_sds):
    # make_semiring_kernel attaches the inner jitted program + the
    # device blob exactly so this checker can lower without executing
    jd, blob = run.jitted_default, run.blob
    return _compiled(jd.lower(
        _sds(tuple(blob.shape), str(blob.dtype)), params_sds, 8,
        _sds((), "float32")))


@builder("mxu:pagerank")
def _b_mxu_pagerank(kernel):
    from memgraph_tpu.ops import spmv_mxu
    run = spmv_mxu.make_semiring_kernel(
        _mxu_plan(), epilogue=spmv_mxu.pagerank_mxu_epilogue,
        x0_default="uniform")
    return _mxu_lower(run, {"damping": _sds((), "float32")})


@builder("mxu:katz")
def _b_mxu_katz(kernel):
    from memgraph_tpu.ops import spmv_mxu
    from memgraph_tpu.ops.katz import _katz_mxu_epilogue
    run = spmv_mxu.make_semiring_kernel(
        _mxu_plan(), epilogue=_katz_mxu_epilogue, x0_default="zeros")
    return _mxu_lower(run, {"alpha": _sds((), "float32"),
                            "beta": _sds((), "float32")})


# ---- PPR serving-plane lane buckets ---------------------------------------


def _ppr_batch_text(bucket: int, warm: bool):
    from memgraph_tpu.ops.pagerank import _build_ppr_batch
    fn = _build_ppr_batch(N_PAD, 8, "f32", warm)
    arrays = _edge_arrays(csr=True)
    arrays["personalization"] = _sds((N_PAD, bucket), "float32")
    x0 = _sds((N_PAD, bucket), "float32") if warm else None
    return _compiled(fn.lower(
        arrays, {"n_nodes": _sds((), "int32"),
                 "damping": _sds((), "float32"),
                 "tol": _sds((), "float32")}, x0))


def _make_bucket_builder(bucket: int):
    @builder(f"segment:ppr_batch:b{bucket}")
    def _b(kernel, _bucket=bucket):
        return _ppr_batch_text(_bucket, warm=False)
    return _b


for _bucket in PPR_LANE_BUCKETS:
    _make_bucket_builder(_bucket)


@builder("segment:ppr_batch:warm8")
def _b_ppr_warm(kernel):
    return _ppr_batch_text(8, warm=True)


# ---- compiled Cypher read lane (r20, mglane) ------------------------------


@builder("segment:lane_agg")
def _b_lane_agg(kernel):
    from memgraph_tpu.ops.pipeline import _build_agg_program
    fn = _build_agg_program(
        preds=((0, ">"), (1, "=")),
        aggs=(("count", None), ("sum", 0), ("min", 0), ("max", 1)))
    return _compiled(fn.lower(
        _sds((2, N_PAD), "int32"), _sds((2, N_PAD), "bool_"),
        _sds((N_PAD,), "bool_"), _sds((2,), "int32")))


def _lane_hops_text(hops: int):
    from memgraph_tpu.ops.pipeline import _build_hops_program
    fn = _build_hops_program(hops, False, True, True, hops == 2, N_PAD)
    return _compiled(fn.lower(
        _sds((N_EDGES,), "int32"), _sds((N_EDGES,), "int32"),
        _sds((N_EDGES,), "bool_"), _sds((N_PAD,), "bool_"),
        _sds((N_PAD,), "float32"), _sds((N_PAD,), "float32")))


@builder("segment:lane_hops:h1")
def _b_lane_hops1(kernel):
    return _lane_hops_text(1)


@builder("segment:lane_hops:h2")
def _b_lane_hops2(kernel):
    return _lane_hops_text(2)


@builder("segment:lane_topk")
def _b_lane_topk(kernel):
    from memgraph_tpu.ops.pipeline import _build_topk_program
    fn = _build_topk_program(preds=((0, ">"),), ascending=False)
    return _compiled(fn.lower(
        _sds((1, N_PAD), "int32"), _sds((1, N_PAD), "bool_"),
        _sds((N_PAD,), "int32"), _sds((N_PAD,), "bool_"),
        _sds((1,), "int32")))


# --------------------------------------------------------------------------
# contract checks
# --------------------------------------------------------------------------


def check_text(contract: KernelContract, text: str) -> list[Violation]:
    """Verify one compiled artifact against its contract."""
    facts = hlo.analyze(text)
    out: list[Violation] = []
    got = tuple(facts.collectives)
    want = tuple(sorted(contract.collectives))
    if got != want:
        pat = "|".join(hlo.COLLECTIVE_OPS)
        out.append(Violation(
            contract.kernel, "collectives",
            f"got={','.join(got) or 'none'} want={','.join(want) or 'none'}",
            hlo.snippet_around(text, pat)))
    elif want and contract.iterates:
        in_body = tuple(facts.while_collectives)
        if in_body != want:
            out.append(Violation(
                contract.kernel, "while-collectives",
                f"in-body={','.join(in_body) or 'none'} "
                f"want={','.join(want)}",
                hlo.snippet_around(text, "|".join(hlo.COLLECTIVE_OPS))))
    if facts.f64:
        out.append(Violation(contract.kernel, "f64",
                             f"{len(facts.f64)} double-precision ops",
                             facts.f64[0]))
    if facts.callbacks:
        out.append(Violation(contract.kernel, "host-callback",
                             f"{len(facts.callbacks)} host round-trips",
                             facts.callbacks[0]))
    if len(facts.donated) < contract.min_donated:
        out.append(Violation(
            contract.kernel, "donation",
            f"donated={len(facts.donated)} < min={contract.min_donated}",
            hlo.snippet_around(text, r"^HloModule")))
    return out


def check_kernel_by_id(kernel: str) -> list[Violation]:
    """Build + check one manifest kernel (library entry for tests)."""
    contract = MANIFEST[kernel]
    if kernel not in BUILDERS:
        return [Violation(kernel, "build", "no registered builder")]
    try:
        text = build_compiled(kernel).as_text()
    except CheckerEnvironmentError:
        raise
    except Exception as e:  # noqa: BLE001 — reported as a typed violation
        return [Violation(kernel, "build",
                          f"{type(e).__name__}: {e}")]
    return check_text(contract, text)


def check_lane_buckets() -> list[Violation]:
    """The compile-count budget across PPR lane buckets, statically:
    widths 1..128 fold onto exactly the declared bucket set (same bucket
    ⇒ same compiled program), every bucket has a manifest row, and the
    manifest mirror equals the product's bucket table."""
    from memgraph_tpu.ops.pagerank import _PPR_LANE_BUCKETS, _bucket_lanes
    out: list[Violation] = []
    if tuple(_PPR_LANE_BUCKETS) != tuple(PPR_LANE_BUCKETS):
        out.append(Violation(
            "lane-buckets", "lane-buckets",
            f"manifest mirror {PPR_LANE_BUCKETS} != product table "
            f"{tuple(_PPR_LANE_BUCKETS)}"))
        return out
    mapped = {b: _bucket_lanes(b) for b in range(1, 129)}
    distinct = sorted(set(mapped.values()))
    if distinct != sorted(PPR_LANE_BUCKETS):
        out.append(Violation(
            "lane-buckets", "lane-buckets",
            f"widths 1..128 compile {len(distinct)} distinct programs "
            f"{distinct}; budget is {sorted(PPR_LANE_BUCKETS)}"))
    bad = [b for b, cap in mapped.items() if cap < b]
    if bad:
        out.append(Violation(
            "lane-buckets", "lane-buckets",
            f"bucket smaller than batch for widths {bad[:4]} — lanes "
            "would be dropped"))
    for b in PPR_LANE_BUCKETS:
        if f"segment:ppr_batch:b{b}" not in MANIFEST:
            out.append(Violation(
                "lane-buckets", "coverage",
                f"bucket {b} has no manifest kernel"))
    return out


def check_coverage() -> list[Violation]:
    """Registry/backend coverage: every SPMV_ALGORITHMS entry covered,
    every declared registry key real, all three backends present, every
    sharded target contract-checked on the mesh backend."""
    from memgraph_tpu.ops import SPMV_ALGORITHMS
    out: list[Violation] = []
    covered = manifest_registry_keys()
    for name in SPMV_ALGORITHMS:
        if name not in covered:
            out.append(Violation(
                "coverage", "coverage",
                f"registry entry {name!r} has no manifest kernel"))
    for name in sorted(covered - set(SPMV_ALGORITHMS)):
        out.append(Violation(
            "coverage", "coverage",
            f"manifest names unknown registry entry {name!r}"))
    have_backends = {c.backend for c in MANIFEST.values()}
    for b in BACKENDS:
        if b not in have_backends:
            out.append(Violation(
                "coverage", "coverage",
                f"backend {b!r} has no contract-checked kernel"))
    mesh_covered = set()
    for c in MANIFEST.values():
        if c.backend == "mesh":
            mesh_covered.update(c.registry)
    for name, entry in SPMV_ALGORITHMS.items():
        if "sharded" in entry and name not in mesh_covered:
            out.append(Violation(
                "coverage", "coverage",
                f"{name!r} declares a sharded target but no mesh "
                "kernel contract covers it"))
    return out


def run_check(only=None, baseline: dict | None = None,
              structural: bool = True) -> CheckReport:
    """Check the full manifest (or `only` kernels). Returns a report
    with baseline applied; `report.ok` is the gate verdict."""
    if baseline is None:
        baseline = load_baseline()
    report = CheckReport()
    kernels = [k for k in sorted(MANIFEST)
               if only is None or k in only]
    found: list[Violation] = []
    for kernel in kernels:
        found.extend(check_kernel_by_id(kernel))
        report.kernels_checked += 1
    if structural:
        found.extend(check_coverage())
        found.extend(check_lane_buckets())
    seen = set()
    for v in found:
        seen.add(v.key)
        if v.key in baseline:
            report.baselined.append(v)
        else:
            report.violations.append(v)
    if only is None:
        report.unused_baseline = sorted(k for k in baseline
                                        if k not in seen)
    return report
