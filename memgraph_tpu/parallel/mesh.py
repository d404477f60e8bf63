"""Device mesh + sharding layer: built once, shared by every sharded kernel.

This is the single place the analytics stack learns about devices. It
provides:

  * `shard_map_fn()` — the one name every sharded kernel takes
    `jax.shard_map` through (replication checking stays on).
  * `MeshContext` — a mesh plus its canonical `NamedSharding`s
    (replicated / edge-blocked / vertex-blocked), built once per
    (device-count, axis) and cached, so kernels never re-derive
    PartitionSpecs ad hoc. The single-device case is a mesh-of-1
    context, NOT a separate code path: `psum` over a 1-device axis is a
    no-op copy and every sharded kernel degenerates correctly.
  * `analytics_mesh()` — the process-wide default mesh the `ops/`
    algorithms route through, controlled by MEMGRAPH_TPU_MESH_DEVICES
    ("all", or an integer; unset → no mesh routing, the classic
    single-chip kernels run).

SNIPPETS [2]/[3] are the exemplars: canonical PartitionSpecs live in one
frozen layout object; call sites ask for shardings by meaning
("replicated", "edge blocks"), never by axis string.
"""

from __future__ import annotations

import logging
import os
import threading
from dataclasses import dataclass, field

import numpy as np

logger = logging.getLogger(__name__)

_EDGE_AXIS = "shard"


# --------------------------------------------------------------------------
# shard_map resolution
# --------------------------------------------------------------------------


def resolve_shard_map():
    """Return (shard_map_fn, is_fallback). The installed JAX exports the
    public `jax.shard_map`, whose varying-axes check covers `while_loop`
    carries; there is no fallback, so the flag is always False."""
    from jax import shard_map
    return shard_map, False


def shard_map_fn():
    """The shard_map callable (most call sites only want this)."""
    return resolve_shard_map()[0]


# --------------------------------------------------------------------------
# MeshContext
# --------------------------------------------------------------------------


def device_count() -> int:
    import jax
    return len(jax.devices())


def streaming_device():
    """The device the out-of-core streamed tier targets: the first
    visible accelerator. The streamed path is deliberately
    single-device — its bottleneck is the host→HBM link, so spreading
    blocks over a mesh would multiply transfer, not hide it; multi-chip
    streaming belongs to a future vertex-sharded tier."""
    import jax
    return jax.devices()[0]


@dataclass(frozen=True)
class MeshContext:
    """A mesh plus its canonical shardings, built once and cached.

    Axis layout: one named axis (default "shard") over which EDGE blocks
    are partitioned; O(n) vertex vectors are either replicated
    (`replicated`) or blocked over the same axis (`vertex_blocks`, the
    1.5D layout). 2D (edges x model) meshes for embedding training keep
    using `make_mesh_2d` below.
    """
    mesh: object                 # jax.sharding.Mesh
    axis: str
    n_shards: int
    replicated: object = field(repr=False)       # NamedSharding, P()
    edge_blocks: object = field(repr=False)      # P(axis, None): (P, per)
    vertex_blocks: object = field(repr=False)    # P(axis): 1D blocked

    def put_edge_blocks(self, arr):
        """Place a (n_shards, per) host array one row per device."""
        import jax
        return jax.device_put(arr, self.edge_blocks)

    def put_replicated(self, arr):
        import jax
        return jax.device_put(arr, self.replicated)

    @property
    def cache_key(self):
        """Stable identity for per-graph plan caches."""
        return (self.axis, self.n_shards,
                tuple(d.id for d in self.mesh.devices.flat))


_ctx_cache: dict = {}
_ctx_lock = threading.Lock()


def get_mesh_context(n_devices: int | None = None,
                     axis: str = _EDGE_AXIS) -> MeshContext:
    """Build (or fetch the cached) MeshContext over the first n devices.

    `n_devices=1` is the mesh-of-1 degeneracy: all sharded kernels run
    unchanged with no cross-device collectives in the compiled program.
    """
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devs = jax.devices()
    if n_devices is None:
        n_devices = len(devs)
    if not 1 <= n_devices <= len(devs):
        raise ValueError(
            f"requested {n_devices} devices; {len(devs)} available")
    key = (n_devices, axis, tuple(d.id for d in devs[:n_devices]))
    with _ctx_lock:
        ctx = _ctx_cache.get(key)
        if ctx is None:
            mesh = Mesh(np.array(devs[:n_devices]), (axis,))
            ctx = MeshContext(
                mesh=mesh, axis=axis, n_shards=n_devices,
                replicated=NamedSharding(mesh, P()),
                edge_blocks=NamedSharding(mesh, P(axis, None)),
                vertex_blocks=NamedSharding(mesh, P(axis)))
            _ctx_cache[key] = ctx
    return ctx


def analytics_mesh() -> MeshContext | None:
    """Process-default mesh for `ops/` analytics, or None (single-chip).

    MEMGRAPH_TPU_MESH_DEVICES = "all" | "<int>" opts the whole analytics
    layer into mesh execution; unset keeps the single-chip
    kernels as the default.
    """
    spec = os.environ.get("MEMGRAPH_TPU_MESH_DEVICES", "").strip()
    if not spec:
        return None
    if spec.lower() == "all":
        return get_mesh_context()
    try:
        n = int(spec)
    except ValueError:
        logger.warning("MEMGRAPH_TPU_MESH_DEVICES=%r is not an int or "
                       "'all'; ignoring", spec)
        return None
    return get_mesh_context(min(max(n, 1), device_count()))


def resolve_mesh(mesh=None) -> MeshContext | None:
    """Normalize an algorithm's `mesh=` argument to a MeshContext.

    Accepts None (→ the env-driven `analytics_mesh()` default, usually
    None), an int device count, a `jax.sharding.Mesh` (first axis is the
    edge axis), or a ready MeshContext.
    """
    if mesh is None:
        return analytics_mesh()
    if isinstance(mesh, MeshContext):
        return mesh
    if isinstance(mesh, int):
        return get_mesh_context(mesh)
    # a raw jax Mesh: wrap its first axis
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    if isinstance(mesh, Mesh):
        axis = mesh.axis_names[0]
        if len(mesh.axis_names) != 1:
            raise ValueError(
                "analytics meshes are 1D over the edge axis; got "
                f"axes {mesh.axis_names}")
        return MeshContext(
            mesh=mesh, axis=axis, n_shards=int(mesh.shape[axis]),
            replicated=NamedSharding(mesh, P()),
            edge_blocks=NamedSharding(mesh, P(axis, None)),
            vertex_blocks=NamedSharding(mesh, P(axis)))
    raise TypeError(f"mesh must be None, int, Mesh or MeshContext; "
                    f"got {type(mesh).__name__}")


# --------------------------------------------------------------------------
# legacy constructors (kept: __graft_entry__ / tests / node2vec use them)
# --------------------------------------------------------------------------


def make_mesh(n_devices: int | None = None, axis_name: str = "edges"):
    """1D mesh over the first n_devices devices (edge-partition axis)."""
    import jax
    from jax.sharding import Mesh
    devs = jax.devices()
    if n_devices is not None:
        devs = devs[:n_devices]
    return Mesh(np.array(devs), (axis_name,))


def make_mesh_2d(data: int, model: int,
                 axis_names: tuple[str, str] = ("data", "model")):
    """2D mesh (data x model) for embedding-training workloads."""
    import jax
    from jax.sharding import Mesh
    devs = np.array(jax.devices()[:data * model]).reshape(data, model)
    return Mesh(devs, axis_names)
