"""Gather-free sparse matvec (PageRank core) built from MXU matmuls,
Benes routing, and roll-based exchanges.

Design premise (the ratio is not measured on the current machine): on
the TPU, XLA elementwise/matmul run at full speed while gather/scatter/
sort formulations are far slower. This module therefore expresses `acc[dst] += rank[src] * mult(edge)` with NO
data-dependent addressing on the device:

  1. EXPAND   — one-hot matmul multicast: per supergroup of 128 rank rows,
                T = einsum(OH(src_row), rank_planes) places rank[src] in
                every edge slot (slot lane == src & 127); multiply by the
                per-slot `mult` (weight / out-weight-sum, 0 on padding).
  2. PERMUTE  — a Benes network (ops.benes) moves every edge slot from its
                gather-layout position to its scatter-layout position via
                2*log2(N)-1 masked-exchange stages. Each stage exchanges
                partners i <-> i^d, realized as two jnp.rolls + selects on
                an (N/128, 128) layout: a row roll for d >= 128, a lane
                roll for d < 128.
  3. REDUCE + EXTRACT — scatter layout keeps each destination's edges
                contiguous within its lane (lane == dst & 127, runs
                aligned per dst-row); a full-run one-hot matmul per chunk
                sums every run directly on the MXU (no roll-tree passes):
                per_chunk[c,k,l] = sum_i OH(run slot)[c,i,k] * x[c,i,l],
                then a small window one-hot sums chunks into aligned
                windows.
  4. RELABEL  — a second (node-sized) Benes converts the accumulator from
                the in-degree-sorted labeling (which keeps scatter padding
                small under skew) to the out-degree-sorted labeling (which
                keeps gather padding small), ready for the next EXPAND.

All routing/masks/layouts are precomputed on the host at export time and
shipped once; per-iteration device work is elementwise + MXU + rolls only.

Reference analog: the sparse power iteration of
/root/reference/mage/cpp/pagerank_module/ and the cuGraph CUDA variant
(mage/cpp/cugraph_module/algorithms/pagerank.cu); the formulation here is
TPU-native rather than scatter/gather-based.
"""

from __future__ import annotations

import logging
import os
import threading
from collections import OrderedDict
from dataclasses import dataclass, field
from functools import partial
from typing import NamedTuple, Optional

import numpy as np

from ..observability.metrics import global_metrics
from .benes import benes_stage_distances, route_packed
from .benes_pallas import BenesPallasSpec

log = logging.getLogger(__name__)

LANES = 128
SG_ROWS = 128          # rank rows per supergroup (=> 16384 nodes)
R_C = 256              # scatter rows per extract chunk
K_C = 256              # dst-rows per aligned output window


def _ceil_to(x: int, m: int) -> int:
    return -(-x // m) * m


@dataclass
class MXUPlan:
    n_nodes: int
    # --- gather (out-degree labeling) ---
    G: int                     # supergroups
    R_G: int                   # gather rows per supergroup (padded uniform)
    rowid: np.ndarray          # (G, R_G) int16: src row within supergroup
    mult: np.ndarray           # (G, R_G, LANES) f32: w/wsum, 0 = pad slot
    out_relabel: np.ndarray    # (n_nodes,) original -> out-label id
    valid_out: np.ndarray      # (G*SG_ROWS*LANES,) f32 1.0 for real nodes
    dangling_out: np.ndarray   # same shape: 1.0 where out-wsum == 0
    # --- big Benes ---
    net_log2: int
    masks_packed: np.ndarray   # (stages, N/8) uint8
    # --- scatter/extract (in-degree labeling) ---
    C: int                     # extract chunks (total rows = C * R_C)
    run_k: np.ndarray          # (C, R_C) int16: window slot of the row's
    #                            dst block (dr % K_C), -1 on padding rows
    win_oh: np.ndarray         # (C, W) f32 one-hot chunk->window
    W: int
    in_relabel: np.ndarray     # (n_nodes,) original -> in-label id
    # --- node relabel Benes (in-label acc -> out-label acc) ---
    node_net_log2: int
    node_masks_packed: np.ndarray
    # per-node out-weight sums (ORIGINAL ids) — the delta-refresh path
    # rescales stale w/wsum multipliers with these (see DeltaPlan)
    wsum: np.ndarray = None
    # the plan's arrays packed and on the device, one _Resident per
    # Benes backend: built by the first kernel over this plan, reused
    # by every later one (_resident_base)
    _resident: dict = field(default_factory=dict, repr=False,
                            compare=False)


def _relabel_by(key: np.ndarray, stripe_groups: int = 0) -> np.ndarray:
    """relabel[node] = position when sorted by key desc (stable).

    With stripe_groups=G, rows of 128 consecutive sorted nodes (degree-
    homogeneous, so each row's max ~ its mean) are dealt round-robin
    across the G supergroups: row j lands at supergroup j%G, slot j//G.
    This balances per-supergroup row totals so the uniform R_G padding of
    the batched expand einsum stays ~1x instead of concentrating all the
    tall rows in supergroup 0."""
    order = np.argsort(-key, kind="stable")
    n = len(key)
    pos = np.arange(n)
    if stripe_groups:
        j, lane = pos >> 7, pos & 127
        r2 = (j % stripe_groups) * SG_ROWS + j // stripe_groups
        pos = r2 * LANES + lane
    relab = np.empty(n, dtype=np.int64)
    relab[order] = pos
    return relab


def _gather_layout(src, w, relab_out, inv_wsum, G, force_R_G=None,
                   pad_R_G=None):
    """Gather-side layout for an edge subset under a FIXED out labeling.

    Returns (R_G, rowid, mult, gp_by_edge): rows per supergroup, the
    src-row id of every gather row, the per-slot multiplier (w/wsum,
    0 on padding), and each edge's flat gather position (edge order).

    force_R_G: use this (>= required) row count so plans for different
    edge shards stack into uniform arrays.
    pad_R_G: required row count -> the (>=) count to lay out with; the
    delta plan's quantisation (build_delta_plan).
    """
    E = len(src)
    node_flat = G * SG_ROWS * LANES
    u = relab_out[src]
    srow, slane = u >> 7, u & 127
    # per-edge count per labeled node (LOCAL to this subset)
    deg_l = np.bincount(u, minlength=node_flat)
    # rows per src-row block = max subset-degree among its 128 nodes
    H_out = deg_l.reshape(-1, LANES).max(axis=1)              # per src-row
    rows_per_sg = H_out.reshape(G, SG_ROWS).sum(axis=1)
    R_G = max(1, int(rows_per_sg.max()))
    if pad_R_G is not None:
        R_G = pad_R_G(R_G)
    if force_R_G is not None:
        if force_R_G < R_G:
            raise ValueError(f"force_R_G={force_R_G} < required {R_G}")
        R_G = force_R_G
    # base row (within supergroup) of each src-row block
    base_in_sg = np.zeros(G * SG_ROWS, dtype=np.int64)
    for g in range(G):
        base_in_sg[g * SG_ROWS:(g + 1) * SG_ROWS] = \
            np.cumsum(H_out[g * SG_ROWS:(g + 1) * SG_ROWS]) \
            - H_out[g * SG_ROWS:(g + 1) * SG_ROWS]
    # per-edge sequence within its (node) bucket, in (src) sorted order
    order_g = np.argsort(u, kind="stable")
    seq = np.arange(E) - np.concatenate(([0], np.cumsum(
        deg_l)))[u[order_g]]
    sg = srow[order_g] >> 7
    grow = base_in_sg[srow[order_g]] + seq                    # row in sg
    gather_pos = ((sg * R_G + grow) * LANES + slane[order_g])

    rowid = np.zeros((G, R_G), dtype=np.int16)
    for g in range(G):
        rs = H_out[g * SG_ROWS:(g + 1) * SG_ROWS]
        rowid[g, :rs.sum()] = np.repeat(np.arange(SG_ROWS, dtype=np.int16),
                                        rs)
    mult = np.zeros((G, R_G, LANES), dtype=np.float32)
    mult_flat = mult.reshape(-1)
    mult_flat[gather_pos] = (w * inv_wsum[src])[order_g]
    gp_by_edge = np.empty(E, dtype=np.int64)
    gp_by_edge[order_g] = gather_pos
    return R_G, rowid, mult, gp_by_edge


def _scatter_layout(dst, relab_in, n_drows_p):
    """Scatter/extract layout for an edge subset under a FIXED in
    labeling. n_drows_p: dst-row count padded to whole K_C windows.

    Returns (C, run_k, win_oh, sp_by_edge, R_total).
    """
    E = len(dst)
    W = n_drows_p // K_C
    v = relab_in[dst]
    drow, dlane = v >> 7, v & 127
    cnt = np.bincount(v, minlength=n_drows_p * LANES)
    H_in = np.maximum(cnt.reshape(-1, LANES).max(axis=1), 1)[:n_drows_p]

    # chunked row allocation: the full-run one-hot extract sums EVERY row
    # of a dst block, so every row of a block must live in chunks claimed
    # by the block's window — pad to a chunk boundary whenever a block
    # would otherwise share a chunk with a different window.
    base2 = np.zeros(n_drows_p, dtype=np.int64)
    chunk_win: dict = {}
    rows_acc = 0
    for dr in range(n_drows_p):
        wdw = dr // K_C
        c = rows_acc // R_C
        if chunk_win.get(c, wdw) != wdw:
            rows_acc = _ceil_to(rows_acc, R_C)
        base2[dr] = rows_acc
        end = rows_acc + int(H_in[dr])
        for cc in range(rows_acc // R_C, (end - 1) // R_C + 1):
            chunk_win[cc] = wdw
        rows_acc = end
    R_total = _ceil_to(rows_acc, R_C)
    C = R_total // R_C

    win_of_chunk = np.zeros(C, dtype=np.int64)
    for c in range(C):
        win_of_chunk[c] = chunk_win.get(
            c, win_of_chunk[c - 1] if c else 0)
    win_oh = np.zeros((C, W), dtype=np.float32)
    win_oh[np.arange(C), win_of_chunk] = 1.0

    # run_k[c, i] = window slot (dr % K_C) of the block owning row
    # c*R_C + i, or -1 for padding rows. Distinct blocks sharing a chunk
    # share its window, so slots cannot collide.
    block_of_row = np.full(R_total, -1, dtype=np.int64)
    for dr in range(n_drows_p):
        block_of_row[base2[dr]:base2[dr] + H_in[dr]] = dr
    run_k = np.full(R_total, -1, dtype=np.int16)
    owned = block_of_row >= 0
    run_k[owned] = (block_of_row[owned] % K_C).astype(np.int16)
    run_k = run_k.reshape(C, R_C)

    # per-edge scatter position
    order_s = np.argsort(v, kind="stable")
    seq2 = np.arange(E) - np.concatenate(([0], np.cumsum(
        cnt)))[v[order_s]]
    scatter_pos = ((base2[drow[order_s]] + seq2) * LANES + dlane[order_s])
    sp_by_edge = np.empty(E, dtype=np.int64)
    sp_by_edge[order_s] = scatter_pos
    return C, run_k, win_oh, sp_by_edge, R_total


def _edge_perm_masks(gp_by_edge, sp_by_edge, net_log2):
    """Route the big Benes: scatter position <- gather position for every
    edge, identity-completed on free slots (all of which carry zeros)."""
    N_net = 1 << net_log2
    perm = np.full(N_net, -1, dtype=np.int64)
    perm[sp_by_edge] = gp_by_edge
    free_out = np.flatnonzero(perm < 0)
    used_in = np.zeros(N_net, dtype=bool)
    used_in[gp_by_edge] = True
    perm[free_out] = np.flatnonzero(~used_in)
    return route_packed(perm)


def _node_relabel_masks(relab_out, relab_in, node_flat, n_drows_p):
    """Route the node Benes: in-label dense acc -> out labeling."""
    acc_flat_len = n_drows_p * LANES
    node_net_log2 = int(np.ceil(np.log2(max(node_flat, acc_flat_len, 2))))
    N_nn = 1 << node_net_log2
    nperm = np.full(N_nn, -1, dtype=np.int64)
    nperm[relab_out] = relab_in                # out position <- in position
    free_out = np.flatnonzero(nperm < 0)
    used_in = np.zeros(N_nn, dtype=bool)
    used_in[relab_in] = True
    nperm[free_out] = np.flatnonzero(~used_in)
    return node_net_log2, route_packed(nperm)


def _global_labelings(src, dst, w, n_nodes):
    """Degree stats + out/in relabelings shared by all shards."""
    out_deg = np.bincount(src, minlength=n_nodes)
    in_deg = np.bincount(dst, minlength=n_nodes)
    wsum = np.bincount(src, weights=w, minlength=n_nodes)
    n_rows = _ceil_to(n_nodes, LANES) // LANES
    G = _ceil_to(n_rows, SG_ROWS) // SG_ROWS
    relab_out = _relabel_by(out_deg, stripe_groups=G)
    relab_in = _relabel_by(in_deg)
    inv_wsum = np.where(wsum > 0, 1.0 / np.maximum(wsum, 1e-300), 0.0)
    node_flat = G * SG_ROWS * LANES
    valid_out = np.zeros(node_flat, dtype=np.float32)
    valid_out[relab_out] = 1.0
    dangling_out = np.zeros(node_flat, dtype=np.float32)
    dangling_out[relab_out[wsum <= 0]] = 1.0
    n_drows = _ceil_to(n_nodes, LANES) // LANES
    n_drows_p = _ceil_to(n_drows, K_C)                        # whole windows
    return (G, relab_out, relab_in, inv_wsum, valid_out, dangling_out,
            n_drows_p, wsum)


def build_plan(src: np.ndarray, dst: np.ndarray,
               weights: Optional[np.ndarray], n_nodes: int,
               normalize: bool = True) -> MXUPlan:
    """Precompute layouts + routing for the MXU semiring-SpMV kernel.

    normalize=True bakes w / out-weight-sum multipliers (the column-
    stochastic matrix PageRank iterates); normalize=False bakes plain w
    (the raw A^T other plus-times algorithms — katz — iterate)."""
    src = np.asarray(src, dtype=np.int64)
    dst = np.asarray(dst, dtype=np.int64)
    E = len(src)
    w = (np.ones(E, dtype=np.float64) if weights is None
         else np.asarray(weights, dtype=np.float64))

    (G, relab_out, relab_in, inv_wsum, valid_out, dangling_out,
     n_drows_p, wsum) = _global_labelings(src, dst, w, n_nodes)
    if not normalize:
        inv_wsum = np.ones_like(inv_wsum)

    R_G, rowid, mult, gp_by_edge = _gather_layout(
        src, w, relab_out, inv_wsum, G)
    C, run_k, win_oh, sp_by_edge, R_total = _scatter_layout(
        dst, relab_in, n_drows_p)

    net = max(G * R_G * LANES, R_total * LANES, 2)
    net_log2 = int(np.ceil(np.log2(net)))
    masks_packed = _edge_perm_masks(gp_by_edge, sp_by_edge, net_log2)

    node_flat = G * SG_ROWS * LANES
    node_net_log2, node_masks_packed = _node_relabel_masks(
        relab_out, relab_in, node_flat, n_drows_p)

    return MXUPlan(
        n_nodes=n_nodes, G=G, R_G=R_G, rowid=rowid, mult=mult,
        out_relabel=relab_out, valid_out=valid_out,
        dangling_out=dangling_out,
        net_log2=net_log2, masks_packed=masks_packed,
        C=C, run_k=run_k, win_oh=win_oh, W=n_drows_p // K_C,
        in_relabel=relab_in,
        node_net_log2=node_net_log2, node_masks_packed=node_masks_packed,
        wsum=wsum)


# ---------------------------------------------------------------------------
# delta plans: O(changed-edges) refresh instead of a full replan
# ---------------------------------------------------------------------------

@dataclass
class DeltaPlan:
    """Side-plan covering edges added/removed since the base plan.

    The base plan keeps serving its (now stale) edges; this plan routes
    only the delta, and two correction vectors make the combination
    exact:
      - scale_out: rank is pre-scaled by wsum_old/wsum_new per source
        before the BASE expand, so stale w/wsum_old multipliers become
        w/wsum_new;
      - removed edges ride the delta net with NEGATIVE multipliers
        -w/wsum_new, cancelling the base contribution exactly;
      - dangling_out replaces the base vector (nodes may gain/lose all
        out-edges).
    Valid only while the node set is unchanged. Analog of the
    reference's online pagerank keeping incremental state
    (/root/reference/query_modules/pagerank_module/
    pagerank_online_module.cpp:17-20) — here the increment is a
    TPU-routable side-net rather than a CPU ordering.
    """
    n_delta: int
    R_G: int
    rowid: np.ndarray          # (G, R_G) int16
    mult: np.ndarray           # (G, R_G, LANES) f32 (signed)
    net_log2: int
    masks_packed: np.ndarray
    C: int
    run_k: np.ndarray
    win_oh: np.ndarray
    scale_out: np.ndarray      # (node_flat,) f32
    dangling_out: np.ndarray   # (node_flat,) f32 — replaces base's
    wsum: np.ndarray           # updated per-node out-weight sums


#: the delta net's shapes are quantised: the floor below, then steps of
#: this factor, so that a delta growing by one burst a CALL against its
#: base anchor keeps ONE compiled program (make_semiring_kernel keys its
#: program table on these shapes) until it is 4x the floor
DELTA_SHAPE_STEP = 4


def _quantise(need: int, floor: int) -> int:
    q = floor
    while q < need:
        q *= DELTA_SHAPE_STEP
    return q


def build_delta_plan(base: MXUPlan,
                     add_src, add_dst, add_w=None,
                     rem_src=None, rem_dst=None, rem_w=None,
                     bucket: bool = True) -> DeltaPlan:
    """Build the O(delta) side-plan. All ids are ORIGINAL node ids and
    must be < base.n_nodes (node additions require a full replan).

    bucket=True quantises R_G / C so growing deltas reuse one compiled
    program: R_G starts at one gather row per source row of a
    supergroup (SG_ROWS), C at two chunks per output window (2 W: one is
    the least the layout can need, a second is taken as soon as one
    destination receives two delta edges), and both grow in steps of
    DELTA_SHAPE_STEP. The padding is dead rows and dead chunks."""
    if base.wsum is None:
        raise ValueError("base plan predates delta support (no wsum)")
    n = base.n_nodes
    add_src = np.asarray(add_src, dtype=np.int64)
    add_dst = np.asarray(add_dst, dtype=np.int64)
    a_w = (np.ones(len(add_src)) if add_w is None
           else np.asarray(add_w, dtype=np.float64))
    rem_src = np.asarray(
        rem_src if rem_src is not None else [], dtype=np.int64)
    rem_dst = np.asarray(
        rem_dst if rem_dst is not None else [], dtype=np.int64)
    r_w = (np.ones(len(rem_src)) if rem_w is None
           else np.asarray(rem_w, dtype=np.float64))
    for arr in (add_src, add_dst, rem_src, rem_dst):
        if len(arr) and (arr.min() < 0 or arr.max() >= n):
            raise ValueError("delta references nodes outside the base plan")

    wsum_new = base.wsum.copy()
    if len(add_src):
        wsum_new += np.bincount(add_src, weights=a_w, minlength=n)
    if len(rem_src):
        wsum_new -= np.bincount(rem_src, weights=r_w, minlength=n)
    wsum_new[np.abs(wsum_new) < 1e-9] = 0.0     # cancel fp dust at zero
    inv_new = np.where(wsum_new > 0, 1.0 / np.maximum(wsum_new, 1e-300),
                       0.0)

    d_src = np.concatenate([add_src, rem_src])
    d_dst = np.concatenate([add_dst, rem_dst])
    d_w = np.concatenate([a_w, -r_w])           # removals route negative

    G = base.G
    n_drows_p = base.W * K_C
    R_G, rowid, mult, gp = _gather_layout(
        d_src, d_w, base.out_relabel, inv_new, G,
        pad_R_G=partial(_quantise, floor=SG_ROWS) if bucket else None)
    C, run_k, win_oh, sp, R_total = _scatter_layout(
        d_dst, base.in_relabel, n_drows_p)
    C_pad = _quantise(C, 2 * base.W) if bucket else C
    if C_pad != C:
        # pad with dead chunks: run_k=-1 rows extract nothing, zero
        # win_oh rows route no window
        run_k = np.concatenate(
            [run_k, np.full((C_pad - C, R_C), -1, dtype=run_k.dtype)])
        win_oh = np.concatenate(
            [win_oh, np.zeros((C_pad - C, win_oh.shape[1]),
                              dtype=win_oh.dtype)])
        C, R_total = C_pad, C_pad * R_C
    net = max(G * R_G * LANES, R_total * LANES, 2)
    net_log2 = int(np.ceil(np.log2(net)))
    masks_packed = _edge_perm_masks(gp, sp, net_log2)

    node_flat = G * SG_ROWS * LANES
    # exact-1 scale for untouched nodes: only rescale where wsum changed
    changed = wsum_new != base.wsum
    scale_nodes = np.ones(n, dtype=np.float64)
    scale_nodes[changed] = base.wsum[changed] * inv_new[changed]
    scale_out = np.zeros(node_flat, dtype=np.float32)
    scale_out[base.out_relabel] = scale_nodes
    dangling_out = np.zeros(node_flat, dtype=np.float32)
    dangling_out[base.out_relabel[wsum_new <= 0]] = 1.0

    return DeltaPlan(
        n_delta=len(d_src), R_G=R_G, rowid=rowid, mult=mult,
        net_log2=net_log2, masks_packed=masks_packed,
        C=C, run_k=run_k, win_oh=win_oh,
        scale_out=scale_out, dangling_out=dangling_out, wsum=wsum_new)


# ---------------------------------------------------------------------------
# device kernel
# ---------------------------------------------------------------------------

def _unpack_mask_words(words, net_log2):
    """(stages, W) uint32 words -> (stages, N/128, 128) bool (flat if
    N < 128). Word layout per blob.unpack_bit_words."""
    from .blob import unpack_bit_words
    N = 1 << net_log2
    bits = unpack_bit_words(words, N)
    if N >= LANES:
        return bits.reshape(words.shape[0], N // LANES, LANES)
    return bits


def _benes_apply_rolls(x2, masks2, net_log2, live_stages=None):
    """Roll-based Benes. x2 is (N/128, 128) (or flat (N,) when N < 128).

    Stage distance d exchanges partners i <-> i^d (masks are symmetric:
    mask[i] == mask[i^d], see ops/benes.py). For i with bit d clear the
    partner is i+d == roll(x, -d)[i]; bit set, i-d == roll(x, +d)[i] —
    so the exchanged view is a two-roll select on a static bit pattern,
    a row roll when d >= 128 and a lane roll when d < 128.

    live_stages: optional bool sequence; stages whose masks are all-zero
    (no swaps routed through that level) are skipped at trace time."""
    import jax.numpy as jnp
    flat = x2.ndim == 1
    for s, d in enumerate(benes_stage_distances(net_log2)):
        if live_stages is not None and not live_stages[s]:
            continue
        if flat:
            bit = ((jnp.arange(x2.shape[0]) // d) & 1) == 1
            sw = jnp.where(bit, jnp.roll(x2, d), jnp.roll(x2, -d))
        elif d >= LANES:
            e = d // LANES
            bit = ((jnp.arange(x2.shape[0]) // e) & 1) == 1
            sw = jnp.where(bit[:, None], jnp.roll(x2, e, axis=0),
                           jnp.roll(x2, -e, axis=0))
        else:
            bit = ((jnp.arange(LANES) // d) & 1) == 1
            sw = jnp.where(bit[None, :], jnp.roll(x2, d, axis=1),
                           jnp.roll(x2, -d, axis=1))
        x2 = jnp.where(masks2[s], sw, x2)
    return x2


def pagerank_mxu_epilogue(rank, acc, env, P):
    """The fused PageRank update + convergence partial, applied to the
    MXU matvec's out-labeled accumulator (shared formula:
    semiring.pagerank_update)."""
    import jax.numpy as jnp
    from .semiring import pagerank_update
    dm = jnp.sum(rank * env["dangling"])
    new_rank = pagerank_update(acc, dm, env["valid"], env["n_f"],
                               P["damping"])
    err = jnp.sum(jnp.abs(new_rank - rank))
    return new_rank, err


class _Net(NamedTuple):
    """What a program needs to know of one edge net (the base's or the
    delta's) before it is traced. ``route`` is how the Benes network
    runs: a BenesPallasSpec (Pallas passes), a tuple of bools (rolls,
    dead stages skipped) or None (rolls, every stage)."""
    R_G: int
    C: int
    net_log2: int
    segs: tuple                # the blob's segment table (blob.segs_key)
    route: object


class _Signature(NamedTuple):
    """Everything static in a fixpoint program, and nothing that is
    data: two kernels with equal signatures run the same jitted
    functions on different blobs."""
    n_nodes: int
    G: int
    W: int
    node_net_log2: int
    node_route: object         # as _Net.route, for the node relabel net
    base: _Net
    delta: Optional[_Net]      # None: the full plan alone
    route_dtype: str
    epilogue: object           # the algorithm's fused update (a function)
    x0_default: str


@dataclass(frozen=True)
class _Resident:
    """One side's arrays as one int32 blob on the device, with the
    statics that read it."""
    blob: object
    net: _Net
    node_route: object = None  # base side only


def _pallas_or_bits(arrays: dict, prefix: str, masks_packed, net_log2,
                    pallas: bool, keep_dead: bool):
    """Add one Benes net's masks to a blob's arrays; returns its
    ``route``."""
    if pallas:
        from .benes_pallas import build_pallas_masks
        spec, mid, out = build_pallas_masks(masks_packed, net_log2,
                                            keep_dead=keep_dead)
        arrays[f"pb_{prefix}_mid"] = mid
        if out is not None:
            arrays[f"pb_{prefix}_out"] = out
        return spec
    arrays[f"{prefix}_masks"] = ("bits", masks_packed)
    # all-zero-mask stages route nothing: skipped at trace time
    return (None if keep_dead
            else tuple(bool(row.any()) for row in masks_packed))


_resident_lock = threading.Lock()


def _resident_base(plan: MXUPlan, use_pallas: bool) -> _Resident:
    """The base plan's blob: packed and uploaded by the first kernel
    over ``plan``, kept with the plan for every later one (a CALL after
    a write packs its delta alone)."""
    import jax
    from .blob import pack_blob, segs_key
    with _resident_lock:
        got = plan._resident.get(use_pallas)
        if got is None:
            arrays = {
                "mult": plan.mult.astype(np.float32),
                "rowid_i32": plan.rowid.astype(np.int32),
                "run_k_i32": plan.run_k.astype(np.int32),
                "win_oh": plan.win_oh.astype(np.float32),
                "valid": plan.valid_out.astype(np.float32),
                "dangling": plan.dangling_out.astype(np.float32),
            }
            # the base nets skip their dead stages: fixed per plan
            big = _pallas_or_bits(arrays, "big", plan.masks_packed,
                                  plan.net_log2, use_pallas, False)
            node = _pallas_or_bits(arrays, "node", plan.node_masks_packed,
                                   plan.node_net_log2, use_pallas, False)
            blob_np, segs = pack_blob(arrays)
            got = plan._resident[use_pallas] = _Resident(
                jax.device_put(blob_np),
                _Net(plan.R_G, plan.C, plan.net_log2, segs_key(segs),
                     big), node)
    return got


def _upload_delta(delta: DeltaPlan, use_pallas: bool) -> _Resident:
    """A CALL's own upload: the delta net and the vectors it replaces.
    Every stage of the delta's Benes net is routed, dead or not: which
    stages a burst leaves dead is data, and the program must not
    follow it."""
    import jax
    from .blob import pack_blob, segs_key
    arrays = {
        "d_mult": delta.mult.astype(np.float32),
        "d_rowid_i32": delta.rowid.astype(np.int32),
        "d_run_k_i32": delta.run_k.astype(np.int32),
        "d_win_oh": delta.win_oh.astype(np.float32),
        "d_scale": delta.scale_out.astype(np.float32),
        # the delta's dangling vector REPLACES the base one
        "dangling": delta.dangling_out.astype(np.float32),
    }
    route = _pallas_or_bits(arrays, "d", delta.masks_packed,
                            delta.net_log2,
                            use_pallas and delta.net_log2 >= 12, True)
    blob_np, segs = pack_blob(arrays)
    return _Resident(
        jax.device_put(blob_np),
        _Net(delta.R_G, delta.C, delta.net_log2, segs_key(segs), route))


# ---------------------------------------------------------------------------
# the program table: one traced fixpoint per signature
# ---------------------------------------------------------------------------

#: jitted (run_impl, run_impl_default) by _Signature. A kernel whose
#: signature is here calls functions JAX has already traced: no trace,
#: no lowering, no executable load. LRU-bounded: a program holds its
#: executables alive.
_PROGRAMS: "OrderedDict[_Signature, tuple]" = OrderedDict()
_PROGRAMS_MAX = 32
_programs_lock = threading.Lock()


def _program(sig: _Signature) -> tuple:
    """Get-then-build-then-store under one lock, counted:
    mxu.program_hit_total / mxu.program_miss_total."""
    with _programs_lock:
        pair = _PROGRAMS.get(sig)
        if pair is not None:
            _PROGRAMS.move_to_end(sig)
            global_metrics.increment("mxu.program_hit_total")
            return pair
        pair = _PROGRAMS[sig] = _build_program(sig)
        while len(_PROGRAMS) > _PROGRAMS_MAX:
            _PROGRAMS.popitem(last=False)
    global_metrics.increment("mxu.program_miss_total")
    return pair


def _build_program(sig: _Signature) -> tuple:
    """Define (not yet trace) the jitted fixpoint of one signature:
    ``run_impl(blob, x0, params, max_iterations, tol, dblob)`` and
    ``run_impl_default(blob, params, max_iterations, tol, dblob)``.
    Everything they close over comes from ``sig``; the plan's data
    arrives in ``blob`` and the delta's in ``dblob`` (None without)."""
    import jax
    import jax.numpy as jnp
    from .benes_pallas import benes_apply_pallas
    from .blob import segs_from_key, unblob

    G = sig.G
    base, delta = sig.base, sig.delta
    N_net = 1 << base.net_log2
    N_nn = 1 << sig.node_net_log2
    node_flat = G * SG_ROWS * LANES
    n_f = float(sig.n_nodes)
    route_dtype = jnp.dtype(sig.route_dtype)
    epilogue, x0_default = sig.epilogue, sig.x0_default

    segs = segs_from_key(base.segs)
    d_segs = segs_from_key(delta.segs) if delta is not None else {}
    # the three Benes nets: how each runs, and its size
    nets = {"big": (base.route, base.net_log2),
            "node": (sig.node_route, sig.node_net_log2)}
    if delta is not None:
        nets["d"] = (delta.route, delta.net_log2)
    # said once per program: which Benes formulation the device will run
    log.info("MXU program: Benes backend %s (net 2^%d, node net 2^%d, "
             "route %s, delta net %s)",
             "pallas" if isinstance(base.route, BenesPallasSpec)
             else "rolls", base.net_log2, sig.node_net_log2,
             route_dtype.name,
             "none" if delta is None else f"2^{delta.net_log2}")

    def _masks(blob, table, net, dv):
        """One Benes net's masks out of its blob, as its route reads
        them."""
        route, net_log2 = nets[net]
        if isinstance(route, BenesPallasSpec):
            dv[f"pb_{net}_mid"] = unblob(blob, table, f"pb_{net}_mid")
            if f"pb_{net}_out" in table:
                dv[f"pb_{net}_out"] = unblob(blob, table, f"pb_{net}_out")
        else:
            dv[f"{net}_masks2"] = _unpack_mask_words(
                unblob(blob, table, f"{net}_masks"), net_log2)

    def _route(x2, dv, net):
        route, net_log2 = nets[net]
        if isinstance(route, BenesPallasSpec):
            return benes_apply_pallas(x2, dv[f"pb_{net}_mid"],
                                      dv.get(f"pb_{net}_out"), route)
        return _benes_apply_rolls(x2, dv[f"{net}_masks2"], net_log2,
                                  live_stages=route)

    @jax.jit
    def prepare(blob, dblob):
        """One compiled pass: slice, bitcast, unpack masks, build one-hots."""
        iota_sg = jnp.arange(SG_ROWS, dtype=jnp.int32)
        iota_kc = jnp.arange(K_C, dtype=jnp.int32)
        # keep int32 on device (ops/blob.py: whole 4-byte words only)
        rowid = unblob(blob, segs, "rowid_i32")
        run_k = unblob(blob, segs, "run_k_i32")
        oh = (rowid[:, :, None] == iota_sg[None, None, :]
              ).astype(jnp.float32)                        # (G, R_G, 128)
        ohe = ((run_k[:, :, None] == iota_kc[None, None, :])
               & (run_k[:, :, None] >= 0)).astype(route_dtype)
        dv = dict(
            oh=oh,
            mult=unblob(blob, segs, "mult"),
            valid=unblob(blob, segs, "valid"),
            dangling=(unblob(blob, segs, "dangling") if delta is None
                      else unblob(dblob, d_segs, "dangling")),
            ohe=ohe,
            win_oh=unblob(blob, segs, "win_oh"),
        )
        _masks(blob, segs, "big", dv)
        _masks(blob, segs, "node", dv)
        if delta is not None:
            d_rowid = unblob(dblob, d_segs, "d_rowid_i32")
            d_run_k = unblob(dblob, d_segs, "d_run_k_i32")
            dv["d_oh"] = (d_rowid[:, :, None] == iota_sg[None, None, :]
                          ).astype(jnp.float32)
            dv["d_ohe"] = ((d_run_k[:, :, None] == iota_kc[None, None, :])
                           & (d_run_k[:, :, None] >= 0)).astype(route_dtype)
            dv["d_mult"] = unblob(dblob, d_segs, "d_mult")
            dv["d_win_oh"] = unblob(dblob, d_segs, "d_win_oh")
            dv["d_scale"] = unblob(dblob, d_segs, "d_scale")
            _masks(dblob, d_segs, "d", dv)
        return dv

    def _delta_acc(rank_planes, dv):
        """Expand + route + extract the delta edges; (W, K_C, 128) f32."""
        T = jnp.einsum("grw,gwl->grl", dv["d_oh"], rank_planes,
                       preferred_element_type=jnp.float32)
        contrib = (T * dv["d_mult"]).astype(route_dtype).reshape(-1, LANES)
        N_rows = max((1 << delta.net_log2) // LANES, 1)
        x2 = jnp.zeros((N_rows, LANES), route_dtype
                       ).at[:contrib.shape[0]].set(contrib)
        x2 = _route(x2, dv, "d")
        xc = x2[:delta.C * R_C].reshape(delta.C, R_C, LANES)
        per_chunk = jnp.einsum("cik,cil->ckl", dv["d_ohe"], xc,
                               preferred_element_type=jnp.float32)
        return jnp.einsum("cw,ckl->wkl", dv["d_win_oh"], per_chunk,
                          preferred_element_type=jnp.float32)

    def matvec(rank_flat, dv):
        """⊕ = sum semiring matvec in OUT labeling (expand -> route ->
        MXU reduce/extract -> node relabel); ⊗ is baked into mult."""
        # base expand reads rank pre-scaled so stale w/wsum_old
        # multipliers become w/wsum_new (exact; see DeltaPlan)
        base_in = (rank_flat * dv["d_scale"] if delta is not None
                   else rank_flat)
        rank_planes = base_in.reshape(G, SG_ROWS, LANES)
        T = jnp.einsum("grw,gwl->grl", dv["oh"], rank_planes,
                       preferred_element_type=jnp.float32)
        contrib = (T * dv["mult"]).astype(route_dtype
                                          ).reshape(-1, LANES)
        x2 = jnp.zeros((N_net // LANES, LANES), route_dtype
                       ).at[:contrib.shape[0]].set(contrib)
        x2 = _route(x2, dv, "big")
        xc = x2[:base.C * R_C].reshape(base.C, R_C, LANES)
        # full-run one-hot reduce+extract on the MXU (no roll-tree);
        # f32 accumulation regardless of the routed dtype
        per_chunk = jnp.einsum("cik,cil->ckl", dv["ohe"], xc,
                               preferred_element_type=jnp.float32)
        accw = jnp.einsum("cw,ckl->wkl", dv["win_oh"], per_chunk,
                          preferred_element_type=jnp.float32)
        if delta is not None:
            accw = accw + _delta_acc(
                rank_flat.reshape(G, SG_ROWS, LANES), dv)
        acc_in2 = accw.reshape(-1, LANES)                  # (W*K_C, 128)
        xa = jnp.zeros((N_nn // LANES, LANES), jnp.float32
                       ).at[:acc_in2.shape[0]].set(acc_in2)
        return _route(xa, dv, "node").reshape(-1)[:node_flat]

    def _loop(x0, params, max_iterations, tol, dv):
        env = {"valid": dv["valid"], "dangling": dv["dangling"],
               "n_f": n_f}

        def body(carry):
            x, _, it = carry
            acc_out = matvec(x, dv)
            # FUSED-PAGERANK: the update + convergence partial run on
            # the accumulator inside the loop body — no extra HBM trip
            new_x, err = epilogue(x, acc_out, env, params)
            return new_x, err, it + 1

        def cond(carry):
            _, err, it = carry
            return (err > tol) & (it < max_iterations)

        return jax.lax.while_loop(
            cond, body, (x0, jnp.float32(jnp.inf), jnp.int32(0)))

    # prepare + loop fused into ONE jit call: the cold path is then a
    # single blob transfer + one compile-cached dispatch + one readback
    @partial(jax.jit, static_argnames=("max_iterations",))
    def run_impl(blob, x0, params, max_iterations: int, tol, dblob=None):
        return _loop(x0, params, max_iterations, tol, prepare(blob, dblob))

    @partial(jax.jit, static_argnames=("max_iterations",))
    def run_impl_default(blob, params, max_iterations: int, tol,
                         dblob=None):
        dv = prepare(blob, dblob)
        if x0_default == "zeros":
            x0 = jnp.zeros_like(dv["valid"])
        else:
            x0 = dv["valid"] * jnp.float32(1.0 / n_f)
        return _loop(x0, params, max_iterations, tol, dv)

    return run_impl, run_impl_default


def make_semiring_kernel(plan: MXUPlan, epilogue, route_dtype=None,
                         delta: "DeltaPlan" = None,
                         x0_default: str = "uniform"):
    """Returns fn(x0_flat, params, max_iter, tol) ->
    (x_flat, err, iters); state vectors are flat in OUT labeling,
    length G*SG_ROWS*LANES.  The semiring-parameterized generalization
    of the pagerank-only r5 kernel: the matvec (expand -> Benes route ->
    MXU reduce/extract -> node relabel) is fixed ⊕ = sum machinery —
    the one-hot extract matmul IS the sum — while the fused
    ``epilogue(x, acc, env, params) -> (new_x, err)`` supplies the
    algorithm (env carries valid / dangling / n_f; params is a dict of
    traced scalars).  ⊗ is baked into the plan's multipliers
    (build_plan(normalize=...)).

    The jitted program is looked up by what is static (_Signature, in
    the process-wide _PROGRAMS table); the arrays are its arguments.
    The base plan's are packed and uploaded once per plan, a delta's per
    kernel, so a CALL after a write costs the delta's upload and a
    dispatch of a program that is already traced.

    route_dtype: dtype for the per-edge contributions through the big
    Benes (the dominant HBM traffic). bfloat16 halves it; sums still
    accumulate in f32 on the MXU, so each contribution carries one
    0.4%-relative rounding. float32 is the exact path.

    delta: optional DeltaPlan — per iteration the base expand reads
    rank pre-scaled by delta.scale_out, the delta edges route through
    their own (small) net, and both accumulators sum before the node
    relabel. Exact for edge additions AND removals.

    x0_default: the on-device start when x0 is None — "uniform"
    (valid/n, pagerank) or "zeros" (katz)."""
    import jax
    import jax.numpy as jnp
    from ..utils.jax_cache import ensure_compile_cache
    ensure_compile_cache()

    if route_dtype is None:
        route_dtype = (jnp.bfloat16 if os.environ.get(
            "MEMGRAPH_TPU_ROUTE_DTYPE", "f32") == "bf16" else jnp.float32)

    # Benes backend: the pallas 3-pass formulation makes 3 HBM round
    # trips where the roll path makes one per stage; the XLA roll path
    # remains for CPU (tests / virtual meshes) and tiny nets
    benes_mode = os.environ.get("MEMGRAPH_TPU_BENES", "auto")
    use_pallas = (benes_mode == "pallas"
                  or (benes_mode == "auto"
                      and jax.default_backend() not in ("cpu",)
                      and plan.net_log2 >= 12
                      and plan.node_net_log2 >= 12))

    resident = _resident_base(plan, use_pallas)
    fresh = _upload_delta(delta, use_pallas) if delta is not None else None
    run_impl, run_impl_default = _program(_Signature(
        n_nodes=plan.n_nodes, G=plan.G, W=plan.W,
        node_net_log2=plan.node_net_log2, node_route=resident.node_route,
        base=resident.net, delta=fresh.net if fresh else None,
        route_dtype=jnp.dtype(route_dtype).name, epilogue=epilogue,
        x0_default=x0_default))
    blob_dev = resident.blob
    dblob_dev = fresh.blob if fresh else None

    def run(x0, params, max_iterations, tol):
        """x0 = None starts from the on-device default state (uniform
        distribution or zeros; saves the x0 host->device transfer)."""
        if x0 is None:
            return run_impl_default(blob_dev, params, max_iterations, tol,
                                    dblob_dev)
        return run_impl(blob_dev, x0, params, max_iterations, tol,
                        dblob_dev)

    # mgxla contract-checker hooks: the inner jitted programs + the
    # device blob, so tools/mgxla can abstractly .lower() the compiled
    # artifact (f64 / host-callback / collective contracts) without
    # executing a matvec
    run.jitted = run_impl
    run.jitted_default = run_impl_default
    run.blob = blob_dev
    run.delta_blob = dblob_dev
    return run


def make_pagerank_kernel(plan: MXUPlan, route_dtype=None,
                         delta: "DeltaPlan" = None):
    """Back-compat pagerank entry: the semiring kernel with the fused
    pagerank epilogue.  Returns jitted fn(rank0_flat, damping,
    max_iter, tol) -> (rank_flat, err, iters)."""
    run = make_semiring_kernel(plan, epilogue=pagerank_mxu_epilogue,
                               route_dtype=route_dtype, delta=delta,
                               x0_default="uniform")

    def run_pr(rank0, damping, max_iterations, tol):
        return run(rank0, {"damping": damping}, max_iterations, tol)

    return run_pr


def pagerank_mxu(src, dst, weights, n_nodes, damping=0.85,
                 max_iterations=100, tol=1e-6, plan: MXUPlan = None):
    """End-to-end: build plan (or reuse), run kernel, return ranks in
    ORIGINAL node ids plus (err, iters)."""
    import jax.numpy as jnp
    if plan is None:
        plan = build_plan(src, dst, weights, n_nodes)
    run = make_pagerank_kernel(plan)
    rank, err, iters = run(None, jnp.float32(damping),
                           max_iterations, jnp.float32(tol))
    rank = np.asarray(rank)
    return rank[plan.out_relabel], float(err), int(iters)
