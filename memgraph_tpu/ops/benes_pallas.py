"""Pallas TPU kernels applying a Benes network in 3 HBM passes.

The XLA roll formulation (ops/spmv_mxu._benes_apply_rolls) re-reads and
re-writes the full array once per stage: 2*log2(N)-1 HBM round trips
(47 at N=2^24). This module exploits the Benes stage order
(d = N/2 ... 2, 1, 2 ... N/2): every stage with distance d < 2^K acts
entirely inside aligned 2^K-element blocks (XOR by d < 2^K cannot leave
the block), and those stages are CONTIGUOUS in the middle of the
schedule. So:

  pass A (outer-down): stages d = 2^(n-1) .. 2^K applied on a
          (2^(n-K), M, 128) view — axis-0 rolls, one read+write of x.
  pass B (middle):     all 2K-1 stages with d < 2^K fused in ONE kernel;
          each grid step holds a 2^K-element block in VMEM and applies
          every middle stage before writing back once.
  pass C (outer-up):   stages d = 2^K .. 2^(n-1), same view as pass A.

Masks are shipped as per-element int32 bit-planes: bit b of
word[plane, i] is stage (plane*31+b)'s swap decision for element i, so
extraction is an elementwise shift+AND — no gathers, no repeats, no
narrow dtypes (ops/blob.py). 31 bits per int32 plane keeps the sign bit out of play.

Reference analog: none — the reference scatters via CUDA/C++; this is
the TPU-native formulation of applying a fixed permutation at HBM speed.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import numpy as np

from .benes import benes_stage_distances

LANES = 128
BITS_PER_PLANE = 31
DEFAULT_K = 17          # middle-block log2 size: 2^17 elems = 1024 rows
# (2^18 blocks hit the 16MB scoped-vmem stack limit when the kernel is
# co-scheduled with the pagerank einsums inside one while_loop body)


def _log2(x: int) -> int:
    return int(x).bit_length() - 1


@dataclass(frozen=True)
class BenesPallasSpec:
    """Static routing metadata (hashable; closed over by the jitted fn).

    mid_stages / outer_down / outer_up: tuples of (plane, bit, distance)
    in application order. Dead (all-zero-mask) stages are omitted.
    """
    net_log2: int
    K: int
    mid_planes: int
    mid_stages: tuple
    outer_down: tuple
    outer_up: tuple


def build_pallas_masks(masks_packed: np.ndarray, net_log2: int,
                       K: int | None = None, keep_dead: bool = False):
    """Reorganize bit-packed stage masks (n_stages, N/8 uint8, packbits
    order) into per-element int32 bit-planes + static spec.

    keep_dead: route all-zero-mask stages too, so the spec follows from
    net_log2 alone and nets of one size share one compiled program
    (ops/spmv_mxu.py: the delta net, whose masks change every CALL).

    Returns (spec, mid_words, outer_words):
      mid_words   (mid_planes, N/128, 128) int32
      outer_words (N/128, 128) int32, or None when net fits one block
    """
    N = 1 << net_log2
    if K is None:
        K = min(net_log2, DEFAULT_K)
    K = min(K, net_log2)
    dists = benes_stage_distances(net_log2)
    n_stages = len(dists)
    assert masks_packed.shape[0] == n_stages

    n_outer = net_log2 - K            # per side
    rows = N // LANES

    mid_stages, outer_down, outer_up = [], [], []
    mid_pos = 0
    n_mid_planes = max(1, -(-(2 * K - 1) // BITS_PER_PLANE))
    mid_words = np.zeros((n_mid_planes, rows, LANES), dtype=np.int64)
    outer_words = np.zeros((rows, LANES), dtype=np.int64)
    outer_bit = 0
    for s, d in enumerate(dists):
        row = masks_packed[s]
        if not keep_dead and not row.any():
            continue                   # dead stage: no swaps routed
        bits = np.unpackbits(row)[:N].astype(np.int64).reshape(rows, LANES)
        if d < (1 << K):
            plane, bit = divmod(mid_pos, BITS_PER_PLANE)
            mid_words[plane] |= bits << bit
            mid_stages.append((plane, bit, d))
            mid_pos += 1
        else:
            assert outer_bit < 31, "outer stages exceed one int32 plane"
            outer_words |= bits << outer_bit
            if s < n_stages // 2:
                outer_down.append((0, outer_bit, d))
            else:
                outer_up.append((0, outer_bit, d))
            outer_bit += 1
    spec = BenesPallasSpec(
        net_log2=net_log2, K=K, mid_planes=n_mid_planes,
        mid_stages=tuple(mid_stages), outer_down=tuple(outer_down),
        outer_up=tuple(outer_up))
    ow = outer_words.astype(np.int32) if n_outer > 0 else None
    return spec, mid_words.astype(np.int32), ow


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def _stage_in_block(x, w_planes, plane, bit, d, row_iota, lane_iota):
    """One masked-exchange stage on an in-VMEM block x (R, 128).

    w_planes: list of (R, 128) int32 bit-plane blocks.
    Partner of i is i^d: roll -d where bit_d(i)==0, +d where ==1.
    """
    import jax.numpy as jnp
    m = ((w_planes[plane] >> bit) & 1) == 1
    if d >= LANES:
        e = d // LANES
        sel = ((row_iota >> _log2(e)) & 1) == 1
        sw = jnp.where(sel, jnp.roll(x, e, axis=0), jnp.roll(x, -e, axis=0))
    else:
        sel = ((lane_iota >> _log2(d)) & 1) == 1
        sw = jnp.where(sel, jnp.roll(x, d, axis=1), jnp.roll(x, -d, axis=1))
    return jnp.where(m, sw, x)


def _mid_kernel(spec):
    import jax
    import jax.numpy as jnp

    def kernel(w_ref, x_ref, o_ref):
        x = x_ref[:]
        R = x.shape[0]
        row_iota = jax.lax.broadcasted_iota(jnp.int32, (R, LANES), 0)
        lane_iota = jax.lax.broadcasted_iota(jnp.int32, (R, LANES), 1)
        planes = [w_ref[p] for p in range(spec.mid_planes)]
        for plane, bit, d in spec.mid_stages:
            x = _stage_in_block(x, planes, plane, bit, d,
                                row_iota, lane_iota)
        o_ref[:] = x
    return kernel


def _outer_kernel(stages):
    """stages: tuple of (plane, bit, d); applied on a (G2, CH, 128) block
    where axis 0 spans the full outer dimension (distance d maps to an
    axis-0 roll by d / 2^K)."""
    import jax
    import jax.numpy as jnp

    def kernel(K, w_ref, x_ref, o_ref):
        x = x_ref[:]
        G2 = x.shape[0]
        a_iota = jax.lax.broadcasted_iota(
            jnp.int32, (G2, x.shape[1], LANES), 0)
        w = w_ref[:]
        for plane, bit, d in stages:
            t = d >> K
            m = ((w >> bit) & 1) == 1
            sel = ((a_iota >> _log2(t)) & 1) == 1
            sw = jnp.where(sel, jnp.roll(x, t, axis=0),
                           jnp.roll(x, -t, axis=0))
            x = jnp.where(m, sw, x)
        o_ref[:] = x
    return kernel


def benes_apply_pallas(x2, mid_words, outer_words, spec: BenesPallasSpec,
                       interpret: bool = False):
    """Apply the Benes network to x2 ((N/128, 128), any fp dtype) via the
    3-pass pallas formulation. Traced (usable under jit / while_loop)."""
    import jax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, K = spec.net_log2, spec.K
    N = 1 << n
    rows = N // LANES
    RB = 1 << (K - 7)                  # rows per middle block
    NB = rows // RB                    # middle grid size
    G2 = 1 << (n - K)                  # outer axis-0 extent
    M = rows // max(G2, 1)             # rows per outer column

    vmem = dict(memory_space=pltpu.VMEM)

    def outer_pass(x2, stages):
        if not stages:
            return x2
        # chunk the row dim so the x block stays ~2^19 elements
        # (~1 MiB bf16 / 2 MiB f32, double-buffered by mosaic)
        target = (1 << 19)
        CH = max(1, min(M, target // max(G2, 1) // LANES))
        while M % CH:
            CH -= 1
        x3 = x2.reshape(G2, M, LANES)
        w3 = outer_words.reshape(G2, M, LANES)
        out = pl.pallas_call(
            partial(_outer_kernel(stages), K),
            out_shape=jax.ShapeDtypeStruct(x3.shape, x3.dtype),
            grid=(M // CH,),
            in_specs=[
                pl.BlockSpec((G2, CH, LANES), lambda i: (0, i, 0), **vmem),
                pl.BlockSpec((G2, CH, LANES), lambda i: (0, i, 0), **vmem),
            ],
            out_specs=pl.BlockSpec((G2, CH, LANES), lambda i: (0, i, 0),
                                   **vmem),
            interpret=interpret,
        )(w3, x3)
        return out.reshape(rows, LANES)

    def mid_pass(x2):
        if not spec.mid_stages:
            return x2
        return pl.pallas_call(
            _mid_kernel(spec),
            out_shape=jax.ShapeDtypeStruct((rows, LANES), x2.dtype),
            grid=(NB,),
            in_specs=[
                pl.BlockSpec((spec.mid_planes, RB, LANES),
                             lambda i: (0, i, 0), **vmem),
                pl.BlockSpec((RB, LANES), lambda i: (i, 0), **vmem),
            ],
            out_specs=pl.BlockSpec((RB, LANES), lambda i: (i, 0), **vmem),
            interpret=interpret,
        )(mid_words, x2)

    x2 = outer_pass(x2, spec.outer_down)
    x2 = mid_pass(x2)
    x2 = outer_pass(x2, spec.outer_up)
    return x2
