"""Microbench round 2: the permutation cost question + pallas primitive costs.

Decides the fused-PageRank design: if XLA can apply a FIXED 12M-element
permutation fast (banded or not), the kernel is [pallas gather] -> [XLA
permute] -> [pallas scatter]. Otherwise the permute must be a pallas
routing network.

All timings amortized inside one jit dispatch via fori_loop where possible.
"""
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

if len(sys.argv) > 1 and sys.argv[1] == "cpu":
    jax.config.update("jax_platforms", "cpu")

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

INTERPRET = jax.devices()[0].platform == "cpu"
E = 12 * 1024 * 1024


def _sync(out):
    # host transfer forces completion
    return float(np.asarray(out).ravel()[0])


def timeit1(fn, *args, n=3):
    _sync(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        s = _sync(fn(*args))
    dt = (time.perf_counter() - t0) / n
    return dt


def bench_xla_take(name, idx, iters=10):
    """jnp.take looped inside one dispatch (cost amortized)."""
    idx = jnp.asarray(idx, dtype=jnp.int32)

    @jax.jit
    def run(x, idx):
        def body(_, acc):
            return jnp.take(acc, idx, unique_indices=False,
                            indices_are_sorted=False) * 1.0000001
        return jax.lax.fori_loop(0, iters, body, x)

    x = jnp.arange(E, dtype=jnp.float32)
    try:
        dt = timeit1(run, x, idx) / iters
    except Exception as e:  # noqa: BLE001
        print(f"  take/{name}: FAILED {type(e).__name__}: {str(e)[:160]}")
        return
    print(f"  take/{name}: {dt*1e3:8.2f} ms/pass  {E/dt/1e6:9.0f} Melem/s")


def bench_dynslice_gather(iters=200):
    """G2 primitive: per-tile 8-row dyn slice + axis-0 gather, looped over
    a big edge array: grid over tiles, fori inside for iterations."""
    R_EDGES = E // 128  # rows of edge slots
    TILE = 512          # rows per grid step (512*128 = 64K edges)
    RANK_R = 8192

    def kernel(grp_ref, row3_ref, rank_ref, out_ref):
        # grp_ref: (TILE//8, 1) int32 in SMEM-ish VMEM: src group per 8-row blk
        def do_block(b, _):
            g = grp_ref[b, 0]
            win = rank_ref[pl.ds(g * 8, 8), :]          # (8,128) dyn slice
            idx = row3_ref[pl.ds(b * 8, 8), :]
            vals = jnp.take_along_axis(win, idx, axis=0,
                                       mode="promise_in_bounds")
            out_ref[pl.ds(b * 8, 8), :] = vals
            return 0
        jax.lax.fori_loop(0, TILE // 8, do_block, 0)

    @jax.jit
    def run(grp, row3, rank):
        return pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((R_EDGES, 128), jnp.float32),
            grid=(R_EDGES // TILE,),
            in_specs=[
                pl.BlockSpec((TILE // 8, 1), lambda i: (i, 0),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec((TILE, 128), lambda i: (i, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec(memory_space=pltpu.VMEM),  # rank fully resident
            ],
            out_specs=pl.BlockSpec((TILE, 128), lambda i: (i, 0),
                                   memory_space=pltpu.VMEM),
            interpret=INTERPRET,
        )(grp, row3, rank)

    rng = np.random.default_rng(0)
    grp = jnp.asarray(rng.integers(0, RANK_R // 8, (R_EDGES // 8, 1)),
                      dtype=jnp.int32)
    row3 = jnp.asarray(rng.integers(0, 8, (R_EDGES, 128)), dtype=jnp.int32)
    rank = jnp.asarray(rng.random((RANK_R, 128), dtype=np.float32))
    try:
        dt = timeit1(run, grp, row3, rank)
    except Exception as e:  # noqa: BLE001
        print(f"  g2_gather: FAILED {type(e).__name__}: {str(e)[:300]}")
        return
    print(f"  g2_gather: {dt*1e3:8.2f} ms/pass  {E/dt/1e6:9.0f} Melem/s")


def bench_onehot_scatter():
    """S3 primitive: per-tile one-hot matmul scatter into a dst-block row."""
    R_EDGES = E // 128
    TILE = 512  # 64K edges per grid step; 64 dst-block sub-tiles of 8 rows
    ACC_R = 8192

    def kernel(dblk_ref, lane_ref, val_ref, acc_ref):
        @pl.when(pl.program_id(0) == 0)
        def _():
            acc_ref[:] = jnp.zeros_like(acc_ref)

        def do_block(b, _):
            d = dblk_ref[b, 0]
            lanes = lane_ref[pl.ds(b * 8, 8), :]          # (8,128) int32
            vals = val_ref[pl.ds(b * 8, 8), :]            # (8,128) f32
            cols = jax.lax.broadcasted_iota(jnp.int32, (8, 128), 1)
            # contribution of this 1024-edge block to dst-block d:
            # onehot.T @ vals — but batched per sublane won't matmul; use
            # the flat trick: sum over sublanes of per-sublane one-hot rows
            # expressed as (8,128) mask-multiply + matmul with ones.
            # out[l] = sum_{s,e} vals[s,e] * (lanes[s,e]==l)
            del cols
            # loop sublanes: build (128,128) one-hot via static slice +
            # transpose-free broadcast, then (1,128)@(128,128) on the MXU
            total = jnp.zeros((1, 128), jnp.float32)
            col_iota = jax.lax.broadcasted_iota(jnp.int32, (128, 128), 1)
            for s in range(8):
                lane_col = lanes[s:s+1, :].reshape(128, 1)    # (128,1)
                oh = (lane_col == col_iota).astype(jnp.float32)
                total = total + jnp.dot(vals[s:s+1, :], oh,
                                        preferred_element_type=jnp.float32)
            acc_ref[pl.ds(d, 1), :] += total
            return 0
        jax.lax.fori_loop(0, TILE // 8, do_block, 0)

    @jax.jit
    def run(dblk, lanes, vals):
        return pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((ACC_R, 128), jnp.float32),
            grid=(R_EDGES // TILE,),
            in_specs=[
                pl.BlockSpec((TILE // 8, 1), lambda i: (i, 0),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec((TILE, 128), lambda i: (i, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((TILE, 128), lambda i: (i, 0),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            interpret=INTERPRET,
        )(dblk, lanes, vals)

    rng = np.random.default_rng(0)
    dblk = jnp.asarray(rng.integers(0, ACC_R, (R_EDGES // 8, 1)),
                       dtype=jnp.int32)
    lanes = jnp.asarray(rng.integers(0, 128, (R_EDGES, 128)), dtype=jnp.int32)
    vals = jnp.asarray(rng.random((R_EDGES, 128), dtype=np.float32))
    try:
        dt = timeit1(run, dblk, lanes, vals)
    except Exception as e:  # noqa: BLE001
        print(f"  s3_scatter: FAILED {type(e).__name__}: {str(e)[:300]}")
        return
    print(f"  s3_scatter: {dt*1e3:8.2f} ms/pass  {E/dt/1e6:9.0f} Melem/s")


if __name__ == "__main__":
    print(f"platform: {jax.devices()[0].platform}")
    rng = np.random.default_rng(1)
    print("XLA take on 12M elements (amortized in-loop):")
    bench_xla_take("random_dup", rng.integers(0, E, E))
    bench_xla_take("random_perm", rng.permutation(E))
    # banded perm: within blocks of 64K, a random permutation
    B = 65536
    banded = (np.arange(E) // B) * B + np.concatenate(
        [rng.permutation(B) for _ in range(E // B)])
    bench_xla_take("banded_perm_64K", banded)
    bench_xla_take("identity", np.arange(E))
    print("pallas primitives:")
    bench_dynslice_gather()
    bench_onehot_scatter()
