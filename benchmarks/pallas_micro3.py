"""Microbench 3: lane-gather + transpose throughput, amortized in-loop.

These two ops are the primitives of the radix-routed PageRank kernel:
  - sandwich [lane-perm][transpose][lane-perm][transpose][lane-perm]
    realizes an arbitrary permutation of a (128,128) tile
  - a 2-stage radix-32 split built from sandwiches realizes the fixed
    CSR->CSC edge permutation
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


def _sync(out):
    # transfer ONE element only: a full-array transfer would swamp the
    # measurement
    return float(np.asarray(out[:1, :1]))


def timeit1(fn, *args, n=3):
    _sync(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        _sync(fn(*args))
    return (time.perf_counter() - t0) / n


def bench_lane_gather_loop(R=4096, iters=500):
    """Chained lane-gathers on (R,128) inside one pallas call."""
    def kernel(x_ref, idx_ref, o_ref):
        def body(_, acc):
            return jnp.take_along_axis(acc, idx_ref[:], axis=1,
                                       mode="promise_in_bounds") + 1.0
        o_ref[:] = jax.lax.fori_loop(0, iters, body, x_ref[:])

    @jax.jit
    def run(x, idx):
        return pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((R, 128), jnp.float32),
            in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM),
                      pl.BlockSpec(memory_space=pltpu.VMEM)],
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            interpret=INTERPRET,
        )(x, idx)

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.random((R, 128), dtype=np.float32))
    idx = jnp.asarray(rng.integers(0, 128, (R, 128)), dtype=jnp.int32)
    try:
        dt = timeit1(run, x, idx) / iters
    except Exception as e:  # noqa: BLE001
        print(f"  lane_gather_loop: FAILED {type(e).__name__}: {str(e)[:200]}")
        return
    print(f"  lane_gather R={R}: {dt*1e6:9.1f} us/op  "
          f"{R*128/dt/1e9:7.2f} Gelem/s")


def bench_transpose_loop(R=8192, iters=500):
    """Per-(128,128)-tile transpose over an (R,128) array, chained."""
    T = R // 128

    def kernel(x_ref, o_ref):
        def body(_, acc):
            # transpose each (128,128) tile; static unroll over tiles would
            # be huge, use reshape trick: (T,128,128) transpose last two dims
            a = acc.reshape(T, 128, 128)
            return jnp.swapaxes(a, 1, 2).reshape(R, 128) + 1.0
        o_ref[:] = jax.lax.fori_loop(0, iters, body, x_ref[:])

    @jax.jit
    def run(x):
        return pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((R, 128), jnp.float32),
            in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            interpret=INTERPRET,
        )(x)

    x = jnp.ones((R, 128), jnp.float32)
    try:
        dt = timeit1(run, x) / iters
    except Exception as e:  # noqa: BLE001
        print(f"  transpose_loop: FAILED {type(e).__name__}: {str(e)[:200]}")
        return
    print(f"  tiled transpose R={R}: {dt*1e6:9.1f} us/op  "
          f"{R*128/dt/1e9:7.2f} Gelem/s")


def bench_sandwich(R=4096, iters=200):
    """Full within-tile permutation sandwich: 3 lane-gathers + 2 transposes."""
    T = R // 128

    def kernel(x_ref, s1_ref, s2_ref, s3_ref, o_ref):
        def tr(a):
            return jnp.swapaxes(a.reshape(T, 128, 128), 1, 2).reshape(R, 128)

        def body(_, acc):
            a = jnp.take_along_axis(acc, s1_ref[:], axis=1,
                                    mode="promise_in_bounds")
            a = tr(a)
            a = jnp.take_along_axis(a, s2_ref[:], axis=1,
                                    mode="promise_in_bounds")
            a = tr(a)
            a = jnp.take_along_axis(a, s3_ref[:], axis=1,
                                    mode="promise_in_bounds")
            return a
        o_ref[:] = jax.lax.fori_loop(0, iters, body, x_ref[:])

    @jax.jit
    def run(x, s1, s2, s3):
        return pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((R, 128), jnp.float32),
            in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)] * 4,
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            interpret=INTERPRET,
        )(x, s1, s2, s3)

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.random((R, 128), dtype=np.float32))
    idx = [jnp.asarray(rng.integers(0, 128, (R, 128)), dtype=jnp.int32)
           for _ in range(3)]
    try:
        dt = timeit1(run, x, *idx) / iters
    except Exception as e:  # noqa: BLE001
        print(f"  sandwich: FAILED {type(e).__name__}: {str(e)[:200]}")
        return
    print(f"  sandwich R={R}: {dt*1e6:9.1f} us/op  "
          f"{R*128/dt/1e9:7.2f} Gelem/s  (full tile perms)")


def bench_big_matmul(iters=500):
    """Reference point: (1024,2048)@(2048,128) matmul rate."""
    def kernel(a_ref, b_ref, o_ref):
        def body(_, acc):
            return acc + jnp.dot(a_ref[:], b_ref[:],
                                 preferred_element_type=jnp.float32)[:1024]
        o_ref[:] = jax.lax.fori_loop(
            0, iters, body, jnp.zeros((1024, 128), jnp.float32))

    @jax.jit
    def run(a, b):
        return pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((1024, 128), jnp.float32),
            in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)] * 2,
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            interpret=INTERPRET,
        )(a, b)

    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.random((1024, 2048), dtype=np.float32))
    b = jnp.asarray(rng.random((2048, 128), dtype=np.float32))
    try:
        dt = timeit1(run, a, b) / iters
    except Exception as e:  # noqa: BLE001
        print(f"  big_matmul: FAILED {type(e).__name__}: {str(e)[:200]}")
        return
    fl = 1024 * 2048 * 128 * 2
    print(f"  matmul 1024x2048x128: {dt*1e6:9.1f} us  {fl/dt/1e12:6.2f} Tflop/s")


if __name__ == "__main__":
    print(f"platform: {jax.devices()[0].platform}")
    bench_lane_gather_loop()
    bench_transpose_loop()
    bench_sandwich()
    bench_big_matmul()
