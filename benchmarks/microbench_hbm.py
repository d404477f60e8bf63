"""On-chip microbench: large-array streaming rates for the kernel's primitive
mix (FMA stream, Benes masked-swap stage, roll, one-hot einsum, transpose).

Safety: runs with an internal deadline and exits cleanly (never SIGTERM
a process with in-flight TPU work). Sync via 1-element host transfer.

Usage: python benchmarks/microbench_hbm.py [deadline_s]
"""
import json
import sys
import time

DEADLINE = float(sys.argv[1]) if len(sys.argv) > 1 else 240.0
T0 = time.perf_counter()


def left():
    return DEADLINE - (time.perf_counter() - T0)


def main():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from functools import partial

    results = {"platform": jax.devices()[0].platform}

    def timeit(name, fn, *args, iters_in_loop=1, reps=2):
        """fn must be jitted and return an array; sync via 1-elem transfer."""
        if left() < 20:
            results[name] = None
            return None
        out = fn(*args)
        _ = float(jnp.ravel(out)[0])  # compile+warm
        best = float("inf")
        for _ in range(reps):
            t = time.perf_counter()
            out = fn(*args)
            _ = float(jnp.ravel(out)[0])
            best = min(best, time.perf_counter() - t)
        per = best / iters_in_loop
        results[name] = round(per * 1e3, 3)  # ms per inner iteration
        print(f"{name}: {per*1e3:.3f} ms", file=sys.stderr, flush=True)
        return per

    # 1) FMA stream at several working-set sizes: x = a*x + b, L loop iters
    for m_elems in (4, 16, 32, 64):
        n = m_elems * 1024 * 1024
        L = 20

        @partial(jax.jit, static_argnames=())
        def fma_loop(x):
            def body(i, x):
                return x * 1.000001 + 1e-9
            return jax.lax.fori_loop(0, L, body, x)

        x = jnp.ones(n, jnp.float32)
        per = timeit(f"fma_{m_elems}M_f32_ms", fma_loop, x, iters_in_loop=L)
        if per:
            gbs = 2 * 4 * n / per / 1e9
            results[f"fma_{m_elems}M_f32_gbs"] = round(gbs, 1)
            print(f"  -> {gbs:.0f} GB/s", file=sys.stderr, flush=True)

    # 2) Benes radix-2 stage chain at N=2^24, f32 vs bf16, bool masks
    N = 1 << 24
    rng = np.random.default_rng(0)
    nstages = 8  # representative distances, incl. small + large
    dists = [1 << k for k in (23, 20, 16, 12, 8, 4, 1, 0)]
    masks_np = rng.random((nstages, N)) < 0.5

    def benes_chain(x, masks):
        for s, d in enumerate(dists):
            d = max(d, 1)
            y = x.reshape(N // (2 * d), 2, d)
            sw = jnp.flip(y, axis=1).reshape(N)
            x = jnp.where(masks[s], sw, x)
        return x

    for dt, tag in ((jnp.float32, "f32"), (jnp.bfloat16, "bf16")):
        x = jnp.ones(N, dt)
        masks = jnp.asarray(masks_np)
        jitted = jax.jit(lambda x, m: benes_chain(x, m))
        per = timeit(f"benes8_{tag}_ms", jitted, x, masks, iters_in_loop=8)
        if per:
            results[f"benes8_{tag}_gbs"] = round(
                (2 * x.dtype.itemsize + 1) * N / per / 1e9, 1)

    # 2b) same but masks unpacked on the fly from packed bits
    packed_np = np.packbits(masks_np, axis=1)

    def benes_chain_packed(x, packed):
        shifts = jnp.arange(7, -1, -1, dtype=jnp.uint8)
        for s, d in enumerate(dists):
            d = max(d, 1)
            bits = ((packed[s][:, None] >> shifts) & 1).reshape(N) != 0
            y = x.reshape(N // (2 * d), 2, d)
            sw = jnp.flip(y, axis=1).reshape(N)
            x = jnp.where(bits, sw, x)
        return x

    x = jnp.ones(N, jnp.bfloat16)
    packed = jnp.asarray(packed_np)
    timeit("benes8_bf16_packedmask_ms", jax.jit(benes_chain_packed), x,
           packed, iters_in_loop=8)

    # 2c) radix-4 stage: 4-way rotate + 2-bit select
    sel_np = rng.integers(0, 4, N).astype(np.int8)

    def radix4_chain(x, sel):
        for d in (1 << 22, 1 << 12, 1 << 2, 1):
            y = x.reshape(N // (4 * d), 4, d)
            r1 = jnp.roll(y, -1, axis=1).reshape(N)
            r2 = jnp.roll(y, -2, axis=1).reshape(N)
            r3 = jnp.roll(y, -3, axis=1).reshape(N)
            x0 = x
            lo = jnp.where((sel & 1) != 0, r1, x0)
            hi = jnp.where((sel & 1) != 0, r3, r2)
            x = jnp.where((sel & 2) != 0, hi, lo)
        return x

    x = jnp.ones(N, jnp.bfloat16)
    sel = jnp.asarray(sel_np)
    timeit("radix4x4_bf16_ms", jax.jit(radix4_chain), x, sel,
           iters_in_loop=4)

    # 3) one-hot extract einsum (C,R_C,K_C)x(C,R_C,128), static bf16 one-hot
    C, R_C, K_C = 350, 256, 256
    ohe = jnp.asarray(rng.random((C, R_C, K_C)) < 0.004, jnp.bfloat16)
    xc = jnp.ones((C, R_C, 128), jnp.bfloat16)

    @jax.jit
    def extract(ohe, xc):
        return jnp.einsum("cik,cil->ckl", ohe, xc,
                          preferred_element_type=jnp.float32)

    timeit("extract_einsum_bf16_ms", extract, ohe, xc)

    # 4) big transpose
    A = 4096
    xt = jnp.ones((A, A), jnp.float32)
    timeit("transpose_4096_ms", jax.jit(lambda x: x.T + 0.0), xt)

    # 5) expand einsum at real plan shape: oh (62,1280,128) x (62,128,128)
    G, R_G = 62, 1280
    oh = jnp.asarray(rng.random((G, R_G, 128)) < 0.008, jnp.bfloat16)
    rank = jnp.ones((G, 128, 128), jnp.bfloat16)

    @jax.jit
    def expand(oh, rank):
        return jnp.einsum("grw,gwl->grl", oh, rank,
                          preferred_element_type=jnp.float32)

    timeit("expand_einsum_bf16_ms", expand, oh, rank)

    print(json.dumps(results), flush=True)


if __name__ == "__main__":
    main()
