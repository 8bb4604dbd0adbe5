"""Microbenchmarks of the specific ops the bulk-pass bisection
implicates: searchsorted variants, uniform_at, i64 elementwise, scans,
batched scatters."""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from shadow_tpu.core import rng


from tools.perfutil import timeit  # noqa: E402


def main():
    H, K, GH = 10240, 48, 10240
    print(f"backend: {jax.default_backend()}  H={H} K={K}")
    key = jax.random.PRNGKey(0)
    table = jnp.sort(jax.random.randint(key, (GH,), 0, 1 << 30,
                                        dtype=jnp.int32)).astype(jnp.int64)
    queries = jax.random.randint(key, (H, K), 0, 1 << 30,
                                 dtype=jnp.int32).astype(jnp.int64)

    for method in ["scan", "scan_unrolled", "compare_all", "sort"]:
        try:
            f = jax.jit(lambda t, q, m=method: jnp.searchsorted(t, q, method=m))
            print(f"searchsorted[{method:13s}]: {timeit(f, table, queries)*1e3:8.2f} ms")
        except Exception as e:
            print(f"searchsorted[{method}] failed: {type(e).__name__}")

    kd = jax.random.key_data(
        jax.vmap(jax.random.fold_in, in_axes=(None, 0))(
            jax.random.key(1), jnp.arange(H, dtype=jnp.uint32)))
    ctr = jnp.broadcast_to(jnp.arange(H, dtype=jnp.uint32)[:, None], (H, K))
    print(f"uniform_at [H,K]:        {timeit(jax.jit(rng.uniform_at), kd, ctr)*1e3:8.2f} ms")

    a64 = queries
    b64 = queries * 3
    f64 = jax.jit(lambda a, b: jnp.where(a > b, a + b, a - b))
    print(f"i64 elementwise [H,K]:   {timeit(f64, a64, b64)*1e3:8.2f} ms")
    a32 = a64.astype(jnp.int32)
    b32 = b64.astype(jnp.int32)
    f32 = jax.jit(lambda a, b: jnp.where(a > b, a + b, a - b))
    print(f"i32 elementwise [H,K]:   {timeit(f32, a32, b32)*1e3:8.2f} ms")

    fc64 = jax.jit(lambda a: jnp.cumsum(a, axis=1))
    print(f"i64 cumsum [H,K]:        {timeit(fc64, a64)*1e3:8.2f} ms")
    fc32 = jax.jit(lambda a: jnp.cumsum(a, axis=1))
    print(f"i32 cumsum [H,K]:        {timeit(fc32, a32)*1e3:8.2f} ms")

    ft = jax.jit(lambda a, o: jnp.take_along_axis(a, o, axis=1))
    order = jnp.argsort(a32, axis=1)
    print(f"take_along i64 [H,K]:    {timeit(ft, a64, order)*1e3:8.2f} ms")
    print(f"take_along i32 [H,K]:    {timeit(ft, a32, order)*1e3:8.2f} ms")

    # batched 2D scatter (the place() pattern) vs flat scatter
    M = K
    lane_h = jnp.arange(H)[:, None]
    col = jnp.where(a32 % 2 == 0, order, M)
    def place(vals):
        base = jnp.full((H, M), -1, jnp.int32)
        return base.at[lane_h, col].set(vals, mode="drop")
    print(f"batched scatter [H,K]->[H,M]: {timeit(jax.jit(place), b32)*1e3:8.2f} ms")

    flat_r = jnp.repeat(jnp.arange(H), K)
    flat_c = col.reshape(-1)
    def place_flat(vals):
        base = jnp.full((H, M), -1, jnp.int32)
        return base.at[flat_r, flat_c].set(vals.reshape(-1), mode="drop")
    print(f"flat scatter [H*K]->[H,M]:    {timeit(jax.jit(place_flat), b32)*1e3:8.2f} ms")

    # gather-based alternative: invert the permutation via argsort
    def place_gather(vals):
        # out[h, m] = vals[h, k] where col[h,k] == m  (cols unique or M)
        ordc = jnp.argsort(col, axis=1)  # positions sorted by target col
        vals_s = jnp.take_along_axis(vals, ordc, axis=1)
        col_s = jnp.take_along_axis(col, ordc, axis=1)
        hit = jnp.arange(M)[None, :] == col_s[:, :M]
        return jnp.where(hit, vals_s[:, :M], -1)
    print(f"sortgather [H,K]->[H,M]:      {timeit(jax.jit(place_gather), b32)*1e3:8.2f} ms")


if __name__ == "__main__":
    main()
