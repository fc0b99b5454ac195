"""Decisive TPU microbench: scatter vs dense one-hot for the cache ops.

Fused loops (ITERS inside one jit), timed over several calls; prints us/op.
Tests per-row-count scaling of scatter and the dense masked-write
alternative at L1 (128 sets) and L2 (1024 sets) geometry, int64 payloads.
"""

import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

import graphite_tpu  # noqa: F401,E402  (x64 + the compile-cache policy)

ITERS = 300
CALLS = 3


def fused(body, init):
    @jax.jit
    def loop(c):
        return jax.lax.fori_loop(0, ITERS, body, c)

    jax.block_until_ready(loop(init))
    t0 = time.perf_counter()
    for _ in range(CALLS):
        out = loop(init)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / CALLS / ITERS * 1e6


def main():
    A = 8
    for T in (64, 1024):
        for SETS in (128, 1024):
            rng = np.random.default_rng(0)
            word = jnp.asarray(rng.integers(0, 1 << 60, (A, T, SETS)),
                               jnp.int64)
            sidx0 = jnp.asarray(rng.integers(0, SETS - 2, (T,)), jnp.int32)
            way0 = jnp.asarray(rng.integers(0, A, (T,)), jnp.int32)
            rows = jnp.arange(T, dtype=jnp.int32)

            def scatter_touch(i, c):
                w, s = c
                sidx = sidx0 + s % 2
                w = w.at[way0, rows, sidx].max(
                    jnp.int64(123) + s, mode="drop")
                return w, s + (w[0, 0, 0] % 2).astype(jnp.int32)

            def dense_touch(i, c):
                w, s = c
                sidx = sidx0 + s % 2
                oh = sidx[:, None] == jnp.arange(SETS, dtype=jnp.int32)
                woh = way0[:, None] == jnp.arange(A, dtype=jnp.int32)
                sel = woh.T[:, :, None] & oh[None, :, :]
                w = jnp.where(sel, jnp.maximum(w, jnp.int64(123) + s), w)
                return w, s + (w[0, 0, 0] % 2).astype(jnp.int32)

            def gather_probe(i, c):
                w, s = c
                sidx = sidx0 + s % 2
                row = jnp.take_along_axis(w, sidx[None, :, None], axis=2)
                return w, s + (row[0, 0, 0] % 2).astype(jnp.int32)

            def dense_probe(i, c):
                w, s = c
                sidx = sidx0 + s % 2
                oh = sidx[:, None] == jnp.arange(SETS, dtype=jnp.int32)
                row = jnp.sum(jnp.where(oh[None], w, 0), axis=2)
                return w, s + (row[0, 0] % 2).astype(jnp.int32)

            init = (word, jnp.int32(0))
            r = {"T": T, "SETS": SETS}
            r["scatter_touch_us"] = round(fused(scatter_touch, init), 1)
            r["dense_touch_us"] = round(fused(dense_touch, init), 1)
            r["gather_probe_us"] = round(fused(gather_probe, init), 1)
            r["dense_probe_us"] = round(fused(dense_probe, init), 1)
            print(json.dumps(r), flush=True)


if __name__ == "__main__":
    main()
