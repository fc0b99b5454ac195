"""Microbenchmark: device-side cost of gather/scatter vs dense one-hot.

Each candidate op runs N times inside one jitted fori_loop returning a
scalar; we time several whole-loop calls and divide.  N is large enough
(1000) that the per-call dispatch overhead amortizes.
"""

import json
import time

import jax
import jax.numpy as jnp
import numpy as np

N = 1000
CALLS = 3


def fused_cost(body, init):
    @jax.jit
    def loop(c):
        c = jax.lax.fori_loop(0, N, body, c)
        return jax.tree_util.tree_map(
            lambda x: x.ravel()[0] if hasattr(x, "ravel") else x, c)

    jax.block_until_ready(loop(init))  # compile
    t0 = time.perf_counter()
    for _ in range(CALLS):
        out = loop(init)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / CALLS / N * 1e6  # us per op


def main():
    for T in (64, 1024):
        A, SETS, K = 8, 1024, 16
        rng = np.random.default_rng(0)
        arr0 = jnp.asarray(rng.integers(0, 1 << 30, (A, T, SETS)), jnp.int32)
        sidxK0 = jnp.asarray(rng.integers(0, SETS - 2, (T, K)), jnp.int32)
        rows = jnp.arange(T)
        vals = jnp.asarray(rng.integers(0, 1 << 20, (T,)), jnp.int32)

        base = fused_cost(lambda i, c: c + 1, jnp.int32(0))

        def mk(body):
            return fused_cost(body, (arr0, sidxK0, jnp.int32(0))) - base

        def dense_probe(i, c):
            arr, sidxK, s = c
            sidx = sidxK[:, 0] + s % 2
            oh = sidx[:, None] == jnp.arange(SETS)[None, :]
            row = jnp.sum(jnp.where(oh[None], arr, 0), axis=2)
            return arr, sidxK, s + row[0, 0] % 2

        def taa_probe(i, c):
            arr, sidxK, s = c
            sidx = sidxK[:, 0] + s % 2
            row = jnp.take_along_axis(arr, sidx[None, :, None], axis=2)
            return arr, sidxK, s + row[0, 0, 0] % 2

        def block_probe(i, c):
            arr, sidxK, s = c
            blk = jnp.take_along_axis(arr, (sidxK + s % 2)[None], axis=2)
            return arr, sidxK, s + blk[0, 0, 0] % 2

        def scat(i, c):
            arr, sidxK, s = c
            arr = arr.at[0, rows, sidxK[:, 0] + s % 2].set(vals + s)
            return arr, sidxK, s + arr[0, 0, 0] % 2

        def scatK(i, c):
            arr, sidxK, s = c
            arr = arr.at[0, rows[:, None], sidxK + s % 2].max(
                vals[:, None] + s)
            return arr, sidxK, s + arr[0, 0, 0] % 2

        def dense_write(i, c):
            arr, sidxK, s = c
            sidx = sidxK[:, 0] + s % 2
            oh = sidx[:, None] == jnp.arange(SETS)[None, :]
            arr = jnp.where(oh[None], (vals + s)[None, :, None], arr)
            return arr, sidxK, s + arr[0, 0, 0] % 2

        def sortk(i, c):
            arr, sidxK, s = c
            v = jnp.sort(vals + s)
            return arr, sidxK, s + v[0] % 2

        def lexsort2(i, c):
            arr, sidxK, s = c
            o = jnp.lexsort((vals + s, vals))
            return arr, sidxK, s + o[0] % 2

        r = {"T": T, "empty_us": round(base, 2)}
        for name, body in [("dense_probe", dense_probe),
                           ("taa_probe", taa_probe),
                           ("block_probe", block_probe),
                           ("scatter", scat), ("scatterK", scatK),
                           ("dense_write", dense_write),
                           ("sort", sortk), ("lexsort", lexsort2)]:
            r[name + "_us"] = round(mk(body), 2)
        print(json.dumps(r), flush=True)


if __name__ == "__main__":
    main()
