"""Pallas kernel microbench: fused LARS kernels + flash_decode vs the
pure-jnp oracles, across a shape sweep.

On this CPU container the kernels execute in interpret mode, so the
numbers are CORRECTNESS + op-count evidence, not TPU wall-times (the
jnp oracle column is the meaningful CPU timing; the kernels' value on
real TPU is the fused single-pass HBM traffic, see DESIGN.md §7).

Usage: PYTHONPATH=src python -m benchmarks.kernel_bench [--quick]
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops, ref
from repro.launch.compile_cache import enable_compile_cache


def timeit(fn, *args, iters=3):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters, out


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()

    shapes = [(1, 4096), (8, 8192)] if args.quick else \
        [(1, 4096), (8, 8192), (16, 65536)]
    print("# lars_norms / lars_apply (interpret-mode Pallas vs jnp ref)")
    for L, n in shapes:
        key = jax.random.key(L * n)
        w = jax.random.normal(key, (L, n), jnp.float32)
        g = 0.01 * w
        m = jnp.zeros_like(w)
        stacked = L > 1
        t_ref, (wn_r, gn_r) = timeit(
            jax.jit(lambda w, g: ref.lars_norms(w, g, stacked=stacked)), w, g)
        t_k, (wn_k, gn_k) = timeit(
            jax.jit(lambda w, g: ops.lars_norms(w, g, stacked=stacked)), w, g)
        np.testing.assert_allclose(wn_k, wn_r, rtol=1e-5)
        lr = jnp.full((L,) if stacked else (), 0.01)
        t_ar, (w_r, m_r) = timeit(jax.jit(
            lambda w, g, m: ref.lars_apply(w, g, m, local_lr=lr,
                                           momentum=0.9, weight_decay=1e-4)),
            w, g, m)
        t_ak, (w_k, m_k) = timeit(jax.jit(
            lambda w, g, m: ops.lars_apply(w, g, m, local_lr=lr,
                                           momentum=0.9, weight_decay=1e-4)),
            w, g, m)
        np.testing.assert_allclose(w_k, w_r, rtol=1e-5, atol=1e-6)
        print(f"  ({L:2d},{n:6d}) norms ref {t_ref*1e3:7.2f}ms "
              f"pallas(interp) {t_k*1e3:7.2f}ms | apply ref "
              f"{t_ar*1e3:7.2f}ms pallas(interp) {t_ak*1e3:7.2f}ms  OK",
              flush=True)

    print("# flash_decode (interpret) vs blockwise-jnp oracle")
    dshapes = [(2, 8, 2, 64, 512)] if args.quick else \
        [(2, 8, 2, 64, 512), (4, 16, 4, 64, 2048)]
    for B, H, Hkv, D, S in dshapes:
        ks = jax.random.split(jax.random.key(S), 3)
        q = jax.random.normal(ks[0], (B, H, D), jnp.float32)
        k = jax.random.normal(ks[1], (B, S, Hkv, D), jnp.float32)
        v = jax.random.normal(ks[2], (B, S, Hkv, D), jnp.float32)
        lens = jnp.full((B,), S, jnp.int32)
        t_r, o_r = timeit(jax.jit(ref.flash_decode), q, k, v, lens)
        t_k, o_k = timeit(jax.jit(ops.flash_decode), q, k, v, lens)
        np.testing.assert_allclose(o_k, o_r, rtol=2e-4, atol=2e-5)
        print(f"  B{B} H{H} S{S}: ref {t_r*1e3:7.2f}ms "
              f"pallas(interp) {t_k*1e3:7.2f}ms  OK", flush=True)


if __name__ == "__main__":
    main()
