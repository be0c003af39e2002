"""Sustained-throughput microbench of chained matmuls and convolutions.

Methodology (the hard-won part): the loop body must CONSUME the
previous iteration's full output, or XLA deletes the work —
``y * 0`` is constant-folded, ``y[0, 0]`` is strength-reduced to a
row-column dot, and a loop-invariant ``a @ b`` is hoisted.  Earlier
probes fell for all three and over-reported by ~17x.  Here each
iteration's output IS the next iteration's input (like a real
network), weights are scaled to keep unit variance, and we divide by
the number of chained applications.  Dispatch cost amortizes across
the chain inside ONE jitted program.

Run: python tools/microbench.py [matmul|conv|all]
"""
import functools
import sys
import time

import jax
import jax.numpy as jnp


def sustained(apply_fn, x0, n=50, repeats=3):
    """Time n chained applications of apply_fn inside one jit program.

    apply_fn: x -> y with y.shape == x.shape (shape-preserving so the
    chain is expressible as fori_loop).  Returns seconds per
    application, best of `repeats`.
    """
    @jax.jit
    def run(x):
        return jax.lax.fori_loop(0, n, lambda i, x: apply_fn(x), x)

    out = run(x0)
    float(jnp.sum(out))  # compile + drain (host read = real sync)
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = run(x0)
        float(jnp.sum(out))
        best = min(best, (time.perf_counter() - t0) / n)
    return best


def bench_matmul():
    print("== sustained matmul (chained y = y @ W) ==")
    rows = []
    for (M, K) in [(4096, 4096), (8192, 8192), (50176, 256),
                   (50176, 1024), (6272, 1024), (8192, 1024)]:
        x = jax.random.normal(jax.random.PRNGKey(0), (M, K), jnp.bfloat16)
        w = (jax.random.normal(jax.random.PRNGKey(1), (K, K), jnp.bfloat16)
             / (K ** 0.5))
        t = sustained(lambda x: x @ w, x)
        tf = 2 * M * K * K / t / 1e12
        rows.append((M, K, tf, t * 1e3))
        print(f"  ({M},{K})@({K},{K}): {tf:.1f} TF/s  ({t*1e3:.2f} ms/op)")
    return rows


def bench_conv():
    print("== sustained conv 3x3 s1 SAME NHWC (chained, C=O) ==")
    for (H, C, N) in [(14, 256, 256), (28, 128, 256), (7, 512, 256),
                      (56, 64, 256), (14, 512, 256)]:
        x = jax.random.normal(jax.random.PRNGKey(0), (N, H, H, C),
                              jnp.bfloat16)
        w = (jax.random.normal(jax.random.PRNGKey(1), (3, 3, C, C),
                               jnp.bfloat16) / (3 * (C ** 0.5)))

        def step(x):
            return jax.lax.conv_general_dilated(
                x, w, (1, 1), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"))

        t = sustained(step, x)
        tf = 2 * N * H * H * C * C * 9 / t / 1e12
        print(f"  b{N} {H}x{H} C={C}: {tf:.1f} TF/s  ({t*1e3:.2f} ms/op)")


if __name__ == "__main__":
    which = sys.argv[1] if len(sys.argv) > 1 else "all"
    print("device:", jax.devices()[0])
    if which in ("matmul", "all"):
        bench_matmul()
    if which in ("conv", "all"):
        bench_conv()
