"""Micro-profiles for the two hot kernels: per-family selector time at 1M,
GBT tree growth vs chunk size, IRLS sweep pass structure.  Run on TPU."""
import os, sys
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import sys
import time

import numpy as np

sys.path.insert(0, ".")


def timeit(fn, reps=3):
    fn()  # warm
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn()
    import jax
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


def main():
    import jax
    import jax.numpy as jnp

    from transmogrifai_tpu.models import trees as T

    n, d = 1_000_000, 128
    rng = np.random.default_rng(0)
    binned = jnp.asarray(rng.integers(0, 65, size=(n, d), dtype=np.int32))
    grad = jnp.asarray(rng.normal(size=(n, 1)).astype(np.float32))
    hess = jnp.asarray(rng.uniform(0.1, 1, size=(n, 1)).astype(np.float32))
    fm = jnp.ones(d, jnp.float32)

    for chunk in (8192, 32768, 131072):
        T._HIST_CHUNK = chunk
        jax.clear_caches()

        @jax.jit
        def grow(b, g, h):
            tree, node = T._grow_tree(
                b, g, h, fm, jax.random.PRNGKey(0), 6, 64,
                jnp.float32(1.0), jnp.float32(0.0), jnp.float32(0.0),
                jnp.float32(1.0), jnp.float32(0.3), jnp.float32(0.0))
            return tree.value.sum() + node.sum()

        dt = timeit(lambda: grow(binned, grad, hess))
        print(f"grow_tree depth6 chunk={chunk}: {dt*1000:.1f} ms "
              f"({2*6*n*d*4/dt/1e9:.1f} GB/s)")

    # GBT 10 rounds end-to-end at best chunk
    T._HIST_CHUNK = 131072
    jax.clear_caches()
    y = (rng.random(n) < 0.5).astype(np.float32)
    yd = jnp.asarray(y)
    w = jnp.ones(n, jnp.float32)

    @jax.jit
    def gbt10(b, yy, ww):
        m, trees = T._fit_gbt_impl(
            b, yy, ww, jax.random.PRNGKey(0), 10, 3, 64, "binary:logistic",
            1, 1.0, 1.0, 1.0, jnp.float32(0.3), jnp.float32(1.0),
            jnp.float32(0.0), jnp.float32(0.0), jnp.float32(1.0),
            jnp.float32(1.0), jnp.float32(0.0), jnp.zeros(1))
        return m.sum()

    dt = timeit(lambda: gbt10(binned, yd, w), reps=2)
    print(f"gbt 10 rounds depth3: {dt:.2f} s -> 50 rounds ~ {5*dt:.1f} s")

    # forest: 10 trees x 3 folds vmap, depth 6
    @jax.jit
    def forest(b, yc, ww, fms, bw):
        trees, nodes = T._fit_forest_impl(b, yc, ww, 6, 64,
                                          jnp.float32(0.0), jnp.float32(1.0),
                                          fms, bw)
        return trees.value.sum()

    fms = jnp.ones((10, d), jnp.float32)
    bw = jnp.asarray(rng.poisson(1.0, size=(10, n)).astype(np.float32))
    yc = yd[:, None]
    dt = timeit(lambda: forest(binned, yc, w, fms, bw), reps=2)
    print(f"forest 10 trees depth6: {dt:.2f} s")

    # IRLS sweep structure at 250k
    from transmogrifai_tpu.models.logistic import _irls_sweep

    n2 = 262144
    x = jnp.asarray(rng.normal(size=(n2, d + 1)).astype(np.float32))
    y2 = jnp.asarray((rng.random(n2) < 0.5).astype(np.float32))
    tw = jnp.asarray(np.ones((3, n2), np.float32))
    regs = jnp.asarray(np.logspace(-4, 0, 8).astype(np.float32))
    dt = timeit(lambda: _irls_sweep(x, y2, tw, regs, 30))
    flops = 8 * 3 * 30 * (2.0 * n2 * d * d)
    print(f"irls_sweep 8x3x30 at 250k: {dt:.3f} s  "
          f"({flops/dt/1e12:.1f} TF/s, {flops/dt/1e12/197:.3f} mfu) "
          f"traffic>= {8*3*30*3*n2*(d+1)*4/dt/1e9:.0f} GB/s")


if __name__ == "__main__":
    main()
