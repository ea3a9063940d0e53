"""Pallas tree-histogram kernel study (VERDICT r3 next-round #1).

The round-3 verdict prescribed replacing the one-hot-matmul histogram in
``models/trees.py`` with a Pallas kernel (VMEM bin accumulators, packed
codes, feature-parallel grid).  This script IS that kernel, in three
variants, measured against the production XLA formulation on v5e.

Findings (docs/performance.md "The histogram kernel, measured to its
floor"): every variant and the XLA path are bound by constructing B*n*d
one-hot elements per level on the VPU; the matmul M dimension equals the
channel count (2K*parents*lanes), so at thin channels the MXU idles no
matter where the accumulator lives, and XLA's fused one-hot (which avoids
the HBM spill at _HIST_CHUNK=2048) is the faster formulation at every
measured channel count.  The production code therefore keeps the XLA
formulation; this prototype is retained as the measured evidence, and as
the starting point should Mosaic grow int8-compare / sub-byte support that
changes the floor.

Run on a TPU host: ``python benchmarks/pallas_hist_prototype.py``
Prints one JSON line per variant: {"variant", "ms_per_level", ...}.

Reference role: the XGBoost C++ ``hist`` builder (GHistBuilder,
src/common/hist_util.cc) — same (node, feature, bin) gradient/hessian
histograms, scatter-free TPU formulation.
"""
import json
import sys
import time

import numpy as np

N = 1_000_000
D = 128
NBINS = 64
B = NBINS + 1


def _kernels():
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def hist_masks(codes, acc, c_pad, R, unroll):
        """Per-bin compare masks in VMEM; per-bin (C, R) @ (R, D) matmuls."""
        n = codes.shape[0]

        def kernel(codes_ref, acc_ref, hist_ref):
            i = pl.program_id(0)

            @pl.when(i == 0)
            def _():
                hist_ref[:] = jnp.zeros_like(hist_ref)

            codes_blk = codes_ref[:].astype(jnp.int32)  # once per chunk
            acc_blk = acc_ref[:]

            def one(b):
                # Mosaic v5e supports i32/f32 compares only (no i8/bf16)
                mask = (codes_blk == b).astype(jnp.bfloat16)
                part = jax.lax.dot_general(
                    acc_blk, mask, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                hist_ref[b] += part

            if unroll:
                for b in range(B):
                    one(b)
            else:
                def body(b, _):
                    one(b)
                    return 0
                jax.lax.fori_loop(0, B, body, 0)

        return pl.pallas_call(
            kernel,
            grid=(n // R,),
            in_specs=[
                pl.BlockSpec((R, D), lambda i: (i, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((c_pad, R), lambda i: (0, i),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec((B, c_pad, D), lambda i: (0, 0, 0),
                                   memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct((B, c_pad, D), jnp.float32),
        )(codes, acc)

    def hist_radix(codes, acc, c_pad, R):
        """Radix masks: 9+8 digit one-hots built once per chunk (17 compares),
        then each bin mask is ONE bf16 multiply.  Same measured floor — the
        per-element materialization, not the compare count, binds."""
        n = codes.shape[0]
        HI, LO = 9, 8  # b = 8*hi + lo for B = 65

        def kernel(codes_ref, acc_ref, hist_ref, ohhi_ref, ohlo_ref):
            i = pl.program_id(0)

            @pl.when(i == 0)
            def _():
                hist_ref[:] = jnp.zeros_like(hist_ref)

            codes_blk = codes_ref[:].astype(jnp.int32)
            hi = codes_blk // LO
            lo = codes_blk % LO
            acc_blk = acc_ref[:]
            for h in range(HI):
                ohhi_ref[h] = (hi == h).astype(jnp.bfloat16)
            for l in range(LO):
                ohlo_ref[l] = (lo == l).astype(jnp.bfloat16)
            for b in range(B):
                mask = ohhi_ref[b // LO] * ohlo_ref[b % LO]
                part = jax.lax.dot_general(
                    acc_blk, mask, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                hist_ref[b] += part

        return pl.pallas_call(
            kernel,
            grid=(n // R,),
            in_specs=[
                pl.BlockSpec((R, D), lambda i: (i, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((c_pad, R), lambda i: (0, i),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec((B, c_pad, D), lambda i: (0, 0, 0),
                                   memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct((B, c_pad, D), jnp.float32),
            scratch_shapes=[
                pltpu.VMEM((HI, R, D), jnp.bfloat16),
                pltpu.VMEM((LO, R, D), jnp.bfloat16),
            ],
        )(codes, acc)

    return hist_masks, hist_radix


def main():
    import jax
    import jax.numpy as jnp

    if jax.default_backend() != "tpu":
        print(json.dumps({"variant": "skipped", "reason": "needs TPU"}))
        return
    hist_masks, hist_radix = _kernels()

    def make_data(key, n, c):
        k1, k2 = jax.random.split(key)
        codes = jax.random.randint(k1, (n, D), 0, B,
                                   dtype=jnp.int32).astype(jnp.int8)
        acc = jax.random.normal(k2, (c, n), dtype=jnp.bfloat16)
        return codes, acc

    def timeit(fn, *args, reps=3):
        out = fn(*args)
        np.asarray(out)  # hard host sync
        t0 = time.perf_counter()
        for _ in range(reps):
            out = fn(*args)
        np.asarray(out)
        return (time.perf_counter() - t0) / reps

    # parity vs a numpy one-hot reference at small n
    codes_s, acc_s = make_data(jax.random.PRNGKey(1), 4096, 8)
    hist_k = np.asarray(hist_masks(codes_s, acc_s, 8, 1024, True))
    oh = (np.asarray(codes_s)[:, None, :] ==
          np.arange(B, dtype=np.int8)[None, :, None])
    ref = np.einsum("cn,nbd->bcd", np.asarray(acc_s, np.float32),
                    oh.astype(np.float32))
    err = float(np.abs(hist_k - ref).max() / (np.abs(ref).max() + 1e-9))

    key = jax.random.PRNGKey(0)
    for tag, builder in [
        ("masks-fori-R2048", lambda c, a, cp: hist_masks(c, a, cp, 2048,
                                                         False)),
        ("masks-unroll-R2048", lambda c, a, cp: hist_masks(c, a, cp, 2048,
                                                           True)),
        ("radix-R1024", lambda c, a, cp: hist_radix(c, a, cp, 1024)),
    ]:
        for C in (2, 16, 32):
            c_pad = max(8, C)
            codes, acc = make_data(key, N, c_pad)
            jax.block_until_ready((codes, acc))
            f = jax.jit(lambda c, a, cp=c_pad, b=builder: b(c, a, cp))
            dt = timeit(f, codes, acc)
            print(json.dumps({
                "variant": tag, "channels": C,
                "ms_per_level": round(dt * 1e3, 2),
                "tflops": round(2 * N * c_pad * B * D / dt / 1e12, 2),
                "parity_max_rel_err": err,
            }), flush=True)


if __name__ == "__main__":
    sys.exit(main())
