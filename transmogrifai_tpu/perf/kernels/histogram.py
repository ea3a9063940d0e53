"""Fused tree-histogram build: Pallas kernel + the XLA reference formulation.

Reference capability (SURVEY §2.9): XGBoost's C++ ``hist`` tree method — the
per-(node, class, feature, bin) gradient/hessian histogram build that
dominates GBT/RF fit time.  ``models/trees.py`` computes it as a scatter-free
one-hot GEMM row-chunked under ``lax.scan`` (TPU lowers scatters to slow
sorts); round 4 (before PRs 1-20, on a set-up that no longer exists — not
measured on today's code) put that formulation at ~4.3 TFLOPs / 0.06 HBM
utilization in the unbatched regime — bound by memory layout (constructing
``B*n*d`` one-hot elements through HBM-visible operands), not math.

The Pallas kernel (:func:`hist_level_pallas`) attacks exactly that bound:
row chunks stream through VMEM once; the node one-hot, the joint
(feature, bin) one-hot, and the (M, B*d) accumulator all live in VMEM for
the whole pass and never round-trip HBM between chunks.  The grid walks the
chunk axis; the output block is pinned to one VMEM-resident accumulator
(constant index map) initialized at step 0 — the classic Pallas reduction
pattern.

Exactness: with ``int_exact`` every operand is int8 and the accumulator
int32, so the kernel is bitwise-equal to the GEMM reference by integer
arithmetic alone (tier-1 pinned, tests/test_kernels.py).  Float paths share
the same per-chunk dot + sequential chunk-accumulation order as the
reference scan.

:func:`hist_level_xla` is the standalone always-available reference — the
same math as ``models/trees.py``'s in-place chunk scan (without the
growth-loop-specific operand pre-chunking), used by the parity tests and
``chip_smoke.py`` as the comparison baseline.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from .dispatch import HIST_CHUNK_DEFAULT


def _pad_rows(local, ghT, binned, chunk: int):
    """Zero-pad the row axis to a chunk multiple: padded gh rows are zero so
    their contribution vanishes regardless of the padded codes/nodes."""
    n = local.shape[1]
    pad = (-n) % chunk
    if pad:
        local = jnp.pad(local, ((0, 0), (0, pad)), constant_values=-1)
        ghT = jnp.pad(ghT, ((0, 0), (0, 0), (0, pad)))
        binned = jnp.pad(binned, ((0, pad), (0, 0)))
    return local, ghT, binned, n + pad


def hist_level_pallas(local: jnp.ndarray, ghT: jnp.ndarray,
                      binned: jnp.ndarray, nn: int, n_bins: int, *,
                      int_exact: bool = False, mxu_dtype=None,
                      interpret: bool = False,
                      chunk: Optional[int] = None) -> jnp.ndarray:
    """(L*nn*2K, B*d) per-(node, class, feature, bin) histograms, fused.

    local: (L, n) int32 per-lane local node index (negative = inactive row —
    its node one-hot row is all-zero, contributing nothing);
    ghT: (L, 2K, n) grad/hess channels (int8 when ``int_exact``, else the
    MXU dtype the caller chose); binned: (n, d) int32 codes in [0, n_bins].

    One Pallas program: grid over row chunks; per step the node one-hot
    (L, nn, chunk) and the joint (chunk, B*d) bin one-hot are built
    IN VMEM, contracted on the MXU, and accumulated into the VMEM-resident
    output block (pl.when-initialized at step 0).  int8 operands accumulate
    in int32 (exact); float operands go through the MXU in ``mxu_dtype``
    (bf16 on TPU, f32 in CPU parity runs — trees' ``_hist_dtype`` contract)
    and accumulate in f32.  ``chunk`` defaults to ``HIST_CHUNK_DEFAULT``.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    L, n = local.shape
    two_k = ghT.shape[1]
    d = binned.shape[1]
    B = n_bins + 1
    M = L * nn * two_k
    hdt = jnp.int8 if int_exact else jnp.dtype(mxu_dtype or ghT.dtype)
    acc_t = jnp.int32 if int_exact else jnp.float32
    chunk = int(chunk or HIST_CHUNK_DEFAULT)
    local, ghT, binned, n_p = _pad_rows(local, ghT, binned, chunk)
    grid = n_p // chunk

    def _chunk_update(lb, gh, bb):
        """The per-chunk math: node one-hot x gh contraction against the
        joint (feature, bin) one-hot."""
        node_ids = jax.lax.broadcasted_iota(jnp.int32, (1, nn, 1), 1)
        # select in a 32-bit type, then narrow: the v5e vector unit has no
        # int8 multiply or int8 sublane broadcast (Mosaic: "failed to
        # legalize 'arith.muli'", "Not implemented: Sublane broadcast"),
        # and a 0/1 mask times gh IS the select
        wide = jnp.int32 if int_exact else hdt
        in_node = lb[:, None, :] == node_ids
        acc = jnp.where(in_node[:, :, None, :],
                        gh.astype(wide)[:, None, :, :],
                        jnp.zeros((), wide)).reshape(M, chunk).astype(hdt)
        bin_ids = jax.lax.broadcasted_iota(jnp.int32, (1, B, 1), 1)
        # (chunk, B, d) layout, matching the reference: the innermost axis
        # stays the 128-lane-aligned feature dim
        bin_oh = (bb[:, None, :] == bin_ids).astype(hdt) \
            .reshape(chunk, B * d)
        return jax.lax.dot_general(
            acc, bin_oh, (((1,), (0,)), ((), ())),
            preferred_element_type=acc_t)

    def kernel(local_ref, gh_ref, binned_ref, out_ref):
        @pl.when(pl.program_id(0) == 0)
        def _init():
            out_ref[:] = jnp.zeros_like(out_ref)

        out_ref[:] += _chunk_update(local_ref[:], gh_ref[:], binned_ref[:])

    return pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((L, chunk), lambda i: (0, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((L, two_k, chunk), lambda i: (0, 0, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((chunk, d), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((M, B * d), lambda i: (0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((M, B * d), acc_t),
        interpret=bool(interpret),
    )(local, ghT, binned)


def hist_level_xla(local: jnp.ndarray, ghT: jnp.ndarray, binned: jnp.ndarray,
                   nn: int, n_bins: int, *, int_exact: bool = False,
                   mxu_dtype=None, chunk: Optional[int] = None
                   ) -> jnp.ndarray:
    """The always-available XLA reference: the one-hot GEMM chunk scan of
    ``models/trees.py`` as a standalone function (same shapes/semantics as
    :func:`hist_level_pallas`), for parity tests and ``chip_smoke.py``."""
    L, n = local.shape
    two_k = ghT.shape[1]
    d = binned.shape[1]
    B = n_bins + 1
    M = L * nn * two_k
    hdt = jnp.int8 if int_exact else jnp.dtype(mxu_dtype or ghT.dtype)
    acc_t = jnp.int32 if int_exact else jnp.float32
    chunk = int(chunk or HIST_CHUNK_DEFAULT)
    local, ghT, binned, n_p = _pad_rows(local, ghT, binned, chunk)
    n_chunks = n_p // chunk

    local_c = local.reshape(L, n_chunks, chunk).swapaxes(0, 1)
    gh_c = ghT.reshape(L, two_k, n_chunks, chunk).transpose(2, 0, 1, 3)
    binned_c = binned.reshape(n_chunks, chunk, d)

    def chunk_step(hacc, blk):
        lb, gb, bb = blk
        node_oh = (lb[:, None, :] ==
                   jnp.arange(nn, dtype=lb.dtype)[None, :, None]).astype(hdt)
        acc = (node_oh[:, :, None, :] * gb[:, None, :, :].astype(hdt)
               ).reshape(M, chunk)
        bin_oh = (bb[:, None, :] ==
                  jnp.arange(B, dtype=bb.dtype)[None, :, None]
                  ).astype(hdt).reshape(chunk, B * d)
        return hacc + jax.lax.dot_general(
            acc, bin_oh, (((1,), (0,)), ((), ())),
            preferred_element_type=acc_t), None

    hist0 = jnp.zeros((M, B * d), acc_t)
    hist, _ = jax.lax.scan(chunk_step, hist0, (local_c, gh_c, binned_c))
    return hist
