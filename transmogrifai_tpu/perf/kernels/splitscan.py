"""Fused split-scan: bin cumsums + XGBoost gain + argmax, kernel + reference.

Reference capability (SURVEY §2.9): XGBoost's ``EnumerateSplit`` over the
built histograms — prefix-sum the per-bin grad/hess, score every
(feature, bin) candidate with the second-order gain formula (L2
``reg_lambda``, L1 ``alpha`` soft-threshold, complexity ``gamma``,
``min_child_weight``), try missing values on both sides, and argmax.

This module holds the ONE definition of that math for the TPU port:

- :func:`split_scan_xla` — the formulation ``models/trees.py`` historically
  inlined per level, moved here verbatim so the XLA path, the Pallas
  kernel and the parity tests all share it;
- :func:`split_scan_pallas` — the fused kernel: grid over lanes, each step
  holds one lane's (B, K, nn, d) histogram block in VMEM and produces
  the per-node best split index / gain / missing-direction without any of
  the intermediate (L, nodes, d, bins) gain tensors touching HBM — the
  histogram epilogue fused to its decision;
- :func:`split_scan` — the dispatcher (``perf.kernels.dispatch`` mode +
  VMEM admission).

Selection parity: both paths score every candidate with the one
``_gain_terms`` formula.  The kernel walks the bins with a running sum
where the reference takes ``cumsum`` + a flattened ``argmax`` (``cumsum``
has no Pallas TPU lowering on jax 0.9.0), and picks the first maximum in
the same (feature, bin) order.  On the exact-int8 histogram path every operand of the
gain formula is an integer-valued f32, so prefix sums are exact in any
order and gains — and therefore split decisions — are bitwise-identical
across paths (tier-1 pinned, tests/test_kernels.py).  A NaN gain (NaN
gradients upstream) is outside the contract: ``argmax`` would return its
index, the kernel never selects it.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from . import dispatch as _dispatch


def soft_threshold(g, alpha):
    """XGBoost L1 shrinkage on the gradient sum (shared with trees' leaf
    values — one definition, models/trees.py aliases it)."""
    return jnp.sign(g) * jnp.maximum(jnp.abs(g) - alpha, 0.0)


def _gain_terms(gl, hl, Gt, Ht, reg_lambda, alpha, gamma, min_child_weight,
                class_axis: int):
    """Gain of every (feature, bin) candidate given left sums ``gl``/``hl``;
    the trees formula verbatim (eps guards empty children as zero gain)."""
    gr, hr = Gt - gl, Ht - hl
    ok = (hl.mean(class_axis) >= min_child_weight) \
        & (hr.mean(class_axis) >= min_child_weight)
    eps = 1e-12
    raw = (soft_threshold(gl, alpha) ** 2 / (hl + reg_lambda + eps)
           + soft_threshold(gr, alpha) ** 2 / (hr + reg_lambda + eps)
           - soft_threshold(Gt, alpha) ** 2 / (Ht + reg_lambda + eps))
    raw = raw.sum(axis=class_axis)
    return jnp.where(ok, 0.5 * raw - gamma, -jnp.inf)


def split_scan_xla(hist_g, hist_h, G, H, level_mask, n_bins: int,
                   reg_lambda, alpha, gamma, min_child_weight
                   ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Reference split search over (L, nn, K, d, B) histograms.

    Returns (best flat (feature, bin) index (L, nn) int32, best gain
    (L, nn) f32, missing-goes-left (L, nn) bool).  ``level_mask`` is the
    (L, d) 1/0 feature mask (colsample); masked features score -inf.
    """
    L, nn = hist_g.shape[:2]
    gl = jnp.cumsum(hist_g[..., :n_bins], axis=-1)[..., :-1]
    hl = jnp.cumsum(hist_h[..., :n_bins], axis=-1)[..., :-1]
    g_miss = hist_g[..., n_bins][..., None]
    h_miss = hist_h[..., n_bins][..., None]
    Gt = G[..., None, None]
    Ht = H[..., None, None]
    args = (reg_lambda, alpha, gamma, min_child_weight)
    gain_mr = _gain_terms(gl, hl, Gt, Ht, *args, class_axis=2)
    gain_ml = _gain_terms(gl + g_miss, hl + h_miss, Gt, Ht, *args,
                          class_axis=2)
    gain = jnp.maximum(gain_mr, gain_ml)
    gain = jnp.where(level_mask[:, None, :, None] > 0, gain, -jnp.inf)

    flat = gain.reshape(L, nn, -1)
    best = flat.argmax(axis=-1).astype(jnp.int32)
    best_gain = jnp.take_along_axis(flat, best[..., None], -1)[..., 0]
    ml_flat = gain_ml.reshape(L, nn, -1)
    mr_flat = gain_mr.reshape(L, nn, -1)
    bml = jnp.take_along_axis(ml_flat, best[..., None], -1)[..., 0] >= \
        jnp.take_along_axis(mr_flat, best[..., None], -1)[..., 0]
    return best, best_gain, bml


def split_scan_pallas(hist_g, hist_h, G, H, level_mask, n_bins: int,
                      reg_lambda, alpha, gamma, min_child_weight, *,
                      interpret: bool = False
                      ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Fused per-lane split scan; same contract as :func:`split_scan_xla`.

    Layout (what the TPU compiler accepts — CHANGES.md PR 21): the
    histograms enter as (L, B, K, nn, d), so every in-kernel value is a
    (nodes, features) tile with the feature axis on the 128 lanes.  The bin
    prefix sums are a running sum over the LEADING bin axis (no in-kernel
    ``cumsum``, which Mosaic does not lower), each bin's gains are scored as
    they appear, and a strict ``>`` keeps the first best bin per feature;
    the winning feature is the smallest flat (feature, bin) index at the
    maximum — ``argmax``'s first-occurrence rule, without a flattening
    reshape or a gather.  Outputs are (L, nn, 1) so a one-lane block is a
    legal (8, 128)-rule block shape.  One lane a grid step."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    L, nn, K, d, B = hist_g.shape
    F = d * (n_bins - 1)
    hg_t = hist_g.transpose(0, 4, 2, 1, 3)              # (L, B, K, nn, d)
    hh_t = hist_h.transpose(0, 4, 2, 1, 3)
    G_t = G.transpose(0, 2, 1)[..., None]               # (L, K, nn, 1)
    H_t = H.transpose(0, 2, 1)[..., None]
    mask3 = level_mask[:, None, :]                      # (L, 1, d)
    params = jnp.stack([
        jnp.asarray(reg_lambda, jnp.float32),
        jnp.asarray(alpha, jnp.float32),
        jnp.asarray(gamma, jnp.float32),
        jnp.asarray(min_child_weight, jnp.float32)]).reshape(1, 4)

    def kernel(hg_ref, hh_ref, g_ref, h_ref, mask_ref, p_ref,
               best_ref, gain_ref, bml_ref):
        args = (p_ref[0, 0], p_ref[0, 1], p_ref[0, 2], p_ref[0, 3])
        Gt, Ht = g_ref[:], h_ref[:]                     # (1, K, nn, 1)
        g_miss, h_miss = hg_ref[:, n_bins], hh_ref[:, n_bins]
        masked = mask_ref[:] > 0                        # (1, 1, d)

        def score(gl, hl):
            mr = _gain_terms(gl, hl, Gt, Ht, *args, class_axis=1)
            ml = _gain_terms(gl + g_miss, hl + h_miss, Gt, Ht, *args,
                             class_axis=1)
            gain = jnp.where(masked, jnp.maximum(mr, ml), -jnp.inf)
            return gain, (ml >= mr).astype(jnp.int32)   # (1, nn, d) each

        def step(b, carry):
            gl, hl, best_gain, best_bin, best_ml = carry
            gl, hl = gl + hg_ref[:, b], hl + hh_ref[:, b]
            gain, ml = score(gl, hl)
            better = gain > best_gain       # strict: first best bin wins
            return (gl, hl, jnp.where(better, gain, best_gain),
                    jnp.where(better, b, best_bin),
                    jnp.where(better, ml, best_ml))

        gl0, hl0 = hg_ref[:, 0], hh_ref[:, 0]
        gain0, ml0 = score(gl0, hl0)
        _, _, best_gain, best_bin, best_ml = jax.lax.fori_loop(
            1, n_bins - 1, step,
            (gl0, hl0, gain0, jnp.zeros_like(ml0), ml0))

        # f32 index arithmetic (exact below 2^24): float lane reductions are
        # the ones every Mosaic version lowers
        top = jnp.max(best_gain, axis=-1, keepdims=True)        # (1, nn, 1)
        feat = jax.lax.broadcasted_iota(jnp.int32, best_bin.shape, 2)
        flat = (feat * (n_bins - 1) + best_bin).astype(jnp.float32)
        first = jnp.min(jnp.where(best_gain == top, flat, float(F - 1)),
                        axis=-1, keepdims=True)
        best_ref[:] = first.astype(jnp.int32)
        gain_ref[:] = top
        bml_ref[:] = jnp.max(
            jnp.where(flat == first, best_ml.astype(jnp.float32), 0.0),
            axis=-1, keepdims=True).astype(jnp.int32)

    hist_spec = pl.BlockSpec((1, B, K, nn, d), lambda l: (l, 0, 0, 0, 0),
                             memory_space=pltpu.VMEM)
    gh_spec = pl.BlockSpec((1, K, nn, 1), lambda l: (l, 0, 0, 0),
                           memory_space=pltpu.VMEM)
    out_spec = pl.BlockSpec((1, nn, 1), lambda l: (l, 0, 0),
                            memory_space=pltpu.VMEM)
    best, best_gain, bml = pl.pallas_call(
        kernel,
        grid=(L,),
        in_specs=[
            hist_spec, hist_spec, gh_spec, gh_spec,
            pl.BlockSpec((1, 1, d), lambda l: (l, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 4), lambda l: (0, 0),
                         memory_space=pltpu.SMEM),
        ],
        out_specs=(out_spec, out_spec, out_spec),
        out_shape=(
            jax.ShapeDtypeStruct((L, nn, 1), jnp.int32),
            jax.ShapeDtypeStruct((L, nn, 1), jnp.float32),
            jax.ShapeDtypeStruct((L, nn, 1), jnp.int32),
        ),
        interpret=bool(interpret),
    )(hg_t, hh_t, G_t, H_t, mask3, params)
    return best[..., 0], best_gain[..., 0], bml[..., 0] != 0


def split_scan(hist_g, hist_h, G, H, level_mask, n_bins: int,
               reg_lambda, alpha, gamma, min_child_weight
               ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Dispatching split scan (the entry ``models/trees.py`` calls)."""
    L, nn, K, d, B = hist_g.shape
    # one step's grad + hess blocks as tiled in VMEM: (nn, d) pads to
    # (8, 128) multiples under the leading (B, K) axes
    tiled = B * K * (-(-nn // 8) * 8) * (-(-d // 128) * 128) * 4
    mode = _dispatch.split_mode(2 * tiled)
    if mode is not None:
        return split_scan_pallas(
            hist_g, hist_h, G, H, level_mask, n_bins, reg_lambda, alpha,
            gamma, min_child_weight, interpret=mode == "interpret")
    return split_scan_xla(hist_g, hist_h, G, H, level_mask, n_bins,
                          reg_lambda, alpha, gamma, min_child_weight)
