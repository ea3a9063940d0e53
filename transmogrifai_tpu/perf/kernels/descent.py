"""One step of the squared-hinge descent in one read of the block.

Reference capability: the gradient of Spark ``LinearSVC``'s loss, which the
SVC refit (``models/svm._svc_body``) takes 100 times over the whole float32
table.  Written in XLA, the step is two fusions: the margins ``x @ beta``
(a reduction over columns) and the gradient ``x.T @ r`` (a reduction over
rows that consumes them), the ``(n,)`` margins written between them.  Each
reads the whole block, so a step reads it twice.

The residual is row-local, ``r_i = w_i * (-2 y_i * max(1 - y_i z_i, 0))``,
so one pass over a row tile can do the whole step: the tile's margins, its
residuals, then its part of ``x.T @ r``.  :func:`hinge_grad_pallas` is that
pass.  It reads each byte of the block once a step.

- **Layout.** The kernel reads the block as ``x.T``, ``(d + 1, n)``: a
  feature's rows on the 128 lanes, 8 features to a register.  On a TPU
  that is how XLA stores an ``(n, d + 1)`` float32 block whose width is not
  a multiple of 128 (``{0,1:T(8,128)}``), so the transpose is a bitcast and
  nothing is copied; a block stored the other way is transposed once a
  program, never once a step.  The margin of 128 rows is a sum of their
  column of registers over the features, the gradient a sum over the rows
  of registers; one sum across sublanes a chunk and one across lanes a
  call are all the reductions there are.  Labels and weights enter as
  ``(n / 128, 128)``, a bitcast of their vectors.
- **Bordered form.** With an intercept and a feature count that fills whole
  registers the ones row is not read: the margin adds ``beta[d]`` and the
  intercept's gradient is ``sum(r)``.  Otherwise the block's last row is
  read as stored and skipped.
- **Float32 throughout,** on the vector unit, as XLA's two fusions are: no
  operand is rounded and no MXU pass is taken.  The sums run in another
  order than XLA's, so the gradient agrees with :func:`hinge_grad_xla` to
  float32 rounding, not bitwise.
- **Tile.** ``tile_rows`` rows a grid step (a multiple of 1024), chosen by
  ``dispatch.descent_tile`` against the VMEM budget and admitted by
  ``dispatch.descent_mode``; rows past ``n`` are zero-padded with weight
  0, so a padded row contributes exactly 0.  The fit's blocks have a
  power-of-two row count and are never padded.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

_LANES = 128
_SUBLANES = 8


class HingeBlock(NamedTuple):
    """The operands of :func:`hinge_grad_pallas`, made once a program."""

    x: jnp.ndarray      # (d + 1 or d, n) float32: the block, transposed
    y: jnp.ndarray      # (n / 128, 128) float32 ±1 targets
    w: jnp.ndarray      # (n / 128, 128) float32 weights, 0 on padded rows
    tile_rows: int


def hinge_grad_xla(x, y_pm, w, beta):
    """``x.T @ r`` as XLA's margin and gradient products (two fusions, two
    reads of ``x``): what the refit runs where the kernel is not selected
    and what the parity tests hold the kernel to (the refit divides it by
    the weights' sum)."""
    z = x @ beta
    margin = 1.0 - y_pm * z
    active = jnp.maximum(margin, 0.0)
    return x.T @ (w * (-2.0 * y_pm * active))


def hinge_block(x, y_pm, w, tile_rows: int) -> HingeBlock:
    """``x.T`` and the ``(n / 128, 128)`` labels and weights, rows
    zero-padded to a multiple of the tile, which is cut to the rows there
    are in whole 1024-row steps."""
    n = x.shape[0]
    step = _SUBLANES * _LANES
    tile = min(int(tile_rows), -(-n // step) * step)
    pad = -n % tile
    xt = jnp.pad(x.T, ((0, 0), (0, pad))) if pad else x.T
    y2 = jnp.pad(y_pm, (0, pad)).reshape(-1, _LANES)
    w2 = jnp.pad(w, (0, pad)).reshape(-1, _LANES)
    return HingeBlock(xt, y2, w2, tile)


def hinge_grad_pallas(block: HingeBlock, beta, has_intercept: bool, *,
                      interpret: bool = False):
    """``x.T @ r`` for the squared hinge at ``beta`` in one read of
    ``block``; the same contract as :func:`hinge_grad_xla`.

    The grid walks the row tiles; a step walks its tile 128 rows at a time.
    For a chunk it sums ``x[:d, rows] * beta[:d]`` over the features into
    the rows' margins (the intercept added), makes their residuals, and adds
    ``x[:d, rows] * r`` to the (d, 128) accumulators it carries.  The output
    block is the same at every step, so the accumulators stay in VMEM for
    the pass; they are summed across lanes once, here."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    d1, n = block.x.shape
    d = d1 - 1 if has_intercept else d1
    bd = d if d % _SUBLANES == 0 else d1    # rows of x a tile reads
    tn = block.tile_rows
    beta = beta.astype(jnp.float32)
    b_cols = jnp.broadcast_to(beta[:d, None], (d, _LANES))
    b0 = (beta[d] if has_intercept else jnp.float32(0.0)).reshape(1, 1)

    def kernel(b0_ref, bc_ref, x_ref, y_ref, w_ref, o_ref):
        @pl.when(pl.program_id(0) == 0)
        def _():
            o_ref[...] = jnp.zeros_like(o_ref)

        b_c, b_0 = bc_ref[...], b0_ref[0, 0]

        def registers(g, carry):
            acc, r_sum = carry
            row = pl.multiple_of(g * _SUBLANES, _SUBLANES)
            y8 = y_ref[pl.ds(row, _SUBLANES), :]
            w8 = w_ref[pl.ds(row, _SUBLANES), :]
            for s in range(_SUBLANES):
                lane = pl.multiple_of((g * _SUBLANES + s) * _LANES, _LANES)
                xc = x_ref[:, pl.ds(lane, _LANES)][:d]
                z = jnp.sum(xc * b_c, axis=0, keepdims=True) + b_0
                y = y8[s:s + 1]
                active = jnp.maximum(1.0 - y * z, 0.0)
                r = w8[s:s + 1] * (-2.0 * y * active)
                acc = acc + xc * r
                r_sum = r_sum + r
            return acc, r_sum

        acc, r_sum = jax.lax.fori_loop(
            0, tn // (_SUBLANES * _LANES), registers,
            (jnp.zeros((d, _LANES), jnp.float32),
             jnp.zeros((1, _LANES), jnp.float32)))
        o_ref[:d] += acc
        if has_intercept:
            o_ref[d:] += r_sum

    parts = pl.pallas_call(
        kernel,
        grid=(n // tn,),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i: (0, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec((d, _LANES), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bd, tn), lambda i: (0, i), memory_space=pltpu.VMEM),
            pl.BlockSpec((tn // _LANES, _LANES), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((tn // _LANES, _LANES), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((d1, _LANES), lambda i: (0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((d1, _LANES), jnp.float32),
        interpret=bool(interpret),
    )(b0, b_cols, block.x, block.y, block.w)
    return parts.sum(axis=1)
