"""Fused serving-prefix encode kernels: level-code one-hot + bucketize.

Reference capability: the reference's vectorizer scoring kernels —
OpOneHotVectorizer.scala's pivot scatter and NumericBucketizer.scala's
right-inclusive interval one-hot — which the TPU port runs inside the fused
transform/scoring prefix (``ops/onehot.py``, ``ops/bucketizers.py``,
``serve/plan.py``).  Those stages are pure layout work (<2 FLOPs/byte — the
TM604 memory-bound worklist named them the standing Pallas targets): every
row reads a code or a value and writes a one-hot block.

The kernels here stream row blocks through VMEM and emit the finished
(rows, width) block in one pass:

- :func:`onehot_codes` — ``jax.nn.one_hot`` semantics for int32 level codes
  (out-of-range/negative codes → all-zero row, exactly the host path's
  untracked-null row);
- :func:`bucketize_right_encode` — the whole
  ``ops.bucketizers.device_bucketize_right`` body fused: searchsorted (as a
  streaming compare-count — the same gather-free trick as
  ``models/trees._digitize_device``), interval one-hot, and the optional
  invalid/null indicator columns, concatenated in-kernel.

Bitwise parity with the XLA reference path is pinned in tier-1
(tests/test_kernels.py): index arithmetic is integer-exact and the one-hot
writes are exact 0.0/1.0 floats, so dispatch mode can never move a record
between buckets.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: row-block size for the encode grids: wide enough to amortize per-step
#: overheads, small enough that (block, width) blocks sit comfortably in
#: VMEM at serving widths (``dispatch.encode_mode`` admits the same 1024
#: rows).
_ENCODE_BLOCK = 1024


def _pad_block(x2d, block: int, fill):
    n = x2d.shape[0]
    pad = (-n) % block
    if pad:
        x2d = jnp.pad(x2d, ((0, pad), (0, 0)), constant_values=fill)
    return x2d, n


def onehot_codes(codes: jnp.ndarray, width: int, *,
                 interpret: bool = False) -> jnp.ndarray:
    """(n, width) float32 one-hot of int32 codes — ``jax.nn.one_hot``
    semantics (out-of-range rows all-zero), as one fused Pallas pass."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    block = _ENCODE_BLOCK
    c2d, n = _pad_block(codes.astype(jnp.int32)[:, None], block, -1)
    grid = c2d.shape[0] // block

    def kernel(c_ref, o_ref):
        ids = jax.lax.broadcasted_iota(jnp.int32, (block, width), 1)
        o_ref[:] = (c_ref[:] == ids).astype(jnp.float32)

    out = pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=[pl.BlockSpec((block, 1), lambda i: (i, 0),
                               memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((block, width), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((c2d.shape[0], width), jnp.float32),
        interpret=bool(interpret),
    )(c2d)
    return out[:n]


def bucketize_right_encode(x: jnp.ndarray, splits: jnp.ndarray,
                           track_nulls: bool, track_invalid: bool, *,
                           interpret: bool = False) -> jnp.ndarray:
    """Fused right-inclusive bucketize one-hot — the device half of
    ``ops.bucketizers.bucketize_right`` in one Pallas pass.

    x: (n,) canonical float32 lift (NaN = missing); splits: (S,) monotone
    edges with ``S >= 2`` (the S==0 shouldSplit=false branch stays host-side
    in the caller).  Output width = (S-1) buckets [+ invalid][+ null].
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n_splits = int(splits.shape[0])
    n_buckets = n_splits - 1
    width = n_buckets + (1 if track_invalid else 0) + (1 if track_nulls else 0)
    block = _ENCODE_BLOCK
    # NaN-pad: padded rows read as missing and are sliced off anyway
    x2d, n = _pad_block(x.astype(jnp.float32)[:, None], block, jnp.nan)
    grid = x2d.shape[0] // block
    s2d = splits.astype(jnp.float32)[None, :]

    def kernel(x_ref, s_ref, o_ref):
        xv = x_ref[:]                                        # (block, 1)
        s = s_ref[:]                                         # (1, S)
        present = ~jnp.isnan(xv)
        finite = present & jnp.isfinite(xv)
        v0 = jnp.nan_to_num(xv)
        # searchsorted(splits, v0, side="left") as a streaming compare-count
        # (binary search serializes on TPU; S is tiny)
        lt = (s < v0).astype(jnp.int32).sum(axis=1, keepdims=True)
        idx = jnp.clip(lt - 1, 0, n_buckets - 1)             # (block, 1)
        in_range = finite & (xv > s[0, 0]) & (xv <= s[0, n_splits - 1])
        ids = jax.lax.broadcasted_iota(jnp.int32, (block, n_buckets), 1)
        oh = (idx == ids).astype(jnp.float32) \
            * in_range.astype(jnp.float32)
        parts = [oh]
        if track_invalid:
            parts.append((present & ~in_range).astype(jnp.float32))
        if track_nulls:
            parts.append((~present).astype(jnp.float32))
        o_ref[:] = parts[0] if len(parts) == 1 \
            else jnp.concatenate(parts, axis=1)

    out = pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((block, 1), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, n_splits), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((block, width), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((x2d.shape[0], width), jnp.float32),
        interpret=bool(interpret),
    )(x2d, s2d)
    return out[:n]
