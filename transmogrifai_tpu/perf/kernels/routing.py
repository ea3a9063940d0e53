"""Row routing of the tree walk: the level's-columns select and the
compare-reduce it replaced.

Reference capability (SURVEY §2.9): XGBoost's row-partition routing — after a
level's splits are chosen, every row reads the bin code of its node's split
feature to pick a child.  The TPU port never gathers (``take_along_axis`` on
the (n, d) code matrix lowers to a serialized per-row dynamic-minor access —
it was the dominant cost of tree growth before the compare-reduce rewrite).

This module holds the ONE definition of that math:

- :func:`level_select_lanes` — the walk's one entry (``models/trees.py``
  ``_route_level``, grower and predictor alike), counted as one ``route:xla``
  selection a traced call site.  A level l has only ``nn = 2^l`` split
  columns a lane, so where ``nn < d`` the d-wide contraction runs on the MXU
  (:func:`level_columns_select_xla`: the level's columns gathered by a one-hot
  matmul, then the row's own node picked among the nn on the vector unit:
  ``n x nn x L`` compare-selects for the compare-reduce's ``n x d x L``);
  from ``nn >= d`` on it is the compare-reduce.  The rule reads shapes at
  trace time alone; :func:`select_cols` counts what it gives a tree;
- :func:`row_select_lanes_xla` — ``binned[i, idx[l, i]]`` as a one-hot
  compare against a feature iota fused into a streaming multiply-reduce over
  ALL d columns: the form of the levels with ``nn >= d`` and of the parity
  tests.

A Pallas routing kernel lost to the compare-reduce on a v5e (74.5 ms a call
at 2^20 x 128 codes, the same for three lanes and for one, where the
compare-reduce takes 2.3 and 0.9 ms): it was never selected, and is gone.

Selection parity: every product is an exact 0/code value (codes < 2^24 in
float32, <= 256 in bfloat16: :func:`select_dtype`) and each sum holds exactly
one nonzero, so the result is BITWISE identical across forms, paths and
reduction orders — pinned in tier-1 (tests/test_kernels.py,
tests/test_tree_level_select.py).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import dispatch as _dispatch


def row_select_lanes_xla(binned: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """``binned[i, idx[l, i]]`` per lane as a fused compare-multiply-reduce
    over all d columns, not a gather.  Exact for codes < 2^24 (f32 integers).

    binned: (n, d) shared codes; idx: (L, n) -> (L, n)."""
    d = binned.shape[1]
    oh = (jnp.arange(d, dtype=jnp.int32)[None, None, :] == idx[:, :, None])
    return (binned.astype(jnp.float32)[None] * oh).sum(axis=-1) \
        .astype(jnp.int32)


#: what :func:`level_select_lanes` runs, whatever the shape and the dispatch
#: mode — XLA's own code, in either of its two forms: a Pallas kernel's
#: rank-3 (block, d, L) one-hot pads the lane axis to a 128-lane tile, so a
#: row would cost it d x 128 compare-multiply-adds for the d x L useful
#: ones.
#: The ``host.launch`` spans of the boosting programs carry it as
#: ``route_kernel`` (models/trees.py ``_launch_counts``).
ROUTE_KERNEL = "xla"


def select_dtype(n_bins: int):
    """Operand dtype of the level's-columns matmul, from ``n_bins`` at trace
    time: the codes are integers in [0, n_bins] and the other operand is a
    0/1 one-hot, so any float that holds ``n_bins`` exactly gives the exact
    code — the narrowest that does: bfloat16 on the TPU up to 256 (8
    significant bits), float32 above that and off the TPU (as
    ``_hist_dtype``: CPU matmuls stay float32).  Never a precision choice."""
    held = ((jnp.bfloat16,) if jax.default_backend() == "tpu" else ()) \
        + (jnp.float32,)
    fits = [dt for dt in held if n_bins <= 2 ** (jnp.finfo(dt).nmant + 1)]
    assert fits, (f"no operand dtype of {[jnp.dtype(t).name for t in held]} "
                  f"holds every bin code up to n_bins={n_bins} exactly")
    return fits[0]


#: most bytes of gathered columns ``C`` (L * nn, rows) one matmul of
#: :func:`level_columns_select_xla` may produce; past it the rows go through
#: a ``lax.scan`` in chunks, as the histogram's do, and ``C`` is a per-chunk
#: temporary (150 forest lanes x 32 nodes x 2^20 rows would be 10 GB whole).
_COLUMNS_WHOLE_MAX_BYTES = 1 << 29


def level_columns_select_xla(binned: jnp.ndarray, feat: jnp.ndarray,
                             local: jnp.ndarray, n_bins: int,
                             chunk: int) -> jnp.ndarray:
    """``binned[i, feat[l, local[l, i]]]`` per lane; 0 where ``local`` names
    none of the level's nodes (a row stuck at an earlier leaf: ``local < 0``).

    binned: (n, d) shared codes in [0, n_bins]; feat: (L, nn) the level's
    split columns; local: (L, n) each row's node less the level's first.
    ``C = one_hot(feat) . codes^T`` -> (L * nn, rows) gathers the level's
    columns on the MXU (rows minor: nothing pads to 128 lanes), then
    ``sum_j [local == j] * C[l, j]`` picks the row's own.  ``chunk``: rows a
    scan step takes where ``C`` whole would pass
    ``_COLUMNS_WHOLE_MAX_BYTES`` (n is padded up to it where it has to be)."""
    n, d = binned.shape
    L, nn = feat.shape
    dt = select_dtype(n_bins)
    codes = binned.astype(dt)
    cols = (feat[..., None] == jnp.arange(d, dtype=feat.dtype)
            ).astype(dt).reshape(L * nn, d)
    nodes = jnp.arange(nn, dtype=local.dtype)[None, :, None]

    def block(codes_blk, local_blk):
        # float32 operands at ``highest``: the TPU's default would round
        # them to bfloat16, which is what the dtype rule just declined
        c = jax.lax.dot_general(
            cols, codes_blk, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST if dt == jnp.float32
            else None, preferred_element_type=dt)
        c = c.reshape(L, nn, codes_blk.shape[0])
        return jnp.where(local_blk[:, None, :] == nodes, c, 0
                         ).sum(axis=1).astype(jnp.int32)

    if L * nn * n * jnp.dtype(dt).itemsize <= _COLUMNS_WHOLE_MAX_BYTES:
        return block(codes, local)
    pad = (-n) % chunk
    if pad:
        codes = jnp.pad(codes, ((0, pad), (0, 0)))
        local = jnp.pad(local, ((0, 0), (0, pad)), constant_values=-1)
    steps = (n + pad) // chunk
    _, out = jax.lax.scan(
        lambda _, blk: (None, block(*blk)), None,
        (codes.reshape(steps, chunk, d),
         local.reshape(L, steps, chunk).swapaxes(0, 1)))
    return out.swapaxes(0, 1).reshape(L, n + pad)[:, :n]


def _level_takes_columns(nn: int, d: int) -> bool:
    """The level's-columns form costs the vector unit ``nn`` compare-selects
    a row and lane, the compare-reduce ``d``: the first wins while a level
    has fewer nodes than the table has columns (depth 8 on at d = 128; level
    4 on at d = 16)."""
    return nn < d


def select_cols(max_depth: int, d: int) -> int:
    """Columns one row's code is compared with while one tree of one lane is
    routed: 2^l at a level that takes the level's-columns form, d at one that
    does not — 7 at depth 3 and 63 at depth 6 for d = 128 (the compare-reduce
    alone: depth x d = 384 and 768)."""
    return sum(2 ** l if _level_takes_columns(2 ** l, d) else d
               for l in range(max_depth))


def level_select_lanes(binned: jnp.ndarray, feat: jnp.ndarray,
                       local: jnp.ndarray, idx: jnp.ndarray, n_bins: int,
                       chunk: int) -> jnp.ndarray:
    """The bin code of each row's split column at one level of a walk, per
    lane, as the tree grower and predictor call it (``models/trees.py``
    ``_route_level``); one ``route:xla`` selection a traced call site.

    ``idx`` (L, n) is ``feat[l, local[l, i]]``, which the caller's packed
    look-up has: the compare-reduce needs it, the level's-columns form reads
    ``feat`` (L, nn) and ``local`` (L, n) themselves.  A row with
    ``local < 0`` gets a value the caller discards (0 or column 0's code)."""
    _dispatch.count_selection("route", ROUTE_KERNEL)
    if _level_takes_columns(feat.shape[-1], binned.shape[1]):
        return level_columns_select_xla(binned, feat, local, n_bins, chunk)
    return row_select_lanes_xla(binned, idx)
