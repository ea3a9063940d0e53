"""Fused routing kernel: the ``_row_select`` compare-reduce, kernel + reference.

Reference capability (SURVEY §2.9): XGBoost's row-partition routing — after a
level's splits are chosen, every row reads the bin code of its node's split
feature to pick a child.  The TPU port never gathers (``take_along_axis`` on
the (n, d) code matrix lowers to a serialized per-row dynamic-minor access —
it was the dominant cost of tree growth before the compare-reduce rewrite);
instead ``binned[i, idx[l, i]]`` is a one-hot compare against a feature iota
fused into a streaming multiply-reduce.

This module holds the ONE definition of that math (closing the routing-kernel
gap the ROADMAP autotuning item called out):

- :func:`row_select_xla` / :func:`row_select_lanes_xla` — the formulation
  ``models/trees.py`` historically inlined, moved here verbatim so the XLA
  path, the Pallas kernel, the parity tests, and the corpus all share it;
- :func:`row_select_lanes_pallas` — the fused kernel: the grid walks row
  blocks, each step holds one (block, d) code tile, the (block, L) lane
  indices, and the (block, d, L) one-hot product in VMEM, emitting the
  routed (block, L) codes in one pass — the one-hot never touches HBM;
- :func:`row_select_lanes` — the grower's one entry.  It returns the XLA
  compare-reduce at every shape and in every dispatch mode (PR 33), and
  counts that (``kernel_selections()["route:xla"]``): on a v5e the kernel
  took 74.5 ms a call at 2^20 x 128 codes, the same for three lanes and for
  one, where the XLA form takes 2.3 and 0.9 ms.  The kernel stays for
  callers that name it (parity tests, the autotuner, ``chip_smoke.py``).

Selection parity: the products are exact 0.0/code floats (codes < 2^24) and
the reduce sums exactly one nonzero per row, so the result is BITWISE
identical across paths and reduction orders — pinned in tier-1
(tests/test_kernels.py).
"""

from __future__ import annotations

import os
from typing import Optional

import jax
import jax.numpy as jnp

from . import dispatch as _dispatch

#: row-block size for the routing grid: the (block, d, L) one-hot product is
#: the VMEM resident — the admission guard scales against it.
#: Env-overridable (``TMOG_ROUTE_BLOCK``) and autotunable per shape class
#: (perf/autotune.py family ``route``).
_ROUTE_BLOCK = 256


def _resolve_block(block: Optional[int], n: int, d: int, L: int,
                   mode: str) -> int:
    """Row-block resolution: explicit arg > ``TMOG_ROUTE_BLOCK`` > the
    autotuner's verified winner for this shape class > module default."""
    if block is not None:
        return int(block)
    if os.environ.get("TMOG_ROUTE_BLOCK") is not None:
        return _dispatch.tuning_int("TMOG_ROUTE_BLOCK", _ROUTE_BLOCK)
    try:
        from .. import autotune as _autotune

        cls = _autotune.shape_class("route", mode, rows=n, features=d,
                                    lanes=L)
        return int(_autotune.kernel_param("route", cls, "block",
                                          _ROUTE_BLOCK))
    except Exception:  # pragma: no cover — autotune unavailable
        return _ROUTE_BLOCK


def row_select_xla(binned: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """``binned[i, idx[i]]`` as a fused compare-multiply-reduce, not a gather.

    Exact for codes < 2^24 (f32 integers).  binned: (n, d); idx: (n,)."""
    d = binned.shape[1]
    oh = (jnp.arange(d, dtype=jnp.int32)[None, :] == idx[:, None])
    return (binned.astype(jnp.float32) * oh).sum(axis=1).astype(jnp.int32)


def row_select_lanes_xla(binned: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """``binned[i, idx[l, i]]`` per lane — lane-batched :func:`row_select_xla`.

    binned: (n, d) shared codes; idx: (L, n) -> (L, n)."""
    d = binned.shape[1]
    oh = (jnp.arange(d, dtype=jnp.int32)[None, None, :] == idx[:, :, None])
    return (binned.astype(jnp.float32)[None] * oh).sum(axis=-1) \
        .astype(jnp.int32)


def row_select_lanes_pallas(binned: jnp.ndarray, idx: jnp.ndarray, *,
                            interpret: bool = False,
                            block: Optional[int] = None) -> jnp.ndarray:
    """Fused per-row-block routing; same contract as
    :func:`row_select_lanes_xla`.

    The lane axis rides the block's minor dimension (idx enters transposed
    to (n, L)), so the one-hot product reduces over the feature axis with
    ``keepdims``-free layouts and each output column is a lane — no
    relayout between the reduce and the store."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, d = binned.shape
    L = idx.shape[0]
    block = _resolve_block(block, int(n), int(d), int(L),
                           "interpret" if interpret else "pallas")
    pad = (-n) % block
    if pad:
        # padded rows select feature 0 of zero-rows and are sliced off
        binned = jnp.pad(binned, ((0, pad), (0, 0)))
        idx = jnp.pad(idx, ((0, 0), (0, pad)))
    n_p = n + pad
    idx_t = idx.T.astype(jnp.int32)                              # (n_p, L)

    def kernel(b_ref, i_ref, o_ref):
        codes = b_ref[:].astype(jnp.float32)                     # (block, d)
        sel = i_ref[:]                                           # (block, L)
        ids = jax.lax.broadcasted_iota(jnp.int32, (block, d), 1)
        oh = (ids[:, :, None] == sel[:, None, :]).astype(jnp.float32)
        o_ref[:] = (codes[:, :, None] * oh).sum(axis=1).astype(jnp.int32)

    out = pl.pallas_call(
        kernel,
        grid=(n_p // block,),
        in_specs=[
            pl.BlockSpec((block, d), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block, L), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((block, L), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((n_p, L), jnp.int32),
        interpret=bool(interpret),
    )(binned.astype(jnp.int32), idx_t)
    return out[:n].T


#: what :func:`row_select_lanes` runs, whatever the shape and the dispatch
#: mode: the kernel's rank-3 (block, d, L) one-hot pads the lane axis to a
#: 128-lane tile, so a row costs it d x 128 compare-multiply-adds for the
#: d x L useful ones, and past 128 lanes VMEM admission refused it anyway.
#: The ``host.launch`` spans of the boosting programs carry it as
#: ``route_kernel`` (models/trees.py ``_launch_counts``).
ROUTE_KERNEL = "xla"


def row_select_lanes(binned: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """Lane-batched routing as the tree grower calls it (``models/trees.py``
    ``_row_select_l``): :func:`row_select_lanes_xla`, counted as one
    ``route:xla`` selection a traced call site."""
    _dispatch.count_selection("route", ROUTE_KERNEL)
    return row_select_lanes_xla(binned, idx)
