"""Column-sharded statistics for wide feature matrices (SURVEY §5.7).

The reference's widest dense object is the SanityChecker's (d+1)² correlation
matrix (SanityChecker.scala:596-620); its wide-input analog is the hashing trick
capped at 16384 features (Transmogrifier.scala:55-56).  BASELINE.json's
wide-sparse 10K-feature config exercises exactly this shape.

TPU-native design: shard the FEATURE dimension over the mesh's data axis with
``shard_map`` —
- per-column moments/label-correlation need no collectives at all (each device
  owns whole columns; the label vector is replicated), and
- the full d×d correlation matrix builds block-by-block with a ``ppermute``
  ring: each device holds its (n, d/k) column shard, computes one
  (d/k, d/k) gram block per step against the shard passing through, and rotates
  the shard around the ring — the standard blocked-gram pattern that rides ICI
  neighbor links instead of materializing (n, d) anywhere.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .mesh import DATA_AXIS, pad_axis

from jax import shard_map


def _mark_varying(x, axis: str):
    """Mark a constant as device-varying over `axis` (scan-carry requirement)."""
    return jax.lax.pcast(x, (axis,), to="varying")


def col_sharding(mesh: Mesh) -> NamedSharding:
    """Shard the trailing (feature) axis over the data axis."""
    return NamedSharding(mesh, P(None, DATA_AXIS))


def pad_cols(arr: np.ndarray, multiple: int) -> Tuple[np.ndarray, int]:
    """Pad the feature axis to a multiple (even shards); returns (padded, d_valid)."""
    return pad_axis(arr, 1, multiple)


def shard_cols(arr, mesh: Mesh):
    """Place (n, d) on device with columns sharded; returns (device_array, d_valid).

    Accepts host ndarrays AND device jax.Arrays — a device input reshards
    device-to-device (the SanityChecker's wide path derives its correlation
    block from the already-placed feature block; coercing through numpy here
    would re-pay a multi-hundred-MB host transfer)."""
    k = mesh.shape[DATA_AXIS]
    if isinstance(arr, jax.Array):
        d_valid = int(arr.shape[1])
        pad_c = (-d_valid) % k
        padded = jnp.pad(arr, ((0, 0), (0, pad_c))) if pad_c else arr
    else:
        padded, d_valid = pad_cols(np.asarray(arr), k)
    return jax.device_put(padded, col_sharding(mesh)), d_valid


@functools.lru_cache(maxsize=None)
def _col_stats_fn(mesh: Mesh):
    """Jitted column-stats program, cached per mesh so calls hit the jit cache."""

    def local_stats(xs, ys):
        n = xs.shape[0]
        mean = xs.mean(axis=0)
        var = xs.var(axis=0)
        xmin = xs.min(axis=0)
        xmax = xs.max(axis=0)
        xc = xs - mean
        yc = ys - ys.mean()
        cov = xc.T @ yc / n
        sx = jnp.sqrt((xc ** 2).mean(axis=0))
        sy = jnp.sqrt((yc ** 2).mean())
        corr = cov / jnp.maximum(sx * sy, 1e-12)
        return mean, var, xmin, xmax, corr

    return jax.jit(shard_map(  # opcheck: allow(TM303) built once per mesh, lru_cache-memoized factory
        local_stats, mesh=mesh,
        in_specs=(P(None, DATA_AXIS), P()),
        out_specs=(P(DATA_AXIS),) * 5))


def wide_col_stats(x, y, mesh: Mesh, d_valid: Optional[int] = None):
    """(mean, var, min, max, corr-with-label) per column, column-sharded.

    Collective-free: every device owns complete columns of its shard and the
    replicated label, so each statistic is a local reduction over rows.
    Pass ``d_valid`` (from ``shard_cols``) to trim the zero-padded phantom
    columns from every returned vector.
    """
    out = _col_stats_fn(mesh)(x, y)
    if d_valid is not None:
        out = tuple(v[:d_valid] for v in out)
    return out


@functools.lru_cache(maxsize=None)
def _gram_ring_fn(mesh: Mesh):
    """Jitted ring-gram program, cached per mesh."""
    k = mesh.shape[DATA_AXIS]

    def local_gram(xs):
        # xs: (n, d_local).  Build the (d_local, d) block-row by rotating shards.
        n = xs.shape[0]
        d_local = xs.shape[1]
        my = jax.lax.axis_index(DATA_AXIS)
        perm = [(i, (i + 1) % k) for i in range(k)]

        def step(carry, _):
            passing, blocks, src = carry
            block = xs.T @ passing / n           # (d_local, d_local) for shard `src`
            blocks = jax.lax.dynamic_update_slice(
                blocks, block[None], (src, 0, 0))
            passing = jax.lax.ppermute(passing, DATA_AXIS, perm)
            # after permute we now hold the shard of the neighbor one step back
            return (passing, blocks, (src - 1) % k), 0.0

        # initial carry must carry the same device-varying type as the outputs
        blocks0 = _mark_varying(jnp.zeros((k, d_local, d_local), xs.dtype),
                                DATA_AXIS)
        (_, blocks, _), _ = jax.lax.scan(
            step, (xs, blocks0, my), None, length=k)
        # blocks[j] = X_local^T X_j / n -> concat into the (d_local, d) block-row
        return jnp.concatenate([blocks[j] for j in range(k)], axis=1)

    return jax.jit(shard_map(local_gram, mesh=mesh,  # opcheck: allow(TM303) built once per mesh, lru_cache-memoized factory
                             in_specs=(P(None, DATA_AXIS),),
                             out_specs=P(DATA_AXIS, None)))


def wide_gram_ring(x, mesh: Mesh):
    """X^T X / n for column-sharded X via a ppermute ring; returns (d, d) sharded
    over rows of the gram matrix (each device owns its shard's block-row)."""
    return _gram_ring_fn(mesh)(x)


def wide_full_corr(x, mesh: Mesh, d_valid: Optional[int] = None):
    """Full (d, d) Pearson correlation of a column-sharded X (ring-blocked gram)."""
    xj = jnp.asarray(x)
    mean = xj.mean(axis=0)
    xc = xj - mean
    gram = wide_gram_ring(xc, mesh)                  # cov matrix (d, d)
    sd = jnp.sqrt(jnp.diag(gram))
    corr = gram / jnp.maximum(sd[:, None] * sd[None, :], 1e-12)
    if d_valid is not None:
        corr = corr[:d_valid, :d_valid]
    return corr
