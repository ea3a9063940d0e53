"""Plain histogram tree growth shared by the two tree references.

Level by level, every candidate (feature, bin) of every open node is scored
from gradient and hessian histograms with the second-order gain

    1/2 [ GL^2/(HL + lambda) + GR^2/(HR + lambda) - G^2/(H + lambda) ] - gamma

(children need a hessian sum of at least ``min_child_weight``; the reserved
missing bin is tried on both sides and goes left on a tie), the first best
candidate in (feature, bin) order wins, a node without a positive gain
becomes a leaf with value ``-eta G / (H + lambda)``, and rows are routed by
``code <= bin``.  Histograms are one-hot matrix products over row blocks at
full float32 precision; no kernels, no sibling subtraction, no cache.  What
comes back is each row's leaf value, which is all a score needs.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .common import quantizer

ROW_BLOCK = 8192
SAMPLE = 65536
EPS = 1e-12


def quantile_edges(x, n_bins: int) -> np.ndarray:
    """(d, n_bins - 1) float32 edges: per-column quantiles at k / n_bins of a
    fixed 65,536-row sample (all rows below that), made monotone."""
    n = x.shape[0]
    if n > SAMPLE:
        idx = np.sort(np.random.default_rng(0).choice(n, SAMPLE,
                                                      replace=False))
        sample = np.asarray(x[jnp.asarray(idx)])
    else:
        sample = np.asarray(x)
    qs = np.linspace(0.0, 1.0, n_bins + 1)[1:-1]
    edges = np.quantile(sample.astype(np.float32), qs, axis=0).T
    return np.maximum.accumulate(edges.astype(np.float32), axis=1)


@jax.jit
def bin_codes(x, edges):
    """searchsorted(edges, v, 'right') per column, as a count of edges <= v."""
    return (edges[None, :, :] <= x[:, :, None]).sum(axis=-1).astype(jnp.int32)


def _lookup(table, local):
    """table (L, nodes), local (L, n) -> table[l, local[l, i]]; 0 where local
    is outside [0, nodes)."""
    nodes = table.shape[1]
    hit = local[..., None] == jnp.arange(nodes)
    return jnp.where(hit, table[:, None, :], 0).sum(axis=-1)


def leaf_values(codes, g, h, depth: int, n_bins: int, reg_lambda, gamma,
                min_child_weight, eta, precision: str = "float32"):
    """codes (n, f) int32, g/h (L, n) -> (L, n): the value of the leaf that
    each row of each lane ends in.  Call under jit with ``depth``,
    ``n_bins`` and ``precision`` static; n must be a multiple of the row
    block or below it."""
    q = quantizer(precision)
    L, n = g.shape
    f = codes.shape[1]
    B = n_bins + 1
    block = ROW_BLOCK if n % ROW_BLOCK == 0 else n
    gq, hq = q(g), q(h)

    def histogram(local, nodes):
        def body(acc, blk):
            c, loc, gb, hb = blk
            bins = (c[:, :, None] == jnp.arange(B)).astype(jnp.float32)
            at = (loc[..., None] == jnp.arange(nodes)).astype(jnp.float32)
            lhs = jnp.concatenate([at * gb[..., None], at * hb[..., None]], -1)
            return acc + jnp.einsum("lcm,ck->lmk", lhs,
                                    bins.reshape(block, f * B)), None

        def blocks(a):      # (..., n) -> (n / block, ..., block)
            return jnp.moveaxis(a.reshape(a.shape[:-1] + (-1, block)), -2, 0)

        acc0 = jnp.zeros((L, 2 * nodes, f * B), jnp.float32)
        acc, _ = jax.lax.scan(body, acc0, (
            codes.reshape(-1, block, f), blocks(local), blocks(gq),
            blocks(hq)))
        return acc.reshape(L, 2, nodes, f, B)

    def leaf(G, H):
        return -G / (H + reg_lambda + EPS) * eta

    node = jnp.zeros((L, n), jnp.int32)         # heap index of each row
    value = jnp.zeros((L, n), jnp.float32)
    for level in range(depth):
        first, nodes = 2 ** level - 1, 2 ** level
        local = node - first                    # < 0: stuck at an older leaf
        hist = histogram(local, nodes)
        hg, hh = hist[:, 0], hist[:, 1]         # (L, nodes, f, B)
        G, H = hg[:, :, 0, :].sum(-1), hh[:, :, 0, :].sum(-1)
        gl = jnp.cumsum(hg[..., :n_bins], axis=-1)[..., :-1]
        hl = jnp.cumsum(hh[..., :n_bins], axis=-1)[..., :-1]
        gm, hm = hg[..., n_bins][..., None], hh[..., n_bins][..., None]
        Gt, Ht = G[..., None, None], H[..., None, None]

        def gain(gl_, hl_):
            gr_, hr_ = Gt - gl_, Ht - hl_
            ok = (hl_ >= min_child_weight) & (hr_ >= min_child_weight)
            raw = (gl_ ** 2 / (hl_ + reg_lambda + EPS)
                   + gr_ ** 2 / (hr_ + reg_lambda + EPS)
                   - Gt ** 2 / (Ht + reg_lambda + EPS))
            return jnp.where(ok, 0.5 * raw - gamma, -jnp.inf)

        right, left = gain(gl, hl), gain(gl + gm, hl + hm)  # missing goes ...
        flat = jnp.maximum(right, left).reshape(L, nodes, -1)
        best = flat.argmax(axis=-1)
        best_gain = jnp.take_along_axis(flat, best[..., None], -1)[..., 0]

        def at_best(a):
            return jnp.take_along_axis(a.reshape(L, nodes, -1),
                                       best[..., None], -1)[..., 0]

        miss_left = at_best(left) >= at_best(right)
        feat, cut = best // (n_bins - 1), best % (n_bins - 1)
        leaf_now = (best_gain <= 0.0) | (H <= 0.0)
        here = local >= 0
        value = jnp.where(here, _lookup(leaf(G, H), local), value)

        row_feat = _lookup(feat, local)
        code = jnp.where(row_feat[..., None] == jnp.arange(f),
                         codes[None], 0).sum(axis=-1)
        go_left = jnp.where(code == n_bins,
                            _lookup(miss_left.astype(jnp.int32), local) > 0,
                            code <= _lookup(cut, local))
        splits = here & (_lookup(leaf_now.astype(jnp.int32), local) == 0)
        if level == depth - 1:
            # the children are leaves: their sums are the chosen split's
            gm_best = jnp.take_along_axis(gm[..., 0], feat[..., None], -1)[..., 0]
            hm_best = jnp.take_along_axis(hm[..., 0], feat[..., None], -1)[..., 0]
            g_left = at_best(gl) + jnp.where(miss_left, gm_best, 0.0)
            h_left = at_best(hl) + jnp.where(miss_left, hm_best, 0.0)
            child = jnp.where(go_left, _lookup(leaf(g_left, h_left), local),
                              _lookup(leaf(G - g_left, H - h_left), local))
            value = jnp.where(splits, child, value)
        node = jnp.where(splits,
                         jnp.where(go_left, 2 * node + 1, 2 * node + 2), node)
    if depth == 0:
        value = jnp.broadcast_to(leaf(g.sum(1), h.sum(1))[:, None], (L, n))
    return value
