"""Boosted trees judged tree by tree: the replay a chaotic ensemble needs.

Fifty rounds of boosting amplify the last bit: where two candidate splits of
a node tie to the last digits of their gains, bfloat16 and float32 operands
may choose differently, every later round then sees other gradients, and
after 50 rounds the two ensembles' scores lie 0.2 standard deviations apart
(root mean square) — for the program, for a float8 control and for a
bfloat16 one alike, on seven seeds of eight at 2^20 rows.  Free-running
scores tell no precision from another.  So an ensemble is held to the
float32 arithmetic ONE TREE AT A TIME, along its own history:

- the margin before round r is the sum of the ensemble's OWN leaf values
  (teacher forcing), so round r is judged on the gradients the ensemble
  itself had;
- at every node the float32 histograms give every candidate's gain: the
  ``regret`` of a tree is the most gain any of its nodes gave up against the
  best candidate (a node made a leaf gives up the best positive gain), as a
  share of the best gain at its root.  A tie costs nothing, whichever way
  it fell;
- the rows are routed by the ensemble's splits, and the float32 sums of each
  leaf give the value the reference would have put there: the ``replayed``
  margin is the sum of those, over the same trees.

``boost_trees`` is the plain grower of ``treegrow`` with the trees kept (the
lower-precision control's ensemble).  Nothing here imports the program.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .common import highest, quantizer
from .treegrow import EPS, ROW_BLOCK

Tree = Dict[str, jnp.ndarray]       # feat, cut, miss_left, leaf, value: (m,)


def _histograms(codes, local, g, h, nodes: int, n_bins: int):
    """(2, nodes, f, B) float32 sums of ``g`` and ``h`` per (node, column,
    bin); a row whose ``local`` is outside [0, nodes) counts nowhere."""
    n, f = codes.shape
    B = n_bins + 1
    block = ROW_BLOCK if n % ROW_BLOCK == 0 else n

    def body(acc, blk):
        c, loc, gb, hb = blk
        bins = (c[:, :, None] == jnp.arange(B)).astype(jnp.float32)
        at = (loc[:, None] == jnp.arange(nodes)).astype(jnp.float32)
        lhs = jnp.concatenate([at * gb[:, None], at * hb[:, None]], -1)
        return acc + lhs.T @ bins.reshape(block, f * B), None

    acc, _ = jax.lax.scan(
        body, jnp.zeros((2 * nodes, f * B), jnp.float32),
        (codes.reshape(-1, block, f), local.reshape(-1, block),
         g.reshape(-1, block), h.reshape(-1, block)))
    return acc.reshape(2, nodes, f, B)


def _take(table, index):
    """table (nodes, c), index (nodes,) -> table[j, index[j]]."""
    return jnp.take_along_axis(table, index[:, None], -1)[:, 0]


def walk(codes, g, h, depth: int, n_bins: int, reg_lambda, gamma,
         min_child_weight, eta, precision: str = "float32",
         forced: Optional[Tree] = None) -> Tuple[Tree, jnp.ndarray, Any]:
    """One tree of ``depth`` levels over the (n,) gradients and hessians.

    ``forced`` None: the tree is grown, ``treegrow``'s rules to the letter
    (histogram operands rounded to ``precision``).  Otherwise the walk
    follows ``forced``'s splits and leaves, and the tree that comes back has
    ``forced``'s structure with THIS walk's leaf values.  Returns (tree,
    the heap index of the leaf each row ends in, the tree's regret as the
    module docstring defines it: 0.0 for a tree grown here)."""
    q = quantizer(precision)
    n, f = codes.shape
    m = 2 ** (depth + 1) - 1
    gq, hq = q(g), q(h)

    def leaf(G, H):
        return -G / (H + reg_lambda + EPS) * eta

    tree = {"feat": jnp.zeros(m, jnp.int32), "cut": jnp.zeros(m, jnp.int32),
            "miss_left": jnp.zeros(m, bool), "leaf": jnp.ones(m, bool),
            "value": jnp.zeros(m, jnp.float32)}
    node = jnp.zeros(n, jnp.int32)          # heap index of each row
    regret = root_best = jnp.float32(0.0)
    for level in range(depth):
        first, nodes = 2 ** level - 1, 2 ** level
        sl = slice(first, first + nodes)
        local = node - first                # < 0: stuck at an older leaf
        hg, hh = _histograms(codes, local, gq, hq, nodes, n_bins)
        G, H = hg[:, 0, :].sum(-1), hh[:, 0, :].sum(-1)
        gl = jnp.cumsum(hg[..., :n_bins], axis=-1)[..., :-1]
        hl = jnp.cumsum(hh[..., :n_bins], axis=-1)[..., :-1]
        gm, hm = hg[..., n_bins][..., None], hh[..., n_bins][..., None]
        Gt, Ht = G[:, None, None], H[:, None, None]

        def gain(gl_, hl_):
            gr_, hr_ = Gt - gl_, Ht - hl_
            ok = (hl_ >= min_child_weight) & (hr_ >= min_child_weight)
            raw = (gl_ ** 2 / (hl_ + reg_lambda + EPS)
                   + gr_ ** 2 / (hr_ + reg_lambda + EPS)
                   - Gt ** 2 / (Ht + reg_lambda + EPS))
            return jnp.where(ok, 0.5 * raw - gamma, -jnp.inf)

        right = gain(gl, hl).reshape(nodes, -1)         # missing goes right
        left = gain(gl + gm, hl + hm).reshape(nodes, -1)
        flat = jnp.maximum(right, left)
        best = flat.argmax(axis=-1)
        best_gain = _take(flat, best)
        if forced is None:
            feat, cut = best // (n_bins - 1), best % (n_bins - 1)
            miss_left = _take(left, best) >= _take(right, best)
            leaf_now = (best_gain <= 0.0) | (H <= 0.0)
        else:
            feat, cut = forced["feat"][sl], forced["cut"][sl]
            miss_left, leaf_now = forced["miss_left"][sl], forced["leaf"][sl]
            at = feat * (n_bins - 1) + jnp.clip(cut, 0, n_bins - 2)
            chosen = jnp.where(miss_left, _take(left, at), _take(right, at))
            gave_up = jnp.maximum(best_gain, 0.0) - jnp.where(
                leaf_now, 0.0, jnp.maximum(chosen, -1e30))
            if level == 0:
                root_best = jnp.maximum(best_gain[0], 1e-30)
            regret = jnp.maximum(regret, jnp.where(
                H > 0.0, gave_up, 0.0).max() / root_best)
        tree = {"feat": tree["feat"].at[sl].set(feat.astype(jnp.int32)),
                "cut": tree["cut"].at[sl].set(cut.astype(jnp.int32)),
                "miss_left": tree["miss_left"].at[sl].set(miss_left),
                "leaf": tree["leaf"].at[sl].set(leaf_now),
                "value": tree["value"].at[sl].set(leaf(G, H))}

        def lookup(table):
            hit = local[:, None] == jnp.arange(nodes)
            return jnp.where(hit, table[None, :], 0).sum(axis=-1)

        row_feat = lookup(feat)
        code = jnp.where(row_feat[:, None] == jnp.arange(f), codes, 0).sum(-1)
        go_left = jnp.where(code == n_bins,
                            lookup(miss_left.astype(jnp.int32)) > 0,
                            code <= lookup(cut))
        splits = (local >= 0) & (lookup(leaf_now.astype(jnp.int32)) == 0)
        if level == depth - 1:
            # the children are leaves: their sums are the chosen split's
            at = feat * (n_bins - 1) + jnp.clip(cut, 0, n_bins - 2)
            g_left = _take(gl.reshape(nodes, -1), at) + jnp.where(
                miss_left, _take(gm[..., 0], feat), 0.0)
            h_left = _take(hl.reshape(nodes, -1), at) + jnp.where(
                miss_left, _take(hm[..., 0], feat), 0.0)
            kids = jnp.stack([leaf(g_left, h_left),
                              leaf(G - g_left, H - h_left)], -1).reshape(-1)
            tree["value"] = tree["value"].at[
                first + nodes:first + 3 * nodes].set(kids)
        node = jnp.where(splits,
                         jnp.where(go_left, 2 * node + 1, 2 * node + 2), node)
    if depth == 0:
        tree["value"] = tree["value"].at[0].set(leaf(g.sum(), h.sum()))
    return tree, node, regret


def _values(tree: Tree, node):
    """``tree["value"][node]`` as a compare and a sum."""
    hit = node[:, None] == jnp.arange(tree["value"].shape[0])
    return jnp.where(hit, tree["value"][None, :], 0.0).sum(-1)


def _gradients(margin, y, w):
    p = jax.nn.sigmoid(margin)
    return w * (p - y), w * jnp.maximum(p * (1 - p), 1e-16)


def _prior(y, w):
    p0 = jnp.clip((w * (y == 1.0)).sum() / jnp.maximum(w.sum(), 1e-12),
                  1e-6, 1 - 1e-6)
    return jnp.log(p0 / (1 - p0))


@partial(jax.jit, static_argnames=("rounds", "depth", "n_bins", "precision"))
def _boost_trees(codes, y, w, eta, reg_lambda, gamma, min_child_weight,
                 rounds: int, depth: int, n_bins: int, precision: str):
    def one_round(margin, _):
        g, h = _gradients(margin, y, w)
        tree, node, _ = walk(codes, g, h, depth, n_bins, reg_lambda, gamma,
                             min_child_weight, eta, precision)
        return margin + _values(tree, node), tree

    margin0 = jnp.full(y.shape, _prior(y, w))
    margin, trees = jax.lax.scan(one_round, margin0, None, length=rounds)
    return trees, margin0[0], jax.nn.sigmoid(margin)


@highest
def boost_trees(codes, y, w, grid: Dict[str, Any], params: Dict[str, Any],
                precision: str = "float32"):
    """The plain boosted ensemble over unit-or-other weights ``w`` (n,), its
    trees kept: ``(trees with a leading round axis, prior margin, (n,)
    probabilities)`` — ``GradientBoostedTreesClassifier.fit_scores``'s
    arithmetic for one weight row."""
    return _boost_trees(
        codes, y, w, jnp.float32(params["eta"]),
        jnp.float32(params["reg_lambda"]), jnp.float32(params["gamma"]),
        jnp.float32(params["min_child_weight"]), int(grid["num_rounds"]),
        int(grid["max_depth"]), int(params["n_bins"]), precision)


@partial(jax.jit, static_argnames=("depth", "n_bins"))
def _replay(codes, y, w, trees, prior, eta, reg_lambda, gamma,
            min_child_weight, depth: int, n_bins: int):
    def one_round(carry, tree):
        forced_margin, replayed = carry
        g, h = _gradients(forced_margin, y, w)
        ours, node, regret = walk(codes, g, h, depth, n_bins, reg_lambda,
                                  gamma, min_child_weight, eta, forced=tree)
        return (forced_margin + _values(tree, node),
                replayed + _values(ours, node)), regret

    start = (jnp.full(y.shape, jnp.float32(prior)),
             jnp.full(y.shape, _prior(y, w)))
    (_, replayed), regrets = jax.lax.scan(one_round, start, trees)
    return jax.nn.sigmoid(replayed), regrets


@highest
def replay(codes, y, w, trees: Tree, prior, grid: Dict[str, Any],
           params: Dict[str, Any]):
    """An ensemble (``trees`` with a leading round axis, its ``prior``
    margin) held to float32 along its own history: ``((n,) probabilities of
    the replayed ensemble — the same trees with the reference's leaf values
    — , (rounds,) regret of each tree)``."""
    return _replay(
        codes, y, w, trees, prior, jnp.float32(params["eta"]),
        jnp.float32(params["reg_lambda"]), jnp.float32(params["gamma"]),
        jnp.float32(params["min_child_weight"]), int(grid["max_depth"]),
        int(params["n_bins"]))
