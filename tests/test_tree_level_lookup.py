"""The tree walk reads a row's node in its LEVEL's slice of the heap
(``models/trees._level_lookup``: 2^l nodes, the four routing tables packed
into one word), not in the whole heap.  Every table is an integer or a flag
and a masked sum of one entry is exact, so the bar is equality to the last
bit with a plain walk kept here: numpy gathers ``tbl[node]`` over the WHOLE
heap, one level at a time."""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from transmogrifai_tpu.models import trees as T


def _heap_walk(tree, codes, max_depth, n_bins):
    """Final node of every row: ``tbl[node]`` over the whole heap."""
    feat, thr, miss, leaf = (np.asarray(getattr(tree, k)) for k in
                             ("feat", "thr_bin", "miss_left", "is_leaf"))
    rows = np.arange(codes.shape[0])
    node = np.zeros(codes.shape[0], np.int32)
    for _ in range(max_depth):
        nb = codes[rows, feat[node]]
        go_left = np.where(nb == n_bins, miss[node], nb <= thr[node])
        child = np.where(go_left, 2 * node + 1, 2 * node + 2)
        node = np.where(leaf[node], node, child).astype(np.int32)
    return node


def _gather_lookup(feat, thr_bin, miss_left, is_leaf, local, d, n_bins):
    """``_level_lookup`` by plain gathers of the unpacked tables."""
    at = jnp.clip(local, 0, feat.shape[-1] - 1)
    return tuple(jnp.take_along_axis(t, at, axis=-1)
                 for t in (feat, thr_bin, miss_left, is_leaf))


def _problem(seed, n, d, n_bins, lanes, zero_weight):
    rng = np.random.default_rng(seed)
    codes = rng.integers(0, n_bins + 1, size=(n, d)).astype(np.int32)
    # a signal on a few columns so that deep levels still find splits
    score = (codes[:, 0] - codes[:, d // 2] + 0.5 * codes[:, d - 1]
             + rng.normal(scale=n_bins / 4, size=n))
    y = (score > np.median(score)).astype(np.float32)
    w = rng.uniform(0.5, 1.5, size=(lanes, n)).astype(np.float32)
    if zero_weight:
        w *= rng.random(size=(lanes, n)) > zero_weight
    p = rng.uniform(0.2, 0.8, size=(lanes, n)).astype(np.float32)
    grad = (w * (p - y[None]))[..., None]
    hess = (w * p * (1 - p))[..., None]
    return codes, grad, hess


def _grow(codes, grad, hess, depth, n_bins, gamma, min_child_weight):
    lanes, d = grad.shape[0], codes.shape[1]
    fn = jax.jit(partial(T._grow_trees, max_depth=depth, n_bins=n_bins,
                         reg_lambda=1.0, alpha=0.0, gamma=gamma,
                         min_child_weight=min_child_weight, eta=0.3,
                         max_delta_step=0.0))
    return fn(jnp.asarray(codes), jnp.asarray(grad), jnp.asarray(hess),
              jnp.ones((lanes, d), jnp.float32), jax.random.PRNGKey(0))


CASES = {
    # name: (depth, lanes, n, d, n_bins, gamma, min_child_weight,
    #        zero-weight share, histogram chunk)
    "depth1-1lane": (1, 1, 600, 12, 32, 0.0, 1.0, 0.0, None),
    "depth1-3lanes": (1, 3, 600, 12, 32, 0.0, 1.0, 0.0, None),
    "depth3-1lane": (3, 1, 600, 12, 32, 0.0, 1.0, 0.0, None),
    "depth3-3lanes": (3, 3, 600, 12, 32, 0.0, 1.0, 0.0, None),
    "depth6-1lane": (6, 1, 900, 12, 32, 0.0, 1.0, 0.0, None),
    "depth6-3lanes": (6, 3, 900, 12, 32, 0.0, 1.0, 0.0, None),
    # shallow nodes become leaves: rows stop early and wait out the walk
    "depth6-gamma": (6, 3, 900, 12, 32, 0.6, 1.0, 0.0, None),
    "depth6-min-child-weight": (6, 1, 900, 12, 32, 0.0, 12.0, 0.0, None),
    "depth3-min-child-weight": (3, 3, 600, 12, 32, 0.0, 25.0, 0.0, None),
    # rows of weight 0 and, 700 rows in chunks of 128, 68 rows of padding
    "depth6-zero-weight-padded": (6, 3, 700, 12, 32, 0.0, 1.0, 0.3, 128),
    "depth3-zero-weight-padded": (3, 1, 700, 12, 32, 0.0, 10.0, 0.5, 128),
    # a word wider than the cells' 7 + 6 + 2 bits: 9 + 7 + 2
    "wide-d300-bins100": (3, 3, 600, 300, 100, 0.0, 1.0, 0.0, None),
    "wide-depth6-d260-bins255": (6, 1, 900, 260, 255, 0.0, 1.0, 0.0, None),
}


@pytest.mark.parametrize("case", list(CASES))
def test_level_walk_equals_the_whole_heap_walk_bitwise(case, monkeypatch):
    depth, lanes, n, d, n_bins, gamma, mcw, zero_w, chunk = CASES[case]
    if chunk:
        monkeypatch.setattr(T, "_HIST_CHUNK", chunk)
        assert n > 2 * chunk and n % chunk
    codes, grad, hess = _problem(len(case) + depth, n, d, n_bins, lanes,
                                 zero_w)
    tree, node = _grow(codes, grad, hess, depth, n_bins, gamma, mcw)
    node = np.asarray(node)
    assert node.shape == (lanes, n)
    m = 2 ** (depth + 1) - 1
    stopped_early = 0
    for lane in range(lanes):
        one = T.Tree(*(np.asarray(a)[lane] for a in tree))
        want = _heap_walk(one, codes, depth, n_bins)
        np.testing.assert_array_equal(node[lane], want)
        assert np.asarray(one.is_leaf)[want].all()
        stopped_early += int((want < m // 2).sum())
        got = T._predict_trees_sum(
            T.Tree(*(jnp.asarray(a)[None] for a in one)), jnp.asarray(codes),
            depth, n_bins)                  # a stack of one tree
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(one.value)[want])
    if gamma or mcw > 1.0:
        assert stopped_early, "no row stopped above the deepest level"
    elif depth > 1 and not zero_w:
        assert len(np.unique(node)) > 2 ** (depth - 1)
    # the trees themselves: the grower with its look-up swapped for gathers
    # of the unpacked tables grows the same heap, bit for bit
    monkeypatch.setattr(T, "_level_lookup", _gather_lookup)
    tree_ref, node_ref = _grow(codes, grad, hess, depth, n_bins, gamma, mcw)
    for a, b in zip(tree, tree_ref):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(node, np.asarray(node_ref))


def test_the_stacked_predictor_sums_the_level_walks():
    """``_predict_trees_sum`` (trees as the lanes of one walk) over a boosted
    ensemble equals the sum of whole-heap walks, tree by tree."""
    n, d, n_bins, depth = 500, 10, 32, 4
    codes, _, _ = _problem(5, n, d, n_bins, 1, 0.0)
    rng = np.random.default_rng(6)
    y = (codes[:, 0] + rng.normal(scale=6, size=n) > 16).astype(np.float32)
    _, trees = T._fit_gbt(
        jnp.asarray(codes), jnp.asarray(y), jnp.ones(n, jnp.float32),
        jax.random.PRNGKey(1), n_rounds=5, max_depth=depth, n_bins=n_bins,
        objective="binary:logistic", num_class=1, subsample=1.0,
        colsample_bytree=1.0, colsample_bylevel=1.0, eta=jnp.float32(0.3),
        reg_lambda=jnp.float32(1.0), alpha=jnp.float32(0.0),
        gamma=jnp.float32(0.05), min_child_weight=jnp.float32(1.0),
        scale_pos_weight=jnp.float32(1.0), max_delta_step=jnp.float32(0.0),
        base_score=jnp.zeros(1, jnp.float32))
    got = np.asarray(T._predict_trees_sum(trees, jnp.asarray(codes), depth,
                                          n_bins))
    want = np.zeros((n, 1), np.float32)
    for r in range(5):
        one = T.Tree(*(np.asarray(a)[r] for a in trees))
        want += np.asarray(one.value)[_heap_walk(one, codes, depth, n_bins)]
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("d,n_bins,ok", [
    (1, 1, True), (128, 32, True), (2 ** 20, 256, True), (2 ** 20 + 1, 256, False),
    (2 ** 22, 64, True), (2 ** 23, 64, False)])
def test_the_packed_word_checks_its_widths_at_trace_time(d, n_bins, ok):
    """feat < d and thr_bin <= n_bins have to fit a 31-bit word beside the
    two flags: the widths come from the caller's ``d`` and ``n_bins``."""
    tbl = jnp.zeros((2,), jnp.int32)
    flag = jnp.zeros((2,), bool)
    local = jnp.zeros((4,), jnp.int32)
    if not ok:
        with pytest.raises(AssertionError, match="node word"):
            T._level_lookup(tbl, tbl, flag, flag, local, d, n_bins)
        return
    # the largest values the widths admit come back whole
    out = T._level_lookup(jnp.array([d - 1, 0]), jnp.array([n_bins, 0]),
                          jnp.array([True, False]), jnp.array([True, False]),
                          jnp.array([0, 1, -1, 0]), d, n_bins)
    assert [np.asarray(o).tolist() for o in out] == [
        [d - 1, 0, 0, d - 1], [n_bins, 0, 0, n_bins],
        [True, False, False, True], [True, False, False, True]]


@pytest.mark.parametrize("depth,want", [(1, 1 + 3), (3, 7 + 15),
                                        (6, 63 + 127), (12, 4095 + 8191)])
def test_lookup_nodes_counts_a_slice_a_level_and_the_heap_once(depth, want):
    assert T._lookup_nodes(depth) == want
    est = T.XGBoostClassifier(num_rounds=2, max_depth=depth)
    codes = jax.ShapeDtypeStruct((640, 4), np.int32)
    assert est._launch_counts(codes, 3, 1)["lookup_nodes"] == want
