"""The tree walk's row select gathers the LEVEL's 2^l split columns on the
MXU and picks the row's own among them (``perf/kernels/routing.py``
``level_columns_select_xla``) while the level has fewer nodes than the table
has columns, and compares the row's column index with all d columns from
there on.  Every product is an exact small integer with one nonzero a row,
so the bar is equality to the last bit: with a plain walk kept here (numpy's
``binned[i, feat[node[i]]]``, one level at a time) and with the same program
run with the compare-reduce in the select's place at every level."""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from transmogrifai_tpu.evaluators import metrics as M
from transmogrifai_tpu.models import trees as T
from transmogrifai_tpu.perf.kernels import routing as KR


def _np_route(codes, feat, thr, miss, leaf, node, first, n_bins):
    """``_route_level`` by gathers: (next node, the row's code where it is at
    a node of the level, who is)."""
    local = node - first
    live = local >= 0
    at = np.clip(local, 0, feat.shape[-1] - 1)
    col = np.take_along_axis(feat, at, axis=-1)
    nb = codes[np.arange(codes.shape[0])[None, :], col]
    go_left = np.where(nb == n_bins, np.take_along_axis(miss, at, axis=-1),
                       nb <= np.take_along_axis(thr, at, axis=-1))
    child = np.where(go_left, 2 * node + 1, 2 * node + 2)
    stay = ~live | np.take_along_axis(leaf, at, axis=-1)
    return np.where(stay, node, child).astype(np.int32), nb, live


def _heap_walk(tree, codes, max_depth, n_bins):
    """Final node of every row of one tree: ``tbl[node]`` over the heap."""
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


@pytest.fixture
def compare_reduce_everywhere(monkeypatch):
    """Call it and every level takes the compare-reduce over all d columns:
    the select as it was before the level's-columns form."""
    def switch():
        monkeypatch.setattr(KR, "_level_takes_columns", lambda nn, d: False)
    return switch


LEVELS = {
    # name: (lanes, level, n, d, n_bins, cap on C's bytes or None)
    "level0-3lanes-d128": (3, 0, 600, 128, 32, None),
    "level1-1lane-d128": (1, 1, 600, 128, 32, None),
    "level5-3lanes-d128": (3, 5, 900, 128, 32, None),
    "level3-3lanes-d16": (3, 3, 600, 16, 32, None),
    # nn >= d: the compare-reduce over all 16 columns
    "level4-3lanes-d16": (3, 4, 600, 16, 32, None),
    "level6-1lane-d16": (1, 6, 900, 16, 32, None),
    "level7-1lane-d128": (1, 7, 900, 128, 32, None),
    "level2-bins255-d300": (3, 2, 600, 300, 255, None),
    "level4-bins300-d300": (1, 4, 600, 300, 300, None),
    # C over its cap: rows in chunks of 128, 700 padded to 768
    "level3-chunked-padded": (3, 3, 700, 16, 32, 0),
    "level5-chunked-150lanes": (150, 5, 300, 128, 32, 0),
}


@pytest.mark.parametrize("case", list(LEVELS))
def test_route_level_equals_the_gathered_step_bitwise(case, monkeypatch):
    lanes, level, n, d, n_bins, cap = LEVELS[case]
    monkeypatch.setattr(T, "_HIST_CHUNK", 128)
    if cap is not None:
        monkeypatch.setattr(KR, "_COLUMNS_WHOLE_MAX_BYTES", cap)
    rng = np.random.default_rng(len(case) + level)
    nn, first = 2 ** level, 2 ** level - 1
    codes = rng.integers(0, n_bins + 1, (n, d)).astype(np.int32)
    feat = rng.integers(0, d, (lanes, nn)).astype(np.int32)
    thr = rng.integers(0, n_bins, (lanes, nn)).astype(np.int32)
    miss = rng.random((lanes, nn)) < 0.5
    leaf = rng.random((lanes, nn)) < 0.2
    node = (first + rng.integers(0, nn, (lanes, n))).astype(np.int32)
    if level:       # a fifth of the rows stopped at an earlier leaf
        node = np.where(rng.random((lanes, n)) < 0.2,
                        rng.integers(0, first, (lanes, n)), node
                        ).astype(np.int32)
    want, want_nb, live = _np_route(codes, feat, thr, miss, leaf, node,
                                    first, n_bins)
    seen = []
    entry = KR.level_select_lanes

    def spy(*args):
        seen.append(np.asarray(entry(*args)))
        return seen[-1]

    monkeypatch.setattr(KR, "level_select_lanes", spy)
    got = T._route_level(*(jnp.asarray(a) for a in
                           (codes, feat, thr, miss, leaf, node)),
                         first, n_bins)
    np.testing.assert_array_equal(np.asarray(got), want)
    (nb,) = seen
    np.testing.assert_array_equal(nb[live], want_nb[live])
    # which form the shapes chose, and what the count says of it
    assert KR._level_takes_columns(nn, d) == (nn < d)
    if nn < d:
        assert not nb[~live].any()


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
    return codes, y, grad, hess


def _grow(codes, grad, hess, depth, n_bins, gamma, min_child_weight):
    lanes, d = grad.shape[0], codes.shape[1]
    fn = jax.jit(partial(T._grow_trees, max_depth=depth, n_bins=n_bins,
                         reg_lambda=1.0, alpha=0.0, gamma=gamma,
                         min_child_weight=min_child_weight, eta=0.3,
                         max_delta_step=0.0))
    return fn(jnp.asarray(codes), jnp.asarray(grad), jnp.asarray(hess),
              jnp.ones((lanes, d), jnp.float32), jax.random.PRNGKey(0))


GROWN = {
    # name: (depth, lanes, n, d, n_bins, gamma, min_child_weight,
    #        zero-weight share, histogram chunk, cap on C's bytes or None)
    "depth1-1lane-d16": (1, 1, 600, 16, 32, 0.0, 1.0, 0.0, None, None),
    "depth1-3lanes-d16": (1, 3, 600, 16, 32, 0.0, 1.0, 0.0, None, None),
    "depth3-1lane-d16": (3, 1, 600, 16, 32, 0.0, 1.0, 0.0, None, None),
    "depth3-3lanes-d16": (3, 3, 600, 16, 32, 0.0, 1.0, 0.0, None, None),
    # d = 16: levels 4 on have nn >= d and take the compare-reduce
    "depth6-1lane-d16": (6, 1, 900, 16, 32, 0.0, 1.0, 0.0, None, None),
    "depth6-3lanes-d16": (6, 3, 900, 16, 32, 0.0, 1.0, 0.0, None, None),
    "depth8-1lane-d16": (8, 1, 1500, 16, 32, 0.0, 1.0, 0.0, None, None),
    "depth8-3lanes-d16": (8, 3, 1500, 16, 32, 0.0, 1.0, 0.0, None, None),
    # d = 128: every level of depth 6 gathers its columns, level 7 does not
    "depth3-3lanes-d128": (3, 3, 600, 128, 32, 0.0, 1.0, 0.0, None, None),
    "depth6-3lanes-d128": (6, 3, 900, 128, 32, 0.0, 1.0, 0.0, None, None),
    "depth8-1lane-d128": (8, 1, 1500, 128, 32, 0.0, 1.0, 0.0, None, None),
    # codes past bfloat16's 256 (float32 operands) and just under it
    "depth3-3lanes-d300-bins255": (3, 3, 600, 300, 255, 0.0, 1.0, 0.0, None,
                                   None),
    "depth6-1lane-d300-bins300": (6, 1, 900, 300, 300, 0.0, 1.0, 0.0, None,
                                  None),
    # shallow nodes become leaves: rows stop early and wait out the walk
    "depth6-gamma": (6, 3, 900, 16, 32, 0.6, 1.0, 0.0, None, None),
    "depth6-min-child-weight": (6, 1, 900, 128, 32, 0.0, 12.0, 0.0, None,
                                None),
    # rows of weight 0 and, 700 rows in chunks of 128, 68 rows of padding;
    # with C capped, the select scans the same chunks
    "depth6-zero-weight-padded": (6, 3, 700, 16, 32, 0.0, 1.0, 0.3, 128,
                                  None),
    "depth6-zero-weight-padded-chunked-select": (6, 3, 700, 128, 32, 0.0, 1.0,
                                                 0.3, 128, 0),
    "depth3-min-child-weight-padded-chunked-select": (3, 1, 700, 16, 32, 0.0,
                                                      10.0, 0.5, 128, 0),
}


@pytest.mark.parametrize("case", list(GROWN))
def test_grown_trees_equal_the_plain_walk_and_the_compare_reduce_grower(
        case, monkeypatch, compare_reduce_everywhere):
    (depth, lanes, n, d, n_bins, gamma, mcw, zero_w, chunk,
     cap) = GROWN[case]
    if chunk:
        monkeypatch.setattr(T, "_HIST_CHUNK", chunk)
        assert n > 2 * chunk and n % chunk
    if cap is not None:
        monkeypatch.setattr(KR, "_COLUMNS_WHOLE_MAX_BYTES", cap)
    codes, _, grad, hess = _problem(len(case) + depth, n, d, n_bins, lanes,
                                    zero_w)
    tree, node = _grow(codes, grad, hess, depth, n_bins, gamma, mcw)
    node = np.asarray(node)
    assert node.shape == (lanes, n)
    stopped_early = 0
    for lane in range(lanes):
        one = T.Tree(*(np.asarray(a)[lane] for a in tree))
        want = _heap_walk(one, codes, depth, n_bins)
        np.testing.assert_array_equal(node[lane], want)
        assert np.asarray(one.is_leaf)[want].all()
        stopped_early += int((want < 2 ** depth - 1).sum())
    if gamma or mcw > 1.0:
        assert stopped_early, "no row stopped above the deepest level"
    # the same grower with the compare-reduce at every level grows the same
    # heap and leaves every row at the same node, bit for bit
    compare_reduce_everywhere()
    tree_ref, node_ref = _grow(codes, grad, hess, depth, n_bins, gamma, mcw)
    for a, b in zip(tree, tree_ref):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(node, np.asarray(node_ref))


def _boost(codes, y, depth, n_bins, rounds=5):
    n = codes.shape[0]
    f32 = jnp.float32
    return T._fit_gbt_impl(
        jnp.asarray(codes), jnp.asarray(y), jnp.ones(n, f32),
        jax.random.PRNGKey(1), n_rounds=rounds, max_depth=depth,
        n_bins=n_bins, objective="binary:logistic", num_class=1,
        subsample=1.0, colsample_bytree=1.0, colsample_bylevel=1.0,
        eta=f32(0.3), reg_lambda=f32(1.0), alpha=f32(0.0), gamma=f32(0.05),
        min_child_weight=f32(1.0), scale_pos_weight=f32(1.0),
        max_delta_step=f32(0.0), base_score=jnp.zeros(1, f32))


@pytest.mark.parametrize("depth,d,n,cap", [
    (4, 10, 500, None), (6, 16, 900, None), (6, 128, 900, None),
    # 5 trees x nn x rows over the cap: 500 rows padded to 512 in the select
    (4, 10, 500, 0), (6, 128, 900, 0)])
def test_the_stacked_predictor_walks_its_trees_as_lanes(
        depth, d, n, cap, monkeypatch, compare_reduce_everywhere):
    """``_predict_trees_sum`` over a boosted ensemble (the trees are the
    lanes of ONE walk, so the select sees how many there are) equals the sum
    of whole-heap walks tree by tree, and itself with the compare-reduce."""
    n_bins = 32
    monkeypatch.setattr(T, "_HIST_CHUNK", 128)
    if cap is not None:
        monkeypatch.setattr(KR, "_COLUMNS_WHOLE_MAX_BYTES", cap)
    codes, y, _, _ = _problem(depth + d, n, d, n_bins, 1, 0.0)
    margin, trees = _boost(codes, y, depth, n_bins)
    predict = partial(T._predict_trees_sum.__wrapped__, max_depth=depth,
                      n_bins=n_bins)
    got = np.asarray(predict(trees, jnp.asarray(codes)))
    want = np.zeros((n, 1), np.float32)
    for r in range(5):
        one = T.Tree(*(np.asarray(a)[r] for a in trees))
        leaf = _heap_walk(one, codes, depth, n_bins)
        want += np.asarray(one.value)[leaf]
        np.testing.assert_array_equal(         # a stack of one tree
            np.asarray(predict(T.Tree(*(jnp.asarray(a)[None] for a in one)),
                               jnp.asarray(codes))),
            np.asarray(one.value)[leaf])
    np.testing.assert_array_equal(got, want)
    compare_reduce_everywhere()
    margin_ref, trees_ref = _boost(codes, y, depth, n_bins)
    np.testing.assert_array_equal(np.asarray(margin), np.asarray(margin_ref))
    for a, b in zip(trees, trees_ref):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(
        np.asarray(predict(trees, jnp.asarray(codes))), got)


@pytest.mark.parametrize("depth,int_exact", [(4, False), (6, True)])
def test_the_forest_sweep_chunks_its_select_where_the_lanes_force_it(
        depth, int_exact, monkeypatch, compare_reduce_everywhere):
    """3 folds x 10 trees = 30 lanes of one ``_grow_trees`` call: with ``C``
    capped at 2^18 bytes the three lanes of a boosted sweep would gather
    every level whole, the forest's 30 only its first levels — the rest go
    through the row-chunk scan.  Fold metrics equal to the last bit with
    the compare-reduce in the select's place."""
    n, d, n_bins, n_trees, cap = 700, 16, 32, 10, 1 << 18
    monkeypatch.setattr(T, "_HIST_CHUNK", 128)
    monkeypatch.setattr(KR, "_COLUMNS_WHOLE_MAX_BYTES", cap)
    rows, item = 768, 4                     # padded rows, float32 off the TPU
    assert 3 * 2 ** 3 * rows * item <= cap            # a boosted sweep: whole
    assert 30 * 1 * rows * item <= cap < 30 * 4 * rows * item   # the forest
    codes, y, _, _ = _problem(depth, n, d, n_bins, 1, 0.0)
    rng = np.random.default_rng(depth)
    folds = rng.integers(0, 3, n)
    tw = np.stack([folds != k for k in range(3)]).astype(np.float32)
    vw = np.stack([folds == k for k in range(3)]).astype(np.float32)
    masks = (rng.random((n_trees, d)) < 0.7).astype(np.float32)
    masks[:, 0] = 1.0
    boot = rng.poisson(1.0, (n_trees, n)).astype(np.float32)

    def sweep():
        return np.asarray(T._forest_cv_program.__wrapped__(
            jnp.asarray(codes), jnp.asarray(y), jnp.asarray(y[:, None]),
            jnp.asarray(tw), jnp.asarray(vw), jnp.asarray(masks),
            jnp.asarray(boot), max_depth=depth, n_bins=n_bins,
            reg_lambda=jnp.float32(1.0), min_child_weight=jnp.float32(1.0),
            classification=True, metric_fn=M.au_pr, int_exact=int_exact))

    got = sweep()
    assert got.shape == (3,) and np.isfinite(got).all()
    compare_reduce_everywhere()
    np.testing.assert_array_equal(sweep(), got)


def test_bfloat16_operands_hold_every_code_up_to_256(monkeypatch):
    """The TPU's operand dtype on the CPU's matmul: codes 0..256 come back
    whole through bfloat16 (8 significant bits), whole and chunked."""
    monkeypatch.setattr(KR, "select_dtype", lambda n_bins: jnp.bfloat16)
    n, d, lanes, nn = 514, 40, 3, 8
    rng = np.random.default_rng(0)
    codes = rng.integers(0, 257, (n, d)).astype(np.int32)
    codes[:257, 0] = np.arange(257)
    feat = rng.integers(0, d, (lanes, nn)).astype(np.int32)
    feat[:, 0] = 0
    local = rng.integers(0, nn, (lanes, n)).astype(np.int32)
    local[:, :257] = 0
    want = codes[np.arange(n)[None, :], np.take_along_axis(feat, local, 1)]
    for cap in (KR._COLUMNS_WHOLE_MAX_BYTES, 0):
        monkeypatch.setattr(KR, "_COLUMNS_WHOLE_MAX_BYTES", cap)
        got = KR.level_columns_select_xla(
            jnp.asarray(codes), jnp.asarray(feat), jnp.asarray(local), 256,
            128)
        np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("depth,d,want", [(3, 128, 7), (6, 128, 63),
                                          (8, 128, 127 + 128),
                                          (6, 16, 15 + 2 * 16)])
def test_select_cols_is_on_the_launch_counts(depth, d, want):
    est = T.XGBoostClassifier(num_rounds=2, max_depth=depth)
    codes = jax.ShapeDtypeStruct((640, d), np.int32)
    assert est._launch_counts(codes, 3, 1)["select_cols"] == want
