"""The tree grower's host spans and launch counts (docs/observability.md):
a tiny boosted selector fit records ``host.bin`` with its cache hits and, on
the ``host.launch`` spans of the sweep and of the winner's refit, what the
program is about to do — from shapes at dispatch, so a CPU run reads the
same counts a chip run does.  Nothing here is a time."""

import jax
import numpy as np
import pytest

from transmogrifai_tpu import (BinaryClassificationModelSelector, Dataset,
                               FeatureBuilder)
from transmogrifai_tpu.data.dataset import Column
from transmogrifai_tpu.models import trees as T
from transmogrifai_tpu.parallel.mesh import place_rows_bucketed_cached
from transmogrifai_tpu.perf.kernels import dispatch as KD
from transmogrifai_tpu.types import OPVector, RealNN

N, D, ROUNDS, DEPTH = 640, 6, 3, 2
CV = "GradientBoostedTreesClassifier/cv_program"
REFIT = "GradientBoostedTreesClassifier/gbt_refit"


def _fit(seed):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(N, D)).astype(np.float32)
    y = (x[:, 0] - 0.5 * x[:, 1] + rng.normal(scale=0.3, size=N) > 0
         ).astype(np.float64)
    selector = BinaryClassificationModelSelector.with_cross_validation(
        num_folds=3, validation_metric="auPR", seed=7, stratify=False,
        models=[(T.GradientBoostedTreesClassifier(),
                 [{"num_rounds": ROUNDS, "max_depth": DEPTH}])])
    label = FeatureBuilder.of("label", RealNN).extract_field().as_response()
    vec = FeatureBuilder.of("features", OPVector).extract_field() \
        .as_predictor()
    label.transform_with(selector, vec)
    selector.fit(Dataset({
        "label": Column(RealNN, y, np.ones(N, np.bool_)),
        "features": Column.vector(x)}))
    rows_padded = int(place_rows_bucketed_cached(x)[0].shape[0])
    return selector.last_fit_profile, rows_padded


def _launches(profile):
    return {s.counts["label"]: s.counts for s in profile.spans
            if s.path == "host.launch" and s.counts
            and "binoh_bytes" in s.counts}


@pytest.fixture
def small_chunks(monkeypatch):
    """640 rows take the chunk-scanned path with its materialised one-hot."""
    monkeypatch.setattr(T, "_HIST_CHUNK", 128)
    jax.clear_caches()      # ``_fit_gbt`` is a plain jit: retrace it
    yield
    jax.clear_caches()      # and do not leak the tiny-chunk trace


def test_a_boosted_fit_records_its_bin_span_and_launch_counts(small_chunks):
    before = KD.kernel_selections()
    profile, rows = _fit(31)
    bins = [s for s in profile.spans if s.path == "host.bin"]
    assert len(bins) == 2                   # the sweep, then the refit
    for s in bins:
        assert set(s.counts) == {"n_bins", "rows", "edges_hit", "codes_hit"}
        assert (s.counts["n_bins"], s.counts["rows"]) == (32, N)
        assert s.parent in ("validate.cv.dispatch."
                            "GradientBoostedTreesClassifier", "refit")
    # the first look-up pays for the edges and the codes, the refit for
    # neither: one sketch and one digitise a fit
    assert [(s.counts["edges_hit"], s.counts["codes_hit"]) for s in bins] \
        == [(False, False), (True, True)]
    launches = _launches(profile)
    assert set(launches) == {CV, REFIT}
    want = {"rounds": ROUNDS, "levels": DEPTH, "hist_kernel": "xla",
            "route_kernel": "xla", "binoh_bytes": rows * 33 * D,
            "binoh_walks": ROUNDS * DEPTH,
            # levels of 1 and 2 nodes, then the heap of 7 once for the value
            # (the whole-heap walk before PR 35: 4 x 2 + 1 look-ups of 7)
            "lookup_nodes": (1 + 2) + 7,
            # the row's code is picked among the level's 1 and 2 split
            # columns (the compare-reduce before PR 37: 2 levels x D)
            "select_cols": 1 + 2}
    assert rows % 128 == 0 and rows >= N
    # M of the deepest fresh level: lanes x 1 left child x (grad, hess)
    assert launches[CV] == {"label": CV, "lanes": 3, **want,
                            "hist_rows_deepest": 6,
                            "grid_point": 0, "grid_points": 1}
    # the refit is no grid point: the selector's ``best`` names its point
    assert launches[REFIT] == {"label": REFIT, "lanes": 1, **want,
                               "hist_rows_deepest": 2}
    # the one-hot both read is built once a fit, by a program of its own
    built = [s.counts["label"] for s in profile.spans
             if s.path == "host.launch"
             and s.counts["label"].endswith("/bin_onehot")]
    assert built == ["GradientBoostedTreesClassifier/bin_onehot"]
    # asking at dispatch is not a trace-time decision: the counter moved by
    # the traces alone, whichever they were
    traced = {k: v - before.get(k, 0)
              for k, v in KD.kernel_selections().items()}
    profile2, _ = _fit(31)                  # warm: nothing is traced
    assert KD.kernel_selections() == {
        k: before.get(k, 0) + v for k, v in traced.items()}
    assert _launches(profile2) == launches
    # a second fit of the same table finds the edges cached, and the codes
    # when the placement handed back the same device block
    assert all(s.counts["edges_hit"] for s in profile2.spans
               if s.path == "host.bin")


def test_the_unchunked_path_counts_no_one_hot():
    """640 rows under the default chunk of 2048: ``_materialize_bin_oh``
    declines, and the count says so."""
    assert T._HIST_CHUNK * 2 > N
    profile, rows = _fit(32)
    launches = _launches(profile)
    assert {c["binoh_bytes"] for c in launches.values()} == {0}
    assert set(launches) == {CV, REFIT}
    assert not any(s.counts["label"].endswith("/bin_onehot")
                   for s in profile.spans if s.path == "host.launch")
    assert T._binoh_bytes(rows, D, 32) == 0
    # over the cap the program rebuilds the one-hot every pass: 0 again
    assert T._binoh_bytes(2 ** 21, 128, 32) == 0
    assert T._binoh_bytes(2 ** 20, 128, 32) == 2 ** 20 * 33 * 128


def test_the_counts_name_the_pallas_kernel_where_it_is_admitted():
    est = T.GradientBoostedTreesClassifier(num_rounds=2, max_depth=2)
    codes = jax.ShapeDtypeStruct((8192, 4), np.int32)
    with KD.force_kernel_mode("interpret"):
        counts = est._launch_counts(codes, 3, 1)
    assert counts["hist_kernel"] == "hist_level_pallas"
    assert counts["route_kernel"] == "xla"  # in every mode
    assert counts["binoh_bytes"] == 0       # the kernel builds its own
    with KD.force_kernel_mode("xla"):
        counts = est._launch_counts(codes, 3, 1)
    assert counts == {"lanes": 3, "rounds": 2, "levels": 2,
                      "hist_kernel": "xla", "route_kernel": "xla",
                      "binoh_bytes": 8192 * 33 * 4,
                      "binoh_walks": 4, "hist_rows_deepest": 6,
                      "lookup_nodes": 10, "select_cols": 1 + 2}


def test_the_shared_one_hot_changes_no_bit_and_is_built_once_a_sweep(
        small_chunks):
    """The int8 operand handed in holds what the program otherwise rebuilds
    at every pass: margins and trees equal to the last bit; a sweep called
    on its own (no selector fit around it) still builds one for all its grid
    points."""
    import jax.numpy as jnp

    from transmogrifai_tpu.perf.timers import record_phases

    rng = np.random.default_rng(33)
    x = rng.normal(size=(N, D)).astype(np.float32)
    y = (x[:, 0] + rng.normal(scale=0.5, size=N) > 0).astype(np.float32)
    codes = jnp.asarray(T.quantile_bin(x, 32)[0])
    args = (codes, jnp.asarray(y), jnp.ones(N, jnp.float32),
            jax.random.PRNGKey(42))
    kw = dict(n_rounds=4, max_depth=3, n_bins=32, objective="binary:logistic",
              num_class=1, subsample=1.0, colsample_bytree=1.0,
              colsample_bylevel=1.0, eta=jnp.float32(0.3),
              reg_lambda=jnp.float32(1.0), alpha=jnp.float32(0.0),
              gamma=jnp.float32(0.0), min_child_weight=jnp.float32(1.0),
              scale_pos_weight=jnp.float32(1.0),
              max_delta_step=jnp.float32(0.0),
              base_score=jnp.zeros(1, jnp.float32))
    built = T._bin_onehot(codes, n_bins=32)
    assert built.shape == (N // 128, 128, 33 * D) and built.dtype == jnp.int8
    inside = T._fit_gbt(*args, **kw)
    handed = T._fit_gbt(*args, **kw, bin_oh=built)
    for a, b in zip(jax.tree.leaves(inside), jax.tree.leaves(handed)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    folds = rng.integers(0, 3, N)
    train_w = np.stack([(folds != f) for f in range(3)]).astype(np.float32)
    val_w = np.stack([(folds == f) for f in range(3)]).astype(np.float32)
    with record_phases() as rec:
        T.GradientBoostedTreesClassifier(num_rounds=2).cv_sweep(
            x, y.astype(np.float64), train_w, val_w,
            [{"max_depth": 2}, {"max_depth": 3}],
            lambda p, y_, w: (w * ((p > 0.5) == y_)).sum() / w.sum())
    labels = [s.counts["label"].rsplit("/", 1)[1] for s in rec.spans
              if s.path == "host.launch" and "/" in s.counts["label"]]
    assert labels.count("bin_onehot") == 1 and labels.count("cv_program") == 2
