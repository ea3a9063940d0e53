"""A fit places its own row-aligned inputs once and derives fold weights,
±1 targets and unit weights on the device (ISSUE 26).

Bits: what the device derives equals what the host built before, to the bit,
with the shape, dtype and sharding the placed host arrays had — so every
family program's cache key and result are what they were.  Counts: what a CPU
can prove of the host work that is gone (bytes stamped and placed, spans).
"""

from __future__ import annotations

import contextlib

import jax
import numpy as np
import pytest

from transmogrifai_tpu.evaluators.base import BinaryClassificationEvaluator
from transmogrifai_tpu.models import base as B
from transmogrifai_tpu.models import svm
from transmogrifai_tpu.models.logistic import LogisticRegression
from transmogrifai_tpu.models.selector import BinaryClassificationModelSelector
from transmogrifai_tpu.models.svm import LinearSVC
from transmogrifai_tpu.models.trees import RandomForestClassifier
from transmogrifai_tpu.models.tuning import (
    CrossValidator, DataBalancer, FoldWeights, TrainValidationSplit, folds_of)
from transmogrifai_tpu.parallel import mesh as M
from transmogrifai_tpu.perf.programs import _sharding_sig
from transmogrifai_tpu.perf.timers import record_phases


def _labels(n, seed, pos=0.2):
    rng = np.random.default_rng(seed)
    return (rng.random(n) < pos).astype(np.float32)


def _validator(kind):
    ev = BinaryClassificationEvaluator("auPR")
    if kind == "cv":
        return CrossValidator(ev, num_folds=3, seed=11)
    if kind == "stratified":
        return CrossValidator(ev, num_folds=4, seed=11, stratify=True)
    return TrainValidationSplit(ev, train_ratio=0.7, seed=11)


def _bits(a):
    return np.ascontiguousarray(np.asarray(a), np.float32).view(np.uint32)


def _mesh(kind):
    if kind is None:
        return contextlib.nullcontext()
    return M.use_mesh(M.make_mesh(n_data=4, n_model=2))


@pytest.mark.parametrize("mesh", [None, "4x2"])
@pytest.mark.parametrize("n", [3000, 1024])      # 3000 pads to 4096
@pytest.mark.parametrize("weights", ["unit", "balanced"])
@pytest.mark.parametrize("kind", ["cv", "stratified", "split"])
def test_device_fold_weights_equal_the_host_form_bit_for_bit(
        kind, weights, n, mesh):
    y = _labels(n, seed=n)
    base_w = np.ones_like(y) if weights == "unit" else \
        DataBalancer(sample_fraction=0.45, reserve_test_fraction=0.1
                     ).prepare(y)[0]
    assert weights == "unit" or len(np.unique(base_w)) == 3
    validator = _validator(kind)
    with _mesh(mesh):
        host = validator.fold_weights(y, base_w)
        folds = FoldWeights(validator.fold_ids(y), base_w,
                            validator.num_folds)
        n_padded = M.padded_row_count(n)
        for derived, built in zip(folds.device(), host):
            assert isinstance(derived, jax.Array)
            placed = M.place_cached(
                M.pad_host(built, [(0, 0), (0, n_padded - n)]),
                (None, M.DATA_AXIS))
            assert derived.shape == placed.shape == (validator.num_folds,
                                                     n_padded)
            assert derived.dtype == placed.dtype == np.float32
            assert _sharding_sig(derived) == _sharding_sig(placed)
            assert np.array_equal(_bits(derived), _bits(placed))
        assert np.array_equal(_bits(folds.host()[0]), _bits(host[0]))
        assert folds.binary == (weights == "unit")


@pytest.mark.parametrize("mesh", [None, "4x2"])
@pytest.mark.parametrize("n", [3000, 1024])
def test_device_targets_and_unit_weights_equal_the_padded_host_vectors(
        n, mesh):
    y = _labels(n, seed=n + 1)
    with _mesh(mesh):
        n_padded = M.padded_row_count(n)
        yd = M.place_fit_rows(y, n_padded)
        pad = (0, n_padded - n)
        for derived, built in [
                (B.derive_on_device(svm._sign_targets, yd, np.int32(n),
                                    axes=(M.DATA_AXIS,), label="t/targets"),
                 np.where(y > 0.5, 1.0, -1.0).astype(np.float32)),
                (B.unit_weights(n, n_padded), np.ones_like(y))]:
            placed = M.place_cached(np.pad(built, pad), (M.DATA_AXIS,))
            assert derived.shape == placed.shape
            assert derived.dtype == placed.dtype
            assert _sharding_sig(derived) == _sharding_sig(placed)
            assert np.array_equal(_bits(derived), _bits(placed))


def _selector(n, seed, families=("lr", "svc", "rf"), validator="cv",
              holdout=0.1):
    from transmogrifai_tpu import Dataset, FeatureBuilder
    from transmogrifai_tpu.data.dataset import Column
    from transmogrifai_tpu.types import OPVector, RealNN

    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, 6)).astype(np.float32)
    beta = rng.normal(size=6)
    y = (rng.random(n) < 1.0 / (1.0 + np.exp(1.0 - x @ beta))
         ).astype(np.float32)
    ds = Dataset({
        "label": Column(RealNN, y.astype(np.float64), np.ones(n, np.bool_)),
        "v": Column.vector(x)})
    label = FeatureBuilder.of("label", RealNN).extract_field().as_response()
    vec = FeatureBuilder.of("v", OPVector).extract_field().as_predictor()
    models = {
        "lr": (LogisticRegression(), [{"reg_param": 0.01},
                                      {"reg_param": 0.1, "elastic_net": 0.5}]),
        "svc": (LinearSVC(), [{"reg_param": 0.01}]),
        "rf": (RandomForestClassifier(), [{"num_trees": 4, "max_depth": 3}]),
    }
    make = BinaryClassificationModelSelector.with_cross_validation \
        if validator == "cv" else \
        BinaryClassificationModelSelector.with_train_validation_split
    sel = make(models=[models[f] for f in families],
               splitter=DataBalancer(sample_fraction=0.45,
                                     reserve_test_fraction=holdout))
    label.transform_with(sel, vec)
    return sel, ds, x


def _outcome(fitted):
    s = fitted.summary
    model = fitted.model
    return {
        "winner": (s.best_model_name, tuple(sorted(s.best_grid.items()))),
        "cv": [[np.float64(v).hex() for v in ev.metric_values]
               for ev in s.validation_results],
        "train": {k: float(v).hex() for k, v in s.train_evaluation.items()},
        "holdout": {k: float(v).hex()
                    for k, v in s.holdout_evaluation.items()},
        "coef": np.asarray(model.coef).tobytes()
        if hasattr(model, "coef") else None,
        "intercept": float(model.intercept).hex()
        if hasattr(model, "intercept") else None,
    }


@pytest.mark.parametrize("validator", ["cv", "split"])
@pytest.mark.parametrize("n", [3000, 1024])
def test_a_selector_fit_is_bit_equal_to_the_host_form_through_the_numpy_way(
        n, validator, monkeypatch):
    """LR + SVC + a tree family: CV metrics, winner, train and holdout
    evaluation and refit coefficients, against the same fit with the host
    fold weights forced through the numpy way in (no family takes device
    folds, no fit table: every array is padded, stamped and looked up)."""
    sel, ds, _ = _selector(n, seed=5, validator=validator)
    before = M.placement_stats()["fit"]
    device_way = _outcome(sel.fit(ds))
    moved = M.placement_stats()["fit"]
    assert moved["derived"] - before["derived"] == 4    # tw, vw, ±1, ones
    assert device_way["coef"] is not None and device_way["holdout"]

    monkeypatch.setattr(B.PredictionEstimatorBase, "takes_device_folds",
                        lambda self: False)
    monkeypatch.setattr(M, "fit_placements", contextlib.nullcontext)
    before = M.placement_stats()["fit"]
    numpy_way = _outcome(sel.fit(ds))
    moved = M.placement_stats()["fit"]
    # only SVC's targets and the evaluators' unit weights have no host form
    assert moved["derived"] - before["derived"] == 2
    assert moved["passed_through"] == before["passed_through"]
    assert numpy_way == device_way


def test_a_family_with_no_device_path_gets_the_host_form():
    """Generic estimators, ``cv_sweep`` overrides and a device sweep that
    declines its grid (returns None) see (k, n) numpy blocks, as before."""
    seen = {}

    class Overrides(LogisticRegression):
        def cv_sweep(self, x, y, train_w, val_w, grids, metric_fn):
            seen["override"] = (type(train_w), train_w.shape)
            return np.full((len(grids), train_w.shape[0]), 0.5)

    class Declines(LinearSVC):
        def _cv_sweep_generic(self, x, y, train_w, val_w, grids, metric_fn):
            seen["generic"] = (type(train_w), train_w.shape,
                               folds_of(train_w))
            return super()._cv_sweep_generic(x, y, train_w, val_w, grids,
                                             metric_fn)

    n = 600
    rng = np.random.default_rng(3)
    x = rng.normal(size=(n, 4)).astype(np.float32)
    y = (x[:, 0] + 0.3 * rng.normal(size=n) > 0).astype(np.float32)
    assert not Overrides().takes_device_folds()
    assert Declines().takes_device_folds()
    cv = CrossValidator(BinaryClassificationEvaluator("auPR"), num_folds=2)
    result = cv.validate(
        [(Overrides(), [{}]),
         (Declines(), [{"max_iter": 5}]),         # not a sweepable grid key
         (LogisticRegression(), [{"reg_param": 0.01}])], x, y)
    assert seen["override"] == (np.ndarray, (2, n))
    assert seen["generic"] == (np.ndarray, (2, n), None)
    assert all(np.isfinite(ev.metric_values).all()
               for ev in result.evaluations)
    host = cv.fold_weights(y, np.ones_like(y))
    # outside validate() nothing vouches for a device block: a fetch, cut to n
    dev = FoldWeights(cv.fold_ids(y), np.ones_like(y), 2).device()
    fetched = B.host_fold_weights(*dev, n)
    assert all(np.array_equal(a, b) for a, b in zip(fetched, host))


def test_a_validator_with_fold_weights_of_its_own_is_asked_for_them():
    """A subclass's (or a patched) ``fold_weights`` is what the families
    get, as host blocks through the numpy way in: the ids stand only for
    the stock weights."""
    seen = []

    class Halved(CrossValidator):
        def fold_weights(self, y, base_w):
            train_w, val_w = super().fold_weights(y, base_w)
            train_w[:, ::2] = 0.0
            return train_w, val_w

    class Spy(LogisticRegression):
        def _cv_sweep_device(self, x, y, train_w, val_w, grids, metric_fn):
            seen.append((type(train_w), float(np.asarray(train_w).sum())))
            return super()._cv_sweep_device(x, y, train_w, val_w, grids,
                                            metric_fn)

    n = 512
    rng = np.random.default_rng(9)
    x = rng.normal(size=(n, 4)).astype(np.float32)
    y = (x[:, 0] > 0).astype(np.float32)
    ev = BinaryClassificationEvaluator("auPR")
    before = M.placement_stats()["fit"]["derived"]
    Halved(ev, num_folds=2).validate([(Spy(), [{"reg_param": 0.01}])], x, y)
    assert M.placement_stats()["fit"]["derived"] == before
    CrossValidator(ev, num_folds=2).validate(
        [(Spy(), [{"reg_param": 0.01}])], x, y)
    assert M.placement_stats()["fit"]["derived"] == before + 2
    (halved, halved_sum), (stock, stock_sum) = seen
    assert halved is np.ndarray and issubclass(stock, jax.Array)
    assert halved_sum == stock_sum / 2 == n / 2


class TestCounts:
    """What a CPU can prove: bytes stamped and placed, pass-throughs, spans."""

    def _moved(self, before, after):
        return {c: {k: after[c][k] - before[c][k] for k in after[c]}
                for c in after}

    @pytest.mark.parametrize("n", [4096, 3000])
    def test_second_fit_stamps_three_vectors_and_places_nothing(self, n):
        sel, ds, x = _selector(n, seed=17, families=("lr", "svc"),
                               holdout=0.0)
        sel.fit(ds)
        before = M.placement_stats()
        sel.fit(ds)
        moved = self._moved(before, M.placement_stats())
        n_padded = M.padded_row_count(n)
        # one full stamp each of ``y``, ``base_w`` (float32) and ``fold_id``
        # (int8), where a fit stamped 13-14 float32 vectors' worth before
        assert moved["aux"] == {
            "hits": 3, "misses": 0, "bytes_placed": 0,
            "bytes_stamped": 2 * 4 * n_padded + n_padded}
        # the table: hits only (memo hits at a real size; full hashes here)
        assert moved["rows"]["misses"] == 0
        assert moved["rows"]["bytes_placed"] == 0
        assert moved["rows"]["bytes_stamped"] == \
            moved["rows"]["hits"] * x.nbytes

        profile = sel.last_fit_profile
        stamps = [s for s in profile.spans if s.path == "host.stamp"]
        own = [s for s in stamps if s.counts["nbytes"] != x.nbytes]
        assert len(own) == 3 and len(stamps) - 3 == moved["rows"]["hits"]
        # at a bucket size the pad width is 0: no copy, no span
        pads = [s.counts["nbytes"] for s in profile.spans
                if s.path == "host.pad"]
        assert sorted(pads) == ([] if n == n_padded else [n, 4 * n, 4 * n])
        assert [s.path for s in profile.spans].count("host.fold_weights") == 1
        assert "host.targets" not in {s.path for s in profile.spans}

    @pytest.mark.parametrize("families", [("lr", "svc"), ("lr", "svc", "rf")])
    def test_pass_throughs_and_derived_are_what_the_families_imply(
            self, families):
        n = 3000
        sel, ds, _ = _selector(n, seed=17, families=families, holdout=0.0)
        before = M.placement_stats()
        fitted = sel.fit(ds)
        moved = self._moved(before, M.placement_stats())["fit"]
        n_padded = M.padded_row_count(n)
        # The validator places y and base_w beside the fold ids, so LR's y
        # and the fold blocks' base_w pass through.  LR: y, tw, vw.  SVC: y,
        # tw, vw.  RF: tw, vw.  The blocks: base_w.  Both evaluators: y.
        # A linear winner's refit: y, w (the forest's refit places its own).
        linear = fitted.summary.best_model_name != "RandomForestClassifier"
        rf = "rf" in families
        assert moved["passed_through"] == \
            3 + 3 + 2 * rf + 1 + 2 + 2 * linear
        assert moved["derived"] == 4                # tw, vw, ±1, unit weights
        assert moved["bytes_derived"] == (2 * 3 + 2) * 4 * n_padded
        assert moved["bytes_passed"] >= 4 * 3 * 4 * n_padded
        # no (k, n) block is padded or hashed on the host, tree family or not
        blocks = (3 * n * 4, 3 * n_padded * 4)
        assert not [s for s in sel.last_fit_profile.spans
                    if s.path in ("host.pad", "host.stamp", "host.h2d")
                    and s.counts["nbytes"] in blocks]

    def test_first_fit_places_no_fold_block_from_the_host(self):
        n = 2048
        sel, ds, _ = _selector(n, seed=int(np.random.SeedSequence().entropy
                                           % 2**31), families=("lr", "svc"),
                               holdout=0.0)
        before = M.placement_stats()
        with record_phases() as rec:
            sel.fit(ds)
        moved = self._moved(before, M.placement_stats())
        # fold_id (int8), base_w and y (float32); nothing of size (k, n)
        assert moved["aux"]["bytes_placed"] == n + 4 * n + 4 * n
        assert moved["aux"]["misses"] == 3 and moved["aux"]["hits"] == 0
        assert sorted(s.counts["nbytes"] for s in rec.spans
                      if s.path == "host.h2d")[:3] == [n, 4 * n, 4 * n]
        assert not [s for s in rec.spans if s.path in ("host.h2d", "host.pad")
                    and s.counts["nbytes"] == 3 * n * 4]

    def test_nothing_is_kept_from_one_fit_to_the_next(self):
        sel, ds, _ = _selector(1024, seed=23, families=("lr",))
        assert M._FIT_PLACED.get() is None
        sel.fit(ds)
        assert M._FIT_PLACED.get() is None

    def test_place_fit_rows_answers_by_identity_inside_a_fit_only(self):
        rng = np.random.default_rng(int(np.random.SeedSequence().entropy
                                        % 2**31))
        v = rng.normal(size=700).astype(np.float32)
        n_padded = M.padded_row_count(700)
        with record_phases() as rec:
            outside = [M.place_fit_rows(v, n_padded) for _ in range(2)]
            with M.fit_placements():
                inside = [M.place_fit_rows(v, n_padded) for _ in range(3)]
                placed = M.place_fit_rows(inside[0], n_padded)
        assert outside[0] is outside[1] is inside[0] is inside[2] is placed
        assert inside[0].shape == (n_padded,)
        assert np.array_equal(np.asarray(inside[0])[:700], v)
        # two stamps outside (miss, then content hit), one inside
        assert [s.path for s in rec.spans].count("host.stamp") == 3
        assert [s.path for s in rec.spans].count("host.pad") == 3
        assert [s.path for s in rec.spans].count("host.h2d") == 1
