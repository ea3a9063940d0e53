"""The fold assignment: what the ids are, and how a fit makes them.

Unstratified, the ids are ``default_rng(seed).permutation(n) % k`` to the
bit (the definition the benchmark's reference holds the program to), made
by shuffling ``arange(n) % k`` in the ids' own dtype.  The stratified ids
are what they were.  In a selector fit the ids are made on a worker thread
while the fit's labels and base weights are placed; the CV metrics, the
winner and every span reader's view of the fit stay as they were.
"""

from __future__ import annotations

import concurrent.futures
import contextlib
import hashlib
import threading

import numpy as np
import pytest

from transmogrifai_tpu import (BinaryClassificationModelSelector, Dataset,
                               FeatureBuilder)
from transmogrifai_tpu.data.dataset import Column
from transmogrifai_tpu.evaluators.base import BinaryClassificationEvaluator
from transmogrifai_tpu.models import base as B
from transmogrifai_tpu.models.logistic import LogisticRegression
from transmogrifai_tpu.models.svm import LinearSVC
from transmogrifai_tpu.models.tuning import CrossValidator
from transmogrifai_tpu.parallel import mesh as M
from transmogrifai_tpu.perf.timers import record_phases
from transmogrifai_tpu.types import OPVector, RealNN

EV = BinaryClassificationEvaluator("auPR")


def _size(n, k):
    return k - 1 if n == "k-1" else n


@pytest.mark.parametrize("seed", [7, 42])
@pytest.mark.parametrize("k", [2, 3, 5, 10, 200])
@pytest.mark.parametrize("n", [0, 1, "k-1", 1_000_003, 2 ** 20])
def test_unstratified_ids_are_the_permutation_mod_k(n, k, seed):
    n = _size(n, k)
    ids = CrossValidator(EV, num_folds=k, seed=seed).fold_ids(np.zeros(n))
    expected = np.random.default_rng(seed).permutation(n) % k
    assert ids.dtype == (np.int8 if k <= 127 else np.int32)
    assert ids.shape == (n,)
    assert np.array_equal(ids, expected)


#: sha256 (first 16 hex digits) of the stratified ids as int64, taken from
#: the code before the unstratified ids were made by a shuffle in place
STRATIFIED_GOLDEN = {
    (0, 3, 7): "e3b0c44298fc1c14", (0, 3, 42): "e3b0c44298fc1c14",
    (0, 200, 7): "e3b0c44298fc1c14", (0, 200, 42): "e3b0c44298fc1c14",
    (1, 3, 7): "af5570f5a1810b7a", (1, 3, 42): "af5570f5a1810b7a",
    (1, 200, 7): "af5570f5a1810b7a", (1, 200, 42): "af5570f5a1810b7a",
    (4, 3, 7): "698e31029fef80c4", (4, 3, 42): "466cfdb0881b781b",
    (4, 200, 7): "698e31029fef80c4", (4, 200, 42): "466cfdb0881b781b",
    (1000003, 3, 7): "9f159c301dd03341",
    (1000003, 3, 42): "42ca6241605ba4c0",
    (1000003, 200, 7): "a422c5d222ec24eb",
    (1000003, 200, 42): "246eb384d49253e1",
}


@pytest.mark.parametrize("case", sorted(STRATIFIED_GOLDEN))
def test_stratified_ids_are_as_they_were(case):
    n, k, seed = case
    y = (np.random.default_rng(n + k).random(n) < 0.3).astype(np.float32)
    ids = CrossValidator(EV, num_folds=k, seed=seed,
                         stratify=True).fold_ids(y)
    assert ids.dtype == (np.int8 if k <= 127 else np.int32)
    digest = hashlib.sha256(ids.astype(np.int64).tobytes()).hexdigest()
    assert digest[:16] == STRATIFIED_GOLDEN[case]


# -- the ids beside the placements --------------------------------------------

def _selector(n=2048, seed=31, num_folds=3):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, 5)).astype(np.float32)
    y = (x[:, 0] - 0.5 * x[:, 1] + rng.normal(scale=0.5, size=n) > 0
         ).astype(np.float64)
    sel = BinaryClassificationModelSelector.with_cross_validation(
        num_folds=num_folds, seed=seed, models=[
            (LogisticRegression(), [{"reg_param": 0.01},
                                    {"reg_param": 0.1, "elastic_net": 0.5}]),
            (LinearSVC(), [{"reg_param": 0.01}])])
    label = FeatureBuilder.of("label", RealNN).extract_field().as_response()
    vec = FeatureBuilder.of("v", OPVector).extract_field().as_predictor()
    label.transform_with(sel, vec)
    ds = Dataset({"label": Column(RealNN, y, np.ones(n, np.bool_)),
                  "v": Column.vector(x)})
    return sel, ds


def _outcome(fitted):
    s = fitted.summary
    return {"winner": (s.best_model_name, sorted(s.best_grid.items())),
            "cv": [[np.float64(v).hex() for v in ev.metric_values]
                   for ev in s.validation_results],
            "train": {k: float(v).hex()
                      for k, v in s.train_evaluation.items()}}


@pytest.fixture
def fresh_placements(monkeypatch):
    """Empty placement caches for one test (the process's are put back), so
    its counts of misses and hits do not depend on what ran before it."""
    monkeypatch.setattr(M, "_PLACED_AUX_CACHE", {})
    monkeypatch.setattr(M, "_PLACED_ROWS_CACHE", {})


def _fold_id_threads():
    return [t for t in threading.enumerate()
            if t.name.startswith("fold_ids")]


def _spy_on_fold_ids(monkeypatch):
    """Every call of ``fold_ids``: the thread it ran on."""
    calls = []
    stock = CrossValidator.fold_ids

    def spy(self, y):
        calls.append(threading.current_thread())
        return stock(self, y)

    monkeypatch.setattr(CrossValidator, "fold_ids", spy)
    return calls


def test_a_fit_is_bitwise_the_fit_with_the_ids_made_first(monkeypatch):
    sel, ds = _selector()
    beside = _outcome(sel.fit(ds))
    monkeypatch.setattr(CrossValidator, "_fold_ids_beside_placements",
                        lambda self, models, y, base_w: self.fold_ids(y))
    assert _outcome(sel.fit(ds)) == beside


@pytest.mark.parametrize("mesh", [None, "4x2"])
def test_labels_and_base_weights_are_placed_once_beside_the_ids(
        mesh, fresh_placements):
    n = 3000
    sel, ds = _selector(n, seed=23)
    laid_out = contextlib.nullcontext() if mesh is None else \
        M.use_mesh(M.make_mesh(n_data=4, n_model=2))
    with laid_out:
        before = M.placement_stats()
        with record_phases() as rec:
            sel.fit(ds)
        after = M.placement_stats()
    moved = {c: {k: after[c][k] - before[c][k] for k in after[c]}
             for c in ("aux", "fit")}
    # fold_id, base_w and y: each placed once; every later request of the
    # fit passes by identity (LR: y, tw, vw; SVC: y, tw, vw; the blocks:
    # base_w; both evaluators: y; a linear refit: y, w)
    assert moved["aux"]["misses"] == 3 and moved["aux"]["hits"] == 0
    assert moved["fit"]["passed_through"] == 3 + 3 + 1 + 2 + 2
    # y and base_w went to the device before the wait for the ids ended
    (wait,) = [s for s in rec.spans if s.path == "host.fold_weights"]
    own = [s for s in rec.spans if s.path == "host.h2d"
           and s.counts["nbytes"] == 4 * M.padded_row_count(n)]
    assert len(own) == 2
    assert all(s.start + s.seconds <= wait.start + wait.seconds for s in own)


def test_an_error_in_the_ids_reaches_the_caller_and_no_thread_outlives(
        monkeypatch):
    class Broken(RuntimeError):
        pass

    def broken(self, y):
        raise Broken("no ids")

    monkeypatch.setattr(CrossValidator, "fold_ids", broken)
    sel, ds = _selector(1024)
    with pytest.raises(Broken, match="no ids"):
        sel.fit(ds)
    assert not _fold_id_threads()


def test_the_ids_are_made_off_the_calling_thread_and_joined(monkeypatch):
    calls = _spy_on_fold_ids(monkeypatch)
    rng = np.random.default_rng(4)
    x = rng.normal(size=(512, 4)).astype(np.float32)
    y = (x[:, 0] > 0).astype(np.float32)
    CrossValidator(EV, num_folds=2).validate(
        [(LogisticRegression(), [{"reg_param": 0.01}])], x, y)
    (made_on,) = calls
    assert made_on is not threading.main_thread()
    assert made_on.name.startswith("fold_ids")
    assert not made_on.is_alive() and not _fold_id_threads()


def test_a_validator_with_fold_weights_of_its_own_makes_no_thread(
        monkeypatch):
    calls = _spy_on_fold_ids(monkeypatch)

    class Own(CrossValidator):
        def fold_weights(self, y, base_w):
            return super().fold_weights(y, base_w)

    pools = []
    monkeypatch.setattr(concurrent.futures, "ThreadPoolExecutor",
                        lambda *a, **kw: pools.append(a) or pytest.fail(
                            "a worker pool was made"))
    rng = np.random.default_rng(5)
    x = rng.normal(size=(512, 4)).astype(np.float32)
    y = (x[:, 0] > 0).astype(np.float32)
    Own(EV, num_folds=2).validate(
        [(LogisticRegression(), [{"reg_param": 0.01}])], x, y)
    assert calls == [threading.main_thread()] and not pools


def test_no_family_with_device_folds_places_nothing_early(
        monkeypatch, fresh_placements):
    monkeypatch.setattr(B.PredictionEstimatorBase, "takes_device_folds",
                        lambda self: False)
    sel, ds = _selector(1024, seed=27)
    with record_phases() as rec:
        sel.fit(ds)
    (wait,) = [s for s in rec.spans if s.path == "host.fold_weights"]
    assert not [s for s in rec.spans if s.path in ("host.stamp", "host.h2d")
                and s.start < wait.start]
    assert wait.counts["hidden_s"] == 0.0


def test_spans_nest_and_the_wait_says_what_was_hidden():
    sel, ds = _selector(2048, seed=9)
    sel.fit(ds)
    spans = sorted(sel.last_fit_profile.spans,
                   key=lambda s: (s.start, -s.seconds))
    open_ = []                         # ends of the open spans, innermost last
    for s in spans:
        while open_ and s.start >= open_[-1]:
            open_.pop()
        end = s.start + s.seconds
        assert not open_ or end <= open_[-1] + 1e-9, s.path
        open_.append(end)
    (wait,) = [s for s in spans if s.path == "host.fold_weights"]
    assert wait.parent == "validate"
    assert set(wait.counts) == {"made_s", "hidden_s"}
    assert 0.0 <= wait.counts["hidden_s"] <= wait.counts["made_s"]


def test_every_fit_makes_its_own_ids(monkeypatch):
    calls = _spy_on_fold_ids(monkeypatch)
    sel, ds = _selector(1024, seed=12)
    sel.fit(ds)
    sel.fit(ds)
    assert len(calls) == 2



def test_a_fit_vector_is_placed_once_in_float32_and_a_placed_one_passes(
        fresh_placements):
    y = (np.arange(100) % 2).astype(np.float32)
    n_padded = M.padded_row_count(len(y))
    assert M.fit_vector(y) is y
    with M.fit_placements():
        placed = M.place_fit_vector(y, n_padded)
        assert M.place_fit_vector(y, n_padded) is placed
        assert M.place_fit_vector(placed, n_padded) is placed
    wide = M.place_fit_vector(y.astype(np.float64), n_padded)
    assert wide.dtype == np.float32 and wide.shape == (n_padded,)
    assert np.array_equal(np.asarray(wide)[:len(y)], y)
