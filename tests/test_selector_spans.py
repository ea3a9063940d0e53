"""What two families in one selector add to a fit's profile
(docs/observability.md): a dispatch and a gather span a family, in dispatch
order; the host's waits labelled by whose they are; one ``host.choose`` with
the counts of the choice across families.  With one family every span is as
it was and the choice has no margin.  Nothing here is a time."""

import re

import numpy as np
import pytest

from transmogrifai_tpu import (BinaryClassificationModelSelector, Dataset,
                               FeatureBuilder)
from transmogrifai_tpu.data.dataset import Column
from transmogrifai_tpu.models.logistic import LogisticRegression
from transmogrifai_tpu.models.svm import LinearSVC
from transmogrifai_tpu.models.trees import GradientBoostedTreesClassifier
from transmogrifai_tpu.types import OPVector, RealNN

N, D = 640, 6
LR = (LogisticRegression, [{"reg_param": 0.01, "elastic_net": 0.0},
                           {"reg_param": 0.1, "elastic_net": 0.5}])
GBT = (GradientBoostedTreesClassifier, [{"num_rounds": 3, "max_depth": 2}])
SVC = (LinearSVC, [{"reg_param": 0.1}])


def _fit(families, seed=5, band=False):
    """``band``: the label is a band of one column, which no linear score
    ranks and a tree of two levels does."""
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(N, D)).astype(np.float32)
    signal = 2.0 * (np.abs(x[:, 0]) - 0.7) if band \
        else x[:, 0] - 0.5 * x[:, 1]
    y = (signal + rng.normal(scale=0.3, size=N) > 0).astype(np.float64)
    selector = BinaryClassificationModelSelector.with_cross_validation(
        num_folds=3, validation_metric="auPR", seed=7, stratify=False,
        models=[(cls(), [dict(g) for g in grid]) for cls, grid in families])
    label = FeatureBuilder.of("label", RealNN).extract_field().as_response()
    vec = FeatureBuilder.of("features", OPVector).extract_field() \
        .as_predictor()
    label.transform_with(selector, vec)
    fitted = selector.fit(Dataset({
        "label": Column(RealNN, y, np.ones(N, np.bool_)),
        "features": Column.vector(x)}))
    return selector.last_fit_profile, fitted.summary


def _cv_spans(profile):
    """The dispatch and gather spans themselves (a first fit's compile
    phases nest under a dispatch), in the order they began."""
    return [s for s in sorted(profile.spans, key=lambda s: s.start)
            if re.fullmatch(r"validate\.cv\.(dispatch|gather)\.\w+", s.path)]


def _activities(profile, name):
    return [s for s in sorted(profile.spans, key=lambda s: s.start)
            if s.path == "host." + name]


@pytest.mark.parametrize("families", [[LR, GBT], [GBT, LR], [LR, SVC, GBT]],
                         ids=["lr_gbt", "gbt_lr", "lr_svc_gbt"])
def test_every_family_has_its_dispatch_and_gather_in_dispatch_order(families):
    profile, _ = _fit(families)
    names = [cls.__name__ for cls, _ in families]
    spans = _cv_spans(profile)
    assert [s.path for s in spans] == [
        f"validate.cv.dispatch.{n}" for n in names] + [
        f"validate.cv.gather.{n}" for n in names]
    # every sweep is in the device's queue before the first metric is fetched
    dispatches, gathers = spans[:len(names)], spans[len(names):]
    assert max(s.start + s.seconds for s in dispatches) <= \
        min(s.start for s in gathers)


@pytest.mark.parametrize("families,winner_wait", [
    ([LR, GBT], "LogisticRegression/refit"),
    ([LR, SVC], None)], ids=["lr_gbt", "lr_svc"])
def test_the_waits_say_whose_they_are(families, winner_wait):
    profile, summary = _fit(families)
    waits = _activities(profile, "device_wait")
    assert all(s.counts.get("label") for s in waits), [
        (s.parent, s.counts) for s in waits]
    by_parent = {}
    for s in waits:
        by_parent.setdefault(s.parent, []).append(s.counts["label"])
    for cls, _ in families:
        name = cls.__name__
        assert by_parent[f"validate.cv.gather.{name}"] == [
            f"{name}/cv_gather"]
    refit = winner_wait or f"{summary.best_model_name}/refit"
    assert by_parent["refit"] == [refit]
    assert set(by_parent["train_eval"]) == {
        "BinaryClassificationEvaluator/summary"}
    assert set(by_parent) == {
        "refit", "train_eval", *(f"validate.cv.gather.{cls.__name__}"
                                 for cls, _ in families)}


@pytest.mark.parametrize("band", [False, True],
                         ids=["linear_label", "band_label"])
def test_one_choice_with_its_counts_and_the_margin_of_the_summary(band):
    profile, summary = _fit([LR, GBT], band=band)
    (chose,) = _activities(profile, "choose")
    assert chose.parent == "validate"
    assert set(chose.counts) == {"families", "candidates", "fold_models",
                                 "winner", "margin"}
    assert (chose.counts["families"], chose.counts["candidates"],
            chose.counts["fold_models"]) == (2, 3, 9)
    assert chose.counts["winner"] == summary.best_model_name == (
        "GradientBoostedTreesClassifier" if band
        else "LogisticRegression")
    means = {}
    for ev in summary.validation_results:
        means.setdefault(ev.model_name, []).append(
            float(np.mean(ev.metric_values)))
    best = {name: max(vals) for name, vals in means.items()}
    winner = chose.counts["winner"]
    (other,) = set(best) - {winner}
    assert chose.counts["margin"] == pytest.approx(
        best[winner] - best[other], abs=1e-12)
    assert chose.counts["margin"] > 0.0
    # the choice is made once every metric is on the host, before the release
    (release,) = _activities(profile, "release")
    last_gather = max(s.start + s.seconds for s in profile.spans
                      if s.path.startswith("validate.cv.gather."))
    assert last_gather <= chose.start <= release.start
    # a winner that is not the family dispatched last is refitted all the same
    refits = [s.counts["label"] for s in _activities(profile, "launch")
              if s.parent == "refit"]
    assert any(label.startswith(winner + "/") for label in refits), refits


@pytest.mark.parametrize("family", [LR, GBT, SVC],
                         ids=["lr", "gbt", "svc"])
def test_one_family_has_no_margin_and_its_spans_as_before(family):
    profile, summary = _fit([family])
    name = family[0].__name__
    (chose,) = _activities(profile, "choose")
    assert chose.counts == {"families": 1, "candidates": len(family[1]),
                            "fold_models": 3 * len(family[1]),
                            "winner": name}
    assert [s.path for s in _cv_spans(profile)] == [
        f"validate.cv.dispatch.{name}", f"validate.cv.gather.{name}"]
    phases = [s.path for s in sorted(profile.spans, key=lambda s: s.start)
              if "." not in s.path]
    assert phases == ["prep", "validate", "refit", "train_eval"]
    assert {s.path for s in profile.spans if s.path.startswith("host.")} \
        >= {"host.fold_weights", "host.launch", "host.device_wait",
            "host.choose", "host.release"}
    assert summary.best_model_name == name
