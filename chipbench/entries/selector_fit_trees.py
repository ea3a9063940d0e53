"""Entry ``selector_fit_trees``: ``selector_fit``'s timed call — one whole
``ModelSelector.fit(dataset)`` — for a configuration of boosted trees, with
a comparison that suits them.  Set-up, the timed step and the release are
``selector_fit``'s own code, imported; ``collect`` also keeps the winner's
trees.

Why its own comparison.  Fifty rounds of boosting amplify the last bit: one
split that falls the other way on a tie sends every later round down another
path, and the free-running scores of two correct ensembles end 0.2 standard
deviations apart (root mean square; the widest gap 1.2 to 1.5) — the program
on seven seeds of eight, a float8 control and a bfloat16 one alike (PERF.md,
section 2).  ``selector_fit``'s ``refit_score_gap`` and ``train_eval_gap``
compare free-running scores and tell no precision from another here.  So the
winner's refit is held to the float32 reference tree by tree, along its own
history (``chipbench/reference/treereplay.py``, named by the family's
``replay`` key):

- ``choice_regret``: how far the reference ranks what the program CHOSE
  under its own best — ``selector_fit``'s grid point (by mean CV metric)
  and, here, every split of the winner's trees: the most gain any node gave
  up against the best candidate of the reference's float32 histograms, as a
  share of the best gain at that tree's root (a tie costs nothing; the
  notes carry it alone as ``split_regret``).  The larger of the two; the
  configuration's limits take ``selector_fit``'s names because the guards
  hold every cell's control to ``selector_fit.compare`` too;
- ``refit_score_gap``: the widest gap, over the sampled rows, between the
  winner's scores (the program's own ``predict_column``) and the scores of
  the REPLAYED ensemble — the same trees with the leaf values the reference
  puts there — in standard deviations of the latter;
- ``train_eval_gap``: the winner's train evaluation against the replayed
  ensemble's (in the notes under ``replays`` whether or not the
  configuration gives it a limit);
- ``cv_metric_gap`` is ``selector_fit``'s to the letter: the sweep hands
  back no trees, so its fold-models are compared free-running, under a
  limit that the bit-amplification sets.
"""

from __future__ import annotations

import importlib
import time
from typing import Any, Dict, List

import numpy as np

from . import selector_fit as single
from .selector_fit import (  # noqa: F401 — the harness calls these as ours
    enable_cache, release, setup, step)


def collect(state, records: List[Dict[str, Any]], table, seed: int) -> None:
    """``selector_fit.collect``, and every timed fit's winning ensemble as
    host arrays in the replay's names (``model.trees``, ``base_score``)."""
    for rec in records:
        model = rec["model"]
        trees = {k: np.asarray(v) for k, v in model.trees.items()}
        rec["trees"] = {
            "prior": float(np.asarray(model.base_score).reshape(-1)[0]),
            "feat": trees["feat"].astype(np.int32),
            "cut": trees["thr_bin"].astype(np.int32),
            "miss_left": trees["miss_left"].astype(bool),
            "leaf": trees["is_leaf"].astype(bool),
            "value": trees["value"][..., 0].astype(np.float32)}
    single.collect(state, records, table, seed)


def compare(config: Dict[str, Any], table, records: List[Dict[str, Any]],
            seed: int, precision: str = "float32", control: bool = False):
    """Program (every timed fit's record) against the plain reference at the
    timed size: ``({name: [value, limit]}, detail)`` for the numbers the
    configuration's ``limits`` name.  ``control=True`` puts the reference,
    computed in ``precision``, in the program's place."""
    import jax.numpy as jnp

    from ..reference import common, treegrow

    cv = config["cv"]
    n, folds = table.x.shape[0], int(cv["folds"])
    fold = common.fold_ids(n, folds, int(cv["seed"]))
    y = np.asarray(table.y, np.float64)
    if not 0.1 <= y.mean() <= 0.9:
        raise ValueError(f"positive rate {y.mean()}: the reference assumes "
                         "the configuration's balancer leaves the weights "
                         "at 1")
    xd, yd = jnp.asarray(table.x), jnp.asarray(y, jnp.float32)
    train_w = jnp.asarray(np.stack(
        [(fold != f) for f in range(folds)]).astype(np.float32))
    ones = jnp.ones(n, jnp.float32)
    rows = single.sample_rows(n, seed)
    val_rows = [np.flatnonzero(fold == f) for f in range(folds)]

    def sweep(precision_: str) -> Dict[str, np.ndarray]:
        """{family key: CV metric (g, k)} of the reference's own sweep."""
        out = {}
        for fam in config["families"]:
            ref = single._family(config, fam["key"])[1]
            scores = np.asarray(ref.fit_scores(
                xd, yd, train_w, fam["grid"], fam.get("params", {}),
                precision_))
            out[fam["key"]] = np.array([
                [common.au_pr(scores[g, f][val_rows[f]], y[val_rows[f]])
                 for f in range(folds)] for g in range(len(fam["grid"]))])
        return out

    def replayer(fam: Dict[str, Any]):
        params = fam.get("params", {})
        n_bins = int(params["n_bins"])
        codes = treegrow.bin_codes(
            xd, jnp.asarray(treegrow.quantile_edges(xd, n_bins)))
        return importlib.import_module(
            f"chipbench.reference.{fam['replay']}"), codes, params

    t0 = time.perf_counter()
    ref_cv = sweep("float32")
    ref_mean = {(k, g): float(v[g].mean()) for k, v in ref_cv.items()
                for g in range(v.shape[0])}
    ref_best = max(ref_mean.values())
    if control:
        low_cv = sweep(precision)
        key, g = max(ref_mean, key=lambda kg: float(low_cv[kg[0]][kg[1]].mean()))
        fam = single._family(config, key)[0]
        replay, codes, params = replayer(fam)
        trees, prior, low = replay.boost_trees(codes, yd, ones, fam["grid"][g],
                                               params, precision)
        low = np.asarray(low, np.float64)
        records = [{"cv": {k: v.tolist() for k, v in low_cv.items()},
                    "best": {"family": key, "grid": fam["grid"][g]},
                    "sample_scores": low[rows],
                    "train_eval": {cv["metric"]: common.au_pr(low, y)},
                    "trees": {"prior": float(prior), **{
                        k: np.asarray(v) for k, v in trees.items()}}}]

    values = dict.fromkeys(("cv_metric_gap", "choice_regret",
                            "refit_score_gap", "train_eval_gap"), 0.0)
    replayed: Dict[Any, Any] = {}
    detail: Dict[str, Any] = {"cv_gaps": {}, "replays": []}
    for rec in records:
        for key, want in ref_cv.items():
            got = np.asarray(rec["cv"][key], np.float64)
            values["cv_metric_gap"] = max(values["cv_metric_gap"],
                                          float(np.abs(got - want).max()))
            detail["cv_gaps"][key] = (got - want).tolist()
        best = rec["best"]
        fam = single._family(config, best["family"])[0]
        g = next(i for i, grid in enumerate(fam["grid"])
                 if all(float(grid[k]) == float(best["grid"].get(k, np.nan))
                        for k in grid))
        values["choice_regret"] = max(
            values["choice_regret"], ref_best - ref_mean[(best["family"], g)])
        trees = dict(rec["trees"])
        prior = trees.pop("prior")
        # the fits of one window grow the same trees: one replay serves them
        tag = (best["family"], g, prior) + tuple(
            v.tobytes() for v in trees.values())
        if tag not in replayed:
            replay, codes, params = replayer(fam)
            scores, regrets = replay.replay(
                codes, yd, ones, {k: jnp.asarray(v) for k, v in trees.items()},
                prior, fam["grid"][g], params)
            replayed[tag] = (np.asarray(scores, np.float64),
                             np.asarray(regrets, np.float64))
        want, regrets = replayed[tag]
        gap = np.abs(rec["sample_scores"] - want[rows]) / max(
            float(want[rows].std()), 1e-12)
        found = {"choice_regret": float(regrets.max()),
                 "refit_score_gap": float(gap.max()),
                 "train_eval_gap": abs(rec["train_eval"][cv["metric"]]
                                       - common.au_pr(want, y))}
        for name, value in found.items():
            values[name] = max(values[name], value)
        detail["replays"].append({
            "split_regret": found["choice_regret"],
            "train_eval_gap": found["train_eval_gap"],
            "trees_with_regret": int((regrets > 0.0).sum()),
            "score_gap_rms": float(np.sqrt(np.mean(gap ** 2)))})
    detail["reference_s"] = time.perf_counter() - t0
    detail["reference_means"] = {f"{k}/{g}": v
                                 for (k, g), v in ref_mean.items()}
    return {k: [values[k], lim] for k, lim in config["limits"].items()}, detail
