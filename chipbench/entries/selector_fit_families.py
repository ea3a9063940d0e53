"""Entry ``selector_fit_families``: ``selector_fit``'s timed call — one whole
``ModelSelector.fit(dataset)`` — for a configuration that puts SEVERAL
families in one selector, the way the default binary selector comes: every
family's sweep is in the device's queue before the first metric is fetched,
one choice is made among all their grid points, and the winner — whichever
family it is — is refitted.  Set-up, the timed call and the release are
``selector_fit``'s own code, imported; the step also records what the
placement caches did during the fit.

The comparison follows the winner.  A winner whose family names a ``replay``
(the boosted trees) is held tree by tree along its own history, as
``selector_fit_trees.compare`` holds it, under the configuration's
``replay_limits``; any other winner as ``selector_fit.compare`` holds it, a
free-running float32 refit under ``limits``.  Either way the reference sweeps
EVERY family, so

- ``cv_metric_gap`` is over all the fit's fold-models, under the limit the
  free-running trees set (``limits``), and
- ``choice_regret`` is over all the families' grid points: how far the
  reference ranks the chosen (family, point) under its own best of them all;
- ``cv_metric_gap_<key>``, for each family under ``family_limits``, is that
  family's fold-models alone under the limit it has where it is the only
  family: the trees' amplification must not hide a linear sweep gone wrong.
  The limit is ``family_limits[key]["cv_metric_gap"]`` as long as
  ``limits["cv_metric_gap"]`` is what the file states it beside
  (``stated_beside``), and moves by as much as that one is moved: a guard
  that allows the all-families limit something for the size it runs at
  allows this one the same.

The notes carry ``family_margin``: the winner family's best mean metric less
the best of any other family, in the program's last fit and in the reference.

``BENCHMARK.json`` runs it as the cell ``lr_gbt_sweep_1m``
(``binsel_lr_gbt_d128`` x ``postprep_1m``, one chip): python3 chipbench/run.py
--workload lr_gbt_sweep_1m --seed <n> --seconds 10 --trace <0|1>.
"""

from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

from . import selector_fit as single
from . import selector_fit_trees as trees
from .selector_fit import (  # noqa: F401 — the harness calls these as ours
    enable_cache, release, setup)

#: the placement counters a fit's record keeps, as ``placement_<cache>_<count>``
PLACEMENT_COUNTS = ("misses", "bytes_placed", "bytes_stamped")


def _placement_counters() -> Dict[str, float]:
    """The program's ``rows`` and ``aux`` placement-cache counters; empty on
    a program that has none."""
    from transmogrifai_tpu.parallel.mesh import placement_stats

    stats = placement_stats()
    return {f"placement_{cache}_{name}": stats[cache][name]
            for cache in ("rows", "aux") for name in PLACEMENT_COUNTS
            if name in stats.get(cache, {})}


def step(state, may_compile: bool = False) -> Dict[str, Any]:
    """One whole ``fit``: ``selector_fit``'s record, with what the fit moved
    the placement counters by beside its other counters."""
    before = _placement_counters()
    rec = single.step(state, may_compile)
    rec["counters"].update(
        {k: v - before[k] for k, v in _placement_counters().items()})
    return rec


def _replayed(config: Dict[str, Any], key: str) -> bool:
    return "replay" in single._family(config, key)[0]


def collect(state, records: List[Dict[str, Any]], table, seed: int) -> None:
    """The sampled scores of every timed fit's winner, and the trees of the
    winners that are replayed."""
    replayed = [r for r in records
                if _replayed(state.config, r["best"]["family"])]
    others = [r for r in records
              if not _replayed(state.config, r["best"]["family"])]
    if replayed:
        trees.collect(state, replayed, table, seed)
    if others:
        single.collect(state, others, table, seed)


def _compared_means(detail: Dict[str, Any]) -> Dict[str, float]:
    """``{"<key>/<g>": mean CV metric}`` of what the accepted comparison held
    last (the window's last fit, or the control): the reference's means and
    the gaps it notes beside them."""
    return {tag: ref + float(np.mean(
        detail["cv_gaps"][tag.split("/")[0]][int(tag.split("/")[1])]))
        for tag, ref in detail["reference_means"].items()}


def _margin(means: Dict[str, float]) -> float:
    """The winner family's best mean less the best of any other family."""
    best: Dict[str, float] = {}
    for tag, mean in means.items():
        key = tag.split("/")[0]
        best[key] = max(best.get(key, -np.inf), mean)
    ranked = sorted(best.values(), reverse=True)
    return ranked[0] - ranked[1] if len(ranked) > 1 else float("nan")


def _family_gap(key: str, records: List[Dict[str, Any]],
                detail: Dict[str, Any], control: bool) -> float:
    """Widest gap of family ``key``'s fold-models to the reference over
    ``records``; the reference's own metrics are the last record's less the
    gaps the accepted comparison notes for it."""
    gaps = np.asarray(detail["cv_gaps"][key], np.float64)
    if control:             # the control is the one record, made inside
        return float(np.abs(gaps).max())
    want = np.asarray(records[-1]["cv"][key], np.float64) - gaps
    return max(float(np.abs(np.asarray(r["cv"][key], np.float64)
                            - want).max()) for r in records)


def compare(config: Dict[str, Any], table, records: List[Dict[str, Any]],
            seed: int, precision: str = "float32", control: bool = False):
    """Program (every timed fit's record) against the plain reference at the
    timed size: ``({name: [value, limit]}, detail)``.  ``control=True`` puts
    the reference, computed in ``precision``, in the program's place."""
    replay_config = {**config, "limits": {
        "cv_metric_gap": config["limits"]["cv_metric_gap"],
        **config["replay_limits"]}}
    if control:
        # the control chooses its own winner inside the accepted comparison
        out, detail = single.compare(config, table, [], seed,
                                     precision=precision, control=True)
        means = _compared_means(detail)
        replayed = _replayed(config, max(means, key=means.get).split("/")[0])
        if replayed:
            out, detail = trees.compare(replay_config, table, [], seed,
                                        precision=precision, control=True)
    else:
        kinds = {_replayed(config, r["best"]["family"]) for r in records}
        if len(kinds) != 1:     # same table, same seed: every fit chooses alike
            raise ValueError("the window's fits chose winners that are held "
                             f"in different ways: {[r['best'] for r in records]}")
        (replayed,) = kinds
        out, detail = (trees.compare(replay_config, table, records, seed)
                       if replayed else
                       single.compare(config, table, records, seed))
    for key, lim in config.get("family_limits", {}).items():
        out[f"cv_metric_gap_{key}"] = [
            _family_gap(key, records, detail, control),
            lim["cv_metric_gap"] + (config["limits"]["cv_metric_gap"]
                                    - lim["stated_beside"])]
    detail["family_margin"] = {
        "compared": _margin(_compared_means(detail)),
        "reference": _margin(detail["reference_means"])}
    detail["winner_replayed"] = replayed
    return out, detail
