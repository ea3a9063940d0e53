"""Entry ``selector_fit``: the timed call is ``ModelSelector.fit(dataset)``
on a ``Dataset`` built from the cell's block — the cross-validated sweep over
the configuration's families and grids, the choice, the winner's refit and
its train evaluation, all inside one call that ends on the host.

The harness drives an entry through four functions: ``setup`` (build + one
warm-up call), ``step`` (one whole timed call -> its record), ``collect``
(read what the timed calls produced, before the device is freed) and
``compare`` (program vs plain reference -> the numbers that decide
``correct``).  This file is the only place that touches the program, and only
through the ``transmogrifai_tpu`` package.
"""

from __future__ import annotations

import importlib
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List, Optional

import numpy as np

#: rows on which the refitted winner's scores are compared, drawn from the seed
SAMPLE_ROWS = 65536


def _resolve(path: str):
    """``package.module.Class.attr`` -> the object: the longest importable
    prefix is the module, the rest are attributes."""
    parts = path.split(".")
    for cut in range(len(parts) - 1, 0, -1):
        try:
            obj = importlib.import_module(".".join(parts[:cut]))
        except ModuleNotFoundError:
            continue
        for attr in parts[cut:]:
            obj = getattr(obj, attr)
        return obj
    raise ImportError(f"cannot resolve {path!r}")


def _counters() -> Dict[str, float]:
    from transmogrifai_tpu.perf import compile_snapshot, program_cache_stats
    from transmogrifai_tpu.workflow.plan import planner_fallbacks

    snap = compile_snapshot()
    return {"aot_fallbacks": program_cache_stats()["fallbacks"],
            "planner_fallbacks": planner_fallbacks(),
            "compiles": snap.backend_compiles,
            "cache_loads": snap.persistent_cache_hits}


def _moved(before: Dict[str, float]) -> Dict[str, float]:
    return {k: v - before[k] for k, v in _counters().items()}


def enable_cache() -> Optional[str]:
    from transmogrifai_tpu.perf import enable_persistent_cache

    return enable_persistent_cache()


class State:
    def __init__(self, config, selector, dataset, families):
        self.config = config
        self.selector = selector
        self.dataset = dataset
        self.families = families     # estimator class name -> family spec
        self.setup_counters: Dict[str, float] = {}


def setup(config: Dict[str, Any], table) -> State:
    from transmogrifai_tpu import Dataset, FeatureBuilder
    from transmogrifai_tpu.data.dataset import Column
    from transmogrifai_tpu.types import OPVector, RealNN

    if config.get("mesh") is not None:
        raise NotImplementedError(
            f"mesh {config['mesh']!r}: this entry runs single-chip "
            "configurations")
    before = _counters()
    models, families = [], {}
    for fam in config["families"]:
        cls = _resolve(fam["estimator"])
        models.append((cls().set_params(**fam.get("params", {})),
                       [dict(g) for g in fam["grid"]]))
        families[cls.__name__] = fam
    cv = config["cv"]
    selector = _resolve(config["selector"])(
        num_folds=int(cv["folds"]), validation_metric=cv["metric"],
        seed=int(cv["seed"]), stratify=bool(cv["stratify"]), models=models)
    y = np.ascontiguousarray(table.y, np.float64)
    dataset = Dataset({
        "label": Column(RealNN, y, np.ones(len(y), np.bool_)),
        "features": Column.vector(table.x)})
    label = FeatureBuilder.of("label", RealNN).extract_field().as_response()
    vec = FeatureBuilder.of("features", OPVector).extract_field() \
        .as_predictor()
    label.transform_with(selector, vec)
    state = State(config, selector, dataset, families)
    # compiles or loads this cell's programs only
    warm = step(state, may_compile=True)
    if warm["failed"]:
        raise RuntimeError(f"the warm-up fit failed: {warm['why_failed']}")
    state.setup_counters = _moved(before)
    return state


def step(state: State, may_compile: bool = False) -> Dict[str, Any]:
    """One whole ``fit``; returns its record (host objects only).  A timed
    fit that compiles counts as failed; the warm-up may."""
    before = _counters()
    t0 = time.perf_counter()
    fitted = state.selector.fit(state.dataset)
    seconds = time.perf_counter() - t0
    moved = _moved(before)
    summary = fitted.summary
    cv: Dict[str, List[List[float]]] = {}
    for ev in summary.validation_results:
        cv.setdefault(state.families[ev.model_name]["key"], []).append(
            [float(v) for v in ev.metric_values])
    attempted = sum(len(f["grid"]) for f in state.families.values()) \
        * int(state.config["cv"]["folds"])
    values = [v for rows in cv.values() for row in rows for v in row]
    why = []
    if len(values) != attempted or not np.isfinite(values).all():
        why.append("a fold-model without a finite CV metric")
    if summary.failed_models:
        why.append(f"failed_models {list(summary.failed_models)}")
    if state.config["cv"]["metric"] not in summary.train_evaluation:
        why.append("no train evaluation of the winner")
    for name in ("aot_fallbacks", "planner_fallbacks", "compiles"):
        if moved[name] and not (may_compile and name == "compiles"):
            why.append(f"{name} moved by {moved[name]}")
    failed = attempted - int(np.isfinite(values).sum()) if values \
        else attempted
    if why and not failed:
        failed = attempted          # the whole fit counts as failed
    return {
        "seconds": seconds, "attempted": attempted, "failed": failed,
        "why_failed": why, "counters": moved,
        "spans": state.selector.last_fit_profile.report(round_to=9),
        "cv": cv,
        "best": {"family": state.families[summary.best_model_name]["key"],
                 "grid": dict(summary.best_grid)},
        "train_eval": {k: float(v) for k, v in
                       summary.train_evaluation.items()
                       if isinstance(v, (int, float))},
        "model": fitted.model,
    }


def sample_rows(n: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng([int(seed), 0x5a3])
    return np.sort(rng.choice(n, size=min(SAMPLE_ROWS, n), replace=False))


def collect(state: State, records: List[Dict[str, Any]], table, seed: int
            ) -> None:
    """Score the sampled rows with every timed fit's winner (the program's
    own ``predict_column``), then drop the fitted objects."""
    from transmogrifai_tpu.data.dataset import Column

    rows = sample_rows(table.x.shape[0], seed)
    block = Column.vector(np.ascontiguousarray(table.x[rows]))
    for rec in records:
        col = rec.pop("model").predict_column(block)
        score = col.prob[:, 1] if col.prob is not None else col.raw[:, 1]
        rec["sample_scores"] = np.asarray(score, np.float64)


def release(state: State) -> None:
    """Free what the program holds on the device, so that the reference
    runs in the room the timed path had."""
    import jax

    state.selector = state.dataset = None
    for arr in jax.live_arrays():
        arr.delete()


# ---------------------------------------------------------------------------
# The comparison that decides ``correct``
# ---------------------------------------------------------------------------

def _family(config: Dict[str, Any], key: str):
    """The configuration's family ``key`` and its plain reference module."""
    fam = next(f for f in config["families"] if f["key"] == key)
    return fam, importlib.import_module(
        f"chipbench.reference.{fam['reference']}")


def compare(config: Dict[str, Any], table, records: List[Dict[str, Any]],
            seed: int, precision: str = "float32",
            control: bool = False):
    """Program (every timed fit's record) against the plain reference, at the
    timed size.  Returns ``({name: [value, limit]}, detail)`` for the numbers
    the configuration's ``limits`` name; the run is
    correct when every value is at or under its limit, and ``detail`` (the
    gap of every fold-model, the reference's seconds) goes into the notes.

    ``control=True`` puts the reference, computed in ``precision``, in the
    program's place and compares it with the float32 reference."""
    import jax.numpy as jnp

    from ..reference import common

    cv = config["cv"]
    n = table.x.shape[0]
    folds = int(cv["folds"])
    fold = common.fold_ids(n, folds, int(cv["seed"]))
    y = np.asarray(table.y, np.float64)
    pos = y.mean()
    if not 0.1 <= pos <= 0.9:
        raise ValueError(f"positive rate {pos}: the reference assumes the "
                         "configuration's balancer leaves the weights at 1")
    xd = jnp.asarray(table.x)
    yd = jnp.asarray(y, jnp.float32)
    train_w = jnp.asarray(np.stack(
        [(fold != f) for f in range(folds)]).astype(np.float32))
    ones = jnp.ones((1, n), jnp.float32)
    rows = sample_rows(n, seed)
    val_rows = [np.flatnonzero(fold == f) for f in range(folds)]

    y_val = [y[r] for r in val_rows]
    pool = ThreadPoolExecutor(max_workers=8)    # numpy's sort drops the GIL

    def reference(precision_: str):
        """{family key: CV metric (g, k)} of the reference's own sweep."""
        out = {}
        for fam in config["families"]:
            ref = _family(config, fam["key"])[1]
            scores = np.asarray(ref.fit_scores(
                xd, yd, train_w, fam["grid"], fam.get("params", {}),
                precision_))
            cells = [(g, f) for g in range(len(fam["grid"]))
                     for f in range(folds)]
            vals = pool.map(lambda gf: common.au_pr(
                scores[gf[0], gf[1]][val_rows[gf[1]]], y_val[gf[1]]), cells)
            out[fam["key"]] = np.array(list(vals)).reshape(-1, folds)
        return out

    def refit(key: str, grid: Dict[str, Any], precision_: str):
        fam, ref = _family(config, key)
        return np.asarray(ref.fit_scores(
            xd, yd, ones, [grid], fam.get("params", {}), precision_)[0, 0],
            np.float64)

    t0 = time.perf_counter()
    ref_cv = reference("float32")
    ref_mean = {(k, g): float(v[g].mean()) for k, v in ref_cv.items()
                for g in range(v.shape[0])}
    ref_best = max(ref_mean.values())
    if control:
        low_cv = reference(precision)
        key, g = max(ref_mean, key=lambda kg: float(low_cv[kg[0]][kg[1]].mean()))
        fam = _family(config, key)[0]
        low_scores = refit(key, fam["grid"][g], precision)
        records = [{"cv": {k: v.tolist() for k, v in low_cv.items()},
                    "best": {"family": key, "grid": fam["grid"][g]},
                    "sample_scores": low_scores[rows],
                    "train_eval": {cv["metric"]: common.au_pr(low_scores, y)}}]

    cv_gap = regret = score_gap = eval_gap = 0.0
    refits: Dict[str, np.ndarray] = {}
    detail: Dict[str, Any] = {"cv_gaps": {}}
    for rec in records:
        for key, want in ref_cv.items():
            got = np.asarray(rec["cv"][key], np.float64)
            cv_gap = max(cv_gap, float(np.abs(got - want).max()))
            detail["cv_gaps"][key] = (got - want).tolist()
        best = rec["best"]
        fam = _family(config, best["family"])[0]
        g = next(i for i, grid in enumerate(fam["grid"])
                 if all(float(grid[k]) == float(best["grid"].get(k, np.nan))
                        for k in grid))
        regret = max(regret, ref_best - ref_mean[(best["family"], g)])
        tag = f"{best['family']}/{g}"
        if tag not in refits:
            refits[tag] = refit(best["family"], fam["grid"][g], "float32")
        want = refits[tag]
        scale = max(float(want[rows].std()), 1e-12)
        score_gap = max(score_gap, float(
            np.abs(rec["sample_scores"] - want[rows]).max()) / scale)
        eval_gap = max(eval_gap, abs(
            rec["train_eval"][cv["metric"]] - common.au_pr(want, y)))
    pool.shutdown()
    detail["reference_s"] = time.perf_counter() - t0
    detail["reference_best"] = max(ref_mean, key=ref_mean.get)
    detail["reference_means"] = {f"{k}/{g}": v for (k, g), v in ref_mean.items()}
    values = {"cv_metric_gap": cv_gap, "choice_regret": regret,
              "refit_score_gap": score_gap, "train_eval_gap": eval_gap}
    # a configuration compares the numbers it gives a limit for (one whose
    # candidates all tie has no use for the regret: PERF.md, section 2)
    return {k: [values[k], lim] for k, lim in config["limits"].items()}, detail
