"""Layer-wise DAG fitting/transform — the scheduler core.

Reference: core/.../utils/stages/FitStagesUtil.scala:51-372 (fitAndTransformDAG :213-240,
fitAndTransformLayer :254, applyOpTransformations :96-119).

Estimators fit per layer, then the layer's transforms apply.  Columnar transforms are
already whole-column vectorized; device stages produce jnp-ready blocks (fusing a layer's
numeric transforms into one jitted program is a planned optimization on this seam).
"""

from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

from ..data.dataset import Dataset
from ..features.feature import Feature
from ..stages.base import Estimator, PipelineStage, Transformer
from ..utils.listener import stage_timer
from .dag import compute_dag


def fit_dag(
    dataset: Dataset,
    result_features: Sequence[Feature],
    fitted: Dict[str, Transformer] | None = None,
    on_fit=None,
    hbm_budget: float | None = None,
    host_budget: float | None = None,
) -> Tuple[Dataset, Dict[str, Transformer]]:
    """Fit every estimator and apply every transformer, layer by layer.

    Returns (transformed dataset, {stage uid -> fitted transformer}).  Already-fitted
    stages (uid present in ``fitted``) are reused, enabling warm-start stacking
    (OpWorkflow.withModelStages :457-461).  ``on_fit(model)`` fires after each
    estimator fit (checkpoint hook).  ``hbm_budget`` arms the TM601 gate on
    every fused transform plan (see ``Workflow.train``).
    """
    fitted = dict(fitted or {})
    # one flattened topo-ordered pass (not per layer): the fused transform
    # planner batches maximal runs of fitted transformers between estimator
    # fits, and those runs may span DAG layers
    stages = [s for layer in compute_dag(result_features) for s in layer]
    dataset = fit_stage_list(dataset, stages, fitted, on_fit=on_fit,
                             hbm_budget=hbm_budget, host_budget=host_budget)
    return dataset, fitted


def _is_chunked(dataset) -> bool:
    from ..data.chunked import ChunkedDataset

    return isinstance(dataset, ChunkedDataset)


def transform_dag(
    dataset: Dataset,
    result_features: Sequence[Feature],
    fitted: Dict[str, Transformer],
    fused: bool | None = None,
) -> Dataset:
    """Scoring path: apply fitted transformers only (no fitting allowed).

    Default execution goes through the fused transform planner
    (workflow/plan.py): the maximal device-capable prefix runs as ONE jitted,
    row-sharded XLA program, the remainder per stage.  ``fused=False`` (or
    ``TMOG_FUSED_TRANSFORM=0``, or an active stage-metrics listener) forces
    the per-stage interpreted path; a planner failure falls back to it too.
    """
    if _is_chunked(dataset):
        from .ooc import transform_dag_chunked

        return transform_dag_chunked(dataset, result_features, fitted,
                                     fused=fused)
    runners = []
    for layer in compute_dag(result_features):
        for stage in layer:
            runner = _resolve(stage, fitted)
            if runner is None:
                raise ValueError(
                    f"Stage {stage.uid} is an unfitted estimator; cannot score. "
                    "Train the workflow first."
                )
            runners.append(runner)
    if fused is not False:
        from .plan import fused_transform

        out = fused_transform(dataset, runners)
        if out is not None:
            return out
    for runner in runners:
        with stage_timer(runner, "transform", dataset) as finish:
            dataset = runner.transform(dataset)
            finish(dataset)
    return dataset


def _resolve(stage: PipelineStage, fitted: Dict[str, Transformer]) -> Transformer | None:
    if stage.uid in fitted:
        return fitted[stage.uid]
    if isinstance(stage, Estimator):
        return None
    assert isinstance(stage, Transformer)
    return stage


def fit_stage_list(dataset: Dataset, stages, fitted: Dict[str, Transformer],
                   on_fit=None, fused: bool | None = None,
                   hbm_budget: float | None = None,
                   host_budget: float | None = None) -> Dataset:
    """Fit/transform an explicit stage list (topological order) — the single
    fit/transform loop shared by fit_dag and the workflow-CV passes.

    Post-fit transforms batch through the fused transform planner: maximal
    runs of already-fitted runners between estimator fits execute as one
    jitted program each (an estimator's fit needs its inputs materialized, so
    fusion flushes at every fit boundary).  ``fused=False`` /
    ``TMOG_FUSED_TRANSFORM=0`` / an active listener keep the per-stage path.

    Each stage's fit/transform also lands as a perf phase span (no-op unless
    a ``perf.timers.record_phases`` recorder is active — callers profiling
    a train get per-stage wall time from the one real fit).

    A :class:`~..data.chunked.ChunkedDataset` routes to the out-of-core
    twin (workflow/ooc.py): fused epochs per chunk with spilled outputs,
    estimator fits over materialized input columns only."""
    from ..perf.timers import phase

    if _is_chunked(dataset):
        from .ooc import fit_stage_list_chunked

        return fit_stage_list_chunked(dataset, stages, fitted, on_fit=on_fit,
                                      fused=fused, hbm_budget=hbm_budget,
                                      host_budget=host_budget)

    def _name(s) -> str:
        return getattr(s, "operation_name", None) or type(s).__name__

    def _flush(ds: Dataset, runners) -> Dataset:
        if not runners:
            return ds
        if fused is not False:
            from .plan import fused_transform

            out = fused_transform(ds, runners, hbm_budget=hbm_budget)
            if out is not None:
                return out
        for runner in runners:
            with phase(f"transform.{_name(runner)}"), \
                    stage_timer(runner, "transform", ds) as finish:
                ds = runner.transform(ds)
                finish(ds)
        return ds

    from ..serve.faults import fault_point
    from .resilience import retry_call

    pending: list = []
    for stage in stages:
        runner = _resolve(stage, fitted)
        if runner is None:
            dataset = _flush(dataset, pending)
            pending = []

            def _fit_once(_stage=stage, _ds=dataset):
                fault_point("stage_fit", stage=_stage.uid)
                return _stage.fit(_ds)

            with phase(f"fit.{_name(stage)}"), \
                    stage_timer(stage, "fit", dataset) as finish:
                # retried with bounded backoff under resilient_training
                # (a transient device fault mid-fit is retryable; the fit
                # is pure given its inputs); a plain call otherwise
                model = retry_call(_fit_once, "stage_fit", stage=stage.uid)
                finish(None)
            fitted[stage.uid] = model
            runner = model
            if on_fit is not None:
                on_fit(model)
        pending.append(runner)
    return _flush(dataset, pending)


def workflow_cv_validate(ds_before: Dataset, during, selector,
                         hbm_budget: float | None = None,
                         host_budget: float | None = None) -> "object":
    """In-fold feature engineering CV (reference OpWorkflow.fitStages withWorkflowCV,
    FitStagesUtil.scala:305-358 + OpWorkflow.scala:403-438).

    For every fold: re-fit copies of the label-dependent ``during`` stages on the
    fold's training rows only, transform ALL rows with those fold-fitted stages,
    then sweep every (estimator, grid) on the fold.  Metrics aggregate fold-robustly
    exactly like the selector-level CV.  Returns a ValidationResult to pre-seed the
    selector.
    """
    import numpy as np

    from ..models.tuning import ModelEvaluation, ValidationResult

    label_f, vec_f = selector.inputs[0], selector.inputs[1]
    if _is_chunked(ds_before):
        # the fold loop's whole-table working set is the during-stage inputs
        # plus the selector's label/vector — materialize exactly that slice
        # (chunk-local assembly) and run the in-memory fold path over it;
        # the big raw/intermediate columns stay spilled.  An armed
        # host_budget gates the materialization (TM607) BEFORE it assembles,
        # same contract as the estimator-fit gate in workflow/ooc.py.
        from .ooc import _gate_fit_residency

        need = {label_f.name, vec_f.name, "__sample_weight__"}
        for s in during:
            need.update(fi.name for fi in s.inputs)
        names = [n for n in ds_before.names if n in need]
        _gate_fit_residency(ds_before, selector, names, host_budget)
        ds_before = ds_before.materialize(names)
    y = ds_before[label_f.name].data.astype(np.float32) \
        if label_f.name in ds_before else None
    if y is None:
        raise ValueError("workflow CV: label column not materialized before selector")
    # same base weights as selector-level CV (selector.py fit_columns):
    # splitter rebalancing/cutting + dataset sample weights
    base_w, _ = (selector.splitter.prepare(y) if selector.splitter is not None
                 else (np.ones_like(y, dtype=np.float32), None))
    if "__sample_weight__" in ds_before:
        base_w = base_w * ds_before["__sample_weight__"].data.astype(np.float32)
    validator = selector.validator
    train_w, val_w = validator.fold_weights(y, base_w)
    k = train_w.shape[0]
    metric_fn = validator.evaluator.metric_fn()

    # the fold fits only read the during-stages' inputs (plus label/weights):
    # restrict the per-fold row take to those columns instead of copying the
    # whole table k times
    fit_cols = {label_f.name}
    for s in during:
        fit_cols.update(fi.name for fi in s.inputs)
    if "__sample_weight__" in ds_before:
        fit_cols.add("__sample_weight__")
    ds_fit_view = ds_before.select([c for c in ds_before.names
                                    if c in fit_cols])

    # fit during-stage copies per fold on that fold's training rows only
    fold_runner_maps: List[Dict[str, Transformer]] = []
    fold_copies: List[list] = []
    for f in range(k):
        train_rows = np.flatnonzero(train_w[f] > 0)
        ds_fold_train = ds_fit_view.take(train_rows)
        fold_fitted: Dict[str, Transformer] = {}
        copies = [s.copy() for s in during]
        fit_stage_list(ds_fold_train, copies, fold_fitted,
                       hbm_budget=hbm_budget)
        # plain transformers in the cut have no fitted entry — the copy runs
        fold_copies.append(copies)
        fold_runner_maps.append(
            {c.uid: fold_fitted.get(c.uid, c) for c in copies})

    # apply fold-fitted stages to ALL rows (train + validation) through the
    # fused planner — one vmapped program over the fold axis when stage
    # states stack, else one fused plan per fold; host loop as fallback
    from .plan import fused_fold_transforms

    fold_datasets = fused_fold_transforms(ds_before, during, fold_runner_maps,
                                          hbm_budget=hbm_budget)
    if fold_datasets is None:
        fold_datasets = []
        for f in range(k):
            runners = fold_runner_maps[f]
            ds_fold_full = ds_before
            for s in during:
                ds_fold_full = runners[s.uid].transform(ds_fold_full)
            fold_datasets.append(ds_fold_full)

    # metric matrix per (model, grid) across folds.  Each (family, fold) is
    # one durable journal unit under resilient_training: a committed block
    # replays its scores without dispatching, failures retry through the
    # backoff/degradation ladders, and non-retryable errors fail fast with
    # every completed block intact (workflow/resilience.py).
    from ..parallel.mesh import current_mesh, mesh_token
    from ..serve.faults import fault_point
    from . import resilience

    res = resilience.active()
    journal = res.journal if res is not None else None
    fold_spec = (validator.num_folds, validator.seed, validator.stratify)
    metric_name = validator.evaluator.default_metric
    per_key: Dict[tuple, list] = {}
    for f in range(k):
        x_f = fold_datasets[f][vec_f.name].data.astype(np.float32)
        fold_digest = resilience.data_digest(
            x_f, y, train_w[f:f + 1], val_w[f:f + 1]) \
            if journal is not None else None
        for est, grids in selector.models:
            grids = grids or [{}]
            name = type(est).__name__
            key = None
            scores = None
            if journal is not None:
                key = resilience.sweep_block_key(
                    name, grids, fold_spec, metric_name, fold_digest,
                    mesh_token(), block=f"fold{f}")
                scores = journal.load(key)
                if scores is not None:
                    from ..obs import flight as obs_flight

                    obs_flight.record_event("sweep_block_resume",
                                            family=name, fold=f, key=key)
            if scores is None:
                def _attempt(mesh_override, row_cap, attempt_i, _est=est,
                             _grids=grids, _name=name, _f=f, _x=x_f):
                    from contextlib import nullcontext

                    from ..parallel.mesh import use_mesh

                    cm = use_mesh(mesh_override) \
                        if mesh_override is not None else nullcontext()
                    with cm:
                        xa, ya, twa, vwa = resilience.capped_views(
                            row_cap, _x, y, train_w[_f:_f + 1],
                            val_w[_f:_f + 1])
                        fault_point(
                            "sweep_dispatch", family=_name, fold=_f,
                            rows=len(ya),
                            dp=resilience.dp_size(
                                mesh_override if mesh_override is not None
                                else current_mesh()),
                            attempt=attempt_i)
                        return np.asarray(_est.cv_sweep(
                            xa, ya, twa, vwa, _grids, metric_fn))

                n_deg = len(res.degradations) if res is not None else 0
                try:
                    scores = resilience.run_sweep_block(
                        _attempt, family=name, rows=len(y), res=res)
                except Exception as e:
                    if res is not None:
                        # run_sweep_block already classified: retryables
                        # exhausted their ladder, non-retryables fail fast
                        # — either way the journal keeps completed blocks
                        raise
                    import logging

                    logging.getLogger(__name__).warning(
                        "model %s failed in workflow CV fold %d (%s)",
                        name, f, e)
                    scores = np.full((len(grids), 1), np.nan)
                else:
                    if res is not None \
                            and len(res.degradations) > n_deg:
                        # degraded (shrunk-mesh / capped-rows) scores must
                        # not journal under the full-fidelity key
                        key = None
                    if journal is not None and key is not None:
                        journal.commit(key, scores, family=name)
            for gi, grid in enumerate(grids):
                per_key.setdefault(
                    (est.uid, type(est).__name__, gi, tuple(sorted(grid.items()))),
                    []).append(float(scores[gi, 0]))

    evaluations = []
    for (uid, name, gi, grid_items), vals in per_key.items():
        evaluations.append(ModelEvaluation(
            model_name=name, model_uid=uid, grid=dict(grid_items),
            metric_name=validator.evaluator.default_metric, metric_values=vals))
    best = validator._best_index(evaluations)
    return ValidationResult(evaluations, best)
