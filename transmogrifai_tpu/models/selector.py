"""ModelSelector — automatic model + hyperparameter selection.

Reference: core/.../selector/ModelSelector.scala:71-195 (findBestEstimator :115-127,
fit :144-193), ModelSelectorSummary.scala, factories in
BinaryClassificationModelSelector.scala / MultiClassificationModelSelector.scala /
RegressionModelSelector.scala, DefaultSelectorParams.scala.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..data.dataset import Column
from ..evaluators.base import (
    BinaryClassificationEvaluator,
    Evaluator,
    Evaluators,
    MultiClassificationEvaluator,
    RegressionEvaluator,
)
from .base import PredictionEstimatorBase, PredictionModelBase
from .linear import LinearRegression
from .logistic import LogisticRegression
from .prediction import PredictionColumn
from .softmax import MultinomialLogisticRegression
from .tuning import (
    CrossValidator,
    DataBalancer,
    DataCutter,
    DataSplitter,
    ModelEvaluation,
    PrepSummary,
    TrainValidationSplit,
    ValidationResult,
)


@dataclass
class ModelSelectorSummary:
    """Validation results + best model + data prep + train/holdout metrics.

    Reference: ModelSelectorSummary.scala:1-309.
    """

    validation_type: str = "cv"
    validation_results: List[ModelEvaluation] = field(default_factory=list)
    best_model_name: str = ""
    best_model_uid: str = ""
    best_grid: Dict[str, Any] = field(default_factory=dict)
    metric_name: str = ""
    larger_is_better: bool = True
    data_prep: Optional[PrepSummary] = None
    train_evaluation: Dict[str, float] = field(default_factory=dict)
    holdout_evaluation: Dict[str, float] = field(default_factory=dict)
    #: families that never produced a finite CV metric (excluded from selection)
    failed_models: List[str] = field(default_factory=list)

    def to_dict(self) -> dict:
        return {
            "validationType": self.validation_type,
            "bestModelName": self.best_model_name,
            "bestModelUID": self.best_model_uid,
            "bestGrid": self.best_grid,
            "metricName": self.metric_name,
            "failedModels": self.failed_models,
            "dataPrep": vars(self.data_prep) if self.data_prep else None,
            "trainEvaluation": self.train_evaluation,
            "holdoutEvaluation": self.holdout_evaluation,
            "validationResults": [
                {
                    "modelName": ev.model_name,
                    "grid": ev.grid,
                    "metric": ev.metric_name,
                    "values": ev.metric_values,
                    "mean": ev.mean_metric,
                }
                for ev in self.validation_results
            ],
        }

    def pretty(self) -> str:
        from ..utils.pretty import Table

        sign = -1.0 if self.larger_is_better else 1.0
        rows = [
            (ev.model_name, _grid_str(ev.grid), f"{ev.mean_metric:.4f}")
            for ev in sorted(self.validation_results,
                             key=lambda e: sign * e.mean_metric
                             if np.isfinite(e.mean_metric) else np.inf)
        ]
        t = Table(("Model", "Grid", f"mean {self.metric_name}"), rows)
        lines = [
            f"Selected model: {self.best_model_name} {_grid_str(self.best_grid)}",
            t.render(),
            f"Train metrics: {self.train_evaluation}",
        ]
        if self.failed_models:
            lines.append(f"FAILED model families (no finite CV metric): "
                         f"{', '.join(self.failed_models)}")
        if self.holdout_evaluation:
            lines.append(f"Holdout metrics: {self.holdout_evaluation}")
        return "\n".join(lines)


def _grid_str(grid: Dict[str, Any]) -> str:
    return "{" + ", ".join(f"{k}={v}" for k, v in sorted(grid.items())) + "}"


class ModelSelector(PredictionEstimatorBase):
    """Estimator over (label, features): validates all (model, grid) candidates, refits best."""

    def __init__(
        self,
        models: Sequence[Tuple[PredictionEstimatorBase, List[Dict[str, Any]]]],
        validator: CrossValidator,
        splitter: Optional[DataSplitter] = None,
        train_evaluators: Sequence[Evaluator] = (),
        **kw,
    ):
        super().__init__(operation_name=kw.pop("operation_name", "modelSelector"), **kw)
        self.models = list(models)
        self.validator = validator
        self.splitter = splitter
        self.train_evaluators = list(train_evaluators)

    def fit_columns(self, cols, dataset):
        from ..obs.profile import maybe_profile
        from ..parallel.mesh import fit_mesh_record, fit_placements
        from ..perf.timers import (
            PhaseRecorder, keep_fit_profile, phase, record_phases)

        # every fit records its own phase profile (about a hundred spans —
        # cheap); ``last_fit_profile`` is how chipbench's entries report the
        # per-phase breakdown of the ONE real fit (no sweep is re-run in
        # isolation), and the process-wide ring (``recent_fit_profiles``)
        # keeps the last fits' whole spans for readers that come later.
        # record_phases nests: an ambient recorder (workflow fit) sees the
        # same spans.  TMOG_PROFILE captures the whole fit, from here to
        # after its last blocking fetch, spans and device programs together.
        # the fit owns its row-aligned inputs (labels, base weights, fold
        # ids): each is padded, stamped and placed once, and the sweep's
        # extras, the refit and the evaluators all get the same handle
        profile = PhaseRecorder()
        with maybe_profile("fit"), record_phases(profile), fit_placements(), \
                fit_mesh_record(len(cols[0])) as laid_out:
            fitted = self._fit_columns_profiled(cols, dataset, phase)
        profile.mesh = laid_out.record
        self.last_fit_profile = profile
        keep_fit_profile(profile)
        return fitted

    def _fit_columns_profiled(self, cols, dataset, phase):
        label, vec = cols
        # asarray, NOT astype: when the stored block is already float32 this
        # preserves the object identity, so the content-stamp memo hits and
        # the fit skips both a 512 MB host copy and a full re-hash (r5 tail
        # profile: ~0.9s of a 12s fit was astype copies + re-hashing)
        x = np.asarray(vec.data, np.float32)
        y = np.asarray(label.data, np.float32)

        with phase("prep"):
            base_w, prep_summary = (
                self.splitter.prepare(y) if self.splitter is not None
                else (np.ones_like(y, dtype=np.float32), None)
            )
            if "__sample_weight__" in dataset:
                base_w = base_w * dataset["__sample_weight__"].data.astype(
                    np.float32)

        # workflow-level CV pre-seeds the validation result (in-fold feature
        # engineering done by Workflow.train; reference ModelSelector receives
        # the BestEstimator from OpWorkflow.fitStages the same way)
        result: ValidationResult = getattr(self, "_preselected", None)
        if result is None:
            with phase("validate"):
                result = self.validator.validate(self.models, x, y, base_w)
        # EVERY candidate failed: there is no meaningful winner — selecting
        # among all-NaN metrics and silently refitting would ship an
        # arbitrary model (reference: robust-to-failing-models stops at
        # surviving models; zero survivors is a hard error).  Derived from
        # metric finiteness, not failed_models, so the workflow-CV path
        # (which builds ValidationResult itself) is covered too.
        if result.evaluations and not any(
                np.isfinite(v) for ev in result.evaluations
                for v in ev.metric_values):
            names = result.failed_models or sorted(
                {ev.model_name for ev in result.evaluations})
            raise RuntimeError(
                "model selection failed: no candidate produced a finite "
                f"CV metric (failed: {', '.join(names)})")
        best_eval = result.best
        best_est = next(e for e, _ in self.models if e.uid == best_eval.model_uid)
        final_est = best_est.copy().set_params(**best_eval.grid)
        with phase("refit"):
            best_model = final_est._fit_arrays(x, y, base_w)

        # Train/holdout evaluation: device fast path when the model can score
        # on the shared placement AND the evaluator can consume device
        # payloads — no (n,)-sized host round trip, just the metric scalars
        # (r5 tail profile: host predict + re-upload was ~1.3s of a 12s fit).
        # A model without a device scoring path returns None and takes the
        # host predict_column path; a device path that FAILS raises (no
        # catch-all here: it would reroute a broken device silently).
        payload = best_model.eval_payload_device(x)
        _pred_cache: List[Any] = []
        _w_dev: Dict[int, Any] = {}

        def pred_col():
            if not _pred_cache:
                _pred_cache.append(best_model.predict_column(Column.vector(x)))
            return _pred_cache[0]

        def evaluate(ev, w: Optional[np.ndarray]) -> Dict[str, float]:
            if payload is not None and hasattr(ev, "evaluate_device") \
                    and getattr(ev, "num_thresholds", 0) == 0:
                from ..parallel.mesh import place_fit_vector
                from .base import unit_weights

                # labels/weights over the PAYLOAD's row count (bucket+mesh
                # padding of the shared placement); padded rows get w=0.  The
                # labels are the handle the sweep and the refit used; the
                # weights are placed once for every evaluator (``w`` outlives
                # this fit's evaluations, so its id stands for it), and unit
                # weights are made on the device
                n_padded = int(payload[0].shape[0])
                if id(w) not in _w_dev:
                    _w_dev[id(w)] = unit_weights(len(y), n_padded) \
                        if w is None else \
                        place_fit_vector(w, n_padded)
                return ev.evaluate_device(
                    payload[0], payload[1],
                    place_fit_vector(y, n_padded), _w_dev[id(w)])
            return ev.evaluate_arrays(y.astype(np.float64), pred_col(), w=w)

        train_eval: Dict[str, float] = {}
        with phase("train_eval"):
            for ev in ([self.validator.evaluator] + self.train_evaluators):
                try:
                    train_eval.update(evaluate(ev, None))
                except Exception:
                    pass

        # holdout metrics on rows the splitter reserved out of training
        # (reference test-set evaluation)
        holdout_eval: Dict[str, float] = {}
        hmask = getattr(self.splitter, "holdout_mask", None)
        if hmask is not None and hmask.any():
            hw = hmask.astype(np.float64)
            with phase("holdout_eval"):
                for ev in ([self.validator.evaluator] + self.train_evaluators):
                    try:
                        holdout_eval.update(evaluate(ev, hw))
                    except Exception:
                        pass

        summary = ModelSelectorSummary(
            validation_type=type(self.validator).__name__,
            validation_results=result.evaluations,
            best_model_name=best_eval.model_name,
            best_model_uid=best_eval.model_uid,
            best_grid=best_eval.grid,
            metric_name=best_eval.metric_name,
            larger_is_better=self.validator.evaluator.larger_is_better,
            data_prep=prep_summary,
            train_evaluation=train_eval,
            holdout_evaluation=holdout_eval,
            failed_models=list(getattr(result, "failed_models", [])),
        )
        return SelectedModel(model=best_model, summary=summary,
                             feature_meta=vec.meta)


class SelectedModel(PredictionModelBase):
    """The winning fitted model + selection summary."""

    def __init__(self, model: PredictionModelBase, summary: ModelSelectorSummary,
                 feature_meta=None, **kw):
        super().__init__(**kw)
        self.model = model
        self.summary = summary
        #: VectorMetadata of the input feature vector (feeds ModelInsights/LOCO grouping)
        self.feature_meta = feature_meta

    def predict_column(self, vec: Column) -> PredictionColumn:
        return self.model.predict_column(vec)


# ---------------------------------------------------------------------------
# Factories with reference-default grids
# ---------------------------------------------------------------------------

class BinaryClassificationModelSelector:
    """Reference: BinaryClassificationModelSelector.scala:49-150 defaults.

    Default candidates mirror the reference set: LogisticRegression, RandomForest,
    GBT, LinearSVC (all native JAX implementations).
    """

    @staticmethod
    def default_models() -> List[Tuple[PredictionEstimatorBase, List[Dict[str, Any]]]]:
        from .svm import LinearSVC
        from .trees import GradientBoostedTreesClassifier, RandomForestClassifier

        lr_grid = [
            {"reg_param": r, "elastic_net": e}
            for r in (0.001, 0.01, 0.1)
            for e in (0.0, 0.5)
        ]
        return [
            (LogisticRegression(), lr_grid),
            (RandomForestClassifier(),
             [{"num_trees": 50, "max_depth": d} for d in (3, 6)]),
            (GradientBoostedTreesClassifier(),
             [{"num_rounds": 50, "max_depth": 3}]),
            (LinearSVC(), [{"reg_param": r} for r in (0.01, 0.1)]),
        ]

    @staticmethod
    def with_cross_validation(
        num_folds: int = 3,
        validation_metric: str = "auPR",
        seed: int = 42,
        splitter: Optional[DataSplitter] = None,
        models: Optional[Sequence] = None,
        stratify: bool = False,
    ) -> ModelSelector:
        ev = BinaryClassificationEvaluator(validation_metric)
        return ModelSelector(
            models=models or BinaryClassificationModelSelector.default_models(),
            validator=CrossValidator(ev, num_folds=num_folds, seed=seed, stratify=stratify),
            splitter=splitter if splitter is not None else DataBalancer(),
            train_evaluators=[Evaluators.binary_classification()],
        )

    @staticmethod
    def with_train_validation_split(
        train_ratio: float = 0.75,
        validation_metric: str = "auPR",
        seed: int = 42,
        splitter: Optional[DataSplitter] = None,
        models: Optional[Sequence] = None,
    ) -> ModelSelector:
        ev = BinaryClassificationEvaluator(validation_metric)
        return ModelSelector(
            models=models or BinaryClassificationModelSelector.default_models(),
            validator=TrainValidationSplit(ev, train_ratio=train_ratio, seed=seed),
            splitter=splitter if splitter is not None else DataBalancer(),
            train_evaluators=[Evaluators.binary_classification()],
        )


class MultiClassificationModelSelector:
    """Reference: MultiClassificationModelSelector.scala:49."""

    @staticmethod
    def default_models():
        """LR, RF, NB, DT — the reference's multiclass candidate set
        (MultiClassificationModelSelector.scala:49-76)."""
        from .naive_bayes import NaiveBayes
        from .trees import DecisionTreeClassifier, RandomForestClassifier

        grid = [{"reg_param": r} for r in (0.001, 0.01, 0.1)]
        return [
            (MultinomialLogisticRegression(), grid),
            (RandomForestClassifier(),
             [{"num_trees": 50, "max_depth": d} for d in (3, 6)]),
            (DecisionTreeClassifier(), [{"max_depth": d} for d in (3, 6)]),
            (NaiveBayes(), [{"smoothing": 1.0}]),
        ]

    @staticmethod
    def with_cross_validation(
        num_folds: int = 3,
        validation_metric: str = "error",
        seed: int = 42,
        splitter: Optional[DataSplitter] = None,
        models: Optional[Sequence] = None,
        stratify: bool = False,
    ) -> ModelSelector:
        ev = MultiClassificationEvaluator(validation_metric)
        return ModelSelector(
            models=models or MultiClassificationModelSelector.default_models(),
            validator=CrossValidator(ev, num_folds=num_folds, seed=seed, stratify=stratify),
            splitter=splitter if splitter is not None else DataCutter(),
            train_evaluators=[Evaluators.multi_classification()],
        )


class RegressionModelSelector:
    """Reference: RegressionModelSelector.scala:49."""

    @staticmethod
    def default_models():
        grid = [{"reg_param": r, "elastic_net": e}
                for r in (0.001, 0.01, 0.1) for e in (0.0, 0.5)]
        from .glm import GeneralizedLinearRegression
        from .trees import GradientBoostedTreesRegressor, RandomForestRegressor

        return [
            (LinearRegression(), grid),
            (RandomForestRegressor(),
             [{"num_trees": 50, "max_depth": d} for d in (3, 6)]),
            (GradientBoostedTreesRegressor(),
             [{"num_rounds": 50, "max_depth": 3}]),
            (GeneralizedLinearRegression(),
             [{"family": "gaussian", "reg_param": r} for r in (0.0, 0.01)]),
        ]

    @staticmethod
    def with_cross_validation(
        num_folds: int = 3,
        validation_metric: str = "rmse",
        seed: int = 42,
        splitter: Optional[DataSplitter] = None,
        models: Optional[Sequence] = None,
    ) -> ModelSelector:
        ev = RegressionEvaluator(validation_metric)
        return ModelSelector(
            models=models or RegressionModelSelector.default_models(),
            validator=CrossValidator(ev, num_folds=num_folds, seed=seed),
            splitter=splitter if splitter is not None else DataSplitter(),
            train_evaluators=[Evaluators.regression()],
        )
