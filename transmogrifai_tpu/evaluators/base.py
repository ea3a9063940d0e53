"""Evaluator classes — full metric suites per problem type.

Reference: core/.../evaluators/ (OpEvaluatorBase.scala, OpBinaryClassificationEvaluator.scala,
OpMultiClassificationEvaluator.scala, OpRegressionEvaluator.scala, Evaluators.scala factory).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Optional

import jax.numpy as jnp
import numpy as np

from ..data.dataset import Dataset
from ..models.prediction import PredictionColumn
from . import metrics as M


class Evaluator:
    """Base evaluator: named default metric + full metric dict."""

    default_metric: str = ""
    problem: str = ""

    @property
    def larger_is_better(self) -> bool:
        return self.default_metric in M.LARGER_IS_BETTER

    def metric_fn(self):
        """Device-side (scores, y, w) -> scalar used by CV sweeps."""
        raise NotImplementedError

    def evaluate_arrays(self, y: np.ndarray, pred: PredictionColumn,
                        w: Optional[np.ndarray] = None) -> Dict[str, float]:
        raise NotImplementedError

    def evaluate(self, ds: Dataset, label_name: str, pred_name: str,
                 w: Optional[np.ndarray] = None) -> Dict[str, float]:
        y = ds[label_name].data.astype(np.float64)
        pred = ds[pred_name]
        if not isinstance(pred, PredictionColumn):
            raise TypeError(f"column {pred_name!r} is not a prediction column")
        return self.evaluate_arrays(y, pred, w)


class BinaryClassificationEvaluator(Evaluator):
    """AuROC, AuPR, precision/recall/F1/error @0.5, confusion counts.

    Reference: OpBinaryClassificationEvaluator.scala:1-202.
    """

    problem = "binary"

    def __init__(self, metric: str = "auPR", num_thresholds: int = 0):
        self.default_metric = metric
        #: >0 adds thresholds/precision/recall/fpr curve arrays to the output
        #: (reference emits them always; opt-in here to keep metric dicts compact)
        self.num_thresholds = num_thresholds

    def metric_fn(self):
        return M.METRICS_BINARY[self.default_metric]

    def evaluate_arrays(self, y, pred, w=None):
        w = np.ones_like(y) if w is None else w
        # zero-weight pad to a power-of-two bucket: the sort-based AUC kernels
        # then compile once per bucket instead of once per dataset size.
        # Transfers go out as float32 through the content cache — half the
        # host-to-device bytes of four float64 copies of a 1M-row eval
        # (~32 MB), and every summary metric is reported at float32-grade
        # precision anyway (sort order of f32-rounded scores decides AUC
        # ties differently at most at the 1e-7 level).
        from ..parallel.mesh import DATA_AXIS, pad_rows_to_bucket, \
            place, place_cached

        score_p, pred_p, y_p, w_p = pad_rows_to_bucket(
            len(y), np.asarray(pred.score, np.float32),
            np.asarray(pred.pred, np.float32), np.asarray(y, np.float32),
            np.asarray(w, np.float32))
        # scores/predictions are single-use per model: plain placement (a
        # cache entry would only churn the LRU that protects fold weights)
        s = place(score_p, (DATA_AXIS,))
        # threshold metrics use the model's OWN predictions (reference evaluates the
        # prediction column) — scores may be margins (LinearSVC), not probabilities
        p = place(pred_p, (DATA_AXIS,))
        # labels/weights recur across evaluators and selector phases: cached
        yj, wj = place_cached(y_p, (DATA_AXIS,)), place_cached(w_p, (DATA_AXIS,))
        # one jitted program + one host fetch for all ten point metrics
        vals = np.asarray(M.binary_summary(s, p, yj, wj))
        out = dict(zip(("auROC", "auPR", "precision", "recall", "f1", "error",
                        "tp", "fp", "tn", "fn"), (float(v) for v in vals)))
        return self._maybe_thresholds(out, pred, y, w)

    def evaluate_device(self, score_dev, pred_dev, y_dev, w_dev
                        ) -> Dict[str, float]:
        """All ten point metrics from device-resident payloads — one program,
        one scalar fetch, no (n,)-sized host round trip.  Inputs are aligned
        1-D device arrays over the padded row block (padded rows carry zero
        weight).  Threshold curves need host arrays, so ``num_thresholds``
        falls back to ``evaluate_arrays``."""
        from ..perf.timers import activity

        with activity("launch", label="BinaryClassificationEvaluator/summary"):
            vals = M.binary_summary(score_dev, pred_dev, y_dev, w_dev)
        with activity("device_wait",
                      label="BinaryClassificationEvaluator/summary"):
            vals = np.asarray(vals)
        return dict(zip(("auROC", "auPR", "precision", "recall", "f1", "error",
                         "tp", "fp", "tn", "fn"), (float(v) for v in vals)))

    def _maybe_thresholds(self, out, pred, y, w):
        w = np.ones_like(y) if w is None else w
        if self.num_thresholds > 0:
            # rank-position sampling is not padding-safe: use the true rows
            th, pr, rc, fpr = M.threshold_curves(
                jnp.asarray(pred.score), jnp.asarray(y), jnp.asarray(w),
                self.num_thresholds)
            out["thresholds"] = np.asarray(th).tolist()
            out["precisionByThreshold"] = np.asarray(pr).tolist()
            out["recallByThreshold"] = np.asarray(rc).tolist()
            out["falsePositiveRateByThreshold"] = np.asarray(fpr).tolist()
        return out


class MultiClassificationEvaluator(Evaluator):
    """Weighted precision/recall/F1/error + confusion matrix + top-N accuracy.

    Reference: OpMultiClassificationEvaluator.scala:1-307.
    """

    problem = "multiclass"

    def __init__(self, metric: str = "error", top_ns=(1, 3), thresholds=()):
        self.default_metric = metric
        self.top_ns = top_ns
        #: when non-empty, adds reference-style ThresholdMetrics: per (topN, threshold)
        #: correct / incorrect / no-prediction counts (max prob below threshold)
        self.thresholds = tuple(thresholds)

    def metric_fn(self):
        if self.default_metric == "error":
            return M.multiclass_error
        raise ValueError(f"no device metric {self.default_metric!r} for multiclass")

    def evaluate_arrays(self, y, pred, w=None):
        w = np.ones_like(y) if w is None else w
        yi = y.astype(np.int64)
        prob = pred.prob
        n_classes = prob.shape[1]
        phat = np.argmax(prob, axis=1)
        conf = np.zeros((n_classes, n_classes))
        np.add.at(conf, (yi, phat), w)
        sw = w.sum()
        per_class_tp = np.diag(conf)
        per_class_pred = conf.sum(axis=0)
        per_class_true = conf.sum(axis=1)
        with np.errstate(divide="ignore", invalid="ignore"):
            prec_c = np.where(per_class_pred > 0, per_class_tp / per_class_pred, 0.0)
            rec_c = np.where(per_class_true > 0, per_class_tp / per_class_true, 0.0)
            f1_c = np.where(prec_c + rec_c > 0, 2 * prec_c * rec_c / (prec_c + rec_c), 0.0)
        class_w = per_class_true / sw
        error = 1.0 - per_class_tp.sum() / sw
        out = {
            "precision": float((prec_c * class_w).sum()),
            "recall": float((rec_c * class_w).sum()),
            "f1": float((f1_c * class_w).sum()),
            "error": float(error),
            "confusion": conf.tolist(),
        }
        order = np.argsort(-prob, axis=1)
        for topn in self.top_ns:
            hit = (order[:, :topn] == yi[:, None]).any(axis=1)
            out[f"top{topn}_accuracy"] = float((w * hit).sum() / sw)
        if self.thresholds:
            max_prob = prob.max(axis=1)
            tm = {"topNs": list(self.top_ns), "thresholds": list(self.thresholds),
                  "correctCounts": {}, "incorrectCounts": {},
                  "noPredictionCounts": {}}
            for topn in self.top_ns:
                hit = (order[:, :topn] == yi[:, None]).any(axis=1)
                cc, ic, npred = [], [], []
                for t in self.thresholds:
                    predicted = max_prob >= t
                    cc.append(float((w * (predicted & hit)).sum()))
                    ic.append(float((w * (predicted & ~hit)).sum()))
                    npred.append(float((w * ~predicted).sum()))
                tm["correctCounts"][topn] = cc
                tm["incorrectCounts"][topn] = ic
                tm["noPredictionCounts"][topn] = npred
            out["thresholdMetrics"] = tm
        return out


class RegressionEvaluator(Evaluator):
    """RMSE, MSE, MAE, R2, SMAPE.  Reference: OpRegressionEvaluator.scala."""

    problem = "regression"

    def __init__(self, metric: str = "rmse"):
        self.default_metric = metric

    def metric_fn(self):
        return M.METRICS_REGRESSION[self.default_metric]

    def evaluate_arrays(self, y, pred, w=None):
        w = np.ones_like(y) if w is None else w
        from ..parallel.mesh import pad_rows_to_bucket

        pred_p, y_p, w_p = pad_rows_to_bucket(len(y), pred.pred, y, w)
        p = jnp.asarray(pred_p)
        yj, wj = jnp.asarray(y_p), jnp.asarray(w_p)
        vals = np.asarray(M.regression_summary(p, yj, wj))
        return dict(zip(("rmse", "mse", "mae", "r2", "smape"),
                        (float(v) for v in vals)))


class ForecastEvaluator(RegressionEvaluator):
    """SMAPE/MASE + seasonal error.  Reference: OpForecastEvaluator.scala."""

    problem = "forecast"

    def __init__(self, metric: str = "smape", seasonal_period: int = 1):
        super().__init__(metric)
        self.seasonal_period = seasonal_period

    def evaluate_arrays(self, y, pred, w=None):
        out = super().evaluate_arrays(y, pred, w)
        m = self.seasonal_period
        if len(y) > m:
            naive_mae = np.abs(y[m:] - y[:-m]).mean()
            pred_mae = np.abs(pred.pred - y).mean()
            out["mase"] = float(pred_mae / max(naive_mae, 1e-12))
            out["seasonalError"] = float(naive_mae)
        return out


@dataclass
class BinScoreMetrics:
    bin_centers: list = field(default_factory=list)
    bin_counts: list = field(default_factory=list)
    bin_avg_scores: list = field(default_factory=list)
    bin_avg_labels: list = field(default_factory=list)
    brier_score: float = 0.0


class BinScoreEvaluator(Evaluator):
    """Calibration-by-bin + Brier score.  Reference: OpBinScoreEvaluator.scala."""

    problem = "binary"
    default_metric = "brierScore"

    def __init__(self, num_bins: int = 100):
        self.num_bins = num_bins

    def evaluate_arrays(self, y, pred, w=None):
        if pred.prob is None:
            raise ValueError(
                "BinScoreEvaluator needs probability outputs; this model emits only "
                "raw margins (e.g. LinearSVC) — calibrate it first")
        w = np.ones_like(y) if w is None else w
        s = pred.score
        bins = np.clip((s * self.num_bins).astype(int), 0, self.num_bins - 1)
        counts = np.bincount(bins, weights=w, minlength=self.num_bins)
        sum_scores = np.bincount(bins, weights=w * s, minlength=self.num_bins)
        sum_labels = np.bincount(bins, weights=w * y, minlength=self.num_bins)
        nz = counts > 0
        brier = float((w * (s - y) ** 2).sum() / w.sum())
        return {
            "brierScore": brier,
            "binCenters": ((np.arange(self.num_bins) + 0.5) / self.num_bins)[nz].tolist(),
            "binCounts": counts[nz].tolist(),
            "binAvgScores": np.divide(sum_scores, counts, out=np.zeros_like(counts),
                                      where=nz)[nz].tolist(),
            "binAvgLabels": np.divide(sum_labels, counts, out=np.zeros_like(counts),
                                      where=nz)[nz].tolist(),
        }


class Evaluators:
    """Factory mirroring reference ``Evaluators`` object."""

    @staticmethod
    def binary_classification(metric: str = "auPR") -> BinaryClassificationEvaluator:
        return BinaryClassificationEvaluator(metric)

    @staticmethod
    def multi_classification(metric: str = "error") -> MultiClassificationEvaluator:
        return MultiClassificationEvaluator(metric)

    @staticmethod
    def regression(metric: str = "rmse") -> RegressionEvaluator:
        return RegressionEvaluator(metric)

    @staticmethod
    def forecast(metric: str = "smape", seasonal_period: int = 1) -> ForecastEvaluator:
        return ForecastEvaluator(metric, seasonal_period)

    @staticmethod
    def bin_score(num_bins: int = 100) -> BinScoreEvaluator:
        return BinScoreEvaluator(num_bins)
