"""Device metric kernels — jit/vmap-able weighted classification & regression metrics.

Reference: Spark BinaryClassificationMetrics semantics (AuROC/AuPR via trapezoid rule,
PR curve prepended with (recall=0, precision=1)) as used by
core/.../evaluators/OpBinaryClassificationEvaluator.scala.

All functions take (scores, labels, weights) device arrays with static shapes so the CV
sweep can vmap them over (grid x fold) without recompilation; weight=0 rows are inert.
Tie handling is per-row rather than per-distinct-threshold — identical for continuous
scores, within noise for discrete ones.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

EPS = 1e-12


def _sorted_cums(scores: jnp.ndarray, y: jnp.ndarray, w: jnp.ndarray):
    # one multi-operand lax.sort carries the weighted labels through the
    # sorting network — argsort + two (n,) gathers serialized badly on TPU
    # (the gathers, not the sort, dominated; r5: the CV sweep evaluates 33
    # fold-models x 1M rows through this kernel).  is_stable keeps tie
    # ordering identical to the former stable argsort.
    _, wy, wn = jax.lax.sort((-scores, w * y, w * (1.0 - y)),
                             num_keys=1, is_stable=True)
    tp = jnp.cumsum(wy)
    fp = jnp.cumsum(wn)
    return tp, fp


def au_roc(scores: jnp.ndarray, y: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """Weighted area under the ROC curve (trapezoid)."""
    tp, fp = _sorted_cums(scores, y, w)
    pos = tp[-1]
    neg = fp[-1]
    tpr = tp / jnp.maximum(pos, EPS)
    fpr = fp / jnp.maximum(neg, EPS)
    tpr = jnp.concatenate([jnp.zeros(1), tpr])
    fpr = jnp.concatenate([jnp.zeros(1), fpr])
    return jnp.sum(0.5 * (tpr[1:] + tpr[:-1]) * (fpr[1:] - fpr[:-1]))


def au_pr(scores: jnp.ndarray, y: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """Weighted area under the precision-recall curve (trapezoid, (0,1) start point)."""
    tp, fp = _sorted_cums(scores, y, w)
    pos = tp[-1]
    recall = tp / jnp.maximum(pos, EPS)
    precision = tp / jnp.maximum(tp + fp, EPS)
    recall = jnp.concatenate([jnp.zeros(1), recall])
    precision = jnp.concatenate([jnp.ones(1), precision])
    return jnp.sum(0.5 * (precision[1:] + precision[:-1]) * (recall[1:] - recall[:-1]))


def binary_counts(scores, y, w, threshold: float = 0.5):
    pred = (scores >= threshold).astype(scores.dtype)
    tp = jnp.sum(w * pred * y)
    fp = jnp.sum(w * pred * (1 - y))
    tn = jnp.sum(w * (1 - pred) * (1 - y))
    fn = jnp.sum(w * (1 - pred) * y)
    return tp, fp, tn, fn


def precision_recall_f1(scores, y, w, threshold: float = 0.5):
    tp, fp, tn, fn = binary_counts(scores, y, w, threshold)
    precision = tp / jnp.maximum(tp + fp, EPS)
    recall = tp / jnp.maximum(tp + fn, EPS)
    f1 = 2 * precision * recall / jnp.maximum(precision + recall, EPS)
    error = (fp + fn) / jnp.maximum(tp + fp + tn + fn, EPS)
    return precision, recall, f1, error


def log_loss(scores, y, w):
    p = jnp.clip(scores, EPS, 1 - EPS)
    ll = -(y * jnp.log(p) + (1 - y) * jnp.log(1 - p))
    return jnp.sum(w * ll) / jnp.maximum(jnp.sum(w), EPS)


def threshold_curves(scores, y, w, num_thresholds: int = 100):
    """(thresholds, precision, recall, fpr) sampled along the score range.

    Reference: OpBinaryClassificationEvaluator.scala:109-118 (thresholds,
    precisionByThreshold, recallByThreshold, falsePositiveRateByThreshold).  One device
    program: sort once, sample ``num_thresholds`` evenly-spaced rank positions.
    """
    order = jnp.argsort(-scores)
    ss = scores[order]
    ys = y[order]
    ws = w[order]
    tp = jnp.cumsum(ws * ys)
    fp = jnp.cumsum(ws * (1.0 - ys))
    pos = jnp.maximum(tp[-1], EPS)
    neg = jnp.maximum(fp[-1], EPS)
    n = scores.shape[0]
    idx = jnp.clip((jnp.arange(num_thresholds) * n) // num_thresholds, 0, n - 1)
    th = ss[idx]
    # collapse tied scores: counts at a threshold are those of the LAST row with that
    # score, else the reported operating points are unrealizable by any threshold
    last = jnp.searchsorted(-ss, -th, side="right") - 1
    return (th, tp[last] / jnp.maximum(tp[last] + fp[last], EPS),
            tp[last] / pos, fp[last] / neg)


# --- regression --------------------------------------------------------------

def mse(pred, y, w):
    return jnp.sum(w * (pred - y) ** 2) / jnp.maximum(jnp.sum(w), EPS)


def rmse(pred, y, w):
    return jnp.sqrt(mse(pred, y, w))


def mae(pred, y, w):
    return jnp.sum(w * jnp.abs(pred - y)) / jnp.maximum(jnp.sum(w), EPS)


def r2(pred, y, w):
    sw = jnp.maximum(jnp.sum(w), EPS)
    ybar = jnp.sum(w * y) / sw
    ss_res = jnp.sum(w * (y - pred) ** 2)
    ss_tot = jnp.maximum(jnp.sum(w * (y - ybar) ** 2), EPS)
    return 1.0 - ss_res / ss_tot


def smape(pred, y, w):
    denom = jnp.maximum(jnp.abs(pred) + jnp.abs(y), EPS)
    return 2.0 * jnp.sum(w * jnp.abs(pred - y) / denom) / jnp.maximum(jnp.sum(w), EPS)


# --- multiclass --------------------------------------------------------------

def multiclass_error(prob, y, w):
    """prob (n, C) — or a 1-D positive-class score when a binary-shaped payload
    reaches a multiclass evaluation (labels with only 2 observed classes take
    models' binary fast paths); y (n,) integer labels, w (n,)."""
    if prob.ndim == 1:
        pred = (prob > 0.5).astype(y.dtype)
    else:
        pred = jnp.argmax(prob, axis=1).astype(y.dtype)
    wrong = (pred != y).astype(jnp.float32)
    return jnp.sum(w * wrong) / jnp.maximum(jnp.sum(w), EPS)


METRICS_BINARY = {
    "auPR": au_pr,
    "auROC": au_roc,
    "logLoss": log_loss,
}
METRICS_REGRESSION = {
    "rmse": rmse,
    "mse": mse,
    "mae": mae,
    "r2": r2,
    "smape": smape,
}
# metrics where larger is better
LARGER_IS_BETTER = {"auPR", "auROC", "r2", "f1", "precision", "recall"}


@jax.jit
def binary_summary(scores, preds, y, w):
    """All binary point metrics in ONE program -> (10,) array, ONE host fetch.

    Order: auROC, auPR, precision, recall, f1, error, tp, fp, tn, fn.
    A single fetch matters: each separate float() is its own blocking
    device-to-host sync.
    """
    tp, fp, tn, fn = binary_counts(preds, y, w)
    prec, rec, f1, err = precision_recall_f1(preds, y, w)
    return jnp.stack([au_roc(scores, y, w), au_pr(scores, y, w),
                      prec, rec, f1, err, tp, fp, tn, fn])


@jax.jit
def regression_summary(pred, y, w):
    """rmse, mse, mae, r2, smape in one program / one fetch."""
    return jnp.stack([rmse(pred, y, w), mse(pred, y, w), mae(pred, y, w),
                      r2(pred, y, w), smape(pred, y, w)])
