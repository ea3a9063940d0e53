"""Linear SVC — squared-hinge loss, full-batch Newton-free gradient descent on device.

Reference capability: core/.../classification/OpLinearSVC.scala (wrapping Spark
LinearSVC: hinge loss via OWLQN, L2 reg, no probability output).

TPU-first: squared hinge is smooth, so a fixed-iteration Nesterov descent under
``lax.fori_loop`` compiles to one XLA program; the gradient is a single matvec pair.
Like Spark's LinearSVC the model emits rawPrediction only (no probabilities) — the
binary evaluator ranks by the margin.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from ..data.dataset import Column
from ..stages.base import Param
from .base import PredictionEstimatorBase, PredictionModelBase
from .logistic import _device_prepare_fit, place_fit_arrays  # noqa: F401
from .prediction import PredictionColumn


def _svc_body(x: jnp.ndarray, y_pm: jnp.ndarray, w: jnp.ndarray, reg: jnp.ndarray,
              max_iter: int, has_intercept: bool = True) -> jnp.ndarray:
    """Squared-hinge descent; y in {-1, +1}.  With ``has_intercept`` the
    trailing ones column is exempt from L2 (it IS the intercept); without it
    every column is a real feature and all are regularized."""
    n, d1 = x.shape
    sw = jnp.maximum(w.sum(), 1e-12)
    reg_mask = (jnp.ones(d1).at[-1].set(0.0) if has_intercept
                else jnp.ones(d1))
    # Lipschitz bound for the step size: squared hinge curvature <= 2 ||x||^2
    lip = 2.0 * (w[:, None] * x * x).sum() / sw + reg
    lr = 1.0 / jnp.maximum(lip, 1e-6)

    def step(_, state):
        beta, vel = state
        with jax.named_scope("svc_step"):
            z = x @ beta
            margin = 1.0 - y_pm * z
            active = jnp.maximum(margin, 0.0)
            g = x.T @ (w * (-2.0 * y_pm * active)) / sw \
                + reg * reg_mask * beta
            vel_new = 0.9 * vel - lr * g
            return beta + vel_new, vel_new

    beta0 = jnp.zeros(d1, dtype=x.dtype)
    beta, _ = jax.lax.fori_loop(0, max_iter, step, (beta0, beta0))
    return beta


_svc_core = partial(jax.jit,
                    static_argnames=("max_iter", "has_intercept"))(_svc_body)


@jax.jit
def _sign_targets(y, n_valid):
    """±1 targets from 0/1 labels over the padded row block; 0 on the padded
    rows, as a zero-padded host vector of them reads."""
    from ..parallel.mesh import constrain_rows

    y_pm = jnp.where(y > 0.5, 1.0, -1.0).astype(jnp.float32)
    return constrain_rows(
        jnp.where(jnp.arange(y.shape[0]) < n_valid, y_pm, 0.0))


@partial(jax.jit, static_argnames=("max_iter", "has_intercept", "metric_fn"))
def _svc_cv_program(x, y, y_pm, train_w, val_w, regs, max_iter: int,
                    has_intercept: bool, metric_fn):
    """The whole (grid x fold) SVC sweep in one XLA program.

    Standardization happens per fold ON DEVICE with the fold's train weights
    (matching _fit_arrays), then the grid vmaps over regs and folds vmap over
    weights; metrics evaluate on the fold margins without leaving the chip.
    Mirrors the reference's all-fold concurrency (OpCrossValidation.scala:114).

    dp x mp sharding rides ambient ``with_sharding_constraint`` annotations
    (identity off-mesh): row operands pin to the data axis so the per-fold
    standardization/descent psums carry only (d,)-sized statistics.
    """
    from ..parallel.mesh import constrain_fold_rows, constrain_rows

    x, y, y_pm = constrain_rows(x), constrain_rows(y), constrain_rows(y_pm)
    train_w = constrain_fold_rows(train_w)
    val_w = constrain_fold_rows(val_w)

    def one_fold(w, vw):
        with jax.named_scope("fold_standardize"):
            sw = jnp.maximum(w.sum(), 1e-12)
            mean = (w[:, None] * x).sum(0) / sw
            var = (w[:, None] * (x - mean) ** 2).sum(0) / sw
            std = jnp.where(var > 0, jnp.sqrt(var), 1.0)
            xs = (x - mean) / std
            if has_intercept:
                xs = jnp.concatenate(
                    [xs, jnp.ones((x.shape[0], 1), x.dtype)], 1)

        def one_grid(reg):
            beta = _svc_body(xs, y_pm, w, reg, max_iter, has_intercept)
            with jax.named_scope("eval_sort"):
                return metric_fn(xs @ beta, y, vw)

        return jax.vmap(one_grid)(regs)

    return jax.vmap(one_fold)(train_w, val_w).T  # (grids, folds)


class LinearSVC(PredictionEstimatorBase):
    """Binary linear SVM (OpLinearSVC capability)."""

    reg_param = Param(default=0.0)
    max_iter = Param(default=100)
    fit_intercept = Param(default=True)
    standardize = Param(default=True)

    sweepable_params = ("reg_param",)

    def _fit_arrays(self, x, y, w):
        from ..perf.timers import activity

        xd, yd, wd = place_fit_arrays(x, y, w)
        with activity("launch", label="LinearSVC/prepare_fit"):
            xs, mean_d, std_d = _device_prepare_fit(
                xd, wd, has_intercept=bool(self.fit_intercept),
                standardize=bool(self.standardize))
            y_pm = jnp.where(yd > 0.5, 1.0, -1.0).astype(jnp.float32)
        with activity("launch", label="LinearSVC/svc_refit"):
            beta = _svc_core(
                xs, y_pm, wd,
                jnp.float32(self.reg_param), int(self.max_iter),
                has_intercept=bool(self.fit_intercept))
        with activity("device_wait"):
            beta, mean, std = (np.asarray(a) for a in (beta, mean_d, std_d))
        if self.fit_intercept:
            coef_s, b0 = beta[:-1], beta[-1]
        else:
            coef_s, b0 = beta, 0.0
        coef = coef_s / std
        intercept = float(b0 - (coef * mean).sum())
        return LinearSVCModel(coef=coef.astype(np.float64), intercept=intercept)

    def _cv_sweep_device(self, x, y, train_w, val_w,
                         grids: List[Dict[str, Any]], metric_fn):
        """Fold-vmapped sweep: the whole (grid x fold) program runs on device
        (per-fold standardization included), one compile keyed on the metric.

        The vectorized program only varies reg_param; grids touching any other
        param (max_iter, fit_intercept, ...) take the generic per-grid path so
        every grid key is honored."""
        if (not self.standardize
                or any(set(g) - {"reg_param"} for g in grids)):
            return None
        from .base import derive_on_device, place_grid, sweep_placements

        regs = place_grid(np.asarray(
            [float(g.get("reg_param", self.reg_param)) for g in grids],
            dtype=np.float32))
        from ..parallel.mesh import DATA_AXIS
        from ..perf.programs import run_cached

        x32 = np.asarray(x, np.float32)
        xd, (yd,), tw, vw, n0 = sweep_placements(
            x32, [np.asarray(y, np.float32)], train_w, val_w)
        # the ±1 targets are a function of the placed labels: made there, not
        # as a second host vector to pad, hash and look up
        ypmd = derive_on_device(_sign_targets, yd, jnp.int32(n0),
                                axes=(DATA_AXIS,),
                                label="LinearSVC/sign_targets")
        return run_cached(
            _svc_cv_program, xd, yd, ypmd, tw, vw, regs,
            statics=dict(max_iter=int(self.max_iter),
                         has_intercept=bool(self.fit_intercept),
                         metric_fn=metric_fn),
            label="LinearSVC/cv_program")


class LinearSVCModel(PredictionModelBase):
    def __init__(self, coef: np.ndarray, intercept: float, **kw):
        super().__init__(**kw)
        self.coef = np.asarray(coef, dtype=np.float64)
        self.intercept = float(intercept)

    def predict_column(self, vec: Column) -> PredictionColumn:
        z = vec.data.astype(np.float64) @ self.coef + self.intercept
        pred = (z > 0.0).astype(np.float64)
        # Spark parity: rawPrediction only, no probability column
        return PredictionColumn(pred, raw=np.column_stack([-z, z]), prob=None)

    def eval_payload_device(self, x32):
        from ..parallel.mesh import place_rows_bucketed_cached
        from .base import _linear_eval_payload

        from ..perf.timers import activity

        xd, _ = place_rows_bucketed_cached(np.asarray(x32, np.float32),
                                           insert=False)
        with activity("launch", label="LinearSVC/eval_payload"):
            return _linear_eval_payload(
                xd, jnp.asarray(self.coef, jnp.float32),
                jnp.float32(self.intercept), link="identity")
