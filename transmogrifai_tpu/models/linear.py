"""Linear regression — weighted ridge, closed-form normal equations on device.

Reference capability: core/.../regression/OpLinearRegression.scala (Spark LinearRegression).
X^T W X is one MXU matmul; the (d+1) solve is exact, and ``cv_sweep`` vmaps the solve over
(fold-weights x reg grid) in a single XLA program.
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
from .prediction import PredictionColumn


@partial(jax.jit, static_argnames=("has_intercept",))
def _ridge_core(x: jnp.ndarray, y: jnp.ndarray, w: jnp.ndarray, reg: jnp.ndarray,
                has_intercept: bool = True) -> jnp.ndarray:
    """Averaged-loss ridge; with ``has_intercept`` the trailing ones column is
    exempt from L2 (it IS the intercept)."""
    d1 = x.shape[1]
    sw = jnp.maximum(w.sum(), 1e-12)
    reg_mask = (jnp.ones(d1).at[-1].set(0.0) if has_intercept
                else jnp.ones(d1))
    xtwx = (x.T * w) @ x / sw
    xtwy = x.T @ (w * y) / sw
    h = xtwx + jnp.diag(reg * reg_mask + 1e-9)
    return jnp.linalg.solve(h, xtwy)


@partial(jax.jit, static_argnames=("has_intercept",))
def _ridge_sweep(x, y, train_w, regs, has_intercept: bool = True):
    """dp x mp sharding annotations as in logistic._fista_sweep: rows pin to
    the data axis (the normal-equation psums carry only (d, d) blocks), the
    beta batch's grid axis to the model axis; identity off-mesh."""
    from ..parallel.mesh import constrain_fold_rows, constrain_grid, \
        constrain_rows

    x, y, train_w = constrain_rows(x), constrain_rows(y), \
        constrain_fold_rows(train_w)
    fit_fold = jax.vmap(
        lambda w, reg: _ridge_core(x, y, w, reg, has_intercept=has_intercept),
        in_axes=(0, None))
    return constrain_grid(
        jax.vmap(lambda reg: fit_fold(train_w, reg), in_axes=0)(regs))


class LinearRegression(PredictionEstimatorBase):
    reg_param = Param(default=0.0)
    elastic_net = Param(default=0.0)
    fit_intercept = Param(default=True)

    sweepable_params = ("reg_param",)

    def _split_beta(self, beta: np.ndarray):
        if self.fit_intercept:
            return beta[:-1].astype(np.float64), float(beta[-1])
        return beta.astype(np.float64), 0.0

    def _fit_arrays(self, x, y, w):
        from .logistic import _device_prepare_fit, place_fit_arrays

        xd, yd, wd = place_fit_arrays(x, y, w)
        xs, _, _ = _device_prepare_fit(
            xd, wd, has_intercept=bool(self.fit_intercept), standardize=False)
        reg = jnp.float32(float(self.reg_param) * (1.0 - float(self.elastic_net)))
        beta = np.asarray(_ridge_core(
            xs, yd, wd, reg, has_intercept=bool(self.fit_intercept)))
        coef, intercept = self._split_beta(beta)
        return LinearRegressionModel(coef=coef, intercept=intercept)

    def _cv_sweep_device(self, x, y, train_w, val_w,
                         grids: List[Dict[str, Any]], metric_fn):
        from .base import (
            count_eval_replicas, eval_linear_sweep_program, place_grid,
            sweep_placements)

        regs = place_grid(np.asarray(
            [float(g.get("reg_param", self.reg_param))
             * (1.0 - float(g.get("elastic_net", self.elastic_net))) for g in grids],
            dtype=np.float32))
        from .logistic import _device_prepare

        has_icpt = bool(self.fit_intercept)
        xd_raw, (yd,), twd, vwd, n0 = sweep_placements(
            np.asarray(x, np.float32), [np.asarray(y, np.float32)],
            train_w, val_w)
        xd = _device_prepare(xd_raw, jnp.int32(n0), has_intercept=has_icpt,
                             standardize=False)
        from ..perf.programs import run_cached

        betas = run_cached(_ridge_sweep, xd, yd, twd, regs,
                           statics=dict(has_intercept=has_icpt),
                           label="LinearRegression/ridge_sweep")
        count_eval_replicas(xd, yd, betas, vwd)
        return run_cached(eval_linear_sweep_program(), xd, yd, betas, vwd,
                          statics=dict(metric_fn=metric_fn),
                          label="LinearRegression/eval_sweep")


class LinearRegressionModel(PredictionModelBase):
    def __init__(self, coef: np.ndarray, intercept: float, **kw):
        super().__init__(**kw)
        self.coef = np.asarray(coef, dtype=np.float64)
        self.intercept = float(intercept)

    def predict_column(self, vec: Column) -> PredictionColumn:
        pred = vec.data.astype(np.float64) @ self.coef + self.intercept
        return PredictionColumn.regression(pred)
