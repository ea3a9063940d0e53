"""Multinomial logistic regression (softmax) — full-batch Newton-free optimizer on device.

Reference capability: multiclass OpLogisticRegression (Spark multinomial family).  Uses
fixed-iteration full-batch Adam under ``lax.fori_loop`` (one XLA program; vmap-able over
fold weights and reg grid for CV sweeps).
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

MAX_ITER_DEFAULT = 200
LR_DEFAULT = 0.3


@partial(jax.jit, static_argnames=("n_classes", "max_iter", "has_intercept"))
def _softmax_core(x, y_onehot, w, reg, n_classes: int, max_iter: int,
                  has_intercept: bool = True):
    """x (n, d[+1]); the trailing ones column (when present) is exempt from
    L2.  Returns B (d[+1], C)."""
    n, d1 = x.shape
    sw = jnp.maximum(w.sum(), 1e-12)
    reg_mask = (jnp.ones((d1, 1)).at[-1, 0].set(0.0) if has_intercept
                else jnp.ones((d1, 1)))

    def loss_grad(b):
        logits = x @ b
        logp = jax.nn.log_softmax(logits, axis=1)
        p = jnp.exp(logp)
        g = x.T @ (w[:, None] * (p - y_onehot)) / sw + reg * reg_mask * b
        return g

    b0 = jnp.zeros((d1, n_classes), dtype=x.dtype)
    m0 = jnp.zeros_like(b0)
    v0 = jnp.zeros_like(b0)
    beta1, beta2, eps, lr = 0.9, 0.999, 1e-8, LR_DEFAULT

    def step(i, state):
        b, m, v = state
        g = loss_grad(b)
        m = beta1 * m + (1 - beta1) * g
        v = beta2 * v + (1 - beta2) * g * g
        mh = m / (1 - beta1 ** (i + 1.0))
        vh = v / (1 - beta2 ** (i + 1.0))
        b = b - lr * mh / (jnp.sqrt(vh) + eps)
        return (b, m, v)

    b, _, _ = jax.lax.fori_loop(0, max_iter, step, (b0, m0, v0))
    return b


class MultinomialLogisticRegression(PredictionEstimatorBase):
    reg_param = Param(default=0.0)
    elastic_net = Param(default=0.0)
    max_iter = Param(default=MAX_ITER_DEFAULT)
    fit_intercept = Param(default=True)
    n_classes = Param(default=None, doc="None = infer from labels")

    sweepable_params = ("reg_param",)

    def _n_classes(self, y: np.ndarray) -> int:
        return int(self.n_classes) if self.n_classes else int(y.max()) + 1

    def _fit_arrays(self, x, y, w):
        from .logistic import _device_prepare_fit, place_fit_arrays

        c = self._n_classes(y)
        xd, yd, wd = place_fit_arrays(x, y, w)
        y_onehot = jax.nn.one_hot(yd.astype(jnp.int32), c, dtype=jnp.float32)
        xs, _, _ = _device_prepare_fit(
            xd, wd, has_intercept=bool(self.fit_intercept), standardize=False)
        reg = jnp.float32(float(self.reg_param) * (1.0 - float(self.elastic_net)))
        b = np.asarray(_softmax_core(xs, y_onehot, wd,
                                     reg, c, int(self.max_iter),
                                     has_intercept=bool(self.fit_intercept)))
        if self.fit_intercept:
            coef, intercept = b[:-1], b[-1]
        else:
            coef, intercept = b, np.zeros(c)
        return MultinomialLogisticRegressionModel(coef=coef, intercept=intercept)

    def _cv_sweep_device(self, x, y, train_w, val_w,
                         grids: List[Dict[str, Any]], metric_fn):
        c = self._n_classes(y)
        y_onehot = np.eye(c, dtype=np.float32)[y.astype(np.int32)]
        from .base import (
            count_eval_replicas, eval_softmax_sweep_program, place_grid,
            sweep_placements)

        regs = place_grid(np.asarray(
            [float(g.get("reg_param", self.reg_param))
             * (1.0 - float(g.get("elastic_net", self.elastic_net))) for g in grids],
            dtype=np.float32))
        from .logistic import _device_prepare

        has_icpt = bool(self.fit_intercept)
        xd_raw, (yd, yoh), twd, vwd, n0 = sweep_placements(
            np.asarray(x, np.float32),
            [y.astype(np.float32), y_onehot], train_w, val_w)
        xd = _device_prepare(xd_raw, jnp.int32(n0), has_intercept=has_icpt,
                             standardize=False)
        fit_fold = jax.vmap(
            lambda w_, reg: _softmax_core(xd, yoh, w_, reg, c,
                                          int(self.max_iter),
                                          has_intercept=has_icpt),
            in_axes=(0, None))
        bs = jax.vmap(lambda reg: fit_fold(twd, reg), in_axes=0)(regs)

        count_eval_replicas(xd, yd, bs, vwd)
        return eval_softmax_sweep_program()(
            xd, yd.astype(jnp.int32), bs, vwd, metric_fn=metric_fn)


class MultinomialLogisticRegressionModel(PredictionModelBase):
    def __init__(self, coef: np.ndarray, intercept: np.ndarray, **kw):
        super().__init__(**kw)
        self.coef = np.asarray(coef, dtype=np.float64)
        self.intercept = np.asarray(intercept, dtype=np.float64)

    def predict_column(self, vec: Column) -> PredictionColumn:
        from .base import softmax_probs

        logits = vec.data.astype(np.float64) @ self.coef + self.intercept
        return PredictionColumn.classification(logits, softmax_probs(logits))
