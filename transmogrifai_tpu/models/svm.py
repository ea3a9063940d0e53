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


def _momentum_descent(loss_grad, curvature, d1: int, reg, max_iter: int,
                      has_intercept: bool, dtype) -> jnp.ndarray:
    """The update rule the refit and the sweep share: ``max_iter`` steps of
    momentum 0.9 from zero on ``loss + reg/2 ||beta||^2``, the intercept slot
    (last, with ``has_intercept``) exempt from the L2 term, step size
    ``1 / max(curvature + reg, 1e-6)``.  ``loss_grad(beta)`` is the averaged
    loss's gradient; ``curvature()`` its Lipschitz bound, a callable so the
    trace keeps the refit's op order (its module stays byte-equal)."""
    reg_mask = (jnp.ones(d1).at[-1].set(0.0) if has_intercept
                else jnp.ones(d1))
    lip = curvature() + reg
    lr = 1.0 / jnp.maximum(lip, 1e-6)

    def step(_, state):
        beta, vel = state
        with jax.named_scope("svc_step"):
            g = loss_grad(beta) + reg * reg_mask * beta
            vel_new = 0.9 * vel - lr * g
            return beta + vel_new, vel_new

    beta0 = jnp.zeros(d1, dtype=dtype)
    beta, _ = jax.lax.fori_loop(0, max_iter, step, (beta0, beta0))
    return beta


def _svc_body(x: jnp.ndarray, y_pm: jnp.ndarray, w: jnp.ndarray, reg: jnp.ndarray,
              max_iter: int, has_intercept: bool = True) -> jnp.ndarray:
    """Squared-hinge descent; y in {-1, +1}.  With ``has_intercept`` the
    trailing ones column is exempt from L2 (it IS the intercept); without it
    every column is a real feature and all are regularized."""
    sw = jnp.maximum(w.sum(), 1e-12)

    def hinge_grad(beta):
        z = x @ beta
        margin = 1.0 - y_pm * z
        active = jnp.maximum(margin, 0.0)
        return x.T @ (w * (-2.0 * y_pm * active)) / sw

    # Lipschitz bound for the step size: squared hinge curvature <= 2 ||x||^2
    return _momentum_descent(
        hinge_grad, lambda: 2.0 * (w[:, None] * x * x).sum() / sw,
        x.shape[1], reg, max_iter, has_intercept, x.dtype)


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


def _svc_shared_block_fit(x, y_pm, train_w, val_w, regs, max_iter: int,
                          has_intercept: bool):
    """Every (fold, reg) lane of the sweep fitted over ONE standardised block.

    Returns ``(xg, mean, std, coefs)``: ``xg`` (n, d+1) is ``x`` standardised
    once with the unit-weight moments ``mean``, ``std`` of the rows some fold
    reads, ones column last; ``coefs`` (k, g, d+1) are the lanes' solutions in
    ``xg``'s space, so a lane's margins are ``xg @ coefs[f, r]``.

    Fold ``f`` standardises with its own train-weighted moments, as
    ``_fit_arrays`` does.  That is an affine map of ``xg``'s columns,
    ``xs_f = (xg - c_f) * a_f``, so it sits on a lane's d+1 coefficients and
    not on a copy of the table: ``xs_f @ beta = xg @ T_f(beta)`` and
    ``xs_f.T @ r = T_f'(xg.T @ r)``, the ones column carrying the offset
    ``-c_f . (a_f * beta)`` one way and ``sum(r)`` the other.  The iterates
    are ``_svc_body``'s on ``xs_f`` in exact arithmetic.  Centring once keeps
    the MXU's bfloat16 operand rounding where a per-fold copy had it.
    """
    from ..parallel.mesh import constrain_rows

    d = x.shape[1]
    d1 = d + 1 if has_intercept else d
    some_fold = ((train_w.sum(0) + val_w.sum(0)) > 0).astype(x.dtype)
    xg, mean, std = _device_prepare_fit(x, some_fold, has_intercept=True,
                                        standardize=True)
    xg = constrain_rows(xg)

    def fold_moments(w):
        sw = jnp.maximum(w.sum(), 1e-12)
        mean_f = (w[:, None] * x).sum(0) / sw
        return mean_f, (w[:, None] * (x - mean_f) ** 2).sum(0) / sw

    with jax.named_scope("fold_standardize"):
        # the fold's moments of the RAW columns, fold by fold (no (k, n, d)
        # value): a column constant on a fold's train rows reads a variance
        # of exactly 0 there, which moments of the centred block would not
        mean_f, var_f = (jnp.stack(m) for m in
                         zip(*[fold_moments(w) for w in train_w]))
        scaled = var_f > 0
        a = std / jnp.where(scaled, jnp.sqrt(var_f), 1.0)
        c = (mean_f - mean) / std
        # sum_i w_i xs_ij^2 / sw is 1 for a column the fold scaled, 0 for one
        # it could not, 1 for the ones column: 2 ||xs||^2 without a pass
        curvature = 2.0 * (scaled.sum(1) + (1.0 if has_intercept else 0.0))

    def one_fold(w, a, c, curvature):
        sw = jnp.maximum(w.sum(), 1e-12)

        def to_block(beta):
            ab = a * beta[:d]
            b = beta[d] if has_intercept else 0.0
            return jnp.concatenate([ab, (b - (c * ab).sum())[None]])

        def from_block(gx):
            g = a * (gx[:d] - c * gx[d])
            return jnp.concatenate([g, gx[d:]]) if has_intercept else g

        def hinge_grad(beta):
            z = xg @ to_block(beta)
            active = jnp.maximum(1.0 - y_pm * z, 0.0)
            return from_block(xg.T @ (w * (-2.0 * y_pm * active))) / sw

        def one_grid(reg):
            return to_block(_momentum_descent(
                hinge_grad, lambda: curvature, d1, reg, max_iter,
                has_intercept, x.dtype))

        return jax.vmap(one_grid)(regs)

    return xg, mean, std, jax.vmap(one_fold)(train_w, a, c, curvature)


@partial(jax.jit, static_argnames=("max_iter", "has_intercept", "metric_fn"))
def _svc_cv_program(x, y, y_pm, train_w, val_w, regs, max_iter: int,
                    has_intercept: bool, metric_fn):
    """The whole (grid x fold) SVC sweep in one XLA program.

    All lanes read one standardised block; each fold's own standardisation
    (its train weights, matching _fit_arrays) is an affine map on the lane's
    coefficients (:func:`_svc_shared_block_fit`).  Metrics evaluate on the
    fold margins without leaving the chip.  Mirrors the reference's all-fold
    concurrency (OpCrossValidation.scala:114).

    dp x mp sharding rides ambient ``with_sharding_constraint`` annotations
    (identity off-mesh): row operands pin to the data axis so the per-fold
    moments and the descent's psums carry only (d,)-sized statistics.
    """
    from ..parallel.mesh import constrain_fold_rows, constrain_rows

    x, y, y_pm = constrain_rows(x), constrain_rows(y), constrain_rows(y_pm)
    train_w = constrain_fold_rows(train_w)
    val_w = constrain_fold_rows(val_w)
    xg, _, _, coefs = _svc_shared_block_fit(x, y_pm, train_w, val_w, regs,
                                            max_iter, has_intercept)

    def one_fold(fold_coefs, vw):
        return jax.vmap(lambda bg: metric_fn(xg @ bg, y, vw))(fold_coefs)

    with jax.named_scope("eval_sort"):
        return jax.vmap(one_fold)(coefs, val_w).T  # (grids, folds)


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
        with activity("device_wait", label="LinearSVC/refit"):
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
        """The whole (grid x fold) sweep as one device program, every lane
        over one shared block (per-fold standardization included, on the
        lanes' coefficients), one compile keyed on the metric; the launch
        span counts the lanes that shared the block.

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
        from ..parallel.mesh import DATA_AXIS, fit_vector
        from ..perf.programs import run_cached

        x32 = np.asarray(x, np.float32)
        xd, (yd,), tw, vw, n0 = sweep_placements(
            x32, [fit_vector(y)], train_w, val_w)
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
            label="LinearSVC/cv_program",
            counts=dict(shared_block_lanes=len(grids) * int(tw.shape[0])))


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
