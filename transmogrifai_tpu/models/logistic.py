"""Logistic regression — full-batch IRLS/Newton on device.

Reference capability: core/.../classification/OpLogisticRegression.scala:1-212 (wrapping
Spark LogisticRegression).  TPU-first design: weighted IRLS with a dense Newton solve per
iteration (the (d+1)x(d+1) Hessian assembles as X^T W X — one MXU matmul), features
standardized internally like Spark's default, fixed iteration count under ``lax.fori_loop``
so the whole fit is one XLA program.  ``cv_sweep`` vmaps the fit over (fold-weights x
regularization grid): the reference's thread-pool of per-fold Spark jobs
(OpCrossValidation.scala:114-134) becomes a single batched device program.

Elastic-net (Spark parametrization: regParam λ, elasticNetParam α): both the CV sweep
and the final fit solve the exact composite objective with FISTA (accelerated proximal
gradient, soft-threshold prox — exact-zero sparsity like Spark's OWL-QN); pure-L2 grid
points take the faster vmapped IRLS path.
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

MAX_ITER_DEFAULT = 30


def _mxu_dtype():
    """MXU input dtype for the Hessian matmul: bf16 on TPU (f32 accumulation),
    f32 elsewhere so CPU tests stay exact.

    Safe because only the HESSIAN goes through bf16 — the gradient stays f32,
    so Newton's fixed point (g(beta*) = 0) is bit-identical; bf16 curvature
    error only perturbs the convergence path (quasi-Newton), not the solution
    a converged fit returns.  Same rationale as the tree kernels' _hist_dtype.

    Caveat (r3 advisor): _irls_core runs a FIXED max_iter loop with no
    convergence check, so a fit that has not fully converged returns a
    path-dependent beta and TPU can drift from the f32 CPU result.  On
    well-scaled (standardized) problems 30 Newton steps converge to well
    below bf16 curvature noise; the ill-conditioned bound is pinned by
    tests/test_model_families.py::test_bf16_hessian_drift_bound, which
    forces the bf16 path on an ill-conditioned fit and bounds the drift.
    """
    return jnp.bfloat16 if jax.default_backend() == "tpu" else jnp.float32


@partial(jax.jit, static_argnames=("max_iter", "has_intercept", "row_sum"))
def _irls_core(x: jnp.ndarray, y: jnp.ndarray, w: jnp.ndarray, reg: jnp.ndarray,
               max_iter: int, has_intercept: bool = True,
               row_sum=None) -> jnp.ndarray:
    """Weighted L2-regularized IRLS on pre-standardized features.

    x: (n, d[+1]) — trailing ones column when ``has_intercept``; returns beta.
    Objective: (1/sum_w) Σ w_i logloss_i + reg/2 ||beta_penalized||²
    (Spark-style averaged loss; the intercept slot is never penalized).

    TPU-first Hessian: with an intercept the augmented design is (n, d+1) and
    an odd d+1 (129 for the canonical post-transmogrify d=128) pads to two
    128-lane MXU tiles with half the lanes idle.  Instead the Hessian is
    assembled as a BORDERED system — the O(n·d²) matmul runs on the clean
    (n, d) feature block (full tiles, bf16-in/f32-accum on TPU), and the
    intercept row/column are O(n·d) matvec borders:

        H = [[Xᵀ S X,  Xᵀ s],
             [sᵀ X,    Σ s ]] / sw + diag(reg·mask)

    ``row_sum`` completes each sum over the rows (``w.sum()``, the
    gradient's ``Xᵀ r``, the bordered Hessian) before it is used: the
    identity when ``x`` holds every row, the all-reduce over the mesh's data
    axis inside :func:`_irls_sweep`'s per-chip region, where it holds a
    chip's share of them (:func:`_data_psum`).
    """
    row_sum = row_sum or (lambda a: a)
    n, d1 = x.shape
    sw = jnp.maximum(row_sum(w.sum()), 1e-12)
    reg_mask = jnp.ones(d1)
    if has_intercept:
        reg_mask = reg_mask.at[-1].set(0.0)  # don't regularize intercept
    xf = x[:, :-1] if has_intercept else x   # (n, d) MXU-friendly block
    md = _mxu_dtype()

    def step(_, beta):
        z = x @ beta
        p = jax.nn.sigmoid(z)
        g = row_sum(x.T @ (w * (p - y))) / sw + reg * reg_mask * beta
        # stable names in the ops' metadata, for per-kernel time from a trace
        with jax.named_scope("irls_hessian"):
            s = jnp.maximum(w * p * (1.0 - p), 1e-10)
            sx = xf * s[:, None]
            hxx = jax.lax.dot_general(                  # (d, d) f32-accum
                xf.T.astype(md), sx.astype(md), (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            if has_intercept:
                hxb = sx.sum(axis=0)                    # Xᵀ S 1 border
                hbb = s.sum()[None]
                h = jnp.concatenate([
                    jnp.concatenate([hxx, hxb[:, None]], axis=1),
                    jnp.concatenate([hxb, hbb])[None, :],
                ], axis=0)
            else:
                h = hxx
            h = row_sum(h) / sw + jnp.diag(reg * reg_mask + 1e-8)
        with jax.named_scope("irls_solve"):
            return beta - jnp.linalg.solve(h, g)

    beta0 = jnp.zeros(d1, dtype=x.dtype)
    return jax.lax.fori_loop(0, max_iter, step, beta0)


@partial(jax.jit, static_argnames=("max_iter", "has_intercept"))
def _fista_elastic(x, y, w, l1, l2, max_iter, has_intercept: bool = True):
    """Exact elastic-net logistic fit: FISTA with soft-threshold prox.

    Objective: (1/sw) Σ w_i logloss_i + l1·‖β₁‖₁ + l2/2·‖β₁‖² — the intercept
    slot (trailing ones column, present only when ``has_intercept``) is never
    penalized.  Step from the logistic Lipschitz bound
    L = λmax(XᵀWX)/(4·sw) + l2, λmax via power iteration.
    """
    d1 = x.shape[1]
    sw = jnp.maximum(w.sum(), 1e-12)
    pen_mask = jnp.ones(d1)
    if has_intercept:
        pen_mask = pen_mask.at[-1].set(0.0)

    def quad(v):
        return x.T @ (w * (x @ v)) / sw

    def power_step(_, v):
        u = quad(v)
        return u / (jnp.linalg.norm(u) + 1e-12)

    v = jax.lax.fori_loop(0, 30, power_step, jnp.ones(d1) / jnp.sqrt(1.0 * d1))
    lmax = v @ quad(v)
    step = 1.0 / (0.25 * lmax + l2 + 1e-12)

    def grad_smooth(b):
        p = jax.nn.sigmoid(x @ b)
        return x.T @ (w * (p - y)) / sw + l2 * pen_mask * b

    def soft(b, thr):
        return jnp.sign(b) * jnp.maximum(jnp.abs(b) - thr, 0.0)

    def fista(carry, _):
        b, z, t = carry
        with jax.named_scope("fista_step"):
            b_new = soft(z - step * grad_smooth(z), step * l1 * pen_mask)
            t_new = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * t * t))
            z_new = b_new + ((t - 1.0) / t_new) * (b_new - b)
        return (b_new, z_new, t_new), 0.0

    b0 = jnp.zeros(d1, x.dtype)
    (b, _, _), _ = jax.lax.scan(fista, (b0, b0, 1.0), None, length=max_iter)
    return b


def _data_psum(a):
    """A row sum over the mesh's data axis: each chip holds its rows' part."""
    from ..parallel.mesh import DATA_AXIS

    return jax.lax.psum(a, DATA_AXIS)


def _irls_lanes(x, y, train_w, regs, max_iter, has_intercept, row_sum=None):
    """The IRLS fit vmapped over fold weights (k, n) and the reg grid (g,)."""
    fit_fold = jax.vmap(
        lambda w, reg: _irls_core(x, y, w, reg, max_iter,
                                  has_intercept=has_intercept,
                                  row_sum=row_sum),
        in_axes=(0, None))
    fit_grid = jax.vmap(lambda reg: fit_fold(train_w, reg), in_axes=0)
    return fit_grid(regs)


def _irls_region(mesh, x, y, train_w, regs, max_iter, has_intercept):
    """The sweep under ``mesh`` as ONE ``shard_map`` region: each chip runs
    the one-chip IRLS step on its own row shard, and only the step's row sums
    cross the chips (:func:`_data_psum`: (d+1) and (d+1)² floats a lane an
    iteration, a fold's ``w.sum()`` once).  Every chip then solves the small
    system itself.  The grid points are dealt over the model axis, padded to
    a multiple of it with copies of the last; the betas leave the region
    replicated over the data axis, on the grid's placement of the
    unpartitioned form (``constrain_grid``).  Why a region: GSPMD's form of
    the same program laid the float32 feature block out row-major and ran
    the Hessian and its border at about a quarter of one chip's speed
    (PERF.md, PR 39)."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from ..parallel.mesh import DATA_AXIS, MODEL_AXIS, constrain_grid

    model = MODEL_AXIS if MODEL_AXIS in mesh.axis_names else None
    g = regs.shape[0]
    dealt = jnp.pad(regs, (0, (-g) % (mesh.shape[model] if model else 1)),
                    mode="edge")
    betas = shard_map(
        partial(_irls_lanes, max_iter=max_iter, has_intercept=has_intercept,
                row_sum=_data_psum),
        mesh=mesh,
        in_specs=(P(DATA_AXIS), P(DATA_AXIS), P(None, DATA_AXIS), P(model)),
        # the loop's zero start is the same on every chip, what it carries
        # after a step varies with the model slice's grid points
        out_specs=P(model), check_vma=False)(x, y, train_w, dealt)
    return constrain_grid(betas[:g])


def irls_allreduce_bytes(k: int, g: int, d1: int, max_iter: int) -> int:
    """Bytes :func:`_irls_region` all-reduces in a sweep of ``g`` grid points
    over ``k`` folds under the ambient mesh, from shapes: each lane (padded
    grid point x fold) its gradient's d+1 and Hessian's (d+1)² floats an
    iteration, and each model slice its k fold weight sums once.  0 off the
    mesh."""
    from ..parallel.mesh import MODEL_AXIS, current_mesh

    mesh = current_mesh()
    if mesh is None:
        return 0
    slices = mesh.shape[MODEL_AXIS] if MODEL_AXIS in mesh.axis_names else 1
    g += (-g) % slices
    return (max_iter * g * k * (d1 * d1 + d1) + slices * k) * 4


@partial(jax.jit, static_argnames=("max_iter", "has_intercept"))
def _irls_sweep(x, y, train_w, regs, max_iter, has_intercept: bool = True):
    """vmap the IRLS fit over fold weights (k, n) and reg grid (g,) -> betas (g, k, d+1).

    Under an ambient mesh (read at trace time; the executable cache keys on
    the mesh token, and operands placed over a mesh key jax's trace apart)
    the fit runs as :func:`_irls_region`: the one-chip step on each chip's
    rows, its row sums all-reduced.  Off the mesh the program is the one-chip
    sweep, unchanged.
    """
    from ..parallel.mesh import current_mesh

    mesh = current_mesh()
    if mesh is not None:
        return _irls_region(mesh, x, y, train_w, regs, max_iter,
                            has_intercept)
    return _irls_lanes(x, y, train_w, regs, max_iter, has_intercept)


@partial(jax.jit, static_argnames=("max_iter", "has_intercept"))
def _fista_sweep(x, y, train_w, l1s, l2s, max_iter, has_intercept: bool = True):
    """vmap the EXACT elastic-net FISTA fit over fold weights (k, n) and the
    (l1, l2) grid (g,) -> betas (g, k, d+1).  Grid points with l1 > 0 are ranked
    under the same composite objective the final fit solves (ADVICE r1: the
    smooth approximation could re-order near-tied grids that vary elastic_net).

    dp x mp sharding rides ambient ``with_sharding_constraint`` annotations
    (parallel/mesh.py:constrain_* — identity off-mesh): row operands pin to
    the data axis so XLA keeps the row math shard-local, and the (g, k, d+1)
    beta batch pins its grid axis to the model axis."""
    from ..parallel.mesh import constrain_fold_rows, constrain_grid, \
        constrain_rows

    x, y, train_w = constrain_rows(x), constrain_rows(y), \
        constrain_fold_rows(train_w)
    fit_fold = jax.vmap(
        lambda w, l1, l2: _fista_elastic(x, y, w, l1, l2, max_iter,
                                         has_intercept=has_intercept),
        in_axes=(0, None, None))
    fit_grid = jax.vmap(lambda l1, l2: fit_fold(train_w, l1, l2))
    return constrain_grid(fit_grid(l1s, l2s))


@partial(jax.jit, static_argnames=("has_intercept", "standardize"))
def _device_prepare_fit(x, w, has_intercept: bool, standardize: bool):
    """WEIGHTED standardize + ones-append for a final fit, on device from the
    shared raw placement (padded rows carry w=0, so the moments are exact).
    Returns (xs, mean, std) — mean/std come back to host only as (d,) vectors,
    instead of shipping a fresh standardized (n, d) block to the device.
    """
    sw = jnp.maximum(w.sum(), 1e-12)
    if standardize:
        mean = (w[:, None] * x).sum(axis=0) / sw
        var = (w[:, None] * (x - mean) ** 2).sum(axis=0) / sw
        std = jnp.sqrt(var)
        std = jnp.where(std < 1e-12, 1.0, std)
    else:
        mean = jnp.zeros(x.shape[1], x.dtype)
        std = jnp.ones(x.shape[1], x.dtype)
    xs = (x - mean) / std
    if has_intercept:
        xs = jnp.concatenate([xs, jnp.ones((x.shape[0], 1), x.dtype)], axis=1)
    return xs, mean, std


def place_fit_arrays(x, y, w):
    """(xd, yd, wd) for a final fit: raw block through the shared placement
    cache (a refit after CV hits the block the sweep already transferred),
    labels/weights zero-padded to match — through ``place_fit_rows``, so
    inside a selector fit the handles the sweep placed come back as they are."""
    from ..parallel.mesh import place_fit_vector, place_rows_bucketed_cached

    x32 = np.asarray(x, np.float32)
    xd, _ = place_rows_bucketed_cached(x32)
    n_padded = int(xd.shape[0])
    return (xd, place_fit_vector(y, n_padded),
            place_fit_vector(w, n_padded))


@partial(jax.jit, static_argnames=("has_intercept", "standardize"))
def _device_prepare(x, n_valid, has_intercept: bool, standardize: bool):
    """Standardize + ones-append ON DEVICE from the shared raw placement.

    ``x`` is zero-row-padded past ``n_valid``; the explicit row mask keeps the
    moments exact (unit-weight standardization, row-mask form).  Padded
    rows end up at (-mean/std) but always carry zero fold weights downstream.
    """
    n = x.shape[0]
    if standardize:
        m = (jnp.arange(n) < n_valid)[:, None].astype(x.dtype)
        tot = jnp.asarray(n_valid, x.dtype)
        mean = (x * m).sum(axis=0) / tot  # zero-padded rows contribute 0
        var = (((x - mean) * m) ** 2).sum(axis=0) / tot
        std = jnp.sqrt(var)
        std = jnp.where(std < 1e-12, 1.0, std)
        xs = (x - mean) / std
    else:
        xs = x
    if has_intercept:
        xs = jnp.concatenate([xs, jnp.ones((n, 1), x.dtype)], axis=1)
    return xs


class LogisticRegression(PredictionEstimatorBase):
    """Binary logistic regression estimator (OpLogisticRegression capability)."""

    reg_param = Param(default=0.0)
    elastic_net = Param(default=0.0)
    max_iter = Param(default=MAX_ITER_DEFAULT)
    fit_intercept = Param(default=True)
    standardize = Param(default=True)

    sweepable_params = ("reg_param",)

    def _effective_reg(self, reg_param=None, elastic_net=None) -> float:
        rp = self.reg_param if reg_param is None else reg_param
        en = self.elastic_net if elastic_net is None else elastic_net
        return float(rp) * (1.0 - float(en))

    def _finalize_beta(self, beta: np.ndarray, mean: np.ndarray, std: np.ndarray):
        """Fold standardization back into raw-space coefficients + intercept."""
        if self.fit_intercept:
            coef_s, b0 = beta[:-1], beta[-1]
        else:
            coef_s, b0 = beta, 0.0
        coef = coef_s / std
        intercept = float(b0 - (coef * mean).sum())
        return coef.astype(np.float64), intercept

    def _fit_arrays(self, x, y, w):
        from ..perf.timers import activity

        xd, yd, wd = place_fit_arrays(x, y, w)
        with activity("launch", label="LogisticRegression/prepare_fit"):
            xs, mean_d, std_d = _device_prepare_fit(
                xd, wd, has_intercept=bool(self.fit_intercept),
                standardize=bool(self.standardize))
        l1 = float(self.reg_param) * float(self.elastic_net)
        if l1 > 0.0:
            # exact composite objective (Spark OWL-QN role): FISTA prox loop
            l2 = float(self.reg_param) * (1.0 - float(self.elastic_net))
            with activity("launch", label="LogisticRegression/fista_refit"):
                beta = _fista_elastic(
                    xs, yd, wd, jnp.float32(l1), jnp.float32(l2),
                    max(10 * self.max_iter, 300),
                    has_intercept=bool(self.fit_intercept))
        else:
            with activity("launch", label="LogisticRegression/irls_refit"):
                beta = _irls_core(
                    xs, yd, wd,
                    jnp.float32(self._effective_reg()), self.max_iter,
                    has_intercept=bool(self.fit_intercept),
                )
        with activity("device_wait", label="LogisticRegression/refit"):
            beta, mean, std = (np.asarray(a) for a in (beta, mean_d, std_d))
        coef, intercept = self._finalize_beta(beta, mean, std)
        return LogisticRegressionModel(coef=coef, intercept=intercept)

    # --- device CV sweep ------------------------------------------------------
    def _cv_sweep_device(self, x, y, train_w, val_w,
                         grids: List[Dict[str, Any]], metric_fn):
        """One XLA program per solver for the whole (grid x fold) sweep: pure-L2
        grids fit via vmapped IRLS, elastic-net grids via vmapped exact FISTA.
        Returns the pending device metric array (no host sync)."""
        l1l2 = []
        for g in grids:
            rp = float(g.get("reg_param", self.reg_param))
            en = float(g.get("elastic_net", self.elastic_net))
            l1l2.append((rp * en, rp * (1.0 - en)))
        # partition covers EVERY grid point: non-positive l1 (including a
        # typo'd negative reg/elastic_net) routes to the smooth IRLS solver —
        # a grid must never silently evaluate as all-zero coefficients
        l2_idx = [i for i, (l1, _) in enumerate(l1l2) if l1 <= 0.0]
        en_idx = [i for i, (l1, _) in enumerate(l1l2) if l1 > 0.0]
        # Rows zero-pad twice over (safe — fold weights pad to zero, so padded
        # rows never enter the weighted IRLS or the validation metric):
        # 1. to a power-of-two bucket, so the sweep compiles per bucket rather
        #    than per dataset size (XLA compile is seconds per shape);
        # 2. to the ambient mesh's data-axis multiple for sharding.
        # The RAW block places once per selector fit (shared across families
        # via sweep_placements); standardization runs on device.
        from .base import sweep_placements

        from ..parallel.mesh import fit_vector
        from ..perf.timers import activity

        x32 = np.asarray(x, np.float32)
        xd_raw, (yd,), train_w, val_w, n0 = sweep_placements(
            x32, [fit_vector(y)], train_w, val_w)
        with activity("launch", label="LogisticRegression/prepare"):
            xd = _device_prepare(xd_raw, jnp.int32(n0),
                                 has_intercept=bool(self.fit_intercept),
                                 standardize=bool(self.standardize))

        k, d1 = train_w.shape[0], int(xd.shape[1])
        has_icpt = bool(self.fit_intercept)
        parts = []
        from ..perf.programs import run_cached
        from .base import place_grid

        if l2_idx:
            regs = place_grid(np.asarray([l1l2[i][1] for i in l2_idx],
                                         dtype=np.float32))
            parts.append((l2_idx, run_cached(
                _irls_sweep, xd, yd, train_w, regs,
                statics=dict(max_iter=int(self.max_iter),
                             has_intercept=has_icpt),
                label="LogisticRegression/irls_sweep",
                counts=dict(irls_allreduce_bytes=irls_allreduce_bytes(
                    k, len(l2_idx), d1, int(self.max_iter))))))
        if en_idx:
            l1s = place_grid(np.asarray([l1l2[i][0] for i in en_idx],
                                        dtype=np.float32))
            l2s = place_grid(np.asarray([l1l2[i][1] for i in en_idx],
                                        dtype=np.float32))
            parts.append((en_idx, run_cached(
                _fista_sweep, xd, yd, train_w, l1s, l2s,
                statics=dict(max_iter=max(10 * int(self.max_iter), 300),
                             has_intercept=has_icpt),
                label="LogisticRegression/fista_sweep")))
        with activity("launch", label="LogisticRegression/gather_betas"):
            betas = jnp.zeros((len(grids), k, d1), dtype=jnp.float32)
            for idx, b in parts:
                betas = betas.at[jnp.asarray(idx)].set(b)

        from .base import count_eval_replicas, eval_linear_sweep_program

        count_eval_replicas(xd, yd, betas, val_w)
        return run_cached(
            eval_linear_sweep_program(), xd, yd, betas, val_w,
            statics=dict(metric_fn=metric_fn, link="sigmoid"),
            label="LogisticRegression/eval_sweep")


class LogisticRegressionModel(PredictionModelBase):
    def __init__(self, coef: np.ndarray, intercept: float, **kw):
        super().__init__(**kw)
        self.coef = np.asarray(coef, dtype=np.float64)
        self.intercept = float(intercept)

    def predict_column(self, vec: Column) -> PredictionColumn:
        z = vec.data.astype(np.float64) @ self.coef + self.intercept
        p1 = 1.0 / (1.0 + np.exp(-z))
        prob = np.column_stack([1.0 - p1, p1])
        raw = np.column_stack([-z, z])
        return PredictionColumn.classification(raw, prob)

    def eval_payload_device(self, x32):
        from ..parallel.mesh import place_rows_bucketed_cached
        from .base import _linear_eval_payload

        from ..perf.timers import activity

        xd, _ = place_rows_bucketed_cached(np.asarray(x32, np.float32),
                                           insert=False)
        with activity("launch", label="LogisticRegression/eval_payload"):
            return _linear_eval_payload(
                xd, jnp.asarray(self.coef, jnp.float32),
                jnp.float32(self.intercept), link="sigmoid")
