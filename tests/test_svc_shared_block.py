"""The SVC sweep over one shared block (``models/svm._svc_cv_program``).

Every (fold, reg) lane reads one standardised block and carries its fold's
own standardisation as an affine map on its coefficients.  The yardstick
here is the formulation that map replaces, stated plainly: a standardised
copy of the table per fold, ``_svc_body`` on it.  CPU float32 throughout, so
the two differ by rounding only.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from transmogrifai_tpu.evaluators import metrics as M
from transmogrifai_tpu.evaluators.base import BinaryClassificationEvaluator
from transmogrifai_tpu.models import svm
from transmogrifai_tpu.models.tuning import CrossValidator
from transmogrifai_tpu.perf.timers import record_phases

N, PAD, D, MAX_ITER = 480, 32, 6, 60
TABLES = ("far_mean", "global_constant", "fold_constant", "padded_rows")


def _table(kind: str, folds: int, seed: int = 0):
    """(x, y, train_w, val_w): a table with the named hazard in column 0,
    fold weights in {1, 2} on the fold's rows and 0 elsewhere."""
    rng = np.random.default_rng(seed)
    n = N + (PAD if kind == "padded_rows" else 0)
    x = rng.normal(size=(n, D)).astype(np.float32)
    x[:, 1] = 3.0 * x[:, 1] - 2.0
    fold_id = rng.integers(0, max(folds, 3), size=n)
    in_val = np.stack([fold_id == f for f in range(folds)])
    if kind == "far_mean":          # mean 1e3, std 1
        x[:, 0] += 1e3
    elif kind == "global_constant":  # sums exactly, so every variance is 0
        x[:, 0] = 2.5
    elif kind == "fold_constant":   # 0 on fold 0's train rows, not on its val
        x[:, 0] = np.where(in_val[0], rng.integers(0, 2, size=n), 0.0)
    logits = x[:, 1] * 0.4 - x[:, 2] + 0.5 * x[:, 3] + 0.3
    if kind != "global_constant":
        logits = logits + 0.8 * (x[:, 0] - x[:, 0].mean())
    y = (rng.random(n) < 1.0 / (1.0 + np.exp(-logits))).astype(np.float32)
    base_w = rng.integers(1, 3, size=n).astype(np.float32)
    if kind == "padded_rows":       # zero rows past N, zero weights on them
        x[N:], y[N:], base_w[N:] = 0.0, 0.0, 0.0
    train_w = np.where(in_val, 0.0, base_w).astype(np.float32)
    val_w = np.where(in_val, base_w, 0.0).astype(np.float32)
    return x, y, train_w, val_w


def _per_fold_copy_sweep(x, y, train_w, val_w, regs, has_intercept, metric_fn):
    """The sweep as it stood: fold f standardises a COPY of the table with its
    own train-weighted moments and every reg descends on that copy.
    -> metrics (g, k), raw-space (coef, offset) per (fold, reg)."""
    x, y = jnp.asarray(x), jnp.asarray(y)
    y_pm = jnp.where(y > 0.5, 1.0, -1.0)
    d = x.shape[1]
    metrics, coefs = [], []
    for w, vw in zip(jnp.asarray(train_w), jnp.asarray(val_w)):
        sw = jnp.maximum(w.sum(), 1e-12)
        mean = (w[:, None] * x).sum(0) / sw
        var = (w[:, None] * (x - mean) ** 2).sum(0) / sw
        std = jnp.where(var > 0, jnp.sqrt(var), 1.0)
        xs = (x - mean) / std
        if has_intercept:
            xs = jnp.concatenate([xs, jnp.ones((x.shape[0], 1))], 1)
        metrics.append([])
        coefs.append([])
        for reg in regs:
            beta = svm._svc_body(xs, y_pm, w, jnp.float32(reg), MAX_ITER,
                                 has_intercept)
            metrics[-1].append(float(metric_fn(xs @ beta, y, vw)))
            coefs[-1].append(_raw_space(
                beta[:d], beta[d] if has_intercept else 0.0, mean, std))
    return np.asarray(metrics).T, coefs


def _raw_space(coef_s, b, mean, std):
    """(coef, offset) of the raw columns, in float64."""
    coef = np.asarray(coef_s, np.float64) / np.asarray(std, np.float64)
    return coef, float(b) - float(coef @ np.asarray(mean, np.float64))


def _program_args(x, y, train_w, val_w, regs):
    y = jnp.asarray(y)
    return (jnp.asarray(x), y, jnp.where(y > 0.5, 1.0, -1.0),
            jnp.asarray(train_w), jnp.asarray(val_w),
            jnp.asarray(regs, jnp.float32))


@pytest.mark.parametrize("lanes,metric", [((3, 2), "auPR"), ((1, 1), "auROC")])
@pytest.mark.parametrize("has_intercept", [True, False])
@pytest.mark.parametrize("table", TABLES)
def test_sweep_equals_the_per_fold_copy_formulation(table, has_intercept,
                                                    lanes, metric):
    folds, n_regs = lanes
    regs = [0.01, 0.1][:n_regs]
    metric_fn = M.METRICS_BINARY[metric]
    x, y, train_w, val_w = _table(table, folds)
    want, want_coefs = _per_fold_copy_sweep(x, y, train_w, val_w, regs,
                                            has_intercept, metric_fn)
    xd, yd, ypm, tw, vw, rd = _program_args(x, y, train_w, val_w, regs)
    got = svm._svc_cv_program(xd, yd, ypm, tw, vw, rd, max_iter=MAX_ITER,
                              has_intercept=has_intercept,
                              metric_fn=metric_fn)
    assert got.shape == (n_regs, folds)
    np.testing.assert_allclose(np.asarray(got), want, rtol=0, atol=1e-5)

    _, mean, std, coefs = svm._svc_shared_block_fit(
        xd, ypm, tw, vw, rd, MAX_ITER, has_intercept)
    assert coefs.shape == (folds, n_regs, D + 1)
    for f in range(folds):
        for r in range(n_regs):
            coef, offset = _raw_space(coefs[f, r, :D], coefs[f, r, D],
                                      mean, std)
            want_coef, want_offset = want_coefs[f][r]
            np.testing.assert_allclose(coef, want_coef, rtol=0, atol=1e-4)
            # the offset is a sum of coef * mean: at a mean of 1e3 float32
            # carries it to 1e-4 of ITS terms
            room = 1e-4 * max(1.0, float(np.abs(x[:, 0]).max()))
            assert abs(offset - want_offset) <= room


def test_the_block_is_the_table_standardised_once_with_a_ones_column():
    """One block for every fold, whatever a fold can or cannot scale: mapped
    back by the GLOBAL moments it is the table, and its last column is 1."""
    x, y, train_w, val_w = _table("fold_constant", 3)
    assert np.all(x[train_w[0] > 0, 0] == 0) and x[val_w[0] > 0, 0].any()
    xd, _, ypm, tw, vw, rd = _program_args(x, y, train_w, val_w, [0.01])
    xg, mean, std, _ = svm._svc_shared_block_fit(xd, ypm, tw, vw, rd, 1, True)
    assert xg.shape == (N, D + 1)
    np.testing.assert_allclose(np.asarray(xg[:, :D] * std + mean), x,
                               atol=1e-5)
    assert np.all(np.asarray(xg[:, D]) == 1.0)


@pytest.mark.parametrize("has_intercept", [True, False])
def test_lowered_program_holds_one_block_and_no_per_fold_copy(has_intercept):
    n, d, k, g = 64, 5, 3, 2
    f32 = jnp.float32
    row = jax.ShapeDtypeStruct((n,), f32)
    lowered = svm._svc_cv_program.lower(
        jax.ShapeDtypeStruct((n, d), f32), row, row,
        jax.ShapeDtypeStruct((k, n), f32), jax.ShapeDtypeStruct((k, n), f32),
        jax.ShapeDtypeStruct((g,), f32), max_iter=4,
        has_intercept=has_intercept, metric_fn=M.METRICS_BINARY["auPR"])
    text = lowered.as_text()
    shapes = set(re.findall(r"tensor<([0-9x]+)x[a-z]+[0-9]+>", text))
    for lead in (f"{k}x", f"{g}x", f"{k}x{g}x", f"{g}x{k}x"):
        for width in (d, d + 1):
            assert f"{lead}{n}x{width}" not in shapes, (lead, width)
    # the descent loop carries one (n, d+1) block, the targets and the fold
    # weights, and nothing else with a row axis
    (carried,) = re.findall(r"stablehlo\.while\(.*\) : (.*)\n", text)
    row_sized = sorted(t for t in re.findall(r"tensor<([0-9x]+)xf32>", carried)
                       if str(n) in t.split("x"))
    assert row_sized == sorted([f"{n}x{d + 1}", f"{n}", f"{k}x{n}"])


def test_validator_counts_the_lanes_that_shared_a_block():
    """Through ``CrossValidator.validate``: the sweep's ``host.launch`` span
    says how many lanes shared one block; a grid the device sweep declines
    runs the generic path and carries no such count."""
    x, y, _, _ = _table("far_mean", 3)
    cv = CrossValidator(BinaryClassificationEvaluator("auPR"), num_folds=3)
    grids = [{"reg_param": 0.01}, {"reg_param": 0.1}]
    with record_phases() as rec:
        result = cv.validate([(svm.LinearSVC(max_iter=MAX_ITER), grids)], x, y)
    train_w, val_w = cv.fold_weights(y, np.ones_like(y))
    want, _ = _per_fold_copy_sweep(x, y, train_w, val_w, [0.01, 0.1], True,
                                   M.METRICS_BINARY["auPR"])
    got = np.asarray([ev.metric_values for ev in result.evaluations])
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-5)
    shared = [s.counts["shared_block_lanes"] for s in rec.spans
              if s.path == "host.launch" and "shared_block_lanes" in s.counts]
    assert shared == [6]

    with record_phases() as rec:
        cv.validate([(svm.LinearSVC(), [{"max_iter": 5}])], x, y)
    assert not any("shared_block_lanes" in (s.counts or {})
                   for s in rec.spans)
