"""What every family's plain reference shares: the fold assignment the
configuration states, operand rounding for the lower-precision control, and
the validation metric in float64 on the host.

Nothing here (or in any file of this directory) imports the program.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

#: (exponent bits, mantissa bits) of the matmul operands in each precision;
#: float8 is e4m3
OPERAND_BITS = {"float32": None, "bfloat16": (8, 7), "float8": (4, 3)}


def quantizer(precision: str) -> Callable[[jnp.ndarray], jnp.ndarray]:
    """Rounds a matmul operand to ``precision``, kept in float32.  The
    products then run at ``highest`` with float32 accumulation, so operand
    rounding is the only thing a lower precision changes.
    ``lax.reduce_precision`` and not a cast there and back: XLA removes such
    a pair of casts on the TPU (``xla_allow_excess_precision``)."""
    bits = OPERAND_BITS[precision]
    if bits is None:
        return lambda a: a
    return lambda a: jax.lax.reduce_precision(a, *bits)


def highest(fn):
    """Run ``fn`` with float32 products at full precision (on a TPU a
    float32 matmul otherwise runs in one bfloat16 pass)."""
    def wrapped(*args, **kwargs):
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kwargs)
    return wrapped


def fold_ids(n: int, folds: int, seed: int) -> np.ndarray:
    """Row -> validation fold, as the configuration's ``cv`` block states it:
    a seeded permutation of the row numbers, modulo the fold count."""
    return np.random.default_rng(seed).permutation(n) % folds


def au_pr(scores: np.ndarray, y: np.ndarray) -> float:
    """Area under the precision-recall curve over unit-weight rows: rows
    ranked by falling score (ties in row order), trapezoid rule, curve
    started at (recall 0, precision 1) — Spark's BinaryClassificationMetrics
    as the configuration's metric names it."""
    order = np.argsort(-scores.astype(np.float64), kind="stable")
    hit = y[order].astype(np.float64)
    tp = np.cumsum(hit)
    pos = max(tp[-1], 1e-12)
    precision = np.concatenate([[1.0], tp / np.arange(1, len(hit) + 1)])
    recall = np.concatenate([[0.0], tp / pos])
    return float(np.sum(0.5 * (precision[1:] + precision[:-1])
                        * np.diff(recall)))


def standardize(x, w):
    """Weighted column standardisation + a trailing ones column (the
    intercept).  A constant column keeps its values (std -> 1)."""
    sw = jnp.maximum(w.sum(), 1e-12)
    mean = (w[:, None] * x).sum(axis=0) / sw
    var = (w[:, None] * (x - mean) ** 2).sum(axis=0) / sw
    std = jnp.where(var > 1e-24, jnp.sqrt(var), 1.0)
    xs = (x - mean) / std
    return jnp.concatenate([xs, jnp.ones((x.shape[0], 1), x.dtype)], axis=1)
