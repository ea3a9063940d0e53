"""Plain reference for the gradient-boosted-trees family (binary labels).

Logistic loss, Newton boosting: the margin starts at the log-odds of the
weighted positive rate; each round fits one tree to the gradient
``w (p - y)`` and hessian ``w p (1 - p)`` over all columns and adds its leaf
values ``-eta G / (H + lambda)``.  The score is the sigmoid of the margin.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, List

import jax
import jax.numpy as jnp

from .common import highest
from .treegrow import bin_codes, leaf_values, quantile_edges

SCORE = "probability"


@partial(jax.jit, static_argnames=("rounds", "depth", "n_bins", "precision"))
def _boost(codes, y, weights, eta, reg_lambda, gamma, min_child_weight,
           rounds: int, depth: int, n_bins: int, precision: str):
    pos = (weights * (y == 1.0)[None, :]).sum(axis=1)
    p0 = jnp.clip(pos / jnp.maximum(weights.sum(axis=1), 1e-12),
                  1e-6, 1 - 1e-6)
    margin0 = jnp.broadcast_to(jnp.log(p0 / (1 - p0))[:, None], weights.shape)

    def one_round(margin, _):
        p = jax.nn.sigmoid(margin)
        grad = weights * (p - y[None, :])
        hess = weights * jnp.maximum(p * (1 - p), 1e-16)
        return margin + leaf_values(codes, grad, hess, depth, n_bins,
                                    reg_lambda, gamma, min_child_weight, eta,
                                    precision), None

    margin, _ = jax.lax.scan(one_round, margin0, None, length=rounds)
    return jax.nn.sigmoid(margin)


@highest
def fit_scores(x, y, weights, grids: List[Dict[str, Any]],
               params: Dict[str, Any], precision: str = "float32"):
    """(g, k, n) boosted probabilities of every (grid point, weight row)."""
    n_bins = int(params["n_bins"])
    codes = bin_codes(x, jnp.asarray(quantile_edges(x, n_bins)))
    return jnp.stack([_boost(
        codes, y, weights, jnp.float32(params["eta"]),
        jnp.float32(params["reg_lambda"]), jnp.float32(params["gamma"]),
        jnp.float32(params["min_child_weight"]), int(grid["num_rounds"]),
        int(grid["max_depth"]), n_bins, precision) for grid in grids])
