"""Plain reference for the random-forest family (binary labels).

Each tree sees a Poisson(1) bootstrap of the rows and sqrt(d) of the columns
(both drawn as the configuration's ``assumed`` list states), splits by
variance reduction of the 0/1 label — the second-order gain with gradient
``-w y`` and hessian ``w`` — and its leaves hold the weighted mean label.
The score is the mean over the trees.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from .common import highest
from .treegrow import bin_codes, leaf_values, quantile_edges

SCORE = "probability"


def feature_subsets(d: int, trees: int, seed: int) -> np.ndarray:
    k = max(1, int(np.sqrt(d)))
    rng = np.random.default_rng(seed)
    return np.stack([np.sort(rng.choice(d, size=k, replace=False))
                     for _ in range(trees)])


@partial(jax.jit, static_argnames=("depth", "n_bins", "precision"))
def _forest(codes, y, weights, boot, subsets, reg_lambda, min_child_weight,
            depth: int, n_bins: int, precision: str):
    def one_tree(total, tree):
        boot_t, cols = tree
        w = weights * boot_t[None, :]
        vals = leaf_values(jnp.take(codes, cols, axis=1), -w * y[None, :], w,
                           depth, n_bins, reg_lambda, 0.0, min_child_weight,
                           1.0, precision)
        return total + vals, None

    total, _ = jax.lax.scan(one_tree, jnp.zeros(weights.shape, jnp.float32),
                            (boot, subsets))
    return total / boot.shape[0]


@highest
def fit_scores(x, y, weights, grids: List[Dict[str, Any]],
               params: Dict[str, Any], precision: str = "float32"):
    """(g, k, n) forest scores of every (grid point, weight row)."""
    n, d = x.shape
    n_bins, seed = int(params["n_bins"]), int(params["seed"])
    codes = bin_codes(x, jnp.asarray(quantile_edges(x, n_bins)))
    out = []
    for grid in grids:
        trees = int(grid["num_trees"])
        boot = jax.random.poisson(jax.random.PRNGKey(seed + 1),
                                  float(params["subsample"]),
                                  (trees, n)).astype(jnp.float32)
        out.append(_forest(
            codes, y, weights, boot,
            jnp.asarray(feature_subsets(d, trees, seed), jnp.int32),
            jnp.float32(params["reg_lambda"]),
            jnp.float32(params["min_child_weight"]),
            int(grid["max_depth"]), n_bins, precision))
    return jnp.stack(out)
