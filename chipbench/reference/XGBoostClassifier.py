"""Plain reference for the XGBoost-style boosted trees under a GRID (binary
labels): ``GradientBoostedTreesClassifier``'s own boosting loop — logistic
loss, Newton boosting from the log-odds of the weighted positive rate,
``treegrow``'s trees, leaf values ``-eta G / (H + lambda)`` — with every
boosting dynamic (``eta``, ``reg_lambda``, ``gamma``, ``min_child_weight``)
read from the grid point first and ``params`` second, so a grid may sweep
them.

The points that differ in ``eta`` alone are boosted as lanes of ONE call of
that loop (``eta`` goes in as an (L, 1) column, one learning rate a lane; it
enters the leaf values and nothing else), sharing the bin one-hot each level
builds: two learning rates at one depth cost the reference what one does.
A lane sees no other lane.
"""

from __future__ import annotations

from typing import Any, Dict, List

import jax.numpy as jnp

from .common import highest
from .GradientBoostedTreesClassifier import _boost
from .treegrow import bin_codes, quantile_edges

SCORE = "probability"
DYNAMICS = ("eta", "reg_lambda", "gamma", "min_child_weight")


def dynamics(grid: Dict[str, Any], params: Dict[str, Any]) -> Dict[str, float]:
    """The boosting dynamics of one grid point: the grid's, else ``params``'."""
    return {k: float(grid[k] if k in grid else params[k]) for k in DYNAMICS}


@highest
def fit_scores(x, y, weights, grids: List[Dict[str, Any]],
               params: Dict[str, Any], precision: str = "float32"):
    """(g, k, n) boosted probabilities of every (grid point, weight row)."""
    n_bins = int(params["n_bins"])
    codes = bin_codes(x, jnp.asarray(quantile_edges(x, n_bins)))
    k = weights.shape[0]
    groups: Dict[Any, List[int]] = {}       # all but eta -> its grid points
    for g, grid in enumerate(grids):
        dyn = dynamics(grid, params)
        key = (int(grid["num_rounds"]), int(grid["max_depth"]),
               dyn["reg_lambda"], dyn["gamma"], dyn["min_child_weight"])
        groups.setdefault(key, []).append(g)
    out = [None] * len(grids)
    for (rounds, depth, reg_lambda, gamma, mcw), points in groups.items():
        eta = jnp.repeat(jnp.asarray(
            [dynamics(grids[g], params)["eta"] for g in points],
            jnp.float32), k)[:, None]
        scores = _boost(codes, y, jnp.tile(weights, (len(points), 1)), eta,
                        jnp.float32(reg_lambda), jnp.float32(gamma),
                        jnp.float32(mcw), rounds, depth, n_bins, precision)
        for i, g in enumerate(points):
            out[g] = scores[i * k:(i + 1) * k]
    return jnp.stack(out)
