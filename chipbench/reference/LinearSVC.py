"""Plain reference for the linear SVM family.

Squared hinge, L2 with the intercept unpenalised, columns standardised with
the fit's own weights.  The configuration states a fixed number of
momentum-descent steps (velocity 0.9, step 1/L with L = 2 sum w x^2 / sum w
+ lambda over the standardised block with its ones column), so the
reference takes the same steps: a fixed-step method is only comparable step
for step.  The score is the margin.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, List

import jax
import jax.numpy as jnp

from .common import highest, quantizer, standardize

SCORE = "margin"


@partial(jax.jit, static_argnames=("steps", "precision"))
def _descent(x, y, w, regs, steps: int, precision: str):
    """One weight row, the grid's lanes together: regs (L,) -> margins (L, n)."""
    q = quantizer(precision)
    xs = standardize(x, w)
    xq = q(xs)
    d1 = xs.shape[1]
    y_pm = jnp.where(y > 0.5, 1.0, -1.0)
    sw = jnp.maximum(w.sum(), 1e-12)
    mask = jnp.ones(d1).at[-1].set(0.0)
    lip = 2.0 * (w[:, None] * xs * xs).sum() / sw + regs          # (L,)
    lr = 1.0 / jnp.maximum(lip, 1e-6)

    def step(_, state):
        beta, vel = state                                         # (L, d1)
        z = xq @ q(beta.T)                                        # (n, L)
        active = jnp.maximum(1.0 - y_pm[:, None] * z, 0.0)
        g = ((xq.T @ q(w[:, None] * (-2.0 * y_pm[:, None] * active))).T / sw
             + regs[:, None] * mask * beta)
        vel = 0.9 * vel - lr[:, None] * g
        return beta + vel, vel

    b0 = jnp.zeros((regs.shape[0], d1), jnp.float32)
    beta, _ = jax.lax.fori_loop(0, steps, step, (b0, b0))
    return (xq @ q(beta.T)).T


@highest
def fit_scores(x, y, weights, grids: List[Dict[str, Any]],
               params: Dict[str, Any], precision: str = "float32"):
    """(g, k, n) margins of every (grid point, weight row)."""
    regs = jnp.asarray([float(g["reg_param"]) for g in grids], jnp.float32)
    steps = int(params["max_iter"])
    return jnp.stack([_descent(x, y, weights[f], regs, steps, precision)
                      for f in range(weights.shape[0])], axis=1)
