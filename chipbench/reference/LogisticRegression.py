"""Plain reference for the logistic-regression family.

Spark's parametrisation: ``reg_param`` = lambda, ``elastic_net`` = alpha;
objective (1/sum w) sum w_i logloss_i + lambda alpha |b|_1
+ lambda (1 - alpha)/2 |b|^2, intercept unpenalised, columns standardised
over all rows.  Pure-L2 points: Newton steps (IRLS) from zero.  Points with
an L1 part: FISTA with the step from a 30-step power iteration, as many
steps as the configuration states — a fixed-step method is only comparable
step for step.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from .common import highest, quantizer, standardize

SCORE = "probability"


@partial(jax.jit, static_argnames=("steps", "precision"))
def _newton(xs, y, w, reg, steps: int, precision: str):
    q = quantizer(precision)
    d1 = xs.shape[1]
    sw = jnp.maximum(w.sum(), 1e-12)
    mask = jnp.ones(d1).at[-1].set(0.0)
    xq = q(xs)

    def step(_, beta):
        p = jax.nn.sigmoid(xq @ q(beta))
        g = xq.T @ q(w * (p - y)) / sw + reg * mask * beta
        s = jnp.maximum(w * p * (1.0 - p), 1e-10)
        h = (xq.T @ q(xs * s[:, None])) / sw + jnp.diag(reg * mask + 1e-8)
        return beta - jnp.linalg.solve(h, g)

    return jax.lax.fori_loop(0, steps, step, jnp.zeros(d1, jnp.float32))


@partial(jax.jit, static_argnames=("steps", "precision"))
def _fista(xs, y, ws, l1s, l2s, steps: int, precision: str):
    """Lanes together: ws (L, n), l1s/l2s (L,) -> betas (L, d+1)."""
    q = quantizer(precision)
    d1 = xs.shape[1]
    sw = jnp.maximum(ws.sum(axis=1), 1e-12)               # (L,)
    mask = jnp.ones(d1).at[-1].set(0.0)
    xq = q(xs)

    def quad(v):                                          # (L, d1)
        return (xq.T @ q(ws.T * (xq @ q(v.T)))).T / sw[:, None]

    def power(_, v):
        u = quad(v)
        return u / (jnp.linalg.norm(u, axis=1, keepdims=True) + 1e-12)

    v0 = jnp.ones((ws.shape[0], d1)) / jnp.sqrt(1.0 * d1)
    v = jax.lax.fori_loop(0, 30, power, v0)
    lmax = (v * quad(v)).sum(axis=1)
    step = 1.0 / (0.25 * lmax + l2s + 1e-12)              # (L,)

    def grad(b):
        p = jax.nn.sigmoid(xq @ q(b.T))                   # (n, L)
        return ((xq.T @ q(ws.T * (p - y[:, None]))).T / sw[:, None]
                + l2s[:, None] * mask * b)

    def soft(b, thr):
        return jnp.sign(b) * jnp.maximum(jnp.abs(b) - thr, 0.0)

    def one(carry, _):
        b, z, t = carry
        b_new = soft(z - step[:, None] * grad(z),
                     (step * l1s)[:, None] * mask)
        t_new = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * t * t))
        return (b_new, b_new + ((t - 1.0) / t_new) * (b_new - b), t_new), 0.0

    b0 = jnp.zeros((ws.shape[0], d1), jnp.float32)
    (b, _, _), _ = jax.lax.scan(one, (b0, b0, 1.0), None, length=steps)
    return b


@partial(jax.jit, static_argnames=("precision",))
def _scores(xs, betas, precision: str):
    q = quantizer(precision)
    return jax.nn.sigmoid(q(xs) @ q(betas.T)).T           # (L, n)


@highest
def fit_scores(x, y, weights, grids: List[Dict[str, Any]],
               params: Dict[str, Any], precision: str = "float32"):
    """Scores of every (grid point, weight row): x (n, d), y (n,), weights
    (k, n) device arrays -> (g, k, n) device array."""
    # under jit the column moments fuse: eagerly each would hold a copy of x
    xs = jax.jit(standardize)(x, jnp.ones(x.shape[0], jnp.float32))
    steps = int(params["max_iter"])
    k = weights.shape[0]
    betas = [None] * len(grids)
    l1l2 = [(float(g["reg_param"]) * float(g.get("elastic_net", 0.0)),
             float(g["reg_param"]) * (1.0 - float(g.get("elastic_net", 0.0))))
            for g in grids]
    for i, (l1, l2) in enumerate(l1l2):
        if l1 <= 0.0:
            betas[i] = jnp.stack([
                _newton(xs, y, weights[f], jnp.float32(l2), steps, precision)
                for f in range(k)])
    prox = [i for i, (l1, _) in enumerate(l1l2) if l1 > 0.0]
    if prox:
        lanes = _fista(
            xs, y, jnp.concatenate([weights] * len(prox), axis=0),
            jnp.asarray(np.repeat([l1l2[i][0] for i in prox], k), jnp.float32),
            jnp.asarray(np.repeat([l1l2[i][1] for i in prox], k), jnp.float32),
            max(10 * steps, 300), precision)
        for j, i in enumerate(prox):
            betas[i] = lanes[j * k:(j + 1) * k]
    return jnp.stack([_scores(xs, b, precision) for b in betas])
