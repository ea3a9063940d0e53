"""The post-prep table: a data file of parameters -> the cell's table.

A training job's traffic is its table.  ``generate`` makes the block a
``transmogrify`` + ``sanity_check`` pass yields (copied from
``chip_smoke.make_table``'s generating model, emitted directly in its
post-prep form): every numeric column mean-imputed and followed by its 0/1
null indicator, then one one-hot block of levels + null per picklist.  The
label is Bernoulli of a logistic model with geometrically decaying
coefficients, so a handful of columns carry most of the signal: ``y`` holds
0.0 and 1.0.  Same seed, same table; every seed gives the same sizes.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict

import numpy as np

from ..traffic import Table


def width(params: Dict[str, Any]) -> int:
    return 2 * int(params["numeric"]) + sum(
        int(k) + 1 for k in params["picklists"])


#: rows per block: each block has its own random stream, so the table is the
#: same whatever the number of threads that fill it
BLOCK_ROWS = 1 << 18
THREADS = 8


def generate(params: Dict[str, Any], seed: int) -> Table:
    rows, n_real = int(params["rows"]), int(params["numeric"])
    null_cut = int(float(params["null_rate"]) * 65536)
    levels = [int(k) for k in params["picklists"]]
    beta = (float(params["beta_scale"])
            * float(params["beta_decay"]) ** np.arange(n_real))
    beta = (beta * np.where(np.arange(n_real) % 2 == 0, 1.0, -1.0)
            ).astype(np.float32)
    effects = [float(params["picklist_effect"]) * np.linspace(-1.0, 1.0, k)
               for k in levels]
    # the block is allocated once and filled in place, a block of rows to a
    # thread: fresh gigabyte-sized temporaries cost seconds in page faults
    block = np.zeros((rows, width(params)), np.float32)
    y = np.empty(rows, np.float64)
    starts = range(0, rows, BLOCK_ROWS)

    def fill(b: int):
        """Rows of block ``b`` but for the imputation, which needs every
        block's column sums; returns those and the present counts."""
        r0 = b * BLOCK_ROWS
        part = block[r0:r0 + BLOCK_ROWS]
        m = part.shape[0]
        rng = np.random.default_rng([int(seed), b])
        pairs = part[:, :2 * n_real].reshape(m, n_real, 2)
        x = rng.standard_normal((m, n_real), dtype=np.float32)
        missing = rng.integers(0, 65536, (m, n_real),
                               dtype=np.uint16) < null_cut
        # a missing value contributes nothing to the label, so mean-fill is
        # the right imputation and the null indicator carries no signal
        np.copyto(x, np.float32(0.0), where=missing)
        logit = (x @ beta).astype(np.float64)
        pairs[:, :, 0] = x
        pairs[:, :, 1] = missing
        at = 2 * n_real
        for n_levels, effect in zip(levels, effects):
            codes = rng.integers(0, n_levels, m)
            absent = rng.integers(0, 65536, m, dtype=np.uint16) < null_cut
            logit += np.where(absent, 0.0, effect[codes])
            part[np.arange(m), at + np.where(absent, n_levels, codes)] = 1.0
            at += n_levels + 1
        y[r0:r0 + m] = rng.random(m) < 1.0 / (1.0 + np.exp(-logit))
        return x.sum(axis=0, dtype=np.float64), m - missing.sum(axis=0)

    def impute(b: int, mean: np.ndarray) -> None:
        part = block[b * BLOCK_ROWS:(b + 1) * BLOCK_ROWS]
        pairs = part[:, :2 * n_real].reshape(part.shape[0], n_real, 2)
        # a missing value holds 0 and its indicator 1
        pairs[:, :, 0] += pairs[:, :, 1] * mean

    with ThreadPoolExecutor(max_workers=THREADS) as pool:
        sums = list(pool.map(fill, range(len(starts))))
        present = np.maximum(sum(p for _, p in sums), 1)
        mean = (sum(s for s, _ in sums) / present).astype(np.float32)
        list(pool.map(lambda b: impute(b, mean), range(len(starts))))
    return Table(block, y)
