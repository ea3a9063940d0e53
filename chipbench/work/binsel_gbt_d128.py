"""The work one fit of the boosted-tree configuration NEEDS, from shapes alone.

Boosting by histograms is a streaming algorithm.  Per (round, level) the bin
codes are read once to accumulate the (node, feature, bin) histograms and
once to route the rows, at ONE BYTE a code (33 bins fit a byte, whatever
width the program stores); the fold lanes of a sweep grow their trees of one
round together, so they can SHARE both reads, as the linear model lets lanes
share the block.  Per (lane, round, level) each row's gradient and hessian
(two float32) are read and its node id is read and written (two int32).
Operations are the two accumulations a row and the two of its routing,
``4 n`` a lane-level — the one-hot matrix product the program builds its
histograms with is a way of doing it, not work the algorithm needs.  So the
group is bound by HBM bytes.  The winner's refit and the metric sort are not
counted as needed work, as in ``binsel_lr_d128``.
"""

from __future__ import annotations

from typing import Any, Dict


def work(config: Dict[str, Any], traffic: Dict[str, Any], width: int
         ) -> Dict[str, Dict[str, float]]:
    n, d = float(traffic["rows"]), float(width)
    folds = int(config["cv"]["folds"])
    out: Dict[str, Dict[str, float]] = {}
    for fam in config["families"]:
        flops = bytes_ = 0.0
        for grid in fam["grid"]:
            levels = float(grid["num_rounds"]) * float(grid["max_depth"])
            bytes_ += levels * (2.0 * n * d * 1.0            # shared by lanes
                                + folds * n * (2 * 4.0 + 2 * 4.0))
            flops += levels * folds * 4.0 * n
        out[fam["key"]] = {"flops": flops, "bytes": bytes_}
    return out
