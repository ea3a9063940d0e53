"""The work one fit of the tree configuration NEEDS, from shapes alone.

Histogram growth is a streaming algorithm: per tree and level, one pass over
the bin codes to accumulate the (node, feature, bin) histograms and one to
route the rows, at ONE BYTE a code (33 bins fit a byte, whatever width the
program stores), plus each row's gradient and hessian (two float32) and its
node id read and written (two int32).  A tree is one lane: a (fold, tree) of
the forest, a (fold, round) of the boosting.  Operations are the two
accumulations a row, nothing else — the one-hot matrix product that the
program uses to build histograms is a way of doing it, not work the
algorithm needs.  So every group is bound by HBM bytes.

The model reads all d columns for every tree.  An implementation that reads
only a forest tree's sqrt(d) columns would need less; a benchmark PR has to
tighten the model before such a program can read near 100%.
"""

from __future__ import annotations

from typing import Any, Dict


def work(config: Dict[str, Any], traffic: Dict[str, Any], width: int
         ) -> Dict[str, Dict[str, float]]:
    n, d = float(traffic["rows"]), float(width)
    folds = int(config["cv"]["folds"])
    per_lane_level = n * (2.0 * d * 1.0 + 2 * 4.0 + 2 * 4.0)
    out: Dict[str, Dict[str, float]] = {}
    for fam in config["families"]:
        lane_levels = 0.0
        for grid in fam["grid"]:
            trees = grid.get("num_trees", grid.get("num_rounds"))
            lane_levels += folds * float(trees) * float(grid["max_depth"])
        out[fam["key"]] = {"flops": lane_levels * 4.0 * n,
                           "bytes": lane_levels * per_lane_level}
    return out
