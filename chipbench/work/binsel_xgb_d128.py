"""The work one fit of the boosted-tree GRID needs, from shapes alone.

``binsel_gbt_d128``'s reckoning, summed over the grid's points at their own
depths.  Boosting by histograms streams: per (round, level) the bin codes
are read once to accumulate the (node, feature, bin) histograms and once to
route the rows, at ONE BYTE a code; per (lane, round, level) each row's
gradient and hessian (two float32) are read and its node id is read and
written (two int32); operations are the two accumulations a row and the two
of its routing, ``4 n`` a lane-level.  So the group is bound by HBM bytes.

What lanes can share.  The lanes that grow trees of ONE DEPTH over the same
rounds advance in lock step — round r, level l, for all of them — whatever
their learning rate, fold or regularisation: folded into one program they
share both reads of the codes at every level, as the fold lanes of one grid
point do today.  Lanes of different depths do not: a depth-3 lane starts
its next round, on new gradients, while a depth-6 lane is half way down its
tree, and the two run different loop nests in different programs.  Sharing a
read between them would take a scheduler that interleaves two programs'
levels, which "fold the lanes" is not; so each group of one (rounds, depth)
pays its own two reads of the codes a level, and the grid pays the sum.
The winner's refit and the metric sort are not counted as needed work, as
in ``binsel_lr_d128``.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple


def work(config: Dict[str, Any], traffic: Dict[str, Any], width: int
         ) -> Dict[str, Dict[str, float]]:
    n, d = float(traffic["rows"]), float(width)
    folds = int(config["cv"]["folds"])
    out: Dict[str, Dict[str, float]] = {}
    for fam in config["families"]:
        lanes: Dict[Tuple[int, int], int] = {}      # (rounds, depth) -> lanes
        for grid in fam["grid"]:
            key = (int(grid["num_rounds"]), int(grid["max_depth"]))
            lanes[key] = lanes.get(key, 0) + folds
        flops = bytes_ = 0.0
        for (rounds, depth), L in lanes.items():
            levels = float(rounds * depth)
            bytes_ += levels * (2.0 * n * d * 1.0       # shared by the group
                                + L * n * (2 * 4.0 + 2 * 4.0))
            flops += levels * L * 4.0 * n
        out[fam["key"]] = {"flops": flops, "bytes": bytes_}
    return out
