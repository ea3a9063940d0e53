"""The work ONE CHIP needs for one fit of the row-sharded configuration: the
whole fit's needed work exactly as ``binsel_lr_d128`` reckons it (the same
formulas at the cell's rows; the sort and the refit are not needed work there
and are not here), divided by the chips of the configuration's ``mesh``.
Rows are split evenly, and every operation and byte counted is per row, so a
chip's share is the whole over the chips; the all-reduced (d, d) statistics
are under 1e-5 of it and left out.  The peaks it is held against are one
chip's (``peaks.json``)."""

from __future__ import annotations

import math
from typing import Any, Dict

from . import binsel_lr_d128


def work(config: Dict[str, Any], traffic: Dict[str, Any], width: int
         ) -> Dict[str, Dict[str, float]]:
    chips = math.prod(int(size) for size in config["mesh"])
    whole = binsel_lr_d128.work(config, traffic, width)
    return {key: {"flops": w["flops"] / chips, "bytes": w["bytes"] / chips}
            for key, w in whole.items()}
