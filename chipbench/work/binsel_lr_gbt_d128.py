"""The work one fit of the two-family configuration NEEDS, from shapes alone:
each family's group is the accepted work model of that family where it is the
only one (``binsel_lr_d128``: the IRLS lanes bound by operations, the FISTA
lanes by the block's reads; ``binsel_gbt_d128``: the boosted lanes bound by
HBM bytes, the codes' two reads a level shared by the fold lanes), called on
this configuration cut to that family, at this cell's rows.  The families
share no needed work: one reads the float32 block, the other one-byte codes.
As in both accepted models the metric sorts and the winner's refit are not
counted — here the refit is the linear winner's, and the trees' never runs —
so each group reads against its lone cell's share like for like.
"""

from __future__ import annotations

from typing import Any, Dict

from . import binsel_gbt_d128, binsel_lr_d128

MODELS = {"lr": binsel_lr_d128, "gbt": binsel_gbt_d128}


def work(config: Dict[str, Any], traffic: Dict[str, Any], width: int
         ) -> Dict[str, Dict[str, float]]:
    out: Dict[str, Dict[str, float]] = {}
    for fam in config["families"]:
        alone = {**config, "families": [fam]}
        out.update(MODELS[fam["key"]].work(alone, traffic, width))
    return out
