"""Least time for the tree sweeps' needed work over their device time."""

from ..layerlib import roofline_percent


def read(ctx):
    return roofline_percent(ctx, ["rf", "gbt"], "tree_roofline")
