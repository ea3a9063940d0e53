"""Least time for the linear sweeps' needed work over their device time."""

from ..layerlib import roofline_percent


def read(ctx):
    return roofline_percent(ctx, ["lr", "svc"], "linear_roofline")
