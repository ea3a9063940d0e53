"""Least time for both families' needed work (the configuration's work file:
each family's accepted model) over the device seconds of both families'
sweep modules; the ``notes`` line names the bound of each group."""

from ..layerlib import roofline_percent


def read(ctx):
    return roofline_percent(
        ctx, [fam["key"] for fam in ctx["config"]["families"]],
        "mix_roofline")
