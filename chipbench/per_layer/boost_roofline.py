"""Least time for the boosted sweep's needed work (``work/binsel_gbt_d128``)
over its modules' device seconds; the ``notes`` line names the bound."""

from ..layerlib import roofline_percent


def read(ctx):
    return roofline_percent(ctx, ["gbt"], "boost_roofline")
