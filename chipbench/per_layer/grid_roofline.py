"""Least time for the needed work of the whole grid (the configuration's
work file: each depth's lanes share the codes' two reads a level) over the
device seconds of the sweep's modules; the ``notes`` line names the bound."""

from ..layerlib import roofline_percent


def read(ctx):
    return roofline_percent(
        ctx, [fam["key"] for fam in ctx["config"]["families"]],
        "grid_roofline")
