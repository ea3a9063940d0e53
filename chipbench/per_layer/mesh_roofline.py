"""Least time for one chip's share of the sweeps' needed work (the
configuration's work file divides by the mesh's chips) over the sweep
modules' device seconds, mean over the chips."""

from ..layerlib import roofline_percent


def read(ctx):
    return roofline_percent(ctx, ["lr"], "mesh_roofline")
