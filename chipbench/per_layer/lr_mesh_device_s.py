"""Device seconds per fit of the family's sweep modules under the mesh, from
the trace: the mean over the mesh's chips (``reduce.py`` averages)."""

from ..layerlib import family_device_seconds


def read(ctx):
    return family_device_seconds(ctx, ["lr"])
