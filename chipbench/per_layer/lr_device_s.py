"""Device seconds per fit of this family's sweep modules, from the trace."""

from ..layerlib import family_device_seconds


def read(ctx):
    return family_device_seconds(ctx, ["lr"])
