"""Device seconds per fit of the boosted sweep's modules, from the trace."""

from ..layerlib import family_device_seconds


def read(ctx):
    return family_device_seconds(ctx, ["gbt"])
