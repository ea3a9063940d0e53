"""Device seconds per fit of the boosted family's sweep modules where the
linear family's sweep shares the fit, from the trace."""

from ..layerlib import family_device_seconds


def read(ctx):
    return family_device_seconds(ctx, ["gbt"])
