"""Device seconds per fit of the grid sweep's modules (every family's
``modules``: one boosting program a grid point, all points), from the
trace."""

from ..layerlib import family_device_seconds


def read(ctx):
    return family_device_seconds(
        ctx, [fam["key"] for fam in ctx["config"]["families"]])
