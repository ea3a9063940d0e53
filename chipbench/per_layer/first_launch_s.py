"""Host seconds from a fit's start to its first ``host.launch``: everything
the host does before the first device program of the fit."""

from ..spanlib import first_launch_per_fit


def read(ctx):
    return first_launch_per_fit(ctx)
