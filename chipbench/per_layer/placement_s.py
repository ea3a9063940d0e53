"""Host seconds per fit placing arrays: the padded copies, the content stamps
and the host side of the transfers (``host.pad`` + ``host.stamp`` +
``host.h2d``)."""

from ..spanlib import activity_seconds_per_fit


def read(ctx):
    return activity_seconds_per_fit(ctx, ["pad", "stamp", "h2d"])
