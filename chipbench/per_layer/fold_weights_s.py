"""Host seconds per fit building the fold weight blocks (``host.fold_weights``)."""

from ..spanlib import activity_seconds_per_fit


def read(ctx):
    return activity_seconds_per_fit(ctx, ["fold_weights"])
