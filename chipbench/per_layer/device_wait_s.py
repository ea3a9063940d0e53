"""Host seconds per fit blocked on the device (``host.device_wait``): the
sweep's gather, the refit's fetches, the evaluator's scalars."""

from ..spanlib import activity_seconds_per_fit


def read(ctx):
    return activity_seconds_per_fit(ctx, ["device_wait"])
