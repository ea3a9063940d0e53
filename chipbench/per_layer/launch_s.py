"""Host seconds per fit building program keys and launching programs
(``host.program_key`` + ``host.launch``)."""

from ..spanlib import activity_seconds_per_fit


def read(ctx):
    return activity_seconds_per_fit(ctx, ["program_key", "launch"])
