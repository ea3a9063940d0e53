"""Host seconds per fit letting go of the fold weights and the pending
sweeps once the choice is made (``host.release``, self time).  Nothing to
read on a program that marks no such span."""

from ..spanlib import ACTIVITY, activity_seconds_per_fit, window_fits


def read(ctx):
    fits = window_fits(ctx)
    if fits is None or not any(
            s.path == ACTIVITY + "release" for fit in fits for s in fit.spans):
        return None
    return activity_seconds_per_fit(ctx, ["release"])
