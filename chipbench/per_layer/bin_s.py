"""Host seconds per fit binning the table (``host.bin``, self time: the
quantile edges on the host and the dispatch of the device digitiser, or the
look-ups that found both cached).  Nothing to read on a program that marks
no such span."""

from ..spanlib import ACTIVITY, activity_seconds_per_fit, window_fits


def read(ctx):
    fits = window_fits(ctx)
    if fits is None or not any(
            s.path == ACTIVITY + "bin" for fit in fits for s in fit.spans):
        return None
    return activity_seconds_per_fit(ctx, ["bin"])
