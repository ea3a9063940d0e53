"""Walks of the bin one-hot a fit makes: the ``binoh_walks`` count (rounds x
levels of one boosting program) summed over every ``host.launch`` span of a
fit that carries it — each grid point's sweep and the winner's refit — mean
over the window's fits.  Nothing to read where no launch carries the count
(a program from before the count)."""

from ..spanlib import ACTIVITY, window_fits


def read(ctx):
    fits = window_fits(ctx)
    if fits is None:
        return None
    per_fit = []
    for fit in fits:
        counted = [s.counts["binoh_walks"] for s in fit.spans
                   if s.path == ACTIVITY + "launch" and s.counts
                   and "binoh_walks" in s.counts]
        if not counted:
            return None
        per_fit.append(sum(counted))
    return sum(per_fit) / len(per_fit)
