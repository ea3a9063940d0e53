"""Gigabytes of the int8 bin one-hot the sweep's program reads at every
level: the ``binoh_bytes`` count on the ``host.launch`` span of a family's
``cv_program``, the largest of a fit's launches, mean over the window's
fits.  0.0 where the program declined the one-hot (the unchunked path of a
small table, or a table over the cap: the fallback that rebuilds it every
pass); nothing to read where no launch carries the count."""

from ..spanlib import ACTIVITY, window_fits


def read(ctx):
    fits = window_fits(ctx)
    if fits is None:
        return None
    per_fit = []
    for fit in fits:
        counted = [s.counts["binoh_bytes"] for s in fit.spans
                   if s.path == ACTIVITY + "launch" and s.counts
                   and str(s.counts.get("label", "")).endswith("/cv_program")
                   and "binoh_bytes" in s.counts]
        if not counted:
            return None
        per_fit.append(max(counted))
    return sum(per_fit) / len(per_fit) / 1e9
