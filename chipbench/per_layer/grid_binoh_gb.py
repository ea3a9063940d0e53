"""Gigabytes of int8 bin one-hot a fit BUILDS for its grid: the largest
``binoh_bytes`` on the fit's boosting launches times the launches of a
``/bin_onehot`` program in the fit, mean over the window's fits.  One
one-hot whatever the number of grid points (every point's sweep and the
winner's refit read the same array) reads the bytes of one; a build a point
reads that many times over.  0.0 where the programs declined the one-hot
(nothing is built: the fallback that rebuilds it every pass); nothing to
read where no launch carries the count."""

from ..spanlib import ACTIVITY, window_fits


def read(ctx):
    fits = window_fits(ctx)
    if fits is None:
        return None
    per_fit = []
    for fit in fits:
        launches = [s.counts for s in fit.spans
                    if s.path == ACTIVITY + "launch" and s.counts]
        held = [c["binoh_bytes"] for c in launches if "binoh_bytes" in c]
        if not held:
            return None
        builds = sum(str(c.get("label", "")).endswith("/bin_onehot")
                     for c in launches)
        per_fit.append(max(held) * builds)
    return sum(per_fit) / len(per_fit) / 1e9
