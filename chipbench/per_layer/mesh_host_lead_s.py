"""Host seconds from a fit's start to its first ``host.launch`` under the
mesh: ``first_launch_s``'s arithmetic under a name of this cell's own,
because that metric's list of cells cannot be widened here (PERF.md, Open
questions: a later benchmark PR folds the two)."""

from ..spanlib import first_launch_per_fit


def read(ctx):
    return first_launch_per_fit(ctx)
