"""Placement-cache misses a window fit: what the program's ``rows`` and
``aux`` caches (``placement_stats()``) missed during the fit, through the
entry's record, mean over the window's fits.  0 = both families' placements
survive from fit to fit; a miss is an array stamped, padded and copied to the
device again.  Nothing to read where the entry records no such counter."""

NAMES = ("placement_rows_misses", "placement_aux_misses")


def read(ctx):
    moved = [[r["counters"].get(name) for name in NAMES]
             for r in ctx["records"]]
    if not moved or any(m is None for row in moved for m in row):
        return None
    return sum(sum(row) for row in moved) / len(moved)
