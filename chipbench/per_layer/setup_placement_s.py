"""Host seconds of the warm-up fit placing arrays, the table among them:
the padded copies, the content stamps and the host side of the transfers
(``host.pad`` + ``host.stamp`` + ``host.h2d``, self time)."""

from ..setuplib import self_seconds


def read(ctx):
    return self_seconds(ctx, ["pad", "stamp", "h2d"])
