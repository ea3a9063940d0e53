"""Host seconds of the warm-up fit loading executables the persistent
cache answered (``host.cache_load``, self time)."""

from ..setuplib import self_seconds


def read(ctx):
    return self_seconds(ctx, ["cache_load"])
