"""Host seconds of the warm-up fit in real compilations
(``host.backend_compile``, self time); 0.0 on a warm cache."""

from ..setuplib import self_seconds


def read(ctx):
    return self_seconds(ctx, ["backend_compile"])
