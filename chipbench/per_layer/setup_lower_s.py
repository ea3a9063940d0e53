"""Host seconds of the warm-up fit tracing programs and lowering them to
MLIR (``host.trace`` + ``host.lower``, self time): paid by every first fit
of a process, whatever the persistent cache holds."""

from ..setuplib import self_seconds


def read(ctx):
    return self_seconds(ctx, ["trace", "lower"])
