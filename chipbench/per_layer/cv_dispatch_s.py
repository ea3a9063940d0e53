"""Host seconds per fit spent dispatching the families' sweeps."""

from ..layerlib import span_seconds_per_call


def read(ctx):
    return span_seconds_per_call(
        ctx, lambda p: p.startswith("validate.cv.dispatch."))
