"""Host seconds per fit outside the sweep: prep, the winner's refit and its
train evaluation."""

from ..layerlib import span_seconds_per_call


def read(ctx):
    return span_seconds_per_call(
        ctx, lambda p: p in ("prep", "refit", "train_eval"))
