"""Seconds of a fit that no ``host.*`` span covers: what the activities do
not name yet."""

from ..spanlib import unspanned_per_fit


def read(ctx):
    return unspanned_per_fit(ctx)
