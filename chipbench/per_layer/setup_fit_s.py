"""Seconds of the warm-up fit that ends set-up, start to end."""

from ..setuplib import table


def read(ctx):
    found = table(ctx)
    return None if found is None else found["fit_s"]
