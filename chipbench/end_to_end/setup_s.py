"""Seconds from the start of the process to the start of the measured window."""


def read(ctx):
    return ctx["setup_seconds"]
