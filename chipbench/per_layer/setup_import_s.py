"""Seconds the program's package took to import in this process
(``perf.timers.package_import_seconds()``: first import to last).  The
benchmark's command imports jax before the package (``run.find_chips``), so
jax's own import, about 3 s, is outside the reading; a caller that imports
the package first has it inside."""

from ..setuplib import import_seconds


def read(ctx):
    return import_seconds()
