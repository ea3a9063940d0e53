"""Device seconds per fit of the winner's refit alone (the families'
``refit_modules``): one boosted lane over every row, at the rounds and depth
of the point the selector chose.  ``boost_refit_device_s``'s arithmetic under
a name of this cell's own: that metric's list of cells cannot be widened
here."""

from .boost_refit_device_s import read  # noqa: F401 — the harness calls it
