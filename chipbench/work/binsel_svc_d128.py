"""The linear work model serves the SVC configuration as it is."""

from .binsel_lr_d128 import work  # noqa: F401
