"""Device seconds per fit of the winner's refit alone (the configuration's
``refit_modules``): one boosted lane over every row, at the rounds and depth
of the sweep's three."""

from ..layerlib import family_device_seconds


def read(ctx):
    # the shared arithmetic, over each family's refit modules in the place of
    # its sweep modules
    families = [{**fam, "modules": fam.get("refit_modules", [])}
                for fam in ctx["config"]["families"]]
    return family_device_seconds(
        {**ctx, "config": {**ctx["config"], "families": families}},
        [fam["key"] for fam in families])
