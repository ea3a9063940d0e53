"""Device seconds per fit, mean over the chips, of the eval program alone
(the configuration's ``eval_modules``): under a mesh it gathers every
fold-model's scores onto every chip and each chip sorts them all."""

from ..layerlib import family_device_seconds


def read(ctx):
    # the shared arithmetic, over each family's eval modules in the place of
    # its sweep modules
    families = [{**fam, "modules": fam.get("eval_modules", [])}
                for fam in ctx["config"]["families"]]
    return family_device_seconds(
        {**ctx, "config": {**ctx["config"], "families": families}},
        [fam["key"] for fam in families])
