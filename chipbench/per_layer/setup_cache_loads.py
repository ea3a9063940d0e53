"""Programs the persistent cache answered during set-up."""


def read(ctx):
    return ctx["setup"].get("cache_loads")
