"""Fold-models validated in the window over the whole window, from its start
to the end of its last call; a failed call's fold-models do not count."""


def read(ctx):
    done = sum(r["attempted"] - r["failed"] for r in ctx["records"])
    return done / ctx["window_s"]
