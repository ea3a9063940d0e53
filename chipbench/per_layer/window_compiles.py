"""Backend compiles inside the window; 0 expected."""


def read(ctx):
    return sum(r["counters"]["compiles"] for r in ctx["records"])
