"""The whole step's share of the chip's peak: least time for the work one fit
needs over the mean seconds of the window's fits (host clock around each whole
fit; the window itself also holds the profiler's stop in a traced run)."""

from ..layerlib import least_seconds


def read(ctx):
    least = least_seconds(ctx)
    if least is None or not ctx["records"]:
        return None
    per_fit = sum(r["seconds"] for r in ctx["records"]) / len(ctx["records"])
    ctx["notes"]["fit_mfu"] = {"least_s": least[0], "fit_s": per_fit,
                               "bound": least[1]}
    return 100.0 * least[0] / per_fit
