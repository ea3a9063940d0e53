"""Host seconds from a fit's start until every family's sweep is in the
device's queue: the end of the last ``host.launch`` made under a
``validate.cv.dispatch.<Family>`` phase less the fit's start (whole spans, as
``first_launch_s`` reads them), mean over the window's fits.  With one family
it is that family's dispatch; with several, the later families' host work
(binning, keys, launches) runs while the device works on the first one's
programs.  Nothing to read where no launch says which phase it was made in."""

from ..spanlib import ACTIVITY, window_fits

DISPATCH = "validate.cv.dispatch."


def read(ctx):
    fits = window_fits(ctx)
    if fits is None:
        return None
    leads = []
    for fit in fits:
        ends = [s.start + s.seconds for s in fit.spans
                if s.path == ACTIVITY + "launch"
                and (s.parent or "").startswith(DISPATCH)]
        if not ends:
            return None
        leads.append(max(ends) - fit.start)
    return sum(leads) / len(leads)
