"""Set-up seen from inside the program: the warm-up fit's profile.

An entry's set-up ends with one warm-up fit, and the program keeps that
fit's recorder like any other (``recent_fit_profiles()``), so it is the kept
profile just before the window's: ``ring[-(len(records) + 1)]``, under
``spanlib.window_fits``' own pairing check and the harness's own mark of
set-up's end (``ctx["setup_seconds"]``): the warm-up fit ended before it and
the window's first fit began after it.  The harness hands the readers no mark
of set-up's BEGINNING (``setup_reached_s.table`` is put into the result after
they ran), so of an entry that fitted twice in set-up the last fit would be
read.  In it the program's compile
probe has left what a first fit of a process pays and a window fit does not:
``host.trace``, ``host.lower``, ``host.cache_load`` and
``host.backend_compile`` spans, each with the ``label`` of the program that
was being launched, beside the placement and binning spans of a table's
first fit.  A program without ``package_import_seconds`` has no such spans
(the mark and the spans arrived together): every reader then returns None.
"""

from __future__ import annotations

import time
from collections import Counter
from typing import Any, Dict, Iterable, List, Optional, Tuple

from . import spanlib
from .reduce import union_seconds

NOTE = "setup_fit"
#: seconds the two clocks may differ by (/proc counts in hundredths)
CLOCK_SLACK = 0.05


def import_seconds() -> Optional[float]:
    """Seconds the program's package took to import, or None."""
    try:
        from transmogrifai_tpu.perf.timers import package_import_seconds
    except ImportError:         # a program without the mark
        return None
    return package_import_seconds()


def setup_end(ctx: Dict[str, Any]) -> float:
    """``time.perf_counter`` at the harness's mark of set-up's end, which is
    the process's age there."""
    from .run import process_age_s

    return time.perf_counter() - (process_age_s() - ctx["setup_seconds"])


def _fits(ctx: Dict[str, Any]) -> Optional[Tuple[Any, List[Any]]]:
    """(the warm-up fit's profile, the window's), or None where the ring no
    longer holds the warm-up fit, the program has no mark, the window's
    pairing is off, or set-up's end does not lie between the two."""
    if import_seconds() is None:
        return None
    from transmogrifai_tpu.perf.timers import recent_fit_profiles

    window = spanlib.window_fits(ctx)
    ring = recent_fit_profiles()
    before = len(ctx["records"]) + 1
    if window is None or len(ring) < before:
        return None
    fit, ended = ring[-before], setup_end(ctx)
    if fit.end > ended + CLOCK_SLACK or window[0].start < ended - CLOCK_SLACK:
        return None
    return fit, window


def _by_label(fits: Iterable[Any]) -> Dict[str, Dict[str, Any]]:
    """The fits' ``compile_table`` rows, summed by label."""
    out: Dict[str, Dict[str, Any]] = {}
    for fit in fits:
        for label, row in fit.compile_table().items():
            into = out.setdefault(label, dict.fromkeys(row, 0))
            for key, value in row.items():
                into[key] += value
    return out


def _unlabelled(fit: Any) -> Dict[str, int]:
    """{jax's name of the function: requests} of the loads and compilations
    that no ``host.launch`` and no compile phase was open for: eager
    operations between the fit's programs."""
    closing = (spanlib.ACTIVITY + "cache_load",
               spanlib.ACTIVITY + "backend_compile")
    return dict(Counter(
        str(s.counts.get("fun")) for s in fit.spans
        if s.path in closing and s.counts["label"] == "unlabelled"))


def nested(spans: Iterable[Tuple[str, float, float]]
            ) -> List[Tuple[str, float, float]]:
    """The spans, with one heard of late put back round what it holds.  The
    compile probe hears of an interval once it is over and dates it back
    from there; on a busy host it hears late, and an outer trace can land
    after the start of its inner one.  Ends are in order whatever the host
    does (the inner event arrives first), so a span that starts in another
    and outlasts it is that one's outer: it starts where that one does.
    Left as it was, what sticks out would count twice, in the span's own
    time and in the self time of the launch round both."""
    out: List[Tuple[str, float, float]] = []
    open_: List[Tuple[float, float]] = []       # (start, end), innermost last
    for name, start, secs in sorted(spans, key=lambda s: (s[1], -s[2])):
        end = start + secs
        while open_ and start >= open_[-1][1]:
            open_.pop()
        inside = []
        while open_ and end > open_[-1][1]:
            inside.append(open_.pop())
        if inside:
            start = inside[-1][0]
        out.append((name, start, end - start))
        open_ += [(start, end)] + inside[::-1]
    return out


def table(ctx: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """What the metrics sum over, kept in the run's notes: the warm-up
    fit's seconds, the self seconds of every ``host.*`` activity in it, the
    seconds no activity covers, the compile probe's spans by label, the
    unlabelled requests by function — and by label what the probe heard in
    the WINDOW's fits, where a load or a compile is a fault and a trace or a
    lowering a callable that jit meets anew every fit."""
    if NOTE in ctx["notes"]:
        return ctx["notes"][NOTE]
    fits = _fits(ctx)
    if fits is None:
        return None
    fit, window = fits
    spans = nested((s.path, s.start, s.seconds) for s in fit.spans
                    if s.path.startswith(spanlib.ACTIVITY))
    seconds = fit.end - fit.start
    covered = union_seconds((max(start, fit.start), min(start + secs, fit.end))
                            for _, start, secs in spans)
    ctx["notes"][NOTE] = {
        "fit_s": seconds,
        "self_s": {path[len(spanlib.ACTIVITY):]: secs for path, secs
                   in sorted(spanlib.innermost_seconds(
                       spans, fit.start, fit.end).items())},
        "unspanned_s": seconds - covered,
        "spans": len(fit.spans),
        "by_label": _by_label([fit]),
        "unlabelled": _unlabelled(fit),
        "window_by_label": _by_label(window),
    }
    return ctx["notes"][NOTE]


def self_seconds(ctx: Dict[str, Any], names: Iterable[str]
                 ) -> Optional[float]:
    """Self seconds of ``host.<name>``, for the ``names`` given, in the
    warm-up fit; 0.0 where it did none of them."""
    found = table(ctx)
    if found is None:
        return None
    return sum(found["self_s"].get(name, 0.0) for name in names)
