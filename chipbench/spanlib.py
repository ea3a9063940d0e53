"""Whole spans of the window's fits, for the per-layer readers that need
more than the totals the entry hands the harness.

The program keeps its last finished fit profiles in memory
(``transmogrifai_tpu.perf.timers.recent_fit_profiles()``: each a recorder
with ``start``, ``end`` and ``spans``, every span a ``path``, ``start`` and
``seconds`` on ``time.perf_counter``).  The window's fits are the last
``len(ctx["records"])`` of them, in order.  A profile longer than its
record's ``seconds``, or shorter than nine tenths of it, means the pairing is
off, and every reader here then returns None, as it does on a program that
keeps no such ring.

The lower layers mark what the host does with flat spans named
``host.<activity>``; a layer's self time is its span's length less what the
spans inside it cover.
"""

from __future__ import annotations

from typing import Any, Dict, Iterable, List, Optional, Tuple

from .reduce import union_seconds

ACTIVITY = "host."


def window_fits(ctx: Dict[str, Any]) -> Optional[List[Any]]:
    """The window's fit profiles, one a record, or None."""
    try:
        from transmogrifai_tpu.perf.timers import recent_fit_profiles
    except ImportError:         # a program without the ring
        return None
    records = ctx["records"]
    ring = recent_fit_profiles()
    if not records or not ring:
        return None
    # a window of more fits than the ring holds reads its last ones
    records = records[-len(ring):]
    fits = ring[-len(records):]
    for fit, rec in zip(fits, records):
        seconds = fit.end - fit.start
        if seconds > rec["seconds"] or seconds < 0.9 * rec["seconds"]:
            return None
    return fits


def innermost_seconds(spans: Iterable[Tuple[str, float, float]],
                      lo: float = float("-inf"), hi: float = float("inf")
                      ) -> Dict[str, float]:
    """``[(name, start, seconds)]`` of one thread -> {name: seconds of
    [lo, hi) in which the span is the innermost one open}: each span's
    (clipped) length less the part its direct children, the spans its
    interval contains, cover.  Over the whole line that is self time."""
    out: Dict[str, float] = {}
    open_: List[Tuple[str, float]] = []          # (name, end), innermost last
    for name, start, seconds in sorted(spans, key=lambda s: (s[1], -s[2])):
        end = start + seconds
        while open_ and start >= open_[-1][1]:
            open_.pop()
        if open_:
            # clipped to the parent, should the clock have put its end later
            inside = max(0.0, min(end, open_[-1][1], hi) - max(start, lo))
            out[open_[-1][0]] -= inside
        out[name] = out.get(name, 0.0) + max(
            0.0, min(end, hi) - max(start, lo))
        open_.append((name, end))
    return out


def _activities(fit) -> List[Tuple[str, float, float]]:
    return [(s.path, s.start, s.seconds) for s in fit.spans
            if s.path.startswith(ACTIVITY)]


def activity_seconds_per_fit(ctx: Dict[str, Any], names: Iterable[str]
                             ) -> Optional[float]:
    """Mean over the window's fits of the self seconds of ``host.<name>``
    for the ``names`` given; 0.0 where a fit did none of them."""
    fits = window_fits(ctx)
    if fits is None:
        return None
    wanted = {ACTIVITY + n for n in names}
    total = sum(secs for fit in fits
                for path, secs in innermost_seconds(_activities(fit)).items()
                if path in wanted)
    return total / len(fits)


def first_launch_per_fit(ctx: Dict[str, Any]) -> Optional[float]:
    """Mean seconds from a fit's start to the start of its first
    ``host.launch``: what the host does before the first device program."""
    fits = window_fits(ctx)
    if fits is None:
        return None
    waits = []
    for fit in fits:
        launches = [s.start for s in fit.spans
                    if s.path == ACTIVITY + "launch"]
        if not launches:
            return None
        waits.append(min(launches) - fit.start)
    return sum(waits) / len(waits)


def unspanned_per_fit(ctx: Dict[str, Any]) -> Optional[float]:
    """Mean fit seconds that no ``host.*`` span covers."""
    fits = window_fits(ctx)
    if fits is None:
        return None
    bare = [(fit.end - fit.start) - union_seconds(
        (max(start, fit.start), min(start + secs, fit.end))
        for _, start, secs in _activities(fit)) for fit in fits]
    return sum(bare) / len(bare)
