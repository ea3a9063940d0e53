"""Idle gaps of the device put down to what the host was doing in them.

    python3 -m chipbench.hostspans <trace dir or .xplane.pb>

The program writes its spans into the profiler's trace
(``jax.profiler.TraceAnnotation`` with a ``span`` stat: ``phase`` for the
nested phases, ``activity`` for the flat ``host.<name>`` spans of the lower
layers), so one ``.xplane.pb`` holds them on the host planes, on the line of
the thread that ran the fit, beside the device planes' programs.  For every
gap between two device programs longer than :data:`MIN_GAP_S` this gives the
seconds of each activity and of each innermost phase that overlap it.

``reduce.py`` keeps only the harness's own window span of the host lines, so
the harness cannot call this yet; it is run by hand on a trace directory.
"""

from __future__ import annotations

import os
import sys
from typing import Any, Dict, List, Optional, Sequence, Tuple

from . import reduce as R
from .spanlib import ACTIVITY, innermost_seconds

#: shorter gaps are launch latency between queued programs, not host work
MIN_GAP_S = 0.010
SPAN_STAT = "span"

#: (name, start_s, seconds)
Event = Tuple[str, float, float]


def read_trace(path: str) -> Dict[str, Any]:
    """``.xplane.pb`` -> the program's spans by host line (``phases`` and
    ``activities``, told apart by their ``span`` stat), the first chip's
    program intervals, and the harness's window where the trace has one."""
    from jax.profiler import ProfileData

    out: Dict[str, Any] = {"phases": {}, "activities": {}, "modules": [],
                           "window": None}
    device_seen = False
    for plane in ProfileData.from_file(path).planes:
        if R.DEVICE_PLANE.match(plane.name):
            if device_seen:
                continue
            device_seen = True
            for line in plane.lines:
                if line.name == R.MODULE_LINE:
                    out["modules"] = [
                        (R.module_name(ev.name), ev.start_ns * 1e-9,
                         ev.duration_ns * 1e-9) for ev in line.events]
            continue
        for line in plane.lines:
            where = f"{plane.name}/{line.name}"
            for ev in line.events:
                if ev.name == R.WINDOW_SPAN:
                    out["window"] = (ev.start_ns * 1e-9,
                                     (ev.start_ns + ev.duration_ns) * 1e-9)
                    continue
                kind = dict(ev.stats).get(SPAN_STAT)
                if kind in ("phase", "activity"):
                    out["phases" if kind == "phase" else "activities"] \
                        .setdefault(where, []).append(
                            (ev.name, ev.start_ns * 1e-9,
                             ev.duration_ns * 1e-9))
    return out


def located_gaps(modules: Sequence[Event], window: Tuple[float, float],
                 min_gap: float = MIN_GAP_S
                 ) -> List[Tuple[str, float, float]]:
    """``[(label, start, end)]`` of the idle gaps inside ``window`` that are
    longer than ``min_gap``, longest first, labelled like ``reduce.gaps``."""
    out = []
    edge, before = window[0], "window_start"
    for name, start, seconds in sorted(modules, key=lambda m: m[1]):
        start, end = max(start, window[0]), min(start + seconds, window[1])
        if end <= start:
            continue
        if start - edge > min_gap:
            out.append((f"{before} -> {name}", edge, start))
        if end > edge:
            edge, before = end, name
    if window[1] - edge > min_gap:
        out.append((f"{before} -> window_end", edge, window[1]))
    return sorted(out, key=lambda g: g[1] - g[2])


def attribute(trace: Dict[str, Any], min_gap: float = MIN_GAP_S
              ) -> List[Dict[str, Any]]:
    """One row a gap: its label, start (seconds after the window's start),
    seconds, the seconds of each ``host.*`` activity and of each innermost
    phase inside it, and the share of the gap the activities name."""
    modules = trace["modules"]
    if not modules:
        raise R.NoDevicePlane("the trace holds no device program")
    window = trace["window"] or (
        min(s for _, s, _ in modules), max(s + d for _, s, d in modules))
    rows = []
    for label, lo, hi in located_gaps(modules, window, min_gap):
        acts: Dict[str, float] = {}
        phases: Dict[str, float] = {}
        for into, lines in ((acts, trace["activities"]),
                            (phases, trace["phases"])):
            for spans in lines.values():
                for name, secs in innermost_seconds(spans, lo, hi).items():
                    if secs > 0.0:
                        into[name] = into.get(name, 0.0) + secs
        rows.append({
            "gap": label, "start_s": lo - window[0], "seconds": hi - lo,
            "activities": dict(sorted(acts.items(), key=lambda kv: -kv[1])),
            "phases": dict(sorted(phases.items(), key=lambda kv: -kv[1])),
            "named_share": sum(acts.values()) / (hi - lo)})
    return rows


def table(rows: Sequence[Dict[str, Any]], top: int = 10) -> str:
    """The rows as a Markdown table, longest gap first."""
    names = sorted({n for r in rows[:top] for n in r["activities"]})
    head = ["gap", "at s", "s"] + [n[len(ACTIVITY):] for n in names] \
        + ["named", "innermost phase"]
    out = ["| " + " | ".join(head) + " |",
           "|" + "---|" * len(head)]
    for r in rows[:top]:
        phase = next(iter(r["phases"]), "-")
        cells = [r["gap"], f"{r['start_s']:.3f}", f"{r['seconds']:.3f}"] \
            + [f"{r['activities'].get(n, 0.0):.3f}" for n in names] \
            + [f"{100 * r['named_share']:.0f}%", phase]
        out.append("| " + " | ".join(cells) + " |")
    return "\n".join(out)


def main(argv: Optional[List[str]] = None) -> int:
    args = sys.argv[1:] if argv is None else argv
    if len(args) != 1:
        print(__doc__.split("\n\n")[1].strip(), file=sys.stderr)
        return 2
    path = args[0] if os.path.isfile(args[0]) else R.find_xplane(args[0])
    trace = read_trace(path)
    lines = sorted(set(trace["phases"]) | set(trace["activities"]))
    print(f"host lines with the program's spans: {lines}")
    print(table(attribute(trace)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
