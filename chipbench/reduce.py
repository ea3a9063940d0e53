"""Reduction from a profiler trace (``.xplane.pb``) to the numbers the
benchmark reports: device busy time, per-module and per-op device seconds,
and the longest idle gaps.

Read with ``jax.profiler.ProfileData`` alone.  On a TPU every chip is one
plane ``/device:TPU:<i>``; its line ``XLA Modules`` holds one event per
executed program (named ``<module>(<fingerprint>)``) and ``XLA Ops`` one
event per device operation inside them.  Busy time is the union of the op
intervals (the module intervals where a plane has no op line); the window is
the host span named :data:`WINDOW_SPAN` that the harness wraps round the
traced work, else the extent of the device events.  Nothing here knows a
program, a family or a metric by name.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

#: the ``jax.profiler.TraceAnnotation`` the harness puts round the traced work
WINDOW_SPAN = "chipbench_window"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"
TOP = 10
#: an op is named by its whole HLO line; the name, result shape and first
#: operands are enough to find it again
OP_NAME_CHARS = 200

Interval = Tuple[float, float]


class NoDevicePlane(ValueError):
    """The trace holds no chip's plane (a CPU run)."""


def find_xplane(trace_dir: str) -> str:
    """The newest ``.xplane.pb`` under a ``jax.profiler`` trace directory."""
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(found, key=os.path.getmtime)


def module_name(event_name: str) -> str:
    """``jit__irls_sweep(123456)`` -> ``jit__irls_sweep``."""
    return re.sub(r"\(\d+\)$", "", event_name).strip()


def union_seconds(intervals: Iterable[Interval]) -> float:
    """Length of the union of [start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals: Sequence[Tuple[float, float, str]], window: Interval
         ) -> List[Tuple[str, float]]:
    """Idle gaps inside ``window`` between named busy intervals, each labelled
    by the work before and after it, longest first."""
    out: List[Tuple[str, float]] = []
    edge, before = window[0], "window_start"
    for s, e, name in sorted(intervals):
        if s > edge:
            out.append((f"{before} -> {name}", s - edge))
        if e > edge:
            edge, before = e, name
    if window[1] > edge:
        out.append((f"{before} -> window_end", window[1] - edge))
    return sorted(out, key=lambda g: -g[1])


def _clip(s: float, e: float, window: Optional[Interval]
          ) -> Optional[Interval]:
    if window is None:
        return (s, e)
    s, e = max(s, window[0]), min(e, window[1])
    return (s, e) if e > s else None


def reduce_planes(planes: Dict[str, Dict[str, List[Tuple[str, float, float]]]]
                  ) -> Dict[str, Any]:
    """``{plane: {line: [(event name, start_s, duration_s), ...]}}`` -> the
    reduction.  Split from the file reading so a test can feed hand-made
    events."""
    window = None
    for name, lines in planes.items():
        if DEVICE_PLANE.match(name):
            continue
        for events in lines.values():
            for ev, s, d in events:
                if ev == WINDOW_SPAN:
                    window = (s, s + d)
    devices = {n: ls for n, ls in planes.items() if DEVICE_PLANE.match(n)}
    if not devices:
        raise NoDevicePlane("the trace holds no /device:TPU:<i> plane")
    if window is None:
        spans = [(s, s + d) for ls in devices.values()
                 for evs in ls.values() for _, s, d in evs]
        if not spans:
            raise ValueError("the trace holds no device event")
        window = (min(s for s, _ in spans), max(e for _, e in spans))
    busy: List[float] = []
    modules: Dict[str, float] = {}
    module_runs: Dict[str, int] = {}
    ops: Dict[str, float] = {}
    first_gaps: List[Tuple[str, float]] = []
    for i, (_, lines) in enumerate(sorted(devices.items())):
        mods = []
        for ev, s, d in lines.get(MODULE_LINE, []):
            c = _clip(s, s + d, window)
            if c is not None:
                name = module_name(ev)
                mods.append((c[0], c[1], name))
                modules[name] = modules.get(name, 0.0) + (c[1] - c[0])
                module_runs[name] = module_runs.get(name, 0) + 1
        op_iv = []
        for ev, s, d in lines.get(OP_LINE, []):
            c = _clip(s, s + d, window)
            if c is not None:
                op_iv.append(c)
                ops[ev] = ops.get(ev, 0.0) + (c[1] - c[0])
        busy.append(union_seconds(op_iv or [(s, e) for s, e, _ in mods]))
        if i == 0:
            first_gaps = gaps(mods, window)
    n = len(devices)
    return {
        "window_s": window[1] - window[0],
        "busy_s": sum(busy) / n,
        "devices": n,
        # per-module and per-op seconds are averaged over the chips, like busy
        "modules": {k: v / n for k, v in modules.items()},
        "module_runs": {k: v // n for k, v in module_runs.items()},
        "device_ops": [[k[:OP_NAME_CHARS], v / n] for k, v in
                       sorted(ops.items(), key=lambda kv: -kv[1])[:TOP]],
        "idle_gaps": [[k, v] for k, v in first_gaps[:TOP]],
    }


def read_planes(path: str
                ) -> Dict[str, Dict[str, List[Tuple[str, float, float]]]]:
    """The device planes' module and op lines and every host line that holds
    the window span, as plain tuples in seconds."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes: Dict[str, Dict[str, List[Tuple[str, float, float]]]] = {}
    for plane in data.planes:
        is_device = bool(DEVICE_PLANE.match(plane.name))
        lines: Dict[str, List[Tuple[str, float, float]]] = {}
        for line in plane.lines:
            if is_device and line.name not in (MODULE_LINE, OP_LINE):
                continue
            events = [(ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9)
                      for ev in line.events
                      if is_device or ev.name == WINDOW_SPAN]
            if events:
                lines.setdefault(line.name, []).extend(events)
        if lines:
            planes[plane.name] = lines
    return planes


def reduce_file(path: str) -> Dict[str, Any]:
    return reduce_planes(read_planes(path))


def describe(path: str, limit: int = 12) -> str:
    """Planes, lines and first event names of a trace, for a look by hand."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        out.append(f"PLANE {plane.name}")
        for line in plane.lines:
            names: Dict[str, int] = {}
            for ev in line.events:
                names[ev.name] = names.get(ev.name, 0) + 1
            out.append(f"  LINE {line.name}: {sum(names.values())} events, "
                       f"{len(names)} names")
            for name, count in sorted(names.items(),
                                      key=lambda kv: -kv[1])[:limit]:
                out.append(f"    {count:6d} x {name[:160]}")
    return "\n".join(out)
