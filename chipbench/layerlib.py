"""Arithmetic the per-layer readers share: spans per call, a family's device
seconds from the reduced trace, and least time against the table of peaks.
A reader that has nothing to read gets None from here and returns it."""

from __future__ import annotations

import importlib
from typing import Any, Dict, Iterable, Optional, Tuple

from . import traffic


def span_seconds_per_call(ctx: Dict[str, Any], match) -> Optional[float]:
    """Mean over the window's calls of the summed spans whose dotted path
    ``match`` accepts."""
    records = ctx["records"]
    if not records:
        return None
    total = sum(secs for r in records for path, secs in r["spans"].items()
                if match(path))
    return total / len(records)


def family_device_seconds(ctx: Dict[str, Any], keys: Iterable[str]
                          ) -> Optional[float]:
    """Device seconds per traced call of the XLA modules the configuration
    lists for the families ``keys``."""
    trace = ctx["trace"]
    if trace is None or not ctx["traced_calls"]:
        return None
    wanted = {m for fam in ctx["config"]["families"] if fam["key"] in keys
              for m in fam["modules"]}
    found = [secs for name, secs in trace["modules"].items() if name in wanted]
    if not found:
        return None
    return sum(found) / ctx["traced_calls"]


def least_seconds(ctx: Dict[str, Any], keys: Optional[Iterable[str]] = None
                  ) -> Optional[Tuple[float, Dict[str, str]]]:
    """Least time the chip could take for one call's needed work (the larger
    of operations / peak and bytes / peak, per program group), and which
    bound holds for each group."""
    peaks = ctx["peaks"]
    if peaks is None:
        return None
    try:
        model = importlib.import_module(
            f"chipbench.work.{ctx['config']['name']}")
    except ModuleNotFoundError:
        return None
    groups = model.work(ctx["config"], ctx["traffic"],
                        traffic.width(ctx["traffic"]))
    total, bound = 0.0, {}
    for key, w in groups.items():
        if keys is not None and key not in keys:
            continue
        by_flops = w["flops"] / peaks["flops_per_s"]
        by_bytes = w["bytes"] / peaks["hbm_bytes_per_s"]
        total += max(by_flops, by_bytes)
        bound[key] = "flops" if by_flops >= by_bytes else "hbm_bytes"
    return total, bound


def roofline_percent(ctx: Dict[str, Any], keys: Iterable[str], note: str
                     ) -> Optional[float]:
    keys = list(keys)
    device = family_device_seconds(ctx, keys)
    least = least_seconds(ctx, keys)
    if device is None or least is None or device <= 0.0:
        return None
    ctx["notes"][note] = {"least_s": least[0], "device_s": device,
                          "bound": least[1]}
    return 100.0 * least[0] / device
