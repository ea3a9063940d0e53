"""The benchmark's one command.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process is one run of one cell of ``BENCHMARK.json``: set-up (table from
the seed, the cell's entry built and called once to warm up), a measured
window of whole back-to-back calls, then the comparison with the plain
reference that decides ``correct``, then one JSON line.  Everything that
belongs to one configuration, traffic mix, entry or metric is a file found
by the name ``BENCHMARK.json`` gives (see README.md); this file names none
of them.  It refuses to run without the chips the cell asks for.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import shutil
import sys
import time
from typing import Any, Dict, List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:            # started as a script, not with -m
    sys.path.insert(0, ROOT)

#: traced runs profile whole calls until this many seconds have gone by
TRACE_SECONDS = 6.0
TRACE_DIR = os.path.join(ROOT, ".chipbench_trace")


class NoChip(SystemExit):
    """Raised (exit code 3, nothing on stdout) when the chips are missing."""

    def __init__(self, why: str):
        print(f"[chipbench] {why}", file=sys.stderr)
        super().__init__(3)


def process_age_s() -> float:
    """Seconds since this process was started (Linux: /proc), so that
    the set-up time counts the interpreter's start and every import."""
    try:
        with open("/proc/self/stat") as f:
            ticks = float(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return uptime - ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.perf_counter() - _IMPORTED


_IMPORTED = time.perf_counter()


def load_benchmark(root: str = ROOT) -> Dict[str, Any]:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def cell_metrics(bench: Dict[str, Any], kind: str, workload: str
                 ) -> List[Dict[str, Any]]:
    return [m for m in bench[kind]
            if "workloads" not in m or workload in m["workloads"]]


def load_config(bench: Dict[str, Any], name: str, root: str = ROOT
                ) -> Dict[str, Any]:
    entry = next(c for c in bench["configs"] if c["name"] == name)
    with open(os.path.join(root, entry["file"])) as f:
        return json.load(f)


def load_peaks(device_kind: str) -> Dict[str, Any]:
    with open(os.path.join(ROOT, "chipbench", "peaks.json")) as f:
        peaks = json.load(f)
    if device_kind not in peaks:
        raise KeyError(f"device kind {device_kind!r} is not in "
                       "chipbench/peaks.json; add it with its source")
    return peaks[device_kind]


def find_chips(need: int, require_tpu: bool) -> Dict[str, Any]:
    import jax

    backend = jax.default_backend()
    devices = jax.devices()
    if require_tpu and backend != "tpu":
        raise NoChip(f"no chip: jax.default_backend() is {backend!r}")
    if require_tpu and len(devices) < need:
        raise NoChip(f"the cell needs {need} chips, found {len(devices)}")
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def memory_peak_bytes(chips: int) -> int:
    import jax

    peaks = []
    for dev in jax.local_devices()[:chips]:
        stats = dev.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def read_metrics(kind: str, entries: List[Dict[str, Any]],
                 ctx: Dict[str, Any]) -> Dict[str, Dict[str, Any]]:
    """Each metric is ``chipbench/<kind>/<name>.py`` with ``read(ctx)``; a
    reader that finds nothing to read returns None and the metric is left
    out of the line."""
    out = {}
    for m in entries:
        reader = importlib.import_module(f"chipbench.{kind}.{m['name']}")
        value = reader.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run(workload: str, seed: int, seconds: float, trace: bool,
        require_tpu: bool = True, overrides: Optional[Dict[str, Any]] = None,
        free_device: bool = True, bench: Optional[Dict[str, Any]] = None
        ) -> Dict[str, Any]:
    """One run; returns the result object.  ``require_tpu=False``,
    ``overrides`` (keys replaced in the traffic parameters and the
    configuration), ``free_device=False`` (a test process shares its
    device arrays with other tests) and ``bench`` (a benchmark object other
    than ``BENCHMARK.json``, for cells that are staged and not yet in it)
    are for the CPU guards in tests/chipbench and for rehearsals."""
    bench = bench or load_benchmark()
    cell = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if cell is None:
        raise SystemExit(f"[chipbench] unknown workload {workload!r}")
    device = find_chips(int(cell["chips"]), require_tpu)
    overrides = overrides or {}
    config = {**load_config(bench, cell["config"]),
              **overrides.get("config", {})}
    from chipbench import traffic

    params = {**traffic.load(cell["traffic"]), **overrides.get("traffic", {})}
    entry = importlib.import_module(f"chipbench.entries.{config['entry']}")
    cache_dir = entry.enable_cache()

    marks = {"imports_and_chips": process_age_s()}
    table = traffic.generate(params, seed)
    marks["table"] = process_age_s()
    state = entry.setup(config, table)
    setup_seconds = marks["entry_setup"] = process_age_s()

    import jax

    records: List[Dict[str, Any]] = []
    traced_calls, reduced = 0, None
    if trace:
        from chipbench import reduce as trace_reduce

        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0     # per-call python events are huge
        jax.profiler.start_trace(TRACE_DIR, profiler_options=options)
    t0 = time.perf_counter()
    if trace:
        try:
            with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
                while not records or time.perf_counter() - t0 < min(
                        TRACE_SECONDS, seconds):
                    records.append(entry.step(state))
        finally:
            jax.profiler.stop_trace()
        traced_calls = len(records)
    while time.perf_counter() - t0 < seconds:
        records.append(entry.step(state))
    window_s = time.perf_counter() - t0
    peak = memory_peak_bytes(int(cell["chips"]))
    if trace:
        try:
            reduced = trace_reduce.reduce_file(
                trace_reduce.find_xplane(TRACE_DIR))
        except trace_reduce.NoDevicePlane:
            if require_tpu:     # a traced run with no device plane is no run
                raise
        shutil.rmtree(TRACE_DIR, ignore_errors=True)

    entry.collect(state, records, table, seed)
    if free_device:
        entry.release(state)
    compared, compare_detail = entry.compare(config, table, records, seed)
    compare_detail["peak_bytes_after"] = memory_peak_bytes(int(cell["chips"]))
    correct = all(value <= limit for value, limit in compared.values())

    ctx = {
        "workload": workload, "config": config, "traffic": params,
        "device": device, "records": records, "window_s": window_s,
        "setup_seconds": setup_seconds, "setup": state.setup_counters,
        "trace": reduced, "traced_calls": traced_calls,
        "memory_peak_bytes": peak, "cache_dir": cache_dir, "notes": {},
        "peaks": load_peaks(device["kind"]) if require_tpu else None,
    }
    kind = "per_layer" if trace else "end_to_end"
    metrics = read_metrics(kind, cell_metrics(bench, kind, workload), ctx)
    result: Dict[str, Any] = {
        "correct": bool(correct),
        "attempted": int(sum(r["attempted"] for r in records)),
        "failed": int(sum(r["failed"] for r in records)),
        "metrics": metrics,
        "device": {**device, "memory_peak_bytes": peak},
    }
    if reduced is not None:
        result["device"]["busy_s"] = reduced["busy_s"]
        result["device"]["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["notes"] = {
        "calls": len(records), "traced_calls": traced_calls,
        "window_s": window_s, **ctx["notes"], "compare": compare_detail,
        "setup_reached_s": {k: round(v, 2) for k, v in marks.items()},
        "call_seconds": [round(r["seconds"], 4) for r in records],
        "spans_mean_s": {k: round(sum(r["spans"].get(k, 0.0) for r in records)
                                  / len(records), 4)
                         for k in records[-1]["spans"]},
        "last_call": {k: records[-1].get(k) for k in ("best",)},
        "modules_s_per_traced_call": {
            k: round(v / traced_calls, 5) for k, v in
            sorted(reduced["modules"].items(), key=lambda kv: -kv[1])
            if v / traced_calls >= 1e-3} if reduced else None,
        "why_failed": sorted({w for r in records for w in r["why_failed"]})}
    result["compared"] = {k: {"value": v, "limit": lim}
                          for k, (v, lim) in compared.items()}
    return result


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    result = run(args.workload, args.seed, args.seconds, bool(args.trace))
    print(json.dumps({"notes": result.pop("notes")}), flush=True)
    print(json.dumps(result), flush=True)
    for name, c in result["compared"].items():
        verdict = "ok" if c["value"] <= c["limit"] else "OVER"
        print(f"[chipbench] compared {name}: {c['value']:.6g} "
              f"(limit {c['limit']:.6g}) {verdict}", file=sys.stderr)
    print(f"[chipbench] correct={result['correct']}", file=sys.stderr,
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
