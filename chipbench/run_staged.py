"""``run.py``'s command with what is staged laid over ``BENCHMARK.json``.

    python3 chipbench/run_staged.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The benchmark object handed to the harness is ``BENCHMARK.json`` plus the
``configs``, ``workloads`` and ``per_layer`` entries of every
``chipbench/staged_*.json`` (cells and metrics that are built and rehearsed
and wait for a benchmark PR to declare them).  Same arguments, same two
output lines; the driver never runs it.
"""

from __future__ import annotations

import functools
import glob
import json
import os
import sys
from unittest import mock

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:            # started as a script, not with -m
    sys.path.insert(0, ROOT)

from chipbench import run as harness  # noqa: E402


def staged_benchmark(root: str = ROOT):
    bench = harness.load_benchmark(root)
    for path in sorted(glob.glob(os.path.join(root, "chipbench",
                                              "staged_*.json"))):
        with open(path) as f:
            staged = json.load(f)
        for key in ("configs", "workloads", "per_layer"):
            bench[key] = bench[key] + staged.get(key, [])
    return bench


def main(argv=None) -> int:
    # ``main`` parses the arguments, calls the module's ``run`` and prints
    with mock.patch.object(harness, "run", functools.partial(
            harness.run, bench=staged_benchmark())):
        return harness.main(argv)


if __name__ == "__main__":
    sys.exit(main())
