"""Test configuration: force an 8-device virtual CPU mesh.

This plays the role of the reference's local[2] SparkSession (SURVEY §4): real sharding and
collective semantics on one host.  Must run before jax initializes its backends.
"""

import os
import sys

# Tests run on the CPU even on a machine that holds a chip: they emulate a
# multi-chip mesh with 8 virtual CPU devices, and a chip belongs to one process
# at a time.  Both variables are read when jax initializes its backends, which
# has not happened yet here; the config update covers a pytest plug-in that
# imported jax before this file ran.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# The library persists EVERY compile (perf/programs.py: minimum compile time 0)
# so that a second process finds every program.  This suite counts compiles:
# its cold-path tests expect the sub-second programs to compile in every
# session, which a cache left warm by an earlier session would turn into hits.
# So the suite keeps to what it always cached — the sweeps that take over a
# second — wherever the directory was placed.
from transmogrifai_tpu import perf as _perf  # noqa: E402,F401 — wires the cache

jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(42)


@pytest.fixture(scope="session", autouse=True)
def _compile_budget_report():
    """Print the suite-wide compile budget at session end: total backend
    compiles/seconds and the sweep executable-cache hit rate.  The sweep
    cache is process-wide, so test modules fitting same-bucket sweeps share
    warm executables — the hit counters make that visible per run."""
    yield
    try:
        from transmogrifai_tpu.perf import compile_snapshot, \
            program_cache_stats

        snap = compile_snapshot()
        prog = program_cache_stats()
        sys.stderr.write(
            f"\n[perf] suite compile budget: {snap.backend_compiles} backend "
            f"compiles, {snap.compile_seconds:.1f}s compiling; sweep "
            f"executable cache: {prog['programs_compiled']} compiled, "
            f"{prog['cache_hits']} hits, "
            f"{snap.persistent_cache_hits} persistent-cache hits\n")
    except Exception:
        pass
