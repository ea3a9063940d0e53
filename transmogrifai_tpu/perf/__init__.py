"""perf/ — compile-budget subsystem (ISSUE 3 tentpole).

Two pillars:

- ``perf.timers``: nestable phase timers (``record_phases`` / ``phase``), flat
  host activities (``activity``), the ring of finished fit profiles, plus
  a process-wide XLA compile probe (``compile_snapshot`` /
  ``measure_compiles``) fed by ``jax.monitoring`` events — compiled-program
  count and compile-seconds become first-class, measurable resources.
- ``perf.programs``: a process-wide content-addressed executable cache for
  the vmapped (fold x grid) training sweep programs.  Programs are
  lowered/AOT-compiled at most once per (program fingerprint, padded shapes,
  statics, lane layout, mesh) key; JAX's persistent compilation cache is
  wired on (``enable_persistent_cache``) so a warm process performs zero new
  backend compilations for shapes it has seen in ANY previous process.

Importing this package wires the persistent cache unless
``TMOG_PERSISTENT_CACHE=0``.
"""

from .timers import (  # noqa: F401
    activity,
    CompileStats,
    compile_snapshot,
    current_recorder,
    measure_compiles,
    phase,
    PhaseRecorder,
    recent_fit_profiles,
    record_phases,
)
from .programs import (  # noqa: F401
    cache_key_fingerprint,
    clear_program_cache,
    deserialize_compiled,
    enable_persistent_cache,
    program_cache_stats,
    run_cached,
    serialize_compiled,
)

enable_persistent_cache()
