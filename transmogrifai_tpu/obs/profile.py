"""``TMOG_PROFILE=<dir>`` — opt-in ``jax.profiler`` capture of a whole fit
or of the serve dispatch.

Reference role: the reference leans on Spark's UI for executor profiles;
the TPU-native equivalent is the XLA profiler (xplane traces viewable in
TensorBoard/XProf).  Setting ``TMOG_PROFILE`` to a directory wraps one whole
``ModelSelector.fit`` / ``Workflow.train`` — from where the fit's recorder
opens to after its last blocking fetch, so the capture holds every
``perf.timers`` span as a host annotation beside whole device programs — and
the compiled serving-plan device call in ``jax.profiler`` start/stop; unset,
the hook is a single ``os.environ`` read per fit or serve dispatch — no
profiler import, no cost.  (It used to wrap each ``run_cached`` dispatch: an
asynchronous launch, so the capture ended before the device program did.)

Captures do not nest: when a trace is already in flight (an outer
``Workflow.train``, another thread), inner hooks run unprofiled instead of
crashing the profiler — a train holds its selector fit in ONE capture.
"""

from __future__ import annotations

import contextlib
import logging
import os
import threading

log = logging.getLogger(__name__)

_LOCK = threading.Lock()
_ACTIVE = False


def profile_dir() -> str:
    """The configured profile directory ('' when profiling is off)."""
    return os.environ.get("TMOG_PROFILE", "")


@contextlib.contextmanager
def maybe_profile(tag: str):
    """Wrap a fit or a dispatch in a ``jax.profiler`` capture when
    ``TMOG_PROFILE`` is set; otherwise (or when a capture is already active)
    a no-op.  The traced computation is NEVER altered — a profiler failure
    logs and the work proceeds unprofiled, so the score path stays bitwise
    identical."""
    d = profile_dir()
    if not d:
        yield
        return
    global _ACTIVE
    with _LOCK:
        claimed = not _ACTIVE
        if claimed:
            _ACTIVE = True
    started = False
    if claimed:
        try:
            os.makedirs(d, exist_ok=True)
            import jax

            # the python tracer's per-call events swamp a whole fit (52 MB
            # for seven tiny fits, PERF.md); the spans name the host side
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(d, profiler_options=options)
            started = True
        except Exception as e:  # noqa: BLE001 — never break the dispatch
            log.warning("TMOG_PROFILE capture (%s) failed to start: %s",
                        tag, e)
    try:
        yield
    finally:
        if started:
            try:
                import jax

                jax.profiler.stop_trace()
            except Exception as e:  # noqa: BLE001 — never break the dispatch
                log.warning("TMOG_PROFILE capture (%s) failed to stop: %s",
                            tag, e)
        if claimed:
            with _LOCK:
                _ACTIVE = False
