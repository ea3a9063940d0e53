"""Typed serving faults + a deterministic fault-injection harness.

Reference role: the reference's serving story leans on input hygiene
(RawFeatureFilter, SURVEY §7) and engine-free local scoring staying up under
production traffic; Clipper (Crankshaw et al., NSDI'17) adds the systems half
— adaptive batching AMPLIFIES failures (one bad record or one transient
device error co-fails every batched peer) unless the serving layer isolates
them.  This module defines the typed error vocabulary the fault-tolerance
layer speaks (serve/resilience.py, serve/batcher.py) and a seeded,
scriptable fault injector so every failure path is testable with EXACT
schedules instead of sleeps and luck.

Fault points (fired by ``CompiledScoringPlan.score``):

- ``encode`` — host-side record extraction/encoding (where malformed payloads
  surface);
- ``device`` — the compiled fused-program dispatch (where transient
  resource-exhausted / XLA runtime errors surface);
- ``host``   — the interpreted host-remainder stages.

Continual-training fault points (the streaming retrain control plane,
workflow/continual.py + serve/swap.py — each fires BEFORE its phase
mutates anything, so an injected fault provably leaves the serving model
untouched):

- ``drift``      — drift evaluation over the stream accumulators;
- ``refit``      — each warm-refit attempt (bounded retry wraps it);
- ``checkpoint`` — the atomic versioned model checkpoint;
- ``shadow``     — mirroring a flushed batch to the staged candidate;
- ``swap``       — the blue/green promotion (before the atomic flip);
- ``rollback``   — restoring the retained last-known-good model.

Multi-tenant fleet fault points (serve/registry.py + serve/batcher.py —
each fires BEFORE its phase mutates state and carries the tenant id in its
context, so one tenant's injected fault is provably invisible to every
other tenant):

- ``register`` — admitting a tenant's model into the fleet registry;
- ``evict``    — evicting a cold tenant's warm bucket executables (the HBM
  admission controller's LRU reclaim);
- ``route``    — dispatching one tenant's sub-batch out of a mixed flush
  (an injected route fault fails only that tenant's records);
- ``shed``     — the batcher's deadline-then-tier backpressure reclaim
  (fired before any queued entry is evicted).

Training-path fault points (the fault-tolerant fit, workflow/resilience.py
— each retried with bounded backoff when a ``resilient_training`` context
is active, a plain raise otherwise; see docs/robustness.md):

- ``ingest_chunk``     — one chunk of the chunked epoch, before its compute
  dispatches (workflow/ooc.py);
- ``prefetch``         — the background chunk loader, on its worker thread
  (readers/prefetch.py);
- ``stage_fit``        — each estimator fit in the DAG fitter
  (workflow/fit.py fit_stage_list);
- ``sweep_dispatch``   — launching one family's fold x grid sweep program
  (models/tuning.py + workflow_cv_validate; carries family/dp/rows so
  predicates can model mesh- or size-dependent device faults);
- ``device_sync``      — the blocking host fetch of a pending sweep result
  (models/base.py gather_scores);
- ``checkpoint_write`` — durable training state: a stage checkpoint
  (workflow/checkpoint.py) or a sweep-journal commit
  (workflow/resilience.py).

Usage in tests::

    harness = FaultHarness(seed=0)
    harness.script("device", [TransientScoringError("oom"), None])
    with harness:                       # first device call fails, rest pass
        server.score_batch(records)
    assert harness.calls["device"] == 2

Schedules are consumed per firing, so a scripted failure happens exactly
once; predicate rules (``fail_when``) fire whenever their predicate matches
the call context (e.g. "any batch containing the poison record").  The
harness is process-global while active (the micro-batcher scores on its own
thread, so a contextvar would not reach it) — one harness at a time.
"""

from __future__ import annotations

import random
import threading
from typing import Any, Callable, Dict, List, Optional

__all__ = [
    "CircuitOpenError",
    "DeadlineExceededError",
    "FaultHarness",
    "LoadShedError",
    "PoisonRecordError",
    "TransientScoringError",
    "fault_point",
    "is_retryable",
]


# ---------------------------------------------------------------------------
# Typed serving errors
# ---------------------------------------------------------------------------

class PoisonRecordError(RuntimeError):
    """One record is individually unscorable: its future fails, its co-batched
    peers do not.  Raised by the bisect-and-retry quarantine
    (serve/resilience.py) with the original failure as ``cause``."""

    def __init__(self, message: str, cause: Optional[BaseException] = None):
        super().__init__(message)
        self.cause = cause


class DeadlineExceededError(TimeoutError):
    """The request's deadline expired while it waited in the batch queue; it
    was evicted before any device call was spent on it."""


class LoadShedError(RuntimeError):
    """The request was evicted from the queue to make room for higher-tier
    traffic (lowest-effective-tier-first shedding under backpressure —
    serve/batcher.py).  Carries the tenant and SLO tier it was shed at so
    callers can retry against a higher class or back off."""

    def __init__(self, message: str, tenant=None, tier=None):
        super().__init__(message)
        self.tenant = tenant
        self.tier = tier


class TransientScoringError(RuntimeError):
    """A retryable infrastructure failure (device resource exhaustion,
    transport hiccup) — retry with backoff, never quarantine the records."""


class CircuitOpenError(RuntimeError):
    """No scoring path is available: the device plan's circuit breaker is
    open AND the interpreted host fallback failed for this request."""

    def __init__(self, message: str, cause: Optional[BaseException] = None):
        super().__init__(message)
        self.cause = cause


#: substrings marking a device/XLA error as retryable infrastructure noise
_RETRYABLE_MARKERS = ("resource_exhausted", "resource exhausted",
                      "out of memory", "deadline_exceeded (xla)",
                      "unavailable:")


def is_retryable(exc: BaseException) -> bool:
    """Transient (retry with backoff) vs permanent (bisect/quarantine).

    Explicit :class:`TransientScoringError` is always retryable; anything the
    XLA runtime raises is sniffed for resource-exhaustion/unavailability
    markers (``jax.errors.JaxRuntimeError`` carries the gRPC-style status in
    its message).  Everything else — type errors, value errors, poison
    payloads — is permanent: retrying cannot fix the input.
    """
    if isinstance(exc, TransientScoringError):
        return True
    from jax.errors import JaxRuntimeError

    if isinstance(exc, JaxRuntimeError):
        msg = str(exc).lower()
        return any(m in msg for m in _RETRYABLE_MARKERS)
    return False


# ---------------------------------------------------------------------------
# Deterministic fault injection
# ---------------------------------------------------------------------------

#: the one active harness (process-global: the batcher flusher is another
#: thread, so contextvars would not propagate to the scoring call site)
_ACTIVE: Optional["FaultHarness"] = None
_ACTIVE_LOCK = threading.Lock()


class FaultHarness:
    """Seeded, scriptable fault schedules for the serving fault points.

    - ``script(point, schedule)`` — the n-th firing of ``point`` raises the
      n-th schedule entry (None entries pass; callables get the call context
      and return an exception or None).  Entries beyond the schedule pass.
    - ``fail_when(point, predicate, make_error, times=None)`` — raise
      whenever ``predicate(ctx)`` matches, at most ``times`` times (None =
      unbounded).  Predicate rules run after (and independent of) scripts.
    - ``max_fires`` (on ``script``/``fail_when``) — a per-point cap on TOTAL
      injected failures: once ``point`` has fired that many times, every
      further schedule entry and predicate match passes.  Retrying training
      loops re-enter their fault points unboundedly, so an uncapped callable
      schedule (or ``times=None`` rule) would otherwise starve the retry
      ladder forever.
    - ``calls`` — firings per point; ``fired`` — (point, call index) log of
      every injected failure, for exact-schedule assertions.

    ``seed`` makes any randomized schedule (callable entries using
    ``harness.rng``) reproducible run-to-run.
    """

    def __init__(self, seed: int = 0):
        self.rng = random.Random(seed)
        self.calls: Dict[str, int] = {}
        self.fired: List[tuple] = []
        self._scripts: Dict[str, List[Any]] = {}
        self._rules: List[tuple] = []  # (point, predicate, make_error, left)
        self._max_fires: Dict[str, int] = {}
        self._fire_counts: Dict[str, int] = {}
        self._lock = threading.Lock()

    # -- schedule construction ----------------------------------------------
    def script(self, point: str, schedule,
               max_fires: Optional[int] = None) -> "FaultHarness":
        # _check fires from serving threads (batcher flusher, shadow
        # worker); schedule edits race with it unless they share its lock
        with self._lock:
            self._scripts.setdefault(point, []).extend(schedule)
            if max_fires is not None:
                self._max_fires[point] = int(max_fires)
        return self

    def fail_when(self, point: str, predicate: Callable[[dict], bool],
                  make_error: Callable[[], BaseException],
                  times: Optional[int] = None,
                  max_fires: Optional[int] = None) -> "FaultHarness":
        with self._lock:
            self._rules.append([point, predicate, make_error, times])
            if max_fires is not None:
                self._max_fires[point] = int(max_fires)
        return self

    # -- firing --------------------------------------------------------------
    def _check(self, point: str, ctx: dict) -> Optional[BaseException]:
        with self._lock:
            idx = self.calls.get(point, 0)
            self.calls[point] = idx + 1
            cap = self._max_fires.get(point)
            if cap is not None and self._fire_counts.get(point, 0) >= cap:
                return None
            entry = None
            sched = self._scripts.get(point)
            if sched and idx < len(sched):
                entry = sched[idx]
            if callable(entry):
                entry = entry(ctx)
            if entry is None:
                for rule in self._rules:
                    rpoint, pred, make_error, left = rule
                    if rpoint != point or left == 0:
                        continue
                    if pred(ctx):
                        if left is not None:
                            rule[3] = left - 1
                        entry = make_error()
                        break
            if entry is not None:
                self.fired.append((point, idx))
                self._fire_counts[point] = \
                    self._fire_counts.get(point, 0) + 1
            return entry

    # -- activation ----------------------------------------------------------
    def __enter__(self) -> "FaultHarness":
        global _ACTIVE
        with _ACTIVE_LOCK:
            if _ACTIVE is not None:
                raise RuntimeError("another FaultHarness is already active")
            _ACTIVE = self
        return self

    def __exit__(self, *exc) -> None:
        global _ACTIVE
        with _ACTIVE_LOCK:
            _ACTIVE = None


def fault_point(point: str, **ctx) -> None:
    """Hook called from the scoring hot path; raises the scheduled fault when
    a harness is active, otherwise costs one global read.  Every injected
    fault is also recorded by the installed flight recorder (obs/flight.py)
    — and auto-dumps the ring buffer when the recorder has a dump_dir — so
    a harness run leaves its own postmortem artifact."""
    harness = _ACTIVE
    if harness is None:
        return
    err = harness._check(point, ctx)
    if err is not None:
        from ..obs import flight as obs_flight

        # the per-tenant fault points (register/evict/route/shed, and any
        # serve-level point the fleet fires with a tenant in its context)
        # carry the tenant into the flight event + auto-dumped snapshot
        tenant = ctx.get("tenant")
        obs_flight.record_fault(point, err,
                                tenant=str(tenant)
                                if tenant is not None else None)
        raise err
