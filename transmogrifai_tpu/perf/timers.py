"""Nestable phase timers + a process-wide XLA compile probe.

Phase timers: the selector fit, the cross-validator, and the workflow fit
loop wrap their phases in ``phase("name")``.  Spans land in every active
``PhaseRecorder`` (recorders nest — the selector records its own fit profile
while a caller's ambient recorder captures the same spans), so the ONE real
fit yields the per-phase breakdown (``last_fit_profile``); nothing is re-run
in isolation to get a per-phase or per-family number.

One span source, three sinks, one clock: past its early-out a span also
enters a ``jax.profiler.TraceAnnotation``, so whenever a profiler capture is
running every span lies in the host plane of the same ``.xplane.pb`` as the
device ops (outside a capture a ``TraceMe`` is a flag check).  The lower
layers mark what the host is doing — padding, stamping, placing, launching,
waiting — with ``activity("name")``: flat ``host.<name>`` spans that recur
under every phase and are summed by activity (docs/observability.md).

Compile probe: ``jax.monitoring`` emits an event per compile request
(``/jax/core/compile/backend_compile_duration``) and per persistent-cache
hit/miss.  A module-level listener accumulates them — a request the
persistent cache answered counts as a hit, not a compile; ``measure_compiles``
yields a live delta object, which is how tests assert "the second fit of the
default sweep performs 0 new XLA compilations".  The same listener makes
what it hears spans of the running fit: a duration event arrives at the END
of the interval it timed, so it records the finished span ``[now - secs,
now)`` as ``host.trace``, ``host.lower``, ``host.cache_load`` or
``host.backend_compile``, labelled with the program that was being launched
(``PhaseRecorder.compile_table``: which step recompiled, and what it cost).
"""

from __future__ import annotations

import contextlib
import contextvars
import threading
import time
from collections import deque
from dataclasses import asdict, dataclass, fields, replace
from typing import Any, Dict, List, Optional

from jax.profiler import TraceAnnotation

from ..obs import trace as obs_trace


# ---------------------------------------------------------------------------
# Phase timers
# ---------------------------------------------------------------------------

@dataclass
class Span:
    """One timed phase execution.  ``path`` is the dotted nesting path.

    An activity's ``path`` is the flat ``host.<name>``; its ``parent`` is the
    dotted phase path that was open when it ran (relative to the recorder,
    like ``path``) and ``counts`` what its site counted (``nbytes``,
    ``hit``, ``label``; on the compile probe's spans ``label``, ``fun`` and
    a load's ``retrieval_s``).  Both stay empty on a phase."""

    name: str
    path: str
    start: float
    seconds: float
    parent: str = ""
    counts: Optional[Dict[str, Any]] = None


#: the compile probe's span paths -> their columns in ``compile_table``
_COMPILE_KEYS = {
    "host.trace": ("trace_s", "traces"),
    "host.lower": ("lower_s", "lowers"),
    "host.cache_load": ("cache_load_s", "cache_loads"),
    "host.backend_compile": ("backend_compile_s", "backend_compiles"),
}


class PhaseRecorder:
    """Collects spans; ``report()`` aggregates seconds by dotted path.

    Paths are RELATIVE to the recorder's activation point: a recorder opened
    inside ``phase("fit.modelSelector")`` records the selector's "validate"
    span as ``validate``, while an outer recorder sees the same span as
    ``fit.modelSelector.validate`` — so consumers (``chipbench``'s span
    readers) parse stable paths regardless of how deep the fit ran.
    """

    def __init__(self):
        self.spans: List[Span] = []
        #: phase-stack depth when this recorder was activated
        self._base = 0
        #: ``perf_counter`` at activation and deactivation (record_phases)
        self.start: Optional[float] = None
        self.end: Optional[float] = None
        #: a selector fit under ``use_mesh``: the mesh's shape, rows per data
        #: shard and the fit's ``placement_stats()["mesh"]`` deltas
        #: (parallel/mesh.py ``fit_mesh_record``); None with no mesh
        self.mesh: Optional[Dict[str, Any]] = None

    def add(self, span: Span) -> None:
        self.spans.append(span)

    def report(self, round_to: int = 4) -> Dict[str, float]:
        """{dotted path: total seconds} over all recorded spans."""
        out: Dict[str, float] = {}
        for s in self.spans:
            out[s.path] = out.get(s.path, 0.0) + s.seconds
        return {k: round(v, round_to) for k, v in out.items()}

    def total(self, path: str) -> float:
        """Summed seconds of spans recorded at exactly ``path``.

        Exact-path only: a parent span's time already includes its nested
        children, so summing the subtree would double-count."""
        return sum(s.seconds for s in self.spans if s.path == path)

    def compile_table(self) -> Dict[str, Dict[str, Any]]:
        """{label: {"trace_s", "lower_s", "cache_load_s",
        "backend_compile_s", the count of each as "traces", "lowers",
        "cache_loads", "backend_compiles", and "retrieval_s"}}: the compile
        probe's spans by the program that was being launched — which step
        recompiled, and what it cost.  Seconds are self time (an inner jit's
        trace inside an outer's, a trace inside a lowering, count once), but
        for ``retrieval_s``: the part of ``cache_load_s`` in which jax read,
        deserialised and loaded the executables, the rest being the cache
        keys it computed from the modules.  Labels come in the order their
        first span started."""
        retro = sorted((s for s in self.spans if s.path in _COMPILE_KEYS),
                       key=lambda s: (s.start, -s.seconds))
        own = [s.seconds for s in retro]
        open_: List[tuple] = []             # (index, end), innermost last
        for i, s in enumerate(retro):
            while open_ and s.start >= open_[-1][1]:
                open_.pop()
            if open_:
                own[open_[-1][0]] -= min(s.seconds, open_[-1][1] - s.start)
            open_.append((i, s.start + s.seconds))
        out: Dict[str, Dict[str, Any]] = {}
        for s, secs in zip(retro, own):
            secs_key, count_key = _COMPILE_KEYS[s.path]
            row = out.setdefault(s.counts["label"], {
                **{k: v for pair in _COMPILE_KEYS.values()
                   for k, v in zip(pair, (0.0, 0))}, "retrieval_s": 0.0})
            row[secs_key] += secs
            row[count_key] += 1
            row["retrieval_s"] += s.counts.get("retrieval_s", 0.0)
        return out


#: stack of active recorders (outermost first) — spans land in ALL of them
_RECORDERS: "contextvars.ContextVar[tuple]" = contextvars.ContextVar(
    "transmogrifai_tpu_perf_recorders", default=())
#: current nesting path of open phases
_PHASE_STACK: "contextvars.ContextVar[tuple]" = contextvars.ContextVar(
    "transmogrifai_tpu_perf_phase_stack", default=())
#: ``label`` of the open activity that carries one (a ``host.launch``) or of
#: the open ``compile_phase``: what the compile probe's spans inside it are
#: put down to
_OPEN_LABEL: "contextvars.ContextVar[Optional[str]]" = contextvars.ContextVar(
    "transmogrifai_tpu_perf_open_label", default=None)


def current_recorder() -> Optional[PhaseRecorder]:
    """Innermost active recorder, or None."""
    stack = _RECORDERS.get()
    return stack[-1] if stack else None


@contextlib.contextmanager
def record_phases(recorder: Optional[PhaseRecorder] = None):
    """Activate a PhaseRecorder for the duration of the block.

    Nesting is additive: an inner ``record_phases`` does not hide the outer
    one — spans recorded inside land in both.
    """
    rec = recorder if recorder is not None else PhaseRecorder()
    rec._base = len(_PHASE_STACK.get())
    token = _RECORDERS.set(_RECORDERS.get() + (rec,))
    rec.start = time.perf_counter()
    try:
        yield rec
    finally:
        rec.end = time.perf_counter()
        _RECORDERS.reset(token)


#: an open activity's token (a phase's is its stack reset token)
_FLAT = object()


class _Phase:
    """Slotted class-based context manager (cheaper than a generator CM on
    both the active and no-op paths — phases sit on hot per-batch loops).

    ``counts`` is None on a phase, which nests (its path is the stack of open
    phases); a dict, possibly empty, on an activity, which is flat: it
    leaves the stack alone and records as ``host.<name>`` with the open
    phase path as its parent."""

    __slots__ = ("name", "counts", "recorders", "tracer", "token", "parts",
                 "t0", "annotation", "label_token")

    def __init__(self, name: str, counts: Optional[Dict[str, Any]] = None):
        self.name = name
        self.counts = counts

    def __enter__(self) -> "_Phase":
        recorders = _RECORDERS.get()
        tracer = obs_trace.active_tracer()
        self.recorders = recorders
        self.tracer = tracer
        if not recorders and tracer is None:
            self.token = None
            return self
        stack = _PHASE_STACK.get()
        # the profiler's clock: inside a capture the span lands on this
        # thread's line of the host plane, with a ``span`` stat that tells
        # it from the runtime's own events there; outside a capture this is
        # a flag check
        if self.counts is None:
            self.parts = stack + (self.name,)
            self.token = _PHASE_STACK.set(self.parts)
            self.annotation = TraceAnnotation(".".join(self.parts),
                                              span="phase")
        else:
            self.parts = stack
            self.token = _FLAT
            self.annotation = TraceAnnotation("host." + self.name,
                                              span="activity")
            label = self.counts.get("label")
            self.label_token = (_OPEN_LABEL.set(label) if label is not None
                                else None)
        self.annotation.__enter__()
        self.t0 = time.perf_counter()
        return self

    def note(self, **counts) -> None:
        """Add counts that are known only once the work is done (a memo hit);
        nothing to do on the no-op path."""
        if self.token is not None:
            self.counts.update(counts)

    def __exit__(self, *exc) -> None:
        if self.token is None:
            return
        dt = time.perf_counter() - self.t0
        self.annotation.__exit__(None, None, None)
        if self.counts is None:
            _PHASE_STACK.reset(self.token)
            for rec in self.recorders:
                rel = self.parts[rec._base:]  # path relative to recorder base
                if rel:
                    rec.add(Span(name=self.name, path=".".join(rel),
                                 start=self.t0, seconds=dt))
            if self.tracer is not None:
                self.tracer.add_complete(".".join(self.parts), "train",
                                         self.t0, dt, {})
            return
        if self.label_token is not None:
            _OPEN_LABEL.reset(self.label_token)
        _emit_activity(self.recorders, self.tracer, self.parts, self.name,
                       self.t0, dt, self.counts)


def _emit_activity(recorders: tuple, tracer, parts: tuple, name: str,
                   start: float, seconds: float, counts: Dict[str, Any]
                   ) -> None:
    """One finished ``host.<name>`` span into the recorders and the tracer;
    ``parts`` is the phase stack that was open."""
    path = "host." + name
    for rec in recorders:
        rec.add(Span(name=name, path=path, start=start, seconds=seconds,
                     parent=".".join(parts[rec._base:]), counts=counts))
    if tracer is not None:
        tracer.add_complete(path, "train", start, seconds,
                            {"parent": ".".join(parts), **counts})


def phase(name: str) -> _Phase:
    """Time a phase.  No-op (zero overhead beyond a contextvar read and a
    tracer-global read) when no recorder AND no trace sink is active.
    Phases nest: ``phase("fit")`` inside ``phase("validate")`` records as
    path ``validate.fit``.  When an ``obs`` tracer is installed
    (docs/observability.md), every phase additionally lands there as a
    ``train``-category span under its full dotted path, and inside a
    ``jax.profiler`` capture as a host annotation of the same name."""
    return _Phase(name)


@contextlib.contextmanager
def compile_phase(label: str):
    """``phase("compile.<label>")`` around one program's lowering and
    compilation (``run_cached``); the compile probe's spans inside it are
    put down to ``label``, as inside a ``host.launch`` that carries one."""
    token = _OPEN_LABEL.set(label)
    try:
        with phase("compile." + label):
            yield
    finally:
        _OPEN_LABEL.reset(token)


def activity(name: str, **counts) -> _Phase:
    """Time what the host is doing, across phases: same class, sinks and
    early-out as :func:`phase`, but the span's path is the flat
    ``host.<name>`` whatever the phase stack is, and it carries ``parent``
    (the open phase path) and ``counts``.  Flat because padding, stamping,
    placing, launching and waiting recur under ``cv.dispatch``, ``refit`` and
    ``train_eval`` alike and are summed by activity.  Activities are leaves
    but for the compile probe's spans, which lie inside the ``host.launch``
    that traced, lowered, loaded or compiled: its self time is the dispatch
    alone."""
    return _Phase(name, counts)


#: the last finished fit profiles of this process, oldest first
_RECENT_FITS: "deque[PhaseRecorder]" = deque(maxlen=128)
_RECENT_FITS_LOCK = threading.Lock()


def keep_fit_profile(profile: PhaseRecorder) -> None:
    """Append one finished fit's recorder (``start``/``end`` stamped by
    ``record_phases``) to the process-wide ring."""
    with _RECENT_FITS_LOCK:
        _RECENT_FITS.append(profile)


def recent_fit_profiles() -> List[PhaseRecorder]:
    """The last (at most 128) finished ``ModelSelector`` fit profiles, oldest
    first: whole spans with their ``perf_counter`` times, for a reader that
    is handed ``report()`` totals only."""
    with _RECENT_FITS_LOCK:
        return list(_RECENT_FITS)


# ---------------------------------------------------------------------------
# Compile probe
# ---------------------------------------------------------------------------

@dataclass
class CompileStats:
    """Cumulative XLA compilation counters (process-wide since import).

    Seconds are sums of the events' own lengths: ``trace_seconds`` counts an
    inner jit's trace again inside its outer's (a recorder's
    ``compile_table`` has the self times)."""

    backend_compiles: int = 0
    compile_seconds: float = 0.0        # real compilations only
    trace_seconds: float = 0.0          # jaxpr trace
    lower_seconds: float = 0.0          # jaxpr -> MLIR module
    cache_load_seconds: float = 0.0     # requests the persistent cache answered
    persistent_cache_hits: int = 0
    persistent_cache_misses: int = 0

    def snapshot(self) -> "CompileStats":
        return replace(self)

    def minus(self, other: "CompileStats") -> "CompileStats":
        return CompileStats(**{f.name: getattr(self, f.name)
                               - getattr(other, f.name) for f in fields(self)})

    def to_dict(self) -> Dict[str, Any]:
        return {k: round(v, 3) if isinstance(v, float) else v
                for k, v in asdict(self).items()}


_GLOBAL = CompileStats()
_LOCK = threading.Lock()
_REGISTERED = False

#: monitoring event names (jax >= 0.4.x)
_EV_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_EV_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_EV_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_EV_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_EV_CACHE_MISS = "/jax/compilation_cache/cache_misses"
_EV_CACHE_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"

#: per-thread, from a persistent-cache hit to the duration event that closes
#: its request: ``cache_hit`` and ``retrieval_s``, the seconds jax took to
#: read, deserialise and load the executable
_TL = threading.local()


def _probe_span(name: str, secs: float, event: Dict[str, Any],
                **counts) -> None:
    """The interval a duration event closed, as a finished activity
    ``[now - secs, now)`` of the running fit.  Its ``label`` is that of the
    ``host.launch`` (a direct jit call) or ``compile_phase``
    (``run_cached``) open on this thread, else ``"unlabelled"``; ``fun`` is
    jax's own name for what it traced, lowered or compiled.  Never a
    ``TraceAnnotation``: the profiler cannot be handed a span after the
    fact, and a capture holds jax's own compile events on this thread's
    line."""
    recorders = _RECORDERS.get()
    tracer = obs_trace.active_tracer()
    if not recorders and tracer is None:
        return
    end = time.perf_counter()
    _emit_activity(recorders, tracer, _PHASE_STACK.get(), name, end - secs,
                   secs, {**counts, "label": _OPEN_LABEL.get() or "unlabelled",
                          "fun": event.get("fun_name")})


def _on_event(name: str, **kw) -> None:
    if name == _EV_CACHE_HIT:
        with _LOCK:
            _GLOBAL.persistent_cache_hits += 1
        _TL.cache_hit = True
    elif name == _EV_CACHE_MISS:
        with _LOCK:
            _GLOBAL.persistent_cache_misses += 1


def _on_duration(name: str, secs: float, **kw) -> None:
    if name == _EV_BACKEND_COMPILE:
        # jax 0.9 times ``compile_or_get_cached`` as a whole, so this event
        # also closes a request the persistent cache answered (its hit and
        # retrieval events land first, on the same thread).  A load is not a
        # compile: only real compilations count as one.
        hit = vars(_TL)
        if hit.pop("cache_hit", False):
            with _LOCK:
                _GLOBAL.cache_load_seconds += secs
            _probe_span("cache_load", secs, kw,
                        retrieval_s=hit.pop("retrieval_s", 0.0))
        else:
            with _LOCK:
                _GLOBAL.backend_compiles += 1
                _GLOBAL.compile_seconds += secs
            _probe_span("backend_compile", secs, kw)
    elif name == _EV_TRACE:
        with _LOCK:
            _GLOBAL.trace_seconds += secs
        _probe_span("trace", secs, kw)
    elif name == _EV_LOWER:
        with _LOCK:
            _GLOBAL.lower_seconds += secs
        _probe_span("lower", secs, kw)
    elif name == _EV_CACHE_RETRIEVAL:
        _TL.retrieval_s = secs


def _ensure_registered() -> None:
    """Register the jax.monitoring listeners once.  Listeners are global and
    live for the process; with no recorder and no tracer active an event
    costs a counter update."""
    global _REGISTERED
    if _REGISTERED:
        return
    with _LOCK:
        if _REGISTERED:
            return
        try:
            from jax import monitoring
        except Exception:  # pragma: no cover — jax without monitoring
            _REGISTERED = True
            return
        monitoring.register_event_listener(_on_event)
        monitoring.register_event_duration_secs_listener(_on_duration)
        _REGISTERED = True


_ensure_registered()


def compile_snapshot() -> CompileStats:
    """A copy of the cumulative process-wide compile counters."""
    _ensure_registered()
    with _LOCK:
        return _GLOBAL.snapshot()


class _CompileDelta:
    """Live view over compiles since ``measure_compiles`` entered; attributes
    resolve lazily so reads after the with-block see the final delta."""

    def __init__(self, base: CompileStats):
        self._base = base

    def _delta(self) -> CompileStats:
        return compile_snapshot().minus(self._base)

    @property
    def backend_compiles(self) -> int:
        return self._delta().backend_compiles

    @property
    def compile_seconds(self) -> float:
        return self._delta().compile_seconds

    @property
    def persistent_cache_hits(self) -> int:
        return self._delta().persistent_cache_hits

    @property
    def persistent_cache_misses(self) -> int:
        return self._delta().persistent_cache_misses

    def to_dict(self) -> Dict[str, Any]:
        return self._delta().to_dict()


@contextlib.contextmanager
def measure_compiles():
    """Yield a delta object tracking XLA compilations inside (and after) the
    block: ``with measure_compiles() as c: fit(); assert c.backend_compiles == 0``."""
    yield _CompileDelta(compile_snapshot())


def package_import_seconds() -> float:
    """Seconds ``import transmogrifai_tpu`` took in this process, first
    import to last (the package notes the clock at both ends); jax's own
    import is inside only where nothing imported jax before."""
    import transmogrifai_tpu as package

    return package._IMPORT_END - package._IMPORT_START
