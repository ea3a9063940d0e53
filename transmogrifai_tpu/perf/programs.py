"""Process-wide content-addressed executable cache for training programs.

The vmapped (fold x grid) sweep programs — IRLS/FISTA sweeps, the SVC CV
program, the GBT/forest CV programs, the linear/softmax metric sweeps — are
``jax.jit`` functions, so jit's own cache already dedups within a process.
This module makes that budget explicit and durable:

- ``run_cached(fn, *args, statics=..., label=...)`` lowers + AOT-compiles the
  program at most once per content-addressed key — (program fingerprint,
  operand shapes/dtypes/shardings, statics, lane layout, ambient mesh) — and
  dispatches through the cached executable afterwards.  The cache is
  process-wide: two selector instances (or two test modules) fitting the
  same-bucket sweep share one executable.
- The key's *stable fingerprint* (``cache_key_fingerprint``) hashes the
  program's SOURCE plus the operand signature, so it is identical across
  processes — paired with JAX's persistent compilation cache
  (``enable_persistent_cache``) a warm process pays zero backend compiles.
- ``program_cache_stats()`` exposes per-program compile counts, compile
  seconds, and hits — the numbers ``chipbench``'s selector entry snapshots
  around a window and tests assert against (compile-at-most-once-per-
  (family, bucket)).

Shape discipline: callers pad sweep row counts to power-of-two buckets
(``parallel.mesh.bucket_size`` — the serve/plan.py idea applied to training),
so nearby dataset sizes land on one key instead of each paying a fresh
lowering.
"""

from __future__ import annotations

import hashlib
import inspect
import logging
import os
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Tuple

import numpy as np

from ..obs import flight as obs_flight
from .timers import activity, compile_phase, measure_compiles

log = logging.getLogger(__name__)

_CACHE: Dict[tuple, Any] = {}
_STATS: Dict[tuple, "ProgramStats"] = {}
_LOCK = threading.RLock()
#: negative-cache sentinel: a key whose AOT call signature proved unusable
#: dispatches through jit forever after — never re-lowers per call
_FALLBACK = object()
#: source-hash memo keyed by the function OBJECT (strong ref: an id()-keyed
#: memo could serve a dead function's fingerprint to a new one reusing its id)
_SRC_FP: Dict[Any, str] = {}


@dataclass
class ProgramStats:
    """Per-key cache record (one sweep program at one operand signature)."""

    label: str
    fingerprint: str
    shapes: str
    compiles: int = 0
    hits: int = 0
    compile_seconds: float = 0.0
    backend_compiles: int = 0
    fallbacks: int = 0

    def to_dict(self) -> Dict[str, Any]:
        return {
            "label": self.label, "fingerprint": self.fingerprint[:16],
            "shapes": self.shapes, "compiles": self.compiles,
            "hits": self.hits,
            "compile_seconds": round(self.compile_seconds, 3),
            "backend_compiles": self.backend_compiles,
            "fallbacks": self.fallbacks,
        }


def _source_fingerprint(fn) -> str:
    """Hash of the program's python source (content-addressing: editing the
    kernel invalidates every cached key derived from it)."""
    target = inspect.unwrap(getattr(fn, "__wrapped__", fn))
    try:
        hit = _SRC_FP.get(target)
    except TypeError:  # unhashable callable
        hit, target = None, None
    if hit is not None:
        return hit
    try:
        src = inspect.getsource(target if target is not None else fn)
    except (OSError, TypeError):
        src = getattr(fn, "__qualname__", repr(fn))
    fp = hashlib.blake2b(src.encode(), digest_size=8).hexdigest()
    if target is not None:
        with _LOCK:  # concurrent run_cached callers race the memo write
            _SRC_FP[target] = fp
    return fp


def _sharding_sig(arr) -> Any:
    """Hashable sharding identity for a device array (None for host arrays).

    The ambient mesh object rides the key separately; here we only need the
    per-operand layout (PartitionSpec or device kind)."""
    sh = getattr(arr, "sharding", None)
    if sh is None:
        return None
    spec = getattr(sh, "spec", None)
    if spec is not None:
        mesh = getattr(sh, "mesh", None)
        mesh_sig = (tuple(mesh.axis_names), tuple(np.asarray(mesh.devices).shape)) \
            if mesh is not None else None
        return (str(spec), mesh_sig)
    return type(sh).__name__


def _arg_sig(a) -> tuple:
    shape = getattr(a, "shape", None)
    dtype = getattr(a, "dtype", None)
    if shape is not None and dtype is not None:
        return (tuple(shape), str(dtype), _sharding_sig(a))
    # non-array dynamic operand (python scalar): type only — the VALUE is a
    # runtime input, not part of the program
    return ("py", type(a).__name__)


def _static_item_sig(v) -> Any:
    if callable(v):
        # identity-stable registry functions: qualname for the stable
        # fingerprint; jit itself keys on identity, matching this
        return f"{getattr(v, '__module__', '?')}.{getattr(v, '__qualname__', repr(v))}"
    return v


def _mesh_sig():
    """Ambient-mesh component of every cache key: axis names/sizes PLUS the
    process topology (``mesh_token``) — a 2-host x 4-device mesh and a
    single-host 8-device mesh lower different collectives (DCN at the host
    boundary), so their executables must never alias."""
    from ..parallel.mesh import mesh_token

    return mesh_token()


def _make_key(fn, args, kwargs: Dict[str, Any], statics: Dict[str, Any],
              key_extras: Dict[str, Any]) -> Tuple[tuple, str, str]:
    """(in-memory key, stable fingerprint, shapes summary)."""
    from .kernels.dispatch import cache_token

    src_fp = _source_fingerprint(fn)
    arg_sigs = tuple(_arg_sig(a) for a in args)
    kwarg_sigs = tuple(sorted((k, _arg_sig(v)) for k, v in kwargs.items()))
    static_sig = tuple(sorted(
        (k, _static_item_sig(v)) for k, v in statics.items()))
    extra_sig = tuple(sorted(
        (k, _static_item_sig(v)) for k, v in key_extras.items()))
    mesh = _mesh_sig()
    name = f"{getattr(fn, '__module__', '?')}.{getattr(fn, '__qualname__', repr(fn))}"
    # the kernel dispatch mode (perf/kernels/dispatch.py) is baked into every
    # traced program, so it keys EVERY executable: flipping TMOG_PALLAS can
    # never serve a stale executable compiled for the other dispatch mode
    stable = (name, src_fp, arg_sigs, kwarg_sigs, static_sig, extra_sig,
              mesh, cache_token())
    fp = hashlib.blake2b(repr(stable).encode(), digest_size=16).hexdigest()
    # the in-memory key also carries the function OBJECT (jit-cache
    # semantics): two closures from one factory share source but bake in
    # different constants — identity keeps their executables apart, while
    # the stable fingerprint above stays source-based for cross-process use
    try:
        hash(fn)
        key = stable + (fn,)
    except TypeError:  # pragma: no cover — unhashable callable
        key = stable + (id(fn),)
    shapes = ",".join(
        "x".join(map(str, s[0])) if isinstance(s[0], tuple) else "scalar"
        for s in arg_sigs)
    return key, fp, shapes


def cache_key_fingerprint(fn, *args, kwargs: Optional[Dict[str, Any]] = None,
                          statics: Optional[Dict[str, Any]] = None,
                          key_extras: Optional[Dict[str, Any]] = None) -> str:
    """The stable (cross-process) content-addressed key of one program call.

    Deterministic in (program source, operand shapes/dtypes/shardings,
    statics, lane-layout key extras, ambient mesh) — tests pin this across
    interpreter runs."""
    return _make_key(fn, args, kwargs or {}, statics or {},
                     key_extras or {})[1]


def run_cached(fn, *args, kwargs: Optional[Dict[str, Any]] = None,
               statics: Optional[Dict[str, Any]] = None,
               key_extras: Optional[Dict[str, Any]] = None,
               label: Optional[str] = None,
               counts: Optional[Dict[str, Any]] = None):
    """Dispatch ``fn(*args, **kwargs, **statics)`` through the process-wide
    AOT cache.

    ``fn`` must be a ``jax.jit``-wrapped callable; ``statics`` are its
    static_argnames kwargs, ``kwargs`` its dynamic keyword operands.
    ``key_extras`` ride the cache key only — call sites thread module-level
    layout constants (``_HIST_CHUNK``) through here so changing one
    invalidates the cached executables it shaped.
    ``counts`` ride the dispatch's ``host.launch`` span beside its label.
    First call per key lowers + AOT-compiles (under the persistent
    compilation cache a warm process deserializes instead of compiling);
    later calls dispatch straight into the cached executable.  Falls back to
    a plain ``fn`` call when AOT lowering is unsupported for the given
    operands (stat: ``fallbacks``).

    Caveat on the fallback path only: ``fn``'s own jit cache keys on
    avals/statics, NOT on the kernel dispatch token, so a program that
    negative-cached under one ``TMOG_PALLAS`` mode and is re-called under
    another in the SAME process serves the first mode's jit executable.
    The AOT path (every program in practice — ``fallbacks`` counts the
    exceptions) is fully mode-keyed; in-process mode flips are a test-only
    pattern and the kernel parity tests call the kernel entry points
    directly.
    """
    kwargs = kwargs or {}
    statics = statics or {}
    counts = counts or {}
    with activity("program_key"):
        key, fp, shapes = _make_key(fn, args, kwargs, statics,
                                    key_extras or {})
    with _LOCK:
        compiled = _CACHE.get(key)
        stats = _STATS.get(key)
        if stats is None:
            stats = _STATS[key] = ProgramStats(
                label=label or key[0].rsplit(".", 1)[-1],
                fingerprint=fp, shapes=shapes)
    if compiled is _FALLBACK:
        # negative-cached: this key's AOT signature proved unusable once —
        # dispatch through jit without re-paying lower+compile per call
        with _LOCK:
            stats.fallbacks += 1
        return fn(*args, **kwargs, **statics)
    if compiled is None:
        with _LOCK:
            compiled = _CACHE.get(key)
            if compiled is None:
                t0 = time.perf_counter()
                try:
                    with compile_phase(stats.label), \
                            obs_flight.compile_context(
                                f"perf.run_cached:{stats.label}",
                                fingerprint=fp), \
                            measure_compiles() as delta:
                        compiled = fn.lower(*args, **kwargs,
                                            **statics).compile()
                        backend = delta.backend_compiles
                except Exception as e:
                    stats.fallbacks += 1
                    _CACHE[key] = _FALLBACK  # never re-lower this key
                    log.warning("AOT lowering failed for %s (%s); calling "
                                "through jit", stats.label, e)
                    return fn(*args, **kwargs, **statics)
                stats.compiles += 1
                stats.compile_seconds += time.perf_counter() - t0
                stats.backend_compiles += backend
                try:
                    with activity("launch", label=stats.label, **counts):
                        out = compiled(*args, **kwargs)
                except TypeError as e:
                    # statics that are NOT static_argnames of fn end up in
                    # the compiled in_tree and the AOT call signature breaks;
                    # negative-cache the key and serve through jit forever
                    # after (correctness over caching — the misuse also
                    # shows up in ``fallbacks``, and the key must not
                    # re-pay lower+compile on every call)
                    stats.fallbacks += 1
                    _CACHE[key] = _FALLBACK
                    log.warning("AOT call failed for %s (%s); calling "
                                "through jit", stats.label, e)
                    return fn(*args, **kwargs, **statics)
                _CACHE[key] = compiled
                return out
    with _LOCK:
        stats.hits += 1
    with activity("launch", label=stats.label, **counts):
        return compiled(*args, **kwargs)


def evict_program_entries(fns) -> int:
    """Drop every cache/stat entry keyed on one of ``fns`` (by identity).

    The in-memory key's last component is the function object itself, so
    per-instance jitted closures (the transform planner's fused programs) can
    release their executables when their owning plan is evicted — without
    this, a long-running process doing repeated trains would pin every dead
    plan's closure, fitted constants, and executables in the unbounded cache.
    Returns the number of entries removed.
    """
    targets = {id(f) for f in fns}
    removed = 0
    with _LOCK:
        for key in [k for k in _CACHE if id(k[-1]) in targets]:
            _CACHE.pop(key, None)
            _STATS.pop(key, None)
            removed += 1
    return removed


def program_cache_stats() -> Dict[str, Any]:
    """Aggregate + per-program cache counters (``chipbench``'s entries and
    ``chip_smoke.py`` read them)."""
    with _LOCK:
        entries = [s.to_dict() for s in _STATS.values()]
    return {
        "programs_compiled": sum(e["compiles"] for e in entries),
        "cache_hits": sum(e["hits"] for e in entries),
        "compile_seconds": round(sum(e["compile_seconds"] for e in entries), 3),
        "fallbacks": sum(e["fallbacks"] for e in entries),
        "programs": entries,
    }


def program_cache_entries() -> Dict[tuple, ProgramStats]:
    """Live per-key stats (tests: compile-at-most-once-per-(family, bucket))."""
    with _LOCK:
        return dict(_STATS)


def clear_program_cache() -> None:
    with _LOCK:
        _CACHE.clear()
        _STATS.clear()


# ---------------------------------------------------------------------------
# Persistent compilation cache wiring
# ---------------------------------------------------------------------------

#: where the cache goes when nothing outside says otherwise: ONE fixed path
#: inside the checkout (never ``~``, a temp name, a pid or a time — every
#: process of this checkout has to look in the same directory to find what
#: an earlier one compiled)
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_persistent_cache() -> Optional[str]:
    """Turn on JAX's persistent compilation cache; return its directory.

    The directory is placed from OUTSIDE: when ``JAX_COMPILATION_CACHE_DIR``
    is set (or the caller already ran ``jax.config.update`` on
    ``jax_compilation_cache_dir``) JAX holds it and this function sets no
    other; only a completely unset config gets :data:`DEFAULT_CACHE_DIR`.
    Every compile is persisted (minimum compile time 0 s, minimum entry
    size 0), so a second process finds every program the first one built.
    ``TMOG_PERSISTENT_CACHE=0`` leaves the cache off (returns None).
    """
    if os.environ.get("TMOG_PERSISTENT_CACHE", "1") == "0":
        return None
    import jax

    if not jax.config.jax_compilation_cache_dir:
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax.config.jax_compilation_cache_dir


# ---------------------------------------------------------------------------
# AOT executable (de)serialization — the deploy/ artifact payload format
# ---------------------------------------------------------------------------

def serialize_compiled(compiled) -> bytes:
    """One AOT-compiled executable -> bytes (the deploy/ artifact payload).

    Wraps ``jax.experimental.serialize_executable``: the XLA executable
    payload plus the call's arg/result treedefs, pickled together so a cold
    process can rehydrate a *runnable* compiled object with ZERO backend
    compiles (``jax.export``'s deserialized form re-compiles on call, which
    would defeat the whole point).  The pickle is jax-version-coupled —
    deploy manifests record ``jax.__version__`` so a drifted reader refuses
    (TM510) instead of unpickling bytes written by another version.

    Raises ``TypeError`` for objects the jax build cannot serialize; callers
    decide whether that is fatal (pack) or a skip (best-effort export).
    """
    import pickle

    import jax
    from jax.experimental import serialize_executable as _se

    payload, in_tree, out_tree = _se.serialize(compiled)
    return pickle.dumps({
        "format": "tmog-aot-v1",
        "jax": jax.__version__,
        "payload": payload,
        "in_tree": in_tree,
        "out_tree": out_tree,
    })


def deserialize_compiled(blob: bytes):
    """bytes (from :func:`serialize_compiled`) -> runnable compiled object.

    Zero backend compiles: the deserialized executable dispatches directly.
    ``ValueError`` on a foreign/garbled blob.  Integrity is the CALLER's
    job: the deploy store verifies the manifest's content hash BEFORE this
    unpickle, so truncated or tampered bytes never reach pickle at all.
    """
    import pickle

    from jax.experimental import serialize_executable as _se

    try:
        d = pickle.loads(blob)
    except Exception as e:
        raise ValueError(f"unreadable AOT executable blob: {e}") from e
    if not isinstance(d, dict) or d.get("format") != "tmog-aot-v1":
        raise ValueError("not a tmog-aot-v1 executable blob")
    return _se.deserialize_and_load(d["payload"], d["in_tree"],
                                    d["out_tree"])
