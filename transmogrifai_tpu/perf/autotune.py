"""Persistent kernel autotuner: sweep, verify, cache the winner (ISSUE 19).

Reference role: the reference's whole value proposition is automatic
selection over a candidate space — ModelSelector sweeps estimators and
grids, scores each candidate, and keeps the winner.  This module applies
the same "sweep, score, cache" discipline one level down, to the Pallas/XLA
kernel configurations behind ``perf/kernels/``: on first contact with a
``(device_kind, kernel family, shape-class)`` triple it times a bounded
candidate grid (hist chunk/unroll, the VMEM-resident double-buffer variant,
encode/routing block shapes, split-scan lane blocking), verifies every
candidate against the reference formulation BEFORE it is eligible, and
persists the winner in a content-addressed, schema-versioned JSON store
next to the executable cache.

Contracts (acceptance: ISSUE 19):

- **At most one sweep per triple per store.**  ``ensure_tuned`` memoizes
  in-process under a per-key lock (two racing first-contact threads produce
  ONE sweep) and a warm store answers every later process from disk —
  zero sweeps, zero warm-path compiles.
- **Verified before eligible.**  A candidate that fails bitwise parity on
  the exact-integer fixture (hist/encode/route/split all verify bitwise;
  the float hist path additionally within ``_FLOAT_TOL``) — or that fails
  to compile at all — can never win.  The winner entry records
  ``verified: true``; entries without it are ignored on load.
- **Winners ride ``dispatch.cache_token()``.**  Adopting any non-default
  winner folds a ``tune=<digest>`` component into the token, so tuned
  executables never alias untuned ones in ``run_cached``, the serving
  ``_EXEC_CACHE``, or PR 17 deploy artifacts.  Loading the store happens
  eagerly through ``tuning_token()`` (which ``cache_token()`` calls), never
  lazily inside a trace — the token a program was keyed under always
  reflects the winners its trace could see.
- **Corrupt / stale entries fall back to defaults, never crash.**  A
  truncated JSON file, a schema-version mismatch, or a foreign device_kind
  all read as "no winner"; ``clear()`` removes entries.

Sweeping is explicit or armed: ``ensure_tuned(..., sweep_on_miss=True)``
and ``cli tune run`` sweep directly;
setting ``TMOG_AUTOTUNE=1`` arms first-contact sweeps in ``ensure_tuned``.
The kernel dispatchers themselves only ever consume cached winners (via
``kernel_param``) — a production trace never pays sweep time.

See docs/performance.md "Kernel autotuning".
"""

from __future__ import annotations

import hashlib
import json
import logging
import math
import os
import threading
import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Tuple

from .kernels import dispatch as _dispatch

log = logging.getLogger(__name__)

#: store schema — bump on any incompatible entry-layout change; mismatched
#: entries read as absent (defaults), never as errors
SCHEMA_VERSION = 1

#: documented tolerance for the float histogram verification pass (the
#: integer fixtures verify bitwise; see docs/performance.md)
_FLOAT_TOL = 1e-3

#: timing repetitions per candidate (min-of-reps, compile excluded)
_SWEEP_REPS = 3

_GUARD_LOCK = threading.Lock()
#: (device_kind, family, shape_class) -> TuneDecision, guarded by _GUARD_LOCK
_MEMO: Dict[Tuple[str, str, str], "TuneDecision"] = {}
#: per-key sweep locks so one first-contact sweep wins; guarded by _GUARD_LOCK
_KEY_LOCKS: Dict[Tuple[str, str, str], threading.Lock] = {}
#: store dirs already bulk-loaded into _MEMO; guarded by _GUARD_LOCK
_LOADED_DIRS: set = set()
#: process-lifetime sweep counter (tests pin "at most one sweep per triple")
_SWEEPS = 0


@dataclass(frozen=True)
class TuneDecision:
    """The resolved tuning for one (device_kind, family, shape_class)."""

    family: str
    shape_class: str
    device_kind: str
    params: Dict[str, Any]
    source: str                      # "default" | "cached" | "swept"
    verified: bool = False
    candidates: int = 0
    best_seconds: Optional[float] = None
    default_seconds: Optional[float] = None

    def is_default(self) -> bool:
        return self.params == family_defaults(self.family, self.shape_class)


# ---------------------------------------------------------------------------
# Store: content-addressed JSON entries, atomic writes, fail-open reads
# ---------------------------------------------------------------------------

def store_dir() -> str:
    """The winner store: ``TMOG_AUTOTUNE_DIR``, else the ``autotune`` sibling
    of the persistent executable cache default."""
    return (os.environ.get("TMOG_AUTOTUNE_DIR")
            or os.path.expanduser("~/.cache/transmogrifai_tpu/autotune"))


def device_kind() -> str:
    """Sanitized accelerator identity for store keys (``cpu`` off-device)."""
    try:
        import jax

        devs = jax.devices()
        raw = devs[0].device_kind if devs else "cpu"
    except Exception:  # pragma: no cover — backend init failure
        raw = "cpu"
    return "".join(c if c.isalnum() or c in "-_" else "-"
                   for c in str(raw).strip().lower()) or "cpu"


def _entry_path(device: str, family: str, shape_class: str,
                store: Optional[str] = None) -> str:
    key = f"tmog-autotune|{SCHEMA_VERSION}|{device}|{family}|{shape_class}"
    digest = hashlib.blake2b(key.encode(), digest_size=10).hexdigest()
    return os.path.join(store or store_dir(), f"{family}-{digest}.json")


def _write_atomic(path: str, payload: Dict[str, Any]) -> None:
    """Torn-write-free entry write: tmp file + fsync + atomic replace (the
    deploy/store.py discipline — a concurrent reader sees the old entry or
    the new one, never a prefix)."""
    os.makedirs(os.path.dirname(path), exist_ok=True)
    tmp = f"{path}.tmp.{os.getpid()}.{threading.get_ident()}"
    data = json.dumps(payload, sort_keys=True, indent=1).encode()
    with open(tmp, "wb") as fh:
        fh.write(data)
        fh.flush()
        os.fsync(fh.fileno())
    os.replace(tmp, path)


def _read_entry(path: str) -> Optional[Dict[str, Any]]:
    """One store entry, fail-open: corrupt JSON, schema drift, or an
    unverified sweep all read as None (defaults) — never an exception."""
    try:
        with open(path, "rb") as fh:
            entry = json.loads(fh.read().decode("utf-8"))
    except (OSError, ValueError, UnicodeDecodeError):
        return None
    if not isinstance(entry, dict):
        return None
    if entry.get("schema") != SCHEMA_VERSION:
        log.warning("autotune: schema %r != %d in %s — ignoring entry",
                    entry.get("schema"), SCHEMA_VERSION, path)
        return None
    if not entry.get("verified") or not isinstance(entry.get("params"), dict):
        return None
    return entry


def winners(store: Optional[str] = None) -> List[Dict[str, Any]]:
    """Every readable winner entry in the store (cli ``tune show``)."""
    root = store or store_dir()
    out: List[Dict[str, Any]] = []
    try:
        names = sorted(os.listdir(root))
    except OSError:
        return out
    for name in names:
        if not name.endswith(".json"):
            continue
        entry = _read_entry(os.path.join(root, name))
        if entry is not None:
            out.append(entry)
    return out


def clear(store: Optional[str] = None) -> int:
    """Remove every entry (cli ``tune clear``); resets in-process adoption
    so the next lookup re-reads the (now empty) store."""
    root = store or store_dir()
    removed = 0
    try:
        names = os.listdir(root)
    except OSError:
        names = []
    for name in names:
        if name.endswith(".json"):
            try:
                os.unlink(os.path.join(root, name))
                removed += 1
            except OSError:  # pragma: no cover — concurrent clear
                pass
    reset()
    return removed


def reset() -> None:
    """Drop in-process adoption state (tests; ``clear``).  The next
    ``tuning_token()`` / lookup reloads the store from disk."""
    global _SWEEPS
    with _GUARD_LOCK:
        _MEMO.clear()
        _KEY_LOCKS.clear()
        _LOADED_DIRS.clear()
        _SWEEPS = 0
        _push_token_locked()


def sweep_count() -> int:
    """Sweeps performed by this process (tests pin once-per-triple)."""
    with _GUARD_LOCK:
        return _SWEEPS


# ---------------------------------------------------------------------------
# Shape classes and family registry
# ---------------------------------------------------------------------------

def _log2_bucket(n: int) -> int:
    return max(1, int(math.ceil(math.log2(max(int(n), 2)))))


def shape_class(family: str, mode: Optional[str] = None,
                **dims: int) -> str:
    """Canonical shape-class string: the kernel mode plus every structural
    dim, with row counts log2-bucketed so nearby batch sizes share a
    winner.  The mode is folded in because a winner swept for the XLA scan
    says nothing about the Pallas grid (and vice versa)."""
    mode = mode or _dispatch.kernel_mode()
    parts = [mode]
    for name in sorted(dims):
        v = int(dims[name])
        if name in ("rows", "n"):
            parts.append(f"{name}2^{_log2_bucket(v)}")
        else:
            parts.append(f"{name}{v}")
    return f"{family}:" + ":".join(parts)


def _mode_of(shape_cls: str) -> str:
    body = shape_cls.split(":", 1)[1] if ":" in shape_cls else shape_cls
    return body.split(":", 1)[0]


#: default sweep fixture dims per family — small enough to sweep on a CPU
#: CI host, large enough that block-shape choices change the timing
DEFAULT_DIMS: Dict[str, Dict[str, int]] = {
    "hist": {"rows": 4096, "features": 16, "bins": 8, "lanes": 2,
             "nodes": 8, "classes": 1},
    "split": {"lanes": 4, "nodes": 8, "classes": 1, "features": 16,
              "bins": 8},
    "encode": {"rows": 4096, "width": 16},
    "route": {"rows": 4096, "features": 16, "lanes": 4},
}

FAMILIES = tuple(sorted(DEFAULT_DIMS))


def family_defaults(family: str, shape_cls: str) -> Dict[str, Any]:
    """The untuned parameter set for a family under the class's mode — what
    the kernels use when the store has no winner."""
    mode = _mode_of(shape_cls)
    if family == "hist":
        if mode == "xla":
            return {"chunk": _dispatch.HIST_CHUNK_DEFAULT,
                    "unroll": _dispatch.HIST_UNROLL_DEFAULT}
        return {"chunk": _dispatch.HIST_CHUNK_DEFAULT, "variant": "stream"}
    if family == "encode":
        return {"block": 1024}
    if family == "route":
        return {"block": 256}
    if family == "split":
        return {"lane_block": 1}
    raise ValueError(f"unknown autotune family {family!r}")


def family_candidates(family: str, shape_cls: str) -> List[Dict[str, Any]]:
    """The bounded candidate grid for one family under the class's mode.
    The default parameter set is always candidate 0, so a sweep can only
    improve on (never silently regress) the untuned configuration."""
    mode = _mode_of(shape_cls)
    grid: List[Dict[str, Any]] = [family_defaults(family, shape_cls)]
    if family == "hist":
        if mode == "xla":
            grid += [{"chunk": c, "unroll": u}
                     for c in (512, 1024, 2048, 4096) for u in (1, 2)]
        else:
            # "resident" is the double-buffer-free variant: every operand
            # VMEM-resident, the kernel loops chunks internally with no
            # per-step DMA; "stream" is the grid pipeline (double-buffered
            # block DMA on TPU)
            grid += [{"chunk": c, "variant": v}
                     for c in (512, 1024, 2048) for v in ("stream",
                                                          "resident")]
    elif family == "encode":
        grid += [{"block": b} for b in (256, 512, 1024, 2048)]
    elif family == "route":
        grid += [{"block": b} for b in (128, 256, 512, 1024)]
    elif family == "split":
        if mode != "xla":  # the XLA path has no lane-blocking knob
            grid += [{"lane_block": b} for b in (1, 2, 4)]
    seen: List[Dict[str, Any]] = []
    for cand in grid:
        if cand not in seen:
            seen.append(cand)
    return seen


def _family_bench(family: str, dims: Dict[str, int], mode: str
                  ) -> Tuple[Callable[[Dict[str, Any]], Callable], Callable]:
    """(make_runner, reference) for one family: ``make_runner(params)``
    returns a zero-arg jitted callable producing the candidate's output;
    ``reference()`` the ground-truth array every candidate must match
    bitwise.  Imports stay function-level: the sweep is the only caller
    that needs jax."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    interpret = mode != "pallas"
    rng = np.random.default_rng(17)

    if family == "hist":
        from .kernels import histogram as KH

        L, n = dims["lanes"], dims["rows"]
        d, n_bins = dims["features"], dims["bins"]
        nn, two_k = dims["nodes"], 2 * dims["classes"]
        local = jnp.asarray(rng.integers(-1, nn, (L, n)).astype(np.int32))
        ghT = jnp.asarray(
            rng.integers(-3, 4, (L, two_k, n)).astype(np.int8))
        binned = jnp.asarray(
            rng.integers(0, n_bins + 1, (n, d)).astype(np.int32))

        def make(params):
            if mode == "xla":
                fn = jax.jit(lambda a, b, c: KH.hist_level_xla(  # opcheck: allow(TM303) sweep-time jit per candidate IS the sweep; never traced in serving
                    a, b, c, nn, n_bins, int_exact=True,
                    chunk=int(params["chunk"]),
                    unroll=int(params.get("unroll", 1))))
            else:
                fn = jax.jit(lambda a, b, c: KH.hist_level_pallas(  # opcheck: allow(TM303) sweep-time jit per candidate IS the sweep; never traced in serving
                    a, b, c, nn, n_bins, int_exact=True,
                    interpret=interpret, chunk=int(params["chunk"]),
                    variant=str(params.get("variant", "stream"))))
            return lambda: fn(local, ghT, binned)

        def reference():
            return np.asarray(KH.hist_level_xla(  # opcheck: allow(TM301) sweep timing/verify requires the host sync; off the serving path
                local, ghT, binned, nn, n_bins, int_exact=True,
                chunk=_dispatch.HIST_CHUNK_DEFAULT))

        return make, reference

    if family == "encode":
        from .kernels import encode as KE

        n, width = dims["rows"], dims["width"]
        codes = jnp.asarray(
            rng.integers(-1, width + 1, n).astype(np.int32))

        def make(params):
            fn = jax.jit(lambda c: KE.onehot_codes(  # opcheck: allow(TM303) sweep-time jit per candidate IS the sweep; never traced in serving
                c, width, interpret=interpret,
                block=int(params["block"])))
            return lambda: fn(codes)

        def reference():
            return np.asarray(  # opcheck: allow(TM301) sweep timing/verify requires the host sync; off the serving path
                jax.nn.one_hot(codes, width, dtype=jnp.float32))

        return make, reference

    if family == "route":
        from .kernels import routing as KR

        n, d, L = dims["rows"], dims["features"], dims["lanes"]
        binned = jnp.asarray(rng.integers(0, 9, (n, d)).astype(np.int32))
        idx = jnp.asarray(rng.integers(0, d, (L, n)).astype(np.int32))

        def make(params):
            fn = jax.jit(lambda b, i: KR.row_select_lanes_pallas(  # opcheck: allow(TM303) sweep-time jit per candidate IS the sweep; never traced in serving
                b, i, interpret=interpret, block=int(params["block"])))
            return lambda: fn(binned, idx)

        def reference():
            return np.asarray(KR.row_select_lanes_xla(binned, idx))  # opcheck: allow(TM301) sweep timing/verify requires the host sync; off the serving path

        return make, reference

    if family == "split":
        from .kernels import splitscan as KS

        L, nn, K = dims["lanes"], dims["nodes"], dims["classes"]
        d, n_bins = dims["features"], dims["bins"]
        B = n_bins + 1
        # a histogram the grower could have built: EVERY feature's bins sum
        # to the node totals (independent draws per feature leave negative
        # right-child hessians, where the gain formula's eps guard — and
        # with it the comparison — degenerates to 1/0 vs 1/1e-12)
        cells = (L, nn, K, d)
        even = np.full(B, 1.0 / B)

        def spread(totals):
            return rng.multinomial(
                np.broadcast_to(totals[..., None], cells), even)

        pos, neg = (rng.integers(0, 120, (L, nn, K)) for _ in range(2))
        tot_h = rng.integers(B, 8 * B, (L, nn, K))
        hg = jnp.asarray((spread(pos) - spread(neg)).astype(np.float32))
        hh = jnp.asarray(spread(tot_h).astype(np.float32))
        G = jnp.asarray((pos - neg).astype(np.float32))
        H = jnp.asarray(tot_h.astype(np.float32))
        mask = jnp.ones((L, d), jnp.float32)
        params_f = tuple(jnp.float32(v) for v in (1.0, 0.5, 0.1, 1.0))

        def make(params):
            fn = jax.jit(lambda a, b, g, h, m: KS.split_scan_pallas(  # opcheck: allow(TM303) sweep-time jit per candidate IS the sweep; never traced in serving
                a, b, g, h, m, n_bins, *params_f, interpret=interpret,
                lane_block=int(params["lane_block"])))
            return lambda: fn(hg, hh, G, H, mask)

        def reference():
            b, g, m = KS.split_scan_xla(hg, hh, G, H, mask, n_bins,
                                        *params_f)
            return np.stack([np.asarray(b).astype(np.float64),  # opcheck: allow(TM301) sweep timing/verify requires the host sync; off the serving path
                             np.asarray(g).astype(np.float64),  # opcheck: allow(TM301) sweep timing/verify requires the host sync; off the serving path
                             np.asarray(m).astype(np.float64)])  # opcheck: allow(TM301) sweep timing/verify requires the host sync; off the serving path

        return make, reference

    raise ValueError(f"unknown autotune family {family!r}")


def _as_comparable(out) -> "Any":
    import numpy as np

    if isinstance(out, tuple):
        return np.stack([np.asarray(o).astype(np.float64) for o in out])
    return np.asarray(out)


def _verify(candidate, reference, family: str) -> bool:
    """Bitwise on the integer fixtures (every family's sweep fixture is
    integer-valued, so float accumulation order cannot drift); the float
    hist path's documented tolerance ``_FLOAT_TOL`` backstops dtype
    promotion differences."""
    import numpy as np

    cand = _as_comparable(candidate)
    ref = _as_comparable(reference)
    if cand.shape != ref.shape:
        return False
    if np.array_equal(cand, ref):
        return True
    if family == "hist" and np.allclose(cand, ref, atol=_FLOAT_TOL, rtol=0):
        return True
    return False


# ---------------------------------------------------------------------------
# The sweep
# ---------------------------------------------------------------------------

def sweep(family: str, dims: Optional[Dict[str, int]] = None, *,
          store: Optional[str] = None, mode: Optional[str] = None,
          reps: int = _SWEEP_REPS) -> TuneDecision:
    """Time the bounded candidate grid for one family/shape-class, verify
    every candidate against the reference, persist and adopt the winner.

    Compile time is excluded (each candidate runs once before its timed
    reps); an unverified or crashing candidate is ineligible.  Returns the
    swept decision (source ``"swept"``)."""
    global _SWEEPS
    if family not in DEFAULT_DIMS:
        raise ValueError(f"unknown autotune family {family!r} "
                         f"(known: {', '.join(FAMILIES)})")
    import numpy as np

    dims = dict(DEFAULT_DIMS[family], **(dims or {}))
    mode = mode or _dispatch.kernel_mode()
    cls = shape_class(family, mode, **dims)
    device = device_kind()
    defaults = family_defaults(family, cls)
    make, reference = _family_bench(family, dims, mode)
    ref = reference()

    best_params, best_dt = dict(defaults), None
    default_dt = None
    eligible = 0
    candidates = family_candidates(family, cls)
    for params in candidates:
        try:
            run = make(params)
            out = run()                      # compile + warm — excluded
            if not _verify(out, ref, family):
                log.warning("autotune: %s candidate %r failed parity — "
                            "ineligible", family, params)
                continue
            dt = min(_time_once(run, np) for _ in range(max(1, reps)))
        except Exception as exc:  # noqa: BLE001 — candidate must not crash
            log.warning("autotune: %s candidate %r failed (%s: %s) — "
                        "ineligible", family, params, type(exc).__name__,
                        exc)
            continue
        eligible += 1
        if params == defaults:
            default_dt = dt
        if best_dt is None or dt < best_dt:
            best_params, best_dt = dict(params), dt

    decision = TuneDecision(
        family=family, shape_class=cls, device_kind=device,
        params=best_params, source="swept", verified=eligible > 0,
        candidates=len(candidates), best_seconds=best_dt,
        default_seconds=default_dt)
    entry = {
        "schema": SCHEMA_VERSION, "device_kind": device, "family": family,
        "shape_class": cls, "params": best_params,
        "verified": decision.verified, "candidates": len(candidates),
        "eligible": eligible, "best_seconds": best_dt,
        "default_seconds": default_dt, "swept_unix": round(time.time(), 3),
    }
    try:
        _write_atomic(_entry_path(device, family, cls, store), entry)
    except OSError as exc:  # pragma: no cover — read-only store
        log.warning("autotune: could not persist %s winner: %s", family, exc)
    with _GUARD_LOCK:
        _SWEEPS += 1
        _MEMO[(device, family, cls)] = decision
        _push_token_locked()
    return decision


def _time_once(run, np) -> float:
    t0 = time.perf_counter()
    out = run()
    if isinstance(out, tuple):
        np.asarray(out[0])
    else:
        np.asarray(out)
    return time.perf_counter() - t0


# ---------------------------------------------------------------------------
# Adoption: memoized store reads, the cache-token component
# ---------------------------------------------------------------------------

def _load_store_locked(root: str) -> None:
    """Bulk-adopt every verified winner for THIS device from ``root`` into
    the in-process memo (once per store dir).  Caller holds _GUARD_LOCK."""
    if root in _LOADED_DIRS:
        return
    _LOADED_DIRS.add(root)  # opcheck: allow(TM306) caller holds _GUARD_LOCK (the _locked suffix contract)
    device = device_kind()
    try:
        names = sorted(os.listdir(root))
    except OSError:
        return
    for name in names:
        if not name.endswith(".json"):
            continue
        entry = _read_entry(os.path.join(root, name))
        if entry is None or entry.get("device_kind") != device:
            continue
        key = (device, str(entry["family"]), str(entry["shape_class"]))
        if key not in _MEMO:
            _MEMO[key] = TuneDecision(  # opcheck: allow(TM306) caller holds _GUARD_LOCK (the _locked suffix contract)
                family=key[1], shape_class=key[2], device_kind=device,
                params=dict(entry["params"]), source="cached",
                verified=True, candidates=int(entry.get("candidates", 0)),
                best_seconds=entry.get("best_seconds"),
                default_seconds=entry.get("default_seconds"))
    _push_token_locked()


def _push_token_locked() -> None:
    """Recompute the cache-token component from every adopted non-default
    winner and install it in the dispatch layer.  Caller holds _GUARD_LOCK."""
    tuned = {}
    for (device, family, cls), dec in _MEMO.items():
        if dec.source in ("cached", "swept") and not dec.is_default():
            tuned[f"{device}|{family}|{cls}"] = dec.params
    if not tuned:
        _dispatch._set_tuning_token("")
        return
    blob = json.dumps(tuned, sort_keys=True).encode()
    digest = hashlib.blake2b(blob, digest_size=6).hexdigest()
    _dispatch._set_tuning_token(f"tune={digest}")


def tuning_token() -> str:
    """Load-the-store-then-report: the ``tune=<digest>`` cache-token
    component over every adopted non-default winner ("" when untuned).
    ``dispatch.cache_token()`` calls this, so any program key computed
    after this point reflects the winners its trace can observe."""
    with _GUARD_LOCK:
        _load_store_locked(store_dir())
    return _dispatch._tuning_token()


def lookup(family: str, shape_cls: str) -> Optional[TuneDecision]:
    """The adopted decision for a triple, loading the store on first use;
    None when the store has no verified winner.  Never sweeps."""
    with _GUARD_LOCK:
        _load_store_locked(store_dir())
        return _MEMO.get((device_kind(), family, shape_cls))


def kernel_param(family: str, shape_cls: str, name: str, fallback):
    """What the kernel dispatchers call at trace time: the winner's value
    for one parameter, else ``fallback``.  Reads the in-process memo (the
    store loads once, eagerly, via ``tuning_token``/``cache_token``)."""
    dec = lookup(family, shape_cls)
    if dec is not None and name in dec.params:
        return dec.params[name]
    return fallback


def ensure_tuned(family: str, dims: Optional[Dict[str, int]] = None, *,
                 sweep_on_miss: Optional[bool] = None,
                 store: Optional[str] = None,
                 mode: Optional[str] = None) -> TuneDecision:
    """First-contact entry point: memo -> warm store -> (optionally) ONE
    sweep -> defaults.

    ``sweep_on_miss=None`` resolves from ``TMOG_AUTOTUNE`` (armed on real
    silicon, off in CI); two threads racing the same cold triple serialize
    on a per-key lock and the loser adopts the winner's result — exactly
    one sweep, no torn store writes."""
    if family not in DEFAULT_DIMS:
        raise ValueError(f"unknown autotune family {family!r} "
                         f"(known: {', '.join(FAMILIES)})")
    dims = dict(DEFAULT_DIMS[family], **(dims or {}))
    mode = mode or _dispatch.kernel_mode()
    cls = shape_class(family, mode, **dims)
    device = device_kind()
    key = (device, family, cls)
    if sweep_on_miss is None:
        sweep_on_miss = os.environ.get("TMOG_AUTOTUNE", "").strip() \
            in ("1", "on", "true", "sweep")
    with _GUARD_LOCK:
        _load_store_locked(store or store_dir())
        hit = _MEMO.get(key)
        if hit is not None:
            return hit
        klock = _KEY_LOCKS.setdefault(key, threading.Lock())
    with klock:
        with _GUARD_LOCK:
            hit = _MEMO.get(key)
            if hit is not None:          # the racing sweep already landed
                return hit
        entry = _read_entry(_entry_path(device, family, cls, store))
        if entry is not None:
            dec = TuneDecision(
                family=family, shape_class=cls, device_kind=device,
                params=dict(entry["params"]), source="cached",
                verified=True, candidates=int(entry.get("candidates", 0)),
                best_seconds=entry.get("best_seconds"),
                default_seconds=entry.get("default_seconds"))
        elif sweep_on_miss:
            return sweep(family, dims, store=store, mode=mode)
        else:
            dec = TuneDecision(
                family=family, shape_class=cls, device_kind=device,
                params=family_defaults(family, cls), source="default")
        with _GUARD_LOCK:
            _MEMO[key] = dec
            _push_token_locked()
        return dec


def provenance() -> Dict[str, Any]:
    """The ``tuning`` provenance block: token, store, and every adopted
    winner with its source (``default`` entries are omitted — absence IS
    the default)."""
    with _GUARD_LOCK:
        _load_store_locked(store_dir())
        adopted = {
            f"{family}/{cls}": {"params": dict(dec.params),
                                "source": dec.source}
            for (_dev, family, cls), dec in sorted(_MEMO.items())
            if dec.source != "default"
        }
    return {
        "token": _dispatch._tuning_token(),
        "store": store_dir(),
        "winners": adopted,
        "sweeps_this_process": sweep_count(),
    }
