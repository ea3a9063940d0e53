"""Kernel dispatch layer: who runs a tree/encode hot loop, and how we know.

Reference role (SURVEY §2.9): the reference dispatches its tree hot loops to
XGBoost's native C++ kernels through the JNI when the library is present and
falls back to Spark MLlib's JVM trees otherwise.  This module is that
decision point for the TPU port — Pallas kernels vs the tuned XLA reference
formulation — with the decision itself made observable and cacheable:

- ``kernel_mode()`` resolves the effective mode from ``TMOG_PALLAS``:

  =============  ==========================================================
  ``TMOG_PALLAS``  effective mode
  =============  ==========================================================
  unset / ``1`` / ``auto``   ``pallas`` on a TPU backend — except under a
                             multi-device ``use_mesh`` (see below) — and
                             ``xla`` elsewhere
  ``0`` / ``off`` / ``xla``  ``xla`` everywhere — the escape hatch
  ``interpret``              ``pallas.interpret=True`` emulation (CPU/CI
                             parity tests; jittable, runs anywhere)
  ``pallas``                 force compiled Pallas even off-TPU (expert)
  =============  ==========================================================

- ``cache_token()`` is the kernel-choice fingerprint.  It rides EVERY
  ``perf.programs.run_cached`` key and every plan content fingerprint
  (``workflow.plan.stage_content_fingerprint``), so flipping the dispatch
  mode can never serve a stale executable compiled for the other mode —
  the same fallback discipline the fused transform planner established
  (``TMOG_FUSED_TRANSFORM``, PR 4).
- VMEM admission guards (``hist_mode``/``split_mode``/``route_mode``/
  ``encode_mode``): compiled Pallas keeps its accumulator and operands
  resident in VMEM, so a shape whose working set exceeds the budget
  (``TMOG_PALLAS_VMEM_BUDGET``, default 10 MiB of the compiler's 16 MiB
  scoped limit — see ``_DEFAULT_VMEM_BUDGET``) takes the XLA path by this
  module's DECISION, before the compiler is asked.  Nothing here catches a
  compiler error and reroutes: a kernel that is selected and then refused
  fails the program.  Interpret mode has no such limit.
- What ``auto`` selects on a TPU, per kernel, as established on a v5e with
  jax 0.9.0 / libtpu 0.0.34 (CHANGES.md PR 21; ``chip_smoke.py`` re-proves
  it):

  ====================  =================================================
  kernel                ``auto`` on TPU
  ====================  =================================================
  ``split_scan``        selected (all tree levels at d=128, 32 bins).
                        Repaired in PR 21: the (8, 128) block rule and the
                        missing ``cumsum`` lowering refused the original.
  ``row_select_lanes``  never (PR 33): the walk's entry
                        (``level_select_lanes``) runs XLA's own code in
                        every mode.  The kernel pads the
                        lane axis to 128, so it took 74.5 ms a call at
                        2^20 x 128 codes for three lanes and for one (22.3
                        of a boosted fit's 26.3 s) where the XLA
                        compare-reduce takes 2.3 and 0.9 ms; ``route_mode``
                        only answers callers that name the kernel
                        themselves.
  ``onehot_codes``,     selected at every serving width.
  ``bucketize_right``
  ``hist_level``        selected only where the working set fits; at
                        d=128, 33 bins and the default 2048-row chunk the
                        (chunk, B*d) one-hot alone is 8.6-17 MB, so the
                        headline shapes run the XLA scan.  Repaired in
                        PR 21 for the int8 path (no int8 multiply or
                        sublane broadcast on the v5e vector unit).
  ====================  =================================================

  Under a multi-device ambient mesh (``parallel.mesh.use_mesh``) ``auto``
  selects NONE of them: jax cannot partition a Mosaic kernel automatically
  and the kernels are not wrapped in ``shard_map`` yet, so the sharded
  programs keep the XLA formulations (``chip_smoke.py --mesh 2x2``).

  ``kernel_selections()`` counts the decisions this process actually made
  (at trace time), so a run can show which kernels it really used.
- ``tuning_int()`` is the one helper every env-overridable tuning knob
  reads through (``TMOG_HIST_CHUNK``, ``TMOG_HIST_UNROLL``, the VMEM
  budget); ``kernel_provenance()`` reports the live values, so a run
  (``chip_smoke.py`` prints them) says what tuning it ran under.
"""

from __future__ import annotations

import logging
import os
import threading
from contextlib import contextmanager
from typing import Any, Dict, Optional, Tuple

log = logging.getLogger(__name__)

#: test override installed by force_kernel_mode(); None = resolve from env.
#: A scalar rebind (not a mutated container) — single-writer test usage.
_FORCED: Optional[str] = None

#: the autotuner's cache-token component ("" = untuned/defaults), installed
#: by perf/autotune.py under its own guard whenever a non-default winner is
#: adopted.  A scalar rebind read lock-free here — same discipline as
#: _FORCED; the writer holds autotune._GUARD.
_TUNING_TOKEN: str = ""

#: test override installed by force_serve_donation(); None = resolve from
#: env.  Same scalar-rebind discipline as _FORCED.
_FORCED_DONATION: Optional[bool] = None

#: default VMEM budget for compiled kernels (bytes).  Source: the TPU
#: compiler's own report for a v5e under libtpu 0.0.34 — "Scoped allocation
#: with size 25.45M and limit 16.00M exceeded scoped vmem limit" (Mosaic
#: kernels get a 16 MiB scoped-VMEM stack by default; the chip has more, but
#: nothing here raises ``vmem_limit_bytes``).  The admission formulas count
#: blocks and the larger temporaries, not every compiler scratch buffer, so
#: the budget keeps 6 MiB of that limit in hand.
_DEFAULT_VMEM_BUDGET = 10 * 1024 * 1024

#: (kernel, chosen mode) -> how many dispatch decisions this process made;
#: decisions happen at trace time, so this counts traces, not executions
_SELECTIONS: Dict[Tuple[str, str], int] = {}
_SELECTIONS_LOCK = threading.Lock()

#: the histogram tuning-knob defaults — ONE definition; models/trees.py and
#: perf/kernels/histogram.py both resolve their knobs against these
HIST_CHUNK_DEFAULT = 2048
HIST_UNROLL_DEFAULT = 1


def tuning_int(name: str, default: int, minimum: int = 1) -> int:
    """THE env-knob reader: ``int(os.environ[name])``, ``default`` when the
    variable is unset, non-integer, or below ``minimum`` — with a logged
    warning on the malformed cases so a typo'd ``TMOG_HIST_CHUNK`` degrades
    a serve boot to the default instead of crashing it or silently running
    a clamped value nobody asked for.  Every tuning knob
    (``TMOG_HIST_CHUNK``, ``TMOG_HIST_UNROLL``, ``TMOG_PALLAS_VMEM_BUDGET``)
    funnels through here so provenance reporting cannot drift from the
    values actually used."""
    raw = os.environ.get(name)
    if raw is None:
        return int(default)
    try:
        value = int(raw)
    except ValueError:
        log.warning("%s=%r is not an integer — using default %d",
                    name, raw, int(default))
        return int(default)
    if value < int(minimum):
        log.warning("%s=%d is below the minimum %d — using default %d",
                    name, value, int(minimum), int(default))
        return int(default)
    return value


def _env_mode() -> str:
    raw = os.environ.get("TMOG_PALLAS", "").strip().lower()
    if raw in ("0", "off", "false", "no", "xla"):
        return "xla"
    if raw in ("interpret", "emulate"):
        return "interpret"
    if raw in ("pallas", "force"):
        return "pallas"
    # "", "1", "on", "true", "auto": backend-resolved below
    return "auto"


def kernel_mode() -> str:
    """Effective kernel dispatch mode: ``"xla"`` | ``"pallas"`` |
    ``"interpret"`` (see module docstring for the ``TMOG_PALLAS`` table).

    Resolved at call time — which for jitted programs means trace time; the
    choice is baked into the traced program and isolated per mode by
    ``cache_token()`` riding every executable-cache key and plan
    fingerprint."""
    if _FORCED is not None:
        return _FORCED
    mode = _env_mode()
    if mode != "auto":
        return mode
    import jax

    if jax.default_backend() != "tpu":
        return "xla"
    # jax refuses to lower a Mosaic kernel into a program that spans more
    # than one device ("Mosaic kernels cannot be automatically partitioned.
    # Please wrap the call in a shard_map.") and none of ours is wrapped
    # yet: under a multi-device ambient mesh ``auto`` keeps the XLA
    # formulations.  An explicit TMOG_PALLAS=pallas still reaches the
    # kernels there, and fails loudly.
    from ...parallel.mesh import current_mesh

    mesh = current_mesh()
    return "xla" if mesh is not None and mesh.size > 1 else "pallas"


@contextmanager
def force_kernel_mode(mode: str):
    """Pin the dispatch mode for a ``with`` block (parity tests: run the
    same growth once per mode and compare).  Not re-entrant across threads —
    test-only, like the planner's ``fused=`` overrides."""
    global _FORCED
    if mode not in ("xla", "pallas", "interpret"):
        raise ValueError(f"unknown kernel mode {mode!r}")
    prev = _FORCED
    _FORCED = mode
    try:
        yield
    finally:
        _FORCED = prev


def serve_donation() -> bool:
    """Whether the serving prefix compiles with ``donate_argnums`` on its
    padded input buffers (``TMOG_SERVE_DONATE``; default off).  The donated
    variant is a DISTINCT executable — resolved here, next to the kernel
    mode, so the choice rides ``cache_token()`` into every program cache
    key, plan fingerprint, and deploy artifact key and can never alias the
    non-donated build (acceptance: ISSUE 18)."""
    if _FORCED_DONATION is not None:
        return _FORCED_DONATION
    raw = os.environ.get("TMOG_SERVE_DONATE", "").strip().lower()
    return raw in ("1", "on", "true", "yes", "donate")


@contextmanager
def force_serve_donation(flag: bool):
    """Pin the serve-donation choice for a ``with`` block (parity tests run
    both variants in one process).  Not re-entrant across threads —
    test-only, like ``force_kernel_mode``."""
    global _FORCED_DONATION
    prev = _FORCED_DONATION
    _FORCED_DONATION = bool(flag)
    try:
        yield
    finally:
        _FORCED_DONATION = prev


def cache_token() -> str:
    """Kernel-choice component of every program cache key / plan
    fingerprint.  Distinct per effective mode so executables never alias
    across dispatch modes (acceptance: ISSUE 10).  In compiled-Pallas mode
    the VMEM admission budget rides the token too: the budget decides which
    call sites trace the kernel vs the XLA fallback, so two budgets are two
    program families even at one mode.  The serve-donation choice rides the
    token the same way: a donated serving prefix consumes its input buffers,
    so it must never be served where a caller expects the non-donated
    build (ISSUE 18)."""
    mode = kernel_mode()
    token = f"kernels:pallas:vmem={vmem_budget()}" if mode == "pallas" \
        else f"kernels:{mode}"
    if serve_donation():
        token += ":serve-donate"
    tune = _load_tuning_token()
    if tune:
        token += f":{tune}"
    return token


def _set_tuning_token(token: str) -> None:
    """Installed by perf/autotune.py (holding its guard) when winners are
    adopted; "" returns the token to the untuned form so default runs stay
    byte-identical to pre-autotuner fingerprints."""
    global _TUNING_TOKEN
    _TUNING_TOKEN = str(token)


def _tuning_token() -> str:
    return _TUNING_TOKEN


def _load_tuning_token() -> str:
    """The autotuner component for :func:`cache_token`: loading the winner
    store happens HERE, eagerly at key-computation time, so a program key
    always reflects every winner its trace could observe — a winner adopted
    mid-trace can never alias the untuned executable."""
    try:
        from .. import autotune as _autotune

        return _autotune.tuning_token()
    except Exception:  # pragma: no cover — autotune import failure
        return _TUNING_TOKEN


def vmem_budget() -> int:
    return tuning_int("TMOG_PALLAS_VMEM_BUDGET", _DEFAULT_VMEM_BUDGET)


def _admit(kernel: str, working_set_bytes: int, counted: bool = True
           ) -> Optional[str]:
    """Mode for ``kernel`` at a VMEM working set of ``working_set_bytes``:
    None = run the XLA reference path.  The decision is counted
    (:func:`kernel_selections`) unless the caller only asks what a trace
    would decide (``counted=False``: a dispatch site's span counts)."""
    mode = kernel_mode()
    if mode == "xla" or (mode == "pallas"
                         and working_set_bytes > vmem_budget()):
        mode = None
    if counted:
        count_selection(kernel, mode or "xla")
    return mode


def count_selection(kernel: str, mode: str) -> None:
    """One more traced call site of ``kernel`` ran as ``mode``."""
    with _SELECTIONS_LOCK:
        _SELECTIONS[kernel, mode] = _SELECTIONS.get((kernel, mode), 0) + 1


def kernel_selections() -> Dict[str, int]:
    """``{"split:pallas": 12, "hist:xla": 9, ...}`` — the dispatch decisions
    made so far in this process (one per traced call site)."""
    with _SELECTIONS_LOCK:
        return {f"{k}:{m}": n for (k, m), n in sorted(_SELECTIONS.items())}


def hist_mode(m_rows: int, bd_cols: int, chunk: int, lanes_bytes_per_row: int,
              elem_bytes: int = 1, counted: bool = True) -> Optional[str]:
    """Dispatch decision for the histogram kernel: the VMEM working set is
    the (M, B*d) accumulator + the per-chunk (M, chunk) activation +
    (chunk, B*d) bin one-hot + streamed operand blocks.  ``elem_bytes`` is
    the MXU dtype width of the one-hot operands (1 = int8-exact, 2 = bf16,
    4 = f32) — undersizing it would admit shapes that fail to compile
    instead of falling back."""
    ws = (m_rows * bd_cols * 4                  # accumulator (f32/int32)
          + m_rows * chunk * elem_bytes         # activation
          + chunk * bd_cols * elem_bytes        # bin one-hot
          + chunk * lanes_bytes_per_row)        # local + gh + codes blocks
    return _admit("hist", ws, counted)


def split_mode(block_bytes: int) -> Optional[str]:
    """Dispatch decision for the split-scan kernel (grid over lane blocks).
    ``block_bytes`` is one step's grad + hess histogram block AS TILED in
    VMEM (nodes padded to 8 sublanes, features to 128 lanes); the Pallas
    pipeline double-buffers it, and the bin loop's running sums and
    candidates are a few (classes, nodes, features) slabs on top."""
    return _admit("split", 2 * block_bytes + block_bytes // 2)


def route_mode(d: int, lanes: int, block_rows: int = 256) -> Optional[str]:
    """Admission of the routing kernel (perf/kernels/routing.py) for a
    caller that names it: the walk's entry ``level_select_lanes`` does not
    ask (PR 33: it runs XLA's own code in every mode).
    Its rank-3 (block, d, lanes) compare-reduce puts the lane axis on the
    128 vector lanes, so VMEM goes by lane TILES, not lanes.  The scratch
    model is fitted to what the v5e compiler reported (libtpu 0.0.34, d=128:
    block 512 -> 18.64M at 1 lane tile, 25.48M at 2; block 1024 -> 36.25M
    and 47.53M): about 192 + 88 * lane_tiles bytes per (row, feature).
    Undersizing would select a kernel the compiler then refuses."""
    lane_tiles = -(-lanes // 128)
    ws = block_rows * d * (192 + 88 * lane_tiles) \
        + 2 * block_rows * (d + 128 * lane_tiles) * 4   # in/out blocks, x2
    return _admit("route", ws)


def encode_mode(width: int, block_rows: int = 1024) -> Optional[str]:
    """Dispatch decision for the serving encode kernels; degenerate widths
    stay on the XLA path (zero-column outputs are host-shape plumbing, not
    a kernel)."""
    if width <= 0:
        return None
    return _admit("encode", 2 * block_rows * (width + 2) * 4)


def kernel_provenance() -> Dict[str, Any]:
    """Dispatch + tuning snapshot (``chip_smoke.py`` prints it).

    ``hist_chunk``/``hist_unroll`` report the values BOUND into
    models/trees.py (import-time env resolution, the values traced programs
    actually used — incl. test monkeypatches), falling back to a live env
    read only when the trees module is absent."""
    prov = {
        "kernel_mode": kernel_mode(),
        "tmog_pallas": os.environ.get("TMOG_PALLAS", ""),
        "hist_chunk": tuning_int("TMOG_HIST_CHUNK", HIST_CHUNK_DEFAULT),
        "hist_unroll": tuning_int("TMOG_HIST_UNROLL", HIST_UNROLL_DEFAULT),
        "pallas_vmem_budget": vmem_budget(),
        "serve_donation": serve_donation(),
        "selected": kernel_selections(),
    }
    try:
        from ...models import trees as _trees

        prov["hist_chunk"] = int(_trees._HIST_CHUNK)
        prov["hist_unroll"] = int(_trees._HIST_UNROLL)
    except Exception:  # pragma: no cover — trees not importable
        pass
    try:
        from .. import autotune as _autotune

        prov["tuning"] = _autotune.provenance()
    except Exception:  # pragma: no cover — autotune import failure
        prov["tuning"] = {"token": _TUNING_TOKEN, "winners": {},
                          "store": None, "sweeps_this_process": 0}
    return prov
