"""Kernel dispatch layer: who runs a tree, encode or descent hot loop, and how
we know.

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
- VMEM admission guards (``hist_mode``/``split_mode``/``encode_mode``/
  ``descent_mode``): compiled Pallas keeps its accumulator and operands
  resident in VMEM, so a shape whose working set exceeds the budget
  (``_VMEM_BUDGET``, 10 MiB of the compiler's 16 MiB scoped limit) takes the
  XLA path by this module's DECISION, before the compiler is asked.
  Nothing here catches a compiler error and reroutes: a kernel that is
  selected and then refused fails the program.  Interpret mode has no such
  limit.
- What ``auto`` selects on a TPU, per kernel, as established on a v5e with
  jax 0.9.0 / libtpu 0.0.34 (CHANGES.md PR 21; ``chip_smoke.py`` re-proves
  it):

  ====================  =================================================
  kernel                ``auto`` on TPU
  ====================  =================================================
  ``split_scan``        selected (all tree levels at d=128, 32 bins).
                        Repaired in PR 21: the (8, 128) block rule and the
                        missing ``cumsum`` lowering refused the original.
  ``onehot_codes``,     selected at every serving width.
  ``bucketize_right``
  ``hist_level``        selected only where the working set fits; at
                        d=128, 33 bins and the default 2048-row chunk the
                        (chunk, B*d) one-hot alone is 8.6-17 MB, so the
                        headline shapes run the XLA scan.  Repaired in
                        PR 21 for the int8 path (no int8 multiply or
                        sublane broadcast on the v5e vector unit).
  ``hinge_grad_pallas`` selected where its tile admits (d=128: 8192-row
                        tiles).  The SVC refit's step over a 2^22 x 129
                        float32 block: 2.84 ms, one read at 768 GB/s,
                        where XLA's margin and gradient fusions take 6.13
                        ms together (``descent_mode``; under a
                        multi-device mesh ``xla`` in every mode).
  ====================  =================================================

  Under a multi-device ambient mesh (``parallel.mesh.use_mesh``) ``auto``
  selects NONE of them: jax cannot partition a Mosaic kernel automatically
  and the kernels are not wrapped in ``shard_map`` yet, so the sharded
  programs keep the XLA formulations (``chip_smoke.py --mesh 2x2``).

  The tree walk's row select runs XLA's own code in every mode
  (``routing.level_select_lanes``): its Pallas kernel lost to it on the
  chip, was never selected, and is gone.

  ``kernel_selections()`` counts the decisions this process actually made
  (at trace time), so a run can show which kernels it really used.
- A kernel's schedule (row chunk, block) is a constant of its module,
  chosen by shape alone; ``kernel_provenance()`` reports the live values
  (``chip_smoke.py`` prints them).
"""

from __future__ import annotations

import logging
import os
import sys
import threading
from contextlib import contextmanager
from typing import Any, Dict, Optional, Tuple

log = logging.getLogger(__name__)

#: test override installed by force_kernel_mode(); None = resolve from env.
#: A scalar rebind (not a mutated container) — single-writer test usage.
_FORCED: Optional[str] = None

#: test override installed by force_serve_donation(); None = resolve from
#: env.  Same scalar-rebind discipline as _FORCED.
_FORCED_DONATION: Optional[bool] = None

#: VMEM budget for compiled kernels (bytes).  Source: the TPU
#: compiler's own report for a v5e under libtpu 0.0.34 — "Scoped allocation
#: with size 25.45M and limit 16.00M exceeded scoped vmem limit" (Mosaic
#: kernels get a 16 MiB scoped-VMEM stack by default; the chip has more, but
#: nothing here raises ``vmem_limit_bytes``).  The admission formulas count
#: blocks and the larger temporaries, not every compiler scratch buffer, so
#: the budget keeps 6 MiB of that limit in hand.
_VMEM_BUDGET = 10 * 1024 * 1024

#: (kernel, chosen mode) -> how many dispatch decisions this process made;
#: decisions happen at trace time, so this counts traces, not executions
_SELECTIONS: Dict[Tuple[str, str], int] = {}
_SELECTIONS_LOCK = threading.Lock()

#: the histogram kernel's row chunk — ONE definition; models/trees.py and
#: perf/kernels/histogram.py both read it
HIST_CHUNK_DEFAULT = 2048


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
    return token


def vmem_budget() -> int:
    return _VMEM_BUDGET


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


def encode_mode(width: int, block_rows: int = 1024) -> Optional[str]:
    """Dispatch decision for the serving encode kernels; degenerate widths
    stay on the XLA path (zero-column outputs are host-shape plumbing, not
    a kernel)."""
    if width <= 0:
        return None
    return _admit("encode", 2 * block_rows * (width + 2) * 4)


#: the descent kernel's largest tile, rows (64 sublane rows of 128 lanes)
_DESCENT_TILE_MAX = 8192


def _descent_working_set(d: int, tile_rows: int) -> int:
    """VMEM of one descent grid step: the (d + 1, tile) block (sublanes
    padded to 8) and the labels' and weights' tiles, double-buffered, and
    the (d, 128) coefficients and (d + 1, 128) accumulators, double-buffered
    too."""
    rows = -(-(d + 1) // 8) * 8
    return 2 * (rows + 2) * tile_rows * 4 + 2 * (2 * rows) * 128 * 4


def descent_tile(d: int) -> int:
    """Rows of the descent kernel's tile at ``d`` feature columns: the
    largest of 1024, 2048, ... 8192 whose working set fits the VMEM budget
    (1024 where none does: ``descent_mode`` then declines the kernel)."""
    rows = 1024
    while (rows < _DESCENT_TILE_MAX and _descent_working_set(d, 2 * rows)
           <= vmem_budget()):
        rows *= 2
    return rows


def descent_mode(d: int) -> str:
    """Dispatch decision for the squared-hinge step kernel
    (perf/kernels/descent.py) at ``d`` feature columns: ``pallas``,
    ``interpret`` or ``xla``.  Unlike the tree kernels it answers ``xla``
    under a multi-device ambient mesh in every mode: the refit's block is
    sharded by rows there and the kernel is not wrapped in ``shard_map``."""
    from ...parallel.mesh import current_mesh

    mesh = current_mesh()
    if mesh is not None and mesh.size > 1:
        count_selection("descent", "xla")
        return "xla"
    return _admit("descent",
                  _descent_working_set(d, descent_tile(d))) or "xla"


#: set once a process has started importing Pallas (:func:`prefetch_pallas`)
_PALLAS_PREFETCHED = False
_PREFETCH_LOCK = threading.Lock()


def prefetch_pallas() -> None:
    """Import jax's Pallas on a daemon thread, once a process, where a
    kernel may be traced (the mode is not ``xla``).  The import takes 1.3 s
    and more (jax 0.9 brings its GPU interpreter with it); a fit that knows
    a kernel is coming starts it before work that releases the GIL, such as
    the table's stamp and transfer, so the kernel's first trace does not
    wait for it.  Where the import is still running, that trace waits for
    it on the module's import lock."""
    global _PALLAS_PREFETCHED
    if "jax.experimental.pallas.tpu" in sys.modules \
            or kernel_mode() == "xla":
        return
    with _PREFETCH_LOCK:
        if _PALLAS_PREFETCHED:
            return
        _PALLAS_PREFETCHED = True
    threading.Thread(target=_import_pallas, name="transmogrifai-pallas",
                     daemon=True).start()


def _import_pallas() -> None:
    try:
        from jax.experimental.pallas import tpu  # noqa: F401
    except Exception:  # pragma: no cover — the kernel's own import raises
        log.debug("Pallas prefetch failed", exc_info=True)


def kernel_provenance() -> Dict[str, Any]:
    """Dispatch snapshot (``chip_smoke.py`` prints it).  ``hist_chunk`` is
    the value BOUND into models/trees.py (the one traced programs used,
    test monkeypatches included)."""
    prov = {
        "kernel_mode": kernel_mode(),
        "tmog_pallas": os.environ.get("TMOG_PALLAS", ""),
        "hist_chunk": HIST_CHUNK_DEFAULT,
        "pallas_vmem_budget": vmem_budget(),
        "serve_donation": serve_donation(),
        "selected": kernel_selections(),
    }
    try:
        from ...models import trees as _trees

        prov["hist_chunk"] = int(_trees._HIST_CHUNK)
    except Exception:  # pragma: no cover — trees not importable
        pass
    return prov
