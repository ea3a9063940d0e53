"""Device mesh + sharding helpers — the distributed substrate.

Replaces the reference's Spark driver/executor row-partitioning (SURVEY §5.8): rows are
sharded over the ``data`` mesh axis; model-selection sweeps shard the (fold × grid) batch
over the ``model`` axis.  XLA inserts the ICI/DCN collectives (psum for statistics,
histograms, gradients) under ``jit`` from sharding annotations — we never hand-write
NCCL-style calls.
"""

from __future__ import annotations

import contextlib
import os
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "data"
MODEL_AXIS = "model"


def device_count() -> int:
    return jax.device_count()


def make_mesh(
    n_data: Optional[int] = None,
    n_model: int = 1,
    devices: Optional[Sequence] = None,
) -> Mesh:
    """A (data, model) mesh.  Default: all devices on the data axis."""
    devs = np.asarray(devices if devices is not None else jax.devices())  # opcheck: allow(TM301) Device objects, not a traced jax value
    total = devs.size
    if n_data is None:
        n_data = total // n_model
    if n_data * n_model != total:
        raise ValueError(f"mesh {n_data}x{n_model} != {total} devices")
    return Mesh(devs.reshape(n_data, n_model), (DATA_AXIS, MODEL_AXIS))


def row_sharding(mesh: Mesh) -> NamedSharding:
    """Shard the leading (row) axis over the data axis, replicate the rest."""
    return NamedSharding(mesh, P(DATA_AXIS))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def pad_axis(arr: np.ndarray, axis: int, multiple: int) -> Tuple[np.ndarray, int]:
    """Zero-pad one axis to a multiple (sharding needs even splits);
    returns (padded, n_valid along that axis)."""
    n = arr.shape[axis]
    rem = (-n) % multiple
    if rem == 0:
        return arr, n
    pad_width = [(0, 0)] * arr.ndim
    pad_width[axis] = (0, rem)
    return np.pad(arr, pad_width), n


def pad_host(arr: np.ndarray, pad_width) -> np.ndarray:
    """``np.pad`` with zeros as one ``host.pad`` activity: the padded host
    copy a placement key is stamped from.  Where every width is 0 the array
    itself comes back: no copy, no span."""
    from ..perf.timers import activity

    if not np.any(pad_width):
        return arr
    with activity("pad", nbytes=int(arr.nbytes)):
        return np.pad(arr, pad_width)


def row_mask(n_padded: int, n_valid: int):
    import jax.numpy as jnp

    return (jnp.arange(n_padded) < n_valid).astype(jnp.float32)


# --- ambient mesh context ---------------------------------------------------
# Stages consult current_mesh() at fit time: when set, they place their row
# blocks with row_sharding(mesh) so XLA turns the row reductions into psums
# over ICI (the Spark treeAggregate / Rabit role, SURVEY §5.8).
import contextvars as _contextvars

_CURRENT_MESH: "_contextvars.ContextVar[Optional[Mesh]]" = _contextvars.ContextVar(
    "transmogrifai_tpu_mesh", default=None)


class use_mesh:
    """Context manager: run workflow fits with row blocks sharded over `mesh`.

    >>> with use_mesh(make_mesh()):
    ...     model = workflow.train()
    """

    def __init__(self, mesh: Mesh):
        self.mesh = mesh
        self._token = None

    def __enter__(self) -> Mesh:
        self._token = _CURRENT_MESH.set(self.mesh)
        return self.mesh

    def __exit__(self, *exc) -> None:
        _CURRENT_MESH.reset(self._token)


def current_mesh() -> Optional[Mesh]:
    return _CURRENT_MESH.get()


def place_rows(arr, mesh: Optional[Mesh] = None):
    """Device-put with rows sharded over the ambient (or given) mesh; no-op
    placement when no mesh is active.  Uneven row counts are fine — GSPMD
    handles non-divisible shardings."""
    import jax.numpy as jnp

    mesh = mesh if mesh is not None else current_mesh()
    if mesh is None:
        return jnp.asarray(arr)
    arr = np.asarray(arr)
    _count_laid_out(int(arr.nbytes), P(DATA_AXIS), mesh)
    return jax.device_put(arr, row_sharding(mesh))


#: a degradation is counted from this many bytes up.  What may degrade by
#: design is a grid's parameter vector or coefficient block over the model
#: axis (a one-point grid over a two-way axis): tens of bytes to kilobytes,
#: 0.7 MB for the widest block the default selectors make (softmax,
#: (6, 3, 1025, 10) float32).  A row-aligned float32 vector reaches 1 MiB at
#: 262,144 rows; below that a replica costs nothing worth a counter.
DEGRADED_MIN_BYTES = 1 << 20


def _effective_spec(shape, axes, mesh, nbytes: int = 0) -> P:
    """The ONE degradation rule place()/constrain() share: an axis the mesh
    doesn't know, or whose dimension size doesn't divide the mesh axis,
    degrades to replication (sharding is a layout hint, never semantics — a
    1-point grid over a 2-way model axis must still run/trace).

    Legal, and seen: where an axis the mesh HAS is dropped because the size
    does not divide it, an array of ``nbytes`` >= :data:`DEGRADED_MIN_BYTES`
    is counted under ``placement_stats()["mesh"]`` (``degraded``,
    ``bytes_degraded``) — a table or an ``(n,)`` vector that becomes one
    replica a device is a different deployment from the one asked for."""
    eff, lost = [], False
    for i, a in enumerate(axes):
        known = a in mesh.axis_names and i < len(shape)
        keep = known and int(shape[i]) % int(mesh.shape[a]) == 0
        lost = lost or (known and not keep)
        eff.append(a if keep else None)
    if lost and nbytes >= DEGRADED_MIN_BYTES:
        _count_placement("mesh", degraded=1, bytes_degraded=int(nbytes))
    return P(*eff)


def _count_laid_out(nbytes: int, spec: P, mesh: Mesh) -> None:
    """Count ``nbytes`` laid out over ``mesh`` with ``spec``: sharded where
    some dimension is split over more than one device, else replicated (every
    device of the mesh holds the whole array)."""
    split = any(int(mesh.shape[a]) > 1 for a in spec if a is not None)
    _count_placement("mesh", **{
        "bytes_sharded" if split else "bytes_replicated": int(nbytes)})


def _count_in_program(mesh: Optional[Mesh], how: str, operands) -> None:
    if mesh is not None:
        _count_placement("mesh", **{how: sum(
            int(np.prod(o.shape)) * np.dtype(o.dtype).itemsize
            for o in operands)})


def count_replicated(mesh: Optional[Mesh], *operands) -> None:
    """Count operands (anything with ``shape`` and ``dtype``) that a program
    pins to every device of ``mesh`` inside its trace — the eval programs'
    labels and validation weights, the multiclass one's probabilities
    (models/base.py ``count_eval_replicas``) — from their shapes, at
    dispatch.  Nothing without a mesh."""
    _count_in_program(mesh, "bytes_replicated", operands)


def count_sharded(mesh: Optional[Mesh], *operands) -> None:
    """:func:`count_replicated`'s twin for what a program lays out split over
    the devices inside its trace: the linear eval program's dealt scores."""
    _count_in_program(mesh, "bytes_sharded", operands)


def place(arr, axes: Tuple[Optional[str], ...], mesh: Optional[Mesh] = None):
    """Device-put with an explicit PartitionSpec over the ambient (or given)
    mesh; plain jnp.asarray when no mesh is active.

    Robust by construction (:func:`_effective_spec` degradation; device_put
    enforces divisibility eagerly).  Arrays already on device reshard in
    place (no host round-trip).
    """
    import jax.numpy as jnp

    mesh = mesh if mesh is not None else current_mesh()
    if mesh is None:
        return jnp.asarray(arr)
    if not isinstance(arr, jax.Array):
        arr = np.asarray(arr)
    eff = _effective_spec(arr.shape, axes, mesh, int(arr.nbytes))
    _count_laid_out(int(arr.nbytes), eff, mesh)
    return jax.device_put(arr, NamedSharding(mesh, eff))


def mesh_token(mesh: Optional[Mesh] = None) -> Optional[tuple]:
    """Hashable topology token of the ambient (or given) mesh: axis names,
    per-axis sizes, and the PROCESS topology (process count, devices per
    process).  None without a mesh.

    This is the component every executable-cache key and plan fingerprint
    carries so a multi-host program can never alias a single-host one: an
    8-device mesh on one host and a 2-host x 4-device mesh have identical
    device-array shapes but different DCN boundaries — XLA lowers different
    collectives for them, so their executables must key apart.
    """
    mesh = mesh if mesh is not None else current_mesh()
    if mesh is None:
        return None
    # mesh in hand => the backend is initialized; process topology is cheap
    return (tuple(mesh.axis_names),
            tuple(int(mesh.shape[a]) for a in mesh.axis_names),
            int(jax.process_count()), len(jax.local_devices()))  # opcheck: allow(TM301) process/axis counts are python ints, not jax values


def constrain(x, *axes, mesh: Optional[Mesh] = None):
    """``with_sharding_constraint`` over the ambient (or given) mesh —
    IDENTITY when no mesh is active (the SNIPPETS [3] pattern: annotations
    are layout constraints for the GSPMD partitioner, never semantics, and
    must no-op off-mesh so one program body serves both modes).

    ``axes`` name a mesh axis per dimension (None = replicate that dim).
    Robust by construction, mirroring :func:`place`: axes the mesh doesn't
    know, or whose dimension size doesn't divide the mesh axis, degrade to
    replication — a 1-point grid over a 2-way model axis must still trace.
    Safe under ``jit``: the mesh is read at trace time and the executable
    cache keys on :func:`mesh_token`, so traces under different meshes never
    alias.
    """
    mesh = mesh if mesh is not None else current_mesh()
    if mesh is None:
        return x
    shape = getattr(x, "shape", ())
    # under jit this runs when the program is traced: a degradation inside a
    # program is counted once a trace, a placement's every time
    eff = _effective_spec(shape, axes, mesh, int(np.prod(shape)) * np.dtype(
        getattr(x, "dtype", np.float32)).itemsize)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, eff))


def constrain_rows(x, mesh: Optional[Mesh] = None):
    """Constrain the leading (row) axis over the data axis — the per-host
    row-block annotation every sweep/transform program applies to its row
    operands so XLA keeps row math shard-local (collectives carry only the
    (d,)-sized statistics, never the rows).  Identity without a mesh."""
    ndim = getattr(x, "ndim", 1)
    return constrain(x, DATA_AXIS, *((None,) * (ndim - 1)), mesh=mesh)


def constrain_fold_rows(x, mesh: Optional[Mesh] = None):
    """Constrain a (k, n) fold-weight block: rows (axis 1) over data,
    the small fold axis replicated.  Identity without a mesh."""
    ndim = getattr(x, "ndim", 2)
    return constrain(x, None, DATA_AXIS, *((None,) * (ndim - 2)), mesh=mesh)


def constrain_grid(x, mesh: Optional[Mesh] = None):
    """Constrain the leading (grid/model-batch) axis over the model axis —
    the fold x grid batch annotation that makes a sweep two-dimensionally
    parallel (each model-axis slice fits its grid points on its own row
    shard).  Identity without a mesh."""
    ndim = getattr(x, "ndim", 1)
    return constrain(x, MODEL_AXIS, *((None,) * (ndim - 1)), mesh=mesh)


def pad_rows_for_mesh(*arrays, mesh: Optional[Mesh] = None):
    """Zero-pad the leading axis of each array to the mesh's data-axis multiple.

    Returns (padded_arrays..., n_valid).  No-op (n_valid = original rows) when
    no mesh is active.  Zero padding is safe wherever rows enter weighted sums
    (weights pad to zero) or masked statistics.
    """
    mesh = mesh if mesh is not None else current_mesh()
    first = np.asarray(arrays[0])
    if mesh is None:
        return (*arrays, first.shape[0])
    mult = mesh.shape[DATA_AXIS]
    out = []
    n_valid = first.shape[0]
    for a in arrays:
        padded, _ = pad_axis(np.asarray(a), 0, mult)
        out.append(padded)
    return (*out, n_valid)


def bucket_size(n: int, minimum: int = 1024) -> int:
    """Next power-of-two row count.  Padding inputs to a bucket lets jitted
    kernels (sort-based AUC especially — seconds of XLA compile each) reuse the
    compile cache across nearby dataset sizes; zero-weight/zero-row padding is
    exact for weighted reductions and masked statistics."""
    n = int(n)
    if n <= minimum:
        return minimum
    return 1 << (n - 1).bit_length()


def pad_rows_to_bucket(n: int, *arrays):
    """Zero-pad each array's leading axis from n to bucket_size(n)."""
    m = bucket_size(n)
    if m == n:
        return arrays
    return tuple(pad_axis(np.asarray(a), 0, m)[0] for a in arrays)


def padded_row_count(n: int, mesh: Optional[Mesh] = None) -> int:
    """Rows of a block of ``n`` rows once bucket- and mesh-padded, without
    the block: what :func:`pad_rows_bucketed_for_mesh` pads to."""
    mesh = mesh if mesh is not None else current_mesh()
    m = bucket_size(n)
    return m if mesh is None else m + (-m) % int(mesh.shape[DATA_AXIS])


def pad_rows_bucketed_for_mesh(*arrays, n: Optional[int] = None):
    """Bucket-pad then mesh-pad leading axes (that order — bucket sizes are
    powers of two, so the mesh multiple keeps dividing them); returns
    (*padded, n_valid).  The one place encoding the composition rule."""
    n_valid = int(arrays[0].shape[0] if n is None else n)
    bucketed = pad_rows_to_bucket(n_valid, *arrays)
    return pad_rows_for_mesh(*bucketed)[:-1] + (n_valid,)


# -- shared device placement cache -------------------------------------------
# One selector fit runs several model families over the SAME feature block;
# without sharing, every family pays its own host->device transfer of the
# padded (n, d) matrix.  The cache
# keys on a CONTENT fingerprint (shape + dtype + full-buffer checksum), so a
# family that re-materialises an identical float32 copy still hits.  An
# in-place mutation of a DIFFERENT object with equal old content misses as
# soon as bytes change; mutating the one memoized source object in place can
# serve a stale stamp until the memo rolls over (_stamp_or_memo docstring) —
# placement sources are frozen by convention.  Bounded strong-ref FIFO: entries survive their
# source array (a family's temporary copy dying must not evict the shared
# transfer) but old blocks roll off so device memory stays bounded.
_PLACED_ROWS_CACHE: dict = {}
_PLACED_ROWS_CACHE_MAX = 3

#: one lock for all three placement caches (stamp memo + row/aux placement
#: FIFOs): fleet refit loops and concurrent selector fits share them.  The
#: device transfers themselves run OUTSIDE the lock — a double-place on a
#: concurrent miss is benign (last insert wins), a torn dict is not.
import threading as _threading

_PLACEMENT_LOCK = _threading.RLock()

#: process-wide cumulative counts of the two placement caches (the twin of
#: ``perf.programs.program_cache_stats``); ``bytes_stamped`` counts the bytes
#: hashed in full for the cache's keys — a stamp-memo hit hashes none.
#: ``fit`` counts what never reached a cache: row-aligned inputs a fit took
#: as already placed (``place_fit_rows``) and arrays it derived on the device.
#: ``mesh`` counts what an active mesh did with the bytes handed to it: laid
#: out split over devices (``bytes_sharded``: ``place``/``place_rows``, and
#: the linear eval program's dealt scores), whole on every device
#: (``bytes_replicated``: ``place``/``place_rows`` with nothing split, and the
#: eval programs' pinned metric inputs), and asked to be split but replicated
#: because a size does not divide its axis (``degraded``, ``bytes_degraded``;
#: :func:`_effective_spec`).  All zero while no mesh is active.
_PLACEMENT_STATS = {
    **{cache: {"hits": 0, "misses": 0, "bytes_stamped": 0, "bytes_placed": 0}
       for cache in ("rows", "aux")},
    "fit": {"passed_through": 0, "bytes_passed": 0,
            "derived": 0, "bytes_derived": 0},
    "mesh": {"bytes_sharded": 0, "bytes_replicated": 0,
             "degraded": 0, "bytes_degraded": 0}}


def placement_stats() -> dict:
    """``{"rows": {...}, "aux": {...}, "fit": {...}, "mesh": {...}}``: hits,
    misses, bytes stamped and bytes placed of ``place_rows_bucketed_cached``
    and ``place_cached``; under ``fit`` the arrays (and their bytes) that
    ``place_fit_rows`` passed through with no pad, stamp or lookup and those
    ``count_derived`` was told of; under ``mesh`` the bytes an active mesh
    laid out sharded, replicated and degraded, all since the process
    started."""
    with _PLACEMENT_LOCK:
        return {cache: dict(counts)
                for cache, counts in _PLACEMENT_STATS.items()}


class fit_mesh_record:
    """What one fit's recorder says of the mesh it ran under: the mesh's
    shape, the rows of the padded block each data shard holds, and what the
    ``mesh`` counters moved by between entry and exit (process-wide counters:
    a concurrent fit's bytes land in both).  ``record`` stays None without a
    mesh."""

    def __init__(self, n_rows: int):
        self.mesh = current_mesh()
        self.n_rows = int(n_rows)
        self.record: Optional[dict] = None

    def __enter__(self) -> "fit_mesh_record":
        if self.mesh is not None:
            self._before = placement_stats()["mesh"]
        return self

    def __exit__(self, *exc) -> None:
        if self.mesh is None:
            return
        n_data = int(self.mesh.shape[DATA_AXIS])
        after = placement_stats()["mesh"]
        self.record = {
            "shape": {a: int(self.mesh.shape[a])
                      for a in self.mesh.axis_names},
            "rows_per_shard": padded_row_count(self.n_rows, self.mesh)
            // n_data,
            **{k: after[k] - self._before[k] for k in after}}


def _count_placement(cache: str, **moved) -> None:
    with _PLACEMENT_LOCK:
        counts = _PLACEMENT_STATS[cache]
        for name, by in moved.items():
            counts[name] += by


_STAMP_MEMO: dict = {}
_STAMP_MEMO_MAX = 16
#: only blocks whose full hash is expensive are worth a memo slot; smaller
#: arrays (fold weights, labels) hash in single-digit ms
_STAMP_MEMO_MIN_BYTES = 64 * 1024 * 1024


def _quick_sig(a: np.ndarray) -> bytes:
    """Cheap strided sub-sample hash used ONLY to validate memo hits — the
    authoritative stamp is the full hash.

    64 evenly-strided 4 KB windows (256 KB hashed, ~0.2 ms on a 512 MB
    block): any contiguous in-place mutation spanning at least
    ceil(n/64) + 4 KB bytes is GUARANTEED to intersect a window (at 1M x
    128 f32 that is ~2% of the rows); narrower edits may escape until the
    memo entry rolls off."""
    import hashlib

    flat = np.frombuffer(memoryview(a).cast("B"), dtype=np.uint8)
    n = flat.shape[0]
    h = hashlib.blake2b(digest_size=8)
    win = 4096
    k = 64
    stride = max(n // k, 1)
    for start in range(0, n, stride):
        h.update(memoryview(flat[start:start + win]))
    h.update(memoryview(flat[max(n - win, 0):]))  # tail window
    return h.digest()


def _content_stamp(a: np.ndarray) -> bytes:
    """Full-buffer blake2b-128 content fingerprint (zero-copy via memoryview);
    see :func:`_stamp_bytes`, which also says how many bytes it hashed."""
    return _stamp_bytes(a)[0]


def _stamp_bytes(a: np.ndarray) -> Tuple[bytes, int]:
    """(full-buffer blake2b-128 content fingerprint, bytes hashed in full for
    it — 0 on a memo hit).  One ``host.stamp`` activity either way."""
    from ..perf.timers import activity

    with activity("stamp", nbytes=int(a.nbytes)) as span:
        stamp, hit = _stamp_or_memo(a)
        span.note(hit=hit)
    return stamp, 0 if hit else int(a.nbytes)


def _stamp_or_memo(a: np.ndarray) -> Tuple[bytes, bool]:
    """(stamp, answered by the memo) — zero-copy via memoryview.

    Negligible next to the multi-second transfer it deduplicates; unlike a
    sampled checksum it covers every byte, and at 128 bits the collision
    probability between distinct blocks is negligible (a 32-bit crc here
    would silently serve another dataset's placement at ~2^-32 per pair —
    r3 advisor finding).

    Memoized per source object for LARGE blocks only (hashing a 512 MB block
    costs ~0.5 s; small arrays hash in ms and would churn the bounded memo).
    The memo holds a WEAK reference (no host-memory pinning; a recycled id
    after the array dies invalidates the entry).  Memoized arrays are FROZEN
    (``writeable=False``): an in-place mutation of a cached placement source
    raises in the caller's code instead of silently serving stale device
    data.  Callers that intend to mutate can simply re-enable
    ``a.flags.writeable = True`` — a writeable array never hits the memo, so
    correctness is preserved (full re-hash).  The freeze is lifted when the
    entry is evicted or its weakref dies.  VIEWS are never memoized or
    frozen — they take the full re-hash path every time (r4 advisor: a
    view hit guarded only by the sampled signature could serve a stale
    placement after a narrow mutation).  Residual caveat: a writeable view
    of the OWNER taken BEFORE memoization keeps its own writeable flag
    (numpy snapshots flags at view creation), so mutation through such a
    pre-existing view bypasses the freeze and is caught only by the
    strided signature below until the entry rolls off.  A hit requires an
    owner that is still non-writeable + matching (shape, dtype) + the
    sub-sample signature; anything else re-hashes."""
    import hashlib
    import weakref

    contiguous = a.flags["C_CONTIGUOUS"]
    memoizable = contiguous and a.nbytes >= _STAMP_MEMO_MIN_BYTES
    if memoizable:  # the memo (and _quick_sig) need zero-copy byte views
        memo_key = id(a)
        with _PLACEMENT_LOCK:
            hit = _STAMP_MEMO.get(memo_key)
        # a hit requires an OWNER array that is still frozen: a re-enabled
        # writeable flag means the caller intends to mutate -> full re-hash.
        # Views never qualify — a mutation through the view or its base
        # narrower than the strided-signature windows would otherwise serve
        # a stale placement silently (r4 advisor finding).
        frozen_ok = a.base is None and not a.flags.writeable
        if hit is not None and hit[0]() is a and frozen_ok \
                and hit[1] == (a.shape, a.dtype.str) \
                and hit[2] == _quick_sig(a):
            return hit[3], True
    raw = a if contiguous else np.ascontiguousarray(a)
    stamp = hashlib.blake2b(memoryview(raw).cast("B"),
                            digest_size=16).digest()
    if memoizable and a.base is None:
        # only OWNER arrays are memoized, and only when the freeze sticks:
        # a memo hit is vouched for by writeable=False on the owner buffer,
        # so any entry whose array cannot be frozen would be guarded by the
        # sampled quick_sig alone — exactly the stale-placement hazard the
        # r4 advisor flagged.  Views always take the full re-hash path.
        with _PLACEMENT_LOCK:
            try:
                ref = weakref.ref(a)  # before the freeze: a weakref-refusing
                # subclass must not leave the array frozen with no memo entry
                # whose eviction would restore it
                was_writeable = bool(a.flags.writeable)
                a.flags.writeable = False  # mutations now raise, loudly
                _STAMP_MEMO[memo_key] = (ref, (a.shape, a.dtype.str),
                                         _quick_sig(a), stamp, was_writeable)
            except (TypeError, ValueError):
                pass  # weakref-refusing subclass / flag-locked array: no memo
            for k in [k for k, v in _STAMP_MEMO.items() if v[0]() is None]:
                _STAMP_MEMO.pop(k)  # prune entries whose array died
            while len(_STAMP_MEMO) > _STAMP_MEMO_MAX:
                _evict_stamp(next(iter(_STAMP_MEMO)))
    return stamp, False


def _evict_stamp(key) -> None:
    """Drop a memo entry and lift its freeze (the caller owns the array
    again once nothing vouches for its content).  Callers hold (or may
    re-enter — RLock) the placement lock."""
    with _PLACEMENT_LOCK:
        entry = _STAMP_MEMO.pop(key, None)
    if entry is not None:
        arr = entry[0]()
        if arr is not None and entry[4]:  # restore ONLY if we froze it
            try:
                arr.flags.writeable = True
            except ValueError:
                pass  # view of a non-writeable base: leave as-is


def place_cached(arr: np.ndarray, axes: tuple,
                 mesh: Optional[Mesh] = None):
    """``place`` with the same content-keyed dedup as the row cache.

    For mid-sized row-aligned blocks that several consumers re-derive
    identically per selector fit — fold weight matrices especially: every
    family pads the validator's (k, n) train/val weights to the same content,
    and without the cache each family pays its own ~24 MB host->device
    transfer.  Keyed on (shape, dtype, blake2b, axes, mesh); bounded FIFO
    shared with the row cache budget."""
    from ..perf.timers import activity

    mesh = mesh if mesh is not None else current_mesh()
    arr = np.asarray(arr)
    stamp, hashed = _stamp_bytes(arr)
    key = (arr.shape, str(arr.dtype), stamp, tuple(axes), mesh)
    with _PLACEMENT_LOCK:
        hit = _PLACED_AUX_CACHE.pop(key, None)
        if hit is not None:
            _PLACED_AUX_CACHE[key] = hit  # LRU: a hit re-inserts at the back
            _count_placement("aux", hits=1, bytes_stamped=hashed)
            return hit
    # host side of the transfer only: device_put returns before the copy ends
    with activity("h2d", nbytes=int(arr.nbytes)):
        placed = place(arr, tuple(axes), mesh=mesh)
    _count_placement("aux", misses=1, bytes_stamped=hashed,
                     bytes_placed=int(arr.nbytes))
    with _PLACEMENT_LOCK:
        _PLACED_AUX_CACHE[key] = placed
        while len(_PLACED_AUX_CACHE) > _PLACED_AUX_CACHE_MAX:
            _PLACED_AUX_CACHE.pop(next(iter(_PLACED_AUX_CACHE)))
    return placed


_PLACED_AUX_CACHE: dict = {}
_PLACED_AUX_CACHE_MAX = 8


# -- a fit's own row-aligned inputs -------------------------------------------
# Labels, base weights and fold ids reach the device from several call sites
# of one fit (the sweep's extras, the refit, every evaluator).  The fit that
# owns them opens ``fit_placements()``; ``place_fit_rows`` then pads, stamps
# and places each source object once and answers every later request of the
# fit by identity.  Nothing outlives the fit: the table dies with the block.
_FIT_PLACED: "_contextvars.ContextVar[Optional[dict]]" = \
    _contextvars.ContextVar("transmogrifai_tpu_fit_placed", default=None)


class fit_placements:
    """Context manager: for the length of one fit, ``place_fit_rows``
    remembers what it placed by source object.  The table holds a strong
    reference to each source until the fit ends, so no ``id`` it is keyed on
    can be recycled; an inner fit gets a table of its own."""

    def __enter__(self) -> None:
        self._token = _FIT_PLACED.set({})

    def __exit__(self, *exc) -> None:
        _FIT_PLACED.reset(self._token)


def fit_table_open() -> bool:
    """Whether a fit's table is open: what ``place_fit_rows`` places now is
    what the fit's later requests for the same object get."""
    return _FIT_PLACED.get() is not None


@contextlib.contextmanager
def ensure_fit_placements():
    """The open fit's table, or one for the length of the block where none
    is open: a family's sweep called from a selector's fit shares the fit's,
    called on its own it has one for its grid points."""
    if _FIT_PLACED.get() is not None:
        yield
        return
    with fit_placements():
        yield


def fit_shared(kind, source, build):
    """What a fit derives on the device from a placed ``source`` and hands to
    several of its programs (the tree families' bin one-hot: every grid
    point's sweep and the winner's refit read one).  ``build()`` runs at the
    first request of a fit; later requests for the same ``kind`` and source
    OBJECT get the same array, which dies with the fit's table.  Outside any
    fit every request builds."""
    table = _FIT_PLACED.get()
    key = ("shared", kind, id(source))
    if table is not None and key in table:
        return table[key][1]
    built = build()
    if table is not None:
        table[key] = (source, built)
    return built


def _count_passed(arr) -> None:
    _count_placement("fit", passed_through=1, bytes_passed=int(arr.nbytes))


def count_derived(*arrays) -> None:
    """Count arrays a fit computed on the device from placed inputs (fold
    weight blocks, sign targets, unit weights) where it used to build, pad,
    stamp and place a host array."""
    _count_placement("fit", derived=len(arrays),
                     bytes_derived=sum(int(a.nbytes) for a in arrays))


def place_fit_rows(arr, n_padded: int, dtype=None):
    """Device handle of a fit's row-aligned input — ``(n,)`` or ``(n, c)``
    labels, weights, fold ids, one-hots — zero-padded to ``n_padded`` rows
    and sharded over the data axis.

    The test is on the input: a placed ``jax.Array`` is taken as it is (no
    pad, no stamp, no lookup).  A host array (cast to ``dtype`` where one is
    given) goes through ``pad_host`` and the content-keyed ``place_cached``;
    inside ``fit_placements()`` that happens once per source object, and a
    second request for the same object in the same fit passes through as
    well."""
    if isinstance(arr, jax.Array):
        _count_passed(arr)
        return arr
    arr = np.asarray(arr, dtype)
    table = _FIT_PLACED.get()
    key = (id(arr), int(n_padded), current_mesh())
    if table is not None and key in table:
        placed = table[key][1]
        _count_passed(placed)
        return placed
    pad_width = [(0, int(n_padded) - arr.shape[0])] + [(0, 0)] * (arr.ndim - 1)
    placed = place_cached(pad_host(arr, pad_width), (DATA_AXIS,))
    if table is not None:
        table[key] = (arr, placed)
    return placed


def fit_vector(v) -> np.ndarray:
    """The host object a fit places for its ``(n,)`` labels or row weights:
    ``v`` in float32, ``v`` itself where it is float32 already.  Every
    request for a fit's labels and base weights — the validator's beside the
    fold ids, the families' sweeps and refits, the evaluators — is made in
    this form (``place_fit_vector``), so that inside ``fit_placements()``
    the first places it and the rest pass by identity."""
    return np.asarray(v, np.float32)


def place_fit_vector(v, n_padded: int):
    """``place_fit_rows`` of ``fit_vector(v)`` (a placed array passes as it
    is): a fit's labels or row weights as every reader of the fit asks for
    them."""
    return place_fit_rows(v, n_padded, np.float32)


def place_rows_bucketed_cached(arr: np.ndarray,
                               mesh: Optional[Mesh] = None,
                               insert: bool = True):
    """(device_array, n_valid) for bucket+mesh padded ``arr``, cached on a
    content fingerprint of the source block so repeated placements of the
    same data (even via a fresh equal-valued copy) are free.

    ``insert=False`` is the serving-path mode: it HITS the cache (a predict
    on the block a model was just fit on reuses the fit transfer) but a
    miss places without inserting — chunked scoring of a large table must
    not churn distinct per-chunk entries through the small FIFO and evict
    the fit block it exists to protect."""
    from ..perf.timers import activity

    mesh = mesh if mesh is not None else current_mesh()
    arr = np.asarray(arr)
    stamp, hashed = _stamp_bytes(arr)
    # key on the Mesh OBJECT (hashable), not id(mesh): a recycled id after GC
    # could otherwise serve arrays sharded under a dead mesh (r3 advisor)
    key = (arr.shape, str(arr.dtype), stamp, mesh)
    with _PLACEMENT_LOCK:
        hit = _PLACED_ROWS_CACHE.pop(key, None)
        if hit is not None:
            _PLACED_ROWS_CACHE[key] = hit  # LRU: a hit re-inserts at the back
            _count_placement("rows", hits=1, bytes_stamped=hashed)
            return hit
    with activity("pad", nbytes=int(arr.nbytes)):
        padded, n_valid = pad_rows_bucketed_for_mesh(arr)[0], arr.shape[0]
    with activity("h2d", nbytes=int(padded.nbytes)):
        placed = place_rows(padded, mesh)
    _count_placement("rows", misses=1, bytes_stamped=hashed,
                     bytes_placed=int(padded.nbytes))
    if insert:
        with _PLACEMENT_LOCK:
            _PLACED_ROWS_CACHE[key] = (placed, n_valid)
            while len(_PLACED_ROWS_CACHE) > _PLACED_ROWS_CACHE_MAX:
                _PLACED_ROWS_CACHE.pop(next(iter(_PLACED_ROWS_CACHE)))
    return placed, n_valid
