"""Shared fused transform planner — one jitted, row-sharded columnar program
for the device-capable prefix of a fitted DAG.

This is the training-side generalization of ``serve/plan.py`` (which remains
the records-in/dicts-out consumer of the same primitives): given topologically
ordered fitted runners, partition them into a maximal *device prefix* (stages
exposing ``device_transform`` whose operands are reachable from materialized
dataset columns or other prefix outputs) and a *host remainder* (everything
else, run through the ordinary per-stage columnar ``transform``).  The whole
prefix — across stages AND across DAG layers — traces into ONE jitted XLA
program per operand-shape signature:

- operands enter as canonical numeric lifts (float32, NaN for missing), the
  float32 block of a vector/geo column, or per-stage host encodings
  (``encode_device_input``, e.g. categorical level codes);
- rows pad to a power-of-two bucket and the ambient mesh's data-axis multiple,
  then place with ``row_sharding`` — the device-transform contract
  (stages/base.py) makes kernels row-local, so padded rows are
  garbage-in/garbage-out and get sliced off;
- executables cache through ``perf.run_cached`` (content-addressed AOT cache),
  and plans themselves cache process-wide on the prefix's fitted-stage content
  fingerprint, so a warm second ``train()`` re-dispatches with ZERO new
  backend compiles.

Unlike the serving plan, outputs materialize back as full ``Column`` objects
with their host-path ``VectorMetadata``: the metadata of every prefix output
is recovered once per plan by replaying the prefix's host ``transform`` over a
ZERO-ROW slice of the input dataset (metadata is a function of fitted state
and input metadata only, never of the batch's values).

Cross-validation folds get a batched mode: when every prefix stage either
exposes the ``device_state`` protocol (fold-fitted constants as stacked traced
operands) or is content-identical across folds, the k fold transforms become
ONE ``jax.vmap``-over-folds dispatch instead of k sequential host passes;
otherwise each fold gets its own fused plan (still one program per fold).

The per-stage interpreted path is kept as an explicit fallback: set
``TMOG_FUSED_TRANSFORM=0``, pass ``fused=False`` to the workflow entry points,
or attach a stage-metrics listener (per-stage timings only exist on the
per-stage path) — and any plan build/execution failure logs a warning and
falls back rather than failing the transform.
"""

from __future__ import annotations

import hashlib
import itertools
import json
import logging
import os
import threading
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..data.dataset import Column, Dataset
from ..features.generator import FeatureGeneratorStage
from ..types import ColumnKind

log = logging.getLogger(__name__)

#: kinds with a canonical device lift everywhere: float32 rows, NaN where the
#: validity mask is off.  VECTOR is deliberately absent — serving compiles
#: per-bucket ahead of any data, so a width only known from the data defeats
#: bucket compilation (TM503).
DEVICE_LIFT_KINDS = frozenset(
    {ColumnKind.FLOAT, ColumnKind.INT, ColumnKind.BOOL, ColumnKind.GEO})

#: the DATASET path additionally lifts materialized OPVector columns: each
#: plan execution sees concrete arrays, and the executable cache keys on
#: operand shapes, so a new width is a new (cached) executable, not a hazard.
DATASET_LIFT_KINDS = DEVICE_LIFT_KINDS | {ColumnKind.VECTOR}

#: unique fingerprints for plans whose stage state cannot be hashed
_UNSHARED_TOKENS = itertools.count()

#: process-wide plan cache: (content fingerprint, available-names key) ->
#: ColumnarTransformPlan.  Plan reuse is what lets run_cached's fn-identity
#: keyed executable cache hit across repeated trains of the same content.
_PLAN_CACHE: Dict[tuple, "ColumnarTransformPlan"] = {}
_PLAN_CACHE_MAX = 32
_PLAN_CACHE_LOCK = threading.Lock()

#: minimum power-of-two row bucket for fused transform dispatches
_TRANSFORM_MIN_BUCKET = 32
#: above this, buckets grow in CHUNK multiples instead of powers of two: a
#: pow-2 bucket wastes up to 2x dispatch work (20000 rows -> 32768), while a
#: training table's shape is steady so chunk-granular buckets still hit one
#: cached executable per table
_TRANSFORM_BUCKET_CHUNK = 8192


def _transform_bucket(n: int) -> int:
    from ..parallel.mesh import bucket_size

    if n <= _TRANSFORM_BUCKET_CHUNK:
        return bucket_size(n, minimum=_TRANSFORM_MIN_BUCKET)
    return -(-n // _TRANSFORM_BUCKET_CHUNK) * _TRANSFORM_BUCKET_CHUNK


def mesh_aligned_tile(n: int) -> int:
    """The fused-dispatch row tile for ``n`` rows: the pow2/8192 bucket,
    rounded up to the ambient mesh's data-axis multiple so row sharding
    splits evenly across the dp axis.  This is THE one place encoding the
    bucket x mesh composition rule — the chunked epoch (workflow/ooc.py)
    calls it ONCE per epoch so every chunk pads to the same mesh-divisible
    tile up front (zero new executables across chunk boundaries on a mesh,
    no per-chunk re-pad)."""
    from ..parallel.mesh import DATA_AXIS, current_mesh

    bucket = _transform_bucket(int(n))
    mesh = current_mesh()
    if mesh is not None:
        bucket += (-bucket) % int(mesh.shape[DATA_AXIS])
    return bucket


def fused_transforms_enabled() -> bool:
    """Process-wide default for the fused transform path (TMOG_FUSED_TRANSFORM,
    on unless explicitly set to 0)."""
    return os.environ.get("TMOG_FUSED_TRANSFORM", "1") != "0"


#: planner failures that were rerouted to the per-stage host path, process-
#: wide.  "Nothing fuses" / "listener active" returns are decisions, not
#: failures, and are not counted.  chip_smoke.py asserts this stays 0 and
#: ``Workflow.train(strict=True)`` raises when it moves — a device prefix
#: that stopped compiling must not hide behind a warning (ROADMAP D7).
_FALLBACKS = 0
_FALLBACK_LOCK = threading.Lock()
_LAST_FALLBACK: Optional[BaseException] = None


def planner_fallbacks() -> int:
    """How many plan-build / ``apply_prefix`` failures this process has
    rerouted to the per-stage host path."""
    with _FALLBACK_LOCK:
        return _FALLBACKS


def last_planner_fallback() -> Optional[BaseException]:
    """The exception behind the most recent counted fallback."""
    with _FALLBACK_LOCK:
        return _LAST_FALLBACK


def note_planner_fallback(what: str, exc: BaseException) -> None:
    """Count + log one planner failure the caller is about to reroute."""
    global _FALLBACKS, _LAST_FALLBACK
    with _FALLBACK_LOCK:
        _FALLBACKS += 1
        _LAST_FALLBACK = exc
        count = _FALLBACKS
    log.warning("%s failed (%s: %s); falling back to the per-stage host "
                "path [planner_fallbacks=%d]", what, type(exc).__name__, exc,
                count, exc_info=exc)


# ---------------------------------------------------------------------------
# Shared partition primitives (serve/plan.py consumes these)
# ---------------------------------------------------------------------------

def device_slots(runner) -> Tuple[int, ...]:
    """Input slots a runner's ``device_transform`` consumes (default: all)."""
    slots = getattr(runner, "device_input_slots", None)
    if slots is None:
        return tuple(range(len(runner.inputs)))
    return tuple(slots)


def partition_device_prefix(runners: Sequence[Any], entry_ok: Callable):
    """Split topo-ordered runners into (device prefix, host remainder).

    A runner joins the prefix when it exposes ``device_transform`` and every
    device-slot input is either another prefix output or admitted by
    ``entry_ok(runner, slot, feature)`` (the path-specific rule: serving
    admits raw numeric/geo features and stage-encoded inputs; the dataset
    path admits any materialized liftable/encodable column).  Returns
    ``(prefix, remainder, device_uids)`` with ``device_uids`` the feature
    uids materialized on device.
    """
    device_uids: set = set()
    prefix: List[Any] = []
    remainder: List[Any] = []
    for runner in runners:
        fn = getattr(runner, "device_transform", None)
        ok = callable(fn) and len(runner.inputs) > 0
        if ok:
            for slot in device_slots(runner):
                f = runner.inputs[slot]
                if f.uid in device_uids:
                    continue
                if entry_ok(runner, slot, f):
                    continue
                ok = False
                break
        if ok:
            prefix.append(runner)
            device_uids.add(runner.get_output().uid)
        else:
            remainder.append(runner)
    return prefix, remainder, device_uids


def run_host_stages(dataset: Dataset, runners: Sequence[Any],
                    phases: bool = True) -> Dataset:
    """Shared host-remainder entry point: the per-stage interpreted transform
    loop.  Every fused-planner consumer (training transform, CV folds, the
    serving plan's remainder, AND the serving circuit breaker's degraded
    host path) runs host stages through here, so the fallback path is the
    same code in every mode — one loop to keep alive, one set of phase spans.

    ``phases=False`` skips the per-stage phase spans: the serving hot path
    passes it when a tracer is installed at the default ``batch`` detail,
    keeping the per-flush telemetry cost down (the enclosing
    ``serve.host`` span still times the whole remainder).
    """
    from ..perf.timers import phase

    out = dataset
    if not phases:
        for runner in runners:
            out = runner.transform(out)
        return out
    for runner in runners:
        with phase(f"transform.{type(runner).__name__}"):
            out = runner.transform(out)
    return out


def _serving_entry_ok(runner, slot, f) -> bool:
    """Serving rule: raw features only, canonical lift or stage encoding."""
    return isinstance(f.origin_stage, FeatureGeneratorStage) and (
        f.ftype.kind in DEVICE_LIFT_KINDS or runner.device_lifts_input(slot))


def partition_scoring_stages(runners: Sequence[Any]):
    """The serving partition (kept under its historical name for
    serve/plan.py and the TM5xx validators)."""
    return partition_device_prefix(runners, _serving_entry_ok)


def stage_content_fingerprint(stages: Sequence[Any],
                              extra: Optional[dict] = None, *,
                              environment: bool = True) -> str:
    """Content hash of a fused program: fitted stage state + wiring extras.

    Two plans with equal fingerprints trace to identical XLA programs (stage
    constants are baked into the trace), so executables may be shared between
    them.  Unhashable stage state falls back to a process-unique token (a
    counter, NOT id() — recycled ids would let a new plan inherit a dead
    plan's executables).

    ``environment=False`` omits the kernel-dispatch and mesh tokens: the
    resulting hash names the fitted *content* alone, stable across kernel
    modes, mesh topologies, and hosts.  The deploy artifact manifest
    (deploy/bundle.py) records it so a hydrator can distinguish *stale
    content* (content fingerprints differ → TM510 refusal) from mere
    *environment drift* (content equal, executable key differs → clean
    miss back to live compilation).  Executable-cache keys must always use
    the default environment-qualified form.
    """
    from ..parallel.mesh import mesh_token
    from ..perf.kernels.dispatch import cache_token
    from ..stages.base import Estimator
    from .serde import _Encoder, encode_stage

    enc = _Encoder()
    try:
        payload = {
            "stages": [encode_stage(s, enc, full=not isinstance(s, Estimator))
                       for s in stages],
            "extra": extra or {},
        }
        if environment:
            # kernel dispatch mode (perf/kernels/dispatch.py): encode/
            # bucketize stages trace to Pallas or XLA kernels depending on
            # it, so plans in different modes must never share executables
            payload["kernels"] = cache_token()
            # ambient mesh + process topology (parallel/mesh.py): the fused
            # prefix bakes its sharding annotations at trace time, so a
            # multi-host plan must never alias a single-host plan of the
            # same fitted content (same rule run_cached keys enforce)
            payload["mesh"] = mesh_token()
        h = hashlib.sha256(
            json.dumps(payload, sort_keys=True, default=repr).encode())
        for key in sorted(enc.arrays):
            arr = np.ascontiguousarray(enc.arrays[key])
            h.update(f"{key}:{arr.shape}:{arr.dtype}".encode())
            h.update(arr.tobytes())
        return h.hexdigest()
    except Exception:
        return f"unshared-{next(_UNSHARED_TOKENS)}"


# ---------------------------------------------------------------------------
# Columnar (Dataset -> Dataset) fused plan
# ---------------------------------------------------------------------------

def _lift_column(col: Column) -> np.ndarray:
    """Canonical device operand of a materialized column: float32 rows, NaN
    where the validity mask is off; vector/geo columns ship their block."""
    kind = col.kind
    if kind is ColumnKind.VECTOR:
        return np.asarray(col.data, np.float32)
    if kind is ColumnKind.GEO:
        # invalid rows are already zeroed in the host representation
        return np.asarray(col.data, np.float32)
    return col.values_f64().astype(np.float32)


class ColumnarTransformPlan:
    """Fitted topo-ordered runners compiled into one fused columnar program.

    ``plan.transform(dataset)`` appends every stage's output column — the
    same Dataset the per-stage interpreted loop produces — with the device
    prefix executed as one jitted program and the host remainder through the
    ordinary ``transform`` path.
    """

    def __init__(self, runners: Sequence[Any], available: frozenset):
        self._runners = list(runners)
        self._available = frozenset(available)

        def entry_ok(runner, slot, f):
            if f.name not in self._available:
                return False
            return (f.ftype.kind in DATASET_LIFT_KINDS
                    or runner.device_lifts_input(slot))

        self._prefix, self._remainder, self._device_uids = \
            partition_device_prefix(self._runners, entry_ok)
        self._build_entries()
        self._build_wiring()
        self._fingerprint = stage_content_fingerprint(
            self._prefix,
            extra={"entries": [list(k) for k in self._entry_keys],
                   "outs": self._out_uids})
        #: (input-meta signature, {out uid -> zero-row template column})
        self._out_info: Optional[Tuple[tuple, Dict[str, Column]]] = None
        self._jitted = None
        self._fold_programs: Dict[tuple, Any] = {}

    # -- introspection -------------------------------------------------------
    @property
    def fingerprint(self) -> str:
        return self._fingerprint

    @property
    def device_stage_uids(self) -> List[str]:
        return [s.uid for s in self._prefix]

    @property
    def host_stage_uids(self) -> List[str]:
        return [s.uid for s in self._remainder]

    # -- construction --------------------------------------------------------
    def _build_entries(self) -> None:
        """Entry operand table: ``("lift", feature_uid)`` canonical lifts
        (shared by every consumer) or ``("enc", stage_uid, slot)`` per-stage
        host encodings; sources are dataset column names."""
        entry_keys: List[tuple] = []
        entry_index: Dict[tuple, int] = {}
        self._entry_names: Dict[tuple, str] = {}
        self._entry_encoders: Dict[tuple, Tuple[Any, int, str]] = {}
        self._slot_sources: Dict[Tuple[str, int], tuple] = {}

        for runner in self._prefix:
            for slot in device_slots(runner):
                f = runner.inputs[slot]
                if f.uid in self._device_uids:
                    self._slot_sources[(runner.uid, slot)] = ("env", f.uid)
                    continue
                if f.ftype.kind in DATASET_LIFT_KINDS \
                        and not runner.device_lifts_input(slot):
                    key = ("lift", f.uid)
                    if key not in entry_index:
                        entry_index[key] = len(entry_keys)
                        entry_keys.append(key)
                        self._entry_names[key] = f.name
                else:
                    key = ("enc", runner.uid, slot)
                    entry_index[key] = len(entry_keys)
                    entry_keys.append(key)
                    self._entry_encoders[key] = (runner, slot, f.name)
        self._entry_keys = entry_keys

    def _build_wiring(self) -> None:
        self._wiring: List[Tuple[Any, List[tuple], str]] = []
        entry_index = {k: i for i, k in enumerate(self._entry_keys)}
        for runner in self._prefix:
            srcs = []
            for slot in device_slots(runner):
                src = self._slot_sources.get((runner.uid, slot))
                if src is None:
                    f = runner.inputs[slot]
                    if f.ftype.kind in DATASET_LIFT_KINDS \
                            and not runner.device_lifts_input(slot):
                        src = ("entry", entry_index[("lift", f.uid)])
                    else:
                        src = ("entry", entry_index[("enc", runner.uid, slot)])
                srcs.append(src)
            self._wiring.append((runner, srcs, runner.get_output().uid))
        # the dataset path materializes EVERY prefix output (the interpreted
        # loop appends each stage's column; downstream fits may read any)
        self._out_uids = [w[2] for w in self._wiring]
        self._out_names = {r.get_output().uid: r.output_name
                           for r in self._prefix}
        #: input column names the plan reads from the dataset
        self._input_names = sorted(
            {self._entry_names[k] for k in self._entry_keys
             if k[0] == "lift"}
            | {name for (_r, _s, name) in self._entry_encoders.values()})

    def _fused(self, *entries):
        # dp x mp: every entry is a row block — pin rows to the data axis so
        # the fused prefix stays shard-local end to end (device transforms
        # are row-local by contract, so a correctly annotated prefix lowers
        # with NO collectives; the TM608 scalability pass asserts that).
        # Identity when traced without an ambient mesh.
        from ..parallel.mesh import constrain_rows

        entries = [constrain_rows(e) for e in entries]
        env: Dict[str, Any] = {}
        for runner, srcs, out_uid in self._wiring:
            ops = [env[key] if tag == "env" else entries[key]
                   for tag, key in srcs]
            env[out_uid] = runner.device_transform(*ops)
        return tuple(env[u] for u in self._out_uids)

    # -- metadata replay -----------------------------------------------------
    def _input_meta_sig(self, dataset: Dataset) -> tuple:
        """Signature of the plan inputs' VectorMetadata — output metadata is a
        function of fitted state AND input metadata, and the plan cache keys
        on fitted content only, so a cached plan re-replays when the same
        prefix content meets differently-annotated input columns."""
        sig = []
        for name in self._input_names:
            meta = dataset[name].meta if name in dataset else None
            if meta is None:
                sig.append((name, None))
            else:
                sig.append((name, hashlib.blake2b(
                    json.dumps(meta.to_dict(), sort_keys=True,
                               default=repr).encode(),
                    digest_size=8).hexdigest()))
        return tuple(sig)

    def _ensure_out_info(self, dataset: Dataset) -> Dict[str, Column]:
        """Recover each prefix output's (ftype, metadata, kind) by replaying
        the host transforms over a ZERO-ROW slice — metadata is a function of
        fitted state and input metadata only, so the empty replay yields the
        exact host-path columns at no compute cost.  Cached per input-meta
        signature (stale-metadata guard for plan-cache hits)."""
        sig = self._input_meta_sig(dataset)
        cached = self._out_info
        if cached is not None and cached[0] == sig:
            return cached[1]
        empty = np.zeros(0, dtype=np.intp)
        cols: Dict[str, Column] = {}
        needed = set()
        for runner in self._prefix:
            needed.update(f.name for f in runner.inputs)
        for name in needed:
            if name in dataset:
                cols[name] = dataset[name].take(empty)
        ds0 = Dataset(cols)
        info: Dict[str, Column] = {}
        for runner in self._prefix:
            ds0 = runner.transform(ds0)
            info[runner.get_output().uid] = ds0[runner.output_name]
        self._out_info = (sig, info)
        return info


    # -- execution -----------------------------------------------------------
    def _host_entries(self, dataset: Dataset) -> List[np.ndarray]:
        """Host operand arrays for the entry table."""
        out = []
        for key in self._entry_keys:
            if key[0] == "lift":
                out.append(_lift_column(dataset[self._entry_names[key]]))
            else:
                runner, slot, name = self._entry_encoders[key]
                out.append(np.asarray(
                    runner.encode_device_input(slot, dataset[name])))
        return out

    def _place(self, entries: List[np.ndarray], n: int,
               tile: Optional[int] = None):
        """Bucket+mesh pad the row axis and place with row sharding.

        ``tile`` overrides the bucket with a caller-computed row tile (the
        chunked epoch computes its mesh-aligned tile ONCE and pads every
        chunk to it up front, so chunk boundaries hit one executable with no
        per-chunk re-pad here)."""
        from ..parallel.mesh import current_mesh, pad_axis, place_rows

        bucket = int(tile) if tile is not None else mesh_aligned_tile(n)
        mesh = current_mesh()
        placed = [place_rows(pad_axis(e, 0, bucket)[0]
                             if e.shape[0] != bucket else e, mesh)
                  if mesh is not None else
                  pad_axis(e, 0, bucket)[0]
                  for e in entries]
        return placed, bucket

    def apply_prefix(self, dataset: Dataset,
                     tile: Optional[int] = None) -> Dataset:
        """Run ONLY the fused device prefix, appending its output columns.

        The host remainder belongs to the caller: the plan cache keys on
        prefix content alone, so a cached plan's own ``_remainder`` list may
        hold stale stage objects from an earlier train of the same prep —
        callers must run their CURRENT remainder runners.  ``tile`` pins the
        row bucket (the chunked epoch's pre-aligned chunk tile).
        """
        import jax

        from ..perf.programs import run_cached
        from ..perf.timers import phase

        if not self._prefix:
            return dataset
        info = self._ensure_out_info(dataset)
        n = dataset.n_rows
        with phase("transform.fused_plan"):
            entries = self._host_entries(dataset)
            placed, _bucket = self._place(entries, n, tile=tile)
            if self._jitted is None:
                self._jitted = jax.jit(self._fused)  # opcheck: allow(TM303) built once per plan, memoized on self._jitted
            outs = run_cached(self._jitted, *placed,
                              label=f"transform_plan/{len(self._prefix)}stages")
            cols = {}
            for uid, dev in zip(self._out_uids, outs):
                cols[self._out_names[uid]] = _materialize_from(
                    info[uid], np.asarray(dev)[:n])
        return dataset.with_columns(cols)

    def transform(self, dataset: Dataset) -> Dataset:
        """Fused device prefix + per-stage host remainder over ``dataset``.

        Only safe on a freshly built plan (the remainder list is this plan's
        own); the cached-plan entry points run ``apply_prefix`` plus the
        caller's current remainder instead.
        """
        return run_host_stages(self.apply_prefix(dataset), self._remainder)

    # -- fold-batched execution ----------------------------------------------
    def _fold_plan_ok(self, fold_by_uid: List[Dict[str, Any]]):
        """Decide the vmapped fold mode: every prefix stage must either expose
        matching-shape ``device_state`` across folds, or be content-identical
        (same baked constants) in every fold.  Returns the per-stage mode list
        (aligned with ``self._prefix``) or None when the batched program
        cannot be built.  Lookups go by uid, never by position — a cached
        plan's own runner list may predate the caller's."""
        modes = []
        for stage in self._prefix:
            per_fold = [m.get(stage.uid) for m in fold_by_uid]
            if any(r is None for r in per_fold):
                return None
            states = [r.device_state() for r in per_fold]
            if all(s is not None for s in states):
                shapes = [tuple(np.asarray(a).shape for a in s)
                          for s in states]
                if len(set(shapes)) == 1:
                    modes.append(("state", stage.uid, states))
                    continue
                return None
            # stateless / baked: every fold must hold identical content
            fps = {stage_content_fingerprint([r]) for r in per_fold}
            if len(fps) == 1 and not next(iter(fps)).startswith("unshared"):
                modes.append(("baked", stage.uid, None))
                continue
            return None
        return modes

    def transform_folds(self, dataset: Dataset,
                        fold_runners: List[List[Any]]) -> Optional[List[Dataset]]:
        """Run k fold-fitted variants of this plan's device PREFIX on ALL rows.

        ``fold_runners[f]`` is the caller's current runner list for fold f
        (any order; lookups go by uid).  When every prefix stage is
        fold-batchable the k prefix transforms run as ONE
        ``jax.vmap``-over-folds program and the per-fold prefix-materialized
        datasets return — host remainders stay with the caller.  Returns None
        when the batched program cannot be built (caller falls back to
        per-fold plans).
        """
        import jax

        from ..perf.programs import run_cached
        from ..perf.timers import phase

        if not self._prefix:
            return None
        # map the CALLER's current fold runners by uid: a cached plan's own
        # runner list may be stale (the cache keys on prefix content only),
        # so every lookup below goes through these maps, and the host
        # remainder comes from the caller's lists, never ``self._remainder``
        fold_by_uid = [{r.uid: r for r in fr} for fr in fold_runners]
        modes = self._fold_plan_ok(fold_by_uid)
        if modes is None:
            return None
        k = len(fold_runners)
        n = dataset.n_rows
        # stacked per-stage states FLATTENED to a positional array list (the
        # executable cache keys on per-operand shapes, so pytree args would
        # collapse distinct state layouts onto one key); ``state_counts``
        # records how many arrays each stateful stage owns so the program can
        # re-slice them.
        state_counts = [len(states[0]) for mode, _uid, states in modes
                        if mode == "state"]
        flat_states = [
            np.stack([np.asarray(states[f][j]) for f in range(k)])
            for mode, _uid, states in modes if mode == "state"
            for j in range(len(states[0]))]

        with phase("transform.fused_fold_plan"):
            # entries: lifts are fold-independent (broadcast); encoder entries
            # re-encode per fold with that fold's fitted runner (stacked)
            shared, per_fold = [], []
            shared_idx, fold_idx = [], []
            for i, key in enumerate(self._entry_keys):
                if key[0] == "lift":
                    shared_idx.append(i)
                    shared.append(_lift_column(
                        dataset[self._entry_names[key]]))
                else:
                    fold_idx.append(i)
                    runner, slot, name = self._entry_encoders[key]
                    col = dataset[name]
                    per_fold.append(np.stack([
                        np.asarray(fold_by_uid[f][runner.uid]
                                   .encode_device_input(slot, col))
                        for f in range(k)]))
            placed_shared, bucket = self._place(shared, n)
            # fold entries pad their ROW axis (axis 1) to the same bucket
            padded_fold = []
            for arr in per_fold:
                pad = bucket - arr.shape[1]
                if pad:
                    arr = np.pad(arr, [(0, 0), (0, pad)]
                                 + [(0, 0)] * (arr.ndim - 2))
                padded_fold.append(arr)

            wiring = self._wiring
            state_uids = {uid for mode, uid, _ in modes if mode == "state"}
            n_states, n_fold = len(flat_states), len(padded_fold)
            counts = list(state_counts)

            def fold_fn(*flat):
                states_flat = flat[:n_states]
                fold_entries = flat[n_states:n_states + n_fold]
                shared_entries = flat[n_states + n_fold:]
                entries: List[Any] = [None] * len(self._entry_keys)
                for j, i in enumerate(shared_idx):
                    entries[i] = shared_entries[j]
                for j, i in enumerate(fold_idx):
                    entries[i] = fold_entries[j]
                env: Dict[str, Any] = {}
                si = 0  # index into the per-stateful-stage layout
                at = 0  # cursor into the flat state operand list
                for runner, srcs, out_uid in wiring:
                    ops = [env[key] if tag == "env" else entries[key]
                           for tag, key in srcs]
                    if runner.uid in state_uids:
                        c = counts[si]
                        env[out_uid] = runner.device_transform_stateful(
                            tuple(states_flat[at:at + c]), *ops)
                        si += 1
                        at += c
                    else:
                        env[out_uid] = runner.device_transform(*ops)
                return tuple(env[u] for u in self._out_uids)

            key = ("fold", k)
            prog = self._fold_programs.get(key)
            if prog is None:
                in_axes = (0,) * (n_states + n_fold) \
                    + (None,) * len(placed_shared)
                prog = jax.jit(jax.vmap(fold_fn, in_axes=in_axes))  # opcheck: allow(TM303) built once per (fold count), memoized in self._fold_programs
                self._fold_programs[key] = prog
            outs = run_cached(
                prog, *flat_states, *padded_fold, *placed_shared,
                label=f"transform_plan/fold{k}x{len(self._prefix)}stages")

            datasets: List[Dataset] = []
            for f in range(k):
                cols = {}
                info = self._fold_out_info(dataset, fold_by_uid[f])
                for uid, dev in zip(self._out_uids, outs):
                    name = self._out_names[uid]
                    cols[name] = _materialize_from(
                        info[uid], np.asarray(dev[f])[:n])
                datasets.append(dataset.with_columns(cols))
        # PREFIX outputs only — the caller applies each fold's current host
        # remainder runners itself (remainder failures are real transform
        # failures, not planner failures, and must not trigger a re-run)
        return datasets

    def _fold_out_info(self, dataset: Dataset,
                       by_uid: Dict[str, Any]) -> Dict[str, Column]:
        """Zero-row metadata replay with fold-substituted runners (by uid)."""
        empty = np.zeros(0, dtype=np.intp)
        cols: Dict[str, Column] = {}
        needed = set()
        for runner in self._prefix:
            needed.update(fi.name for fi in runner.inputs)
        for name in needed:
            if name in dataset:
                cols[name] = dataset[name].take(empty)
        ds0 = Dataset(cols)
        info: Dict[str, Column] = {}
        for runner in self._prefix:
            sub = by_uid[runner.uid]
            ds0 = sub.transform(ds0)
            info[runner.get_output().uid] = ds0[sub.output_name]
        return info


def _materialize_from(template: Column, arr: np.ndarray) -> Column:
    kind = template.kind
    if kind is ColumnKind.VECTOR:
        return Column.vector(np.ascontiguousarray(arr), template.meta)
    if kind in (ColumnKind.FLOAT, ColumnKind.INT, ColumnKind.BOOL):
        mask = ~np.isnan(arr)
        data = np.where(mask, arr.astype(np.float64), 0.0)
        if kind is not ColumnKind.FLOAT:
            data = data.astype(template.data.dtype)
        return Column(template.ftype, data, mask, template.meta)
    return Column(template.ftype, np.asarray(arr), None, template.meta)


# ---------------------------------------------------------------------------
# Entry points
# ---------------------------------------------------------------------------

def plan_for(runners: Sequence[Any], available: frozenset
             ) -> Tuple[Optional[ColumnarTransformPlan], List[Any]]:
    """(cached plan for the fused prefix, CURRENT host remainder runners).

    The plan cache keys on the PREFIX content fingerprint only — a cached
    plan's executables are valid for any runner list whose prefix content
    matches, but its remainder list may be stale, so the freshly partitioned
    remainder is returned alongside for the caller to run.  Plan is None when
    nothing fuses (empty prefix).
    """
    probe = ColumnarTransformPlan(runners, available)
    if not probe._prefix:
        return None, list(probe._remainder)
    key = (probe.fingerprint, probe._available & set(probe._input_names))
    with _PLAN_CACHE_LOCK:
        hit = _PLAN_CACHE.pop(key, None)
        if hit is not None:
            _PLAN_CACHE[key] = hit  # LRU re-insert
            return hit, list(probe._remainder)
        _PLAN_CACHE[key] = probe
        evicted = []
        while len(_PLAN_CACHE) > _PLAN_CACHE_MAX:
            evicted.append(_PLAN_CACHE.pop(next(iter(_PLAN_CACHE))))
    for plan in evicted:
        # release the evicted plan's executables from the process-wide AOT
        # cache — its per-instance jitted closures (and the fitted arrays
        # they bake in) would otherwise be pinned there forever
        fns = [f for f in [plan._jitted, *plan._fold_programs.values()]
               if f is not None]
        if fns:
            from ..perf.programs import evict_program_entries

            evict_program_entries(fns)
    return probe, list(probe._remainder)


def plan_for_features(dataset: Dataset, result_features, fitted
                      ) -> Optional[ColumnarTransformPlan]:
    """The fused transform plan ``transform_dag`` would dispatch for a
    fitted workflow over ``dataset`` (None when nothing fuses or any stage
    is unfitted): the continual refit's prefix-reuse check and the tests
    that cost or fingerprint a plan derive it here, so they all see the
    SAME program the planner runs."""
    from .dag import compute_dag
    from .fit import _resolve

    runners = []
    for layer in compute_dag(result_features):
        for stage in layer:
            runner = _resolve(stage, dict(fitted))
            if runner is None:
                return None
            runners.append(runner)
    plan, _remainder = plan_for(runners, frozenset(dataset.names))
    return plan


def check_plan_hbm_budget(plan: "ColumnarTransformPlan", dataset: Dataset,
                          hbm_budget: float):
    """TM601 admission gate on a fused transform plan: estimate the prefix's
    peak live-buffer HBM at the dataset's row bucket by abstract jaxpr trace
    (checkers/plancheck.py — zero backend compiles) and raise
    :class:`OpCheckError` before the over-budget program dispatches.
    Returns the :class:`PlanCostReport` when the plan is admitted.

    An analyzer failure fails CLOSED as TM606 (still an OpCheckError): the
    armed budget contract cannot be evaluated, and silently dispatching the
    unchecked program would admit anything.
    """
    from ..checkers.diagnostics import (DiagnosticReport, OpCheckError,
                                        make_diagnostic)
    from ..checkers.plancheck import analyze_transform_plan, cost_diagnostics

    try:
        report = analyze_transform_plan(plan, dataset)
        diags = [d for d in cost_diagnostics(report, hbm_budget=hbm_budget)
                 if d.code == "TM601"]
    except Exception as e:  # noqa: BLE001 — fail closed, not raw
        raise OpCheckError(DiagnosticReport(diagnostics=[make_diagnostic(
            "TM606",
            f"hbm_budget contract requested but the plan cost could not be "
            f"computed ({type(e).__name__}: {e})")])) from e
    if diags:
        full = DiagnosticReport(diagnostics=diags, plan_cost=report)
        raise OpCheckError(full)
    return report


def fused_transform(dataset: Dataset, runners: Sequence[Any],
                    hbm_budget: Optional[float] = None
                    ) -> Optional[Dataset]:
    """Fused transform of ``runners`` over ``dataset``; None -> caller falls
    back to the per-stage path (nothing fuses, listener active, or failure).

    ``hbm_budget`` (bytes): the TM601 gate — an over-budget plan raises
    :class:`OpCheckError` (NOT a fallback: silently running the same work
    through the host path would hide the admission failure).
    """
    from ..utils.listener import active_listeners

    if not fused_transforms_enabled() or active_listeners():
        return None
    try:
        plan, remainder = plan_for(runners, frozenset(dataset.names))
        if plan is None:
            return None
    except Exception as e:  # noqa: BLE001 — transform must never get flakier
        note_planner_fallback("fused transform planning", e)
        return None
    if hbm_budget is not None:
        # deliberately OUTSIDE the fallback guard: an OpCheckError here is an
        # admission decision that must propagate, not a planner failure
        check_plan_hbm_budget(plan, dataset, hbm_budget)
    try:
        out = plan.apply_prefix(dataset)
    except Exception as e:  # noqa: BLE001 — transform must never get flakier
        note_planner_fallback("fused transform plan", e)
        return None
    # the remainder runs the caller's CURRENT stage objects; its failures are
    # real transform failures and must propagate, not trigger a re-run
    return run_host_stages(out, remainder)


def fused_fold_transforms(dataset: Dataset, during: Sequence[Any],
                          fold_runner_maps: List[Dict[str, Any]],
                          hbm_budget: Optional[float] = None
                          ) -> Optional[List[Dataset]]:
    """Apply fold-fitted ``during`` stages to ALL rows for every fold through
    the fused planner — vmapped over folds when stage states stack, else one
    fused plan per fold.  None -> caller falls back to the host loop.

    ``hbm_budget``: the TM601 gate on the fold plans.  A per-fold plan over
    budget raises :class:`OpCheckError` (never falls back); the fold-vmapped
    program holds all k folds' buffers at once, so when k x the per-fold
    peak exceeds the budget the vmapped mode is simply SKIPPED — the k
    sequential per-fold plans still fit and still run fused.
    """
    from ..utils.listener import active_listeners

    if not fused_transforms_enabled() or active_listeners():
        return None
    k = len(fold_runner_maps)
    resolved = [[m.get(s.uid, s) for s in during] for m in fold_runner_maps]
    try:
        plan0, _ = plan_for(resolved[0], frozenset(dataset.names))
        if plan0 is None:
            return None
    except Exception as e:  # noqa: BLE001
        note_planner_fallback("fused fold transform planning", e)
        return None
    vmapped_ok = True
    if hbm_budget is not None:
        # outside the fallback guard: an admission refusal must propagate
        cost = check_plan_hbm_budget(plan0, dataset, hbm_budget)
        if cost.peak_hbm_bytes * k > hbm_budget:
            vmapped_ok = False  # one fold at a time fits; k stacked don't
            log.info("fold-vmapped transform skipped: %d folds x %d bytes "
                     "peak exceeds hbm_budget %d; running per-fold plans",
                     k, cost.peak_hbm_bytes, int(hbm_budget))
    try:
        batched = plan0.transform_folds(dataset, resolved) if vmapped_ok \
            else None
        if batched is not None:
            fused_uids = set(plan0.device_stage_uids)
            remainders = [[r for r in resolved[f] if r.uid not in fused_uids]
                          for f in range(k)]
        else:
            # per-fold fused plans (fold states too ragged to vmap); these
            # dispatch one fold at a time, so each plan gets the full budget
            batched, remainders = [], []
            for f in range(k):
                plan, remainder = plan_for(resolved[f],
                                           frozenset(dataset.names))
                if plan is None:
                    return None
                if hbm_budget is not None:
                    check_plan_hbm_budget(plan, dataset, hbm_budget)
                batched.append(plan.apply_prefix(dataset))
                remainders.append(remainder)
    except Exception as e:  # noqa: BLE001
        from ..checkers.diagnostics import OpCheckError

        if isinstance(e, OpCheckError):
            raise  # admission refusal, not a planner failure to retry
        note_planner_fallback("fused fold transform", e)
        return None
    # host remainders run OUTSIDE the fallback guard: their failures are real
    # transform failures that must propagate, not planner failures to retry
    return [run_host_stages(ds_f, remainder)
            for ds_f, remainder in zip(batched, remainders)]


def clear_plan_cache() -> None:
    with _PLAN_CACHE_LOCK:
        _PLAN_CACHE.clear()
