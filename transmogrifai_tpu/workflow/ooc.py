"""Out-of-core chunked execution of fitted-stage runs — the residency layer
under ``fit_dag``/``transform_dag`` (ISSUE 13 tentpole).

Reference: the Reader layer's streaming contract (DataReader.scala:57-198)
— a table is an iterator of partitions, never a resident array; this module
applies that to the fused transform planner: a
:class:`~..data.chunked.ChunkedDataset` feeds the SAME
:class:`~.plan.ColumnarTransformPlan` one fixed-shape chunk tile at a time,
with the next chunk's disk decode prefetched behind the current chunk's
device dispatch (readers/prefetch.py), and every output column spilled back
to the chunk store as it lands.

Invariants the tests pin:

- **program identity**: a chunk tile is exactly the planner's 8192-row
  bucket, and the tail chunk pads up to it, so a whole chunked epoch hits
  ONE executable-cache entry — zero new backend compiles across chunk
  boundaries, and the cache key is the same one an in-memory dispatch of
  the same shape uses (the chunked path must not fork the program surface).
- **bitwise parity**: device transforms are row-local (stages/base.py
  contract) and host transforms are row-wise applications of fitted state,
  so per-chunk outputs concatenate to exactly the whole-table outputs.
- **bounded residency**: the host working set of an epoch is the prefetch
  depth times one chunk's input tile plus one output tile; estimator fits
  materialize ONLY their input columns (plus ``__sample_weight__``).
- **crash-and-resume**: with an :class:`~..readers.OffsetCheckpoint`, the
  epoch commits its chunk offset after each chunk's outputs are durable;
  a re-run skips the committed prefix (outputs already spilled).
"""

from __future__ import annotations

import logging
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from ..data.chunked import ChunkedDataset, ColumnChunkWriter
from ..data.dataset import Column, Dataset
from ..obs import flight as obs_flight

log = logging.getLogger(__name__)


@dataclass
class EpochStats:
    """What one chunked epoch did: chunk counts, bytes spilled, and the
    prefetcher's overlap accounting."""

    chunks_total: int = 0
    chunks_skipped: int = 0
    chunks_processed: int = 0
    bytes_spilled: int = 0
    prefetch: Dict[str, Any] = field(default_factory=dict)


def _pad_chunk(ds: Dataset, rows: int) -> Optional[Dataset]:
    """Pad a partial tail chunk up to the full chunk tile with garbage rows
    (mask off / None), so the tail dispatches through the SAME fixed-shape
    executable as every other chunk.  Device transforms are row-local, so
    the padded rows are garbage-in/garbage-out and get sliced off.  Returns
    None when a column cannot be padded (exotic subclass)."""
    n = ds.n_rows
    pad = rows - n
    if pad <= 0:
        return ds
    cols: Dict[str, Column] = {}
    for name in ds.names:
        c = ds[name]
        if type(c) is not Column:
            return None
        if c.data.dtype == object:
            extra = np.empty(pad, dtype=object)
            data = np.concatenate([c.data, extra])
            mask = None
        else:
            data = np.concatenate(
                [c.data, np.zeros((pad,) + c.data.shape[1:], c.data.dtype)])
            old = c.mask if c.mask is not None \
                else np.ones(n, dtype=np.bool_)
            mask = np.concatenate([old, np.zeros(pad, dtype=np.bool_)]) \
                if (c.mask is not None or c.is_numeric) else None
        cols[name] = Column(c.ftype, data, mask, c.meta)
    return Dataset(cols)


def _zero_row_templates(cds: ChunkedDataset, runners: Sequence[Any]
                        ) -> Dict[str, Column]:
    """Output-column templates (ftype/meta/dtype/trailing shape) by replaying
    the runners' host transforms over a ZERO-ROW slice — metadata is a
    function of fitted state and input metadata only, never of values
    (same principle as the fused planner's metadata replay)."""
    empty = np.zeros(0, dtype=np.intp)
    needed = set()
    for r in runners:
        needed.update(f.name for f in r.inputs)
    ds0 = cds.select([n for n in cds.names if n in needed]).take(empty)
    out: Dict[str, Column] = {}
    for r in runners:
        ds0 = r.transform(ds0)
        out[r.output_name] = ds0[r.output_name]
    return out


def _epoch_fingerprint(runners: Sequence[Any]) -> str:
    from .plan import stage_content_fingerprint

    return stage_content_fingerprint(list(runners))


def _epoch_id(fp: str, cds: ChunkedDataset) -> str:
    """Resume key of one epoch: fitted-runner content + table shape + the
    INGESTED DATA's identity token — a re-ingest into the same spill dir
    stamps a new token, so stale offsets (and the previous ingest's output
    chunks) can never be resumed over."""
    return (f"epoch:{fp[:24]}:{cds.n_rows}x{cds.chunk_rows}"
            f":{cds.data_token[:16]}")


def _run_host_chunk(ds: Dataset, runners: Sequence[Any]) -> Dataset:
    """Host-path chunk execution honoring the listener contract: with a
    stage-metrics listener active each stage lands one timing event PER
    CHUNK (the chunked analogue of the in-memory per-stage loop)."""
    from ..utils.listener import active_listeners, stage_timer
    from .plan import run_host_stages

    if not active_listeners():
        return run_host_stages(ds, runners)
    for r in runners:
        with stage_timer(r, "transform", ds) as finish:
            ds = r.transform(ds)
            finish(ds)
    return ds


def chunked_transform_epoch(cds: ChunkedDataset, runners: Sequence[Any],
                            hbm_budget: Optional[float] = None,
                            checkpoint=None,
                            checkpoint_id: Optional[str] = None,
                            fused: Optional[bool] = None,
                            stats: Optional[EpochStats] = None
                            ) -> ChunkedDataset:
    """Apply fitted ``runners`` to every row of ``cds``, chunk by chunk.

    The maximal device prefix runs as the fused plan (one cached executable
    for every chunk), the host remainder per stage per chunk, and each
    chunk's output columns spill to the chunk store before the next chunk's
    offset commits.  Exotic output columns (``PredictionColumn``) cannot
    spill and stay resident — an epoch producing one disables resume (a
    skipped chunk would hole the resident column).

    ``fused=False``, ``TMOG_FUSED_TRANSFORM=0``, or an active stage-metrics
    listener force the per-stage interpreted path per chunk — the same
    contract as the in-memory ``fused_transform`` gate.
    """
    from ..perf.timers import phase
    from ..readers.prefetch import PrefetchStats, prefetch_chunks
    from ..serve.faults import fault_point
    from ..utils.listener import active_listeners
    from . import resilience
    from .plan import (check_plan_hbm_budget, fused_transforms_enabled,
                       mesh_aligned_tile, note_planner_fallback, plan_for,
                       run_host_stages)

    runners = list(runners)
    if not runners:
        return cds
    if checkpoint is None:
        # ambient durability: Workflow.train(resume=dir) parks an
        # OffsetCheckpoint on the active resilience context so the chunked
        # epochs it reaches (via fused_transform's out-of-core path) commit
        # progress without threading the handle through every layer
        checkpoint = resilience.active_chunk_checkpoint()
    stats = stats if stats is not None else EpochStats()
    plan, remainder = None, runners
    if fused is not False and fused_transforms_enabled() \
            and not active_listeners():
        try:
            plan, remainder = plan_for(runners, frozenset(cds.names))
        except Exception as e:  # noqa: BLE001 — same fallback contract as fused_transform
            note_planner_fallback("chunked epoch planning", e)
            plan, remainder = None, runners

    templates = _zero_row_templates(cds, runners)
    out_names = list(templates)
    spillable = {n for n, t in templates.items() if type(t) is Column}
    resident_out = [n for n in out_names if n not in spillable]

    n_chunks = cds.n_chunks
    chunk_rows = cds.chunk_rows
    # the dispatch tile is computed ONCE per epoch: the chunk quantum rounded
    # up to the ambient mesh's data-axis multiple (mesh_aligned_tile — a
    # no-op off-mesh and on meshes whose dp axis divides 8192).  Every chunk
    # pads to THIS tile up front, so chunk boundaries under ``use_mesh`` hit
    # one row-sharded executable — never a per-chunk re-pad inside _place,
    # never a second bucket shape (regression-pinned in test_multihost.py)
    tile = mesh_aligned_tile(chunk_rows)
    stats.chunks_total = n_chunks
    if hbm_budget is not None and plan is not None and n_chunks:
        # the admission gate sees the CHUNK tile — that is the program that
        # will dispatch (an over-budget refusal must propagate, not fall back)
        check_plan_hbm_budget(plan, cds.chunk(0), hbm_budget)

    store = cds.store
    if store is None:
        from ..data.chunked import ChunkStore

        store = ChunkStore()
    # output chunk files are NAMESPACED by the epoch's runner-content
    # fingerprint: two epochs over the same table with different fitted
    # stages (shadow-scoring old vs new models, say) must not clobber each
    # other's spill files behind the functional with_spilled_columns API —
    # same content re-runs (and resumes) still land on the same files
    epoch_fp = _epoch_fingerprint(runners)
    writers = {n: ColumnChunkWriter(store, f"{n}@{epoch_fp[:12]}",
                                    chunk_rows)
               for n in spillable}

    # -- resume: skip the committed chunk prefix (outputs already durable) --
    start = 0
    epoch_id = checkpoint_id or _epoch_id(epoch_fp, cds)
    if checkpoint is not None and not resident_out:
        start = min(int(checkpoint.load(epoch_id, 0)), n_chunks)
        # trust but verify: every skipped chunk's spill files must exist —
        # a store wiped (or holed) behind the checkpoint rewinds to the
        # first missing chunk instead of resuming over the hole
        for ci in range(start):
            if not all(w.has_chunk(ci) for w in writers.values()):
                start = ci
                break
        for ci in range(start):
            chunk_n = min(chunk_rows, cds.n_rows - ci * chunk_rows)
            for w in writers.values():
                w.note_existing(chunk_n)
        stats.chunks_skipped = start
        if start:
            # flight-recorder postmortem trail: a resumed epoch records
            # exactly how much committed prefix it skipped (ISSUE 14 —
            # the out-of-core path joins the event log)
            obs_flight.record_event("chunk_resume", epoch=epoch_id,
                                    skipped_chunks=start,
                                    total_chunks=n_chunks)
    elif checkpoint is not None and resident_out:
        log.info("chunked epoch %s produces resident column(s) %s: "
                 "crash-resume disabled for this epoch", epoch_id,
                 resident_out)

    needed = set()
    for r in runners:
        needed.update(f.name for f in r.inputs)
    in_names = [n for n in cds.names if n in needed]
    resident_parts: Dict[str, List[Column]] = {n: [] for n in resident_out}

    pf_stats = PrefetchStats()
    with phase("transform.chunked_epoch"), \
            prefetch_chunks(cds, names=in_names, start=start,
                            stats=pf_stats) as chunks:
        for ci, ds_chunk in chunks:
            n = ds_chunk.n_rows

            def _process_chunk(_ci=ci, _chunk=ds_chunk, _n=n):
                # retryable under resilient_training: a transient chunk-read
                # or dispatch fault re-runs THIS chunk (outputs overwrite in
                # place; the offset commits only after success below)
                nonlocal plan
                fault_point("ingest_chunk", chunk=_ci, epoch=epoch_id)
                if plan is not None:
                    padded = _pad_chunk(_chunk, tile) or _chunk
                    try:
                        out = plan.apply_prefix(padded, tile=tile)
                    except Exception as e:  # noqa: BLE001 — fall back, stay correct
                        if resilience.active() is not None \
                                and resilience.is_retryable_training(e):
                            # transient ≠ broken plan: retry the fused path
                            # instead of demoting the rest of the epoch
                            raise
                        note_planner_fallback("chunked fused dispatch", e)
                        plan = None
                        return _run_host_chunk(_chunk, runners)
                    if padded is not _chunk:
                        out = out.take(np.arange(_n, dtype=np.intp))
                    return run_host_stages(out, remainder)
                return _run_host_chunk(_chunk, runners)

            out = resilience.retry_call(_process_chunk, "ingest_chunk",
                                        chunk=ci, epoch=epoch_id)
            for name in spillable:
                writers[name].write(ci, out[name])
            for name in resident_out:
                resident_parts[name].append(out[name])
            stats.chunks_processed += 1
            if checkpoint is not None and not resident_out:
                checkpoint.commit(epoch_id, ci + 1)

    stats.prefetch = pf_stats.to_dict()
    new_spilled = {}
    for name, w in writers.items():
        new_spilled[name] = w.finish(template=templates[name])
        stats.bytes_spilled += w.bytes_written
    out_cds = cds.with_spilled_columns(new_spilled) \
        if new_spilled else cds
    for name in resident_out:
        parts = resident_parts[name]
        col = _concat_parts(parts) if parts else templates[name]
        out_cds = out_cds.with_resident_column(name, col)
    return out_cds


def _concat_parts(parts: List[Column]) -> Column:
    """Single-pass concatenation of per-chunk resident columns — pairwise
    ``Column.concat`` would re-copy the accumulated block every chunk
    (O(chunks²) bytes on exactly the long tables this path targets)."""
    if len(parts) == 1:
        return parts[0]
    from ..models.prediction import PredictionColumn

    if all(type(p) is PredictionColumn for p in parts):
        first = parts[0]
        return PredictionColumn(
            np.concatenate([p.pred for p in parts]),
            np.concatenate([p.raw for p in parts])
            if first.raw is not None else None,
            np.concatenate([p.prob for p in parts])
            if first.prob is not None else None)
    out = parts[0]  # unknown exotic subclass: its own pairwise concat
    for p in parts[1:]:
        out = out.concat(p)
    return out


def _gate_fit_residency(cds: ChunkedDataset, stage, names,
                        host_budget: Optional[float]) -> None:
    """TM607 runtime twin of the static residency gate: the estimator-input
    materialization is the one working set a chunked fit cannot avoid — an
    armed ``host_budget`` refuses it BEFORE the columns assemble."""
    if host_budget is None:
        return
    from ..data.chunked import column_nbytes

    need = sum(column_nbytes(cds[n]) for n in names)
    if need > host_budget:
        from ..checkers.diagnostics import (DiagnosticReport, OpCheckError,
                                            make_diagnostic)

        raise OpCheckError(DiagnosticReport(diagnostics=[make_diagnostic(
            "TM607",
            f"stage {stage.uid}: fitting requires materializing "
            f"{need} bytes of input columns ({', '.join(names)}) in host "
            f"DRAM, over the armed host_budget of {int(host_budget)} bytes",
            stage_uid=stage.uid)]))


def fit_stage_list_chunked(cds: ChunkedDataset, stages, fitted,
                           on_fit=None, fused: Optional[bool] = None,
                           hbm_budget: Optional[float] = None,
                           host_budget: Optional[float] = None,
                           checkpoint=None) -> ChunkedDataset:
    """The out-of-core twin of ``fit_stage_list``: maximal runs of fitted
    runners between estimator fits execute as chunked epochs (fused prefix
    per chunk, outputs spilled), and each estimator fit materializes ONLY
    its input columns (plus ``__sample_weight__``) — the bounded working
    set the TM607 residency gate models."""
    from ..perf.timers import phase
    from ..utils.listener import stage_timer
    from .fit import _resolve

    def _name(s) -> str:
        return getattr(s, "operation_name", None) or type(s).__name__

    pending: list = []
    for stage in stages:
        runner = _resolve(stage, fitted)
        if runner is None:
            cds = chunked_transform_epoch(cds, pending, fused=fused,
                                          hbm_budget=hbm_budget,
                                          checkpoint=checkpoint)
            pending = []
            need = {f.name for f in stage.inputs}
            need.add("__sample_weight__")
            names = [n for n in cds.names if n in need]
            _gate_fit_residency(cds, stage, names, host_budget)
            ds_fit = cds.materialize(names)
            with phase(f"fit.{_name(stage)}"), \
                    stage_timer(stage, "fit", ds_fit) as finish:
                model = stage.fit(ds_fit)
                finish(None)
            fitted[stage.uid] = model
            runner = model
            if on_fit is not None:
                on_fit(model)
        pending.append(runner)
    return chunked_transform_epoch(cds, pending, fused=fused,
                                   hbm_budget=hbm_budget,
                                   checkpoint=checkpoint)


def transform_dag_chunked(cds: ChunkedDataset, result_features, fitted,
                          fused: Optional[bool] = None,
                          checkpoint=None) -> ChunkedDataset:
    """Chunked scoring: apply every fitted transformer to ``cds`` chunk by
    chunk (one fused epoch over the whole runner list)."""
    from .dag import compute_dag
    from .fit import _resolve

    runners = []
    for layer in compute_dag(result_features):
        for stage in layer:
            runner = _resolve(stage, fitted)
            if runner is None:
                raise ValueError(
                    f"Stage {stage.uid} is an unfitted estimator; cannot "
                    "score. Train the workflow first.")
            runners.append(runner)
    return chunked_transform_epoch(cds, runners, fused=fused,
                                   checkpoint=checkpoint)
