"""Workflow — DAG construction, training, and the fitted WorkflowModel.

Reference: core/.../OpWorkflow.scala:59-566 (train :332-357, fitStages :368-444),
OpWorkflowCore.scala, OpWorkflowModel.scala:59-465 (score :255-269, evaluate :320-325,
summary :184-212).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import numpy as np

from ..data.dataset import Dataset
from ..evaluators.base import Evaluator
from ..features.feature import Feature
from ..features.generator import FeatureGeneratorStage
from ..models.selector import ModelSelectorSummary, SelectedModel
from ..stages.base import Estimator, Transformer
from .dag import all_stages, compute_dag, raw_feature_generators
from .fit import fit_dag, transform_dag


def dedup_raw_features(result_features: Sequence[Feature]) -> List[Feature]:
    """All raw ancestors of the result features, deduplicated by uid.

    Shared ancestors (e.g. a key column feeding both label and predictors) must be
    extracted once, not once per result feature.
    """
    out: Dict[str, Feature] = {}
    for f in result_features:
        for r in f.raw_features():
            out.setdefault(r.uid, r)
    return list(out.values())


class Workflow:
    """Lazy DAG of stages reached from the result features; ``train()`` fits it."""

    def __init__(self):
        self.result_features: List[Feature] = []
        self._input_dataset: Optional[Dataset] = None
        self._reader = None
        self._raw_feature_filter = None
        self._blacklist: List[str] = []
        self._warm_models: Dict[str, Transformer] = {}
        self._op_params = None
        self._workflow_cv = False

    def with_workflow_cv(self) -> "Workflow":
        """Move the CV loop outside the ModelSelector (OpWorkflowCore.withWorkflowCV
        :104): label-dependent feature-engineering stages re-fit inside every fold,
        so the CV estimate carries no label leakage from those fits."""
        self._workflow_cv = True
        return self

    # -- configuration -------------------------------------------------------
    def set_result_features(self, *features: Feature) -> "Workflow":
        self.result_features = list(features)
        self._validate_dag()
        if self._op_params is not None:
            self._op_params.apply_to_stages(all_stages(self.result_features))
        return self

    def set_input_dataset(self, ds: Dataset) -> "Workflow":
        self._input_dataset = ds
        return self

    def set_reader(self, reader) -> "Workflow":
        self._reader = reader
        return self

    def with_raw_feature_filter(self, rff) -> "Workflow":
        """Attach a RawFeatureFilter applied before fitting (SURVEY §2.8)."""
        self._raw_feature_filter = rff
        return self

    def with_model_stages(self, model: "WorkflowModel") -> "Workflow":
        """Warm-start: reuse fitted stages by uid (OpWorkflow.withModelStages :457-461)."""
        self._warm_models.update(model.fitted)
        return self

    def set_parameters(self, params) -> "Workflow":
        """Inject OpParams stage overrides; params set in code win
        (OpWorkflow.setStageParameters :166-188)."""
        self._op_params = params
        if self.result_features:
            params.apply_to_stages(all_stages(self.result_features))
        return self

    # -- validation (reference OpWorkflow.scala:265-323) -----------------------
    def _validate_dag(self) -> None:
        seen_uids: Dict[str, object] = {}
        for stage in all_stages(self.result_features):
            if stage.uid in seen_uids and seen_uids[stage.uid] is not stage:
                raise ValueError(f"[TM102] Duplicate stage uid in DAG: {stage.uid}")
            seen_uids[stage.uid] = stage

    def validate(self, serving: bool = False, cost: bool = False,
                 hbm_budget: Optional[float] = None,
                 single_host: bool = False,
                 host_budget: Optional[float] = None,
                 rows: Optional[int] = None) -> "DiagnosticReport":
        """Static pre-execution validation — runs WITHOUT touching data.

        Walks the DAG reached from the result features through every opcheck
        analyzer family (structural, type/shape, JAX-hazard AST lint, label
        leakage) and returns the typed :class:`DiagnosticReport`.  Shape/dtype
        checking goes through ``jax.eval_shape`` on ``ShapeDtypeStruct`` specs,
        so no device buffer is ever allocated.  See docs/static_analysis.md
        for the diagnostic code table.

        ``serving=True`` adds the TM5xx servability analyzers (host
        round-trips splitting the fused scoring prefix, unbounded shapes
        defeating padding buckets); unfitted-estimator TM501 checks need a
        fitted model — use :meth:`WorkflowModel.validate` for those.

        ``cost=True`` (or a non-None ``hbm_budget``) adds the TM6xx
        plan-cost analyzers (checkers/plancheck.py).  On an untrained
        workflow only the recompile-hazard map is computable; the full
        FLOPs/bytes/HBM analysis needs fitted stages
        (:meth:`WorkflowModel.validate`).
        """
        from ..checkers.opcheck import validate_result_features

        return validate_result_features(self.result_features,
                                        workflow_cv=self._workflow_cv,
                                        serving=serving, cost=cost,
                                        hbm_budget=hbm_budget,
                                        single_host=single_host,
                                        host_budget=host_budget, rows=rows)

    # -- data ----------------------------------------------------------------
    def raw_features(self) -> List[Feature]:
        return dedup_raw_features(self.result_features)

    def generate_raw_data(self) -> Dataset:
        if self._reader is not None:
            return self._reader.generate_dataset(self.raw_features())
        if self._input_dataset is not None:
            ds = self._input_dataset
            missing = [f.name for f in self.raw_features() if f.name not in ds]
            if missing:
                raise KeyError(f"Input dataset is missing raw feature columns: {missing}")
            return ds
        raise ValueError("No input data: call set_input_dataset or set_reader first")

    # -- training ------------------------------------------------------------
    def train(self, test_fraction: float = 0.0, seed: int = 42,
              checkpointer=None, strict: bool = False,
              hbm_budget: Optional[float] = None,
              host_budget: Optional[float] = None,
              telemetry=None, resume: Optional[str] = None) -> "WorkflowModel":
        """Fit the DAG.  ``checkpointer`` (a StageCheckpointer) persists each
        fitted stage as it completes and resumes from disk on re-run —
        sweep-level resume for preemptible hardware (SURVEY §5.4).

        ``strict=True`` runs the static validator first and raises
        :class:`OpCheckError` on any error-severity diagnostic, so a broken
        DAG fails in milliseconds instead of minutes into a TPU job.  It
        also raises ``RuntimeError`` when a fused transform plan failed and
        the fit was rerouted to the per-stage host path (otherwise a warning
        plus the ``workflow.plan.planner_fallbacks()`` counter).

        ``hbm_budget`` (bytes) arms the TM601 admission gate on every fused
        transform plan the fit builds: before a fused prefix dispatches, its
        jaxpr-level peak live-buffer estimate (checkers/plancheck.py) is
        compared against the budget and an over-budget plan raises
        :class:`OpCheckError` instead of launching a device job that will
        OOM minutes in.

        ``host_budget`` (bytes; default the ``TMOG_HOST_BUDGET`` env var)
        bounds HOST DRAM residency: an in-memory input table over the
        budget spills to a chunked store (data/chunked.py) and fits
        out-of-core through the chunked epochs (workflow/ooc.py), and the
        TM607 residency gate (checkers/plancheck.py) raises
        :class:`OpCheckError` when a materialized working set the fit
        cannot avoid (an estimator's input columns) exceeds the budget.

        ``telemetry`` (an output directory path, or a prebuilt
        :class:`~transmogrifai_tpu.obs.Telemetry`; default: the
        ``TMOG_TELEMETRY`` env var) wraps the fit in the obs backbone
        (docs/observability.md): every ``perf.phase`` site lands as a trace
        span and backend compiles land in the flight recorder, dumped as
        ``trace.json`` / ``flight.json`` / a ``metrics.jsonl`` compile-stats
        line under the directory when the fit finishes.

        ``resume`` (a directory path) makes the fit DURABLE: fitted stages
        checkpoint under ``resume/stages`` (unless a ``checkpointer`` was
        passed), every completed sweep fold-block commits to the fsync'd
        ``resume/sweep_journal.json``, chunked-epoch progress commits to
        ``resume/chunk_offsets.json``, and the whole fit runs inside
        :func:`workflow.resilience.resilient_training` — retryable faults
        retry with bounded backoff and degrade gracefully (shrunk mesh /
        smaller row bucket), and a killed run re-invoked with the same
        ``resume`` dir skips every completed block, producing a
        bitwise-identical model at zero extra warm compiles
        (docs/robustness.md).
        """
        import os
        from contextlib import ExitStack

        from ..obs import resolve_telemetry
        from ..obs.profile import maybe_profile

        with ExitStack() as stack:
            # TMOG_PROFILE: one capture of the whole train (the selector's
            # own hook finds it in flight and stays out)
            stack.enter_context(maybe_profile("train"))
            if resume is not None:
                from ..readers.streaming import OffsetCheckpoint
                from .checkpoint import StageCheckpointer
                from .resilience import SweepJournal, resilient_training

                os.makedirs(resume, exist_ok=True)
                if checkpointer is None:
                    checkpointer = StageCheckpointer(
                        os.path.join(resume, "stages"))
                stack.enter_context(resilient_training(
                    journal=SweepJournal(
                        os.path.join(resume, "sweep_journal.json")),
                    chunk_checkpoint=OffsetCheckpoint(
                        os.path.join(resume, "chunk_offsets.json")),
                    seed=seed))
            tel = resolve_telemetry(telemetry)
            if tel is None:
                return self._train(test_fraction=test_fraction, seed=seed,
                                   checkpointer=checkpointer, strict=strict,
                                   hbm_budget=hbm_budget,
                                   host_budget=host_budget)
            from ..perf import PhaseRecorder, compile_snapshot, record_phases

            # ownership-aware activation: a caller that already started this
            # bundle keeps its session — we neither stop nor dump over it
            owned = tel.activate()
            t0 = compile_snapshot()
            rec = PhaseRecorder()
            try:
                with record_phases(rec):
                    return self._train(test_fraction=test_fraction, seed=seed,
                                       checkpointer=checkpointer,
                                       strict=strict, hbm_budget=hbm_budget,
                                       host_budget=host_budget)
            finally:
                if owned:
                    # dump in the finally so a FAILED fit still leaves its
                    # trace/flight postmortem, with one export (not two)
                    tel.stop()
                    tel.dump(metrics_payload={
                        "compile": compile_snapshot().minus(t0).to_dict(),
                        "phases": rec.report(),
                        "source": "Workflow.train",
                    })

    def _train(self, test_fraction: float = 0.0, seed: int = 42,
               checkpointer=None, strict: bool = False,
               hbm_budget: Optional[float] = None,
               host_budget: Optional[float] = None) -> "WorkflowModel":
        if not self.result_features:
            raise ValueError("set_result_features before train()")
        from .plan import last_planner_fallback, planner_fallbacks

        if strict:
            report = self.validate()
            if report.errors():
                from ..checkers.diagnostics import OpCheckError

                raise OpCheckError(report)
        fallbacks_before = planner_fallbacks()
        raw = self.generate_raw_data()

        blacklist: List[str] = []
        rff_summary = None
        if self._raw_feature_filter is not None:
            from ..data.chunked import ChunkedDataset

            if isinstance(raw, ChunkedDataset):
                # the filter's distribution pass is whole-column; fall back
                # to the materialized table (logged — the filter predates
                # the out-of-core path and is typically run on samples)
                import logging

                logging.getLogger(__name__).warning(
                    "RawFeatureFilter on a chunked dataset materializes the "
                    "raw table in host DRAM")
                raw = raw.materialize()
            raw, blacklist, rff_summary = self._raw_feature_filter.filter_raw(
                raw, self.raw_features(), self.result_features)

        # host-DRAM residency (ISSUE 13): an in-memory table over the budget
        # spills to the chunked store and the whole fit goes out-of-core
        from ..data.chunked import host_budget as _env_host_budget
        from ..data.chunked import maybe_chunk

        if host_budget is None:
            host_budget = _env_host_budget()
        if host_budget is not None:
            raw = maybe_chunk(raw, budget=host_budget)

        train_ds, test_ds = (raw, None)
        if test_fraction > 0.0:
            from ..data.chunked import ChunkedDataset

            if isinstance(raw, ChunkedDataset):
                import logging

                logging.getLogger(__name__).warning(
                    "test_fraction on a chunked dataset materializes both "
                    "splits in host DRAM transiently; use test_fraction=0 "
                    "for fits whose train split must stay out-of-core")
            train_ds, test_ds = raw.split(test_fraction, seed=seed)
            if host_budget is not None:
                # the split materialized in-memory datasets: re-arm the
                # residency budget on the train split so the fit (and its
                # TM607 gate) still runs out-of-core when over budget
                train_ds = maybe_chunk(train_ds, budget=host_budget)

        preseeded_selector = None
        warm = self._warm_models
        on_fit = None
        if checkpointer is not None:
            from .checkpoint import stage_fingerprint

            by_uid = {s.uid: s for s in all_stages(self.result_features)}
            entries = checkpointer.load_entries()
            if entries:
                # bind DAG input/output features onto the resurrected models
                warm = dict(warm)
                for uid, (model, saved_fp) in entries.items():
                    dag_stage = by_uid.get(uid)
                    if dag_stage is None:
                        continue
                    if saved_fp is not None and \
                            saved_fp != stage_fingerprint(dag_stage):
                        import logging

                        logging.getLogger(__name__).warning(
                            "checkpoint for %s has different stage params; "
                            "refitting", uid)
                        continue
                    model._input_features = tuple(dag_stage.inputs)
                    model._output_feature = dag_stage.get_output()
                    warm[uid] = model

                # cascade invalidation: a checkpoint downstream of any stage
                # that will REFIT was fitted on stale inputs — drop it too.
                # Only Estimator ancestors count as refit sources: stateless
                # Transformers are deterministic given params and never enter
                # ``warm`` (param edits are caught by the lineage fingerprint
                # instead — see stage_fingerprint), so treating their absence
                # as staleness would refit every checkpointed estimator
                # downstream of a tokenize/math stage on every resume.  The
                # walk looks THROUGH transformer parents to the nearest
                # estimator ancestors, so E1 -> transform -> E2 still
                # invalidates E2 when E1 refits.
                def _estimator_ancestors(stage):
                    seen, stack, found = set(), list(stage.inputs), []
                    while stack:
                        st = stack.pop().origin_stage
                        if st is None or st.uid in seen:
                            continue
                        seen.add(st.uid)
                        if isinstance(st, Estimator):
                            found.append(st)
                        else:
                            stack.extend(st.inputs)
                    return found

                loaded_uids = set(entries) & set(warm)
                changed = True
                while changed:
                    changed = False
                    for uid in list(loaded_uids):
                        dag_stage = by_uid[uid]
                        stale = any(
                            est.uid in by_uid and est.uid not in warm
                            for est in _estimator_ancestors(dag_stage))
                        if stale:
                            del warm[uid]
                            loaded_uids.discard(uid)
                            changed = True

            def on_fit(model, _by_uid=by_uid):
                dag_stage = _by_uid.get(model.uid)
                fp = stage_fingerprint(dag_stage) if dag_stage is not None else None
                checkpointer.save_stage(model, fingerprint=fp)
        if self._workflow_cv:
            from .dag import cut_dag
            from .fit import fit_stage_list, workflow_cv_validate

            cut = cut_dag(self.result_features)
            if cut is None:
                raise ValueError("with_workflow_cv requires a ModelSelector in the DAG")
            before, during, selector = cut
            if selector.uid not in warm:  # checkpoint resume: sweep already done
                warm = dict(warm)
                ds_before = fit_stage_list(train_ds, before, warm,
                                           on_fit=on_fit,
                                           hbm_budget=hbm_budget,
                                           host_budget=host_budget)
                selector._preselected = workflow_cv_validate(
                    ds_before, during, selector, hbm_budget=hbm_budget,
                    host_budget=host_budget)
                preseeded_selector = selector

        try:
            _, fitted = fit_dag(train_ds, self.result_features, fitted=warm,
                                on_fit=on_fit, hbm_budget=hbm_budget,
                                host_budget=host_budget)
        finally:
            if preseeded_selector is not None and hasattr(
                    preseeded_selector, "_preselected"):
                del preseeded_selector._preselected

        model = WorkflowModel(
            result_features=self.result_features,
            fitted=fitted,
            blacklist=blacklist,
            rff_summary=rff_summary,
            workflow_cv=self._workflow_cv,
        )
        # the fitted model inherits the workflow's reader (reference: OpWorkflowModel
        # shares OpWorkflowCore state); override with set_reader for a scoring source
        if self._reader is not None:
            model.set_reader(self._reader)

        if strict and planner_fallbacks() > fallbacks_before:
            # the fit finished on the per-stage host path because a fused
            # plan failed to build or dispatch — under strict that is an
            # error, not a warning (workflow/plan.py note_planner_fallback)
            raise RuntimeError(
                "strict train: the fused transform planner fell back to the "
                f"host path {planner_fallbacks() - fallbacks_before} time(s)"
            ) from last_planner_fallback()

        # holdout evaluation on the test reserve (reference HasTestEval semantics)
        if test_ds is not None and test_ds.n_rows > 0:
            model._evaluate_holdout(test_ds)
        return model


class WorkflowModel:
    """A fitted workflow: score/evaluate/save, summaries and insights."""

    def __init__(self, result_features: Sequence[Feature], fitted: Dict[str, Transformer],
                 blacklist: Sequence[str] = (), rff_summary=None,
                 workflow_cv: bool = False):
        self.result_features = list(result_features)
        self.fitted = dict(fitted)
        self.blacklist = list(blacklist)
        self.rff_summary = rff_summary
        #: whether the producing workflow re-fit label-dependent stages per
        #: fold (with_workflow_cv) — validate() suppresses TM402 when so
        self.workflow_cv = workflow_cv
        self._reader = None

    def set_reader(self, reader) -> "WorkflowModel":
        self._reader = reader
        return self

    # -- scoring -------------------------------------------------------------
    def score(self, dataset: Optional[Dataset] = None,
              keep_intermediate: bool = False) -> Dataset:
        if dataset is None:
            if self._reader is None:
                raise ValueError("score() needs a dataset or a reader")
            dataset = self._reader.generate_dataset(
                dedup_raw_features(self.result_features))
        out = transform_dag(dataset, self.result_features, self.fitted)
        if keep_intermediate:
            return out
        keep = [f.name for f in self.result_features if f.name in out]
        raw_names = [c for c in dataset.names if c in out.names]
        return out.select(list(dict.fromkeys(raw_names + keep)))

    @staticmethod
    def _check_eval_args(evaluator, dataset):
        """Forgive swapped (dataset, evaluator) order; fail fast on bad types."""
        if isinstance(evaluator, Dataset) and isinstance(dataset, Evaluator):
            evaluator, dataset = dataset, evaluator
        if not isinstance(evaluator, Evaluator):
            raise TypeError(
                f"expected an Evaluator (e.g. Evaluators.binary_classification()), "
                f"got {type(evaluator).__name__}: call evaluate(evaluator, dataset)")
        return evaluator, dataset

    @staticmethod
    def _eval_view(scored, names):
        """Evaluators read whole columns — materialize exactly those when
        the scored output is chunked (the rest of the table stays spilled)."""
        from ..data.chunked import as_dataset

        return as_dataset(scored, [n for n in names if n in scored])

    def evaluate(self, evaluator: Evaluator, dataset: Optional[Dataset] = None
                 ) -> Dict[str, float]:
        evaluator, dataset = self._check_eval_args(evaluator, dataset)
        label, pred = self._label_and_pred()
        scored = self.score(dataset, keep_intermediate=True)
        view = self._eval_view(scored, [label.name, pred.name])
        return evaluator.evaluate(view, label.name, pred.name)

    def score_and_evaluate(self, evaluator: Evaluator,
                           dataset: Optional[Dataset] = None):
        evaluator, dataset = self._check_eval_args(evaluator, dataset)
        label, pred = self._label_and_pred()
        scored = self.score(dataset, keep_intermediate=True)
        view = self._eval_view(scored, [label.name, pred.name])
        metrics = evaluator.evaluate(view, label.name, pred.name)
        keep = [f.name for f in self.result_features if f.name in scored]
        return scored.select(keep), metrics

    def _label_and_pred(self):
        label = next((f for f in self.result_features if f.is_response), None)
        pred = next(
            (f for f in self.result_features if f.ftype.__name__ == "Prediction"), None)
        if label is None or pred is None:
            raise ValueError(
                "evaluate() needs a response feature and a Prediction result feature")
        return label, pred

    def _evaluate_holdout(self, test_ds: Dataset) -> None:
        try:
            label, pred = self._label_and_pred()
        except ValueError:
            return
        selector_model = self.selector_model()
        if selector_model is None:
            return
        scored = transform_dag(test_ds, self.result_features, self.fitted)
        from ..evaluators.base import (
            BinaryClassificationEvaluator,
            MultiClassificationEvaluator,
            RegressionEvaluator,
        )

        n_classes = None
        col = scored[pred.name]
        if getattr(col, "prob", None) is not None:
            n_classes = col.prob.shape[1]
        if n_classes == 2:
            ev = BinaryClassificationEvaluator()
        elif n_classes is not None and n_classes > 2:
            ev = MultiClassificationEvaluator()
        else:
            ev = RegressionEvaluator()
        selector_model.summary.holdout_evaluation = ev.evaluate(
            scored, label.name, pred.name)

    # -- introspection -------------------------------------------------------
    def selector_model(self) -> Optional[SelectedModel]:
        for t in self.fitted.values():
            if isinstance(t, SelectedModel):
                return t
        return None

    def summary(self) -> Optional[ModelSelectorSummary]:
        m = self.selector_model()
        return m.summary if m else None

    def summary_pretty(self) -> str:
        s = self.summary()
        return s.pretty() if s else "(no model selector in workflow)"

    def compute_data_up_to(self, feature: Feature, dataset: Dataset) -> Dataset:
        """Materialize the DAG only up to ``feature`` (OpWorkflowModel.computeDataUpTo)."""
        return transform_dag(dataset, [feature], self.fitted)

    def model_insights(self):
        from ..insights.model_insights import extract_model_insights

        return extract_model_insights(self)

    def score_function(self):
        """Engine-free serving closure (reference model.scoreFunction,
        OpWorkflowModelLocal.scala:93): ``scorer(record) -> {result: value}``,
        plus ``scorer.batch(records)`` for columnar multi-record scoring."""
        from ..local.scoring import score_function

        return score_function(self)

    # -- serving (serve/, docs/serving.md) -----------------------------------
    def validate(self, serving: bool = True, cost: bool = False,
                 hbm_budget: Optional[float] = None,
                 single_host: bool = False,
                 host_budget: Optional[float] = None,
                 rows: Optional[int] = None) -> "DiagnosticReport":
        """Static validation of the FITTED model, scoring-path aware.

        Same analyzer suite as :meth:`Workflow.validate` but estimators
        resolve through the fitted models, so a missing fit is a TM501
        error and the TM502/TM503 servability analyzers see the stages that
        will actually run at request time.

        ``cost=True`` (or a non-None ``hbm_budget``, or
        ``single_host=True``) additionally traces the fused scoring prefix
        abstractly (checkers/plancheck.py — zero backend compiles) and
        attaches the :class:`PlanCostReport` as ``report.plan_cost``, with
        TM601 (HBM budget), TM602 (recompile hazards), TM603 (collectives
        under a single-host contract), TM604 (memory-bound segments), and
        TM605 (order-dependent numerics) findings.
        """
        from ..checkers.opcheck import validate_result_features

        return validate_result_features(self.result_features,
                                        workflow_cv=self.workflow_cv,
                                        serving=serving, fitted=self.fitted,
                                        cost=cost, hbm_budget=hbm_budget,
                                        single_host=single_host,
                                        host_budget=host_budget, rows=rows)

    def serving_plan(self, min_bucket: int = 8, max_bucket: int = 1024,
                     strict: bool = True,
                     hbm_budget: Optional[float] = None):
        """Compile this model for online scoring
        (:class:`~transmogrifai_tpu.serve.CompiledScoringPlan`): maximal
        jit-fused device prefix + host remainder, specialized per
        power-of-two padding bucket.  ``hbm_budget`` (bytes) arms the TM601
        admission gate: a plan whose static peak-HBM estimate exceeds the
        budget refuses to build (serve/validator.py)."""
        from ..serve import compile_plan

        return compile_plan(self, min_bucket=min_bucket,
                            max_bucket=max_bucket, strict=strict,
                            hbm_budget=hbm_budget)

    def serve(self, **kwargs):
        """In-process scoring server over this model
        (:class:`~transmogrifai_tpu.serve.ScoringServer`): compiled plan
        behind a Clipper-style micro-batcher.  Close it (or use as a context
        manager) to drain the request queue cleanly."""
        from ..serve import ScoringServer

        return ScoringServer(self, **kwargs)

    # -- persistence ---------------------------------------------------------
    def save(self, path: str) -> None:
        from .serde import save_model

        save_model(self, path)

    @staticmethod
    def load(path: str) -> "WorkflowModel":
        from .serde import load_model

        return load_model(path)
