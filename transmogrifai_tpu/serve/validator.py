"""Servability validator — TM5xx diagnostics for the compiled scoring path.

Reference role: OpWorkflowModelLocal refuses stages it cannot convert at
load time rather than failing mid-request; this port folds the same guarantee
into the opcheck diagnostic system (checkers/diagnostics.py) so serving
hazards surface from ``workflow.validate(serving=True)``, ``cli lint
--serving``, and ``CompiledScoringPlan`` construction with stable codes:

- **TM501** (error): an estimator in the scoring path has no fitted model —
  the plan cannot transform at request time.  Only reported when a ``fitted``
  mapping is supplied (an untrained Workflow is legitimately all-estimators).
- **TM502** (warning): a stage without ``device_transform`` consumes a
  device-capable stage's output AND feeds a device-capable consumer — the
  fused prefix must stop, round-trip through host, and re-upload.
- **TM503** (warning): a raw feature whose device width is only known from
  the data (an OPVector column) feeds a device-capable stage; padding buckets
  amortize the row axis only, so every new width forces a recompile and the
  planner keeps such consumers on host.
- **TM505** (error) / **TM506** (warning): fault-tolerance configuration
  checks (:func:`check_resilience_config`) — invalid retry/breaker numbers,
  and a default deadline the flush wait makes unmeetable.  Run by
  :class:`~.server.ScoringServer` before any request is accepted.
- **TM507** (error) / **TM508** (info): blue/green swap admission
  (:func:`check_swap_compatibility`) — a staged candidate must serve the
  same result feature names AND the same precision class as the active
  model, and a fingerprint-changing swap (candidate cannot share the
  cached prefix executables) is called out.
- **TM511** (error): reduced-precision calibration parity
  (:func:`check_precision_parity`) — a bf16/int8 plan whose max prediction
  delta vs the same model's f32 plan over the calibration batch exceeds
  the class bound (``serve.plan.TM511_BOUNDS``) is refused fail-closed at
  registry admission.
- **TM509** (error): fleet HBM admission (:func:`check_fleet_admission`) —
  the multi-tenant registry (serve/registry.py) sums TM601-style static
  peak-HBM estimates across every resident warm executable; a candidate
  that still does not fit after the LRU eviction of cold tenants' buckets
  is refused with this code instead of OOMing the device.
- **TM601** (error): HBM admission (:func:`check_plan_admission`) — the
  plan's static peak live-buffer estimate at its largest padding bucket
  (checkers/plancheck.py, abstract jaxpr trace) exceeds the configured
  device budget; the plan refuses to build instead of OOMing mid-request.
"""

from __future__ import annotations

from typing import Any, Dict, List, Mapping, Optional, Sequence

from ..checkers.diagnostics import Diagnostic, DiagnosticReport, make_diagnostic
from ..features.feature import Feature
from ..features.generator import FeatureGeneratorStage
from ..stages.base import Estimator
from ..types import ColumnKind


def check_resilience_config(*, max_retries: int = 0,
                            backoff_base_s: float = 0.05,
                            backoff_cap_s: float = 1.0,
                            failure_threshold: int = 3,
                            recovery_batches: int = 8,
                            dead_letter: Any = None,
                            default_deadline_ms: Optional[float] = None,
                            max_wait_ms: Optional[float] = None
                            ) -> DiagnosticReport:
    """Static validation of the serving fault-tolerance parameters.

    TM505 (error): numerically impossible retry/backoff/breaker settings, or
    a non-callable dead-letter hook — the layer could never run as asked.
    TM506 (warning): a default deadline no longer than the batcher's flush
    wait, so every request that waits out a full flush window is evicted
    unscored.
    """
    report = DiagnosticReport()

    def bad(msg: str) -> None:
        report.extend([make_diagnostic("TM505", msg)])

    if max_retries < 0:
        bad(f"max_retries must be >= 0, got {max_retries}")
    if backoff_base_s <= 0 or backoff_cap_s <= 0:
        bad(f"backoff seconds must be > 0, got base={backoff_base_s}, "
            f"cap={backoff_cap_s}")
    if backoff_cap_s < backoff_base_s:
        bad(f"backoff_cap_s ({backoff_cap_s}) < backoff_base_s "
            f"({backoff_base_s}): the cap would truncate the first retry")
    if failure_threshold < 1:
        bad(f"failure_threshold must be >= 1, got {failure_threshold}")
    if recovery_batches < 1:
        bad(f"recovery_batches must be >= 1, got {recovery_batches}")
    if dead_letter is not None and not callable(dead_letter):
        bad(f"dead_letter must be callable, got {type(dead_letter).__name__}")
    if default_deadline_ms is not None and default_deadline_ms <= 0:
        bad(f"default_deadline_ms must be > 0, got {default_deadline_ms}")
    if default_deadline_ms is not None and max_wait_ms is not None \
            and 0 < default_deadline_ms <= max_wait_ms:
        report.extend([make_diagnostic(
            "TM506",
            f"default deadline ({default_deadline_ms} ms) is not longer "
            f"than the batcher flush wait ({max_wait_ms} ms); queued "
            "requests will expire before they can flush")])
    return report


def check_plan_admission(plan, hbm_budget: float) -> DiagnosticReport:
    """HBM admission control for a compiled scoring plan (TM601).

    Traces the plan's fused prefix abstractly across its padding-bucket
    ladder (checkers/plancheck.py — zero backend compiles, zero data) and
    reports TM601 when the peak live-buffer estimate at any bucket exceeds
    ``hbm_budget`` bytes.  :class:`~.plan.CompiledScoringPlan` runs this at
    construction when a budget is configured, so a plan that cannot fit the
    device is rejected before any executable compiles — the admission seam
    the multi-tenant serving fleet (ROADMAP) builds on.
    """
    from ..checkers.plancheck import analyze_scoring_plan, cost_diagnostics

    report = DiagnosticReport()
    if not plan.device_stage_uids:
        return report  # all-host plan: no device buffers to admit
    cost = analyze_scoring_plan(plan)
    report.plan_cost = cost
    # TM601 gates admission; TM609 (per-host replicated operands over the
    # budget share — the pod-scale blocker) rides along as a warning when
    # the plan was built under a mesh, so fleet operators see the scale-out
    # ceiling at admission time instead of at the first multi-host deploy
    report.extend(d for d in cost_diagnostics(cost, hbm_budget=hbm_budget)
                  if d.code in ("TM601", "TM609"))
    return report


def check_fleet_admission(tenant: str, need_bytes: float,
                          resident_bytes: float, hbm_budget: float,
                          evicted: Sequence[str] = ()) -> DiagnosticReport:
    """Fleet-wide HBM admission (TM509) for the multi-tenant registry.

    ``need_bytes`` is the candidate plan's static peak-HBM estimate
    (TM601's per-plan number, :func:`check_plan_admission`);
    ``resident_bytes`` sums the estimates of every DISTINCT warm fingerprint
    still resident after the registry's LRU eviction pass (a candidate
    sharing a resident fingerprint costs nothing extra).  Reports TM509
    when the fleet still does not fit — the registry raises it as a typed
    refusal instead of trial-and-error OOMing the device.
    """
    report = DiagnosticReport()
    if need_bytes + resident_bytes > hbm_budget:
        evicted_note = (
            f" (after evicting {len(evicted)} cold tenant(s): "
            f"{sorted(evicted)})" if evicted else "")
        report.extend([make_diagnostic(
            "TM509",
            f"cannot admit tenant {tenant!r}: candidate peak-HBM estimate "
            f"{need_bytes:.0f} B + resident warm executables "
            f"{resident_bytes:.0f} B exceed the fleet hbm_budget "
            f"{hbm_budget:.0f} B{evicted_note}")])
    return report


def check_swap_compatibility(active_plan, candidate_plan) -> DiagnosticReport:
    """Blue/green swap admission (TM507/TM508).

    TM507 (error): the candidate does not serve the same result feature
    names as the active plan — a swap would silently change the response
    schema under live clients.  TM508 (info): the candidate's fused-prefix
    fingerprint differs from the active plan's, so the swap cannot reuse the
    cached executables (a frozen-prep warm refit would); still admitted, but
    the compile cost is called out.
    """
    report = DiagnosticReport()
    active_names = sorted(f.name for f in active_plan.result_features)
    cand_names = sorted(f.name for f in candidate_plan.result_features)
    if active_names != cand_names:
        report.extend([make_diagnostic(
            "TM507",
            f"candidate serves result features {cand_names} but the active "
            f"model serves {active_names}; refusing a schema-changing swap")])
        return report
    active_prec = getattr(active_plan, "precision", "f32")
    cand_prec = getattr(candidate_plan, "precision", "f32")
    if active_prec != cand_prec:
        # a precision flip changes prediction numerics under live clients
        # exactly like a schema change — stage it as a NEW tenant (or
        # re-register) so the TM511 calibration gate and the operators see
        # it, instead of sliding it through a blue/green swap
        report.extend([make_diagnostic(
            "TM507",
            f"candidate precision class {cand_prec!r} differs from the "
            f"active plan's {active_prec!r}; refusing a numerics-changing "
            "swap")])
        return report
    if candidate_plan.fingerprint != active_plan.fingerprint:
        report.extend([make_diagnostic(
            "TM508",
            "candidate fused-prefix fingerprint "
            f"{candidate_plan.fingerprint[:12]} differs from the active "
            f"plan's {active_plan.fingerprint[:12]}; the swap compiles a "
            "fresh prefix instead of sharing the executable cache")])
    return report


def _calibration_entries(plan, n_rows: int):
    """Deterministic synthetic calibration batch for ``plan``'s fused-program
    entry operands, built from ``entry_specs`` alone: float lifts draw from a
    seeded standard normal (plus a NaN row so the missing path is exercised),
    integer encodings draw small non-negative codes (out-of-range codes are
    in-contract — they encode the untracked-null row)."""
    import numpy as np

    rng = np.random.default_rng(511)
    ops = []
    for trailing, dtype in plan.entry_specs:
        dt = np.dtype(dtype)
        shape = (n_rows,) + tuple(trailing)
        if np.issubdtype(dt, np.floating):
            arr = rng.standard_normal(shape).astype(dt) * 3.0
            if n_rows > 1 and arr.ndim == 1:
                arr[-1] = np.nan
        else:
            arr = rng.integers(0, 8, size=shape).astype(dt)
        ops.append(arr)
    return ops


def check_precision_parity(f32_plan, candidate_plan, *,
                           records: Optional[Sequence[Mapping[str, Any]]]
                           = None,
                           n_rows: int = 64) -> DiagnosticReport:
    """Calibration parity gate for reduced-precision plans (TM511).

    Scores the candidate and the same model's f32 plan over a calibration
    batch and reports TM511 when the measured delta exceeds the candidate
    class's bound (``serve.plan.TM511_BOUNDS``).  With ``records`` the gate
    is the real thing: both plans score the records end to end and the
    delta is the max absolute difference over the prediction outputs.
    Without records a deterministic synthetic batch built from the plan's
    entry specs runs through the fused PREFIX only; since prefix outputs
    are feature-space (arbitrary magnitude, unlike O(1) predictions) the
    delta is normalized by each output's max |f32| magnitude (floor 1.0) —
    a conservative stand-in that still catches a one-hot bucket flip as a
    full-magnitude violation.  The registry runs this at
    ``register()``/``stage_candidate()`` admission and refuses on error,
    fail-closed: a class whose bound is unknown is refused too.  The
    measured delta lands on the report (``max_precision_delta``) so
    statusz can surface it.
    """
    import numpy as np

    from .plan import Precision, TM511_BOUNDS

    report = DiagnosticReport()
    report.max_precision_delta = None
    precision = getattr(candidate_plan, "precision", Precision.F32)
    if precision == Precision.F32:
        return report
    bound = TM511_BOUNDS.get(precision)
    if bound is None:
        report.extend([make_diagnostic(
            "TM511",
            f"precision class {precision!r} has no documented parity bound "
            "(serve.plan.TM511_BOUNDS); refusing fail-closed")])
        return report
    if not candidate_plan.device_stage_uids:
        return report  # all-host plan: precision lowering never runs

    if records is not None:
        from ..types import Prediction

        ref_rows = f32_plan.score(list(records))
        got_rows = candidate_plan.score(list(records))
        delta = 0.0
        for ref, got in zip(ref_rows, got_rows):
            for name, rv in ref.items():
                gv = got.get(name)
                if isinstance(rv, Mapping):
                    # the argmax class decision is a step function — a
                    # boundary record legitimately flips under ANY numeric
                    # perturbation; the gate bounds the continuous scores
                    # (probability/raw margin) the decision derives from
                    delta = max(delta, *(abs(float(rv[k]) - float(gv[k]))
                                         for k in rv
                                         if k != Prediction.PredictionName),
                                0.0)
                elif isinstance(rv, (int, float)) \
                        and not isinstance(rv, bool):
                    delta = max(delta, abs(float(rv) - float(gv)))
                elif isinstance(rv, (list, tuple, np.ndarray)):
                    delta = max(delta, float(np.max(np.abs(
                        np.asarray(rv, dtype=np.float64)
                        - np.asarray(gv, dtype=np.float64)), initial=0.0)))
    else:
        ops = _calibration_entries(candidate_plan, n_rows)
        ref_outs = f32_plan._fused(*ops)
        got_outs = candidate_plan._fused(*ops)
        delta = 0.0
        for ref, got in zip(ref_outs, got_outs):
            r = np.asarray(ref)
            if not np.issubdtype(r.dtype, np.floating):
                continue
            d = np.abs(r.astype(np.float64)
                       - np.asarray(got).astype(np.float64))
            # feature-space outputs: normalize by the f32 magnitude so the
            # prediction-space bounds stay meaningful (see docstring)
            norm = max(1.0, float(np.max(np.nan_to_num(np.abs(r)),
                                         initial=0.0)))
            delta = max(delta,
                        float(np.max(np.nan_to_num(d), initial=0.0)) / norm)

    report.max_precision_delta = delta
    if delta > bound:
        report.extend([make_diagnostic(
            "TM511",
            f"{precision} plan's max prediction delta {delta:.3e} vs the "
            f"f32 plan over the calibration batch exceeds the class bound "
            f"{bound:.0e}; refusing the reduced-precision plan")])
    return report


def check_servability(result_features: Sequence[Feature],
                      fitted: Optional[Mapping[str, Any]] = None
                      ) -> DiagnosticReport:
    """Run the TM5xx analyzers over the DAG reached from ``result_features``.

    ``fitted`` (uid -> fitted transformer) switches the validator into
    scoring-path mode: estimators resolve to their models and missing models
    become TM501 errors.
    """
    from ..workflow.dag import all_stages
    from .plan import device_slots, partition_scoring_stages

    report = DiagnosticReport()
    stages = all_stages(result_features)

    # resolve each DAG stage to what would actually run at request time
    resolved: List[Any] = []
    for st in stages:
        runner = fitted.get(st.uid) if fitted is not None else None
        if runner is None:
            if fitted is not None and isinstance(st, Estimator):
                report.extend([make_diagnostic(
                    "TM501",
                    f"estimator {type(st).__name__} ({st.uid}) has no fitted "
                    "model in the scoring path",
                    stage_uid=st.uid)])
            runner = st
        resolved.append(runner)

    prefix, remainder, device_uids = partition_scoring_stages(resolved)

    # TM504 (info) — the planner's prefix/remainder split, so `cli lint
    # --serving` shows what will fuse before any data is touched
    if resolved:
        host_names = ", ".join(sorted({type(r).__name__ for r in remainder})) \
            or "none"
        report.extend([make_diagnostic(
            "TM504",
            f"transform planner fuses {len(prefix)} of {len(resolved)} "
            f"stage(s) into the jitted device prefix; host remainder: "
            f"{len(remainder)} stage(s) ({host_names})")])

    # TM502 — host stage sandwiched between device-capable stages
    consumers: Dict[str, List[Any]] = {}
    for r in resolved:
        for f in r.inputs:
            consumers.setdefault(f.uid, []).append(r)
    for r in remainder:
        takes_device = any(f.uid in device_uids for f in r.inputs)
        if not takes_device:
            continue
        out_uid = r.get_output().uid
        feeds_device = any(
            callable(getattr(c, "device_transform", None))
            for c in consumers.get(out_uid, ()))
        if feeds_device:
            report.extend([make_diagnostic(
                "TM502",
                f"{type(r).__name__} ({r.uid}) has no device_transform but "
                "sits between device-capable stages; the fused scoring "
                "prefix breaks here and pays a device->host->device "
                "round-trip per batch",
                stage_uid=r.uid)])

    # TM503 — data-dependent device width entering the compiled path
    seen_raw: set = set()
    for r in resolved:
        if not callable(getattr(r, "device_transform", None)):
            continue
        for slot in device_slots(r):
            if slot >= len(r.inputs):
                continue
            f = r.inputs[slot]
            if not isinstance(f.origin_stage, FeatureGeneratorStage):
                continue
            if f.ftype.kind is ColumnKind.VECTOR and f.uid not in seen_raw:
                seen_raw.add(f.uid)
                report.extend([make_diagnostic(
                    "TM503",
                    f"raw feature {f.name!r} is an OPVector whose width is "
                    f"only known from the data; {type(r).__name__} ({r.uid}) "
                    "cannot join the bucketed fused prefix and stays on host",
                    stage_uid=r.uid)])
    return report
