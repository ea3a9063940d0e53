"""Multi-tenant serving fleet (ISSUE 12): model registry, SLO-tiered load
shedding, HBM-aware admission/eviction, and tenant isolation under fault
injection (serve/registry.py + serve/batcher.py).

Acceptance criteria proven here:
- tenant A's poison records, breaker trip, and forced rollback leave
  tenant B's scores bitwise-unchanged vs its single-tenant run, with zero
  new backend compiles for a shared-fingerprint tenant pair;
- under injected overload with one tripped breaker, lowest-tier traffic is
  shed first, the tripped tenant degrades to its host path, every other
  tenant stays bitwise-equal to its solo run, and the admission controller
  evicts at least one cold tenant's executables instead of OOMing —
  refusals surface as the typed TM509 diagnostic.
"""

import threading
import time

import numpy as np
import pytest

from transmogrifai_tpu import (
    BinaryClassificationModelSelector,
    FeatureBuilder,
    Workflow,
    transmogrify,
)
from transmogrifai_tpu.checkers.diagnostics import OpCheckError
from transmogrifai_tpu.models.logistic import LogisticRegression
from transmogrifai_tpu.perf import measure_compiles
from transmogrifai_tpu.readers.files import DataReaders
from transmogrifai_tpu.serve import (
    DEFAULT_SLO_CLASSES,
    FaultHarness,
    FleetServer,
    LoadShedError,
    ModelRegistry,
    PoisonRecordError,
    TransientScoringError,
    UnknownTenantError,
)

MIN_BUCKET, MAX_BUCKET = 8, 64


def _train(seed: int, n: int = 220):
    """One fitted binary model + its unlabeled records; distinct seeds give
    distinct fitted content, hence distinct plan fingerprints."""
    rng = np.random.default_rng(seed)
    x1 = rng.normal(0, 1, n)
    color = rng.choice(["red", "green", "blue"], n)
    y = (rng.random(n) < 1 / (1 + np.exp(-(1.5 * x1 + (color == "red"))))
         ).astype(float)
    records = [{"label": float(y[i]), "x1": float(x1[i]),
                "color": str(color[i])} for i in range(n)]
    label = FeatureBuilder.RealNN("label").extract_field().as_response()
    f_x1 = FeatureBuilder.Real("x1").extract_field().as_predictor()
    f_color = FeatureBuilder.PickList("color").extract_field().as_predictor()
    checked = label.sanity_check(transmogrify([f_x1, f_color]))
    sel = BinaryClassificationModelSelector.with_train_validation_split(
        models=[(LogisticRegression(), [{"reg_param": 0.01}])])
    pred = label.transform_with(sel, checked)

    import pandas as pd

    model = (Workflow().set_result_features(label, pred)
             .set_reader(DataReaders.Simple.dataframe(pd.DataFrame(records)))
             ).train()
    nolabel = [{k: v for k, v in r.items() if k != "label"} for r in records]
    return model, nolabel


@pytest.fixture(scope="module")
def fleet_models():
    """Three distinct-fingerprint models (A, B, C) + records; solo plan
    scores are the bitwise single-tenant references."""
    out = {}
    for name, seed in (("A", 7), ("B", 99), ("C", 123)):
        model, records = _train(seed)
        plan = model.serving_plan(min_bucket=MIN_BUCKET,
                                  max_bucket=MAX_BUCKET)
        out[name] = (model, records, plan)
    fps = {out[k][2].fingerprint for k in out}
    assert len(fps) == 3, "fixture models must have distinct fingerprints"
    return out


def _peak(plan):
    from transmogrifai_tpu.checkers.plancheck import analyze_scoring_plan

    return int(analyze_scoring_plan(plan).peak_hbm_bytes)


class TestRegistryLifecycle:
    def test_register_routes_and_per_tenant_metrics(self, fleet_models):
        model_a, recs_a, plan_a = fleet_models["A"]
        model_b, recs_b, plan_b = fleet_models["B"]
        with FleetServer(max_batch=32, max_wait_ms=2, min_bucket=MIN_BUCKET,
                         max_bucket=MAX_BUCKET) as fleet:
            fleet.register("a", model_a, slo="gold")
            fleet.register("b", model_b, slo="bronze")
            assert fleet.tenants() == ["a", "b"]
            futs = [fleet.submit("a", r) for r in recs_a[:12]] + \
                   [fleet.submit("b", r) for r in recs_b[:12]]
            out = [f.result(timeout=30) for f in futs]
            m = fleet.metrics()
        assert out[:12] == plan_a.score(recs_a[:12])
        assert out[12:] == plan_b.score(recs_b[:12])
        assert m["tenants"]["a"]["scored_records"] == 12
        assert m["tenants"]["b"]["scored_records"] == 12
        assert m["tenants"]["a"]["slo"] == "gold"
        assert m["tenants"]["a"]["latency_p99_ms"] is not None
        assert m["fleet"]["tenants"] == 2

    def test_duplicate_and_unknown_tenant(self, fleet_models):
        model_a, recs_a, _ = fleet_models["A"]
        with FleetServer(max_batch=8, max_wait_ms=1) as fleet:
            fleet.register("a", model_a, warm=False)
            with pytest.raises(ValueError, match="already registered"):
                fleet.register("a", model_a)
            with pytest.raises(UnknownTenantError):
                fleet.submit("nope", recs_a[0])
            with pytest.raises(ValueError, match="unknown SLO"):
                fleet.register("b", model_a, slo="platinum")

    def test_shared_fingerprint_pair_compiles_once(self, fleet_models):
        """Fleet-wide dedup: the second tenant of a shared-fingerprint pair
        warms its full ladder at ZERO new backend compiles."""
        model_a, recs_a, plan_a = fleet_models["A"]
        with FleetServer(max_batch=32, max_wait_ms=2, min_bucket=MIN_BUCKET,
                         max_bucket=MAX_BUCKET) as fleet:
            fleet.register("alpha", model_a, slo="gold")
            with measure_compiles() as probe:
                fleet.register("beta", model_a, slo="silver")
            m = fleet.metrics()
            assert probe.backend_compiles == 0
            assert m["fleet"]["shared_prefix_registrations"] == 1
            assert m["tenants"]["beta"]["warm_buckets"] == \
                m["tenants"]["alpha"]["warm_buckets"]
            assert fleet.score("beta", recs_a[0], timeout=30) == \
                plan_a.score([recs_a[0]])[0]

    def test_every_tenant_past_the_first_registers_at_zero_compiles(
            self, fleet_models):
        """Four tenants on one model behind one batcher: tenants - 1 shared
        registrations, none of them compiles, every tenant's rows are the
        solo plan's bitwise and every tenant has a p99 on record."""
        model_a, recs_a, plan_a = fleet_models["A"]
        tenants = [("t_gold", "gold"), ("t_silver", "silver"),
                   ("t_bronze", "bronze"), ("t_bulk", "bronze")]
        with FleetServer(max_batch=32, max_wait_ms=2, min_bucket=MIN_BUCKET,
                         max_bucket=MAX_BUCKET,
                         max_queue=4 * 24 + 1) as fleet:
            fleet.register(tenants[0][0], model_a, slo=tenants[0][1])
            with measure_compiles() as probe:
                for t, slo in tenants[1:]:
                    fleet.register(t, model_a, slo=slo)
            assert probe.backend_compiles == 0
            assert fleet.metrics()["fleet"]["shared_prefix_registrations"] \
                == len(tenants) - 1
            futs = {t: [fleet.submit(t, r) for r in recs_a[:24]]
                    for t, _slo in tenants}
            out = {t: [f.result(timeout=30) for f in fs]
                   for t, fs in futs.items()}
            m = fleet.metrics()
        ref = plan_a.score(recs_a[:24])
        for t, _slo in tenants:
            assert out[t] == ref
            assert m["tenants"][t]["scored_records"] == 24
            assert m["tenants"][t]["latency_p99_ms"] is not None
        assert m["batcher"]["shed"] == 0

    def test_unregister_prunes_labeled_series(self, fleet_models):
        model_a, recs_a, _ = fleet_models["A"]
        with FleetServer(max_batch=8, max_wait_ms=1) as fleet:
            fleet.register("gone", model_a, warm=False)
            fleet.score("gone", recs_a[0], timeout=30)
            assert "gone" in fleet.registry.labeled_values("tenant")
            fleet.unregister("gone")
            assert "gone" not in fleet.registry.labeled_values("tenant")
            assert not [v for v in fleet.registry.labeled_values("entry")
                        if v.startswith("gone/")]
            with pytest.raises(UnknownTenantError):
                fleet.submit("gone", recs_a[0])

    def test_per_tenant_blue_green_swap_and_rollback(self, fleet_models):
        """stage/promote/rollback are per tenant: swapping tenant a leaves
        tenant b's active version untouched, and per-tenant entry labels
        stay namespaced so pruning one tenant cannot drop another's."""
        model_a, recs_a, plan_a = fleet_models["A"]
        model_b, recs_b, plan_b = fleet_models["B"]
        with FleetServer(max_batch=16, max_wait_ms=1, min_bucket=MIN_BUCKET,
                         max_bucket=MAX_BUCKET) as fleet:
            fleet.register("a", model_a, slo="gold")
            fleet.register("b", model_b, slo="silver")
            fp = fleet.stage_candidate("a", model_a, warm=False)
            assert fp == plan_a.fingerprint
            fleet.score("a", recs_a[0], timeout=30)  # mirrors to candidate
            rec = fleet.promote("a", probation_batches=2)
            assert rec["shared_prefix"] is True and rec["tenant"] == "a"
            rb = fleet.rollback("a")
            assert rb["tenant"] == "a"
            m = fleet.metrics()
            assert m["tenants"]["a"]["swap"]["swaps"] == 1
            assert m["tenants"]["a"]["swap"]["rollbacks"] == 1
            assert m["tenants"]["b"]["swap"]["swaps"] == 0
            assert fleet.score("b", recs_b[0], timeout=30) == \
                plan_b.score([recs_b[0]])[0]


class TestHbmAdmission:
    def test_eviction_lru_then_typed_refusal(self, fleet_models):
        """Over-budget registration evicts the coldest tenant's warm
        buckets (LRU by last-scored) instead of OOMing; when eviction
        cannot make room the refusal is the typed TM509 diagnostic."""
        model_a, recs_a, plan_a = fleet_models["A"]
        model_b, recs_b, plan_b = fleet_models["B"]
        model_c, recs_c, plan_c = fleet_models["C"]
        pa, pb = _peak(plan_a), _peak(plan_b)
        with FleetServer(max_batch=32, max_wait_ms=2, min_bucket=MIN_BUCKET,
                         max_bucket=MAX_BUCKET,
                         hbm_budget=pa + pb) as fleet:
            fleet.register("a", model_a, slo="gold")
            fleet.register("b", model_b, slo="bronze")
            # LRU clock: b scores first, then a — b is the cold one
            [f.result(30) for f in [fleet.submit("b", r)
                                    for r in recs_b[:8]]]
            [f.result(30) for f in [fleet.submit("a", r)
                                    for r in recs_a[:8]]]
            fleet.register("c", model_c, slo="silver")
            m = fleet.metrics()
            assert m["fleet"]["evictions"] == 1
            assert m["tenants"]["b"]["warm_buckets"] == []       # evicted
            assert m["tenants"]["a"]["warm_buckets"]             # spared
            assert m["tenants"]["c"]["warm_buckets"]             # admitted
            # the cold tenant still serves (lazy recompile, not an OOM)
            assert fleet.score("b", recs_b[0], timeout=30) == \
                plan_b.score([recs_b[0]])[0]

        # terminal refusal: nothing evictable can make a 16-byte budget fit
        fleet2 = FleetServer(max_batch=16, max_wait_ms=1, hbm_budget=16.0)
        try:
            with pytest.raises(OpCheckError, match="TM509") as ei:
                fleet2.register("tiny", model_a)
            assert [d.code for d in ei.value.report.errors()] == ["TM509"]
            assert fleet2.metrics()["fleet"]["admission_refusals"] == 1
            assert fleet2.tenants() == []  # refusal left no tenant behind
        finally:
            fleet2.close()

    def test_eviction_spares_shared_fingerprints(self, fleet_models):
        """Eviction must free real bytes: cold a2's release would free
        nothing (warm a1 shares its fingerprint), so the LRU skips it and
        evicts next-coldest b instead — the shared pair keeps serving at
        zero compiles and never loses its process-cache entries."""
        model_a, recs_a, plan_a = fleet_models["A"]
        model_b, recs_b, plan_b = fleet_models["B"]
        model_c, _, plan_c = fleet_models["C"]
        pa, pb = _peak(plan_a), _peak(plan_b)
        with FleetServer(max_batch=32, max_wait_ms=2, min_bucket=MIN_BUCKET,
                         max_bucket=MAX_BUCKET, hbm_budget=pa + pb) as fleet:
            fleet.register("a1", model_a, slo="gold")
            fleet.register("a2", model_a, slo="silver")  # shared fingerprint
            fleet.register("b", model_b, slo="bronze")
            # LRU clock, coldest first: a2, then b, then a1
            [f.result(30) for f in [fleet.submit("a2", r)
                                    for r in recs_a[:4]]]
            [f.result(30) for f in [fleet.submit("b", r)
                                    for r in recs_b[:4]]]
            [f.result(30) for f in [fleet.submit("a1", r)
                                    for r in recs_a[:4]]]
            # admitting C needs bytes: a2 (coldest) would free nothing, so
            # the controller evicts b; the shared pair is never touched
            fleet.register("c", model_c, slo="silver")
            m = fleet.metrics()
            assert m["fleet"]["evictions"] == 1
            assert m["tenants"]["b"]["warm_buckets"] == []
            assert m["tenants"]["a1"]["warm_buckets"]
            assert m["tenants"]["a2"]["warm_buckets"]
            with measure_compiles() as probe:
                out = fleet.score("a1", recs_a[0], timeout=30)
                out2 = fleet.score("a2", recs_a[0], timeout=30)
            assert probe.backend_compiles == 0
            assert out == out2 == plan_a.score([recs_a[0]])[0]


class TestTenantIsolationUnderFaults:
    def test_poison_trip_and_rollback_leave_other_tenant_bitwise(
            self, fleet_models):
        """Satellite acceptance: tenant A's poison records, breaker trip,
        and forced rollback leave tenant B's scores bitwise-unchanged and
        its p99 bounded, at zero new backend compiles for the
        shared-fingerprint pair."""
        model_a, recs_a, plan_a = fleet_models["A"]
        solo = plan_a.score(recs_a[:24])  # the single-tenant reference
        with FleetServer(max_batch=16, max_wait_ms=2, min_bucket=MIN_BUCKET,
                         max_bucket=MAX_BUCKET,
                         resilience={"max_retries": 0,
                                     "failure_threshold": 1,
                                     "recovery_batches": 1000,
                                     "seed": 0}) as fleet:
            fleet.register("victim", model_a, slo="gold")
            with measure_compiles() as probe:
                fleet.register("bystander", model_a, slo="silver")
            assert probe.backend_compiles == 0  # shared-fingerprint pair

            # victim's records carry a marker so injected faults target
            # ONLY batches containing them (the shared plan object is per
            # tenant, so the device point fires per-tenant sub-batch)
            marked = [dict(r, __victim__=1) for r in recs_a]
            harness = FaultHarness(seed=0).fail_when(
                "device",
                lambda ctx: any("__victim__" in r
                                for r in ctx.get("records", ())),
                lambda: TransientScoringError("RESOURCE_EXHAUSTED"))
            with measure_compiles() as bprobe, harness:
                vfuts = [fleet.submit("victim", r) for r in marked[:16]]
                bfuts = [fleet.submit("bystander", r) for r in recs_a[:24]]
                poison = fleet.submit(
                    "victim", {"x1": "not-a-number", "color": "red"})
                bout = [f.result(timeout=60) for f in bfuts]
                vout = [f.result(timeout=60) for f in vfuts]
                with pytest.raises(PoisonRecordError):
                    poison.result(timeout=60)
                # forced rollback churn on the victim, mid-traffic
                fleet.stage_candidate("victim", model_a, warm=False)
                fleet.promote("victim", probation_batches=0)
                fleet.rollback("victim")
                bout2 = [f.result(timeout=60) for f in
                         [fleet.submit("bystander", r) for r in recs_a[:24]]]
            m = fleet.metrics()

        # victim degraded to its host path (breaker open) yet still served
        assert m["tenants"]["victim"]["resilience"]["breaker"]["state"] \
            == "open"
        assert m["tenants"]["victim"]["resilience"]["fallback_records"] >= 16
        assert m["tenants"]["victim"]["resilience"]["quarantined"] == 1
        host_ref = plan_a.score_host(marked[:16])
        assert vout == host_ref
        # bystander: bitwise-unchanged, clean counters, bounded p99, and the
        # whole incident compiled nothing for the shared-fingerprint pair
        assert bout == solo and bout2 == solo
        assert m["tenants"]["bystander"]["resilience"]["breaker"]["state"] \
            == "closed"
        assert m["tenants"]["bystander"]["resilience"]["quarantined"] == 0
        assert m["tenants"]["bystander"]["resilience"]["fallback_records"] \
            == 0
        assert m["tenants"]["bystander"]["latency_p99_ms"] is not None
        assert m["tenants"]["bystander"]["latency_p99_ms"] < 10_000
        assert bprobe.backend_compiles == 0

    def test_route_fault_fails_only_its_tenant(self, fleet_models):
        """The per-tenant route fault point: an injected routing fault for
        tenant a fails a's co-flushed records only."""
        model_a, recs_a, plan_a = fleet_models["A"]
        with FleetServer(max_batch=32, max_wait_ms=50, min_bucket=MIN_BUCKET,
                         max_bucket=MAX_BUCKET) as fleet:
            fleet.register("a", model_a, slo="gold")
            fleet.register("b", model_a, slo="silver")
            harness = FaultHarness(seed=1).fail_when(
                "route", lambda ctx: ctx.get("tenant") == "a",
                lambda: RuntimeError("routing blackout"), times=1)
            with harness:
                afuts = [fleet.submit("a", r) for r in recs_a[:4]]
                bfuts = [fleet.submit("b", r) for r in recs_a[:4]]
                bout = [f.result(timeout=30) for f in bfuts]
                aerrs = [f.exception(timeout=30) for f in afuts]
        assert bout == plan_a.score(recs_a[:4])
        assert all(isinstance(e, RuntimeError) for e in aerrs)
        assert harness.calls["route"] >= 1


class TestOverloadEndToEnd:
    def test_overload_with_tripped_breaker_and_eviction(self, fleet_models):
        """The ISSUE acceptance e2e: N tenants + injected overload + one
        tripped breaker under the FaultHarness — lowest-tier traffic sheds
        first, the tripped tenant serves degraded from its host path, every
        other tenant stays bitwise-equal to its single-tenant run, and the
        admission controller evicted at least one cold executable along the
        way (typed TM509 refusal covered in TestHbmAdmission)."""
        model_a, recs_a, plan_a = fleet_models["A"]
        model_b, recs_b, plan_b = fleet_models["B"]
        model_c, recs_c, plan_c = fleet_models["C"]
        pa, pb = _peak(plan_a), _peak(plan_b)
        fleet = FleetServer(max_batch=4096, max_wait_ms=300.0, max_queue=32,
                            min_bucket=MIN_BUCKET, max_bucket=MAX_BUCKET,
                            hbm_budget=pa + pb,
                            resilience={"max_retries": 0,
                                        "failure_threshold": 1,
                                        "recovery_batches": 1000,
                                        "seed": 2})
        try:
            fleet.register("gold_t", model_a, slo="gold")
            fleet.register("bronze_t", model_b, slo="bronze")
            # LRU clock: bronze_t goes cold, then silver_t's registration
            # must evict it to fit the budget (admission, not OOM)
            [f.result(30) for f in [fleet.submit("bronze_t", r)
                                    for r in recs_b[:8]]]
            [f.result(30) for f in [fleet.submit("gold_t", r)
                                    for r in recs_a[:8]]]
            fleet.register("silver_t", model_c, slo="silver")
            assert fleet.metrics()["fleet"]["evictions"] >= 1

            # trip bronze_t's breaker: its marked records always fail the
            # device point, degrading bronze_t to the host path
            marked_b = [dict(r, __bad__=1) for r in recs_b]
            harness = FaultHarness(seed=2).fail_when(
                "device",
                lambda ctx: any("__bad__" in r
                                for r in ctx.get("records", ())),
                lambda: TransientScoringError("RESOURCE_EXHAUSTED"))
            with harness:
                trip = [fleet.submit("bronze_t", r) for r in marked_b[:8]]
                tout = [f.result(timeout=60) for f in trip]
                assert tout == plan_b.score_host(marked_b[:8])  # host path
                m = fleet.metrics()
                assert m["tenants"]["bronze_t"]["resilience"]["breaker"][
                    "state"] == "open"

                # overload: the degraded bronze flood fills the queue while
                # the flusher waits out its 300 ms window; the gold+silver
                # bursts shed ONLY bronze entries and complete in full
                time.sleep(0.05)  # drain the wake: queue empty, flusher idle
                flood = [fleet.submit("bronze_t", r) for r in
                         (marked_b * 2)[:32]]
                gold_burst = [fleet.submit("gold_t", r)
                              for r in recs_a[:12]]
                silver_burst = [fleet.submit("silver_t", r)
                                for r in recs_c[:8]]
                gout = [f.result(timeout=60) for f in gold_burst]
                sout = [f.result(timeout=60) for f in silver_burst]
                shed = [f for f in flood
                        if isinstance(f.exception(timeout=60),
                                      LoadShedError)]
                m = fleet.metrics()
        finally:
            fleet.close()

        # lowest tier (and degraded) shed first — exactly the burst size,
        # none of it gold or silver
        assert len(shed) == 20
        assert m["tenants"]["bronze_t"]["shed"] == 20
        assert m["tenants"]["gold_t"].get("shed", 0) == 0
        assert m["tenants"]["silver_t"].get("shed", 0) == 0
        assert m["batcher"]["rejected"] == 0
        # every other tenant: bitwise-equal to its single-tenant run
        assert gout == plan_a.score(recs_a[:12])
        assert sout == plan_c.score(recs_c[:8])
        # the tripped tenant kept serving degraded (host path, no OOM)
        assert m["tenants"]["bronze_t"]["resilience"]["fallback_records"] > 0


class TestFlightAttribution:
    def test_quarantine_and_dead_letter_events_carry_tenant(
            self, fleet_models):
        """Satellite: a poisoned record is attributable in the flight
        recorder — the quarantine/dead-letter events carry the owning
        tenant id threaded through ResilientScorer."""
        from transmogrifai_tpu.obs.flight import (FlightRecorder,
                                                  install_recorder,
                                                  uninstall_recorder)

        model_a, recs_a, _ = fleet_models["A"]
        rec = FlightRecorder()
        install_recorder(rec)
        try:
            with FleetServer(max_batch=8, max_wait_ms=2,
                             min_bucket=MIN_BUCKET, max_bucket=MAX_BUCKET,
                             resilience={"seed": 0,
                                         "dead_letter": lambda r, e: None}
                             ) as fleet:
                fleet.register("acme", model_a, slo="gold", warm=False)
                f = fleet.submit("acme",
                                 {"x1": "not-a-number", "color": "red"})
                with pytest.raises(PoisonRecordError):
                    f.result(timeout=30)
                # rollback attribution rides the same tenant id
                fleet.stage_candidate("acme", model_a, warm=False)
                fleet.promote("acme", probation_batches=0)
                fleet.rollback("acme")
        finally:
            uninstall_recorder(rec)
        q = rec.events("quarantine")
        assert q and q[-1]["data"]["tenant"] == "acme"
        dl = rec.events("dead_letter")
        assert dl and dl[-1]["data"]["tenant"] == "acme"
        rb = rec.events("rollback")
        assert rb and rb[-1]["data"]["tenant"] == "acme"


class TestSloClasses:
    def test_default_ladder_and_tiered_deadlines(self):
        assert DEFAULT_SLO_CLASSES["gold"].tier \
            > DEFAULT_SLO_CLASSES["silver"].tier \
            > DEFAULT_SLO_CLASSES["bronze"].tier

    def test_slo_deadline_applies(self):
        """A class-tiered deadline bounds queue life exactly like an
        explicit deadline_ms."""
        from transmogrifai_tpu.serve import (DeadlineExceededError,
                                             MicroBatcher, SloClass)

        gate = threading.Event()

        def scorer(rs):
            gate.wait(5)
            return list(rs)

        classes = {"rt": SloClass("rt", 2, deadline_ms=1.0),
                   "batch": SloClass("batch", 0)}
        mb = MicroBatcher(scorer, max_batch=1, max_wait_ms=1, max_queue=8,
                          slo_classes=classes, pipeline_depth=2)
        try:
            # saturate the pipelined in-flight window (depth + 1 claimed
            # batches: one finalizing, one staged, one blocked in put) so
            # the deadline request genuinely ages in the submit queue
            for i in range(3):
                mb.submit({"i": i})
            time.sleep(0.05)
            f = mb.submit({"i": 99}, slo="rt")
            with pytest.raises(DeadlineExceededError):
                f.result(timeout=10)
        finally:
            gate.set()
            mb.shutdown(drain=True, timeout=10)
        assert mb.metrics()["deadline_expired"] == 1

    def test_registry_rejects_unknown_class_at_submit(self, fleet_models):
        model_a, recs_a, _ = fleet_models["A"]
        with FleetServer(max_batch=8, max_wait_ms=1) as fleet:
            fleet.register("a", model_a, warm=False)
            with pytest.raises(ValueError, match="unknown SLO"):
                fleet.submit("a", recs_a[0], slo="diamond")


class TestRegistryStandalone:
    def test_model_registry_is_usable_without_a_batcher(self, fleet_models):
        """The control plane stands alone: registration, admission memo,
        and lifecycle work against a bare ModelRegistry."""
        model_a, recs_a, plan_a = fleet_models["A"]
        reg = ModelRegistry(min_bucket=MIN_BUCKET, max_bucket=MAX_BUCKET)
        state = reg.register("solo", model_a, slo="gold")
        assert "solo" in reg and len(reg) == 1
        out = state.swapper.score_isolated(recs_a[:4])
        assert out == plan_a.score(recs_a[:4])
        m = reg.metrics()
        assert m["fleet"]["resident_hbm_bytes"] > 0
        reg.unregister("solo")
        assert len(reg) == 0
