"""Fault-isolated serving (ISSUE 5): poison-record quarantine, request
deadlines, retry/backoff, and the host-path circuit breaker — all driven by
the deterministic fault harness (serve/faults.py), no sleeps-and-luck.

Acceptance criteria proven here:
- a poison record fails only its own future; co-batched survivors return
  results BITWISE equal to a clean-run score;
- an expired request is evicted without a device call;
- a scripted transient fault succeeds on retry;
- the breaker opens -> serves host-path results matching engine output
  bitwise -> a half-open probe recloses it — with zero new backend compiles
  during degradation and recovery (perf/timers.py compile probe).
"""

import json
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
from transmogrifai_tpu.local import score_function
from transmogrifai_tpu.models.logistic import LogisticRegression
from transmogrifai_tpu.perf import measure_compiles
from transmogrifai_tpu.readers.files import DataReaders
from transmogrifai_tpu.serve import (
    CircuitBreaker,
    DeadlineExceededError,
    FaultHarness,
    MicroBatcher,
    PoisonRecordError,
    QueueFullError,
    ResilientScorer,
    ScoringServer,
    TransientScoringError,
    check_resilience_config,
    is_retryable,
)


@pytest.fixture(scope="module")
def model_and_records():
    rng = np.random.default_rng(17)
    n = 240
    x1 = rng.normal(0, 1, n)
    color = rng.choice(["red", "green", "blue"], n)
    age = np.where(rng.random(n) < 0.15, None, rng.normal(40, 10, n))
    y = (rng.random(n) < 1 / (1 + np.exp(-(1.5 * x1 + (color == "red"))))
         ).astype(float)
    records = [
        {"label": float(y[i]), "x1": float(x1[i]), "color": str(color[i]),
         "age": None if age[i] is None else float(age[i])}
        for i in range(n)
    ]
    label = FeatureBuilder.RealNN("label").extract_field().as_response()
    f_x1 = FeatureBuilder.Real("x1").extract_field().as_predictor()
    f_color = FeatureBuilder.PickList("color").extract_field().as_predictor()
    f_age = FeatureBuilder.Real("age").extract_field().as_predictor()
    vec = transmogrify([f_x1, f_color, f_age])
    checked = label.sanity_check(vec)
    sel = BinaryClassificationModelSelector.with_train_validation_split(
        models=[(LogisticRegression(), [{"reg_param": 0.01}])])
    pred = label.transform_with(sel, checked)

    import pandas as pd

    df = pd.DataFrame(records)
    model = (Workflow().set_result_features(label, pred)
             .set_reader(DataReaders.Simple.dataframe(df))).train()
    nolabel = [{k: v for k, v in r.items() if k != "label"} for r in records]
    return model, nolabel, df, pred


# ---------------------------------------------------------------------------
# Fault harness
# ---------------------------------------------------------------------------

class TestFaultHarness:
    def test_script_consumed_per_firing(self):
        from transmogrifai_tpu.serve.faults import fault_point

        h = FaultHarness(seed=3).script(
            "device", [None, TransientScoringError("boom")])
        with h:
            fault_point("device")                      # entry 0: pass
            with pytest.raises(TransientScoringError):
                fault_point("device")                  # entry 1: fail
            fault_point("device")                      # beyond schedule: pass
        assert h.calls["device"] == 3
        assert h.fired == [("device", 1)]

    def test_fail_when_predicate_and_times(self):
        from transmogrifai_tpu.serve.faults import fault_point

        h = FaultHarness().fail_when(
            "encode", lambda ctx: ctx.get("n", 0) > 2,
            lambda: ValueError("big"), times=1)
        with h:
            fault_point("encode", n=1)
            with pytest.raises(ValueError):
                fault_point("encode", n=5)
            fault_point("encode", n=5)  # times=1 exhausted
        assert [p for p, _ in h.fired] == ["encode"]

    def test_single_active_harness(self):
        with FaultHarness():
            with pytest.raises(RuntimeError, match="already active"):
                FaultHarness().__enter__()
        with FaultHarness():  # released cleanly
            pass

    def test_inactive_is_noop(self):
        from transmogrifai_tpu.serve.faults import fault_point

        fault_point("device")  # no harness: must not raise

    def test_max_fires_caps_total_injected_failures(self):
        """PR 20 regression: ``max_fires`` bounds TOTAL injections per point
        — once hit, remaining schedule entries AND matching predicates pass,
        so "fail persistently, then let the degraded retry succeed"
        scenarios script in one line."""
        from transmogrifai_tpu.serve.faults import fault_point

        h = FaultHarness().script(
            "device", [TransientScoringError("a"), TransientScoringError("b"),
                       TransientScoringError("c")], max_fires=2)
        with h:
            with pytest.raises(TransientScoringError):
                fault_point("device")
            with pytest.raises(TransientScoringError):
                fault_point("device")
            fault_point("device")  # schedule entry 2 exists, but cap passes it
        assert len(h.fired) == 2
        assert h.calls["device"] == 3

        h2 = FaultHarness().fail_when(
            "encode", lambda ctx: True, lambda: ValueError("x"), max_fires=1)
        with h2:
            with pytest.raises(ValueError):
                fault_point("encode")
            fault_point("encode")  # predicate still matches; cap passes it
        assert len(h2.fired) == 1

    def test_is_retryable_classification(self):
        assert is_retryable(TransientScoringError("x"))
        assert not is_retryable(ValueError("bad payload"))

        # the class the installed jax raises for device/runtime failures
        from jax.errors import JaxRuntimeError

        assert is_retryable(JaxRuntimeError("RESOURCE_EXHAUSTED: oom"))
        assert not is_retryable(JaxRuntimeError("INVALID_ARGUMENT: shape"))


# ---------------------------------------------------------------------------
# Poison-record quarantine
# ---------------------------------------------------------------------------

class TestPoisonIsolation:
    def test_poison_fails_own_future_survivors_bitwise(self, model_and_records):
        """One malformed payload in a co-batched flush: its future alone
        fails with PoisonRecordError; every survivor's result is bitwise
        equal to a clean run of the same records."""
        model, records, *_ = model_and_records
        good = records[:7]
        poison = {"x1": "not-a-number", "color": "red", "age": 1.0}
        dead = []
        with ScoringServer(
                model, max_batch=8, max_wait_ms=200, warm=False,
                resilience={"dead_letter": lambda r, e: dead.append((r, e)),
                            "seed": 0}) as server:
            clean = server.score_batch(good)  # the clean-run reference
            futs = [server.submit(r) for r in good]
            fpoison = server.submit(poison)   # 8th record: same flush
            out = [f.result(timeout=30) for f in futs]
            with pytest.raises(PoisonRecordError):
                fpoison.result(timeout=30)
            m = server.metrics()
        assert out == clean  # dict equality on floats IS bitwise
        assert m["resilience"]["quarantined"] == 1
        assert m["resilience"]["breaker"]["state"] == "closed"
        assert m["batcher"]["failed"] == 1
        assert m["batcher"]["completed"] == 7
        assert len(dead) == 1 and dead[0][0] is poison

    def test_injected_encode_fault_bisects_to_marked_record(
            self, model_and_records):
        """Scripted encode-point failure for any batch containing the marked
        record: bisect-and-retry quarantines exactly that record."""
        model, records, *_ = model_and_records
        plan = model.serving_plan()
        rs = ResilientScorer(plan, seed=1)
        batch = list(records[:6])
        batch[3] = dict(batch[3], __mark__=1)
        clean = plan.score([r for i, r in enumerate(records[:6]) if i != 3])
        h = FaultHarness().fail_when(
            "encode",
            lambda ctx: any("__mark__" in r for r in ctx["records"]),
            lambda: ValueError("marked record rejected"))
        with h:
            out = rs.score_isolated(batch)
        assert isinstance(out[3], PoisonRecordError)
        assert [r for i, r in enumerate(out) if i != 3] == clean
        assert rs.metrics()["quarantined"] == 1
        assert rs.metrics()["bisect_batches"] >= 1

    def test_all_records_clean_passthrough(self, model_and_records):
        model, records, *_ = model_and_records
        plan = model.serving_plan()
        rs = ResilientScorer(plan, seed=2)
        assert rs.score_isolated(records[:5]) == plan.score(records[:5])
        m = rs.metrics()
        assert m["quarantined"] == 0 and m["retries"] == 0


# ---------------------------------------------------------------------------
# Request deadlines
# ---------------------------------------------------------------------------

class TestDeadlines:
    def test_expired_request_evicted_without_device_call(self):
        """Acceptance: the expired request never reaches the scorer."""
        calls = []

        def scorer(rs):
            calls.append(list(rs))
            return list(rs)

        mb = MicroBatcher(scorer, max_batch=8, max_wait_ms=60, max_queue=8)
        try:
            f = mb.submit({"i": 0}, deadline_ms=1)
            with pytest.raises(DeadlineExceededError):
                f.result(timeout=10)
        finally:
            mb.shutdown(drain=True, timeout=10)
        assert calls == []
        m = mb.metrics()
        assert m["deadline_expired"] == 1 and m["completed"] == 0

    def test_mixed_batch_scores_only_live_requests(self):
        seen = []

        def scorer(rs):
            seen.extend(r["i"] for r in rs)
            return list(rs)

        mb = MicroBatcher(scorer, max_batch=8, max_wait_ms=40, max_queue=8)
        try:
            f_dead = mb.submit({"i": 0}, deadline_ms=1)
            f_live = mb.submit({"i": 1})
            assert f_live.result(timeout=10) == {"i": 1}
            with pytest.raises(DeadlineExceededError):
                f_dead.result(timeout=10)
        finally:
            mb.shutdown(drain=True, timeout=10)
        assert seen == [1]

    def test_queue_side_eviction_makes_room_under_backpressure(self):
        gate = threading.Event()

        def scorer(rs):
            gate.wait(10)
            return list(rs)

        mb = MicroBatcher(scorer, max_batch=1, max_wait_ms=1, max_queue=2,
                          pipeline_depth=2)
        try:
            # saturate the pipelined in-flight window (depth + 1 claimed
            # batches: one finalizing, one staged, one blocked in put) so
            # later submits genuinely age in the queue; each filler must be
            # CLAIMED before the next submit or the fillers themselves
            # overflow the 2-slot queue
            fillers = []
            for _ in range(3):
                fillers.append(mb.submit({"i": 0}))
                deadline = time.monotonic() + 5
                while (mb.metrics()["queue_depth"]
                       and time.monotonic() < deadline):
                    time.sleep(0.001)
            time.sleep(0.05)
            f1 = mb.submit({"i": 1}, deadline_ms=1)   # queued, will expire
            f2 = mb.submit({"i": 2}, deadline_ms=1)   # queue now full
            time.sleep(0.02)
            f3 = mb.submit({"i": 3})       # expired entries evicted -> admitted
            with pytest.raises(DeadlineExceededError):
                f1.result(timeout=10)
            with pytest.raises(DeadlineExceededError):
                f2.result(timeout=10)
            gate.set()
            assert f3.result(timeout=10) == {"i": 3}
            assert all(f.result(timeout=10) == {"i": 0} for f in fillers)
            m = mb.metrics()
            assert m["deadline_expired"] == 2 and m["rejected"] == 0
        finally:
            gate.set()
            mb.shutdown(drain=True, timeout=10)

    def test_server_default_deadline_applies(self, model_and_records):
        model, records, *_ = model_and_records
        with ScoringServer(model, max_batch=4, max_wait_ms=100, warm=False,
                           deadline_ms=1.0) as server:
            f = server.submit(records[0])
            with pytest.raises(DeadlineExceededError):
                f.result(timeout=30)
            # an explicit per-request deadline overrides the tight default
            assert server.score(records[0], timeout=30,
                                deadline_ms=10_000)


# ---------------------------------------------------------------------------
# Transient retry with backoff
# ---------------------------------------------------------------------------

class TestTransientRetry:
    def test_scripted_transient_fault_succeeds_on_retry(self, model_and_records):
        """Acceptance: first device call fails with a transient error, the
        retry lands, results equal the clean run, nobody quarantined."""
        model, records, *_ = model_and_records
        plan = model.serving_plan()
        clean = plan.score(records[:6])
        sleeps = []
        rs = ResilientScorer(plan, max_retries=2, backoff_base_s=0.01,
                             seed=7, sleep=sleeps.append)
        h = FaultHarness(seed=7).script(
            "device", [TransientScoringError("RESOURCE_EXHAUSTED")])
        with h:
            out = rs.score_isolated(records[:6])
        assert out == clean
        m = rs.metrics()
        assert m["retries"] == 1 and m["quarantined"] == 0
        assert m["breaker"]["state"] == "closed"
        assert len(sleeps) == 1 and 0.005 <= sleeps[0] <= 0.01  # jittered base

    def test_backoff_grows_exponentially_and_is_bounded(self, model_and_records):
        model, records, *_ = model_and_records
        plan = model.serving_plan()
        sleeps = []
        rs = ResilientScorer(plan, max_retries=3, backoff_base_s=0.01,
                             backoff_cap_s=0.02, seed=8, sleep=sleeps.append)
        h = FaultHarness().script(
            "device", [TransientScoringError("oom")] * 3)
        with h:
            out = rs.score_isolated(records[:4])
        assert out == plan.score(records[:4])
        assert len(sleeps) == 3
        assert all(s <= 0.02 for s in sleeps)  # cap bounds every delay

    def test_split_to_smaller_bucket_on_batch_shaped_failure(
            self, model_and_records):
        """Retries exhausted on the full batch, halves succeed: the split
        fallback serves everything without a breaker trip."""
        model, records, *_ = model_and_records
        plan = model.serving_plan()
        rs = ResilientScorer(plan, max_retries=0, seed=9,
                             sleep=lambda s: None)
        h = FaultHarness().script(
            "device", [TransientScoringError("oom")])  # full batch only
        with h:
            out = rs.score_isolated(records[:8])
        assert out == plan.score(records[:8])
        m = rs.metrics()
        assert m["bucket_splits"] == 1
        assert m["breaker"]["state"] == "closed" and m["device_failures"] == 0


# ---------------------------------------------------------------------------
# Circuit breaker: open -> host path -> half-open -> reclose
# ---------------------------------------------------------------------------

class TestCircuitBreaker:
    def test_state_machine_unit(self):
        br = CircuitBreaker(failure_threshold=2, recovery_batches=2)
        assert br.allow_device() and br.state == br.CLOSED
        br.record_failure()
        assert br.state == br.CLOSED  # 1 of 2
        br.record_failure()
        assert br.state == br.OPEN
        assert not br.allow_device()
        br.record_host_batch()
        assert not br.allow_device()  # 1 of 2 recovery batches
        br.record_host_batch()
        assert br.allow_device() and br.state == br.HALF_OPEN  # the probe
        br.record_failure()           # probe failed: back to open (re-open)
        assert br.state == br.OPEN
        br.record_host_batch(), br.record_host_batch()
        assert br.allow_device()      # next probe
        br.record_success()
        assert br.state == br.CLOSED
        m = br.metrics()
        assert m["opened"] == 2 and m["reclosed"] == 1 and m["probes"] == 2

    def test_force_open_holds_until_force_close(self):
        br = CircuitBreaker(failure_threshold=1, recovery_batches=1)
        br.force_open()
        for _ in range(5):
            br.record_host_batch()
        assert not br.allow_device()  # held: no half-open probes
        br.force_close()
        assert br.allow_device() and br.state == br.CLOSED

    def test_breaker_degrades_to_host_bitwise_and_recloses_zero_compiles(
            self, model_and_records):
        """The acceptance sequence: persistent device failure opens the
        breaker; degraded batches serve host-path results bitwise equal to
        the engine/local output; the half-open probe recloses; the compile
        probe sees ZERO new backend compiles throughout."""
        model, records, df, pred = model_and_records
        plan = model.serving_plan(min_bucket=8, max_bucket=32)
        plan.warm()
        recs = records[:8]
        clean = plan.score(recs)          # device path, warm
        host_ref = plan.score_host(recs)  # host path, warm
        # host path == interpreted local scorer == engine, bitwise
        assert host_ref == score_function(model).batch(recs)
        ds = DataReaders.Simple.dataframe(df.head(8)).generate_dataset(
            _raws(model))
        engine_vals = model.score(ds)[pred.name].to_values()
        for row, eng in zip(host_ref, engine_vals):
            assert row[pred.name] == eng

        rs = ResilientScorer(plan, max_retries=0, failure_threshold=1,
                             recovery_batches=1, seed=4,
                             sleep=lambda s: None)
        # 4 scripted faults = the split fallback's leftmost descent for an
        # 8-record batch (8 -> 4 -> 2 -> 1; the first singleton failure
        # aborts the split): the device path is down for ALL of batch 1,
        # healthy again from batch 2 on
        h = FaultHarness(seed=4).script(
            "device", [TransientScoringError("RESOURCE_EXHAUSTED")] * 4)
        with measure_compiles() as probe:
            with h:
                out1 = rs.score_isolated(recs)   # opens -> host-served
                m1 = rs.metrics()
                out2 = rs.score_isolated(recs)   # half-open probe -> recloses
                m2 = rs.metrics()
                out3 = rs.score_isolated(recs)   # closed again, device path
            compiles = probe.backend_compiles
        assert m1["breaker"]["state"] == "open"
        assert m1["breaker"]["opened"] == 1 and m1["device_failures"] == 1
        assert m1["fallback_batches"] == 1 and m1["fallback_records"] == 8
        assert out1 == host_ref               # degraded == engine, bitwise
        assert m2["breaker"]["state"] == "closed"
        assert m2["breaker"]["reclosed"] == 1 and m2["breaker"]["probes"] == 1
        assert out2 == clean and out3 == clean
        assert m2["quarantined"] == 0         # infrastructure != poison
        assert compiles == 0, \
            "degradation/recovery must not trigger new XLA compiles"
        assert "closed->open" in m1["breaker"]["transitions"]
        assert m2["breaker"]["transitions"][-2:] == \
            ["open->half_open", "half_open->closed"]

    def test_bisect_success_resets_consecutive_failures(self, model_and_records):
        """A poison batch whose survivors score fine on the device proves the
        plan healthy: the breaker's consecutive-failure count must reset, not
        carry stale history into the next transient blip."""
        model, records, *_ = model_and_records
        plan = model.serving_plan()
        rs = ResilientScorer(plan, max_retries=0, failure_threshold=3,
                             recovery_batches=2, seed=6, sleep=lambda s: None)
        with FaultHarness().script("device", [TransientScoringError("oom")] * 2):
            rs.score_isolated(records[:1])   # transient failure 1 (singleton)
            rs.score_isolated(records[:1])   # transient failure 2
        assert rs.metrics()["breaker"]["consecutive_failures"] == 2
        batch = list(records[:3]) + [
            {"x1": "not-a-number", "color": "red", "age": None}]
        out = rs.score_isolated(batch)       # poison bisected, device healthy
        assert isinstance(out[3], PoisonRecordError)
        assert rs.metrics()["breaker"]["consecutive_failures"] == 0
        with FaultHarness().script("device", [TransientScoringError("oom")]):
            rs.score_isolated(records[:1])   # a fresh blip: 1 of 3, not 3 of 3
        m = rs.metrics()["breaker"]
        assert m["state"] == "closed" and m["opened"] == 0, m
        assert m["consecutive_failures"] == 1

    def test_breaker_open_with_poison_still_isolates(self, model_and_records):
        """Host fallback keeps per-record isolation: a poison record under an
        open breaker quarantines alone on the host path too."""
        model, records, *_ = model_and_records
        plan = model.serving_plan()
        rs = ResilientScorer(plan, max_retries=0, failure_threshold=1,
                             recovery_batches=100, seed=5,
                             sleep=lambda s: None)
        rs.breaker.force_open()
        batch = list(records[:3]) + [
            {"x1": "not-a-number", "color": "red", "age": None}]
        out = rs.score_isolated(batch)
        assert out[:3] == plan.score_host(records[:3])
        assert isinstance(out[3], PoisonRecordError)
        assert rs.metrics()["quarantined"] == 1


# ---------------------------------------------------------------------------
# Batcher accounting + server wiring
# ---------------------------------------------------------------------------

class TestBatcherAccounting:
    def test_shutdown_no_drain_counts_cancelled_not_failed(self):
        gate = threading.Event()

        def scorer(rs):
            gate.wait(10)
            return list(rs)

        mb = MicroBatcher(scorer, max_batch=1, max_wait_ms=1, max_queue=8)
        mb.submit({"i": 0})            # occupies the flusher
        time.sleep(0.05)
        futs = [mb.submit({"i": i}) for i in range(1, 4)]
        # drain=False while the flusher is still parked on the gate: the
        # queued requests are evicted as CANCELLED, not misfiled as failed
        mb.shutdown(drain=False, timeout=0.2)
        m = mb.metrics()
        assert m["cancelled"] == 3, m
        assert m["failed"] == 0, m
        for f in futs:
            assert f.done()
        gate.set()                     # release the flusher; it exits
        mb.shutdown(drain=False, timeout=10)

    def test_reclaim_counter_split_deadline_vs_cancelled_vs_shed(self):
        """Regression (ISSUE 12 satellite): the backpressure reclaim is
        deadline-then-tier aware and its accounting stays distinct — an
        expired entry counts deadline_expired, a client-cancelled entry
        discovered by the scan counts cancelled, a live lower-tier entry
        evicted for a higher-tier request counts shed.  Pre-refactor the
        scan only reclaimed expired deadlines and refused everything else
        blindly."""
        from transmogrifai_tpu.serve import LoadShedError

        gate = threading.Event()

        def scorer(rs):
            gate.wait(10)
            return list(rs)

        mb = MicroBatcher(scorer, max_batch=1, max_wait_ms=1, max_queue=3,
                          pipeline_depth=2)
        try:
            # saturate the in-flight window (see the queue-side eviction
            # test) so the reclaim-scan scenarios age in the queue
            fillers = []
            for _ in range(3):
                fillers.append(mb.submit({"i": 0}))
                deadline = time.monotonic() + 5
                while (mb.metrics()["queue_depth"]
                       and time.monotonic() < deadline):
                    time.sleep(0.001)
            time.sleep(0.05)
            f_exp = mb.submit({"i": 1}, deadline_ms=1, slo="bronze")
            f_cancel = mb.submit({"i": 2}, slo="bronze")
            f_low = mb.submit({"i": 3}, slo="bronze")   # queue now full
            time.sleep(0.02)               # f_exp's deadline passes
            # 1) deadline reclaim admits gold1 without shedding anyone
            f_gold1 = mb.submit({"i": 4}, slo="gold")
            with pytest.raises(DeadlineExceededError):
                f_exp.result(timeout=10)
            m = mb.metrics()
            assert (m["deadline_expired"], m["cancelled"], m["shed"],
                    m["rejected"]) == (1, 0, 0, 0), m
            # 2) a client-abandoned entry found by the scan is CANCELLED,
            #    not shed — removing it already makes room
            assert f_cancel.cancel()
            f_gold2 = mb.submit({"i": 5}, slo="gold")
            m = mb.metrics()
            assert (m["deadline_expired"], m["cancelled"], m["shed"],
                    m["rejected"]) == (1, 1, 0, 0), m
            # 3) queue full of live entries: the bronze one is shed for gold
            f_gold3 = mb.submit({"i": 6}, slo="gold")
            with pytest.raises(LoadShedError):
                f_low.result(timeout=10)
            m = mb.metrics()
            assert (m["deadline_expired"], m["cancelled"], m["shed"],
                    m["rejected"]) == (1, 1, 1, 0), m
            # 4) equal/lower tier never sheds: a bronze arrival against a
            #    gold-only queue is refused outright
            with pytest.raises(QueueFullError):
                mb.submit({"i": 7}, slo="bronze")
            m = mb.metrics()
            assert m["rejected"] == 1 and m["shed"] == 1, m
            gate.set()
            for f in (f_gold1, f_gold2, f_gold3, *fillers):
                assert f.result(timeout=10)
        finally:
            gate.set()
            mb.shutdown(drain=True, timeout=10)

    def test_degraded_tenant_absorbs_shedding_first(self):
        """Breaker-driven escalation: a degraded tenant's queued requests
        drop below every tier, so even its gold traffic is shed before a
        healthy tenant's bronze."""
        from transmogrifai_tpu.serve import LoadShedError

        gate = threading.Event()

        def scorer(rs):
            gate.wait(10)
            return list(rs)

        mb = MicroBatcher(scorer, max_batch=1, max_wait_ms=1, max_queue=2)
        try:
            mb.submit({"i": 0})
            time.sleep(0.05)
            mb.set_degraded("sick", True)
            f_sick = mb.submit({"i": 1}, tenant="sick", slo="gold")
            f_healthy = mb.submit({"i": 2}, tenant="ok", slo="bronze")
            f_in = mb.submit({"i": 3}, tenant="ok", slo="bronze")
            with pytest.raises(LoadShedError) as ei:
                f_sick.result(timeout=10)
            assert ei.value.tenant == "sick"
            assert not f_healthy.done()
            m = mb.metrics()
            assert m["shed"] == 1 and m["rejected"] == 0, m
            assert mb.tenant_metrics()["sick"]["shed"] == 1
            # recovery clears the demotion: the tenant sheds normally again
            mb.set_degraded("sick", False)
            gate.set()
            assert f_in.result(timeout=10) == {"i": 3}
        finally:
            gate.set()
            mb.shutdown(drain=True, timeout=10)

    def test_client_cancel_counts_cancelled(self):
        gate = threading.Event()

        def scorer(rs):
            gate.wait(10)
            return list(rs)

        mb = MicroBatcher(scorer, max_batch=1, max_wait_ms=1, max_queue=8)
        try:
            mb.submit({"i": 0})
            time.sleep(0.05)
            f = mb.submit({"i": 1})
            assert f.cancel()
            gate.set()
            mb.submit({"i": 2}).result(timeout=10)
        finally:
            gate.set()
            mb.shutdown(drain=True, timeout=10)
        m = mb.metrics()
        assert m["cancelled"] == 1 and m["failed"] == 0


class TestServerWiring:
    def test_resilient_server_matches_plain_plan(self, model_and_records):
        model, records, *_ = model_and_records
        with ScoringServer(model, max_batch=16, max_wait_ms=2,
                           warm=False) as server:
            assert server.resilience is not None
            futs = [server.submit(r) for r in records[:20]]
            out = [f.result(timeout=30) for f in futs]
            direct = server.score_batch(records[:20])
            m = server.metrics()
        assert out == direct
        assert m["resilience"]["breaker"]["state"] == "closed"
        assert m["resilience"]["quarantined"] == 0

    def test_clean_replay_counts_nothing_then_forced_open_serves_host(
            self, model_and_records):
        """A clean replay through submit() moves no failure counter; the same
        replay with the breaker pinned open is served record for record from
        the host path, bitwise, at ZERO backend compiles."""
        model, records, *_ = model_and_records
        recs = records[:96]
        with ScoringServer(model, max_batch=16, max_wait_ms=1.0,
                           max_queue=len(recs) + 1) as server:
            clean_out = [f.result(timeout=60)
                         for f in [server.submit(r) for r in recs]]
            clean = server.metrics()
            host_ref = server.plan.score_host(recs)
            server.resilience.breaker.force_open()
            with measure_compiles() as probe:
                degraded_out = [f.result(timeout=60)
                                for f in [server.submit(r) for r in recs]]
            server.resilience.breaker.force_close()
            m = server.metrics()
        res, bat = clean["resilience"], clean["batcher"]
        assert res["quarantined"] == 0 and res["retries"] == 0
        assert res["breaker"]["opened"] == 0
        assert res["fallback_records"] == 0
        assert bat["deadline_expired"] == 0 and bat["failed"] == 0
        assert bat["cancelled"] == 0
        assert bat["completed"] == bat["submitted"] == len(recs)
        assert clean_out == server.plan.score(recs)
        assert probe.backend_compiles == 0
        assert m["resilience"]["fallback_records"] == len(recs)
        assert degraded_out == host_ref
        assert m["batcher"]["failed"] == 0

    def test_resilience_opt_out(self, model_and_records):
        model, records, *_ = model_and_records
        with ScoringServer(model, max_batch=8, max_wait_ms=1, warm=False,
                           resilience=False) as server:
            assert server.resilience is None
            assert "resilience" not in server.metrics()
            assert server.score(records[0], timeout=30)

    def test_unknown_resilience_param_rejected(self, model_and_records):
        model = model_and_records[0]
        with pytest.raises(TypeError, match="unknown resilience"):
            ScoringServer(model, warm=False, resilience={"bogus": 1})


class TestResilienceConfigValidation:
    def test_tm505_errors(self):
        report = check_resilience_config(max_retries=-1, backoff_base_s=0.0,
                                         failure_threshold=0,
                                         recovery_batches=0,
                                         dead_letter="not-callable")
        codes = [d.code for d in report.errors()]
        assert codes and set(codes) == {"TM505"}
        assert len(codes) >= 4

    def test_tm506_deadline_vs_flush_wait(self):
        report = check_resilience_config(default_deadline_ms=1.0,
                                         max_wait_ms=2.0)
        assert [d.code for d in report.warnings()] == ["TM506"]
        assert not report.errors()
        ok = check_resilience_config(default_deadline_ms=50.0,
                                     max_wait_ms=2.0)
        assert not ok.by_code("TM506")

    def test_server_raises_on_invalid_config(self, model_and_records):
        from transmogrifai_tpu.checkers.diagnostics import OpCheckError

        model = model_and_records[0]
        with pytest.raises(OpCheckError, match="TM505"):
            ScoringServer(model, warm=False,
                          resilience={"failure_threshold": 0})


# ---------------------------------------------------------------------------
# cli serve hardening
# ---------------------------------------------------------------------------

class TestCliServeHardening:
    def test_malformed_lines_and_poison_records(self, model_and_records,
                                                tmp_path, capsys):
        """Malformed JSONL lines are skipped-and-counted; a poison record
        emits an {"error": ...} line in its position; the replay finishes
        with a nonzero exit code instead of dying on the first bad future."""
        model, records, *_ = model_and_records
        model_dir = str(tmp_path / "model")
        model.save(model_dir)
        good = records[:5]
        lines = [json.dumps(r) for r in good[:3]]
        lines.append("{ this is not json")                    # malformed
        lines.append(json.dumps({"x1": "not-a-number",
                                 "color": "red", "age": 1.0}))  # poison
        lines.extend(json.dumps(r) for r in good[3:])
        rec_file = tmp_path / "records.jsonl"
        rec_file.write_text("\n".join(lines) + "\n")
        out_file = tmp_path / "scores.jsonl"
        metrics_file = tmp_path / "metrics.json"

        from transmogrifai_tpu.cli.gen import main

        rc = main(["serve", "--model", model_dir,
                   "--records", str(rec_file),
                   "--output", str(out_file),
                   "--metrics-out", str(metrics_file),
                   "--max-batch", "8", "--max-wait-ms", "1",
                   "--min-bucket", "8", "--no-warm"])
        assert rc != 0  # record errors surface in the exit code
        rows = [json.loads(line) for line in
                out_file.read_text().splitlines()]
        assert len(rows) == 6  # 5 good + 1 error row; malformed line skipped
        err_rows = [r for r in rows if "error" in r]
        assert len(err_rows) == 1
        assert err_rows[0]["error_type"] == "PoisonRecordError"
        loaded = model.__class__.load(model_dir)
        expected = loaded.serving_plan().score(good)
        ok_rows = [r for r in rows if "error" not in r]
        assert ok_rows == json.loads(json.dumps(expected))
        metrics = json.loads(metrics_file.read_text())
        assert metrics["replay"]["skipped_malformed"] == 1
        assert metrics["replay"]["record_errors"] == 1
        assert metrics["resilience"]["quarantined"] == 1
        assert "serve: skipping malformed JSONL line 4" in \
            capsys.readouterr().err

    def test_clean_replay_exit_zero(self, model_and_records, tmp_path):
        model, records, *_ = model_and_records
        model_dir = str(tmp_path / "model")
        model.save(model_dir)
        rec_file = tmp_path / "records.jsonl"
        rec_file.write_text(
            "\n".join(json.dumps(r) for r in records[:6]) + "\n")
        out_file = tmp_path / "scores.jsonl"

        from transmogrifai_tpu.cli.gen import main

        rc = main(["serve", "--model", model_dir,
                   "--records", str(rec_file),
                   "--output", str(out_file),
                   "--max-batch", "8", "--max-wait-ms", "1", "--no-warm"])
        assert rc == 0
        assert len(out_file.read_text().splitlines()) == 6


def _raws(model):
    seen = {}
    for f in model.result_features:
        for r in f.raw_features():
            seen.setdefault(r.uid, r)
    return list(seen.values())
