"""Unified telemetry (ISSUE 11): trace spans, metrics registry, and the
flight-recorder event log across train/serve/refit.

Acceptance criteria proven here:
- under an injected fault schedule (breaker trip -> auto-rollback), the
  flight-recorder dump contains the compile events, breaker transition,
  swap, and rollback events in causal order with matching plan
  fingerprints (TestFlightE2E);
- a warm refit run records ZERO compile events, and the TM901
  unexpected-recompile diagnostic fires when one is injected;
- the Chrome-trace export of a ``cli serve`` replay is structurally valid
  (non-negative ts/dur, pid/tid present, X events) and spans nest
  correctly within every batcher worker thread (TestCliTelemetry);
- telemetry is default-off and every exported metrics/flight payload is
  ``json.dumps``-able with stable key ordering (satellite round-trip).
"""

import json
import os
import time

import numpy as np
import pytest

from transmogrifai_tpu import (
    BinaryClassificationModelSelector,
    FeatureBuilder,
    Workflow,
    transmogrify,
)
from transmogrifai_tpu.models.logistic import LogisticRegression
from transmogrifai_tpu.obs import (
    CANONICAL_METRICS,
    FlightRecorder,
    MetricsRegistry,
    Telemetry,
    Tracer,
    flight as obs_flight,
    resolve_telemetry,
    trace as obs_trace,
)
from transmogrifai_tpu.obs.metrics import assert_json_stable, legacy_aliases
from transmogrifai_tpu.readers.files import DataReaders
from transmogrifai_tpu.serve import (
    FaultHarness,
    ScoringServer,
    TransientScoringError,
)
from transmogrifai_tpu.workflow.continual import RefitController
from transmogrifai_tpu.workflow.workflow import dedup_raw_features

N_TRAIN = 256


def make_records(n, seed, shift=0.0):
    r = np.random.default_rng(seed)
    x = r.normal(size=(n, 3)) + shift
    out = []
    for i in range(n):
        rec = {"label": float(r.random() < 1 / (1 + np.exp(-x[i, 0])))}
        for j in range(3):
            rec[f"num{j}"] = float(x[i, j])
        out.append(rec)
    return out


@pytest.fixture(scope="module")
def base():
    """(model, train records, raw features, train dataset, candidate) —
    candidate is a frozen-prep warm-refit model sharing the plan
    fingerprint (the swap e2e needs matching fingerprints)."""
    import pandas as pd

    from transmogrifai_tpu.readers.base import rows_to_dataset

    train = make_records(N_TRAIN, 1)
    label = FeatureBuilder.RealNN("label").extract_field().as_response()
    feats = [FeatureBuilder.Real(f"num{j}").extract_field().as_predictor()
             for j in range(3)]
    checked = label.sanity_check(transmogrify(feats))
    sel = BinaryClassificationModelSelector.with_train_validation_split(
        models=[(LogisticRegression(), [{"reg_param": 0.01}])])
    pred = label.transform_with(sel, checked)
    model = (Workflow().set_result_features(label, pred)
             .set_reader(DataReaders.Simple.dataframe(pd.DataFrame(train)))
             ).train()
    raws = dedup_raw_features(model.result_features)
    train_ds = rows_to_dataset(train, raws)
    refit = RefitController(model, sleep=lambda s: None)
    refit.prime(train_ds)
    candidate = refit.refit(train_ds).model
    return model, train, raws, train_ds, candidate


@pytest.fixture(autouse=True)
def _clean_telemetry():
    """Every test starts and ends with telemetry fully uninstalled."""
    obs_trace.uninstall_tracer()
    obs_flight.uninstall_recorder()
    yield
    obs_trace.uninstall_tracer()
    obs_flight.uninstall_recorder()


def nesting_violations(events):
    """Within each tid, X events must be properly nested: any two spans
    either disjoint or one contains the other (small float tolerance)."""
    by_tid = {}
    for ev in events:
        if ev.get("ph") == "X":
            by_tid.setdefault(ev["tid"], []).append(ev)
    bad = []
    eps = 1.0  # us: timestamps round to 0.1us; clock noise tolerance
    for tid, evs in by_tid.items():
        evs = sorted(evs, key=lambda e: (e["ts"], -e["dur"]))
        stack = []
        for ev in evs:
            while stack and ev["ts"] >= stack[-1]["ts"] \
                    + stack[-1]["dur"] - eps:
                stack.pop()
            if stack and ev["ts"] + ev["dur"] > stack[-1]["ts"] \
                    + stack[-1]["dur"] + eps:
                bad.append((tid, stack[-1]["name"], ev["name"]))
            stack.append(ev)
    return bad


# ---------------------------------------------------------------------------
# Tracer
# ---------------------------------------------------------------------------

class TestTracer:
    def test_disabled_is_noop(self):
        assert obs_trace.active_tracer() is None
        with obs_trace.span("nothing", cat="test"):
            pass
        obs_trace.instant("nothing")  # must not raise anywhere

    def test_span_nesting_records_contextvar_parent(self):
        tracer = obs_trace.install_tracer(Tracer())
        try:
            with obs_trace.span("outer", cat="test"):
                with obs_trace.span("inner", cat="test"):
                    assert obs_trace.current_span_stack() == ("outer",
                                                              "inner")
        finally:
            obs_trace.uninstall_tracer()
        evs = tracer.chrome_trace()["traceEvents"]
        inner = next(e for e in evs if e.get("name") == "inner")
        outer = next(e for e in evs if e.get("name") == "outer")
        assert inner["args"]["parent"] == "outer"
        assert "parent" not in outer["args"]
        # inner nests inside outer on the same thread
        assert nesting_violations(evs) == []

    def test_chrome_trace_structure(self):
        tracer = obs_trace.install_tracer(Tracer())
        try:
            with obs_trace.span("a", cat="test", k=1):
                time.sleep(0.001)
            obs_trace.instant("mark", cat="test")
        finally:
            obs_trace.uninstall_tracer()
        doc = tracer.chrome_trace()
        assert "traceEvents" in doc
        xs = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
        metas = [e for e in doc["traceEvents"] if e.get("ph") == "M"]
        insts = [e for e in doc["traceEvents"] if e.get("ph") == "i"]
        assert len(xs) == 1 and len(insts) == 1
        assert any(m["name"] == "thread_name" for m in metas)
        for e in xs + insts:
            assert e["ts"] >= 0 and "pid" in e and "tid" in e
        assert xs[0]["dur"] >= 1000  # slept 1ms
        json.dumps(doc)  # exportable

    def test_bounded_capacity_counts_drops(self):
        tracer = Tracer(capacity=4)
        for i in range(10):
            tracer.add_instant(f"e{i}", "test")
        assert len(tracer) == 4 and tracer.dropped == 6

    def test_second_install_raises(self):
        t = obs_trace.install_tracer(Tracer())
        try:
            with pytest.raises(RuntimeError):
                obs_trace.install_tracer(Tracer())
        finally:
            obs_trace.uninstall_tracer(t)

    def test_requests_detail_validation(self):
        with pytest.raises(ValueError):
            Tracer(detail="everything")


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------

class TestMetricsRegistry:
    def test_counter_gauge_histogram(self):
        reg = MetricsRegistry()
        c = reg.counter("tmog_test_total")
        c.inc()
        c.inc(3)
        assert c.value == 4
        g = reg.gauge("tmog_test_depth")
        g.set(7)
        assert g.value == 7
        h = reg.histogram("tmog_test_size", exact=True)
        for v in (1, 2, 2, 8):
            h.observe(v)
        assert h.count == 4 and h.sum == 13
        assert h.exact_counts() == {1: 1, 2: 2, 8: 1}
        assert h.quantile(0.5) == 2

    def test_get_or_create_and_type_conflict(self):
        reg = MetricsRegistry()
        a = reg.counter("tmog_x_total")
        assert reg.counter("tmog_x_total") is a
        with pytest.raises(TypeError):
            reg.gauge("tmog_x_total")

    def test_labels_render_in_prometheus(self):
        reg = MetricsRegistry()
        reg.counter("tmog_y_total", "help text",
                    labels={"entry": "1"}).inc(5)
        reg.counter("tmog_y_total", labels={"entry": "2"}).inc(7)
        text = reg.to_prometheus()
        assert '# TYPE tmog_y_total counter' in text
        assert 'tmog_y_total{entry="1"} 5' in text
        assert 'tmog_y_total{entry="2"} 7' in text
        assert '# HELP tmog_y_total help text' in text

    def test_snapshot_sorted_and_json_stable(self):
        reg = MetricsRegistry()
        reg.counter("tmog_b_total").inc()
        reg.counter("tmog_a_total").inc()
        reg.histogram("tmog_c_size", exact=True).observe(3)
        snap = reg.snapshot()
        assert list(snap) == sorted(snap)
        assert assert_json_stable(snap)  # dumps with sort_keys

    def test_canonical_table_audit(self):
        """Satellite: the canonical name table is collision-free — every
        (owner, legacy alias) pair maps to exactly ONE canonical name, and
        the styles that collided across the old namespaces (e.g. the
        batcher's 'cancelled' vs the swap layer's 'shadow_dropped') are
        disambiguated by the owner prefix in the canonical name."""
        seen = {}
        for name, (kind, owner, alias, help_) in CANONICAL_METRICS.items():
            assert kind in ("counter", "gauge", "histogram"), name
            assert name.startswith("tmog_"), name
            assert help_, f"{name} has no help text"
            if alias is not None:
                key = (owner, alias)
                assert key not in seen, \
                    f"alias collision: {key} -> {seen.get(key)} and {name}"
                seen[key] = name
        # the historic cross-namespace collisions are now distinct names
        assert ("batcher", "batches") in seen \
            and ("continual", "batches") in seen
        assert seen[("batcher", "batches")] != seen[("continual", "batches")]


class TestRegistryEviction:
    def test_drop_labeled_and_labeled_values(self):
        reg = MetricsRegistry()
        reg.counter("tmog_z_total", labels={"entry": "1"}).inc()
        reg.counter("tmog_z_total", labels={"entry": "2"}).inc()
        reg.gauge("tmog_z_state", labels={"entry": "1"}).set(1)
        assert reg.labeled_values("entry") == ["1", "2"]
        assert reg.drop_labeled("entry", "1") == 2
        assert reg.labeled_values("entry") == ["2"]
        assert 'tmog_z_total{entry="2"}' in reg.snapshot()

    def test_server_prunes_dead_entry_series(self, base):
        """A continual loop stages one entry per refit; the registry must
        stay bounded to the live active/previous/candidate generations."""
        model, train, raws, train_ds, candidate = base
        with ScoringServer(model, max_batch=8, max_wait_ms=1.0) as server:
            for _ in range(4):  # stage/discard churn: versions 2..5
                server.stage_candidate(candidate, warm=False)
                server.discard_candidate()
            server.stage_candidate(candidate, warm=False)
            live = set(server.registry.labeled_values("entry"))
            # active v1 + the latest candidate only — dead entries evicted
            assert "1" in live and len(live) <= 3, live


class TestTelemetryOwnership:
    def test_nested_enter_does_not_tear_down_outer(self, tmp_path):
        tel = Telemetry(out_dir=str(tmp_path / "t"))
        with tel:
            with tel:  # inner enter: not the owner
                pass
            # outer session still recording
            assert obs_trace.active_tracer() is tel.tracer
            assert obs_flight.active_recorder() is tel.recorder
        assert obs_trace.active_tracer() is None

    def test_train_with_caller_started_telemetry(self, base, tmp_path):
        """train(telemetry=<already-started bundle>) must not stop the
        caller's session (and must not dump over it mid-session)."""
        import pandas as pd

        model, train, *_ = base
        label = FeatureBuilder.RealNN("label").extract_field().as_response()
        feats = [FeatureBuilder.Real(f"num{j}").extract_field()
                 .as_predictor() for j in range(3)]
        checked = label.sanity_check(transmogrify(feats))
        sel = BinaryClassificationModelSelector.with_train_validation_split(
            models=[(LogisticRegression(), [{"reg_param": 0.01}])])
        pred = label.transform_with(sel, checked)
        tel = Telemetry(out_dir=str(tmp_path / "outer")).start()
        try:
            (Workflow().set_result_features(label, pred)
             .set_reader(DataReaders.Simple.dataframe(pd.DataFrame(train)))
             ).train(telemetry=tel)
            # the caller's session survived the inner train
            assert obs_trace.active_tracer() is tel.tracer
            assert not os.path.exists(tmp_path / "outer" / "trace.json")
        finally:
            tel.stop()


class TestPartialWarm:
    def test_partial_warm_does_not_arm_tm901(self, base):
        model, *_ = base
        plan = model.serving_plan(strict=False)
        plan.warm(buckets=[8])  # partial: later buckets legitimately compile
        assert plan._warmed is False
        plan.warm()  # the full ladder arms the expectation
        assert plan._warmed is True


class TestLegacyViews:
    """Satellite: metrics() plain dicts survive as views over the registry,
    and every exported payload round-trips through json with stable keys."""

    def test_batcher_view_matches_registry(self):
        from transmogrifai_tpu.serve import MicroBatcher

        with MicroBatcher(lambda recs: [{"v": 1} for _ in recs],
                          max_batch=4, max_wait_ms=1.0) as mb:
            for _ in range(3):
                mb.score({"a": 1})
            view = mb.metrics()
            snap = mb.registry.snapshot()
        for legacy, canonical in legacy_aliases("batcher").items():
            assert legacy in view, legacy
            if legacy in ("batch_size_hist",):
                continue  # shape differs (exact counts vs summary)
            if isinstance(view[legacy], (int, float)):
                assert view[legacy] == snap[canonical], (legacy, canonical)
        assert view["submitted"] == 3 and view["completed"] == 3
        assert assert_json_stable(view)

    def test_server_views_json_stable(self, base):
        model, *_ = base
        with ScoringServer(model, max_batch=8, max_wait_ms=1.0) as server:
            server.score({f"num{j}": 0.1 for j in range(3)}, timeout=10)
            m = server.metrics()
            snap = server.metrics_snapshot()
            prom = server.prometheus()
        assert assert_json_stable(m)
        assert assert_json_stable(snap)
        # one registry covers batcher + swap + breaker + resilience
        assert "tmog_serve_batcher_submitted_total" in snap
        assert "tmog_serve_swap_swaps_total" in snap
        assert any(k.startswith("tmog_serve_breaker_state") for k in snap)
        assert any(k.startswith("tmog_serve_resilience_quarantined_total")
                   for k in snap)
        assert "# TYPE tmog_serve_batcher_submitted_total counter" in prom
        # legacy view values mirror the canonical source of truth
        assert m["batcher"]["submitted"] \
            == snap["tmog_serve_batcher_submitted_total"]

    def test_trainer_counters_view(self, base):
        from transmogrifai_tpu.readers import (ListSource,
                                               MicroBatchStreamingReader)
        from transmogrifai_tpu.workflow.continual import ContinualTrainer

        model, train, raws, train_ds, _cand = base
        reader = MicroBatchStreamingReader(
            ListSource(make_records(32, 5), "s"), batch_interval=0.0,
            max_batch_records=16, max_empty_polls=1)
        with ScoringServer(model, max_batch=16, max_wait_ms=1.0) as server:
            trainer = ContinualTrainer(server, model, reader,
                                       refit_enabled=False)
            metrics = trainer.run()
        assert trainer.counters["batches"] >= 2
        assert trainer.counters["records"] == 32
        # the trainer joined the SERVER's registry (one scrape covers both)
        assert server.registry.snapshot()["tmog_continual_records_total"] \
            == 32
        assert assert_json_stable(metrics)


# ---------------------------------------------------------------------------
# Flight recorder
# ---------------------------------------------------------------------------

class TestFlightRecorder:
    def test_ring_bound_and_payload_stable(self):
        rec = FlightRecorder(capacity=4)
        for i in range(9):
            rec.record("tick", i=i)
        assert len(rec) == 4 and rec.dropped == 5
        payload = rec.to_payload()
        assert payload["events"][-1]["data"]["i"] == 8
        assert [e["seq"] for e in payload["events"]] == [6, 7, 8, 9]
        assert assert_json_stable(payload)

    def test_compile_event_tagged_with_context(self):
        import jax
        import jax.numpy as jnp

        rec = obs_flight.install_recorder(FlightRecorder())
        try:
            salt = time.time_ns() % 97

            @jax.jit
            def f(v):
                return (v * salt).sum()

            with obs_flight.compile_context("test.site",
                                            fingerprint="fp123"):
                f(jnp.arange(8, dtype=jnp.float32))
        finally:
            obs_flight.uninstall_recorder()
        evs = rec.events("backend_compile")
        assert len(evs) >= 1
        assert evs[-1]["data"]["site"] == "test.site"
        assert evs[-1]["data"]["fingerprint"] == "fp123"
        assert evs[-1]["data"]["unexpected"] is False
        assert rec.unexpected_compiles == 0

    def test_warm_context_compile_fires_tm901(self):
        import jax
        import jax.numpy as jnp

        rec = obs_flight.install_recorder(FlightRecorder())
        try:
            salt = time.time_ns() % 89

            @jax.jit
            def g(v):
                return (v + salt).sum() * 2

            # inner context inherits the WARM expectation from the outer
            # one (the refit wraps dispatch layers that open their own)
            with obs_flight.compile_context("outer.warm", warm=True):
                with obs_flight.compile_context("inner.dispatch",
                                                fingerprint="fpX"):
                    g(jnp.arange(16, dtype=jnp.float32))
        finally:
            obs_flight.uninstall_recorder()
        evs = rec.events("backend_compile")
        assert evs and evs[-1]["data"]["unexpected"] is True
        assert evs[-1]["data"]["site"] == "inner.dispatch"
        assert rec.unexpected_compiles >= 1
        diags = rec.diagnostics()
        assert diags and all(d.code == "TM901" for d in diags)
        assert "inner.dispatch" in diags[-1].message

    def test_fault_injection_records_and_autodumps(self, base, tmp_path):
        model, *_ = base
        rec = obs_flight.install_recorder(
            FlightRecorder(dump_dir=str(tmp_path)))
        harness = FaultHarness(seed=0)
        harness.script("device", [TransientScoringError("boom")])
        try:
            with ScoringServer(model, max_batch=4, max_wait_ms=1.0) as srv:
                with harness:
                    out = srv.score({f"num{j}": 0.2 for j in range(3)},
                                    timeout=10)
            assert "error" not in out  # retry/fallback served the record
        finally:
            obs_flight.uninstall_recorder()
        faults = rec.events("fault_injected")
        assert faults and faults[0]["data"]["point"] == "device"
        assert faults[0]["data"]["error"] == "TransientScoringError"
        dumps = [f for f in os.listdir(tmp_path)
                 if f.startswith("flight-fault-")]
        assert dumps, "injected fault did not auto-dump the recorder"
        blob = json.load(open(tmp_path / dumps[0]))
        assert blob["reason"] == "fault_injected:device"
        assert any(e["kind"] == "fault_injected" for e in blob["events"])


# ---------------------------------------------------------------------------
# Serve-path telemetry
# ---------------------------------------------------------------------------

class TestServeTelemetry:
    def test_spans_cover_the_request_lifecycle(self, base):
        model, *_ = base
        tel = Telemetry()
        with tel:
            with ScoringServer(model, max_batch=8, max_wait_ms=1.0) as srv:
                futs = [srv.submit({f"num{j}": 0.1 * i for j in range(3)})
                        for i in range(24)]
                for f in futs:
                    f.result(timeout=10)
        evs = tel.tracer.chrome_trace()["traceEvents"]
        names = {e["name"] for e in evs if e.get("ph") == "X"}
        assert {"serve.flush", "serve.encode", "serve.device",
                "serve.host"} <= names
        assert nesting_violations(evs) == []
        # pipelined serving (ISSUE 18): encode runs on the flusher thread
        # while the flush span wraps finalize on the finalizer thread, so
        # the causal chain joins on the batch_seq key, not the tid
        flush = next(e for e in evs if e["name"] == "serve.flush")
        seq = flush["args"].get("batch_seq")
        assert seq is not None
        enc = next(e for e in evs if e["name"] == "serve.encode"
                   and e["args"].get("batch_seq") == seq)
        host = next(e for e in evs if e["name"] == "serve.host"
                    and e["args"].get("batch_seq") == seq)
        # the host remainder runs inside its batch's finalize/flush span
        assert host["tid"] == flush["tid"]
        assert host["args"].get("parent") == "serve.flush"
        # encode precedes the batch's host remainder (overlap-safe order)
        assert enc["ts"] <= host["ts"]

    def test_warm_serve_records_zero_compile_events(self, base):
        """Acceptance: a WARM serve replay under the recorder logs no
        backend compiles — and an injected one raises TM901."""
        import jax
        import jax.numpy as jnp

        model, *_ = base
        with ScoringServer(model, max_batch=8, max_wait_ms=1.0) as srv:
            srv.score({f"num{j}": 0.3 for j in range(3)}, timeout=10)
            rec = obs_flight.install_recorder(FlightRecorder())
            try:
                for i in range(12):
                    srv.score({f"num{j}": 0.01 * i for j in range(3)},
                              timeout=10)
                assert rec.events("backend_compile") == []
                assert rec.unexpected_compiles == 0
                # inject a compile into the warm path: TM901 must fire
                salt = time.time_ns() % 83

                @jax.jit
                def h(v):
                    return (v - salt).sum()

                with obs_flight.compile_context("serve.warm-injected",
                                                warm=True):
                    h(jnp.arange(4, dtype=jnp.float32))
                # >= 1: one jit call may emit several backend programs
                assert rec.unexpected_compiles >= 1
                diags = rec.diagnostics()
                assert diags and {d.code for d in diags} == {"TM901"}
            finally:
                obs_flight.uninstall_recorder()


# ---------------------------------------------------------------------------
# The acceptance e2e: fault schedule -> flight record in causal order
# ---------------------------------------------------------------------------

class TestFlightE2E:
    def test_breaker_trip_rollback_causal_order(self, base):
        """Acceptance: under the injected fault schedule (breaker trip ->
        auto-rollback), the flight dump holds compile, breaker-transition,
        swap, and rollback events in causal (seq) order, with the swap's
        plan fingerprints matching the compile events'."""
        model, train, raws, train_ds, candidate = base
        rec = obs_flight.install_recorder(FlightRecorder())
        harness = FaultHarness(seed=0)
        records = [{k: v for k, v in r.items() if k != "label"}
                   for r in make_records(8, 33)]
        try:
            # min_bucket=2 keeps at least one bucket executable out of the
            # process-wide cache, so the build logs compile events even
            # after earlier tests served the same plan
            with ScoringServer(model, max_batch=4, max_wait_ms=1.0,
                               min_bucket=2,
                               resilience={"max_retries": 0,
                                           "failure_threshold": 2,
                                           "recovery_batches": 8}) as srv:
                srv.stage_candidate(candidate)
                srv.promote(probation_batches=6)
                harness.script("device", [TransientScoringError("dead"),
                                          TransientScoringError("dead")])
                with harness:
                    for r in records[:3]:
                        srv.score(r, timeout=10)
                m = srv.swap_metrics()
                assert m["rollbacks"] == 1 and m["active_version"] == 1
        finally:
            obs_flight.uninstall_recorder()

        payload = rec.to_payload()
        assert assert_json_stable(payload)
        compiles = rec.events("backend_compile")
        swaps = rec.events("swap")
        rollbacks = rec.events("rollback")
        transitions = rec.events("breaker_transition")
        faults = rec.events("fault_injected")
        assert compiles and swaps and rollbacks and transitions and faults
        # causal order: plan compiles < swap < injected faults < breaker
        # open < rollback
        opened = next(t for t in transitions if t["data"]["to"] == "open")
        assert max(c["seq"] for c in compiles) < swaps[0]["seq"]
        assert swaps[0]["seq"] < faults[0]["seq"] <= opened["seq"]
        assert opened["seq"] < rollbacks[0]["seq"]
        # matching plan fingerprints: the frozen-prep candidate shares the
        # active plan's fingerprint, and the compiles carry the same one
        fp = swaps[0]["data"]["from"]
        assert swaps[0]["data"]["to"] == fp  # shared prefix
        assert rollbacks[0]["data"]["from"] == fp
        assert rollbacks[0]["data"]["to"] == fp
        serve_compiles = [c for c in compiles
                          if c["data"]["site"] == "serve.plan"]
        assert serve_compiles
        assert all(c["data"]["fingerprint"] == fp for c in serve_compiles)
        assert all(c["data"]["unexpected"] is False for c in compiles)

    def test_warm_refit_zero_compile_events(self, base):
        """Acceptance: a warm refit under the recorder logs ZERO backend
        compiles (plan + executable caches hit) and no TM901."""
        model, train, raws, train_ds, _cand = base
        refit = RefitController(model, sleep=lambda s: None)
        refit.prime(train_ds)
        refit.refit(train_ds)  # ensure every program is cache-warm
        rec = obs_flight.install_recorder(FlightRecorder())
        try:
            res = refit.refit(train_ds)
        finally:
            obs_flight.uninstall_recorder()
        assert res.backend_compiles == 0
        assert rec.events("backend_compile") == []
        assert rec.unexpected_compiles == 0 and rec.diagnostics() == []


# ---------------------------------------------------------------------------
# CLI integration
# ---------------------------------------------------------------------------

class TestCliTelemetry:
    def _save(self, model, tmp_path):
        model_dir = str(tmp_path / "model")
        model.save(model_dir)
        return model_dir

    def test_cli_serve_telemetry_artifacts(self, base, tmp_path):
        """Acceptance: the Chrome-trace export of a ``cli serve`` replay is
        structurally valid and spans nest across batcher worker threads."""
        from transmogrifai_tpu.cli.gen import main

        model, *_ = base
        model_dir = self._save(model, tmp_path)
        records = [{k: v for k, v in r.items() if k != "label"}
                   for r in make_records(48, 7)]
        stream = tmp_path / "r.jsonl"
        stream.write_text("".join(json.dumps(r) + "\n" for r in records))
        teldir = tmp_path / "tel"
        # --min-bucket 1: bucket 1 is compiled by no other test, so the
        # flight record deterministically holds >=1 compile event even
        # after earlier tests warmed the process-wide executable cache
        rc = main(["serve", "--model", model_dir, "--records", str(stream),
                   "--output", str(tmp_path / "out.jsonl"),
                   "--metrics-out", str(tmp_path / "m.json"),
                   "--min-bucket", "1",
                   "--telemetry", str(teldir)])
        assert rc == 0
        assert sorted(os.listdir(teldir)) == [
            "flight.json", "metrics.jsonl", "metrics.prom", "trace.json"]
        doc = json.load(open(teldir / "trace.json"))
        evs = doc["traceEvents"]
        xs = [e for e in evs if e.get("ph") == "X"]
        assert xs, "no complete events in the trace"
        for e in xs:
            assert e["ts"] >= 0 and e["dur"] >= 0
            assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
        assert {"serve.flush", "serve.encode", "serve.device",
                "serve.host"} <= {e["name"] for e in xs}
        # thread metadata present for the batcher worker thread
        names = {m["args"]["name"] for m in evs if m.get("ph") == "M"
                 and m["name"] == "thread_name"}
        assert any("microbatcher" in n for n in names), names
        # spans nest correctly within every thread
        assert nesting_violations(evs) == []
        # flight + prometheus artifacts parse
        fl = json.load(open(teldir / "flight.json"))
        assert any(e["kind"] == "backend_compile" for e in fl["events"])
        assert fl["unexpected_compiles"] == 0
        prom = (teldir / "metrics.prom").read_text()
        assert "tmog_serve_batcher_submitted_total" in prom
        line = json.loads(
            (teldir / "metrics.jsonl").read_text().splitlines()[-1])
        assert line["source"] == "cli serve"
        # scores are unaffected by telemetry
        rows = (tmp_path / "out.jsonl").read_text().splitlines()
        assert len(rows) == len(records)

    def test_follow_snapshot_lines(self, base, tmp_path):
        """Satellite: ``--follow --snapshot-interval`` emits periodic
        metrics-snapshot JSONL lines while scores and offsets stay
        byte-identical to a run without them."""
        from transmogrifai_tpu.cli.gen import main

        model, *_ = base
        model_dir = self._save(model, tmp_path)
        records = make_records(64, 9)
        stream = tmp_path / "s.jsonl"
        stream.write_text("".join(json.dumps(r) + "\n" for r in records))
        snaps = tmp_path / "snapshots.jsonl"
        offsets = str(tmp_path / "off.json")
        out_file = tmp_path / "o.jsonl"
        rc = main(["serve", "--model", model_dir, "--records", str(stream),
                   "--output", str(out_file),
                   "--metrics-out", str(tmp_path / "m.json"),
                   "--follow", "--offsets", offsets,
                   "--batch-interval", "0", "--max-empty-polls", "1",
                   "--max-batch-records", "16", "--max-wait-ms", "1",
                   "--snapshot-interval", "0",
                   "--snapshots-out", str(snaps)])
        assert rc == 0
        lines = [json.loads(ln) for ln in
                 snaps.read_text().splitlines()]
        assert len(lines) >= 4  # one per 16-record batch
        for ln in lines:
            assert ln["type"] == "metrics_snapshot"
            assert "tmog_serve_batcher_submitted_total" in ln["metrics"]
            assert "continual" in ln
        # scoring output and offsets unaffected
        assert len(out_file.read_text().splitlines()) == len(records)
        committed = json.load(open(offsets))
        assert committed["jsonl:s.jsonl"] == stream.stat().st_size
        metrics = json.loads((tmp_path / "m.json").read_text())
        assert metrics["metrics_snapshots_emitted"] == len(lines)

    def test_tmog_telemetry_env_switch(self, base, tmp_path, monkeypatch):
        """The TMOG_TELEMETRY env var enables the same artifacts with no
        CLI flag (and resolve_telemetry defers when already active)."""
        from transmogrifai_tpu.cli.gen import main

        model, *_ = base
        model_dir = self._save(model, tmp_path)
        records = [{k: v for k, v in r.items() if k != "label"}
                   for r in make_records(8, 11)]
        stream = tmp_path / "e.jsonl"
        stream.write_text("".join(json.dumps(r) + "\n" for r in records))
        teldir = tmp_path / "envtel"
        monkeypatch.setenv("TMOG_TELEMETRY", str(teldir))
        rc = main(["serve", "--model", model_dir, "--records", str(stream),
                   "--output", str(tmp_path / "eo.jsonl"),
                   "--metrics-out", str(tmp_path / "em.json")])
        assert rc == 0
        assert (teldir / "trace.json").exists()
        assert (teldir / "flight.json").exists()
        # while a bundle is active, env resolution returns None (an inner
        # train() must not fight the outer entry point)
        tel = Telemetry().start()
        try:
            assert resolve_telemetry(None) is None
        finally:
            tel.stop()


# ---------------------------------------------------------------------------
# Workflow.train telemetry + TMOG_PROFILE
# ---------------------------------------------------------------------------

class TestTrainTelemetry:
    def test_train_writes_trace_and_metrics(self, base, tmp_path):
        import pandas as pd

        model, train, *_ = base
        teldir = str(tmp_path / "traintel")
        label = FeatureBuilder.RealNN("label").extract_field().as_response()
        feats = [FeatureBuilder.Real(f"num{j}").extract_field()
                 .as_predictor() for j in range(3)]
        checked = label.sanity_check(transmogrify(feats))
        sel = BinaryClassificationModelSelector.with_train_validation_split(
            models=[(LogisticRegression(), [{"reg_param": 0.01}])])
        pred = label.transform_with(sel, checked)
        (Workflow().set_result_features(label, pred)
         .set_reader(DataReaders.Simple.dataframe(pd.DataFrame(train)))
         ).train(telemetry=teldir)
        doc = json.load(open(os.path.join(teldir, "trace.json")))
        cats = {e.get("cat") for e in doc["traceEvents"]
                if e.get("ph") == "X"}
        assert "train" in cats  # perf.phase sites re-emit as spans
        names = {e["name"] for e in doc["traceEvents"]
                 if e.get("ph") == "X"}
        assert any(n.startswith("fit.") for n in names), names
        line = json.loads(open(os.path.join(teldir, "metrics.jsonl"))
                          .read().splitlines()[-1])
        assert line["source"] == "Workflow.train"
        assert "backend_compiles" in line["compile"]
        assert any(p.startswith("fit.") for p in line["phases"])
        assert os.path.exists(os.path.join(teldir, "flight.json"))
        # telemetry is OFF again after the context
        assert obs_trace.active_tracer() is None
        assert obs_flight.active_recorder() is None


class TestProfileHook:
    def test_profile_dir_created_and_scores_bitwise_identical(
            self, base, tmp_path, monkeypatch):
        """Satellite: TMOG_PROFILE wraps the serve dispatch in
        jax.profiler.trace — artifact dir created, scores unchanged."""
        model, *_ = base
        records = [{f"num{j}": 0.1 * i for j in range(3)}
                   for i in range(8)]
        plan = model.serving_plan(strict=False)
        baseline = plan.score(records)
        prof = tmp_path / "prof"
        monkeypatch.setenv("TMOG_PROFILE", str(prof))
        profiled = plan.score(records)
        monkeypatch.delenv("TMOG_PROFILE")
        assert os.path.isdir(prof)
        assert json.dumps(profiled, sort_keys=True) \
            == json.dumps(baseline, sort_keys=True)

    def test_one_capture_holds_a_whole_selector_fit(self, tmp_path,
                                                    monkeypatch):
        """TMOG_PROFILE captures one whole ``ModelSelector.fit`` (the
        per-dispatch hook in ``run_cached`` is gone): one ``.xplane.pb``
        whose host plane holds the fit's spans, first phase to last."""
        import glob
        import inspect

        import numpy as np
        from jax.profiler import ProfileData

        from transmogrifai_tpu import Dataset
        from transmogrifai_tpu.data.dataset import Column
        from transmogrifai_tpu.perf import programs
        from transmogrifai_tpu.types import OPVector, RealNN

        assert "TMOG_PROFILE" not in inspect.getsource(programs.run_cached)
        assert "maybe_profile" not in inspect.getsource(programs)
        rng = np.random.default_rng(3)
        x = rng.normal(size=(256, 4)).astype(np.float32)
        y = (rng.random(256) < 0.5).astype(np.float64)
        ds = Dataset({"label": Column(RealNN, y, np.ones(256, np.bool_)),
                      "v": Column.vector(x)})
        label = FeatureBuilder.of("label", RealNN).extract_field() \
            .as_response()
        vec = FeatureBuilder.of("v", OPVector).extract_field().as_predictor()
        sel = BinaryClassificationModelSelector.with_cross_validation(
            num_folds=2,
            models=[(LogisticRegression(), [{"reg_param": 0.01}])])
        label.transform_with(sel, vec)
        plain = sel.fit(ds)
        prof = tmp_path / "fitprof"
        monkeypatch.setenv("TMOG_PROFILE", str(prof))
        profiled = sel.fit(ds)
        monkeypatch.delenv("TMOG_PROFILE")
        assert profiled.summary.to_dict()["validationResults"] \
            == plain.summary.to_dict()["validationResults"]
        (path,) = glob.glob(str(prof / "plugins" / "profile" / "*"
                                / "*.xplane.pb"))
        names = [ev.name for plane in ProfileData.from_file(path).planes
                 if plane.name == "/host:CPU"
                 for line in plane.lines for ev in line.events
                 if dict(ev.stats).get("span") in ("phase", "activity")]
        recorded = [s.path for s in sel.last_fit_profile.spans]
        assert sorted(names) == sorted(recorded)
        assert {"prep", "validate", "refit", "train_eval", "host.launch",
                "host.device_wait"} <= set(names)

    def test_a_train_and_its_selector_fit_share_one_capture(
            self, base, tmp_path, monkeypatch):
        """``Workflow.train`` opens the capture; the selector's own hook
        finds it in flight and stays out (captures do not nest)."""
        import glob

        import pandas as pd

        _, train, *_ = base
        label = FeatureBuilder.RealNN("label").extract_field().as_response()
        feats = [FeatureBuilder.Real(f"num{j}").extract_field()
                 .as_predictor() for j in range(3)]
        sel = BinaryClassificationModelSelector.with_train_validation_split(
            models=[(LogisticRegression(), [{"reg_param": 0.01}])])
        pred = label.transform_with(sel, transmogrify(feats))
        prof = tmp_path / "trainprof"
        monkeypatch.setenv("TMOG_PROFILE", str(prof))
        (Workflow().set_result_features(label, pred)
         .set_reader(DataReaders.Simple.dataframe(pd.DataFrame(train)))
         ).train()
        monkeypatch.delenv("TMOG_PROFILE")
        assert len(glob.glob(str(prof / "plugins" / "profile" / "*"
                                 / "*.xplane.pb"))) == 1
        assert len(sel.last_fit_profile.spans) > 0

    def test_unset_env_is_noop(self, base, monkeypatch):
        monkeypatch.delenv("TMOG_PROFILE", raising=False)
        from transmogrifai_tpu.obs.profile import maybe_profile, profile_dir

        assert profile_dir() == ""
        with maybe_profile("test"):
            pass
