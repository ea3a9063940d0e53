"""Fused DAG transform planner (workflow/plan.py): bitwise parity of the
fused path against the per-stage columnar path on the train, score, and
fold-fitted CV transforms, compile-budget guarantees on warm refits, the new
bucketizer/scaler device kernels, and the TM504 split diagnostic.

Parity discipline mirrors tests/test_serve.py's three-way harness: the fused
plan must not perturb a single bit of what the interpreted path computes on
the fixture pipelines (selection/scatter/fill kernels)."""

import numpy as np
import pytest

from transmogrifai_tpu import (
    BinaryClassificationModelSelector,
    Dataset,
    FeatureBuilder,
    Workflow,
    transmogrify,
)
from transmogrifai_tpu.models.logistic import LogisticRegression
from transmogrifai_tpu.perf import measure_compiles
from transmogrifai_tpu.types import Real, RealNN
from transmogrifai_tpu.workflow.fit import transform_dag
from transmogrifai_tpu.workflow.plan import (
    ColumnarTransformPlan,
    fused_transform,
    plan_for,
)


def _mixed_dataset(n=300, seed=3):
    """Numeric (with missing) + categorical raw table, the transmogrify shape."""
    rng = np.random.default_rng(seed)
    x1 = rng.normal(0, 1, n)
    color = rng.choice(["red", "green", "blue"], n)
    age = [None if rng.random() < 0.15 else float(v)
           for v in rng.normal(40, 10, n)]
    z = 1.5 * x1 + (color == "red")
    y = (rng.random(n) < 1 / (1 + np.exp(-z))).astype(float)
    import pandas as pd

    df = pd.DataFrame({"label": y, "x1": x1, "color": color, "age": age})
    from transmogrifai_tpu.readers.files import DataReaders

    label = FeatureBuilder.RealNN("label").extract_field().as_response()
    f_x1 = FeatureBuilder.Real("x1").extract_field().as_predictor()
    f_color = FeatureBuilder.PickList("color").extract_field().as_predictor()
    f_age = FeatureBuilder.Real("age").extract_field().as_predictor()
    vec = transmogrify([f_x1, f_color, f_age])
    checked = label.sanity_check(vec)
    reader = DataReaders.Simple.dataframe(df)
    return reader, label, checked


@pytest.fixture(scope="module")
def trained():
    reader, label, checked = _mixed_dataset()
    sel = BinaryClassificationModelSelector.with_train_validation_split(
        models=[(LogisticRegression(), [{"reg_param": 0.01}])])
    pred = label.transform_with(sel, checked)
    model = (Workflow().set_result_features(label, pred)
             .set_reader(reader)).train()
    raws = {}
    for f in model.result_features:
        for r in f.raw_features():
            raws.setdefault(r.uid, r)
    ds = reader.generate_dataset(list(raws.values()))
    return model, ds, checked, pred


class TestScorePathParity:
    def test_fused_vs_interpreted_bitwise(self, trained):
        model, ds, checked, pred = trained
        out_f = transform_dag(ds, model.result_features, model.fitted)
        out_i = transform_dag(ds, model.result_features, model.fitted,
                              fused=False)
        assert set(out_f.names) == set(out_i.names)
        # the feature vector: bitwise, metadata included
        cf, ci = out_f[checked.name], out_i[checked.name]
        assert np.array_equal(cf.data, ci.data)
        assert cf.data.dtype == ci.data.dtype
        assert cf.meta.to_dict() == ci.meta.to_dict()
        # the prediction: bitwise
        pf, pi = out_f[pred.name], out_i[pred.name]
        assert np.array_equal(np.asarray(pf.score), np.asarray(pi.score))
        assert np.array_equal(np.asarray(pf.prob), np.asarray(pi.prob))

    def test_plan_partition_and_tm504(self, trained):
        model, ds, *_ = trained
        from transmogrifai_tpu.serve.plan import resolve_scoring_stages

        runners = resolve_scoring_stages(model.result_features, model.fitted)
        plan, remainder = plan_for(runners, frozenset(ds.names))
        assert plan is not None
        # vectorizers + one-hot + combiner + sanity fuse; the model stays host
        assert len(plan.device_stage_uids) == len(runners) - 1
        assert [r.uid for r in remainder] == plan.host_stage_uids
        report = model.validate()
        tm504 = report.by_code("TM504")
        assert len(tm504) == 1
        assert f"fuses {len(plan.device_stage_uids)}" in tm504[0].message
        assert not report.errors()

    def test_cached_plan_does_not_serve_stale_remainder(self, trained):
        """Two models sharing identical prep content must each score through
        their OWN host-remainder stages (the plan cache keys on prefix
        content only)."""
        model, ds, checked, pred = trained
        from transmogrifai_tpu.serve.plan import resolve_scoring_stages

        runners = resolve_scoring_stages(model.result_features, model.fitted)
        plan1, rem1 = plan_for(runners, frozenset(ds.names))
        plan2, rem2 = plan_for(runners, frozenset(ds.names))
        assert plan2 is plan1           # cache hit on equal prefix content
        assert [r.uid for r in rem2] == [r.uid for r in rem1]

    def test_score_entry_point_uses_fused_path(self, trained):
        model, ds, checked, pred = trained
        s1 = model.score(ds)
        import os

        os.environ["TMOG_FUSED_TRANSFORM"] = "0"
        try:
            s2 = model.score(ds)
        finally:
            os.environ["TMOG_FUSED_TRANSFORM"] = "1"
        assert np.array_equal(np.asarray(s1[pred.name].score),
                              np.asarray(s2[pred.name].score))


    def test_warm_transform_dag_compiles_nothing(self, trained):
        """Steady state: a second fused ``transform_dag`` over the same fitted
        DAG and table comes out of the plan and executable caches — ZERO
        backend compiles."""
        model, ds, _checked, _pred = trained
        transform_dag(ds, model.result_features, model.fitted)  # warm
        with measure_compiles() as probe:
            transform_dag(ds, model.result_features, model.fitted)
        assert probe.backend_compiles == 0, \
            f"warm fused transform recompiled {probe.backend_compiles}"

    def test_fused_plan_names_and_costs_itself(self, trained):
        """The plan ``transform_dag`` dispatches carries its own identity and
        cost: a content fingerprint, the canonical-IR fingerprint of the
        fused prefix, and nonzero predicted FLOPs / bytes / peak HBM from the
        abstract trace — all at zero backend compiles."""
        from transmogrifai_tpu.checkers.irsnap import snapshot_transform_plan
        from transmogrifai_tpu.checkers.plancheck import \
            analyze_transform_plan
        from transmogrifai_tpu.workflow.plan import plan_for_features

        model, ds, _checked, _pred = trained
        with measure_compiles() as probe:
            plan = plan_for_features(ds, model.result_features, model.fitted)
            snap = snapshot_transform_plan(plan, ds)
            cost = analyze_transform_plan(plan, ds)
        assert probe.backend_compiles == 0
        assert plan.fingerprint
        assert len(snap.ir_fingerprint) == 32
        # the same plan, looked up again, is the same program
        again = plan_for_features(ds, model.result_features, model.fitted)
        assert again.fingerprint == plan.fingerprint
        assert snapshot_transform_plan(again, ds).ir_fingerprint \
            == snap.ir_fingerprint
        b = cost.buckets[-1]
        assert b.flops > 0 and b.bytes_read + b.bytes_written > 0
        assert b.peak_hbm_bytes > 0


class TestTrainPathParity:
    def test_fused_train_matches_interpreted_train(self):
        """Whole-train parity: the fused fit path must select the same model
        with bitwise-equal CV metrics and scores as the per-stage path."""
        import os

        def train_once():
            reader, label, checked = _mixed_dataset(seed=11)
            sel = BinaryClassificationModelSelector.with_train_validation_split(
                models=[(LogisticRegression(), [{"reg_param": 0.01}])])
            pred = label.transform_with(sel, checked)
            model = (Workflow().set_result_features(label, pred)
                     .set_reader(reader)).train()
            raws = {}
            for f in model.result_features:
                for r in f.raw_features():
                    raws.setdefault(r.uid, r)
            ds = reader.generate_dataset(list(raws.values()))
            return model, np.asarray(model.score(ds)[pred.name].score)

        m_fused, s_fused = train_once()
        os.environ["TMOG_FUSED_TRANSFORM"] = "0"
        try:
            m_interp, s_interp = train_once()
        finally:
            os.environ["TMOG_FUSED_TRANSFORM"] = "1"
        assert np.array_equal(s_fused, s_interp)
        sf, si = m_fused.summary(), m_interp.summary()
        assert sf.best_model_name == si.best_model_name
        for rf, ri in zip(sf.validation_results, si.validation_results):
            assert rf.metric_values == ri.metric_values

    def test_warm_refit_zero_new_backend_compiles(self):
        """Acceptance: a second train() of the same workflow content performs
        ZERO new XLA compilations — the transform plans and their executables
        come back from the content-addressed caches."""
        reader, label, checked = _mixed_dataset(seed=5)

        def build():
            sel = BinaryClassificationModelSelector.with_train_validation_split(
                models=[(LogisticRegression(), [{"reg_param": 0.01}])])
            return label.transform_with(sel, checked)

        p1 = build()
        (Workflow().set_result_features(label, p1).set_reader(reader)).train()
        p2 = build()
        with measure_compiles() as probe:
            (Workflow().set_result_features(label, p2)
             .set_reader(reader)).train()
        assert probe.backend_compiles == 0, \
            f"warm refit recompiled {probe.backend_compiles} programs"


class TestFoldPathParity:
    def _cv_pipeline(self, seed=0, n=240, d=5):
        rng = np.random.default_rng(seed)
        cols = {f"x{i}": rng.normal(size=n).tolist() for i in range(d)}
        beta = rng.normal(size=d)
        z = sum(beta[i] * np.asarray(cols[f"x{i}"]) for i in range(d))
        cols["label"] = (rng.random(n) < 1 / (1 + np.exp(-z))
                         ).astype(float).tolist()
        ds = Dataset.from_features(
            cols, {**{f"x{i}": Real for i in range(d)}, "label": RealNN})
        label = FeatureBuilder.of("label", RealNN).extract_field().as_response()
        feats = [FeatureBuilder.of(f"x{i}", Real).extract_field().as_predictor()
                 for i in range(d)]
        checked = label.sanity_check(transmogrify(feats))
        sel = BinaryClassificationModelSelector.with_cross_validation(
            num_folds=3,
            models=[(LogisticRegression(),
                     [{"reg_param": r} for r in (0.01, 0.1)])])
        pred = label.transform_with(sel, checked)
        return ds, label, pred

    def test_workflow_cv_fused_matches_interpreted(self):
        """The fold-fitted CV transforms through the (vmapped) fused planner
        must reproduce the host loop's metrics and final scores bitwise."""
        import os

        ds, label, pred = self._cv_pipeline(seed=21)
        m1 = (Workflow().set_input_dataset(ds)
              .set_result_features(label, pred).with_workflow_cv()).train()
        s1 = np.asarray(m1.score(ds)[pred.name].score)
        sum1 = m1.summary()

        ds2, label2, pred2 = self._cv_pipeline(seed=21)
        os.environ["TMOG_FUSED_TRANSFORM"] = "0"
        try:
            m2 = (Workflow().set_input_dataset(ds2)
                  .set_result_features(label2, pred2)
                  .with_workflow_cv()).train()
        finally:
            os.environ["TMOG_FUSED_TRANSFORM"] = "1"
        s2 = np.asarray(m2.score(ds2)[pred2.name].score)
        sum2 = m2.summary()
        assert sum1.best_model_name == sum2.best_model_name
        assert sum1.best_grid == sum2.best_grid
        for r1, r2 in zip(sum1.validation_results, sum2.validation_results):
            assert r1.metric_values == r2.metric_values
        assert np.array_equal(s1, s2)

    def test_fold_vmap_engages_on_stackable_states(self):
        """With 3 folds of a sanity-checked pipeline whose folds keep equal
        slot counts, the fold axis must run as ONE vmapped program."""
        from transmogrifai_tpu.perf.programs import program_cache_entries

        ds, label, pred = self._cv_pipeline(seed=33)
        (Workflow().set_input_dataset(ds)
         .set_result_features(label, pred).with_workflow_cv()).train()
        fold_entries = [s for s in program_cache_entries().values()
                        if s.label.startswith("transform_plan/fold3x")]
        assert fold_entries, "fold-batched transform program never dispatched"


class TestDeviceKernels:
    def test_decision_tree_bucketizer_device_matches_host(self):
        from transmogrifai_tpu.ops.bucketizers import (
            DecisionTreeNumericBucketizer,
        )
        from transmogrifai_tpu.types import OPNumeric

        rng = np.random.default_rng(4)
        n = 400
        v = [None if rng.random() < 0.1 else float(x)
             for x in rng.normal(0, 2, n)]
        y = [float(x is not None and x > 0.3) for x in v]
        ds = Dataset.from_features({"label": y, "v": v},
                                   {"label": RealNN, "v": Real})
        label = FeatureBuilder.of("label", RealNN).extract_field().as_response()
        fv = FeatureBuilder.of("v", Real).extract_field().as_predictor()
        est = DecisionTreeNumericBucketizer(track_invalid=True)
        est.set_input(label, fv)
        model = est.fit(ds)
        assert model.should_split
        host = model.transform(ds)[model.output_name]
        lift = ds["v"].values_f64().astype(np.float32)
        dev = np.asarray(model.device_transform(lift))
        assert np.array_equal(host.data, dev)
        # stateful form agrees with the baked form
        dev2 = np.asarray(model.device_transform_stateful(
            tuple(map(np.asarray, model.device_state())), lift))
        assert np.array_equal(dev, dev2)

    def test_bucketizer_no_split_null_only(self):
        from transmogrifai_tpu.ops.bucketizers import (
            DecisionTreeNumericBucketizerModel,
        )

        m = DecisionTreeNumericBucketizerModel(
            should_split=False, splits=[], track_nulls=True)
        lift = np.asarray([1.0, np.nan, 2.0], np.float32)
        out = np.asarray(m.device_transform(lift))
        assert out.shape == (3, 1)
        assert np.array_equal(out[:, 0], [0.0, 1.0, 0.0])

    @pytest.mark.parametrize("splits,track_invalid", [
        ((-np.inf, -1.0, 0.5, np.inf), False),
        ((0.0, 1.0, 2.0), False),   # finite edges: out-of-range -> edge bucket
        ((0.0, 1.0, 2.0), True),    # finite edges: out-of-range -> own column
    ])
    def test_numeric_bucketizer_device_matches_host(self, splits,
                                                    track_invalid):
        from transmogrifai_tpu.ops.scalers import NumericBucketizer

        stage = NumericBucketizer(splits=splits, track_nulls=True,
                                  track_invalid=track_invalid)
        rng = np.random.default_rng(6)
        vals = [None if rng.random() < 0.2 else float(x)
                for x in rng.normal(0.5, 1.5, 300)]
        vals += [0.0, 1.0, 2.0, -3.0, 9.0]  # edges + both out-of-range sides
        ds = Dataset.from_features({"v": vals}, {"v": Real})
        fv = FeatureBuilder.of("v", Real).extract_field().as_predictor()
        stage.set_input(fv)
        host = stage.transform(ds)[stage.output_name]
        dev = np.asarray(stage.device_transform(
            ds["v"].values_f64().astype(np.float32)))
        assert np.array_equal(host.data, dev)

    def test_percentile_calibrator_device_matches_host(self):
        from transmogrifai_tpu.ops.scalers import PercentileCalibrator

        rng = np.random.default_rng(8)
        vals = rng.normal(size=500).tolist()
        ds = Dataset.from_features({"s": vals}, {"s": RealNN})
        fs = FeatureBuilder.of("s", RealNN).extract_field().as_predictor()
        est = PercentileCalibrator(buckets=10)
        est.set_input(fs)
        model = est.fit(ds)
        host = model.transform(ds)[model.output_name]
        dev = np.asarray(model.device_transform(
            np.asarray(vals, np.float32)))
        assert np.array_equal(host.data.astype(np.float32), dev)

    def test_bucketizer_fuses_into_train_prefix(self):
        """A tree bucketizer between raw numerics and the combiner must join
        the fused prefix on the dataset path (the satellite's point: widen
        the fusable prefix)."""
        from transmogrifai_tpu.ops.bucketizers import (
            DecisionTreeNumericBucketizer,
        )

        rng = np.random.default_rng(9)
        n = 200
        v = rng.normal(size=n).tolist()
        y = (np.asarray(v) > 0).astype(float).tolist()
        ds = Dataset.from_features({"label": y, "v": v},
                                   {"label": RealNN, "v": Real})
        label = FeatureBuilder.of("label", RealNN).extract_field().as_response()
        fv = FeatureBuilder.of("v", Real).extract_field().as_predictor()
        est = DecisionTreeNumericBucketizer()
        est.set_input(label, fv)
        model = est.fit(ds)
        plan, remainder = plan_for([model], frozenset(ds.names))
        assert plan is not None and plan.device_stage_uids == [model.uid]
        out = fused_transform(ds, [model])
        ref = model.transform(ds)
        assert np.array_equal(out[model.output_name].data,
                              ref[model.output_name].data)
        assert out[model.output_name].meta.to_dict() == \
            ref[model.output_name].meta.to_dict()


class TestFallbacks:
    def test_listener_forces_per_stage_path(self, trained):
        """Per-stage stage_timer events only exist on the interpreted path —
        an active listener must keep it."""
        from transmogrifai_tpu.utils.listener import (
            OpMetricsListener,
            add_listener,
            remove_listener,
        )

        model, ds, checked, pred = trained
        listener = add_listener(OpMetricsListener())
        try:
            out = model.score(ds)
        finally:
            remove_listener(listener)
        transforms = [m for m in listener.metrics.stage_metrics
                      if m.phase == "transform"]
        assert len(transforms) == len(model.fitted) or transforms

    def test_env_kill_switch(self, trained, monkeypatch):
        monkeypatch.setenv("TMOG_FUSED_TRANSFORM", "0")
        from transmogrifai_tpu.workflow.plan import fused_transforms_enabled

        assert not fused_transforms_enabled()
        model, ds, checked, pred = trained
        out = fused_transform(ds, [])
        assert out is None

    def test_plan_none_when_nothing_fuses(self):
        ds = Dataset.from_features({"x": [1.0, 2.0]}, {"x": Real})
        plan, remainder = plan_for([], frozenset(ds.names))
        assert plan is None and remainder == []
