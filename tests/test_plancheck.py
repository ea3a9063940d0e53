"""plancheck static cost analyzer (ISSUE 6 tentpole): jaxpr-level FLOPs /
bytes / peak-HBM / collective / recompile-hazard analysis over fused
programs, the TM6xx diagnostic family, and the admission gates it powers
(``train(hbm_budget=...)``, serving admission, ``validate(cost=True)``).

Discipline mirrored from test_opcheck.py: every seeded fixture fires its
stable code exactly once, and the whole analyzer suite runs purely on
abstract specs — the compile probe must read ZERO backend compiles across a
full cost-validate pass.
"""

import numpy as np
import pytest

from transmogrifai_tpu import (
    BinaryClassificationModelSelector,
    FeatureBuilder,
    Workflow,
    transmogrify,
)
from transmogrifai_tpu.checkers.diagnostics import OpCheckError, Severity
from transmogrifai_tpu.checkers.opcheck import validate_result_features
from transmogrifai_tpu.checkers.plancheck import (
    MEMORY_BOUND_INTENSITY,
    PlanCostReport,
    cost_diagnostics,
    trace_cost,
)
from transmogrifai_tpu.data.dataset import Column
from transmogrifai_tpu.models.logistic import LogisticRegression
from transmogrifai_tpu.perf import measure_compiles
from transmogrifai_tpu.readers.files import DataReaders
from transmogrifai_tpu.stages.base import BinaryTransformer, UnaryTransformer
from transmogrifai_tpu.types import OPVector, Real, RealNN


# ---------------------------------------------------------------------------
# fixture stages
# ---------------------------------------------------------------------------

class PcSortStage(UnaryTransformer):
    """Seeded TM605: a float sort in the device path (row-local: sorts a
    per-row pair, not across rows)."""

    input_types = (Real,)
    output_type = Real

    def transform_columns(self, cols, dataset):
        v = cols[0].values_f64()
        return Column.from_values(Real, list(np.minimum(v, v * 0.5)))

    def device_transform(self, x):
        import jax.numpy as jnp

        pair = jnp.stack([x, x * 0.5], axis=1)
        return jnp.sort(pair, axis=1)[:, 0]


class PcShardStage(UnaryTransformer):
    """Seeded TM603: an explicit resharding annotation inside the device
    transform (a 1-device mesh keeps it runnable on any host)."""

    input_types = (Real,)
    output_type = Real

    def transform_columns(self, cols, dataset):
        return Column.from_values(Real, list(cols[0].values_f64() * 1.0))

    def device_transform(self, x):
        import jax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec

        mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
        return jax.lax.with_sharding_constraint(
            x * 1.0, NamedSharding(mesh, PartitionSpec("data")))


class PcVecCombine(BinaryTransformer):
    """Device-capable consumer of raw OPVector features — the TM602
    data-dependent-width recompile hazard."""

    input_types = (OPVector, OPVector)
    output_type = OPVector

    def transform_columns(self, cols, dataset):
        return Column.vector(np.concatenate(
            [np.asarray(cols[0].data, np.float32),
             np.asarray(cols[1].data, np.float32)], axis=1))

    def device_transform(self, a, b):
        import jax.numpy as jnp

        return jnp.concatenate([a, b], axis=1)


def _raw(name, ftype=Real, response=False):
    b = FeatureBuilder.of(name, ftype).extract_field()
    return b.as_response() if response else b.as_predictor()


@pytest.fixture(scope="module")
def fitted_model():
    """Small fitted workflow whose scoring plan has a real fused prefix
    (vectorizers + combiner + sanity checker), the test_serve shape."""
    import pandas as pd

    rng = np.random.default_rng(11)
    n = 300
    records = [
        {"label": float(rng.random() < 0.5), "x1": float(rng.normal()),
         "color": str(rng.choice(["red", "green", "blue"])),
         "age": None if rng.random() < 0.1 else float(rng.normal(40, 10))}
        for _ in range(n)]
    label = FeatureBuilder.RealNN("label").extract_field().as_response()
    f_x1 = FeatureBuilder.Real("x1").extract_field().as_predictor()
    f_color = FeatureBuilder.PickList("color").extract_field().as_predictor()
    f_age = FeatureBuilder.Real("age").extract_field().as_predictor()
    vec = transmogrify([f_x1, f_color, f_age])
    checked = label.sanity_check(vec)
    sel = BinaryClassificationModelSelector.with_train_validation_split(
        models=[(LogisticRegression(), [{"reg_param": 0.01}])])
    pred = label.transform_with(sel, checked)
    model = (Workflow().set_result_features(label, pred)
             .set_reader(DataReaders.Simple.dataframe(pd.DataFrame(records)))
             ).train()
    return model


# ---------------------------------------------------------------------------
# core: jaxpr walk
# ---------------------------------------------------------------------------

class TestTraceCost:
    def test_dot_general_flops_exact(self):
        import jax

        a = jax.ShapeDtypeStruct((8, 4), np.dtype("float32"))
        b = jax.ShapeDtypeStruct((4, 3), np.dtype("float32"))
        seg = trace_cost(lambda x, y: x @ y, a, b, name="matmul")
        assert seg.flops == 2 * 8 * 3 * 4
        # reads both operands, writes the result (at least once each)
        assert seg.bytes_read >= (8 * 4 + 4 * 3) * 4
        assert seg.bytes_written >= 8 * 3 * 4
        assert seg.peak_live_bytes >= (8 * 4 + 4 * 3 + 8 * 3) * 4

    def test_elementwise_and_reduce_counts(self):
        import jax

        x = jax.ShapeDtypeStruct((64,), np.dtype("float32"))
        seg = trace_cost(lambda v: (v * 2.0 + 1.0).sum(), x, name="ew")
        # mul(64) + add(64) + reduce_sum(64) — broadcasts of the scalars may
        # add a few more elementwise flops, never fewer
        assert 3 * 64 <= seg.flops <= 6 * 64
        assert seg.op_counts.get("reduce_sum") == 1

    def test_trace_is_abstract_zero_compiles(self):
        import jax

        x = jax.ShapeDtypeStruct((128, 16), np.dtype("float32"))
        with measure_compiles() as c:
            seg = trace_cost(lambda v: (v @ v.T).sum(), x, name="abstract")
        assert c.backend_compiles == 0
        assert seg.flops > 0

    def test_traces_through_jit_and_scan(self):
        import jax
        import jax.numpy as jnp

        @jax.jit
        def stepped(v):
            def body(carry, _):
                return carry * 1.5 + 1.0, ()
            out, _ = jax.lax.scan(body, v, None, length=10)
            return out

        x = jax.ShapeDtypeStruct((32,), np.dtype("float32"))
        seg = trace_cost(stepped, x, name="scan")
        # body is mul+add over 32 elements, 10 trips: >= 640 flops
        assert seg.flops >= 10 * 2 * 32

    def test_baked_constants_counted_once_in_peak(self):
        """A fn closing over a constant must count its bytes once, not twice
        (a ClosedJaxpr binds consts to constvars — both walks saw them)."""
        import jax
        import jax.numpy as jnp

        w = np.ones((512, 512), np.float32)  # 1 MiB baked constant

        def f(x):
            return x @ jnp.asarray(w)

        x = jax.ShapeDtypeStruct((4, 512), np.dtype("float32"))
        seg = trace_cost(f, x, name="const")
        w_bytes = w.size * 4
        io_bytes = (4 * 512 + 4 * 512) * 4
        assert seg.peak_live_bytes < 1.5 * w_bytes, \
            "constant bytes double-counted in the peak-HBM estimate"
        assert seg.peak_live_bytes >= w_bytes + io_bytes

    def test_order_sensitive_ops_recorded(self):
        import jax
        import jax.numpy as jnp

        x = jax.ShapeDtypeStruct((16, 2), np.dtype("float32"))
        seg = trace_cost(lambda v: jnp.sort(v, axis=1), x, name="sort")
        assert seg.order_sorts >= 1


# ---------------------------------------------------------------------------
# full model analysis + TM6xx wiring
# ---------------------------------------------------------------------------

    def test_static_flops_within_band_of_the_analytic_irls_count(self):
        """The static FLOP model against a count made by hand: the IRLS
        fold x grid sweep at the headline width does, per (grid, fold,
        iteration), the bordered Hessian X^T S X (2 n d^2), the scalings,
        borders and matvecs (~6 n d1) and the solve (2/3 d1^3).  The traced
        count must sit within 0.2-5.0 of it, or every roofline and MFU
        figure that rests on the model is off."""
        import jax

        from transmogrifai_tpu.models.logistic import _irls_sweep

        n, d, folds, iters = 2048, 128, 3, 30
        d1 = d + 1
        regs = 8
        f32 = np.dtype("float32")
        seg = trace_cost(
            lambda a, b, c, r: _irls_sweep(a, b, c, r, iters),
            jax.ShapeDtypeStruct((n, d), f32),
            jax.ShapeDtypeStruct((n,), f32),
            jax.ShapeDtypeStruct((folds, n), f32),
            jax.ShapeDtypeStruct((regs,), f32), name="irls_sweep")
        analytic = regs * folds * iters * (
            2.0 * n * d * d + 6.0 * n * d1 + (2 / 3) * d1 ** 3)
        assert seg.flops > 0
        assert 0.2 <= seg.flops / analytic <= 5.0, seg.flops / analytic


class TestCostValidate:
    def test_cost_report_nonzero_and_zero_compiles(self, fitted_model):
        with measure_compiles() as c:
            report = fitted_model.validate(serving=True, cost=True)
        assert c.backend_compiles == 0, \
            "cost analyzers must run purely on abstract specs"
        cost = report.plan_cost
        assert cost is not None
        assert cost.total_flops > 0 and cost.total_bytes > 0
        assert cost.buckets, "per-bucket HBM estimates missing"
        assert all(b.peak_hbm_bytes > 0 for b in cost.buckets)
        # the ladder grows monotonically with the bucket
        peaks = [b.peak_hbm_bytes for b in cost.buckets]
        assert peaks == sorted(peaks)
        assert cost.segments, "per-stage segments missing"
        # serialization round-trips
        d = cost.to_dict()
        assert d["totalFlops"] == cost.total_flops
        assert "PlanCostReport" in cost.pretty()

    def test_default_validate_skips_cost(self, fitted_model):
        report = fitted_model.validate(serving=True)
        assert report.plan_cost is None
        assert not report.by_code("TM604")

    def test_tm601_fires_on_tiny_budget(self, fitted_model):
        report = fitted_model.validate(serving=True, hbm_budget=16)
        tm601 = report.by_code("TM601")
        assert len(tm601) == 1
        assert tm601[0].severity == Severity.ERROR
        assert report.errors()

    def test_generous_budget_is_clean(self, fitted_model):
        report = fitted_model.validate(serving=True, hbm_budget=1e15)
        assert not report.by_code("TM601")

    def test_tm604_memory_bound_worklist(self, fitted_model):
        report = fitted_model.validate(serving=True, cost=True)
        tm604 = report.by_code("TM604")
        # the prep prefix is elementwise/gather work: memory-bound by design
        assert len(tm604) == 1
        assert tm604[0].severity == Severity.INFO
        assert "Pallas" in tm604[0].message

    def test_unfitted_workflow_reports_hazards_only(self):
        label = _raw("label", RealNN, response=True)
        x = _raw("x")
        vec = transmogrify([x])
        checked = label.sanity_check(vec)
        sel = BinaryClassificationModelSelector.with_train_validation_split(
            models=[(LogisticRegression(), [{"reg_param": 0.01}])])
        pred = label.transform_with(sel, checked)
        wf = Workflow().set_result_features(label, pred)
        report = wf.validate(cost=True)
        assert report.plan_cost is not None
        assert report.plan_cost.total_flops == 0
        assert any("unfitted" in n for n in report.plan_cost.notes)
        assert not report.by_code("TM606")  # no contract armed: advisory only
        # an ARMED budget gate on an uncostable plan must fail CLOSED
        armed = wf.validate(hbm_budget=1e9)
        tm606 = armed.by_code("TM606")
        assert len(tm606) == 1 and tm606[0].severity == Severity.ERROR
        assert armed.errors()


class TestSeededTm60x:
    def test_tm605_float_sort(self):
        out = _raw("a").transform_with(PcSortStage())
        report = validate_result_features([out], fitted={}, cost=True)
        tm605 = report.by_code("TM605")
        assert len(tm605) == 1
        assert tm605[0].severity == Severity.WARNING
        assert "sort" in tm605[0].message
        # the evidence behind TM605 is a first-class, serialized field
        assert report.plan_cost.order_sorts >= 1
        d = report.plan_cost.to_dict()
        assert d["orderSensitiveOps"]["sorts"] >= 1

    def test_tm603_collective_under_single_host(self):
        out = _raw("a").transform_with(PcShardStage())
        report = validate_result_features([out], fitted={}, cost=True,
                                          single_host=True)
        tm603 = report.by_code("TM603")
        assert len(tm603) == 1
        assert tm603[0].severity == Severity.ERROR
        assert "sharding_constraint" in tm603[0].message

    def test_collective_inventory_without_single_host_is_not_an_error(self):
        out = _raw("a").transform_with(PcShardStage())
        report = validate_result_features([out], fitted={}, cost=True)
        assert not report.by_code("TM603")
        assert report.plan_cost.collectives.get("sharding_constraint", 0) >= 1

    def test_tm602_data_dependent_width(self):
        va, vb = _raw("va", OPVector), _raw("vb", OPVector)
        out = va.transform_with(PcVecCombine(), vb)
        report = validate_result_features([out], fitted={}, cost=True)
        tm602 = report.by_code("TM602")
        assert len(tm602) == 2  # one per raw OPVector input
        assert all(d.severity == Severity.WARNING for d in tm602)
        kinds = {h.kind for h in report.plan_cost.hazards}
        assert kinds == {"data_dependent_width"}

    def test_cost_diagnostics_threshold_is_configurable(self):
        from transmogrifai_tpu.checkers.plancheck import BucketCost, SegmentCost

        seg = SegmentCost(name="s", flops=10, bytes_read=50, bytes_written=50)
        rep = PlanCostReport(plan="t", segments=[seg],
                             buckets=[BucketCost(8, 10, 50, 50, 400)])
        assert [d.code for d in cost_diagnostics(rep)] == ["TM604"]
        assert cost_diagnostics(rep, intensity_threshold=0.01) == []
        assert seg.intensity < MEMORY_BOUND_INTENSITY


# ---------------------------------------------------------------------------
# admission gates: train(hbm_budget=...) and serving
# ---------------------------------------------------------------------------

class TestAdmissionGates:
    def _workflow(self, n=200):
        import pandas as pd

        rng = np.random.default_rng(5)
        records = [{"label": float(rng.random() < 0.5),
                    "x1": float(rng.normal()), "x2": float(rng.normal())}
                   for _ in range(n)]
        label = FeatureBuilder.RealNN("label").extract_field().as_response()
        f1 = FeatureBuilder.Real("x1").extract_field().as_predictor()
        f2 = FeatureBuilder.Real("x2").extract_field().as_predictor()
        vec = transmogrify([f1, f2])
        checked = label.sanity_check(vec)
        sel = BinaryClassificationModelSelector.with_train_validation_split(
            models=[(LogisticRegression(), [{"reg_param": 0.01}])])
        pred = label.transform_with(sel, checked)
        return (Workflow().set_result_features(label, pred)
                .set_reader(DataReaders.Simple.dataframe(
                    pd.DataFrame(records))))

    def test_train_hbm_budget_blocks_over_budget_plan(self):
        wf = self._workflow()
        with pytest.raises(OpCheckError, match="TM601"):
            wf.train(strict=True, hbm_budget=16)

    def test_train_generous_budget_trains(self):
        model = self._workflow().train(strict=True, hbm_budget=1e15)
        assert model.selector_model() is not None

    def test_workflow_cv_path_is_gated_too(self):
        """The with_workflow_cv train path (fold-fitted during stages) must
        run under the same TM601 gate — the fold programs were the review's
        ungated hole."""
        wf = self._workflow().with_workflow_cv()
        with pytest.raises(OpCheckError, match="TM601"):
            wf.train(strict=True, hbm_budget=16)
        model = self._workflow().with_workflow_cv().train(
            strict=True, hbm_budget=1e15)
        assert model.selector_model() is not None

    def test_serving_plan_admission_blocks(self, fitted_model):
        with pytest.raises(OpCheckError, match="TM601"):
            fitted_model.serving_plan(hbm_budget=16)

    def test_scoring_server_admission_blocks(self, fitted_model):
        from transmogrifai_tpu.serve import ScoringServer

        with pytest.raises(OpCheckError, match="TM601"):
            ScoringServer(fitted_model, hbm_budget=16)

    def test_check_plan_admission_direct(self, fitted_model):
        from transmogrifai_tpu.serve import check_plan_admission

        plan = fitted_model.serving_plan()
        blocked = check_plan_admission(plan, hbm_budget=16)
        assert [d.code for d in blocked] == ["TM601"]
        assert blocked.plan_cost is not None
        admitted = check_plan_admission(plan, hbm_budget=1e15)
        assert len(admitted) == 0

    def test_admission_is_abstract_zero_compiles(self, fitted_model):
        from transmogrifai_tpu.serve import check_plan_admission

        plan = fitted_model.serving_plan()
        with measure_compiles() as c:
            check_plan_admission(plan, hbm_budget=1e15)
        assert c.backend_compiles == 0
