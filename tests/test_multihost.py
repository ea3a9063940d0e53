"""Pod-scale dp x mp sweep execution (ISSUE 15): sharded fold x grid
programs on the 8-device simulated-CPU mesh, the host-local global-array
assembly path, the TM608/TM609 static scalability gate, and the chunk-tile /
mesh divisibility contract.

CI has no multi-process backend, so verification is the zero-hardware stack:
bitwise sharded-vs-unsharded parity on simulated devices, mocked
``process_index``/``process_count`` arithmetic for the multi-host seams
(the pattern test_distributed.py established), and abstract-trace static
analysis for the scale-out properties no single host can execute.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from transmogrifai_tpu import (
    BinaryClassificationModelSelector,
    Dataset,
    FeatureBuilder,
    Workflow,
    transmogrify,
)
from transmogrifai_tpu.models.logistic import LogisticRegression
from transmogrifai_tpu.models.svm import LinearSVC
from transmogrifai_tpu.parallel import distributed as D
from transmogrifai_tpu.parallel.mesh import (
    constrain,
    constrain_rows,
    make_mesh,
    mesh_token,
    use_mesh,
)
from transmogrifai_tpu.perf import measure_compiles
from transmogrifai_tpu.types import Real, RealNN


def _selector_pipeline(n=211, seed=29, folds=2):
    """LR (IRLS grid) + SVC + GBT: the sharded sweep programs under test."""
    from transmogrifai_tpu.models.trees import GradientBoostedTreesClassifier

    rng = np.random.default_rng(seed)
    cols = {f"x{i}": rng.normal(size=n).tolist() for i in range(4)}
    z = sum((i + 1) * 0.4 * np.asarray(cols[f"x{i}"]) for i in range(4))
    cols["label"] = (rng.random(n) < 1 / (1 + np.exp(-z))
                     ).astype(float).tolist()
    ds = Dataset.from_features(
        cols, {**{f"x{i}": Real for i in range(4)}, "label": RealNN})
    label = FeatureBuilder.of("label", RealNN).extract_field().as_response()
    fs = [FeatureBuilder.of(f"x{i}", Real).extract_field().as_predictor()
          for i in range(4)]
    sel = BinaryClassificationModelSelector.with_cross_validation(
        num_folds=folds,
        models=[(LogisticRegression(),
                 [{"reg_param": r} for r in (0.0, 0.01, 0.1)]),
                (LinearSVC(), [{"reg_param": r} for r in (0.01, 0.1)]),
                (GradientBoostedTreesClassifier(num_rounds=3, max_depth=2),
                 [{}])])
    p = label.transform_with(sel, transmogrify(fs))
    return ds, label, p


class TestShardedSweepParity:
    """ACCEPTANCE: sharded-vs-unsharded CV metrics and winner selection
    bitwise-equal on the 4x2 simulated-CPU mesh, and a warm sharded refit
    compiles NOTHING (plan + sweep executable caches keyed on the mesh
    token serve it)."""

    def test_cv_metrics_and_winner_bitwise_and_warm_refit_zero_compiles(self):
        ds, label, p = _selector_pipeline()
        m1 = (Workflow().set_input_dataset(ds)
              .set_result_features(label, p).train())
        with use_mesh(make_mesh(n_data=4, n_model=2)):
            m2 = (Workflow().set_input_dataset(ds)
                  .set_result_features(label, p).train())
            # warm sharded refit: every sweep program, eval program, and
            # fused-prefix executable must come out of the mesh-keyed caches
            with measure_compiles() as probe:
                m3 = (Workflow().set_input_dataset(ds)
                      .set_result_features(label, p).train())
        assert probe.backend_compiles == 0, (
            f"warm sharded refit recompiled {probe.backend_compiles} "
            f"program(s)")

        sm1, sm2, sm3 = m1.summary(), m2.summary(), m3.summary()
        assert sm1.failed_models == [] and sm2.failed_models == []
        ev1 = {(e.model_name, tuple(sorted(e.grid.items()))): e
               for e in sm1.validation_results}
        ev2 = {(e.model_name, tuple(sorted(e.grid.items()))): e
               for e in sm2.validation_results}
        assert set(ev1) == set(ev2)
        for key in ev1:
            v1, v2 = ev1[key].metric_values, ev2[key].metric_values
            assert v1 == v2, (  # bitwise: sharding is layout, never math
                f"CV metrics diverged under the 4x2 mesh for {key}: "
                f"{v1} != {v2}")
        assert sm1.best_model_name == sm2.best_model_name
        assert sm2.best_model_name == sm3.best_model_name

    def test_irls_sweep_dispatch_bitwise_and_warm_on_the_4x2_mesh(self):
        """The sharded IRLS fold x grid dispatch alone (no selector, no
        other family): its CV metrics under the 4x2 mesh equal the
        single-device dispatch bitwise, and a warm sharded dispatch compiles
        NOTHING (the executable cache keys on the mesh token)."""
        from transmogrifai_tpu.evaluators import metrics as M
        from transmogrifai_tpu.models.base import gather_scores

        n, d, k = 1500, 16, 2
        grids = [{"reg_param": r} for r in (0.0, 0.01, 0.1, 1.0)]
        rng = np.random.default_rng(1215)
        x = rng.normal(size=(n, d)).astype(np.float32)
        beta = rng.normal(size=d).astype(np.float32)
        y = (rng.random(n) < 1 / (1 + np.exp(-(x @ beta)))
             ).astype(np.float32)
        folds = rng.integers(0, k, size=n)
        train_w = np.stack([(folds != f).astype(np.float32)
                            for f in range(k)])
        val_w = np.stack([(folds == f).astype(np.float32)
                          for f in range(k)])
        est = LogisticRegression(max_iter=10)

        def dispatch():
            return gather_scores(est._cv_sweep_device(
                x, y, train_w, val_w, grids, M.METRICS_BINARY["auPR"]))

        single = dispatch()
        with use_mesh(make_mesh(n_data=4, n_model=2)):
            sharded = dispatch()
            with measure_compiles() as probe:
                warm = dispatch()
        assert probe.backend_compiles == 0
        assert single.shape == (len(grids), k)
        np.testing.assert_array_equal(single, sharded)
        np.testing.assert_array_equal(sharded, warm)

    def test_fused_prefix_runs_sharded_and_bitwise(self):
        """The meshed fused transform prefix must actually execute as ONE
        row-sharded program (it silently fell back to the host path before
        ISSUE 15 — a placed-array indexing bug) and its columns must be
        bitwise-equal to the unmeshed dispatch."""
        from transmogrifai_tpu.workflow.dag import compute_dag
        from transmogrifai_tpu.workflow.plan import plan_for

        ds, label, p = _selector_pipeline(n=150)
        checked = label.sanity_check(
            transmogrify([FeatureBuilder.of(f"x{i}", Real).extract_field()
                          .as_predictor() for i in range(4)]))
        m = (Workflow().set_input_dataset(ds)
             .set_result_features(label, checked).train())
        runners = [m.fitted.get(s.uid, s)
                   for layer in compute_dag(m.result_features)
                   for s in layer]
        plan_u, _ = plan_for(runners, frozenset(ds.names))
        out_u = plan_u.apply_prefix(ds)
        with use_mesh(make_mesh(n_data=4, n_model=2)):
            plan_m, _ = plan_for(runners, frozenset(ds.names))
            # the mesh token keys the plan fingerprint: no aliasing
            assert plan_m.fingerprint != plan_u.fingerprint
            out_m = plan_m.apply_prefix(ds)  # must NOT raise/fall back
        a = np.asarray(out_u[checked.name].data)
        b = np.asarray(out_m[checked.name].data)
        np.testing.assert_array_equal(a, b)


class TestTopologyKeys:
    """Cache keys and plan fingerprints carry the global mesh shape AND the
    process topology, so multi-host executables can never alias
    single-host ones."""

    def test_mesh_token_carries_process_topology(self, monkeypatch):
        with use_mesh(make_mesh(4, 2)):
            t1 = mesh_token()
            monkeypatch.setattr(jax, "process_count", lambda: 4)
            t2 = mesh_token()
        assert t1 != t2 and t1[:2] == t2[:2]
        assert mesh_token() is None  # no ambient mesh -> no token

    def test_run_cached_fingerprint_differs_by_topology(self, monkeypatch):
        from transmogrifai_tpu.models.logistic import _irls_sweep
        from transmogrifai_tpu.perf import cache_key_fingerprint

        args = (np.zeros((64, 5), np.float32), np.zeros(64, np.float32),
                np.zeros((2, 64), np.float32), np.zeros(2, np.float32))
        statics = dict(max_iter=3, has_intercept=True)
        fp_none = cache_key_fingerprint(_irls_sweep, *args, statics=statics)
        with use_mesh(make_mesh(4, 2)):
            fp_mesh = cache_key_fingerprint(_irls_sweep, *args,
                                            statics=statics)
            monkeypatch.setattr(jax, "process_count", lambda: 2)
            fp_pod = cache_key_fingerprint(_irls_sweep, *args,
                                           statics=statics)
        assert len({fp_none, fp_mesh, fp_pod}) == 3

    def test_plan_fingerprint_differs_by_topology(self, monkeypatch):
        from transmogrifai_tpu.ops.numeric import NumericVectorizerModel
        from transmogrifai_tpu.workflow.plan import stage_content_fingerprint

        stage = NumericVectorizerModel(fills=np.array([0.0, 1.0]),
                                       track_nulls=True)
        fp_none = stage_content_fingerprint([stage])
        with use_mesh(make_mesh(4, 2)):
            fp_mesh = stage_content_fingerprint([stage])
            monkeypatch.setattr(jax, "process_count", lambda: 2)
            fp_pod = stage_content_fingerprint([stage])
        assert len({fp_none, fp_mesh, fp_pod}) == 3


class TestGlobalRowAssembly:
    """The host-local ingest seam: each host decodes only its own row span
    and the spans compose to the global array/fit — exercised single-process
    via the mocked process arithmetic (the hardware two-process run stays
    xfail in test_distributed.py)."""

    def test_spans_partition_exactly(self):
        for n, pc in ((10, 3), (8192, 4), (7, 8), (0, 2), (5, 1)):
            spans = D.host_row_spans(n, pc)
            assert len(spans) == pc
            covered = []
            for s in spans:
                covered.extend(range(s.start, s.stop))
            assert covered == list(range(n))

    def test_single_process_assembly_matches_direct_placement(self):
        rng = np.random.default_rng(3)
        x = rng.normal(size=(64, 4)).astype(np.float32)
        with use_mesh(make_mesh(4, 2)) as mesh:
            g = D.global_row_array(x, n_global_rows=64)
            direct = jax.device_put(
                x, jax.sharding.NamedSharding(
                    mesh, jax.sharding.PartitionSpec("data")))
            np.testing.assert_array_equal(np.asarray(g), np.asarray(direct))

    def test_single_process_partial_block_refused(self):
        x = np.zeros((10, 2), np.float32)
        with use_mesh(make_mesh(4, 2)):
            with pytest.raises(ValueError, match="full 16 rows"):
                D.global_row_array(x[:5], n_global_rows=16)

    def test_mocked_two_host_span_decoding(self, monkeypatch):
        """Under mocked 2-process topology every host's ``host_local_rows``
        slice is its decode contract; the spans must tile the table."""
        monkeypatch.setattr(jax, "process_count", lambda: 2)
        blocks = []
        n = 100
        rng = np.random.default_rng(0)
        x = rng.normal(size=(n, 3)).astype(np.float32)
        for pid in range(2):
            monkeypatch.setattr(jax, "process_index", lambda p=pid: p)
            s = D.host_local_rows(n)
            blocks.append(x[s])
        assert blocks[0].shape == (50, 3) and blocks[1].shape == (50, 3)
        np.testing.assert_array_equal(np.vstack(blocks), x)

    def test_two_simulated_host_contributions_compose_to_global_fit(self):
        """The IRLS/ridge psum math decomposes over host row spans: the
        per-span weighted Gram/moment contributions must sum EXACTLY to the
        single-host statistics (integer-valued fixtures make float addition
        exact), so a two-host fit on span-decoded rows reproduces the
        global fit."""
        n, d = 96, 4
        rng = np.random.default_rng(7)
        x = rng.integers(-3, 4, size=(n, d)).astype(np.float64)
        y = rng.integers(0, 2, size=n).astype(np.float64)
        w = np.ones(n)
        spans = D.host_row_spans(n, 2)
        gram = sum((w[s, None] * x[s]).T @ x[s] for s in spans)
        xty = sum(x[s].T @ (w[s] * y[s]) for s in spans)
        np.testing.assert_array_equal(gram, (w[:, None] * x).T @ x)
        np.testing.assert_array_equal(xty, x.T @ (w * y))
        # and the closed-form fit from composed statistics == global fit
        reg = np.eye(d)
        beta_spans = np.linalg.solve(gram + reg, xty)
        beta_global = np.linalg.solve((w[:, None] * x).T @ x + reg,
                                      x.T @ (w * y))
        np.testing.assert_allclose(beta_spans, beta_global, rtol=1e-12)

    def test_global_mesh_refuses_host_crossing_model_axis(self, monkeypatch):
        monkeypatch.setattr(jax, "process_count", lambda: 2)
        monkeypatch.setattr(jax, "local_devices", lambda: jax.devices()[:4])
        with pytest.raises(ValueError, match="span hosts"):
            D.global_mesh(n_model=8)
        # non-strict downgrades to a warning
        mesh = D.global_mesh(n_model=8, strict_topology=False)
        assert mesh.shape["model"] == 8

    def test_global_mesh_explicit_devices_checks_process_groups(self):
        """An explicit ``devices`` list is checked off the Device objects'
        own process_index (a per-host count is meaningless there): a model
        group straddling two processes is refused even though the list size
        divides evenly."""
        class _Dev:
            def __init__(self, pidx):
                self.process_index = pidx

        two_hosts = [_Dev(i // 4) for i in range(8)]  # 2 procs x 4 devices
        with pytest.raises(ValueError, match="span hosts"):
            D.global_mesh(n_model=8, devices=two_hosts)
        # groups confined to one process pass the topology check and reach
        # mesh construction (real devices: all one process here)
        mesh = D.global_mesh(n_model=4, devices=jax.devices())
        assert mesh.shape["model"] == 4


class TestStaticScalabilityGate:
    """ACCEPTANCE: TM608 fires on a seeded plan whose collective volume
    scales with global rows, stays quiet on the fixed per-host form; TM609
    flags replicated operands over the per-host HBM share."""

    @staticmethod
    def _specs(buckets, d=8):
        return [(b, [jax.ShapeDtypeStruct((b, d), np.float32),
                     jax.ShapeDtypeStruct((d,), np.float32)])
                for b in buckets]

    def test_tm608_fires_on_rows_proportional_collectives(self):
        from transmogrifai_tpu.checkers.plancheck import (
            analyze_program, cost_diagnostics)

        def seeded_bad(x, w):
            # replicated pin on a row-shaped intermediate: a per-step
            # all-gather of the whole row block — the shape that cannot
            # scale past one host
            scores = x @ w                       # (rows,)
            scores = constrain(scores)           # P() -> full all-gather
            return scores.sum()

        def fixed(x, w):
            x = constrain_rows(x)                # rows stay on the data axis
            return (x @ w).sum()                 # psum carries a scalar

        with use_mesh(make_mesh(4, 2)):
            r_bad = analyze_program(seeded_bad, self._specs((1024, 8192)),
                                    label="seeded-bad")
            r_fix = analyze_program(fixed, self._specs((1024, 8192)),
                                    label="fixed")
            codes_bad = {d_.code for d_ in cost_diagnostics(r_bad)}
            codes_fix = {d_.code for d_ in cost_diagnostics(r_fix)}
        assert "TM608" in codes_bad, codes_bad
        assert "TM608" not in codes_fix, codes_fix
        assert r_bad.collective_bytes_per_step > 0
        assert r_fix.buckets[-1].collective_bytes == 0

    def test_tm608_quiet_without_mesh(self):
        from transmogrifai_tpu.checkers.plancheck import (
            analyze_program, cost_diagnostics)

        def prog(x, w):
            return (x @ w).sum()

        r = analyze_program(prog, self._specs((1024, 8192)))
        assert all(d_.code not in ("TM608", "TM609")
                   for d_ in cost_diagnostics(r, hbm_budget=1.0))

    def test_tm609_fires_on_replicated_operands_over_share(self):
        from transmogrifai_tpu.checkers.plancheck import (
            analyze_program, cost_diagnostics)

        baked = jnp.asarray(np.ones((512, 512), np.float32))  # 1 MiB const

        def prog(x, w):
            x = constrain_rows(x)
            return (x[:, :1] * baked.sum()).sum() + (x @ w).sum()

        with use_mesh(make_mesh(4, 2)):
            r = analyze_program(prog, self._specs((1024,)))
            over = cost_diagnostics(r, hbm_budget=1024 * 1024)      # 1 MiB
            under = cost_diagnostics(r, hbm_budget=64 * 1024 * 1024)
        assert "TM609" in {d_.code for d_ in over}
        assert "TM609" not in {d_.code for d_ in under}
        assert r.replicated_bytes >= 512 * 512 * 4

    def test_tm609_sees_consts_baked_inside_jit_wrapped_programs(self):
        """Every real caller hands analyze_program a jit-WRAPPED fn, which
        stages as one pjit eqn binding its consts in the sub-jaxpr — the
        top-level constvars are empty.  The replication evidence must see
        through the wrapper or the gate silently never fires."""
        from transmogrifai_tpu.checkers.plancheck import (
            analyze_program, cost_diagnostics)

        baked = jnp.asarray(np.ones((512, 512), np.float32))  # 1 MiB const

        @jax.jit
        def prog(x, w):
            x = constrain_rows(x)
            return (x[:, :1] * baked.sum()).sum() + (x @ w).sum()

        with use_mesh(make_mesh(4, 2)):
            r = analyze_program(prog, self._specs((1024,)))
            over = cost_diagnostics(r, hbm_budget=1024 * 1024)
        assert r.replicated_bytes >= 512 * 512 * 4
        assert "TM609" in {d_.code for d_ in over}

    def test_sharded_sweep_program_passes_the_gate(self):
        """The REAL sharded IRLS sweep must be per-host clean: collective
        volume flat across the row ladder (no TM608)."""
        from functools import partial

        from transmogrifai_tpu.checkers.plancheck import (
            analyze_program, cost_diagnostics)
        from transmogrifai_tpu.models.logistic import _irls_sweep

        k, g, d1 = 2, 3, 9

        def specs(b):
            return [jax.ShapeDtypeStruct((b, d1), np.float32),
                    jax.ShapeDtypeStruct((b,), np.float32),
                    jax.ShapeDtypeStruct((k, b), np.float32),
                    jax.ShapeDtypeStruct((g,), np.float32)]

        fn = partial(_irls_sweep, max_iter=3, has_intercept=True)
        with use_mesh(make_mesh(4, 2)):
            r = analyze_program(fn, [(b, specs(b)) for b in (1024, 8192)],
                                label="irls_sweep@4x2")
            codes = {d_.code for d_ in cost_diagnostics(r)}
        assert "TM608" not in codes, codes


class TestChunkTileMeshDivisibility:
    """ISSUE 15 satellite: chunked epochs under ``use_mesh`` keep the chunk
    tile divisible by the data-axis size (computed once per epoch), so chunk
    boundaries compile ZERO new executables on a mesh and the outputs stay
    bitwise-equal to the in-memory dispatch."""

    def test_mesh_aligned_tile(self):
        from transmogrifai_tpu.workflow.plan import mesh_aligned_tile

        assert mesh_aligned_tile(8192) == 8192          # no mesh: unchanged
        with use_mesh(make_mesh(4, 2)):
            assert mesh_aligned_tile(8192) == 8192      # 4 | 8192
            assert mesh_aligned_tile(100) == 128        # pow2 already aligned
        with use_mesh(make_mesh(8, 1)):
            assert mesh_aligned_tile(8192) == 8192

    def test_chunked_epoch_zero_compiles_and_bitwise_under_4x2_mesh(self):
        from transmogrifai_tpu.data.chunked import ChunkedDataset
        from transmogrifai_tpu.workflow.dag import compute_dag
        from transmogrifai_tpu.workflow.fit import transform_dag
        from transmogrifai_tpu.workflow.ooc import chunked_transform_epoch

        rng = np.random.default_rng(17)
        n = 700
        cols = {f"x{i}": rng.normal(size=n).tolist() for i in range(3)}
        cols["label"] = (rng.random(n) < 0.5).astype(float).tolist()
        ds = Dataset.from_features(
            cols, {**{f"x{i}": Real for i in range(3)}, "label": RealNN})
        label = FeatureBuilder.of("label", RealNN).extract_field() \
            .as_response()
        checked = label.sanity_check(transmogrify(
            [FeatureBuilder.of(f"x{i}", Real).extract_field().as_predictor()
             for i in range(3)]))
        m = (Workflow().set_input_dataset(ds)
             .set_result_features(label, checked).train())
        runners = [m.fitted.get(s.uid, s)
                   for layer in compute_dag(m.result_features)
                   for s in layer]

        with use_mesh(make_mesh(n_data=4, n_model=2)):
            in_mem = transform_dag(ds, m.result_features, m.fitted)
            cds = ChunkedDataset.from_dataset(ds, chunk_rows=256)
            out1 = chunked_transform_epoch(cds, runners)
            # chunk boundaries + a full second epoch: zero new executables
            with measure_compiles() as probe:
                out2 = chunked_transform_epoch(cds, runners)
            assert probe.backend_compiles == 0, probe.backend_compiles
        idx = np.arange(n, dtype=np.intp)
        for out in (out1, out2):
            got = np.asarray(out.take(idx)[checked.name].data)
            np.testing.assert_array_equal(
                got, np.asarray(in_mem[checked.name].data))
