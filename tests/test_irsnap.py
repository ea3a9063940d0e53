"""IR golden corpus + semantic program differ (ISSUE 7 tentpole).

Discipline mirrored from test_plancheck.py: every differ class has a seeded
fixture that fires it exactly as classified, the whole corpus snapshot+diff
pass runs purely on abstract lowering (compile probe == 0 — the acceptance
criterion), and the checked-in goldens under tests/goldens/ir must match the
live lowering bit-for-bit so a jax upgrade (or kernel edit) cannot land
without a reviewed, classified IR diff.
"""

import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from transmogrifai_tpu.checkers import irsnap
from transmogrifai_tpu.checkers.diagnostics import Severity
from transmogrifai_tpu.checkers.irsnap import (
    IRSnapshot,
    build_corpus,
    canonicalize_stablehlo,
    default_goldens_dir,
    diff_corpus,
    diff_snapshots,
    ir_fingerprint,
    load_corpus,
    save_corpus,
)
from transmogrifai_tpu.perf import measure_compiles

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _spec(*shape, dtype="float32"):
    return jax.ShapeDtypeStruct(tuple(shape), np.dtype(dtype))


# ---------------------------------------------------------------------------
# canonicalization
# ---------------------------------------------------------------------------

class TestCanonicalize:
    def test_ssa_renumbering_is_alpha_equivalence(self):
        a = 'module @jit_f {\n  %12 = stablehlo.add %3, %3 : tensor<4xf32>\n}'
        b = 'module @jit_g {\n  %0 = stablehlo.add %arg0, %arg0 : tensor<4xf32>\n}'
        assert canonicalize_stablehlo(a) == canonicalize_stablehlo(b)
        assert ir_fingerprint(canonicalize_stablehlo(a)) == \
            ir_fingerprint(canonicalize_stablehlo(b))

    def test_locations_stripped(self):
        a = '%0 = stablehlo.abs %1 : tensor<2xf32> loc("x.py":3:1)\n#loc = loc(unknown)'
        b = '%0 = stablehlo.abs %1 : tensor<2xf32>'
        assert canonicalize_stablehlo(a) == canonicalize_stablehlo(b)

    def test_large_constants_hash_small_ones_survive(self):
        small = "dense<[1, 2, 3]>"
        big = "dense<[" + ", ".join("1.25" for _ in range(64)) + "]>"
        out = canonicalize_stablehlo(small + "\n" + big)
        assert "dense<[1, 2, 3]>" in out
        assert "#blake2b:" in out and "1.25" not in out

    def test_dtype_semantics_not_stripped(self):
        a = canonicalize_stablehlo("%0 = stablehlo.abs %1 : tensor<2xf32>")
        b = canonicalize_stablehlo("%0 = stablehlo.abs %1 : tensor<2xf64>")
        assert a != b

    def test_real_lowering_canonicalizes_deterministically(self):
        low = jax.jit(lambda x: (x * 2.0).sum()).lower(_spec(32))
        t1 = canonicalize_stablehlo(low.as_text())
        t2 = canonicalize_stablehlo(
            jax.jit(lambda x: (x * 2.0).sum()).lower(_spec(32)).as_text())
        assert t1 == t2


# ---------------------------------------------------------------------------
# the differ: one seeded fixture per TM70x class
# ---------------------------------------------------------------------------

def _snap_of(fn, *specs, key="prog"):
    return irsnap.snapshot_lowered(key, jax.jit(fn).lower(*specs))


class TestDiffer:
    def test_identical_snapshots_are_clean(self):
        s1 = _snap_of(lambda x: x * 2.0, _spec(16))
        s2 = _snap_of(lambda x: x * 2.0, _spec(16))
        assert diff_snapshots(s1, s2) == []

    def test_tm700_missing_and_extra_golden(self):
        s = _snap_of(lambda x: x + 1.0, _spec(8))
        new = diff_snapshots(None, s)
        gone = diff_snapshots(s, None)
        assert [d.code for d in new] == ["TM700"]
        assert [d.code for d in gone] == ["TM700"]
        assert all(d.severity == Severity.INFO for d in new + gone)

    def test_tm701_benign_text_drift(self):
        s = _snap_of(lambda x: x * 3.0, _spec(8))
        # metadata-only tamper: semantic features identical, text differs
        tampered = IRSnapshot.from_text(
            s.key, s.text.replace('jax.result_info = ""',
                                  'jax.result_info = "renamed"'))
        assert tampered.ir_fingerprint != s.ir_fingerprint
        diags = diff_snapshots(s, tampered)
        assert [d.code for d in diags] == ["TM701"]
        assert diags[0].severity == Severity.INFO
        assert diags[0].location == s.key

    def test_tm702_fusion_layout_change(self):
        old = _snap_of(lambda x: (x * 2.0).sum(), _spec(32))
        new = _snap_of(lambda x: (x * 2.0 + 1.0).sum(), _spec(32))
        diags = diff_snapshots(old, new)
        assert [d.code for d in diags] == ["TM702"]
        assert diags[0].severity == Severity.WARNING
        assert "op histogram" in diags[0].message

    def test_tm703_collective_drift(self):
        from jax.sharding import NamedSharding, PartitionSpec

        from transmogrifai_tpu.parallel.mesh import make_mesh

        mesh = make_mesh(4, 2)
        rep = NamedSharding(mesh, PartitionSpec())
        old = _snap_of(lambda x: x * 2.0, _spec(16))
        new = _snap_of(
            lambda x: jax.lax.with_sharding_constraint(x * 2.0, rep),
            _spec(16))
        codes = [d.code for d in diff_snapshots(old, new)]
        assert "TM703" in codes
        assert "TM704" not in codes and "TM705" not in codes

    def test_tm704_dtype_drift(self):
        # the differ classifies CANONICAL TEXT deltas, and that is exactly
        # what a jax upgrade hands it — seed the dtype flip there (x64 is
        # disabled in this environment, so an f64 SPEC would canonicalize
        # back to the identical f32 program)
        old = _snap_of(lambda x: x * 2.0, _spec(16))
        new = IRSnapshot.from_text(old.key,
                                   old.text.replace("xf32>", "xf64>"))
        diags = diff_snapshots(old, new)
        codes = [d.code for d in diags]
        assert "TM704" in codes
        tm704 = next(d for d in diags if d.code == "TM704")
        assert tm704.severity == Severity.ERROR
        assert "f64" in tm704.message

    def test_tm704_float_width_migration(self):
        # same dtype SET, counts migrate between float widths: one f32
        # tensor silently becomes bf16 in a program already holding both
        import jax.numpy as jnp

        def mixed(x):
            return (x.astype(jnp.bfloat16).sum().astype(np.float32)
                    + x.sum())

        old = _snap_of(mixed, _spec(16))
        assert {"f32", "bf16"} <= set(old.dtype_counts)
        new = IRSnapshot.from_text(
            old.key, old.text.replace("tensor<16xf32>", "tensor<16xbf16>", 1))
        assert old.dtype_counts.keys() == new.dtype_counts.keys()
        codes = [d.code for d in diff_snapshots(old, new)]
        assert "TM704" in codes

    def test_tm704_reads_no_dtype_from_a_collectives_device_table(self):
        """A program that gains its first all-reduce (PR 39: the IRLS sweep's
        per-chip region) gains a ``replica_groups`` table typed
        ``tensor<..xi64>``: an attribute, not a value, so no TM704."""
        old = _snap_of(lambda x: x * 2.0, _spec(16))
        reduce = ('    %9 = "stablehlo.all_reduce"(%0) <{replica_groups = '
                  'dense<[[0, 2], [1, 3]]> : tensor<2x2xi64>, '
                  'use_global_device_ids}> : (tensor<16xf32>) -> '
                  'tensor<16xf32>\n')
        lines = old.text.splitlines(keepends=True)
        at = next(i for i, ln in enumerate(lines) if "stablehlo.multiply" in ln
                  or "stablehlo.mul" in ln)
        new = IRSnapshot.from_text(old.key,
                                   "".join(lines[:at] + [reduce] + lines[at:]))
        assert "i64" not in new.dtype_counts
        assert "TM704" not in [d.code for d in diff_snapshots(old, new)]


class TestTm705Regression:
    """The GSPMD sharded-sort-dim miscompile class: the detector must fire
    on a minimal reconstruction of the exact pre-PR-4 eval-sweep pattern
    (sort-based AUC over row-sharded scores with replicated (grid, fold)
    batch dims) and stay QUIET on the fixed per-mesh-closure form from
    models/base.py (the lanes dealt over the devices, each sorting whole
    rows locally)."""

    @pytest.fixture(scope="class")
    def mesh(self):
        from transmogrifai_tpu.parallel.mesh import make_mesh

        if jax.device_count() < 8:
            pytest.skip("needs 8 devices (conftest forces them on cpu)")
        return make_mesh(4, 2)

    def _metric(self):
        from transmogrifai_tpu.evaluators import metrics as M

        return M.METRICS_BINARY["auPR"]

    def test_fires_on_pre_pr4_pattern(self, mesh):
        from jax.sharding import NamedSharding, PartitionSpec as P

        mfn = self._metric()

        def bad_eval(scores, y, vw):
            # the pre-PR-4 shape: scores (g, k, n) row-sharded over `data`,
            # batch dims replicated; the metric sorts over the sharded n
            s = jax.lax.with_sharding_constraint(
                scores, NamedSharding(mesh, P(None, None, "data")))
            return jax.vmap(
                lambda ps: jax.vmap(lambda p, w: mfn(p, y, w))(ps, vw))(s)

        snap = _snap_of(bad_eval, _spec(2, 2, 64), _spec(64), _spec(2, 64),
                        key="bad_eval")
        hazards = snap.sharded_sort_hazards()
        assert hazards, "detector must fire on the miscompile pattern"
        assert hazards[0].dimension == 2
        clean = _snap_of(lambda x: x * 1.0, _spec(2, 2, 64), key="bad_eval")
        diags = diff_snapshots(clean, snap)
        tm705 = [d for d in diags if d.code == "TM705"]
        assert len(tm705) == 1
        assert tm705[0].severity == Severity.ERROR
        assert "sort" in tm705[0].message.lower()

    def test_quiet_on_fixed_per_mesh_closure(self, mesh):
        from transmogrifai_tpu.models.base import _eval_linear_sweep_for

        snap = irsnap.snapshot_program(
            "fixed_eval", _eval_linear_sweep_for(mesh),
            [_spec(64, 5), _spec(64), _spec(2, 2, 5), _spec(2, 64)],
            statics=dict(metric_fn=self._metric(), link="sigmoid"))
        # the fixed form still SORTS (the AUC metric) — but whole rows of its
        # own share of the lanes, on each device, inside a shard_map region
        assert snap.sorts, "expected the metric's sort in the program"
        assert snap.sharded_sort_hazards() == []

    def test_fires_on_brand_new_family_without_golden(self, mesh):
        """A NEW program family carrying the hazard must not hide behind the
        TM700 info: the hazard scan runs even when there is no golden yet."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        mfn = self._metric()

        def bad_eval(scores, y, vw):
            s = jax.lax.with_sharding_constraint(
                scores, NamedSharding(mesh, P(None, None, "data")))
            return jax.vmap(
                lambda ps: jax.vmap(lambda p, w: mfn(p, y, w))(ps, vw))(s)

        snap = _snap_of(bad_eval, _spec(2, 2, 64), _spec(64), _spec(2, 64),
                        key="new_family")
        codes = [d.code for d in diff_snapshots(None, snap)]
        assert codes.count("TM705") == 1
        assert "TM700" in codes

    def test_sharding_resolves_through_generic_printer_form(self):
        """The pass-through walk must survive the generic MLIR printer form
        ('"stablehlo.negate"(%v0)') for elementwise ops — a printer-form
        change across a jax bump is exactly the scenario the corpus guards,
        and a silent parse miss would turn TM705 off."""
        text = """
module @m {
  func.func public @main(%arg0: tensor<2x2x64xf32>) -> tensor<2x2x64xf32> {
    %0 = stablehlo.custom_call @Sharding(%arg0) {mhlo.sharding = "{devices=[1,1,8]<=[8]}"} : (tensor<2x2x64xf32>) -> tensor<2x2x64xf32>
    %1 = "stablehlo.negate"(%0) : (tensor<2x2x64xf32>) -> tensor<2x2x64xf32>
    %2 = "stablehlo.sort"(%1) <{dimension = 2 : i64, is_stable = false}> ({
    ^bb0(%arg1: tensor<f32>, %arg2: tensor<f32>):
      %3 = stablehlo.compare LT, %arg1, %arg2 : (tensor<f32>, tensor<f32>) -> tensor<i1>
      stablehlo.return %3 : tensor<i1>
    }) : (tensor<2x2x64xf32>) -> tensor<2x2x64xf32>
    return %2 : tensor<2x2x64xf32>
  }
}
"""
        snap = IRSnapshot.from_text("generic_form", text)
        hazards = snap.sharded_sort_hazards()
        assert hazards and hazards[0].dimension == 2

    def test_hazard_present_in_both_does_not_refire(self, mesh):
        from jax.sharding import NamedSharding, PartitionSpec as P

        mfn = self._metric()

        def bad_eval(scores, y, vw):
            s = jax.lax.with_sharding_constraint(
                scores, NamedSharding(mesh, P(None, None, "data")))
            return jax.vmap(
                lambda ps: jax.vmap(lambda p, w: mfn(p, y, w))(ps, vw))(s)

        snap = _snap_of(bad_eval, _spec(2, 2, 64), _spec(64), _spec(2, 64))
        # golden already carries the (accepted/baselined) hazard: no TM705
        assert "TM705" not in [d.code for d in diff_snapshots(snap, snap)]


# ---------------------------------------------------------------------------
# corpus: build, persist, and the acceptance criterion
# ---------------------------------------------------------------------------

class TestCorpus:
    @pytest.fixture(scope="class")
    def corpus(self):
        with measure_compiles() as c:
            snaps, skipped = build_corpus()
        return snaps, skipped, c.backend_compiles

    def test_snapshot_all_families_zero_compiles(self, corpus):
        """Acceptance criterion: snapshot + diff of ALL program families at
        zero backend compiles."""
        snaps, _skipped, compiles = corpus
        assert compiles == 0, \
            "IR corpus snapshot must lower abstractly (no backend compile)"
        keys = set(snaps)
        # every family the framework emits is covered
        for expected in (
                "models.logistic.irls_sweep", "models.logistic.fista_sweep",
                "models.linear.ridge_sweep", "models.svm.svc_cv_program",
                "models.trees.gbt_cv_program",
                "models.trees.forest_cv_program",
                "models.base.eval_linear_sweep",
                "models.base.eval_softmax_sweep",
                "workflow.plan.transform_prefix",
                "serve.plan.scoring_prefix"):
            assert expected in keys, f"missing corpus family {expected}"
        for snap in snaps.values():
            assert snap.op_counts and snap.dtype_counts
            assert snap.ir_fingerprint == ir_fingerprint(snap.text)
            assert snap.content_fingerprint

    def test_diff_against_checked_in_goldens_is_clean(self, corpus):
        """The checked-in corpus matches the live lowering exactly — the
        test that makes every kernel edit / jax bump produce a reviewable
        diff instead of a silent behavior change.  (Diffing is also part of
        the zero-compile criterion: features derive from text only.)"""
        if jax.default_backend() != "cpu":
            pytest.skip("golden corpus is the CPU lowering")
        snaps, skipped, _ = corpus
        goldens, index = load_corpus(default_goldens_dir())
        assert index["version"] == irsnap.CORPUS_VERSION
        with measure_compiles() as c:
            diags = diff_corpus(goldens, snaps, skipped=skipped)
        assert c.backend_compiles == 0
        assert diags == [], (
            "IR corpus drifted from tests/goldens/ir — review the diff "
            "classes above, then re-golden with "
            "`cli lint --ir --update-goldens`:\n"
            + "\n".join(d.pretty() for d in diags))

    def test_corpus_roundtrips_through_disk(self, corpus, tmp_path):
        snaps, _skipped, _ = corpus
        save_corpus(snaps, str(tmp_path))
        loaded, index = load_corpus(str(tmp_path))
        assert set(loaded) == set(snaps)
        for key, snap in snaps.items():
            assert loaded[key].ir_fingerprint == snap.ir_fingerprint
            assert loaded[key].op_counts == snap.op_counts
            assert loaded[key].sorts == snap.sorts
            assert index["entries"][key]["irFingerprint"] == \
                snap.ir_fingerprint
        assert diff_corpus(loaded, snaps) == []

    def test_save_corpus_drops_stale_files(self, corpus, tmp_path):
        snaps, _skipped, _ = corpus
        stale = tmp_path / "gone.family.stablehlo.txt"
        stale.write_text("module @m {\n}\n")
        save_corpus(snaps, str(tmp_path))
        assert not stale.exists()

    def test_family_filter(self):
        snaps, skipped = build_corpus(families=["models.linear"])
        assert list(snaps) == ["models.linear.ridge_sweep"]
        assert "models.trees.gbt_cv_program" in skipped

    def test_content_fingerprints_match_executable_cache_keys(self, corpus):
        """Corpus entries are keyed alongside the run_cached content
        fingerprints, so BENCH/cache records correlate with the exact IR."""
        from transmogrifai_tpu.models.linear import _ridge_sweep
        from transmogrifai_tpu.perf.programs import cache_key_fingerprint

        snaps, _skipped, _ = corpus
        n, d, k, g = 64, 4, 2, 2
        expected = cache_key_fingerprint(
            _ridge_sweep, _spec(n, d + 1), _spec(n), _spec(k, n), _spec(g),
            statics=dict(has_intercept=True))
        assert snaps["models.linear.ridge_sweep"].content_fingerprint \
            == expected


# ---------------------------------------------------------------------------
# gates: ir_gate + static_gate exit-code contracts
# ---------------------------------------------------------------------------

def _run(cmd, **kw):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=8"}
    return subprocess.run(cmd, capture_output=True, text=True, env=env,
                          cwd=REPO_ROOT, **kw)


class TestIrGate:
    """rc contract on a tampered corpus copy: flips on injected TM704/TM705,
    stays green on TM701 text drift (acceptance criterion).  Runs the real
    subprocess pipeline, restricted to one cheap family per invocation."""

    def _gate(self, goldens_dir, *extra):
        return _run([sys.executable, "tools/ir_gate.py", "--baseline",
                     os.path.join(goldens_dir, "_baseline.json"), "--",
                     "--goldens", goldens_dir,
                     "--ir-family", "models.linear", *extra])

    @pytest.fixture()
    def goldens_copy(self, tmp_path):
        import shutil

        dst = tmp_path / "ir"
        shutil.copytree(default_goldens_dir(), dst)
        return str(dst)

    def test_green_on_clean_corpus(self, goldens_copy):
        r = self._gate(goldens_copy)
        assert r.returncode == 0, r.stdout + r.stderr
        assert "OK" in r.stdout

    def test_rc_flips_on_injected_tm704(self, goldens_copy):
        p = os.path.join(goldens_copy,
                         "models.linear.ridge_sweep.stablehlo.txt")
        with open(p) as fh:
            src = fh.read()
        with open(p, "w") as fh:
            fh.write(src.replace("xf32>", "xf64>"))
        r = self._gate(goldens_copy)
        assert r.returncode == 1, r.stdout + r.stderr
        assert "TM704" in r.stdout and "NEW error" in r.stdout

    def test_rc_stays_green_on_tm701_text_drift(self, goldens_copy):
        p = os.path.join(goldens_copy,
                         "models.linear.ridge_sweep.stablehlo.txt")
        with open(p) as fh:
            src = fh.read()
        assert 'jax.result_info = ""' in src
        with open(p, "w") as fh:
            fh.write(src.replace('jax.result_info = ""',
                                 'jax.result_info = "drifted"'))
        r = self._gate(goldens_copy)
        assert r.returncode == 0, r.stdout + r.stderr
        assert "TM701" in r.stdout and "never gates" in r.stdout

    @pytest.mark.slow
    def test_baselined_error_keeps_rc_zero(self, goldens_copy):
        p = os.path.join(goldens_copy,
                         "models.linear.ridge_sweep.stablehlo.txt")
        with open(p) as fh:
            src = fh.read()
        with open(p, "w") as fh:
            fh.write(src.replace("xf32>", "xf64>"))
        # record the error into the baseline, then the same delta is known
        r1 = _run([sys.executable, "tools/ir_gate.py", "--baseline",
                   os.path.join(goldens_copy, "_baseline.json"),
                   "--update-baseline", "--", "--goldens", goldens_copy,
                   "--ir-family", "models.linear"])
        assert r1.returncode == 0
        r2 = self._gate(goldens_copy)
        assert r2.returncode == 0, r2.stdout + r2.stderr
        assert "known error" in r2.stdout

    @pytest.mark.slow
    def test_missing_corpus_is_fatal_not_green(self, tmp_path):
        r = self._gate(str(tmp_path / "nowhere"))
        assert r.returncode != 0
        assert "refusing to report OK" in r.stderr + r.stdout

    def test_nonmatching_family_filter_is_fatal_not_green(self):
        """A typo'd --ir-family compares 0 families — the lint must refuse
        (and ir_gate's no-parseable-output guard turns that fatal) instead
        of validating nothing while reporting green."""
        r = _run([sys.executable, "-m", "transmogrifai_tpu.cli", "lint",
                  "--ir", "--ir-family", "models.liner"])  # typo
        assert r.returncode != 0
        assert "0 program families compared" in r.stderr + r.stdout
        g = _run([sys.executable, "tools/ir_gate.py", "--",
                  "--ir-family", "models.liner"])
        assert g.returncode != 0
        assert "refusing to report OK" in g.stderr + g.stdout


class TestStaticGate:
    """The merged entrypoint: green path and the new-error path, both
    halves (satellite: one CI entrypoint, one exit-code contract)."""

    def test_green_ir_only(self, tmp_path):
        import shutil

        dst = tmp_path / "ir"
        shutil.copytree(default_goldens_dir(), dst)
        r = _run([sys.executable, "tools/static_gate.py",
                  "--ir-baseline", str(tmp_path / "irb.json"),
                  "--goldens", str(dst)])
        assert r.returncode == 0, r.stdout + r.stderr
        assert "static_gate: OK" in r.stdout
        assert "lint_gate skipped" in r.stdout

    def test_new_error_in_either_half_flips_rc(self, tmp_path):
        import shutil

        # half 1: tampered IR corpus (TM705 injected by resharding a golden
        # sort's operand annotation would be synthetic; dtype flip = TM704)
        dst = tmp_path / "ir"
        shutil.copytree(default_goldens_dir(), dst)
        p = dst / "models.linear.ridge_sweep.stablehlo.txt"
        p.write_text(p.read_text().replace("xf32>", "xf64>"))
        r = _run([sys.executable, "tools/static_gate.py",
                  "--ir-baseline", str(tmp_path / "irb.json"),
                  "--goldens", str(dst)])
        assert r.returncode == 1
        assert "static_gate: FAIL" in r.stdout
        # half 2: a lint target with an error-severity finding
        bad = tmp_path / "bad.py"
        bad.write_text("def transform_columns(x):\n    retur x\n")  # syntax
        dst2 = tmp_path / "ir2"
        shutil.copytree(default_goldens_dir(), dst2)
        r2 = _run([sys.executable, "tools/static_gate.py",
                   "--ir-baseline", str(tmp_path / "irb2.json"),
                   "--lint-baseline", str(tmp_path / "lb.json"),
                   "--goldens", str(dst2), "--", "--path", str(bad)])
        assert r2.returncode == 1, r2.stdout + r2.stderr
        assert "lint_gate" in r2.stdout

    def test_skip_ir_without_lint_args_refuses(self):
        r = _run([sys.executable, "tools/static_gate.py", "--skip-ir"])
        assert r.returncode != 0
        assert "refusing" in r.stderr + r.stdout


class TestShadowPrefixFamily:
    def test_swap_candidate_dedups_to_scoring_prefix_golden(self):
        """ISSUE 9 satellite: the blue/green swap path's shadow-scoring
        prefix needs no separate golden family — a candidate built through
        the server's swap machinery for the corpus fixture model lowers to
        the EXACT canonical IR (and content fingerprint) already pinned as
        ``serve.plan.scoring_prefix``, so ``tools/ir_gate.py`` keeps the
        swap path covered for free."""
        from transmogrifai_tpu.checkers.irsnap import (
            _plan_fixture_runners,
            _Shim,
            default_goldens_dir,
            load_corpus,
            snapshot_scoring_plan,
        )
        from transmogrifai_tpu.serve import ScoringServer

        goldens, _index = load_corpus(default_goldens_dir())
        golden = goldens["serve.plan.scoring_prefix"]

        features, _runners = _plan_fixture_runners()
        shim = _Shim(features, {})
        with measure_compiles() as probe:
            with ScoringServer(shim, max_batch=64, min_bucket=8,
                               warm=False) as server:
                server.stage_candidate(shim, warm=False)
                active_fp = server.plan.fingerprint
                # reach the staged candidate's plan through the swapper
                server.promote(probation_batches=0)
                candidate_plan = server.plan
        # the swap shared the active plan's fingerprint (frozen prefix)...
        assert candidate_plan.fingerprint == active_fp
        snap = snapshot_scoring_plan(candidate_plan, bucket=64)
        # ...and the lowered program is bit-identical to the checked-in
        # golden: same canonical StableHLO text, same IR fingerprint (the
        # content fingerprint bakes in per-process stage uids, so identity
        # is asserted at the IR level — exactly what ir_gate diffs)
        assert snap.ir_fingerprint == golden.ir_fingerprint
        assert snap.text == golden.text
        assert probe.backend_compiles == 0  # lower-only, zero compiles
