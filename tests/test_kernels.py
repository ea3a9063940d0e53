"""Pallas fused tree/encode kernels + dispatch layer (ISSUE 10 tentpole).

Tier-1 discipline: every kernel runs here in ``pallas.interpret=True`` mode
(jittable emulation, no TPU required) and is pinned against the XLA
reference formulation — BITWISE on the exact-int8 histogram path and on the
encode kernels, identical split decisions on seeded growth fixtures, and
unchanged GBT/RF CV winners with kernels enabled vs ``TMOG_PALLAS=0``.
Device-compiled variants are ``slow``/TPU-gated at the bottom.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from transmogrifai_tpu.models import trees as T
from transmogrifai_tpu.perf.kernels import dispatch as KD
from transmogrifai_tpu.perf.kernels import encode as KE
from transmogrifai_tpu.perf.kernels import histogram as KH
from transmogrifai_tpu.perf.kernels import splitscan as KS


def _hist_fixture(seed=0, L=3, n=700, two_k=2, d=5, nn=4, n_bins=8):
    rng = np.random.default_rng(seed)
    B = n_bins + 1
    local = rng.integers(-1, nn, (L, n)).astype(np.int32)
    ghT = rng.integers(-3, 4, (L, two_k, n)).astype(np.int8)
    binned = rng.integers(0, B, (n, d)).astype(np.int32)
    return local, ghT, binned, nn, n_bins


def _np_exact_hist(local, ghT, binned, nn, n_bins):
    """Scatter-built exact integer reference — the mathematical ground truth
    every formulation (GEMM scan, Pallas) must reproduce bit-for-bit."""
    L, two_k, n = ghT.shape
    d = binned.shape[1]
    B = n_bins + 1
    ref = np.zeros((L, nn, two_k, B, d), np.int64)
    cols = np.arange(d)
    lanes, rows = np.nonzero(local >= 0)
    for l, i in zip(lanes, rows):
        for c in range(two_k):
            ref[l, local[l, i], c, binned[i], cols] += int(ghT[l, c, i])
    return ref.reshape(L * nn * two_k, B * d).astype(np.int32)


# ---------------------------------------------------------------------------
# dispatch layer
# ---------------------------------------------------------------------------

class TestDispatch:
    def test_default_mode_tracks_backend(self, monkeypatch):
        monkeypatch.delenv("TMOG_PALLAS", raising=False)
        expected = "pallas" if jax.default_backend() == "tpu" else "xla"
        assert KD.kernel_mode() == expected

    def test_auto_keeps_xla_under_a_multi_device_mesh(self, monkeypatch):
        """jax refuses to lower a Mosaic kernel into a multi-device program
        ("cannot be automatically partitioned"), so on a TPU ``auto`` must
        not select one under ``use_mesh`` — by decision, and counted — while
        an explicit TMOG_PALLAS=pallas still reaches the kernel."""
        from transmogrifai_tpu.parallel.mesh import make_mesh, use_mesh

        monkeypatch.delenv("TMOG_PALLAS", raising=False)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert KD.kernel_mode() == "pallas"
        with use_mesh(make_mesh(n_data=1, n_model=1,
                                devices=jax.devices()[:1])):
            assert KD.kernel_mode() == "pallas"      # one device: no SPMD
        with use_mesh(make_mesh(n_data=2, n_model=2,
                                devices=jax.devices()[:4])):
            assert KD.kernel_mode() == "xla"
            assert KD.cache_token() == "kernels:xla"
            before = KD.kernel_selections().get("split:xla", 0)
            assert KD.split_mode(1 << 16) is None
            assert KD.kernel_selections()["split:xla"] == before + 1
            monkeypatch.setenv("TMOG_PALLAS", "pallas")
            assert KD.kernel_mode() == "pallas"

    def test_escape_hatch_and_interpret_env(self, monkeypatch):
        monkeypatch.setenv("TMOG_PALLAS", "0")
        assert KD.kernel_mode() == "xla"
        monkeypatch.setenv("TMOG_PALLAS", "interpret")
        assert KD.kernel_mode() == "interpret"
        monkeypatch.setenv("TMOG_PALLAS", "pallas")
        assert KD.kernel_mode() == "pallas"

    def test_force_context_nests_and_restores(self):
        before = KD.kernel_mode()
        with KD.force_kernel_mode("interpret"):
            assert KD.kernel_mode() == "interpret"
            with KD.force_kernel_mode("xla"):
                assert KD.kernel_mode() == "xla"
            assert KD.kernel_mode() == "interpret"
        assert KD.kernel_mode() == before

    def test_cache_token_distinct_per_mode(self):
        tokens = set()
        for mode in ("xla", "pallas", "interpret"):
            with KD.force_kernel_mode(mode):
                tokens.add(KD.cache_token())
        assert len(tokens) == 3

    def test_vmem_admission_falls_back_to_xla(self):
        with KD.force_kernel_mode("pallas"):
            # tiny working set: admitted
            assert KD.hist_mode(16, 64, 128, 64) == "pallas"
            # absurd working set: compiled mode refuses, XLA path serves
            assert KD.hist_mode(1 << 20, 1 << 16, 2048, 1024) is None
        with KD.force_kernel_mode("interpret"):
            # emulation has no VMEM: always admitted
            assert KD.hist_mode(1 << 20, 1 << 16, 2048, 1024) == "interpret"

    def test_run_cached_key_carries_kernel_choice(self):
        """Acceptance: kernel choice is part of the run_cached key — no
        stale-executable aliasing across dispatch modes."""
        from transmogrifai_tpu.perf import cache_key_fingerprint

        x = np.ones((8, 4), np.float32)
        fps = {}
        for mode in ("xla", "interpret"):
            with KD.force_kernel_mode(mode):
                fps[mode] = cache_key_fingerprint(
                    T._fit_forest, x, statics=dict(max_depth=2))
        assert fps["xla"] != fps["interpret"]

    def test_plan_fingerprint_carries_kernel_choice(self):
        """Acceptance: plan content fingerprints key on the dispatch mode."""
        from transmogrifai_tpu.ops.numeric import BinaryVectorizer
        from transmogrifai_tpu.workflow.plan import stage_content_fingerprint

        fps = {}
        for mode in ("xla", "interpret"):
            with KD.force_kernel_mode(mode):
                fps[mode] = stage_content_fingerprint([BinaryVectorizer()])
        assert fps["xla"] != fps["interpret"]

    def test_provenance_reports_bound_knobs(self, monkeypatch):
        # provenance reports the values BOUND into models/trees.py and
        # dispatch — the ones traced programs actually used, incl. test
        # monkeypatches
        monkeypatch.setattr(T, "_HIST_CHUNK", 512)
        monkeypatch.setattr(KD, "_VMEM_BUDGET", 2097152)
        prov = KD.kernel_provenance()
        assert prov["hist_chunk"] == 512
        assert prov["pallas_vmem_budget"] == 2097152
        assert prov["kernel_mode"] in ("xla", "pallas", "interpret")
        assert set(prov) == {"kernel_mode", "tmog_pallas", "hist_chunk",
                             "pallas_vmem_budget", "serve_donation",
                             "selected"}

    def test_cache_token_carries_vmem_budget_in_pallas_mode(self, monkeypatch):
        # the budget decides which call sites trace the kernel vs the XLA
        # fallback, so two budgets must be two program families
        with KD.force_kernel_mode("pallas"):
            t1 = KD.cache_token()
            monkeypatch.setattr(KD, "_VMEM_BUDGET", 2097152)
            t2 = KD.cache_token()
        assert t1 != t2
        with KD.force_kernel_mode("xla"):
            monkeypatch.setattr(KD, "_VMEM_BUDGET", 4194304)
            t3 = KD.cache_token()
            monkeypatch.undo()
            # budget is irrelevant off the compiled path: token stable
            assert KD.cache_token() == t3

    @pytest.mark.parametrize("mode,donate,token", [
        ("xla", False, "kernels:xla"),
        ("interpret", False, "kernels:interpret"),
        ("pallas", False, "kernels:pallas:vmem=10485760"),
        ("xla", True, "kernels:xla:serve-donate"),
        ("interpret", True, "kernels:interpret:serve-donate"),
        ("pallas", True, "kernels:pallas:vmem=10485760:serve-donate")])
    def test_cache_token_literal_strings(self, mode, donate, token):
        """The token every program key, plan fingerprint and deploy
        manifest carries: the kernel mode (with the VMEM budget where
        compiled Pallas runs) and the serve-donation choice, nothing else."""
        with KD.force_kernel_mode(mode), KD.force_serve_donation(donate):
            assert KD.cache_token() == token


# ---------------------------------------------------------------------------
# one schedule per kernel: a module constant, chosen by shape alone
# ---------------------------------------------------------------------------

_S = jax.ShapeDtypeStruct


def _schedules():
    """What the kernels resolve, as jaxpr text (a Pallas call's jaxpr
    carries its grid and block shapes; the XLA scan's its chunk and unroll),
    with every schedule argument left to the kernel; the cache token in
    every mode; and the VMEM budget with what it decides."""
    from functools import partial

    from transmogrifai_tpu.perf.kernels import routing as KR

    i32, f32 = jnp.int32, jnp.float32
    hist = (_S((2, 4097), i32), _S((2, 2, 4097), jnp.int8),
            _S((4097, 16), i32))
    split = ([_S((3, 8, 1, 16, 9), f32)] * 2 + [_S((3, 8, 1), f32)] * 2
             + [_S((3, 16), f32)] + [_S((), f32)] * 4)
    level = (_S((4097, 16), i32), _S((3, 4), i32), _S((3, 4097), i32),
             _S((3, 4097), i32))
    programs = {
        "hist_pallas": (partial(KH.hist_level_pallas, nn=8, n_bins=8,
                                int_exact=True, interpret=True), hist),
        "hist_xla": (partial(KH.hist_level_xla, nn=8, n_bins=8,
                             int_exact=True), hist),
        "split_pallas": (lambda *a: KS.split_scan_pallas(
            *a[:5], 8, *a[5:], interpret=True), split),
        "onehot": (lambda c: KE.onehot_codes(c, 16, interpret=True),
                   (_S((4097,), i32),)),
        "bucketize": (lambda x, e: KE.bucketize_right_encode(
            x, e, True, True, interpret=True),
            (_S((4097,), f32), _S((6,), f32))),
        "route": (partial(KR.level_select_lanes, n_bins=8, chunk=2048),
                  level),
    }
    out = {name: str(jax.make_jaxpr(fn)(*specs))
           for name, (fn, specs) in programs.items()}
    for mode in ("xla", "interpret", "pallas"):
        with KD.force_kernel_mode(mode):
            out[f"token:{mode}"] = KD.cache_token()
    out["vmem"] = (KD.vmem_budget(), KD.descent_tile(128),
                   KD.kernel_provenance()["hist_chunk"])
    return out


@pytest.fixture(scope="module")
def default_schedules():
    return _schedules()


class TestOneSchedule:
    @pytest.mark.parametrize("name,value", [
        ("TMOG_AUTOTUNE", "1"), ("TMOG_AUTOTUNE_DIR", None),
        ("TMOG_HIST_CHUNK", "256"), ("TMOG_HIST_UNROLL", "4"),
        ("TMOG_ENCODE_BLOCK", "256"), ("TMOG_ROUTE_BLOCK", "128"),
        ("TMOG_SPLIT_LANE_BLOCK", "2"),
        ("TMOG_PALLAS_VMEM_BUDGET", "2097152")])
    def test_removed_variable_moves_neither_token_nor_schedule(
            self, name, value, default_schedules, monkeypatch, tmp_path):
        """None of these variables is read: setting one changes neither
        ``cache_token()`` nor any schedule a kernel resolves."""
        monkeypatch.setenv(name, value or str(tmp_path))
        assert _schedules() == default_schedules


# ---------------------------------------------------------------------------
# each kernel against its XLA twin, in interpret mode, at the default
# schedule: 16 features, 8 bins, 8 nodes, one class
# ---------------------------------------------------------------------------

class TestKernelTwinParity:
    @pytest.mark.parametrize("int_exact", [True, False],
                             ids=["int8", "float"])
    @pytest.mark.parametrize("rows", [4096, 4097])
    def test_hist_kernel_matches_twin(self, rows, int_exact):
        local, ghT, binned, nn, n_bins = _hist_fixture(
            seed=rows, L=2, n=rows, d=16, nn=8)
        if not int_exact:
            ghT = np.random.default_rng(rows).normal(
                size=ghT.shape).astype(np.float32)
        args = (jnp.asarray(local), jnp.asarray(ghT), jnp.asarray(binned),
                nn, n_bins)
        hp = np.asarray(KH.hist_level_pallas(*args, int_exact=int_exact,
                                             interpret=True))
        hx = np.asarray(KH.hist_level_xla(*args, int_exact=int_exact))
        np.testing.assert_array_equal(hp, hx)
        if int_exact:
            np.testing.assert_array_equal(
                hp, _np_exact_hist(local, ghT, binned, nn, n_bins))

    @pytest.mark.parametrize("lanes", [1, 3, 4])
    def test_split_kernel_matches_twin(self, lanes):
        """On histograms the grower could have built: every feature's bins
        sum to the node totals."""
        rng = np.random.default_rng(lanes)
        nn, d, n_bins = 8, 16, 8
        B = n_bins + 1
        cells = (lanes, nn, 1, d)

        def spread(totals):
            return rng.multinomial(
                np.broadcast_to(totals[..., None], cells), np.full(B, 1 / B))

        pos, neg = (rng.integers(0, 120, (lanes, nn, 1)) for _ in range(2))
        tot_h = rng.integers(B, 8 * B, (lanes, nn, 1))
        hists = [jnp.asarray(h.astype(np.float32))
                 for h in (spread(pos) - spread(neg), spread(tot_h),
                           pos - neg, tot_h)]
        mask = jnp.ones((lanes, d), jnp.float32)
        params = [jnp.float32(v) for v in (1.0, 0.5, 0.1, 1.0)]
        got = KS.split_scan_pallas(*hists, mask, n_bins, *params,
                                   interpret=True)
        want = KS.split_scan_xla(*hists, mask, n_bins, *params)
        for g, w in zip(got, want):
            assert g.shape == (lanes, nn)
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))

    @pytest.mark.parametrize("rows", [1, 1025, 4097])
    def test_encode_kernel_matches_twin(self, rows):
        width = 16
        codes = jnp.asarray(np.random.default_rng(rows).integers(
            -1, width + 1, rows).astype(np.int32))
        got = np.asarray(KE.onehot_codes(codes, width, interpret=True))
        np.testing.assert_array_equal(
            got, np.asarray(jax.nn.one_hot(codes, width, dtype=jnp.float32)))


# ---------------------------------------------------------------------------
# histogram kernel parity (acceptance: bitwise vs the exact-int8 GEMM path)
# ---------------------------------------------------------------------------

class TestHistogramParity:
    def test_int8_exact_bitwise_all_paths(self):
        local, ghT, binned, nn, n_bins = _hist_fixture()
        ref = _np_exact_hist(local, ghT, binned, nn, n_bins)
        args = (jnp.asarray(local), jnp.asarray(ghT), jnp.asarray(binned),
                nn, n_bins)
        hx = np.asarray(KH.hist_level_xla(*args, int_exact=True, chunk=128))
        hp = np.asarray(KH.hist_level_pallas(*args, int_exact=True,
                                             interpret=True, chunk=128))
        np.testing.assert_array_equal(hx, ref)
        np.testing.assert_array_equal(hp, ref)
        assert hp.dtype == np.int32

    def test_float_path_matches_reference(self):
        local, _ghT, binned, nn, n_bins = _hist_fixture(seed=2)
        rng = np.random.default_rng(3)
        ghT = rng.normal(size=(3, 2, 700)).astype(np.float32)
        args = (jnp.asarray(local), jnp.asarray(ghT), jnp.asarray(binned),
                nn, n_bins)
        hx = np.asarray(KH.hist_level_xla(*args, chunk=256))
        hp = np.asarray(KH.hist_level_pallas(*args, interpret=True,
                                             chunk=256))
        # same per-chunk dot + same sequential chunk-accumulation order
        np.testing.assert_array_equal(hx, hp)

    def test_unaligned_rows_pad_to_zero_contribution(self):
        # n deliberately prime: the kernel's internal zero-padding must be
        # invisible in the totals
        local, ghT, binned, nn, n_bins = _hist_fixture(seed=4, n=641)
        ref = _np_exact_hist(local, ghT, binned, nn, n_bins)
        hp = np.asarray(KH.hist_level_pallas(
            jnp.asarray(local), jnp.asarray(ghT), jnp.asarray(binned),
            nn, n_bins, int_exact=True, interpret=True, chunk=128))
        np.testing.assert_array_equal(hp, ref)


# ---------------------------------------------------------------------------
# split-scan kernel parity
# ---------------------------------------------------------------------------

class TestSplitScanParity:
    def _fixture(self, seed=5, L=3, nn=4, K=1, d=6, n_bins=8):
        rng = np.random.default_rng(seed)
        B = n_bins + 1
        hg = rng.integers(-20, 20, (L, nn, K, d, B)).astype(np.float32)
        hh = rng.integers(0, 30, (L, nn, K, d, B)).astype(np.float32)
        # per-node totals must be bin sums of one feature (trees contract)
        G = jnp.asarray(hg[:, :, :, 0, :].sum(-1))
        H = jnp.asarray(hh[:, :, :, 0, :].sum(-1))
        mask = np.ones((L, d), np.float32)
        mask[0, 2] = 0.0  # a colsample-masked feature must never win
        return (jnp.asarray(hg), jnp.asarray(hh), G, H, jnp.asarray(mask),
                n_bins)

    def test_pallas_matches_xla_bitwise_on_integer_hists(self):
        args = self._fixture()
        params = (jnp.float32(1.0), jnp.float32(0.5), jnp.float32(0.1),
                  jnp.float32(1.0))
        bx, gx, mx = KS.split_scan_xla(*args, *params)
        bp, gp, mp = KS.split_scan_pallas(*args, *params, interpret=True)
        np.testing.assert_array_equal(np.asarray(bx), np.asarray(bp))
        np.testing.assert_array_equal(np.asarray(gx), np.asarray(gp))
        np.testing.assert_array_equal(np.asarray(mx), np.asarray(mp))
        assert np.asarray(mp).dtype == bool

    def test_masked_feature_never_selected(self):
        args = self._fixture()
        params = (jnp.float32(1.0), jnp.float32(0.0), jnp.float32(0.0),
                  jnp.float32(1.0))
        bp, _gp, _mp = KS.split_scan_pallas(*args, *params, interpret=True)
        n_bins = args[-1]
        feat = np.asarray(bp)[0] // (n_bins - 1)
        assert not np.any(feat == 2)


# ---------------------------------------------------------------------------
# end-to-end growth + CV-winner parity (acceptance)
# ---------------------------------------------------------------------------

def _growth_fixture(seed=1, n=600, d=7, lanes=4):
    rng = np.random.default_rng(seed)
    n_bins = 8
    binned = jnp.asarray(rng.integers(0, n_bins + 1, (n, d)).astype(np.int32))
    y = (rng.random(n) < 0.5).astype(np.float32)
    boot = rng.poisson(1.0, (lanes, n)).astype(np.float32)
    grad = jnp.asarray(-boot[:, :, None] * y[None, :, None])
    hess = jnp.asarray(boot[:, :, None] * np.ones((1, 1, 1), np.float32))
    masks = jnp.asarray(np.ones((lanes, d), np.float32))
    return binned, grad, hess, masks, n_bins


class TestGrowthParity:
    @pytest.mark.parametrize("int_exact", [True, False])
    def test_grow_trees_bitwise_across_modes(self, int_exact):
        """The full level-wise grower — histogram kernel + split-scan kernel
        + routing — produces the IDENTICAL Tree under interpret-mode Pallas
        and the XLA reference (split decisions and leaf values both)."""
        binned, grad, hess, masks, n_bins = _growth_fixture()

        def grow():
            return T._grow_trees(binned, grad, hess, masks,
                                 jax.random.PRNGKey(0), 3, n_bins,
                                 0.0, 0.0, 0.0, 1.0, 1.0, 0.0,
                                 int_exact=int_exact)

        with KD.force_kernel_mode("xla"):
            tx, nodex = grow()
        with KD.force_kernel_mode("interpret"):
            tp, nodep = grow()
        for name, a, b in zip(tx._fields, tx, tp):
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b),
                err_msg=f"Tree.{name} drifted across kernel dispatch modes")
        np.testing.assert_array_equal(np.asarray(nodex), np.asarray(nodep))

    def test_cv_winners_unchanged_gbt_and_rf(self):
        """Acceptance: GBT/RF CV winners are unchanged with kernels enabled
        vs TMOG_PALLAS=0, through the real run_cached sweep programs."""
        from transmogrifai_tpu.evaluators.base import (
            BinaryClassificationEvaluator,
        )
        from transmogrifai_tpu.models.trees import (
            GradientBoostedTreesClassifier,
            RandomForestClassifier,
        )
        from transmogrifai_tpu.models.tuning import CrossValidator

        rng = np.random.default_rng(7)
        n, d = 400, 6
        x = rng.normal(size=(n, d)).astype(np.float32)
        w = rng.normal(size=d)
        y = (rng.random(n) < 1 / (1 + np.exp(-(x @ w)))).astype(np.float64)
        ev = BinaryClassificationEvaluator("auPR")
        cv = CrossValidator(ev, num_folds=2, seed=3)
        tw, vw = cv.fold_weights(y, np.ones_like(y))
        metric = ev.metric_fn()
        fams = [
            (RandomForestClassifier(num_trees=4, max_depth=2),
             [{"max_depth": 2}, {"max_depth": 3}]),
            (GradientBoostedTreesClassifier(num_rounds=4, max_depth=2),
             [{"eta": 0.3}, {"eta": 0.1}]),
        ]
        results = {}
        for mode in ("xla", "interpret"):
            with KD.force_kernel_mode(mode):
                results[mode] = {
                    type(est).__name__: np.asarray(
                        est.cv_sweep(x, y, tw, vw, grids, metric))
                    for est, grids in fams}
        for fam, mx in results["xla"].items():
            mp = results["interpret"][fam]
            np.testing.assert_allclose(
                mp, mx, atol=1e-6, rtol=0,
                err_msg=f"{fam} CV metrics moved across dispatch modes")
            assert int(np.nanargmax(mx.mean(axis=-1))) == \
                int(np.nanargmax(mp.mean(axis=-1))), fam


# ---------------------------------------------------------------------------
# serving encode kernels (ops/onehot.py, ops/bucketizers.py, serve prefix)
# ---------------------------------------------------------------------------

class TestEncodeParity:
    def test_onehot_codes_bitwise(self):
        rng = np.random.default_rng(8)
        codes = jnp.asarray(rng.integers(-1, 9, 1500).astype(np.int32))
        got = np.asarray(KE.onehot_codes(codes, 9, interpret=True))
        ref = np.asarray(jax.nn.one_hot(codes, 9, dtype=jnp.float32))
        np.testing.assert_array_equal(got, ref)

    @pytest.mark.parametrize("track_nulls", [True, False])
    @pytest.mark.parametrize("track_invalid", [True, False])
    def test_bucketize_bitwise_incl_nan_inf(self, track_nulls, track_invalid):
        from transmogrifai_tpu.ops.bucketizers import device_bucketize_right

        rng = np.random.default_rng(9)
        x = rng.normal(size=1203).astype(np.float32)
        x[::7] = np.nan
        x[3] = np.inf
        x[11] = -np.inf
        x[20] = 0.1  # exactly on a split: ties must agree
        splits = jnp.asarray(
            np.array([-np.inf, -0.5, 0.1, 0.9, np.inf], np.float32))
        xd = jnp.asarray(x)
        with KD.force_kernel_mode("xla"):
            ref = np.asarray(device_bucketize_right(
                xd, splits, track_nulls, track_invalid))
        got = np.asarray(KE.bucketize_right_encode(
            xd, splits, track_nulls, track_invalid, interpret=True))
        np.testing.assert_array_equal(got, ref)
        with KD.force_kernel_mode("interpret"):
            via_dispatch = np.asarray(device_bucketize_right(
                xd, splits, track_nulls, track_invalid))
        np.testing.assert_array_equal(via_dispatch, ref)

    def test_onehot_stage_dispatch_parity(self):
        """OneHotVectorizerModel.device_transform routes through the encode
        kernel under interpret mode and matches the XLA path bitwise."""
        from transmogrifai_tpu.ops.onehot import OneHotVectorizerModel

        model = OneHotVectorizerModel(vocabs=[["a", "b", "c"]],
                                      track_nulls=True)
        rng = np.random.default_rng(10)
        codes = jnp.asarray(rng.integers(0, 5, 900).astype(np.int32))
        with KD.force_kernel_mode("xla"):
            ref = np.asarray(model.device_transform(codes))
        with KD.force_kernel_mode("interpret"):
            got = np.asarray(model.device_transform(codes))
        np.testing.assert_array_equal(got, ref)

    def test_scoring_plan_parity_across_modes(self):
        """A CompiledScoringPlan built per mode: distinct fingerprints (no
        executable aliasing), bitwise-equal scores."""
        from transmogrifai_tpu.checkers.irsnap import (
            _plan_fixture_runners,
            _Shim,
        )
        from transmogrifai_tpu.serve.plan import CompiledScoringPlan

        records = [{"x1": 0.25, "x2": None, "b1": i % 2 == 0}
                   for i in range(9)]
        outs = {}
        fps = {}
        for mode in ("xla", "interpret"):
            with KD.force_kernel_mode(mode):
                features, _ = _plan_fixture_runners()
                plan = CompiledScoringPlan(_Shim(features, {}), min_bucket=8,
                                           max_bucket=16, strict=False)
                fps[mode] = plan.fingerprint
                # output names carry per-build stage uids: compare VALUES
                outs[mode] = [[row[k] for k in sorted(row)]
                              for row in plan.score(records)]
        assert fps["xla"] != fps["interpret"]
        assert outs["xla"] == outs["interpret"]


# ---------------------------------------------------------------------------
# IR corpus integration (satellite: kernel program families pinned)
# ---------------------------------------------------------------------------

class TestKernelIrFamilies:
    def test_custom_call_counted_by_target_name(self):
        """Op histograms must count Pallas custom_calls by call_target_name
        in BOTH MLIR printer forms, not lump them as one opaque op."""
        from transmogrifai_tpu.checkers.irsnap import _op_histogram

        pretty = ('%v1 = stablehlo.custom_call @tpu_custom_call(%v0) '
                  '{backend_config = "x"} : (tensor<8xf32>) -> tensor<8xf32>')
        generic = ('%v1 = "stablehlo.custom_call"(%v0) <{api_version = 1 : '
                   'i32, call_target_name = "tpu_custom_call"}> : '
                   '(tensor<8xf32>) -> tensor<8xf32>')
        for text in (pretty, generic):
            counts = _op_histogram(text)
            assert counts.get("custom_call@tpu_custom_call") == 1, \
                (text, counts)

    def test_mosaic_payload_elided_from_canonical_text(self):
        """The serialized Mosaic module inside backend_config is not stable
        across processes; canonicalization must elide it so the kernel
        families golden deterministically."""
        from transmogrifai_tpu.checkers.irsnap import canonicalize_stablehlo

        payload = "TUzvUgFNTElS" * 40
        a = canonicalize_stablehlo(
            f'module @m {{\n  %0 = stablehlo.custom_call @tpu_custom_call'
            f'(%arg0) {{backend_config = "{payload}AAA"}} : '
            f'(tensor<8xf32>) -> tensor<8xf32>\n}}\n')
        b = canonicalize_stablehlo(
            f'module @m {{\n  %0 = stablehlo.custom_call @tpu_custom_call'
            f'(%arg0) {{backend_config = "{payload}BBB"}} : '
            f'(tensor<8xf32>) -> tensor<8xf32>\n}}\n')
        assert a == b
        assert "TUzvUg" not in a

    def test_kernel_families_lower_at_zero_compiles(self):
        from transmogrifai_tpu.checkers.irsnap import build_corpus
        from transmogrifai_tpu.perf import measure_compiles

        with measure_compiles() as c:
            snaps, _skipped = build_corpus(families=["perf.kernels"])
        assert c.backend_compiles == 0
        assert "perf.kernels.hist@interpret" in snaps
        assert "perf.kernels.split_scan@interpret" in snaps
        assert "perf.kernels.encode@interpret" in snaps
        tpu = snaps.get("perf.kernels.hist@tpu")
        if tpu is not None:  # cross-lowering available in this jax build
            assert tpu.op_counts.get("custom_call@tpu_custom_call", 0) >= 1


# ---------------------------------------------------------------------------
# device-compiled variants — TPU-gated
# ---------------------------------------------------------------------------

class TestRoutingParity:
    """The row select of the tree walk (perf/kernels/routing.py, ISSUE 15
    satellite; the level's-columns form since PR 37) must be BITWISE
    identical across the XLA compare-reduce, the level's-columns matmul and
    the walk's entry, in every mode — routing decides which child every row
    takes, so a single off-by-one moves rows between leaves."""

    def _fixture(self, seed=0, n=700, d=9, L=4, n_bins=8):
        rng = np.random.default_rng(seed)
        binned = rng.integers(0, n_bins + 1, (n, d)).astype(np.int32)
        idx = rng.integers(0, d, (L, n)).astype(np.int32)
        return binned, idx

    def _level(self, seed=0, n=700, d=9, L=4, nn=4, n_bins=8):
        """One level of a walk: the level's split columns ``feat`` (L, nn),
        each row's node in the level ``local`` (a fifth of the rows at none:
        -1 down to -nn), the row's own column ``idx`` and its code."""
        rng = np.random.default_rng(seed)
        binned = rng.integers(0, n_bins + 1, (n, d)).astype(np.int32)
        feat = rng.integers(0, d, (L, nn)).astype(np.int32)
        local = rng.integers(0, nn, (L, n)).astype(np.int32)
        local = np.where(rng.random((L, n)) < 0.2,
                         -1 - rng.integers(0, nn, (L, n)), local
                         ).astype(np.int32)
        idx = np.take_along_axis(feat, np.clip(local, 0, nn - 1), axis=1)
        truth = binned[np.arange(n)[None, :], idx]
        return binned, feat, local, idx, truth

    def _walk_entry(self, binned, feat, local, idx):
        """``level_select_lanes`` in interpret mode, on the rows at a node
        of the level (the others' value is the caller's to discard)."""
        from transmogrifai_tpu.perf.kernels import routing as KR

        with KD.force_kernel_mode("interpret"):
            out = np.asarray(KR.level_select_lanes(
                jnp.asarray(binned), jnp.asarray(feat), jnp.asarray(local),
                jnp.asarray(idx), 8, 2048))
        return out[local >= 0]

    def test_interpret_kernel_bitwise_vs_xla_and_ground_truth(self):
        """The compare-reduce and the walk's entry, in the mode that once
        emulated a routing kernel, against ground truth."""
        from transmogrifai_tpu.perf.kernels import routing as KR

        binned, idx = self._fixture()
        truth = np.stack([binned[np.arange(binned.shape[0]), idx[l]]
                          for l in range(idx.shape[0])])
        ref = np.asarray(KR.row_select_lanes_xla(jnp.asarray(binned),
                                                 jnp.asarray(idx)))
        np.testing.assert_array_equal(ref, truth)
        for nn in (4, 16):     # the level's columns, then the compare-reduce
            binned, feat, local, idx, truth = self._level(seed=nn, nn=nn)
            np.testing.assert_array_equal(
                self._walk_entry(binned, feat, local, idx),
                truth[local >= 0])

    def test_unaligned_rows_and_single_lane(self):
        from transmogrifai_tpu.perf.kernels import routing as KR

        for n, d, L in ((257, 3, 1), (100, 12, 7), (513, 5, 2)):
            binned, idx = self._fixture(seed=n, n=n, d=d, L=L)
            truth = np.stack([binned[np.arange(n), idx[l]]
                              for l in range(L)])
            ref = np.asarray(KR.row_select_lanes_xla(jnp.asarray(binned),
                                                     jnp.asarray(idx)))
            np.testing.assert_array_equal(ref, truth)
            binned, feat, local, idx, truth = self._level(
                seed=n, n=n, d=d, L=L, nn=4)
            np.testing.assert_array_equal(
                self._walk_entry(binned, feat, local, idx),
                truth[local >= 0])

    def test_dispatcher_honors_mode_and_trees_alias(self):
        """The walk's entry is XLA's own code in EVERY mode (PR 33), in
        either of its forms (a level of 4 nodes over 9 columns takes the
        level's columns, one of 16 nodes the compare-reduce), and says so:
        one ``route:xla`` a call, never a kernel selection."""
        from transmogrifai_tpu.perf.kernels import routing as KR

        for nn in (4, 16):
            binned, feat, local, idx, truth = self._level(seed=3, nn=nn)
            live = local >= 0
            for mode in ("xla", "interpret", "pallas"):
                before = KD.kernel_selections()
                with KD.force_kernel_mode(mode):
                    out = np.asarray(KR.level_select_lanes(
                        jnp.asarray(binned), jnp.asarray(feat),
                        jnp.asarray(local), jnp.asarray(idx), 8, 2048))
                np.testing.assert_array_equal(out[live], truth[live],
                                              err_msg=mode)
                moved = {k: v - before.get(k, 0)
                         for k, v in KD.kernel_selections().items()
                         if k.startswith("route:") and v != before.get(k, 0)}
                assert moved == {"route:xla": 1}, (mode, moved)
        # the walk step of grower and predictor goes through the ONE entry
        binned, feat, local, idx, _ = self._level(seed=4, nn=4)
        before = KD.kernel_selections().get("route:xla", 0)
        flag = jnp.zeros(feat.shape, bool)
        T._route_level(jnp.asarray(binned), jnp.asarray(feat),
                       jnp.asarray(feat), flag, flag,
                       jnp.asarray(local + 3), 3, 8)
        assert KD.kernel_selections()["route:xla"] == before + 1

    @pytest.mark.parametrize("n,d,L,nn,n_bins,whole_max", [
        (700, 9, 4, 1, 8, None), (700, 9, 4, 2, 8, None),
        (700, 9, 4, 8, 8, None), (257, 128, 1, 32, 32, None),
        (513, 300, 3, 64, 255, None), (100, 12, 7, 4, 300, None),
        # C would pass the cap: rows in chunks of 128, 700 padded to 768
        (700, 9, 4, 8, 8, 0), (768, 128, 3, 32, 32, 0),
        (130, 16, 150, 8, 32, 0)])
    def test_level_columns_select_bitwise_vs_compare_reduce(
            self, n, d, L, nn, n_bins, whole_max, monkeypatch):
        """The level's columns gathered by a one-hot matmul, the row's own
        picked among them: equal to ``binned[i, feat[l, local[l, i]]]`` and
        to the compare-reduce on every row at a node of the level, 0 on the
        rows at none; whole and in row chunks alike."""
        from transmogrifai_tpu.perf.kernels import routing as KR

        if whole_max is not None:
            monkeypatch.setattr(KR, "_COLUMNS_WHOLE_MAX_BYTES", whole_max)
        binned, feat, local, idx, truth = self._level(
            seed=n + nn, n=n, d=d, L=L, nn=nn, n_bins=n_bins)
        live = local >= 0
        got = np.asarray(KR.level_columns_select_xla(
            jnp.asarray(binned), jnp.asarray(feat), jnp.asarray(local),
            n_bins, 128))
        ref = np.asarray(KR.row_select_lanes_xla(jnp.asarray(binned),
                                                 jnp.asarray(idx)))
        assert got.shape == (L, n) and got.dtype == np.int32
        np.testing.assert_array_equal(got[live], truth[live])
        np.testing.assert_array_equal(got[live], ref[live])
        assert not got[~live].any()

    @pytest.mark.parametrize("backend,n_bins,want", [
        ("cpu", 32, "float32"), ("cpu", 300, "float32"),
        ("tpu", 32, "bfloat16"), ("tpu", 255, "bfloat16"),
        ("tpu", 256, "bfloat16"), ("tpu", 257, "float32"),
        ("tpu", 300, "float32"), ("tpu", 2 ** 24, "float32"),
        ("tpu", 2 ** 24 + 1, None), ("cpu", 2 ** 24 + 1, None)])
    def test_select_dtype_holds_every_code_exactly(self, backend, n_bins,
                                                   want, monkeypatch):
        """The matmul's operand dtype follows from ``n_bins`` at trace time:
        the narrowest the backend multiplies that holds every code."""
        from transmogrifai_tpu.perf.kernels import routing as KR

        monkeypatch.setattr(jax, "default_backend", lambda: backend)
        if want is None:
            with pytest.raises(AssertionError, match="exactly"):
                KR.select_dtype(n_bins)
            return
        dt = KR.select_dtype(n_bins)
        assert jnp.dtype(dt).name == want
        # the largest code, and its neighbour, come back whole
        top = np.array([n_bins, n_bins - 1], np.int32)
        np.testing.assert_array_equal(
            np.asarray(jnp.asarray(top).astype(dt).astype(jnp.int32)), top)

    @pytest.mark.parametrize("depth,d,want", [
        (3, 128, 7), (6, 128, 63), (8, 128, 127 + 128), (1, 128, 1),
        (6, 16, 15 + 2 * 16), (3, 300, 7), (12, 128, 127 + 5 * 128)])
    def test_select_cols_counts_the_form_each_level_takes(self, depth, d,
                                                         want):
        from transmogrifai_tpu.perf.kernels import routing as KR

        assert KR.select_cols(depth, d) == want

    def test_growth_bitwise_across_routing_modes(self):
        """End-to-end: tree growth must produce identical trees in
        interpret mode and on the XLA path — routing runs the same XLA code
        in both."""
        binned, grad, hess, masks, n_bins = _growth_fixture()

        def grow():
            return T._grow_trees(binned, grad, hess, masks,
                                 jax.random.PRNGKey(0), 3, n_bins,
                                 0.0, 0.0, 0.0, 1.0, 1.0, 0.0,
                                 int_exact=True)

        with KD.force_kernel_mode("xla"):
            tx, nx = grow()
        with KD.force_kernel_mode("interpret"):
            ti, ni = grow()
        for name, a, b in zip(tx._fields, tx, ti):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=name)
        np.testing.assert_array_equal(np.asarray(nx), np.asarray(ni))

    def test_vmem_admission_falls_back(self, monkeypatch):
        """With compiled Pallas forced, the walk's entry runs XLA's code at
        shapes any VMEM budget would admit or refuse alike."""
        from transmogrifai_tpu.perf.kernels import routing as KR

        monkeypatch.setenv("TMOG_PALLAS", "pallas")
        for d, L, nn in ((8, 2, 4), (8, 2, 8), (40, 130, 16), (40, 130, 64)):
            binned, feat, local, idx, truth = self._level(
                seed=d, n=64, d=d, L=L, nn=nn)
            out = np.asarray(KR.level_select_lanes(
                jnp.asarray(binned), jnp.asarray(feat), jnp.asarray(local),
                jnp.asarray(idx), 8, 2048))
            np.testing.assert_array_equal(out[local >= 0], truth[local >= 0])


@pytest.mark.slow
@pytest.mark.skipif(jax.default_backend() != "tpu",
                    reason="compiled Pallas kernels need a TPU backend")
class TestCompiledOnTpu:
    def test_compiled_hist_matches_exact_reference(self):
        local, ghT, binned, nn, n_bins = _hist_fixture()
        ref = _np_exact_hist(local, ghT, binned, nn, n_bins)
        hp = np.asarray(KH.hist_level_pallas(
            jnp.asarray(local), jnp.asarray(ghT), jnp.asarray(binned),
            nn, n_bins, int_exact=True, interpret=False, chunk=128))
        np.testing.assert_array_equal(hp, ref)

    def test_compiled_growth_matches_xla(self):
        binned, grad, hess, masks, n_bins = _growth_fixture()

        def grow():
            return T._grow_trees(binned, grad, hess, masks,
                                 jax.random.PRNGKey(0), 3, n_bins,
                                 0.0, 0.0, 0.0, 1.0, 1.0, 0.0,
                                 int_exact=True)

        with KD.force_kernel_mode("xla"):
            tx, _ = grow()
        with KD.force_kernel_mode("pallas"):
            tp, _ = grow()
        for name, a, b in zip(tx._fields, tx, tp):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=name)
