"""The squared-hinge step kernel (``perf/kernels/descent.py``) and its
dispatch: one read of the block a step where XLA takes two.

CPU only but for the TPU-gated case at the bottom: the kernel runs in
interpret mode, which proves its arithmetic, tiling and padding, never a
time.  The kernel sums in another order than XLA, so results agree to
float32 rounding, not bitwise.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from transmogrifai_tpu.models import svm
from transmogrifai_tpu.perf.kernels import descent as KDE
from transmogrifai_tpu.perf.kernels import dispatch as KD
from transmogrifai_tpu.perf.timers import record_phases

D = 128


def _block(n, has_intercept, seed=0, padded=0):
    """(x, y_pm, w, beta) as the refit sees them: standardised columns, the
    ones column last with an intercept, and ``padded`` trailing rows with
    weight 0 whose features are far from the rest."""
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, D)).astype(np.float32)
    if has_intercept:
        x = np.concatenate([x, np.ones((n, 1), np.float32)], axis=1)
    y = np.where(x[:, 0] - 0.5 * x[:, 1] + rng.normal(size=n) > 0, 1.0, -1.0
                 ).astype(np.float32)
    w = rng.integers(1, 3, size=n).astype(np.float32)
    if padded:
        x[-padded:, :D] = 1e3
        w[-padded:] = 0.0
    beta = (0.05 * rng.normal(size=x.shape[1])).astype(np.float32)
    return tuple(jnp.asarray(a) for a in (x, y, w, beta))


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def _kernel_grad(x, y, w, beta, has_intercept, tile_rows):
    block = KDE.hinge_block(x, y, w, tile_rows)
    return KDE.hinge_grad_pallas(block, beta, has_intercept, interpret=True)


@pytest.mark.parametrize("has_intercept", [True, False],
                         ids=["d1=129", "d1=128"])
@pytest.mark.parametrize("n", [1000, 4096, 8192])
def test_kernel_gradient_matches_xla(n, has_intercept):
    """At the dispatch's own tile (one grid step here) and at 1024 rows a
    step (several, the last one padded where ``n`` is not a multiple)."""
    x, y, w, beta = _block(n, has_intercept, padded=n // 10)
    ref = KDE.hinge_grad_xla(x, y, w, beta)
    for tile in (KD.descent_tile(D), 1024):
        got = _kernel_grad(x, y, w, beta, has_intercept, tile)
        assert got.shape == (x.shape[1],)
        assert _rel(got, ref) < 1e-6, tile


def test_padded_rows_contribute_exactly_nothing():
    """Rows of weight 0, the tile's zero padding among them, leave the
    gradient as the weighted rows alone give it."""
    x, y, w, beta = _block(3000, True, padded=1000)
    whole = _kernel_grad(x, y, w, beta, True, 1024)
    kept = _kernel_grad(x[:2000], y[:2000], w[:2000], beta, True, 1024)
    np.testing.assert_array_equal(np.asarray(whole), np.asarray(kept))


@pytest.mark.parametrize("d1,has_intercept", [(18, True), (17, False)])
def test_feature_counts_off_the_register_rows(d1, has_intercept):
    """A feature count that does not fill whole registers reads the block's
    rows as stored (the ones row included) and skips the ones row."""
    rng = np.random.default_rng(3)
    x = rng.normal(size=(2500, d1)).astype(np.float32)
    if has_intercept:
        x[:, -1] = 1.0
    y = np.where(rng.random(2500) > 0.5, 1.0, -1.0).astype(np.float32)
    w = rng.random(2500).astype(np.float32)
    beta = (0.1 * rng.normal(size=d1)).astype(np.float32)
    x, y, w, beta = (jnp.asarray(a) for a in (x, y, w, beta))
    got = _kernel_grad(x, y, w, beta, has_intercept, 1024)
    assert _rel(got, KDE.hinge_grad_xla(x, y, w, beta)) < 1e-6


@pytest.mark.parametrize("has_intercept", [True, False])
def test_svc_body_hundred_steps_match_the_xla_path(has_intercept):
    x, y, w, _ = _block(1000, has_intercept, seed=1, padded=24)
    reg = jnp.float32(0.01)
    ref = svm._svc_body(x, y, w, reg, 100, has_intercept)
    got = svm._svc_body(x, y, w, reg, 100, has_intercept,
                        descent="interpret")
    assert _rel(got, ref) < 1e-5
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5 * float(
                                   np.abs(np.asarray(ref)).max()))


class TestDescentMode:
    def test_auto_off_tpu_is_xla_and_counted(self, monkeypatch):
        monkeypatch.delenv("TMOG_PALLAS", raising=False)
        if jax.default_backend() == "tpu":
            pytest.skip("auto selects the kernel on a TPU")
        before = KD.kernel_selections().get("descent:xla", 0)
        assert KD.descent_mode(D) == "xla"
        assert KD.kernel_selections()["descent:xla"] == before + 1

    def test_env_modes(self, monkeypatch):
        monkeypatch.setenv("TMOG_PALLAS", "0")
        assert KD.descent_mode(D) == "xla"
        monkeypatch.setenv("TMOG_PALLAS", "interpret")
        before = KD.kernel_selections().get("descent:interpret", 0)
        assert KD.descent_mode(D) == "interpret"
        assert KD.kernel_selections()["descent:interpret"] == before + 1

    def test_pallas_on_a_tpu_off_the_mesh_where_the_tile_admits(
            self, monkeypatch):
        monkeypatch.delenv("TMOG_PALLAS", raising=False)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert KD.descent_tile(D) == 8192
        assert KD.descent_mode(D) == "pallas"
        # no tile of a 4096-column block fits the budget: XLA serves it
        assert KD.descent_tile(4096) == 1024
        assert KD.descent_mode(4096) == "xla"
        monkeypatch.setattr(KD, "_VMEM_BUDGET", 4 << 20)
        assert KD.descent_tile(D) == 2048
        assert KD.descent_mode(D) == "pallas"

    @pytest.mark.parametrize("mode", ["pallas", "interpret"])
    def test_xla_under_a_multi_device_mesh_in_every_mode(self, mode):
        from transmogrifai_tpu.parallel.mesh import make_mesh, use_mesh

        with KD.force_kernel_mode(mode):
            with use_mesh(make_mesh(n_data=1, n_model=1,
                                    devices=jax.devices()[:1])):
                assert KD.descent_mode(D) == mode
            before = KD.kernel_selections().get("descent:xla", 0)
            with use_mesh(make_mesh(n_data=2, n_model=2,
                                    devices=jax.devices()[:4])):
                assert KD.descent_mode(D) == "xla"
            assert KD.kernel_selections()["descent:xla"] == before + 1

    def test_cache_token_unchanged_by_the_kernel(self):
        for mode in ("xla", "pallas", "interpret"):
            with KD.force_kernel_mode(mode):
                token = KD.cache_token()
                KD.descent_mode(D)
                assert KD.cache_token() == token
                assert "descent" not in token


def test_pallas_is_prefetched_once_and_only_where_a_kernel_may_run(
        monkeypatch):
    """The SVC sweep starts Pallas' import on a thread (the refit's kernel
    needs it); never in ``xla`` mode, never twice in a process."""
    import sys
    import types

    started = []

    def thread(**kw):
        started.append(kw["target"])
        return types.SimpleNamespace(start=lambda: None)

    monkeypatch.setattr(KD, "_PALLAS_PREFETCHED", False)
    monkeypatch.setattr(KD.threading, "Thread", thread)
    monkeypatch.delitem(sys.modules, "jax.experimental.pallas.tpu",
                        raising=False)
    with KD.force_kernel_mode("xla"):
        KD.prefetch_pallas()
    assert started == []
    with KD.force_kernel_mode("interpret"):
        KD.prefetch_pallas()
        KD.prefetch_pallas()
    assert started == [KD._import_pallas]


def _fit_data(n=900, d=5, seed=2):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, d))
    y = (x[:, 0] - x[:, 1] + 0.3 * rng.normal(size=n) > 0).astype(np.float64)
    return x, y, np.ones(n)


class TestRefit:
    def test_launch_span_carries_the_choice(self):
        x, y, w = _fit_data()
        for mode in ("xla", "interpret"):
            with KD.force_kernel_mode(mode), record_phases() as rec:
                svm.LinearSVC(reg_param=0.01)._fit_arrays(x, y, w)
            counts = [s.counts for s in rec.spans if s.path == "host.launch"
                      and s.counts.get("label") == "LinearSVC/svc_refit"]
            assert counts == [{"label": "LinearSVC/svc_refit",
                               "descent_kernel": mode}]

    def test_a_flipped_mode_compiles_its_own_program(self):
        """The mode is a static argument of ``_svc_core``: the other mode's
        executable is never served, and a mode seen before is reused."""
        x, y, w = _fit_data(n=1500, d=7, seed=4)    # shapes no test fits
        est = svm.LinearSVC(reg_param=0.01)
        seen = svm._svc_core._cache_size()
        with KD.force_kernel_mode("xla"):
            ref = est._fit_arrays(x, y, w)
        assert svm._svc_core._cache_size() == seen + 1
        seen += 1
        with KD.force_kernel_mode("interpret"):
            got = est._fit_arrays(x, y, w)
        assert svm._svc_core._cache_size() == seen + 1
        with KD.force_kernel_mode("xla"):
            again = est._fit_arrays(x, y, w)
        assert svm._svc_core._cache_size() == seen + 1
        np.testing.assert_array_equal(again.coef, ref.coef)
        np.testing.assert_allclose(got.coef, ref.coef, rtol=1e-5, atol=1e-6)
        assert abs(got.intercept - ref.intercept) < 1e-5


@pytest.mark.slow
@pytest.mark.skipif(jax.default_backend() != "tpu",
                    reason="compiled Pallas kernels need a TPU backend")
@pytest.mark.parametrize("has_intercept", [True, False])
def test_compiled_kernel_matches_xla_on_the_chip(has_intercept):
    x, y, w, beta = _block(65536, has_intercept, padded=4096)
    block = KDE.hinge_block(x, y, w, KD.descent_tile(D))
    got = jax.jit(lambda b: KDE.hinge_grad_pallas(
        block, b, has_intercept))(beta)
    assert _rel(got, KDE.hinge_grad_xla(x, y, w, beta)) < 1e-6
