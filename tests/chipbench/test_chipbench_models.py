"""The work models against hand counts, each plain reference against the
program at a tiny size, and the traffic generator's shape.  CPU only."""

import importlib

import numpy as np
import pytest

from chipbench import layerlib, run as harness, traffic
from chipbench.generators import postprep_table
from chipbench.reference import common

PARAMS = {**traffic.load("postprep_4m"), "rows": 2048}


def test_table_has_the_post_transmogrify_shape():
    t = traffic.generate(PARAMS, 2**31 + 3)
    assert traffic.width(PARAMS) == 128
    assert t.x.shape == (2048, 128) and t.x.dtype == np.float32
    assert t.x.flags["C_CONTIGUOUS"]
    nulls = t.x[:, 1:112:2]
    assert set(np.unique(nulls)) == {0.0, 1.0}
    assert 0.01 < nulls.mean() < 0.06
    # a null is mean-imputed: one value per column wherever the flag is set
    for j in (0, 7, 55):
        assert np.unique(t.x[nulls[:, j] == 1.0, 2 * j]).size <= 1
    for block in (t.x[:, 112:120], t.x[:, 120:128]):
        assert np.array_equal(block.sum(axis=1), np.ones(2048))
    assert set(np.unique(t.y)) == {0.0, 1.0} and 0.3 < t.y.mean() < 0.7
    again = traffic.generate(PARAMS, 2**31 + 3)
    assert np.array_equal(t.x, again.x) and np.array_equal(t.y, again.y)
    other = traffic.generate(PARAMS, 2**31 + 4)
    assert other.x.shape == t.x.shape and not np.array_equal(other.y, t.y)


def test_table_does_not_depend_on_the_number_of_threads(monkeypatch):
    """Each block of rows has its own random stream; the imputed mean is
    taken over all blocks."""
    monkeypatch.setattr(postprep_table, "BLOCK_ROWS", 500)  # 5 blocks, 1 short
    many = traffic.generate(PARAMS, 2**31 + 3)
    monkeypatch.setattr(postprep_table, "THREADS", 1)
    one = traffic.generate(PARAMS, 2**31 + 3)
    assert np.array_equal(many.x, one.x) and np.array_equal(many.y, one.y)
    nulls = many.x[:, 1] == 1.0
    assert np.unique(many.x[nulls, 0]).size == 1
    assert many.x[nulls, 0][0] == pytest.approx(many.x[~nulls, 0].mean(),
                                                abs=1e-6)


def test_linear_work_model_against_hand_counts():
    model = importlib.import_module("chipbench.work.binsel_lr_d128")
    cfg = {"cv": {"folds": 3}, "families": [
        {"key": "lr", "params": {"max_iter": 30},
         "grid": [{"reg_param": 0.1, "elastic_net": 0.0},
                  {"reg_param": 0.1, "elastic_net": 0.5}]},
        {"key": "svc", "params": {"max_iter": 100},
         "grid": [{"reg_param": 0.1}]}]}
    w = model.work(cfg, {"rows": 1000}, 10)
    # IRLS: 1 point x 3 folds x 30 steps x (2*1000*100 + 6*1000*11)
    # FISTA: 1 point x 3 folds x 330 steps x 4*1000*11
    assert w["lr"]["flops"] == 3 * 30 * 266000 + 3 * 330 * 44000
    # every step reads the float32 block once, lanes sharing the read
    assert w["lr"]["bytes"] == (30 + 330) * 1000 * 10 * 4
    assert w["svc"]["flops"] == 3 * 100 * 44000
    assert w["svc"]["bytes"] == 100 * 1000 * 10 * 4


def test_least_time_takes_the_larger_bound_per_group():
    bench = harness.load_benchmark()
    ctx = {"peaks": {"flops_per_s": 1e6, "hbm_bytes_per_s": 1e6},
           "traffic": {**PARAMS, "rows": 1000},
           "config": harness.load_config(bench, "binsel_lr_d128")}
    total, bound = layerlib.least_seconds(ctx)
    assert bound == {"lr": "flops"}
    # 3 IRLS + 2 FISTA points x 3 folds, as the hand count above
    assert total == pytest.approx(
        (9 * 30 * (2 * 1000 * 128 ** 2 + 6 * 1000 * 129)
         + 6 * 330 * 4 * 1000 * 129) / 1e6)
    assert layerlib.least_seconds(ctx, ["svc"]) == (0.0, {})
    svc = {**ctx, "config": harness.load_config(bench, "binsel_svc_d128")}
    assert layerlib.least_seconds(svc)[1] == {"svc": "flops"}
    slow_hbm = {**svc, "peaks": {"flops_per_s": 1e12, "hbm_bytes_per_s": 1.0}}
    assert layerlib.least_seconds(slow_hbm) == (
        pytest.approx(100 * 1000 * 128 * 4.0), {"svc": "hbm_bytes"})
    assert layerlib.least_seconds({**ctx, "peaks": None}) is None


def test_plain_au_pr_against_a_hand_count():
    # ranked: 1, 0, 1 -> precision 1, 1/2, 2/3 at recall 1/2, 1/2, 1
    got = common.au_pr(np.array([0.9, 0.8, 0.1]), np.array([1.0, 0.0, 1.0]))
    assert got == pytest.approx(0.5 * 1.0 + 0.5 * (0.5 + 2 / 3) * 0.5)


def test_fold_ids_are_a_seeded_partition():
    a, b = common.fold_ids(999, 3, 7), common.fold_ids(999, 3, 7)
    assert np.array_equal(a, b) and np.bincount(a).tolist() == [333] * 3


@pytest.mark.parametrize("family", ["lr", "svc"])
def test_reference_agrees_with_the_program_at_a_tiny_size(family):
    """Both in float32 on the CPU: the winner-style refit (unit weights)
    through the program's own ``_fit_arrays`` and through the reference."""
    import jax.numpy as jnp

    from chipbench.entries.selector_fit import _resolve
    from transmogrifai_tpu.data.dataset import Column

    cfg = harness.load_config(harness.load_benchmark(),
                              f"binsel_{family}_d128")
    fam = next(f for f in cfg["families"] if f["key"] == family)
    t = traffic.generate(PARAMS, 2**31 + 9)
    ref = importlib.import_module(f"chipbench.reference.{fam['reference']}")
    ones = np.ones((1, 2048), np.float32)
    want = np.asarray(ref.fit_scores(
        jnp.asarray(t.x), jnp.asarray(t.y, jnp.float32), jnp.asarray(ones),
        fam["grid"], fam["params"]))
    for g, grid in enumerate(fam["grid"]):
        est = _resolve(fam["estimator"])().set_params(**fam["params"], **grid)
        col = est._fit_arrays(t.x, t.y.astype(np.float32), ones[0]) \
            .predict_column(Column.vector(t.x))
        got = col.prob[:, 1] if col.prob is not None else col.raw[:, 1]
        assert np.abs(got - want[g, 0]).max() < 2e-3 * max(
            1.0, np.abs(want[g, 0]).max()), grid
