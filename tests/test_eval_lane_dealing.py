"""The linear eval program under a mesh deals its (grid x fold) lanes over the
devices (``models/base._lane_dealer``): whatever the mesh and however the lanes
divide over it, it returns the no-mesh program's metrics to the bit, sorts
whole rows on one device and gathers no scores.  CPU, 8 virtual devices
(``conftest.py``): results, shapes and collectives, never a time."""

import re

import jax
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from transmogrifai_tpu.checkers import irsnap
from transmogrifai_tpu.evaluators import metrics as EM
from transmogrifai_tpu.models import base
from transmogrifai_tpu.parallel import mesh as M

MESHES = [(4, 1), (2, 2), (4, 2), (8, 1)]
#: 15 and 6 lanes divide over no mesh here, 1 lies under every one, 33 over
LANES = [(5, 3), (2, 3), (1, 1), (11, 3)]
METRICS = [("auPR", EM.au_pr, "sigmoid"), ("auROC", EM.au_roc, "sigmoid"),
           ("rmse", EM.rmse, "identity")]


def _mesh(n_data, n_model):
    if jax.device_count() < n_data * n_model:
        pytest.skip("needs 8 devices (conftest forces them on cpu)")
    return M.make_mesh(n_data, n_model,
                       devices=jax.devices()[:n_data * n_model])


def _inputs(g, k, n_data, seed):
    """Small-integer features and coefficients: every margin is an integer of
    a few bits, exact in float32 whatever the blocking of the einsum, and
    lands on one of a few dozen values, so the scores are full of ties.  n is
    an odd multiple of the data axis; the last rows are padding (weight 0 in
    every fold), and so are rows inside the block."""
    rng = np.random.default_rng(seed)
    n, d = n_data * 13, 5
    x = rng.integers(-3, 4, size=(n, d)).astype(np.float32)
    betas = rng.integers(-2, 3, size=(g, k, d)).astype(np.float32)
    y = (rng.random(n) < 0.4).astype(np.float32)
    vw = (rng.integers(0, k, size=n)[None, :]
          == np.arange(k)[:, None]).astype(np.float32)
    vw[:, -7:] = 0.0
    vw[:, 3] = 0.0
    return x, y, betas, vw


def _placed(mesh, x, y, vw):
    rows = NamedSharding(mesh, P(M.DATA_AXIS))
    return (jax.device_put(x, rows), jax.device_put(y, rows),
            jax.device_put(vw, NamedSharding(mesh, P(None, M.DATA_AXIS))))


@pytest.mark.parametrize("name,metric_fn,link", METRICS,
                         ids=[m[0] for m in METRICS])
@pytest.mark.parametrize("g,k", LANES, ids=[f"{g}x{k}" for g, k in LANES])
@pytest.mark.parametrize("n_data,n_model", MESHES,
                         ids=[f"{a}x{b}" for a, b in MESHES])
def test_dealt_lanes_return_the_no_mesh_metrics_bitwise(
        n_data, n_model, g, k, name, metric_fn, link):
    mesh = _mesh(n_data, n_model)
    x, y, betas, vw = _inputs(g, k, n_data, seed=100 * g + k)
    want = np.asarray(base._eval_linear_sweep_for(None)(
        x, y, betas, vw, metric_fn=metric_fn, link=link))
    xd, yd, vwd = _placed(mesh, x, y, vw)
    got = np.asarray(base._eval_linear_sweep_for(mesh)(
        xd, yd, betas, vwd, metric_fn=metric_fn, link=link))
    assert got.shape == (g, k) and np.all(np.isfinite(want))
    # a miscompile of the sharded-sort kind reads near -n: exact or nothing
    assert got.tobytes() == want.tobytes(), (name, got, want)


def _spec(*shape):
    return jax.ShapeDtypeStruct(tuple(shape), np.dtype("float32"))


@pytest.mark.parametrize("n_data,n_model", [(4, 1), (4, 2)],
                         ids=["4x1", "4x2"])
def test_the_lowered_program_sorts_whole_rows_locally_and_gathers_no_scores(
        n_data, n_model):
    """(5, 3) lanes of 64 rows: 15 lanes pad to 16; each device sorts its
    16 / devices lanes over all 64 rows inside the manual region; the scores
    cross in one all-to-all; the two all-gathers are the labels' and the
    (3, 64) validation weights'."""
    mesh = _mesh(n_data, n_model)
    n, d, g, k = 64, 5, 5, 3
    snap = irsnap.snapshot_program(
        "dealt_eval", base._eval_linear_sweep_for(mesh),
        [_spec(n, d), _spec(n), _spec(g, k, d), _spec(k, n)],
        statics=dict(metric_fn=EM.au_pr, link="sigmoid"))
    local = 16 // (n_data * n_model)
    assert [(s.dimension, s.shape) for s in snap.sorts] == \
        [(1, f"{local}x{n}xf32")]
    assert snap.sharded_sort_hazards() == []
    assert snap.collectives.get("stablehlo.all_to_all") == 1
    assert snap.collectives.get("stablehlo.all_gather") == 2
    gathered = re.findall(
        r'"stablehlo\.all_gather"\(.*-> tensor<([0-9x]+)xf32>', snap.text)
    assert sorted(gathered) == sorted([f"{n}", f"{k}x{n}"])
    # nothing is pinned to replicated any more, in either partitioner's form
    assert "sharding_constraint" not in snap.text
    assert "custom_call @Sharding" not in snap.text
    # the sort sits inside the shard_map region: GSPMD never partitions it
    body = snap.text[snap.text.index("sdy.manual_computation"):
                     snap.text.index("sdy.return")]
    assert "stablehlo.sort" in body and "stablehlo.all_to_all" in body


def test_count_eval_replicas_counts_dealt_scores_and_pinned_labels():
    """(5, 3) lanes over 4 x 2 devices pad to 16: 16 n floats laid out split,
    the labels and (3, n) weights on every device; a (g, k, d, C) block is
    the multiclass program's, which still pins its probabilities."""
    mesh = _mesh(4, 2)
    n = 4096
    xd, yd, vw = _spec(n, 5), _spec(n), _spec(3, n)
    with M.use_mesh(mesh):
        before = M.placement_stats()["mesh"]
        base.count_eval_replicas(xd, yd, _spec(5, 3, 5), vw)
        mid = M.placement_stats()["mesh"]
        base.count_eval_replicas(xd, yd, _spec(2, 3, 5, 4), vw)
        after = M.placement_stats()["mesh"]
    assert mid["bytes_sharded"] - before["bytes_sharded"] == 16 * 4 * n
    assert mid["bytes_replicated"] - before["bytes_replicated"] == 4 * 4 * n
    assert after["bytes_sharded"] == mid["bytes_sharded"]
    assert after["bytes_replicated"] - mid["bytes_replicated"] == \
        (2 * 3 * 4 + 4) * 4 * n
    assert after["degraded"] == before["degraded"]
    before = M.placement_stats()["mesh"]
    base.count_eval_replicas(xd, yd, _spec(5, 3, 5), vw)       # no mesh
    assert M.placement_stats()["mesh"] == before
