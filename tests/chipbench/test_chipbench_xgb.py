"""The boosted-tree GRID cell's own pieces (``xgb_grid_1m``): its reference
and replay, which read the boosting dynamics from the grid point; the 4-point
grid through the selector at 4,096 rows with depth 6 KEPT (the guards of
``test_chipbench_run.py`` cut every depth to 3 and see two points and no
deep level); the counts a grid's launch spans carry; its work model against
a hand count; its five per-layer readers on hand-made contexts.  CPU only:
nothing here is a time, a rate or a device number."""

import importlib
from types import SimpleNamespace

import numpy as np
import pytest

from chipbench import run as harness
from chipbench import traffic

ROWS = 4096
SEED = 2**31 + 5
READERS = ["grid_device_s", "grid_refit_device_s", "grid_roofline",
           "binoh_walks", "grid_binoh_gb"]
CV = "XGBoostClassifier/cv_program"
REFIT = "XGBoostClassifier/gbt_refit"
BUILD = "XGBoostClassifier/bin_onehot"


def _config(rounds=None):
    cfg = harness.load_config(harness.load_benchmark(), "binsel_xgb_d128")
    if rounds is None:
        return cfg
    return {**cfg, "families": [
        dict(f, grid=[dict(g, num_rounds=rounds) for g in f["grid"]])
        for f in cfg["families"]]}


def _read(name, ctx):
    return importlib.import_module(f"chipbench.per_layer.{name}").read(ctx)


def test_the_cell_is_declared_with_its_grid_and_five_metrics():
    bench = harness.load_benchmark()
    cell = next(w for w in bench["workloads"] if w["name"] == "xgb_grid_1m")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "binsel_xgb_d128", "postprep_1m", 1)
    own = [m["name"] for m in bench["per_layer"]
           if m.get("workloads") == ["xgb_grid_1m"]]
    assert own == READERS
    # no accepted metric's list of cells was widened for it but set-up's six
    # (PR 40)
    from test_chipbench_setup_spans import METRICS as SETUP

    listed = {m["name"] for m in bench["per_layer"]
              if "xgb_grid_1m" in m.get("workloads", [])}
    assert listed == set(READERS) | set(SETUP)
    cfg = _config()
    (fam,) = cfg["families"]
    assert fam["estimator"].endswith(".XGBoostClassifier")
    assert [(g["max_depth"], g["eta"]) for g in fam["grid"]] == [
        (3, 0.1), (3, 0.3), (6, 0.1), (6, 0.3)]
    assert {g["num_rounds"] for g in fam["grid"]} == {50}
    # the grid's the learning rate: nothing can read it from params
    assert "eta" not in fam["params"]
    # the point (depth 3, eta 0.3) is gbt_sweep_1m's own, letter for letter
    gbt = harness.load_config(bench, "binsel_gbt_d128")["families"][0]
    assert {**fam["params"], **fam["grid"][1]} == {
        **gbt["params"], **gbt["grid"][0]}
    assert (cfg["cv"], cfg["width"], cfg["bins"]) == (
        harness.load_config(bench, "binsel_gbt_d128")["cv"], 128, 32)
    assert set(cfg["limits"]) == {"cv_metric_gap", "choice_regret",
                                  "refit_score_gap"}


@pytest.fixture(scope="module")
def small():
    """A 2048-row table as the reference sees it: x, codes, labels, weights."""
    import jax.numpy as jnp

    from chipbench.reference import treegrow

    t = traffic.generate({**traffic.load("postprep_1m"), "rows": 2048},
                         2**31 + 9)
    x = jnp.asarray(t.x)
    codes = treegrow.bin_codes(x, jnp.asarray(treegrow.quantile_edges(x, 32)))
    return x, codes, jnp.asarray(t.y, jnp.float32), jnp.ones(2048, jnp.float32)


def test_the_reference_reads_the_dynamics_from_the_grid_point(small):
    """Two learning rates boosted as lanes of one call are, each, the
    accepted one-point reference at that ``eta``; a point without one takes
    ``params``'."""
    import jax.numpy as jnp

    from chipbench.reference import GradientBoostedTreesClassifier as one
    from chipbench.reference import XGBoostClassifier as ref

    x, _, y, w = small
    weights = jnp.stack([w, w.at[::3].set(0.0)])
    params = {**_config()["families"][0]["params"], "eta": 0.3}
    shape = {"num_rounds": 4, "max_depth": 4}
    grids = [dict(shape, eta=0.1), dict(shape, max_depth=2, eta=0.1),
             dict(shape, eta=0.3), shape]
    got = np.asarray(ref.fit_scores(x, y, weights, grids, params))
    assert got.shape == (4, 2, 2048)
    for g, grid in enumerate(grids):
        want = np.asarray(one.fit_scores(
            x, y, weights, [grid], {**params, "eta": grid.get("eta", 0.3)}))
        assert np.abs(got[g] - want[0]).max() < 1e-6, grid
    assert np.abs(got[0] - got[2]).max() > 1e-2     # the rates do differ
    assert np.array_equal(got[2], got[3])


DEEP = {"num_rounds": 5, "max_depth": 6, "eta": 0.1}


def test_the_replay_of_a_depth_6_ensemble_reads_no_regret(small):
    """The reference's own trees of 64 leaves replay onto themselves — no
    gain given up, the same scores — at the GRID's learning rate, whatever
    ``params`` says."""
    import jax.numpy as jnp

    from chipbench.reference import XGBoostClassifier as ref
    from chipbench.reference import gridreplay, treereplay

    x, codes, y, w = small
    params = {**_config()["families"][0]["params"], "eta": 0.3}
    trees, prior, scores = gridreplay.boost_trees(codes, y, w, DEEP, params)
    assert trees["feat"].shape == (5, 127)
    assert int((~np.asarray(trees["leaf"])[:, 31:63]).sum()) > 40  # level 6
    want = np.asarray(ref.fit_scores(x, y, w[None], [DEEP], params))[0, 0]
    assert np.abs(np.asarray(scores) - want).max() < 1e-6
    replayed, regrets = gridreplay.replay(codes, y, w, trees, prior, DEEP,
                                          params)
    assert np.asarray(regrets).tolist() == [0.0] * 5
    assert np.abs(np.asarray(replayed) - np.asarray(scores)).max() < 1e-6
    # the accepted replay reads params' 0.3 and puts other leaf values there
    other, _ = treereplay.replay(codes, y, w, trees, prior, DEEP, params)
    assert np.abs(np.asarray(other) - np.asarray(scores)).max() > 1e-2
    # a root split moved to another column's median gives up gain in that
    # tree, not before it (the rows below go another way, and a forced split
    # that leaves a child under min_child_weight reads as 1e30 given up)
    moved = {k: np.array(v) for k, v in trees.items()}
    assert moved["feat"][2, 0] != 2
    moved["feat"][2, 0], moved["cut"][2, 0] = 2, 15
    _, regrets = gridreplay.replay(
        codes, y, w, {k: jnp.asarray(v) for k, v in moved.items()}, prior,
        DEEP, params)
    regrets = np.asarray(regrets)
    assert regrets[:2].tolist() == [0.0, 0.0] and regrets[2] > 1e-3


def _entry():
    return importlib.import_module("chipbench.entries.selector_fit_trees")


def _one_fit(cfg, table):
    entry = _entry()
    state = entry.setup(cfg, table)
    records = [entry.step(state)]
    profile = state.selector.last_fit_profile
    entry.collect(state, records, table, SEED)
    return records, profile


def test_the_four_point_grid_at_depth_6_against_the_reference():
    """Through the selector, 6 rounds a point, depths 3 and 6 as the
    configuration has them: every fold-model, the choice and the replayed
    winner against the new reference (float32 on both sides)."""
    import jax.numpy as jnp

    from chipbench.entries.selector_fit import _resolve, sample_rows
    from chipbench.reference import common, gridreplay, treegrow

    cfg = _config(rounds=6)
    table = traffic.generate({**traffic.load("postprep_1m"), "rows": ROWS},
                             SEED)
    records, _ = _one_fit(cfg, table)
    rec = records[0]
    assert (rec["attempted"], rec["failed"]) == (12, 0)
    assert np.asarray(rec["cv"]["xgb"]).shape == (4, 3)
    compared, detail = _entry().compare(cfg, table, records, SEED)
    assert len(detail["reference_means"]) == 4
    gaps = np.abs(np.asarray(detail["cv_gaps"]["xgb"]))
    # the depth-3 fold-models free-running: half a positive's recall under
    # the plain metric (PERF.md, section 7)
    assert gaps[:2].max() < 4.0 / ROWS
    # a depth-6 leaf holds some 40 of a fold's 2,731 rows and candidates tie
    # to the last bit, so the free-running trees part ways (below: each
    # lane's trees give up no gain) and the metric follows loosely
    assert gaps[2:].max() < 0.05
    # the choice: the reference ranks the chosen point first ...
    fam = cfg["families"][0]
    assert rec["best"]["grid"]["max_depth"] == 6
    assert rec["trees"]["feat"].shape == (6, 127)
    split = detail["replays"][0]["split_regret"]
    assert compared["choice_regret"][0] == split < 1e-6
    # ... and the winner's trees score as the replayed ensemble does
    assert compared["refit_score_gap"][0] < 1e-5
    assert detail["replays"][0]["train_eval_gap"] < 1e-6
    # every depth-6 fold-model, tree by tree: the estimator on the fold's
    # training rows gives up no gain and puts the reference's leaf values
    x, y = jnp.asarray(table.x), jnp.asarray(table.y, jnp.float32)
    codes = treegrow.bin_codes(x, jnp.asarray(treegrow.quantile_edges(x, 32)))
    fold = common.fold_ids(ROWS, 3, int(cfg["cv"]["seed"]))
    rows = sample_rows(ROWS, SEED)
    assert len(rows) == ROWS                # under 65,536 rows: all of them
    for grid in fam["grid"][2:]:
        for f in range(3):
            w = (fold != f).astype(np.float32)
            lane = [{"model": _resolve(fam["estimator"])().set_params(
                **fam["params"], **grid)._fit_arrays(
                    table.x, table.y.astype(np.float32), w)}]
            _entry().collect(None, lane, table, SEED)
            trees = lane[0]["trees"]
            prior = trees.pop("prior")
            want, regrets = gridreplay.replay(
                codes, y, jnp.asarray(w),
                {k: jnp.asarray(v) for k, v in trees.items()}, prior, grid,
                fam["params"])
            assert float(np.asarray(regrets).max()) < 1e-6, (grid, f)
            assert np.abs(lane[0]["sample_scores"]
                          - np.asarray(want)[rows]).max() < 1e-5, (grid, f)


def test_the_launch_spans_of_a_four_point_sweep_say_what_the_grid_walks():
    """The configuration as it is (50 rounds), 4,096 rows: four sweep
    launches and the refit's, counted from shapes at dispatch."""
    table = traffic.generate({**traffic.load("postprep_1m"), "rows": ROWS},
                             SEED + 1)
    records, profile = _one_fit(_config(), table)
    launches = [s.counts for s in profile.spans
                if s.path == "host.launch" and s.counts
                and "binoh_walks" in s.counts]
    sweep = [c for c in launches if c["label"] == CV]
    assert [c["grid_point"] for c in sweep] == [0, 1, 2, 3]
    assert {c["grid_points"] for c in sweep} == {4}
    assert [c["binoh_walks"] for c in sweep] == [150, 150, 300, 300]
    assert [c["hist_rows_deepest"] for c in sweep] == [12, 12, 96, 96]
    assert [(c["lanes"], c["rounds"]) for c in sweep] == [(3, 50)] * 4
    (refit,) = [c for c in launches if c["label"] == REFIT]
    depth = records[0]["best"]["grid"]["max_depth"]
    assert refit["binoh_walks"] == 50 * depth
    assert refit["hist_rows_deepest"] == {3: 4, 6: 32}[depth]
    assert "grid_point" not in refit        # the refit is no grid point
    assert len(launches) == 5
    # the reader sums them; the one-hot is declined at this size (0.0)
    from transmogrifai_tpu.perf import timers

    ring = timers.recent_fit_profiles()
    assert ring[-1] is profile
    ctx = {"records": records}
    assert _read("binoh_walks", ctx) == 900 + 50 * depth
    assert _read("grid_binoh_gb", ctx) == 0.0


def test_grid_work_model_against_a_hand_count():
    model = importlib.import_module("chipbench.work.binsel_xgb_d128")
    cfg = {"cv": {"folds": 3}, "families": [{"key": "xgb", "grid": [
        {"num_rounds": 50, "max_depth": 3, "eta": 0.1},
        {"num_rounds": 50, "max_depth": 3, "eta": 0.3},
        {"num_rounds": 50, "max_depth": 6, "eta": 0.1},
        {"num_rounds": 10, "max_depth": 6, "eta": 0.3}]}]}
    w = model.work(cfg, {"rows": 1000}, 10)
    # the two points of (50 rounds, depth 3) share the codes' two reads a
    # level between their six lanes; the two of depth 6 differ in rounds and
    # share nothing; 16 bytes a row, lane and level
    want = (150 * (2 * 1000 * 10 + 6 * 1000 * 16)
            + 300 * (2 * 1000 * 10 + 3 * 1000 * 16)
            + 60 * (2 * 1000 * 10 + 3 * 1000 * 16))
    assert w == {"xgb": {"bytes": want,
                         "flops": (150 * 6 + 300 * 3 + 60 * 3) * 4 * 1000}}
    # at the cell's size: 450 levels of shared reads, 2,700 lane-levels
    full = model.work(_config(), traffic.load("postprep_1m"), 128)["xgb"]
    assert full["bytes"] == 450 * 2 * 2**20 * 128 + 2700 * 2**20 * 16
    assert full["bytes"] / 819e9 == pytest.approx(0.2028, rel=0.01)
    assert full["bytes"] / 819e9 > full["flops"] / 197e12


def _trace_ctx(modules, traced_calls=2, peaks=True):
    return {"config": _config(), "traffic": traffic.load("postprep_1m"),
            "trace": {"modules": modules} if modules is not None else None,
            "traced_calls": traced_calls, "notes": {},
            "peaks": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
            if peaks else None}


def test_device_readers_on_a_canned_trace():
    ctx = _trace_ctx({"jit__gbt_cv_program": 18.0, "jit__fit_gbt": 5.0,
                      "jit_binary_summary": 0.3})
    assert _read("grid_device_s", ctx) == pytest.approx(9.0)
    assert _read("grid_refit_device_s", ctx) == pytest.approx(2.5)
    assert _read("grid_roofline", ctx) == pytest.approx(
        100 * 0.2028 / 9.0, rel=0.01)
    assert ctx["notes"]["grid_roofline"]["bound"] == {"xgb": "hbm_bytes"}
    other = _trace_ctx({"jit__svc_cv_program": 1.0})
    untraced = _trace_ctx(None, traced_calls=0)
    for name in READERS[:3]:
        assert _read(name, other) is None
        assert _read(name, untraced) is None
    assert _read("grid_roofline", _trace_ctx(
        {"jit__gbt_cv_program": 18.0}, peaks=False)) is None


def _fit(start, spans, seconds=10.0):
    return SimpleNamespace(start=start, end=start + seconds, spans=[
        SimpleNamespace(path="host.launch", start=start + at, seconds=0.1,
                        counts=c) for at, c in spans])


@pytest.fixture
def ring(monkeypatch):
    """Hand-made profiles in the place of the program's ring."""
    from transmogrifai_tpu.perf import timers

    kept = []
    monkeypatch.setattr(timers, "recent_fit_profiles", lambda: list(kept))
    return kept


def _point(label, walks=None, nbytes=None):
    counts = {"label": label}
    if walks is not None:
        counts["binoh_walks"] = walks
    if nbytes is not None:
        counts["binoh_bytes"] = nbytes
    return counts


def test_counter_readers_on_canned_profiles(ring):
    one = [(1.0, _point(BUILD)), (1.5, _point(CV, 150, 4e9)),
           (2.0, _point(CV, 150, 4e9)), (3.0, _point(CV, 300, 4e9)),
           (4.0, _point(CV, 300, 4e9)), (8.0, _point(REFIT, 300, 4e9))]
    # a fit that builds a one-hot a grid point, and whose refit is shallow
    two = [(1.0, _point(BUILD)), (1.5, _point(CV, 150, 4e9)),
           (2.0, _point(BUILD)), (2.5, _point(CV, 300, 4e9)),
           (8.0, _point(REFIT, 150, 4e9)), (9.0, None)]
    ring.extend([_fit(100.0, one), _fit(110.0, two)])
    ctx = {"records": [{"seconds": 10.1}, {"seconds": 10.1}]}
    assert _read("binoh_walks", ctx) == pytest.approx((1200 + 600) / 2)
    assert _read("grid_binoh_gb", ctx) == pytest.approx((4.0 + 8.0) / 2)


def test_a_declined_one_hot_reads_zero_and_a_missing_count_none(ring):
    ctx = {"records": [{"seconds": 10.1}]}
    assert _read("binoh_walks", ctx) is None
    assert _read("grid_binoh_gb", ctx) is None
    # a small table: the counts are there, nothing is built
    ring.append(_fit(100.0, [(2.0, _point(CV, 150, 0)),
                             (8.0, _point(REFIT, 150, 0))]))
    assert _read("grid_binoh_gb", ctx) == 0.0
    assert _read("binoh_walks", ctx) == 300
    # the parent commit's program: bytes on the launch, no walks
    ring[:] = [_fit(100.0, [(1.0, _point(BUILD)),
                            (2.0, _point(CV, nbytes=4e9)), (8.0, None)])]
    assert _read("binoh_walks", ctx) is None
    assert _read("grid_binoh_gb", ctx) == pytest.approx(4.0)
    # a program from before either count, and a profile that does not pair
    ring[:] = [_fit(100.0, [(2.0, _point(CV))])]
    assert _read("grid_binoh_gb", ctx) is None
    ring[:] = [_fit(100.0, [(2.0, _point(CV, 150, 1))], seconds=20.0)]
    assert _read("binoh_walks", ctx) is None
    assert _read("grid_binoh_gb", ctx) is None
