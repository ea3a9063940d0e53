"""The boosted-tree cell's own pieces: its plain reference against the
program at a tiny size, the replay that holds an ensemble to float32 tree by
tree (``reference/treereplay.py``) and the entry's comparison built on it,
its work model against a hand count, and its five per-layer readers on
hand-made contexts (a value where there is something to read, None where
there is not).  CPU only: nothing here is a time, a rate or a device
number."""

import importlib
from types import SimpleNamespace

import numpy as np
import pytest

from chipbench import run as harness
from chipbench import traffic

PARAMS = {**traffic.load("postprep_1m"), "rows": 2048}
READERS = ["boost_device_s", "boost_refit_device_s", "boost_roofline",
           "bin_s", "binoh_gb"]


def _config():
    return harness.load_config(harness.load_benchmark(), "binsel_gbt_d128")


def _read(name, ctx):
    return importlib.import_module(f"chipbench.per_layer.{name}").read(ctx)


def test_the_cell_is_declared_with_its_five_metrics():
    bench = harness.load_benchmark()
    cell = next(w for w in bench["workloads"] if w["name"] == "gbt_sweep_1m")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "binsel_gbt_d128", "postprep_1m", 1)
    own = [m["name"] for m in bench["per_layer"]
           if m.get("workloads") == ["gbt_sweep_1m"]]
    assert own == READERS
    # beside them the six of set-up, listed for every cell
    from test_chipbench_setup_spans import METRICS as SETUP

    listed = {m["name"] for m in bench["per_layer"]
              if "gbt_sweep_1m" in m.get("workloads", [])}
    assert listed == set(READERS) | set(SETUP)
    cfg = _config()
    assert [f["key"] for f in cfg["families"]] == ["gbt"]
    # selector_fit's names (the guards hold the control to its compare too)
    assert set(cfg["limits"]) == {"cv_metric_gap", "choice_regret",
                                  "refit_score_gap"}
    assert traffic.load("postprep_1m")["rows"] == 2 ** 20


def test_reference_agrees_with_the_program_at_a_tiny_size():
    """Both in float32 on the CPU: the winner-style refit (unit weights)
    through the program's own ``_fit_arrays`` and through the reference."""
    import jax.numpy as jnp

    from chipbench.entries.selector_fit import _resolve
    from transmogrifai_tpu.data.dataset import Column

    fam = _config()["families"][0]
    t = traffic.generate(PARAMS, 2**31 + 9)
    ref = importlib.import_module(f"chipbench.reference.{fam['reference']}")
    grid = [{"num_rounds": 6, "max_depth": 3}]
    ones = np.ones((1, 2048), np.float32)
    want = np.asarray(ref.fit_scores(
        jnp.asarray(t.x), jnp.asarray(t.y, jnp.float32), jnp.asarray(ones),
        grid, fam["params"]))[0, 0]
    est = _resolve(fam["estimator"])().set_params(**fam["params"], **grid[0])
    got = est._fit_arrays(t.x, t.y.astype(np.float32), ones[0]) \
        .predict_column(Column.vector(t.x)).prob[:, 1]
    assert 0.05 < want.std() < 0.5          # the trees did split
    assert np.abs(got - want).max() < 1e-5


def test_boosted_work_model_against_a_hand_count():
    model = importlib.import_module("chipbench.work.binsel_gbt_d128")
    cfg = {"cv": {"folds": 3}, "families": [
        {"key": "gbt", "grid": [{"num_rounds": 50, "max_depth": 3},
                                {"num_rounds": 10, "max_depth": 2}]}]}
    w = model.work(cfg, {"rows": 1000}, 10)
    levels = 50 * 3 + 10 * 2
    # two reads of the one-byte codes a level, shared by the three lanes;
    # gradient, hessian (float32) and the node id read and written (int32)
    # per lane
    assert w == {"gbt": {
        "bytes": levels * (2 * 1000 * 10 + 3 * 1000 * 16),
        "flops": levels * 3 * 4 * 1000}}
    # at the cell's size the least time is 0.058 s a fit, bound by bytes
    full = model.work(_config(), traffic.load("postprep_1m"), 128)["gbt"]
    assert full["bytes"] / 819e9 == pytest.approx(0.0584, rel=0.01)
    assert full["bytes"] / 819e9 > full["flops"] / 197e12


def _trace_ctx(modules, traced_calls=2, peaks=True):
    return {"config": _config(), "traffic": traffic.load("postprep_1m"),
            "trace": {"modules": modules} if modules is not None else None,
            "traced_calls": traced_calls, "notes": {},
            "peaks": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
            if peaks else None}


def test_device_readers_on_a_canned_trace():
    ctx = _trace_ctx({"jit__gbt_cv_program": 27.2, "jit__fit_gbt": 25.0,
                      "jit_binary_summary": 0.3})
    assert _read("boost_device_s", ctx) == pytest.approx(13.6)
    assert _read("boost_refit_device_s", ctx) == pytest.approx(12.5)
    share = _read("boost_roofline", ctx)
    assert share == pytest.approx(100 * 0.0584 / 13.6, rel=0.01)
    assert ctx["notes"]["boost_roofline"]["bound"] == {"gbt": "hbm_bytes"}
    # another family's trace, no trace at all, no table of peaks
    other = _trace_ctx({"jit__svc_cv_program": 1.0})
    untraced = _trace_ctx(None, traced_calls=0)
    for name in READERS[:3]:
        assert _read(name, other) is None
        assert _read(name, untraced) is None
    assert _read("boost_roofline", _trace_ctx(
        {"jit__gbt_cv_program": 27.2}, peaks=False)) is None


def _fit(start, spans, seconds=10.0):
    """A finished fit's profile as the program keeps it: ``spans`` are
    (path, seconds after the fit's start, seconds, counts)."""
    return SimpleNamespace(start=start, end=start + seconds, spans=[
        SimpleNamespace(path=p, start=start + at, seconds=secs, counts=c)
        for p, at, secs, c in spans])


def _launch(label, **counts):
    return {"label": label, **counts}


@pytest.fixture
def ring(monkeypatch):
    """Hand-made profiles in the place of the program's ring."""
    from transmogrifai_tpu.perf import timers

    kept = []
    monkeypatch.setattr(timers, "recent_fit_profiles", lambda: list(kept))
    return kept


CV = "GradientBoostedTreesClassifier/cv_program"
REFIT = "GradientBoostedTreesClassifier/gbt_refit"


def test_span_and_counter_readers_on_canned_profiles(ring):
    bins = {"n_bins": 32, "rows": 4096, "edges_hit": True, "codes_hit": True}
    one = [("host.bin", 1.0, 0.5, bins), ("host.stamp", 1.25, 0.125, {}),
           ("host.launch", 2.0, 0.1, _launch(CV, binoh_bytes=4_000_000_000)),
           ("host.bin", 8.0, 0.25, bins),
           ("host.launch", 8.5, 0.1, _launch(REFIT, binoh_bytes=9e9))]
    two = [("host.bin", 1.0, 0.25, bins),
           ("host.launch", 2.0, 0.1, _launch(CV, binoh_bytes=2_000_000_000)),
           ("host.launch", 3.0, 0.1, _launch(CV, binoh_bytes=0))]
    ring.extend([_fit(100.0, one), _fit(110.0, two)])
    ctx = {"records": [{"seconds": 10.1}, {"seconds": 10.1}]}
    # self time: the stamp inside the first look-up is the placement's
    assert _read("bin_s", ctx) == pytest.approx(
        ((0.5 - 0.125 + 0.25) + 0.25) / 2)
    # the sweep's launches only, the largest of a fit, mean over the fits
    assert _read("binoh_gb", ctx) == pytest.approx(3.0)


def test_the_declined_one_hot_reads_zero_and_a_missing_count_none(ring):
    ctx = {"records": [{"seconds": 10.1}]}
    assert _read("bin_s", ctx) is None and _read("binoh_gb", ctx) is None
    # the unchunked path of a tiny table: the count is there and is 0
    ring.append(_fit(100.0, [
        ("host.launch", 2.0, 0.1, _launch(CV, binoh_bytes=0))]))
    assert _read("binoh_gb", ctx) == 0.0
    assert _read("bin_s", ctx) is None          # no host.bin span in the fit
    # the parent commit's program: the launch carries a label and no count
    ring[:] = [_fit(100.0, [("host.launch", 2.0, 0.1, _launch(CV)),
                            ("host.launch", 8.0, 0.1, None)])]
    assert _read("binoh_gb", ctx) is None
    # a profile that does not pair with its record
    ring[:] = [_fit(100.0, [("host.bin", 1.0, 0.5, {}), (
        "host.launch", 2.0, 0.1, _launch(CV, binoh_bytes=1))], seconds=20.0)]
    assert _read("bin_s", ctx) is None and _read("binoh_gb", ctx) is None


# ---------------------------------------------------------------------------
# The replay, and the comparison that decides ``correct``
# ---------------------------------------------------------------------------

GRID = {"num_rounds": 6, "max_depth": 3}


@pytest.fixture(scope="module")
def small():
    """A 2048-row table as the reference sees it: codes, labels, weights."""
    import jax.numpy as jnp

    from chipbench.reference import treegrow

    t = traffic.generate(PARAMS, 2**31 + 9)
    x = jnp.asarray(t.x)
    codes = treegrow.bin_codes(x, jnp.asarray(treegrow.quantile_edges(x, 32)))
    return t, codes, jnp.asarray(t.y, jnp.float32), jnp.ones(2048, jnp.float32)


def test_the_kept_trees_are_the_references_own_ensemble(small):
    """``boost_trees`` is ``fit_scores`` with the trees kept, and an ensemble
    grown in float32 replays onto itself: no regret, the same scores."""
    import jax.numpy as jnp

    from chipbench.reference import GradientBoostedTreesClassifier as ref
    from chipbench.reference import treereplay

    t, codes, y, w = small
    params = _config()["families"][0]["params"]
    want = np.asarray(ref.fit_scores(jnp.asarray(t.x), y, w[None], [GRID],
                                     params))[0, 0]
    trees, prior, scores = treereplay.boost_trees(codes, y, w, GRID, params)
    assert np.abs(np.asarray(scores) - want).max() < 1e-6
    assert trees["feat"].shape == (6, 15)
    replayed, regrets = treereplay.replay(codes, y, w, trees, prior, GRID,
                                          params)
    assert np.asarray(regrets).tolist() == [0.0] * 6
    assert np.abs(np.asarray(replayed) - np.asarray(scores)).max() < 1e-6


def test_the_replay_sees_a_worse_split_and_a_wrong_leaf(small):
    import jax.numpy as jnp

    from chipbench.reference import treereplay

    _, codes, y, w = small
    params = _config()["families"][0]["params"]
    trees, prior, scores = treereplay.boost_trees(codes, y, w, GRID, params)
    trees = {k: np.array(v) for k, v in trees.items()}
    # the third tree's root cut moved four bins: gain given up at the root
    # (the rows below it go another way, so their nodes give up some too)
    moved = {k: v.copy() for k, v in trees.items()}
    moved["cut"][2, 0] = (moved["cut"][2, 0] + 4) % 31
    _, regrets = treereplay.replay(
        codes, y, w, {k: jnp.asarray(v) for k, v in moved.items()}, prior,
        GRID, params)
    regrets = np.asarray(regrets)
    assert regrets[:2].tolist() == [0.0, 0.0] and 1e-3 < regrets[2] <= 1.0
    # a leaf value a tenth too large: the splits are the reference's, the
    # scores are not
    scaled = {**trees, "value": trees["value"] * 1.1}
    replayed, regrets = treereplay.replay(
        codes, y, w, {k: jnp.asarray(v) for k, v in scaled.items()}, prior,
        GRID, params)
    assert np.asarray(regrets)[0] == 0.0
    assert np.abs(np.asarray(replayed) - np.asarray(scores)).max() > 1e-3


def _tiny_config():
    cfg = _config()
    return {**cfg, "families": [dict(f, grid=[GRID])
                                for f in cfg["families"]]}


def test_the_programs_trees_replay_without_regret():
    """The entry end to end on the CPU (float32 histograms): the program's
    own trees, kept by ``collect``, give up no gain and score as the replayed
    ensemble does."""
    entry = importlib.import_module("chipbench.entries.selector_fit_trees")
    cfg = _tiny_config()
    table = traffic.generate({**PARAMS, "rows": 4096}, 2**31 + 5)
    state = entry.setup(cfg, table)
    records = [entry.step(state)]
    entry.collect(state, records, table, 2**31 + 5)
    assert records[0]["trees"]["feat"].shape == (6, 15)
    assert "model" not in records[0]
    compared, detail = entry.compare(cfg, table, records, 2**31 + 5)
    assert set(compared) == set(cfg["limits"])
    assert compared["choice_regret"][0] == 0.0
    assert compared["refit_score_gap"][0] < 1e-5
    assert detail["replays"][0]["train_eval_gap"] < 1e-6
    assert detail["replays"][0]["trees_with_regret"] == 0
    # half a positive's recall under the plain metric (PERF.md, section 7)
    assert compared["cv_metric_gap"][0] < 4.0 / 4096


def test_the_entrys_control_fails_by_the_replay():
    """float8 in the program's place: the kept trees give up gain and their
    leaf values are off, whatever the free-running scores do; float32 in the
    program's place reads 0 throughout."""
    entry = importlib.import_module("chipbench.entries.selector_fit_trees")
    cfg = _tiny_config()
    table = traffic.generate({**PARAMS, "rows": 4096}, 2**31 + 6)
    same, _ = entry.compare(cfg, table, [], 2**31 + 6, control=True)
    assert all(v == 0.0 for v, _ in same.values()), same
    low, detail = entry.compare(cfg, table, [], 2**31 + 6,
                                precision="float8", control=True)
    assert low["refit_score_gap"][0] > low["refit_score_gap"][1]
    assert low["choice_regret"][0] == detail["replays"][0]["split_regret"]
