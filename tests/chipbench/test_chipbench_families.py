"""The two-family cell's own pieces (``lr_gbt_sweep_1m``): its configuration
against the two accepted ones it is made of; the selector over both families
through the entry ``selector_fit_families`` at 4,096 rows against the plain
references — every fold-model under its family's limit, the choice among all
six points, the linear winner's refit; the float8 control failing the linear
family's own limit; a table of this test's making on which the trees win, so
that the entry's replay branch runs; the work model against the two accepted
ones; the cell's six per-layer metrics as declared, and their readers on
hand-made contexts.  CPU only: nothing here is a time, a rate or a device
number."""

import importlib
from types import SimpleNamespace

import numpy as np
import pytest

from chipbench import run as harness
from chipbench import traffic

CELL, CONFIG = "lr_gbt_sweep_1m", "binsel_lr_gbt_d128"
ROWS = 4096
SEED = 2**31 + 38
READERS = ["mix_lr_device_s", "mix_gbt_device_s", "mix_roofline",
           "families_queued_s", "release_s", "placement_misses"]
BENCH = harness.load_benchmark()
#: the six as PR 38 built them and PR 40 declared them: unit, better, source,
#: layer
DECLARED = {
    "mix_lr_device_s": ("s/fit", "lower", "device_trace", "family programs"),
    "mix_gbt_device_s": ("s/fit", "lower", "device_trace", "family programs"),
    "mix_roofline": ("%", "higher", "device_trace", "kernels and XLA scans"),
    "families_queued_s": ("s/fit", "lower", "program_span", "selector"),
    "release_s": ("s/fit", "lower", "program_span", "selector"),
    "placement_misses": ("count", "lower", "program_counter", "placement")}
entry = importlib.import_module("chipbench.entries.selector_fit_families")


def _config():
    return harness.load_config(harness.load_benchmark(), CONFIG)


def _tiny_config():
    """The guards' cut: 4 rounds of depth 3, the linear grid whole, and the
    all-families limit widened by the weighted metric's 4 / rows."""
    from test_chipbench_run import _tiny

    return {**_config(), **_tiny(CELL)["config"]}


def _read(name, ctx):
    return importlib.import_module(f"chipbench.per_layer.{name}").read(ctx)


@pytest.fixture(autouse=True)
def _interpret(monkeypatch):
    monkeypatch.setenv("TMOG_PALLAS", "interpret")


def test_the_cell_is_declared_and_its_families_are_the_accepted_ones():
    bench = harness.load_benchmark()
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "postprep_1m", 1)
    assert bench["workloads"][-1] is cell       # appended, nothing moved
    assert bench["configs"][-1]["name"] == CONFIG
    cfg = _config()
    lr = harness.load_config(bench, "binsel_lr_d128")
    gbt = harness.load_config(bench, "binsel_gbt_d128")
    # estimator, params, grid, modules, reference, replay: letter for letter
    assert cfg["families"] == [lr["families"][0], gbt["families"][0]]
    assert cfg["cv"] == lr["cv"] == gbt["cv"]
    assert (cfg["width"], cfg["bins"], cfg["mesh"]) == (128, gbt["bins"], None)
    assert cfg["selector"] == lr["selector"] == gbt["selector"]
    assert cfg["reduced"] == ["model_families"] + lr["reduced"][1:] \
        + gbt["reduced"][1:]
    assert set(cfg["reduced_notes"]) == set(cfg["reduced"])
    # selector_fit's four names (the guards hold the control to its compare);
    # what the entry adds lives under keys that compare does not read
    assert set(cfg["limits"]) == {"cv_metric_gap", "choice_regret",
                                  "refit_score_gap", "train_eval_gap"}
    assert cfg["limits"]["cv_metric_gap"] == gbt["limits"]["cv_metric_gap"]
    assert {k: cfg["limits"][k] for k in (
        "choice_regret", "refit_score_gap", "train_eval_gap")} == {
        k: lr["limits"][k] for k in (
            "choice_regret", "refit_score_gap", "train_eval_gap")}
    assert cfg["family_limits"] == {"lr": {
        "cv_metric_gap": lr["limits"]["cv_metric_gap"],
        "stated_beside": cfg["limits"]["cv_metric_gap"]}}
    assert cfg["replay_limits"] == {k: gbt["limits"][k] for k in (
        "choice_regret", "refit_score_gap")}
    assert set(cfg["limits_notes"]) >= set(cfg["limits"]) | {
        "cv_metric_gap_lr"}
    assert traffic.load(cell["traffic"])["rows"] == 2 ** 20


@pytest.mark.parametrize("name", READERS)
def test_the_six_are_declared_for_the_cell(name):
    """Pinned by what the entry says, not by where it stands, and read in
    the cell and in no other accepted one (the contract's guards hold the
    characters, the reader's file and unique names)."""
    (m,) = [m for m in BENCH["per_layer"] if m["name"] == name]
    assert set(m) == {"name", "unit", "better", "source", "layer", "moves",
                      "workloads"}
    assert (m["unit"], m["better"], m["source"], m["layer"]) == DECLARED[name]
    assert m["moves"] == "fold_models_per_s" and CELL in m["workloads"]
    assert not {"lr_sweep_4m", "svc_sweep_4m", "lr_sweep_mesh4_16m",
                "gbt_sweep_1m", "xgb_grid_1m"} & set(m["workloads"])


@pytest.fixture(scope="module")
def tiny_run(tmp_path_factory):
    """One traced run of the cell at 4,096 rows."""
    from test_chipbench_run import _tiny

    mp = pytest.MonkeyPatch()
    mp.setenv("TMOG_PALLAS", "interpret")
    # another worker's traced runs empty the harness's own directory
    mp.setattr(harness, "TRACE_DIR",
               str(tmp_path_factory.mktemp("trace") / "t"))
    try:
        yield harness.run(CELL, SEED, 0.5, True, require_tpu=False,
                          overrides=_tiny(CELL), free_device=False,
                          bench=BENCH)
    finally:
        mp.undo()


def test_two_families_through_the_entry_against_the_references(tiny_run):
    result = tiny_run
    assert result["correct"] is True, result["compared"]
    assert result["failed"] == 0
    assert result["attempted"] == 18 * result["notes"]["calls"]
    compared = result["compared"]
    assert list(compared) == ["cv_metric_gap", "choice_regret",
                              "refit_score_gap", "train_eval_gap",
                              "cv_metric_gap_lr"]
    detail = result["notes"]["compare"]
    # every fold-model under its family's limit: 15 + 3 gaps
    gaps = {k: np.abs(np.asarray(v)) for k, v in detail["cv_gaps"].items()}
    assert {k: v.shape for k, v in gaps.items()} == {
        "lr": (5, 3), "gbt": (1, 3)}
    assert gaps["lr"].max() == compared["cv_metric_gap_lr"]["value"] \
        <= compared["cv_metric_gap_lr"]["limit"]
    assert max(gaps["lr"].max(), gaps["gbt"].max()) == \
        compared["cv_metric_gap"]["value"] <= \
        compared["cv_metric_gap"]["limit"]
    # the linear family's limit moves with the all-families one, by as much
    cfg = _config()
    assert compared["cv_metric_gap_lr"]["limit"] == pytest.approx(
        cfg["family_limits"]["lr"]["cv_metric_gap"] + 4.0 / ROWS)
    assert compared["cv_metric_gap"]["limit"] == pytest.approx(
        cfg["limits"]["cv_metric_gap"] + 4.0 / ROWS)
    # the choice: the reference's own best among all six points
    assert len(detail["reference_means"]) == 6
    best = result["notes"]["last_call"]["best"]
    key, g = detail["reference_best"]
    assert (best["family"], best["grid"]) == (
        key, next(f for f in cfg["families"] if f["key"] == key)["grid"][g])
    assert key == "lr" and detail["winner_replayed"] is False
    assert compared["choice_regret"]["value"] == 0.0
    # the linear winner's free-running float32 refit and its evaluation
    assert compared["refit_score_gap"]["value"] < 1e-5
    assert compared["train_eval_gap"]["value"] < 1e-6
    # the margin between the families, program and reference alike
    margin = detail["family_margin"]
    assert margin["reference"] > 0.05
    assert margin["compared"] == pytest.approx(margin["reference"], abs=2e-3)


def test_the_traced_line_holds_the_six_a_cpu_can_read(tiny_run):
    metrics = tiny_run["metrics"]
    declared = {m["name"] for m in harness.cell_metrics(
        BENCH, "per_layer", CELL)}
    device_only = {m["name"] for m in BENCH["per_layer"]
                   if m["source"] == "device_trace"} | {
                       "fit_mfu", "peak_hbm_gb"}
    assert set(metrics) == declared - device_only
    assert set(READERS[3:]) <= set(metrics)
    assert metrics["placement_misses"]["value"] == 0.0
    assert 0.0 < metrics["families_queued_s"]["value"] < \
        min(tiny_run["notes"]["call_seconds"])
    assert metrics["release_s"]["value"] >= 0.0
    # both families' sweeps were launched inside cv_dispatch_s's spans
    assert metrics["families_queued_s"]["value"] >= \
        0.5 * metrics["cv_dispatch_s"]["value"]


def test_the_float8_sweep_fails_the_linear_familys_own_limit():
    """float8 in the program's place at the file's own limits (both sides
    take the plain metric, so nothing is owed to the weighted one): the
    all-families gap stays under the trees' limit, the linear family's own
    does not; float32 in the program's place reads 0 throughout."""
    cfg = {**_tiny_config(), "limits": _config()["limits"]}
    table = traffic.generate({**traffic.load("postprep_1m"), "rows": ROWS},
                             SEED + 1)
    same, _ = entry.compare(cfg, table, [], SEED + 1, control=True)
    assert all(v == 0.0 for v, _ in same.values()), same
    low, detail = entry.compare(cfg, table, [], SEED + 1,
                                precision="float8", control=True)
    assert detail["winner_replayed"] is False
    assert low["cv_metric_gap_lr"][1] == \
        cfg["family_limits"]["lr"]["cv_metric_gap"]
    assert low["cv_metric_gap_lr"][0] > low["cv_metric_gap_lr"][1]
    assert low["cv_metric_gap_lr"][0] == np.abs(
        np.asarray(detail["cv_gaps"]["lr"])).max()


def _band_table(seed):
    """The cell's columns under a label no linear score ranks: a band of the
    first column (a tree of two levels finds it)."""
    t = traffic.generate({**traffic.load("postprep_1m"), "rows": ROWS}, seed)
    rng = np.random.default_rng([seed, 38])
    inside = np.abs(t.x[:, 0] - np.median(t.x[:, 0])) < 0.6
    flip = rng.random(ROWS) < 0.05
    return type(t)(t.x, (inside ^ flip).astype(np.float64))


def test_where_the_trees_win_the_winner_is_held_tree_by_tree():
    cfg = _tiny_config()
    table = _band_table(SEED + 2)
    assert 0.2 <= table.y.mean() <= 0.8
    state = entry.setup(cfg, table)
    records = [entry.step(state), entry.step(state)]
    assert all(r["failed"] == 0 for r in records), records[0]["why_failed"]
    assert {r["best"]["family"] for r in records} == {"gbt"}
    # the second fit found every placement of the first still in its cache
    placed = {k: v for k, v in records[1]["counters"].items()
              if k.startswith("placement_")}
    assert set(placed) == {f"placement_{cache}_{name}"
                           for cache in ("rows", "aux")
                           for name in entry.PLACEMENT_COUNTS}
    assert placed["placement_rows_misses"] == \
        placed["placement_aux_misses"] == 0
    assert placed["placement_rows_bytes_placed"] == \
        placed["placement_aux_bytes_placed"] == 0
    entry.collect(state, records, table, SEED + 2)
    assert all("trees" in r and "model" not in r for r in records)
    compared, detail = entry.compare(cfg, table, records, SEED + 2)
    assert detail["winner_replayed"] is True
    assert list(compared) == ["cv_metric_gap", "choice_regret",
                              "refit_score_gap", "cv_metric_gap_lr"]
    assert [compared[k][1] for k in ("choice_regret", "refit_score_gap")] \
        == [cfg["replay_limits"][k]
            for k in ("choice_regret", "refit_score_gap")]
    assert all(v <= lim for v, lim in compared.values()), compared
    # float32 on the CPU: the program's trees give up no gain
    assert compared["choice_regret"][0] == 0.0
    assert len(detail["replays"]) == 2
    assert detail["family_margin"]["reference"] > 0.05
    # the control on this table goes down the replay branch too, and fails
    low, low_detail = entry.compare(cfg, table, [], SEED + 2,
                                    precision="float8", control=True)
    assert low_detail["winner_replayed"] is True
    assert any(v > lim for v, lim in low.values()), low


def test_the_work_model_is_the_two_accepted_ones_side_by_side():
    model = importlib.import_module(f"chipbench.work.{CONFIG}")
    lr = importlib.import_module("chipbench.work.binsel_lr_d128")
    gbt = importlib.import_module("chipbench.work.binsel_gbt_d128")
    bench = harness.load_benchmark()
    params = traffic.load("postprep_1m")
    got = model.work(_config(), params, 128)
    assert got == {
        "lr": lr.work(harness.load_config(bench, "binsel_lr_d128"),
                      params, 128)["lr"],
        "gbt": gbt.work(harness.load_config(bench, "binsel_gbt_d128"),
                        params, 128)["gbt"]}
    # the sweeps alone: the trees' refit (which never runs here) and the
    # linear winner's are counted by neither accepted model, nor by this one
    peaks = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    least = {k: max(w["flops"] / peaks["flops_per_s"],
                    w["bytes"] / peaks["hbm_bytes_per_s"])
             for k, w in got.items()}
    # a quarter of lr_sweep_4m's 0.94 s; gbt_sweep_1m's 0.058 s
    assert least["lr"] == pytest.approx(0.94 / 4, rel=0.02)
    assert least["gbt"] == pytest.approx(0.0584, rel=0.01)


def _ctx(modules, traced_calls=2, peaks=True):
    return {"config": _config(), "traffic": traffic.load("postprep_1m"),
            "trace": {"modules": modules} if modules is not None else None,
            "traced_calls": traced_calls, "notes": {}, "records": [],
            "peaks": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
            if peaks else None}


def test_device_readers_on_a_canned_trace():
    ctx = _ctx({"jit__irls_sweep": 0.24, "jit__fista_sweep": 0.5,
                "jit_eval_linear_sweep": 0.22, "jit__device_prepare": 0.04,
                "jit__gbt_cv_program": 2.3, "jit__irls_core": 0.2,
                "jit__fit_gbt": 9.0, "jit_binary_summary": 0.3})
    assert _read("mix_lr_device_s", ctx) == pytest.approx(0.5)
    assert _read("mix_gbt_device_s", ctx) == pytest.approx(1.15)
    share = _read("mix_roofline", ctx)
    note = ctx["notes"]["mix_roofline"]
    assert note["bound"] == {"lr": "hbm_bytes", "gbt": "hbm_bytes"}
    assert note["device_s"] == pytest.approx(1.65)
    assert share == pytest.approx(100 * note["least_s"] / 1.65)
    assert 15.0 < share < 20.0
    # one family's modules alone, no trace at all, no table of peaks
    assert _read("mix_gbt_device_s", _ctx({"jit__irls_sweep": 1.0})) is None
    assert _read("mix_lr_device_s", _ctx({"jit__fit_gbt": 1.0})) is None
    for name in READERS[:3]:
        assert _read(name, _ctx(None, traced_calls=0)) is None
    assert _read("mix_roofline", _ctx({"jit__gbt_cv_program": 1.0},
                                      peaks=False)) is None


def _fit(start, spans, seconds=10.0):
    """A finished fit's profile as the program keeps it: ``spans`` are
    (path, seconds after the fit's start, seconds, parent)."""
    return SimpleNamespace(start=start, end=start + seconds, spans=[
        SimpleNamespace(path=p, start=start + at, seconds=secs, parent=parent,
                        counts=None) for p, at, secs, parent in spans])


@pytest.fixture
def ring(monkeypatch):
    """Hand-made profiles in the place of the program's ring."""
    from transmogrifai_tpu.perf import timers

    kept = []
    monkeypatch.setattr(timers, "recent_fit_profiles", lambda: list(kept))
    return kept


LR_D = "validate.cv.dispatch.LogisticRegression"
GBT_D = "validate.cv.dispatch.GradientBoostedTreesClassifier"


def test_span_readers_on_canned_profiles(ring):
    one = [("host.launch", 0.5, 0.25, "validate"),      # the fold weights
           ("host.launch", 1.0, 0.5, LR_D), ("host.launch", 2.0, 0.25, LR_D),
           ("host.bin", 2.5, 0.5, GBT_D), ("host.launch", 3.0, 0.5, GBT_D),
           ("host.launch", 3.5, 0.25, GBT_D),
           ("host.device_wait", 4.0, 3.0, "validate.cv.gather."),
           ("host.release", 7.0, 0.5, "validate"),
           ("host.stamp", 7.125, 0.125, "validate"),
           ("host.launch", 8.0, 0.5, "refit")]
    two = [("host.launch", 1.0, 0.5, LR_D), ("host.launch", 2.0, 0.25, GBT_D),
           ("host.release", 7.0, 0.25, "validate"),
           ("host.launch", 8.0, 0.5, "refit")]
    ring.extend([_fit(100.0, one), _fit(110.0, two)])
    ctx = {"records": [{"seconds": 10.1}, {"seconds": 10.1}]}
    # the end of the last launch under a dispatch phase; the refit's is later
    assert _read("families_queued_s", ctx) == pytest.approx(
        (3.75 + 2.25) / 2)
    # self time: the stamp inside the first release is the placement's
    assert _read("release_s", ctx) == pytest.approx(
        ((0.5 - 0.125) + 0.25) / 2)
    # a fit with no launch under a dispatch phase, a program with no such
    # span, a ring that does not pair with the records
    ring[:] = [_fit(100.0, [("host.launch", 8.0, 0.5, "refit")])]
    ctx = {"records": [{"seconds": 10.1}]}
    assert _read("families_queued_s", ctx) is None
    assert _read("release_s", ctx) is None
    ring[:] = [_fit(100.0, one, seconds=5.0)]
    assert _read("families_queued_s", ctx) is None
    assert _read("release_s", ctx) is None


def test_the_placement_reader_on_canned_records():
    def rec(rows, aux):
        return {"counters": {"placement_rows_misses": rows,
                             "placement_aux_misses": aux, "compiles": 0}}

    assert _read("placement_misses",
                 {"records": [rec(0, 0), rec(0, 0)]}) == 0.0
    assert _read("placement_misses",
                 {"records": [rec(1, 2), rec(0, 1)]}) == 2.0
    # an entry that records no placement counter, an empty window
    assert _read("placement_misses",
                 {"records": [{"counters": {"compiles": 0}}]}) is None
    assert _read("placement_misses", {"records": []}) is None
