"""The run function end to end at a tiny size on the CPU (kernels in
interpret mode), the control that has to fail the comparison, and the timed
path broken underneath a whole run.  CPU only: nothing here is a time, a
rate or a device number."""

import json
import os

import numpy as np
import pytest

from chipbench import run as harness
from chipbench import traffic
from chipbench.entries import selector_fit

REPO = harness.ROOT
ROWS = 4096
def _with_staged_cells():
    """BENCHMARK.json plus the cells that are built but not yet in it."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(REPO, "chipbench", "staged_cells.json")) as f:
        staged = json.load(f)
    for key in ("configs", "workloads", "per_layer"):
        bench[key] = bench[key] + staged[key]
    return bench


BENCH = _with_staged_cells()
CELLS = [w["name"] for w in BENCH["workloads"]]


def _config(cell):
    return harness.load_config(BENCH, next(
        w["config"] for w in BENCH["workloads"] if w["name"] == cell))


def _tiny(cell):
    """The cell at 4096 rows, its ensembles cut to 4 trees of depth <= 3
    (widths, bins, folds and the linear grids stay)."""
    cfg = _config(cell)
    # the program's metric under fold weights sits half a positive's recall
    # (about 3 / ROWS) under the plain one: PERF.md, Open questions
    limits = dict(cfg["limits"],
                  cv_metric_gap=cfg["limits"]["cv_metric_gap"] + 4.0 / ROWS)
    families = []
    for fam in cfg["families"]:
        grid = [{k: (4 if k in ("num_trees", "num_rounds") else
                     min(v, 3) if k == "max_depth" else v)
                 for k, v in g.items()} for g in fam["grid"]]
        grid = [g for i, g in enumerate(grid) if g not in grid[:i]]
        families.append(dict(fam, grid=grid))
    return {"traffic": {"rows": ROWS},
            "config": {"limits": limits, "families": families}}


@pytest.fixture(autouse=True)
def _interpret(monkeypatch):
    monkeypatch.setenv("TMOG_PALLAS", "interpret")


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", [False, True])
def test_run_reports_every_declared_metric(cell, trace, capsys, monkeypatch):
    result = harness.run(cell, 2**31 + 11 + trace, 0.5, trace,
                         require_tpu=False, overrides=_tiny(cell),
                         free_device=False, bench=BENCH)
    assert result["correct"] is True, result["compared"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["device"]["platform"] == "cpu"
    kind = "per_layer" if trace else "end_to_end"
    declared = {m["name"] for m in harness.cell_metrics(BENCH, kind, cell)}
    # what only a chip can read is left out of a CPU line, never zeroed
    device_only = {m["name"] for m in BENCH["per_layer"]
                   if m["source"] == "device_trace"} | {
                       "fit_mfu", "peak_hbm_gb"}
    assert set(result["metrics"]) == declared - (device_only if trace
                                                 else set())
    for name, m in result["metrics"].items():
        assert np.isfinite(m["value"]), name
    monkeypatch.setattr(harness, "run", lambda *a, **k: dict(result))
    assert harness.main(["--workload", cell, "--seed", "1", "--seconds",
                         "1", "--trace", str(int(trace))]) == 0
    out, err = capsys.readouterr()
    last = json.loads(out.splitlines()[-1])
    assert list(last)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(last)[-1] == "compared" and "notes" not in last
    for name, c in last["compared"].items():
        assert set(c) == {"value", "limit"}
        assert f"compared {name}:" in err
    assert err.splitlines()[-1] == "[chipbench] correct=True"


def test_refuses_to_run_without_a_chip(capsys):
    with pytest.raises(SystemExit) as exc:
        harness.run(CELLS[0], 1, 1.0, False)
    assert exc.value.code == 3
    assert capsys.readouterr().out == ""


@pytest.mark.parametrize("cell", CELLS)
def test_lower_precision_control_fails_the_comparison(cell):
    """The reference in the precision below the configuration's, put in the
    program's place, has to come out as not correct."""
    cfg = {**_config(cell), **_tiny(cell)["config"]}
    table = traffic.generate({**traffic.load("postprep_4m"), "rows": ROWS}, 5)
    same, _ = selector_fit.compare(cfg, table, [], 5, precision="float32",
                                   control=True)
    assert all(v == 0.0 for v, _ in same.values()), same
    for precision in cfg["controls"]:
        low, _ = selector_fit.compare(cfg, table, [], 5, precision=precision,
                                      control=True)
        assert any(v > lim for v, lim in low.values()), (precision, low)


def _broken_run(cell, seed):
    return harness.run(cell, seed, 0.3, False, require_tpu=False,
                       overrides=_tiny(cell), free_device=False, bench=BENCH)


@pytest.mark.parametrize("cell", CELLS)
def test_half_of_the_rows_left_out_is_not_correct(cell, monkeypatch):
    """Every fold trains on half of its rows, the mean taken over the rest."""
    from transmogrifai_tpu.models.tuning import CrossValidator

    whole = CrossValidator.fold_weights

    def half(self, y, base_w):
        train_w, val_w = whole(self, y, base_w)
        train_w[:, ::2] = 0.0
        return train_w, val_w

    monkeypatch.setattr(CrossValidator, "fold_weights", half)
    result = _broken_run(cell, 2**31 + 21)
    assert result["correct"] is False, result["compared"]


@pytest.mark.parametrize("cell", CELLS)
def test_a_metric_altered_where_it_is_produced_is_not_correct(
        cell, monkeypatch):
    """Every fold-model's CV metric comes back a hundredth too high."""
    from transmogrifai_tpu.models import base

    gather = base.gather_scores
    monkeypatch.setattr(base, "gather_scores",
                        lambda pending: gather(pending) + 0.01)
    result = _broken_run(cell, 2**31 + 22)
    assert result["correct"] is False, result["compared"]
    assert result["compared"]["cv_metric_gap"]["value"] > \
        result["compared"]["cv_metric_gap"]["limit"]


@pytest.mark.parametrize("cell", CELLS)
def test_a_winner_refitted_on_altered_labels_is_not_correct(
        cell, monkeypatch):
    """The winner's refit (not the sweep) sees a tenth of the labels
    flipped: the model the fit hands back is not the one asked for."""
    for fam in _config(cell)["families"]:
        cls = selector_fit._resolve(fam["estimator"])
        fit = cls._fit_arrays

        def off(self, x, y, w, fit=fit):
            y = np.array(y, copy=True)
            y[::10] = 1.0 - y[::10]
            return fit(self, x, y, w)

        monkeypatch.setattr(cls, "_fit_arrays", off)
    result = _broken_run(cell, 2**31 + 24)
    assert result["correct"] is False, result["compared"]
    assert result["compared"]["refit_score_gap"]["value"] > \
        result["compared"]["refit_score_gap"]["limit"]


def test_a_failed_family_counts_as_failed(monkeypatch):
    """One family dies in the sweep, the other still wins: the selector
    would hand back a model, the benchmark must not take it."""
    from transmogrifai_tpu.models.trees import RandomForestClassifier

    def boom(self, *a, **k):
        raise RuntimeError("planted")

    monkeypatch.setattr(RandomForestClassifier, "_cv_sweep_device", boom)
    with pytest.raises(RuntimeError, match="warm-up fit failed"):
        _broken_run("tree_sweep_1m", 2**31 + 23)
