"""The six readers of set-up from inside the program (``chipbench/setuplib.py``
and ``chipbench/per_layer/setup_*.py``): on hand-made profiles of the
program's own classes, on a program without the import mark, on a ring that
has lost the warm-up fit, and on a tiny CPU run of every cell.  CPU only:
nothing here is a time or a device number."""

import importlib
import json
import os
import time

import pytest

from chipbench import run as harness
from chipbench import setuplib
from transmogrifai_tpu.perf import timers
from transmogrifai_tpu.perf.timers import PhaseRecorder, Span

REPO = harness.ROOT
METRICS = ["setup_import_s", "setup_fit_s", "setup_lower_s",
           "setup_cache_load_s", "setup_compile_s", "setup_placement_s"]
#: the cells the six are declared for (PR 40); a later cell appends its name
CELLS = ["lr_sweep_4m", "svc_sweep_4m", "lr_sweep_mesh4_16m", "gbt_sweep_1m",
         "xgb_grid_1m", "lr_gbt_sweep_1m"]
with open(os.path.join(REPO, "BENCHMARK.json")) as f:
    BENCH = json.load(f)


def _read(name, ctx):
    return importlib.import_module(f"chipbench.per_layer.{name}").read(ctx)


def _fit(start, spans, seconds=10.0):
    """A finished fit's profile as the program keeps it: ``spans`` are
    (path, seconds after the fit's start, seconds[, label[, fun]])."""
    fit = PhaseRecorder()
    fit.start, fit.end = start, start + seconds
    for path, at, secs, *names in spans:
        fit.add(Span(name=path.rsplit(".", 1)[-1], path=path,
                     start=start + at, seconds=secs,
                     counts=dict(zip(("label", "fun"), names)) or None))
    return fit


#: a warm-up fit of 20 s on a warm cache: the table's stamp with its pad
#: inside and its transfer, a binning with a stamp and an eager operation's
#: load inside, one ``run_cached`` program (compile phase, then its launch)
#: and one direct jit call whose launch holds the probe's spans — an inner
#: jit's trace inside the outer's, a trace inside the lowering
WARMUP = [
    ("validate", 0.0, 15.0),
    ("host.stamp", 0.0, 2.0), ("host.pad", 0.5, 0.5), ("host.h2d", 2.0, 1.0),
    ("host.bin", 3.0, 2.0), ("host.stamp", 3.5, 0.5),
    ("host.cache_load", 4.5, 0.25, "unlabelled", "jit(_digitize)"),
    ("validate.compile.Fam/sweep", 5.0, 4.0),
    ("host.trace", 5.0, 1.0, "Fam/sweep"),
    ("host.trace", 5.25, 0.25, "Fam/sweep"),
    ("host.lower", 6.0, 2.0, "Fam/sweep"),
    ("host.trace", 6.5, 0.5, "Fam/sweep"),
    ("host.cache_load", 8.0, 1.0, "Fam/sweep"),
    ("host.launch", 9.0, 0.5, "Fam/sweep"),
    ("host.device_wait", 9.5, 5.5),
    ("refit", 15.0, 5.0),
    ("host.launch", 15.0, 3.0, "Fam/refit"),
    ("host.trace", 15.25, 0.5, "Fam/refit"),
    ("host.lower", 15.75, 0.75, "Fam/refit"),
    ("host.cache_load", 16.5, 1.25, "Fam/refit"),
    ("host.device_wait", 18.0, 1.0)]
WINDOW = [("validate", 0.0, 8.0), ("host.launch", 1.0, 0.5, "Fam/sweep"),
          ("host.device_wait", 1.5, 8.0)]
WANT = {
    "setup_fit_s": 20.0,
    # traces 1.0 + 0.5 + 0.5 (the nested quarter counted once), lowerings
    # 2.0 - 0.5 + 0.75
    "setup_lower_s": 2.0 + 2.25,
    "setup_cache_load_s": 0.25 + 1.0 + 1.25,
    "setup_compile_s": 0.0,
    # the stamps 2.0 - 0.5 + 0.5, the pad 0.5, the transfer 1.0
    "setup_placement_s": 2.0 + 0.5 + 1.0,
}


@pytest.fixture
def ring(monkeypatch):
    """Hand-made profiles in the place of the program's ring."""
    kept = []
    monkeypatch.setattr(timers, "recent_fit_profiles", lambda: list(kept))
    return kept


def _ctx(fits=2, setup_ended=99.0):
    """The harness's context for a window of ``fits`` fits; set-up ended,
    by its own clock (the process's age), at ``setup_ended`` on the
    profiles'."""
    age = harness.process_age_s() - (time.perf_counter() - setup_ended)
    return {"records": [{"seconds": 10.05}] * fits, "notes": {},
            "setup_seconds": age}


@pytest.mark.parametrize("name", METRICS)
def test_readers_take_the_warm_up_fits_self_time(ring, name):
    ring.extend([_fit(1.0, WINDOW), _fit(50.0, WARMUP, 20.0),
                 _fit(100.0, WINDOW), _fit(110.0, WINDOW)])
    want = {**WANT, "setup_import_s": timers.package_import_seconds()}
    assert _read(name, _ctx()) == pytest.approx(want[name], abs=1e-9)


@pytest.mark.parametrize("name", METRICS[2:])
def test_a_span_the_warm_up_fit_lacks_reads_zero_not_none(ring, name):
    ring.extend([_fit(50.0, WINDOW), _fit(100.0, WINDOW)])
    assert _read(name, _ctx(1)) == 0.0


@pytest.mark.parametrize("name", METRICS)
def test_a_program_without_the_mark_reads_none(ring, monkeypatch, name):
    """The parent commit's program keeps a ring and no mark: it has none of
    the probe's spans either, so nothing is read and nothing raises."""
    ring.extend([_fit(50.0, WARMUP, 20.0), _fit(100.0, WINDOW)])
    monkeypatch.delattr(timers, "package_import_seconds")
    ctx = _ctx(1)
    assert _read(name, ctx) is None and ctx["notes"] == {}


@pytest.mark.parametrize("case", [
    "no_warm_up_left", "window_over_the_ring", "empty_ring", "pairing_off",
    "warm_up_ends_after_set_up", "window_starts_before_set_up_ends"])
def test_a_ring_without_the_warm_up_fit_reads_none(ring, case):
    whole = [_fit(50.0, WARMUP, 20.0), _fit(100.0, WINDOW),
             _fit(110.0, WINDOW)]
    ring.extend({"no_warm_up_left": whole[1:],
                 "window_over_the_ring": whole[1:2],
                 "empty_ring": [],
                 "pairing_off": [whole[0], _fit(100.0, WINDOW, 12.0),
                                 whole[2]]}.get(case, whole))
    # the profile before the window's is the warm-up fit only if the
    # harness's mark of set-up's end lies between the two
    ctx = _ctx(setup_ended={"warm_up_ends_after_set_up": 69.0,
                            "window_starts_before_set_up_ends": 101.0
                            }.get(case, 99.0))
    got = {name: _read(name, ctx) for name in METRICS}
    # the mark needs no ring
    assert got.pop("setup_import_s") == timers.package_import_seconds()
    assert set(got.values()) == {None} and ctx["notes"] == {}


def test_the_notes_table_is_what_the_metrics_sum_over(ring):
    ring.extend([_fit(50.0, WARMUP, 20.0), _fit(100.0, WINDOW),
                 _fit(110.0, WINDOW + [("host.trace", 0.5, 0.25, "Fam/new"),
                                       ("host.lower", 0.75, 0.125, "Fam/new")])])
    ctx = _ctx()
    assert _read("setup_fit_s", ctx) == 20.0
    note = ctx["notes"][setuplib.NOTE]
    assert note["self_s"] == pytest.approx({
        "stamp": 2.0, "pad": 0.5, "h2d": 1.0, "bin": 1.25, "trace": 2.0,
        "lower": 2.25, "cache_load": 2.5, "launch": 0.5 + 0.5,
        "device_wait": 6.5})
    # every second of the fit is some activity's or nobody's
    assert sum(note["self_s"].values()) + note["unspanned_s"] == \
        pytest.approx(note["fit_s"])
    assert note["unspanned_s"] == pytest.approx(20.0 - 19.0)
    assert note["spans"] == len(WARMUP)
    assert note["unlabelled"] == {"jit(_digitize)": 1}
    assert note["by_label"].pop("unlabelled")["cache_loads"] == 1
    assert note["by_label"] == {
        "Fam/sweep": {"trace_s": 1.5, "traces": 3, "lower_s": 1.5,
                      "lowers": 1, "cache_load_s": 1.0, "cache_loads": 1,
                      "backend_compile_s": 0.0, "backend_compiles": 0,
                      "retrieval_s": 0.0},
        "Fam/refit": {"trace_s": 0.5, "traces": 1, "lower_s": 0.75,
                      "lowers": 1, "cache_load_s": 1.25, "cache_loads": 1,
                      "backend_compile_s": 0.0, "backend_compiles": 0,
                      "retrieval_s": 0.0}}
    # a callable that jit meets anew in a window fit is named, not hidden
    assert note["window_by_label"] == {
        "Fam/new": {"trace_s": 0.25, "traces": 1, "lower_s": 0.125,
                    "lowers": 1, "cache_load_s": 0.0, "cache_loads": 0,
                    "backend_compile_s": 0.0, "backend_compiles": 0,
                    "retrieval_s": 0.0}}
    # read once a run: the other readers find the table in the notes
    ring.clear()
    assert _read("setup_lower_s", ctx) == pytest.approx(4.25)


def test_a_span_the_probe_heard_of_late_counts_once(ring):
    """On a busy host the listener hears of an outer trace late and dates it
    back to after the start of its inner one.  Ends stay in order, so the
    one that outlasts the other is the outer: it is put back round it, and
    what stuck out is not counted twice."""
    ring.extend([_fit(50.0, [
        ("host.launch", 1.0, 8.0, "Fam/refit"),
        ("host.trace", 2.0, 1.0, "Fam/refit"),          # the inner one
        ("host.trace", 2.5, 3.0, "Fam/refit"),          # its outer, late
        ("host.trace", 3.5, 0.5, "Fam/refit"),          # another inner one
        ("host.lower", 5.5, 1.0, "Fam/refit"),
        ("host.device_wait", 9.0, 1.5)]),               # past the fit's end
        _fit(100.0, WINDOW)])
    ctx = _ctx(1)
    # the outer trace is [2.0, 5.5) with 1.5 s of inner traces
    assert _read("setup_lower_s", ctx) == pytest.approx(3.5 + 1.0)
    note = ctx["notes"][setuplib.NOTE]
    assert note["self_s"] == pytest.approx({
        "launch": 8.0 - 3.5 - 1.0, "trace": 3.5, "lower": 1.0,
        "device_wait": 1.0})
    assert sum(note["self_s"].values()) + note["unspanned_s"] == \
        pytest.approx(note["fit_s"])
    assert note["unspanned_s"] == pytest.approx(1.0)


@pytest.mark.parametrize("name", METRICS)
def test_the_declared_entries(name):
    """Pinned by what the entry says, not by where it stands: a later PR
    appends its metrics after these and its cell to their ``workloads``."""
    (m,) = [m for m in BENCH["per_layer"] if m["name"] == name]
    assert set(CELLS) <= set(m["workloads"])
    assert (m["name"], m["unit"], m["better"], m["source"], m["moves"]) == (
        name, "s", "lower", "program_span", "setup_s")


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_a_tiny_run_of_every_cell_reads_the_six(cell, monkeypatch, tmp_path):
    from test_chipbench_run import _tiny

    monkeypatch.setenv("TMOG_PALLAS", "interpret")
    # another worker's traced runs empty the harness's own directory
    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path / "trace"))
    result = harness.run(cell, 2**31 + 36, 0.3, True, require_tpu=False,
                         overrides=_tiny(cell), free_device=False,
                         bench=BENCH)
    assert result["correct"] is True, result["compared"]
    got = {name: result["metrics"][name]["value"] for name in METRICS}
    assert all(v >= 0.0 for v in got.values()), got
    assert got["setup_import_s"] > 0.0 and got["setup_fit_s"] > 0.0
    note = result["notes"][setuplib.NOTE]
    assert note["fit_s"] == got["setup_fit_s"]
    assert sum(note["self_s"].values()) + note["unspanned_s"] == \
        pytest.approx(note["fit_s"])
    assert got["setup_lower_s"] == pytest.approx(
        note["self_s"].get("trace", 0.0) + note["self_s"].get("lower", 0.0))
    # a window fit loads and compiles nothing
    for row in note["window_by_label"].values():
        assert row["cache_loads"] == row["backend_compiles"] == 0
    json.dumps(result["notes"])
