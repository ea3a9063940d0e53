"""The readers of the program's host spans (``chipbench/spanlib.py`` and the
six metrics on it) on hand-made profiles, ``chipbench/hostspans.py`` on
hand-made planes and on a small trace recorded on a v5e
(``chipbench/testdata/fit_small.xplane.pb``: one tiny ``ModelSelector.fit``
after its warm-up), and the two older span metrics on a tiny CPU run of each
cell: they read the sum of exactly the paths they read before the host
activities existed.  CPU only: nothing here is a time or a device number."""

import importlib
import json
import os
from types import SimpleNamespace

import pytest

from chipbench import hostspans as H
from chipbench import run as harness
from chipbench import spanlib

REPO = harness.ROOT
TRACE = os.path.join(REPO, "chipbench", "testdata", "fit_small.xplane.pb")
NS = 1e-9
METRICS = ["first_launch_s", "fold_weights_s", "placement_s", "launch_s",
           "device_wait_s", "host_unspanned_s"]


def _read(name, ctx):
    return importlib.import_module(f"chipbench.per_layer.{name}").read(ctx)


def _fit(start, spans, seconds=10.0):
    """A finished fit's profile as the program keeps it: ``spans`` are
    (path, seconds after the fit's start, seconds)."""
    return SimpleNamespace(start=start, end=start + seconds, spans=[
        SimpleNamespace(path=p, start=start + at, seconds=secs)
        for p, at, secs in spans])


#: one fit of 10 s: 1 s of fold weights, a stamp with a nested pad (clocks
#: that nest are the reader's to take apart), a launch at 3 s, a wait
ONE = [("prep", 0.0, 0.5), ("validate", 0.5, 8.0),
       ("host.fold_weights", 0.5, 1.0),
       ("validate.cv.dispatch.Fam", 1.5, 2.0),
       ("host.stamp", 1.5, 1.0), ("host.pad", 1.75, 0.5),
       ("host.program_key", 2.75, 0.25), ("host.launch", 3.0, 0.5),
       ("host.device_wait", 3.5, 5.0), ("refit", 8.5, 1.5),
       ("host.launch", 8.5, 0.25), ("host.device_wait", 8.75, 1.0)]


@pytest.fixture
def ring(monkeypatch):
    """Hand-made profiles in the place of the program's ring."""
    from transmogrifai_tpu.perf import timers

    kept = []
    monkeypatch.setattr(timers, "recent_fit_profiles", lambda: list(kept))
    return kept


def test_readers_take_self_time_and_the_first_launch(ring):
    ring.extend([_fit(5.0, [("host.launch", 9.0, 0.5)]),      # the warm-up
                 _fit(100.0, ONE), _fit(110.0, ONE)])
    ctx = {"records": [{"seconds": 10.001}, {"seconds": 10.5}]}
    got = {name: _read(name, ctx) for name in METRICS}
    assert got == pytest.approx({
        "first_launch_s": 3.0,
        "fold_weights_s": 1.0,
        # the stamp's second less the half a second of the pad inside it,
        # and the pad itself: the nested time is counted once
        "placement_s": 0.5 + 0.5,
        "launch_s": 0.25 + 0.5 + 0.25,
        "device_wait_s": 6.0,
        # 10 s less the union of 0.5-2.5 and 2.75-9.75
        "host_unspanned_s": 10.0 - 9.0}, abs=1e-9)
    # an activity no fit did reads 0.0, not None: the ring is there
    assert spanlib.activity_seconds_per_fit(ctx, ["h2d"]) == 0.0


def test_self_time_by_interval_containment():
    spans = [("a", 0.0, 10.0), ("b", 1.0, 4.0), ("c", 2.0, 1.0),
             ("b", 6.0, 2.0), ("d", 20.0, 1.0)]
    assert spanlib.innermost_seconds(spans) == pytest.approx(
        {"a": 10.0 - 4.0 - 2.0, "b": 3.0 + 2.0, "c": 1.0, "d": 1.0})
    # clipped to a gap: who was innermost between 3.5 and 7.0
    assert spanlib.innermost_seconds(spans, 3.5, 7.0) == pytest.approx(
        {"a": 1.0, "b": 1.5 + 1.0, "c": 0.0, "d": 0.0})


@pytest.mark.parametrize("seconds", [9.9, 11.2], ids=["shorter", "longer"])
def test_a_record_whose_seconds_do_not_match_reads_none(ring, seconds):
    """The profile is longer than its record, or under nine tenths of it:
    the pairing is off and every reader returns None."""
    ring.extend([_fit(100.0, ONE), _fit(110.0, ONE)])
    ctx = {"records": [{"seconds": 10.2}, {"seconds": seconds}]}
    assert [_read(name, ctx) for name in METRICS] == [None] * len(METRICS)


def test_no_ring_no_fits_or_no_launch_reads_none(ring, monkeypatch):
    from transmogrifai_tpu.perf import timers

    ctx = {"records": [{"seconds": 10.1}]}
    assert [_read(name, ctx) for name in METRICS] == [None] * len(METRICS)
    ring.append(_fit(100.0, [s for s in ONE if s[0] != "host.launch"]))
    assert _read("first_launch_s", ctx) is None
    assert _read("device_wait_s", ctx) == pytest.approx(6.0)
    # the parent commit's program keeps no ring: nothing to read, no error
    monkeypatch.delattr(timers, "recent_fit_profiles")
    assert [_read(name, ctx) for name in METRICS] == [None] * len(METRICS)


def test_a_window_longer_than_the_ring_reads_its_last_fits(ring):
    ring.extend([_fit(100.0, ONE), _fit(110.0, ONE)])
    ctx = {"records": [{"seconds": 1.0}, {"seconds": 10.1},
                       {"seconds": 10.1}]}
    assert _read("fold_weights_s", ctx) == pytest.approx(1.0)


# ---------------------------------------------------------------------------
# hostspans: idle gaps put down to host spans
# ---------------------------------------------------------------------------

def _hand_made():
    line = "/host:CPU/python"
    return {
        "window": (0.0, 10.0),
        "modules": [("jit_a", 2.0, 1.0), ("jit_b", 3.005, 1.0),
                    ("jit_c", 6.0, 3.0)],
        "phases": {line: [("validate", 0.0, 5.5),
                          ("validate.cv.dispatch.Fam", 0.5, 1.6),
                          ("refit", 5.5, 4.5)]},
        "activities": {line: [("host.fold_weights", 0.1, 0.4),
                              ("host.stamp", 0.5, 1.0),
                              ("host.launch", 1.9, 0.2),
                              ("host.device_wait", 2.1, 3.0),
                              ("host.pad", 5.5, 0.25),
                              ("host.launch", 5.9, 0.2)]}}


def test_gaps_are_located_and_attributed_by_hand():
    rows = H.attribute(_hand_made())
    # the 5 ms between a and b is launch latency, not a gap
    assert [r["gap"] for r in rows] == [
        "window_start -> jit_a", "jit_b -> jit_c", "jit_c -> window_end"]
    first, second, last = rows
    assert (first["start_s"], first["seconds"]) == (0.0, 2.0)
    assert first["activities"] == pytest.approx(
        {"host.stamp": 1.0, "host.fold_weights": 0.4, "host.launch": 0.1})
    assert first["named_share"] == pytest.approx(0.75)
    # innermost phase: the dispatch's 1.5 s come off validate's 2.0
    assert first["phases"] == pytest.approx(
        {"validate.cv.dispatch.Fam": 1.5, "validate": 0.5})
    assert list(first["phases"])[0] == "validate.cv.dispatch.Fam"
    assert second["seconds"] == pytest.approx(6.0 - 4.005)
    assert second["activities"] == pytest.approx(
        {"host.device_wait": 5.1 - 4.005, "host.pad": 0.25,
         "host.launch": 0.1})
    assert second["phases"] == pytest.approx(
        {"validate": 5.5 - 4.005, "refit": 0.5})
    assert last["activities"] == {} and last["named_share"] == 0.0
    assert last["phases"] == pytest.approx({"refit": 1.0})
    text = H.table(rows)
    assert text.splitlines()[0].startswith("| gap | at s | s | device_wait")
    assert "| window_start -> jit_a | 0.000 | 2.000 |" in text
    assert text.splitlines()[2].endswith("| 75% | validate.cv.dispatch.Fam |")


def test_without_a_window_the_programs_extent_is_taken_and_none_is_refused():
    trace = dict(_hand_made(), window=None)
    assert [r["gap"] for r in H.attribute(trace)] == ["jit_b -> jit_c"]
    with pytest.raises(H.R.NoDevicePlane):
        H.attribute(dict(trace, modules=[]))


def test_the_recorded_fit_has_its_spans_beside_the_programs(capsys):
    """One tiny fit on a v5e: the phases and activities are on one host
    line, the device programs on the chip's plane, and the gaps between the
    programs are named by what the host did in them."""
    trace = H.read_trace(TRACE)
    (line,) = set(trace["phases"]) | set(trace["activities"])
    assert line.startswith("/host:CPU/")
    phases = [name for name, _, _ in trace["phases"][line]]
    assert phases == ["prep", "validate",
                      "validate.cv.dispatch.LogisticRegression",
                      "validate.cv.gather.LogisticRegression", "refit",
                      "train_eval"]
    acts = trace["activities"][line]
    assert {name for name, _, _ in acts} == {
        "host.fold_weights", "host.stamp", "host.pad", "host.program_key",
        "host.launch", "host.device_wait"}
    assert trace["window"] == pytest.approx((43738938 * NS, 58836017 * NS),
                                            abs=1e-12)
    assert len(trace["modules"]) == 22
    names = {name for name, _, _ in trace["modules"]}
    assert {"jit__irls_sweep", "jit_eval_linear_sweep",
            "jit__irls_core"} <= names
    # every span lies inside the window the script wrapped round the fit
    lo, hi = trace["window"]
    assert all(lo <= s and s + d <= hi
               for spans in (trace["phases"][line], acts)
               for _, s, d in spans)
    rows = H.attribute(trace, min_gap=50e-6)
    assert len(rows) == 21
    assert rows == sorted(rows, key=lambda r: -r["seconds"])
    # the longest: the 2.4 ms after the refit's program, before the next one
    # starts.  Added up by hand from the file's own events (ns): programs
    # end 51693973, next starts 54095290; in between three stamps (102680 +
    # 17550 + 16950), two pads (52300 + 31690), two launches (332510 +
    # 643320) and, from 53084808 on, the wait; validate until 51715498,
    # refit from 51766138.
    first = rows[0]
    assert first["gap"] == "jit__irls_core -> jit__squeeze"
    assert first["seconds"] == pytest.approx(2401317 * NS, abs=1e-12)
    assert first["activities"] == pytest.approx({
        "host.device_wait": 1010482 * NS, "host.launch": 975830 * NS,
        "host.stamp": 137180 * NS, "host.pad": 83990 * NS}, abs=1e-12)
    assert list(first["activities"])[0] == "host.device_wait"
    assert first["phases"] == pytest.approx(
        {"refit": 2329152 * NS, "validate": 21525 * NS}, abs=1e-12)
    assert first["named_share"] == pytest.approx(2207482 / 2401317)
    # the host spans name most of what the chip waited for, even at a size
    # where a fit is 15 ms and the two clocks differ by a tenth of one
    total = sum(r["seconds"] for r in rows)
    named = sum(sum(r["activities"].values()) for r in rows)
    assert total == pytest.approx(14941056 * NS, abs=1e-12)
    assert named / total >= 0.85, (named, total)
    for r in rows:
        assert sum(r["activities"].values()) <= r["seconds"] * (1 + 1e-9)
        assert sum(r["phases"].values()) <= r["seconds"] * (1 + 1e-9)
    assert H.main([TRACE]) == 0
    out = capsys.readouterr().out
    assert line in out.splitlines()[0] and "| gap | at s | s |" in out
    assert H.main([]) == 2


# ---------------------------------------------------------------------------
# the span metrics the cells had read what they read before
# ---------------------------------------------------------------------------

ROWS = 4096


@pytest.mark.parametrize("cell", ["lr_sweep_4m", "svc_sweep_4m"])
def test_older_span_metrics_read_exactly_the_paths_they_read_before(
        cell, monkeypatch):
    from transmogrifai_tpu.perf.timers import recent_fit_profiles

    monkeypatch.setenv("TMOG_PALLAS", "interpret")
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    config = harness.load_config(bench, next(
        w["config"] for w in bench["workloads"] if w["name"] == cell))
    limits = dict(config["limits"], cv_metric_gap=config["limits"][
        "cv_metric_gap"] + 4.0 / ROWS)      # PERF.md, Open questions
    result = harness.run(cell, 2**31 + 31, 0.3, True, require_tpu=False,
                         overrides={"traffic": {"rows": ROWS},
                                    "config": {"limits": limits}},
                         free_device=False)
    assert result["correct"] is True and result["failed"] == 0
    calls = result["notes"]["calls"]
    fits = recent_fit_profiles()[-calls:]
    families = [fam["estimator"].rsplit(".", 1)[-1]
                for fam in config["families"]]
    dispatch = {f"validate.cv.dispatch.{name}" for name in families}
    tail = {"prep", "refit", "train_eval"}
    for wanted, metric in ((dispatch, "cv_dispatch_s"), (tail, "tail_s")):
        by_hand = sum(s.seconds for fit in fits for s in fit.spans
                      if s.path in wanted) / calls
        assert result["metrics"][metric]["value"] == pytest.approx(
            by_hand, abs=1e-9)
    # no new path matches the older readers' filters
    paths = {s.path for fit in fits for s in fit.spans}
    assert {p for p in paths if p.startswith("validate.cv.dispatch.")} \
        == dispatch
    assert tail <= paths and {p for p in paths if p.startswith("host.")}
    assert not any(p.startswith(("prep.", "refit.", "train_eval."))
                   for p in paths)
    # and the six new metrics are in the same traced line
    assert set(METRICS) <= set(result["metrics"])
    fit_s = sum(fit.end - fit.start for fit in fits) / calls
    assert result["metrics"]["host_unspanned_s"]["value"] <= 0.1 * fit_s
    assert len(max(fits, key=lambda f: len(f.spans)).spans) <= 200
