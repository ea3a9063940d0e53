"""The compile probe's spans (ISSUE 36): what ``jax.monitoring`` tells the
listeners of ``perf/timers.py`` becomes ``host.trace`` / ``host.lower`` /
``host.cache_load`` / ``host.backend_compile`` spans of the running fit,
labelled with the program that was being launched.

Three fits of one tiny selector in a persistent cache of their own: the
first compiles, the second finds everything in memory, the third (in-memory
caches cleared) loads from the persistent cache.  CPU only: counts, labels
and nesting, never a time but for the one share a first fit's ``host.launch``
has to keep to.
"""

from __future__ import annotations

import jax
import numpy as np
import pytest

from chipbench.setuplib import nested
from chipbench.spanlib import _activities, innermost_seconds
from transmogrifai_tpu.perf import timers
from transmogrifai_tpu.perf.programs import clear_program_cache
from transmogrifai_tpu.perf.timers import (
    CompileStats, activity, compile_phase, compile_snapshot, phase,
    record_phases)

#: ``compile_phase(label)`` is the phase ``compile.<label>``
COMPILE_PHASE = "compile."

PROBE = ("host.trace", "host.lower", "host.cache_load",
         "host.backend_compile")
FIRST, SECOND, LOADED = 0, 1, 2
#: ``run_cached`` programs and direct jit calls of the tiny fit
CACHED = "LogisticRegression/irls_sweep"
DIRECT = ("BinaryClassificationEvaluator/summary",
          "LogisticRegression/irls_refit")


def _selector(n=1100, d=5):
    from transmogrifai_tpu import Dataset, FeatureBuilder
    from transmogrifai_tpu.data.dataset import Column
    from transmogrifai_tpu.models.logistic import LogisticRegression
    from transmogrifai_tpu.models.selector import (
        BinaryClassificationModelSelector)
    from transmogrifai_tpu.types import OPVector, RealNN

    rng = np.random.default_rng(36)
    x = rng.normal(size=(n, d)).astype(np.float32)
    y = (x[:, 0] + rng.normal(size=n) > 0).astype(np.float64)
    ds = Dataset({"label": Column(RealNN, y, np.ones(n, np.bool_)),
                  "v": Column.vector(x)})
    label = FeatureBuilder.of("label", RealNN).extract_field().as_response()
    vec = FeatureBuilder.of("v", OPVector).extract_field().as_predictor()
    sel = BinaryClassificationModelSelector.with_cross_validation(
        num_folds=2, models=[(LogisticRegression(),
                              [{"reg_param": 0.01}, {"reg_param": 0.1}])])
    label.transform_with(sel, vec)
    return sel, ds


@pytest.fixture(scope="module")
def fits(tmp_path_factory):
    """[(profile, compile counters moved)] of the three fits."""
    from jax.experimental.compilation_cache import compilation_cache

    keep = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    jax.config.update("jax_compilation_cache_dir",
                      str(tmp_path_factory.mktemp("jax_cache")))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    compilation_cache.reset_cache()
    out = []
    try:
        for which in (FIRST, SECOND, LOADED):
            if which != SECOND:
                clear_program_cache()
                jax.clear_caches()
            sel, ds = _selector()
            before = compile_snapshot()
            sel.fit(ds)
            out.append((sel.last_fit_profile,
                        compile_snapshot().minus(before)))
    finally:
        for k, v in keep.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
    return out


def _probe(profile, path=None):
    return [s for s in profile.spans
            if (s.path == path if path else s.path in PROBE)]


@pytest.mark.parametrize("path", ["host.trace", "host.lower",
                                  "host.backend_compile"])
def test_a_first_fit_records_what_it_traced_lowered_and_compiled(fits, path):
    assert _probe(fits[FIRST][0], path)
    assert not _probe(fits[FIRST][0], "host.cache_load")


@pytest.mark.parametrize("label", [CACHED, *DIRECT])
@pytest.mark.parametrize("which", [FIRST, LOADED])
def test_run_cached_programs_and_direct_jit_calls_are_labelled(
        fits, which, label):
    closing = "host.backend_compile" if which == FIRST else "host.cache_load"
    for path in ("host.trace", "host.lower", closing):
        assert label in {s.counts["label"]
                         for s in _probe(fits[which][0], path)}, path


def test_a_second_fit_records_none(fits):
    profile, moved = fits[SECOND]
    assert _probe(profile) == []
    assert moved.to_dict() == CompileStats().to_dict()


def test_the_persistent_cache_answers_the_third_fit(fits):
    profile, moved = fits[LOADED]
    loads = _probe(profile, "host.cache_load")
    assert loads and not _probe(profile, "host.backend_compile")
    assert moved.backend_compiles == 0 and moved.compile_seconds == 0.0
    assert moved.cache_load_seconds == pytest.approx(
        sum(s.seconds for s in loads))
    for s in loads:
        # the retrieval is inside the request it answered
        assert 0.0 < s.counts["retrieval_s"] <= s.seconds + 1e-3
    # ... and is spent with it
    assert "retrieval_s" not in vars(timers._TL)


def test_a_load_does_not_carry_the_retrieval_of_the_one_before():
    with record_phases() as rec:
        for retrieval in (0.125, None):
            timers._on_event(timers._EV_CACHE_HIT)
            if retrieval is not None:
                timers._on_duration(timers._EV_CACHE_RETRIEVAL, retrieval)
            timers._on_duration(timers._EV_BACKEND_COMPILE, 0.5)
    assert [s.counts["retrieval_s"] for s in rec.spans] == [0.125, 0.0]
    assert rec.compile_table()["unlabelled"]["retrieval_s"] == 0.125


@pytest.mark.parametrize("which", [FIRST, SECOND, LOADED])
def test_spans_equal_the_counters(fits, which):
    profile, moved = fits[which]
    assert len(_probe(profile, "host.cache_load")) == \
        moved.persistent_cache_hits
    assert len(_probe(profile, "host.backend_compile")) == \
        moved.backend_compiles
    assert moved.trace_seconds == pytest.approx(
        sum(s.seconds for s in _probe(profile, "host.trace")))
    assert moved.lower_seconds == pytest.approx(
        sum(s.seconds for s in _probe(profile, "host.lower")))
    assert moved.compile_seconds == pytest.approx(
        sum(s.seconds for s in _probe(profile, "host.backend_compile")))


@pytest.mark.parametrize("which", [FIRST, LOADED])
def test_every_span_is_flat_labelled_and_inside_what_it_is_put_down_to(
        fits, which):
    profile = fits[which][0]
    launches = [s for s in profile.spans if s.path == "host.launch"]
    compiles = [s for s in profile.spans
                if s.name.startswith(COMPILE_PHASE)]
    assert compiles
    for s in _probe(profile):
        assert s.path == "host." + s.name and s.seconds >= 0.0
        label = s.counts["label"]
        assert label != "unlabelled"
        around = [e for e in launches if e.counts["label"] == label] + [
            e for e in compiles if e.name == COMPILE_PHASE + label]
        # jax times the interval on ``time.time``: a millisecond of slack
        assert any(e.start - 1e-3 <= s.start and
                   s.start + s.seconds <= e.start + e.seconds + 1e-3
                   for e in around), (s.path, label)
        # the open phase path, like every activity's: its launch's own, or
        # the compile phase it lies in
        assert s.parent in {e.parent if e.path == "host.launch" else e.path
                            for e in around}


def test_host_launch_is_a_dispatch_again(fits):
    """The probe's spans take what a first fit traced, lowered and compiled
    out of ``host.launch``'s self time; what stays is the dispatch and jit's
    own bookkeeping round a cache miss."""
    first, second = fits[FIRST][0], fits[SECOND][0]
    whole = first.total("host.launch")
    # under load the listener hears late: ``nested`` puts an outer span
    # dated after its inner one's start back round it
    self_first = innermost_seconds(nested(_activities(first)))["host.launch"]
    self_second = innermost_seconds(_activities(second))["host.launch"]
    # nothing lies inside a warm launch
    assert self_second == pytest.approx(second.total("host.launch"))
    assert 0.0 < self_first < 0.25 * whole


@pytest.mark.parametrize("which", [FIRST, LOADED])
def test_compile_table_rows(fits, which):
    profile = fits[which][0]
    table = profile.compile_table()
    assert {CACHED, *DIRECT} <= set(table)
    keys = ["trace_s", "traces", "lower_s", "lowers", "cache_load_s",
            "cache_loads", "backend_compile_s", "backend_compiles",
            "retrieval_s"]
    for row in table.values():
        assert list(row) == keys
    for path, (secs, count) in timers._COMPILE_KEYS.items():
        spans = _probe(profile, path)
        assert sum(r[count] for r in table.values()) == len(spans)
        # self time: never more than the spans' own lengths, and the whole
        # of them where nothing nests (a load, a compilation)
        total = sum(r[secs] for r in table.values())
        assert total <= sum(s.seconds for s in spans) + 1e-6
        if path in ("host.cache_load", "host.backend_compile"):
            assert total == pytest.approx(sum(s.seconds for s in spans))
    closing = "backend_compiles" if which == FIRST else "cache_loads"
    other = "cache_loads" if which == FIRST else "backend_compiles"
    assert table[CACHED][closing] == 1 and table[CACHED][other] == 0
    assert table[CACHED]["lowers"] == 1
    # the disk read against the rest of a load
    for row in table.values():
        assert 0.0 <= row["retrieval_s"] <= row["cache_load_s"] + 1e-3
        assert (row["retrieval_s"] > 0.0) == (row["cache_loads"] > 0)
    # self seconds of the table are those of the span reader
    own = innermost_seconds(_activities(profile))
    for path, (secs, _) in timers._COMPILE_KEYS.items():
        assert sum(r[secs] for r in table.values()) == pytest.approx(
            own.get(path, 0.0), abs=1e-6)


def test_compile_table_counts_a_nested_trace_once():
    rec = timers.PhaseRecorder()
    for name, start, seconds, label in [
            ("trace", 0.0, 1.0, "a"), ("trace", 0.2, 0.3, "a"),
            ("lower", 1.0, 2.0, "a"), ("trace", 1.5, 0.5, "a"),
            ("backend_compile", 3.0, 4.0, "a"),
            ("cache_load", 8.0, 0.25, "b")]:
        counts = {"label": label}
        if name == "cache_load":
            counts["retrieval_s"] = 0.125
        rec.add(timers.Span(name=name, path="host." + name, start=start,
                            seconds=seconds, counts=counts))
    assert rec.compile_table() == {
        "a": {"trace_s": 1.5, "traces": 3, "lower_s": 1.5, "lowers": 1,
              "cache_load_s": 0.0, "cache_loads": 0,
              "backend_compile_s": 4.0, "backend_compiles": 1,
              "retrieval_s": 0.0},
        "b": {"trace_s": 0.0, "traces": 0, "lower_s": 0.0, "lowers": 0,
              "cache_load_s": 0.25, "cache_loads": 1,
              "backend_compile_s": 0.0, "backend_compiles": 0,
              "retrieval_s": 0.125}}


@pytest.mark.parametrize("site, want", [
    ("launch", "Fam/direct"), ("compile", "Fam/cached"),
    ("bare", "unlabelled")])
def test_a_span_takes_the_label_of_what_is_open(site, want):
    """A freshly jitted function compiles under an open ``host.launch``,
    under ``run_cached``'s compile phase, and under neither."""
    fn = jax.jit(lambda v: v * 3.0 + float(len(site)))
    with record_phases() as rec:
        with phase("refit"):
            if site == "launch":
                with activity("launch", label="Fam/direct"):
                    fn(np.ones(3, np.float32))
            elif site == "compile":
                with compile_phase("Fam/cached"):
                    fn.lower(np.ones(3, np.float32)).compile()
            else:
                fn(np.ones(3, np.float32))
    spans = _probe(rec)
    assert {s.path for s in spans} >= {"host.trace", "host.lower"}
    assert {s.counts["label"] for s in spans} == {want}
    # jax's own name of what it traced, lowered or compiled: the lambda,
    # and the operations inside it
    assert all(s.counts["fun"] for s in spans)
    assert any("<lambda>" in s.counts["fun"] for s in spans)
    want_parent = "refit." + COMPILE_PHASE + "Fam/cached" \
        if site == "compile" else "refit"
    assert {s.parent for s in spans} == {want_parent}
    # the label is the open launch's or compile phase's only while it is open
    assert timers._OPEN_LABEL.get() is None


def test_the_obs_tracer_gets_the_spans_at_their_own_start():
    from transmogrifai_tpu.obs import trace as obs_trace
    from transmogrifai_tpu.obs.trace import Tracer

    fn = jax.jit(lambda v: v - 36.0)
    obs_trace.uninstall_tracer()
    tracer = obs_trace.install_tracer(Tracer())
    try:
        with activity("launch", label="Fam/traced"):
            fn(np.ones(4, np.float32))
    finally:
        obs_trace.uninstall_tracer()
    events = [e for e in tracer.chrome_trace()["traceEvents"]
              if e.get("ph") == "X"]
    (launch,) = [e for e in events if e["name"] == "host.launch"]
    probe = [e for e in events if e["name"] in PROBE]
    assert {e["name"] for e in probe} >= {"host.trace", "host.lower"}
    for e in probe:
        assert e["cat"] == "train" and e["args"]["label"] == "Fam/traced"
        # a finished span, back-dated: it starts inside the launch that was
        # still open when the listener heard of it (microseconds, rounded)
        assert launch["ts"] - 1e3 <= e["ts"]
        assert e["ts"] + e["dur"] <= launch["ts"] + launch["dur"] + 1e3


def test_without_a_recorder_or_a_tracer_only_the_sums_move():
    fn = jax.jit(lambda v: v + 36.5)
    before = compile_snapshot()
    fn(np.ones(5, np.float32))
    moved = compile_snapshot().minus(before)
    assert moved.trace_seconds > 0.0 and moved.lower_seconds > 0.0
    assert moved.backend_compiles + moved.persistent_cache_hits == 1
    assert set(moved.to_dict()) == {
        "backend_compiles", "compile_seconds", "trace_seconds",
        "lower_seconds", "cache_load_seconds", "persistent_cache_hits",
        "persistent_cache_misses"}
    assert not hasattr(moved, "events")


def test_the_package_notes_its_own_import():
    import transmogrifai_tpu

    assert timers.package_import_seconds() > 0.0
    assert timers.package_import_seconds() == (
        transmogrifai_tpu._IMPORT_END - transmogrifai_tpu._IMPORT_START)
