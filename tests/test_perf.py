"""perf/ subsystem tests: phase timers, compile probe, the content-addressed
executable cache for training sweeps and bucket-padding numerics (ISSUE 3
tentpole + satellites).

Key discipline mirrored from tests/test_serve.py: compile-at-most-once per
(program, bucket) and zero new XLA compilations on a warm refit.
"""

import os
import subprocess
import sys
import time

import numpy as np
import pytest

from transmogrifai_tpu.evaluators.base import BinaryClassificationEvaluator
from transmogrifai_tpu.models.logistic import LogisticRegression
from transmogrifai_tpu.models.svm import LinearSVC
from transmogrifai_tpu.models.trees import (
    GradientBoostedTreesClassifier,
    RandomForestClassifier,
)
from transmogrifai_tpu.models.tuning import CrossValidator
from transmogrifai_tpu.perf import (
    activity,
    cache_key_fingerprint,
    compile_snapshot,
    measure_compiles,
    phase,
    program_cache_stats,
    recent_fit_profiles,
    record_phases,
    run_cached,
)
from transmogrifai_tpu.perf.programs import program_cache_entries

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _binary(n=500, d=6, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, d)).astype(np.float32)
    w = rng.normal(size=d)
    y = (rng.random(n) < 1 / (1 + np.exp(-(x @ w)))).astype(np.float32)
    return x, y


def _small_models():
    """The default 4-family shape at test scale (small trees/rounds)."""
    return [
        (LogisticRegression(), [{"reg_param": 0.01},
                                {"reg_param": 0.1, "elastic_net": 0.5}]),
        (LinearSVC(), [{"reg_param": 0.01}]),
        (RandomForestClassifier(num_trees=6, max_depth=3), [{"max_depth": 3}]),
        (GradientBoostedTreesClassifier(num_rounds=5, max_depth=2),
         [{"num_rounds": 5}]),
    ]


class TestPhaseTimers:
    def test_nested_paths_and_totals(self):
        with record_phases() as rec:
            with phase("outer"):
                with phase("inner"):
                    time.sleep(0.01)
            with phase("other"):
                pass
        rep = rec.report()
        assert "outer" in rep and "outer.inner" in rep and "other" in rep
        assert rep["outer"] >= rep["outer.inner"] >= 0.01
        # total() is exact-path (a parent span already contains its children)
        assert abs(rec.total("outer") - rep["outer"]) < 1e-3

    def test_noop_without_recorder(self):
        with phase("nothing"):  # must not raise nor record anywhere
            pass

    def test_recorders_nest_additively(self):
        with record_phases() as outer:
            with record_phases() as inner:
                with phase("p"):
                    pass
        assert [s.path for s in outer.spans] == ["p"]
        assert [s.path for s in inner.spans] == ["p"]



def _tiny_selector(n=512, d=6, seed=0):
    """(selector wired to a dataset, dataset): LR by IRLS and FISTA + SVC."""
    from transmogrifai_tpu import Dataset, FeatureBuilder
    from transmogrifai_tpu.data.dataset import Column
    from transmogrifai_tpu.models.selector import (
        BinaryClassificationModelSelector,
    )
    from transmogrifai_tpu.types import OPVector, RealNN

    x, y = _binary(n=n, d=d, seed=seed)
    ds = Dataset({
        "label": Column(RealNN, y.astype(np.float64), np.ones(n, np.bool_)),
        "v": Column.vector(x)})
    label = FeatureBuilder.of("label", RealNN).extract_field().as_response()
    vec = FeatureBuilder.of("v", OPVector).extract_field().as_predictor()
    sel = BinaryClassificationModelSelector.with_cross_validation(
        num_folds=2, models=[
            (LogisticRegression(), [{"reg_param": 0.01},
                                    {"reg_param": 0.1, "elastic_net": 0.5}]),
            (LinearSVC(), [{"reg_param": 0.01}])])
    label.transform_with(sel, vec)
    return sel, ds


class TestHostActivities:
    """``activity()``: flat ``host.<name>`` spans from the same source as
    ``phase()``, into the same sinks."""

    def test_flat_path_with_parent_and_counts_under_any_stack(self):
        with record_phases() as rec:
            with activity("pad", nbytes=8):
                pass
            with phase("validate"):
                with phase("cv.dispatch.Fam"):
                    with activity("stamp", nbytes=3) as span:
                        span.note(hit=True)
                with activity("device_wait"):
                    pass
        got = {(s.path, s.parent): s for s in rec.spans}
        assert set(got) == {("host.pad", ""),
                            ("host.stamp", "validate.cv.dispatch.Fam"),
                            ("host.device_wait", "validate"),
                            ("validate.cv.dispatch.Fam", ""),
                            ("validate", "")}
        stamp = got[("host.stamp", "validate.cv.dispatch.Fam")]
        assert stamp.name == "stamp"
        assert stamp.counts == {"nbytes": 3, "hit": True}
        assert got[("host.device_wait", "validate")].counts == {}
        assert got[("validate", "")].counts is None
        # an activity leaves the phase stack alone and stays out of the
        # phases' paths: no path starts with or ends in a host segment
        assert not [p for p, _ in got if ".host." in p or p.endswith(".host")]
        rep = rec.report()
        assert set(rep) == {"host.pad", "host.stamp", "host.device_wait",
                            "validate", "validate.cv.dispatch.Fam"}

    def test_noop_without_recorder_or_tracer(self):
        with activity("nothing", nbytes=1) as span:
            span.note(hit=False)       # must not raise nor record anywhere
        assert span.token is None and span.counts == {"nbytes": 1}

    def test_lands_in_every_recorder_and_in_the_obs_tracer(self):
        from transmogrifai_tpu.obs import trace as obs_trace
        from transmogrifai_tpu.obs.trace import Tracer

        obs_trace.uninstall_tracer()
        tracer = obs_trace.install_tracer(Tracer())
        try:
            with record_phases() as outer:
                with phase("fit.sel"):
                    with record_phases() as inner:
                        with phase("refit"):
                            with activity("launch", label="p"):
                                pass
        finally:
            obs_trace.uninstall_tracer()
        (a,) = [s for s in inner.spans if s.path == "host.launch"]
        (b,) = [s for s in outer.spans if s.path == "host.launch"]
        # the parent is relative to each recorder, as a phase's path is
        assert (a.parent, b.parent) == ("refit", "fit.sel.refit")
        assert a.counts == b.counts == {"label": "p"}
        evs = {e["name"]: e for e in tracer.chrome_trace()["traceEvents"]
               if e.get("ph") == "X"}
        assert evs["host.launch"]["cat"] == "train"
        assert evs["host.launch"]["args"] == {"parent": "fit.sel.refit",
                                              "label": "p"}
        assert "fit.sel.refit" in evs
        # a tracer alone (no recorder) is a sink too
        tracer = obs_trace.install_tracer(Tracer())
        try:
            with activity("pad", nbytes=2):
                pass
        finally:
            obs_trace.uninstall_tracer()
        assert [e["name"] for e in tracer.chrome_trace()["traceEvents"]
                if e.get("ph") == "X"] == ["host.pad"]

    def test_recorder_is_stamped_with_its_start_and_end(self):
        t0 = time.perf_counter()
        with record_phases() as rec:
            with phase("p"):
                pass
        assert t0 <= rec.start <= rec.spans[0].start
        assert rec.spans[0].start + rec.spans[0].seconds <= rec.end


class TestFitProfiles:
    def test_spans_land_in_the_profilers_trace_inside_their_parents(
            self, tmp_path):
        """A tiny fit under ``jax.profiler``: the phases and the host
        activities are on the host plane, on the line of the thread that
        ran the fit, each inside the interval of its parent."""
        import glob

        import jax
        from jax.profiler import ProfileData

        sel, ds = _tiny_selector()
        sel.fit(ds)                                     # compiles
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            with jax.profiler.TraceAnnotation("the_fit"):
                sel.fit(ds)
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                                / "*.xplane.pb"))
        (plane,) = [p for p in ProfileData.from_file(path).planes
                    if p.name == "/host:CPU"]
        (line,) = [ln for ln in plane.lines
                   if any(ev.name == "the_fit" for ev in ln.events)]
        spans = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                  dict(ev.stats).get("span")) for ev in line.events]
        ours = [s for s in spans if s[3] in ("phase", "activity")]
        names = {s[0] for s in ours}
        assert {"prep", "validate", "refit", "train_eval",
                "validate.cv.dispatch.LogisticRegression",
                "validate.cv.gather.LinearSVC", "host.fold_weights",
                "host.stamp", "host.pad", "host.launch", "host.program_key",
                "host.device_wait"} <= names
        assert {s[3] for s in ours if s[0].startswith("host.")} == {
            "activity"}

        # one source, two sinks: the trace's events are the recorder's spans,
        # in the same order on the one thread
        profile = sel.last_fit_profile
        ours.sort(key=lambda s: (s[1], -s[2]))
        recorded = sorted(profile.spans, key=lambda s: (s.start, -s.seconds))
        assert [s[0] for s in ours] == [s.path for s in recorded]
        (fit,) = [s for s in spans if s[0] == "the_fit"]
        for event, span in zip(ours, recorded):
            around = [p for p in ours if p is not event
                      and p[1] <= event[1] and event[2] <= p[2]]
            tightest = min(around, key=lambda p: p[2] - p[1]) if around \
                else fit
            assert tightest[1] <= event[1] and event[2] <= tightest[2]
            if span.path.startswith("host."):
                want = span.parent or "the_fit"
            else:
                want = "validate" if span.path.startswith("validate.") \
                    else "the_fit"
            assert tightest[0] == want, (span.path, span.parent)

    def test_a_fit_keeps_to_200_spans_and_leaves_little_unspanned(self):
        sel, ds = _tiny_selector(n=8192)
        sel.fit(ds)

        def unspanned_share(profile):
            host = sorted((s.start, s.start + s.seconds)
                          for s in profile.spans if s.path.startswith("host."))
            covered, edge = 0.0, profile.start
            for lo, hi in host:
                covered += max(0.0, hi - max(lo, edge))
                edge = max(edge, hi)
            fit_s = profile.end - profile.start
            return (fit_s - covered) / fit_s

        shares = []
        for _ in range(3):       # a host that is busy elsewhere slows the
            sel.fit(ds)          # python between the spans: the best counts
            shares.append(unspanned_share(sel.last_fit_profile))
        profile = sel.last_fit_profile
        assert len(profile.spans) <= 200
        assert min(shares) <= 0.10, shares
        # every path report() gave before the activities keeps its name
        rep = profile.report()
        assert {"prep", "validate", "refit", "train_eval",
                "validate.cv.dispatch.LinearSVC",
                "validate.cv.gather.LogisticRegression"} <= set(rep)
        assert not [p for p in rep if p.startswith("host.")
                    and p.count(".") != 1]

    def test_recent_fit_profiles_is_bounded_and_ordered(self):
        from transmogrifai_tpu.perf import timers

        sel, ds = _tiny_selector(n=256)
        sel.fit(ds)
        first = sel.last_fit_profile
        sel.fit(ds)
        recent = recent_fit_profiles()
        assert recent[-1] is sel.last_fit_profile and recent[-2] is first
        assert recent[-2].end <= recent[-1].start
        for _ in range(timers._RECENT_FITS.maxlen + 5):
            timers.keep_fit_profile(first)
        recent = recent_fit_profiles()
        assert len(recent) == timers._RECENT_FITS.maxlen == 128
        assert recent[-1] is first
        # a copy: a reader cannot disturb the ring
        recent.clear()
        assert len(recent_fit_profiles()) == 128

    def test_placement_stats_count_a_miss_then_a_hit(self):
        from transmogrifai_tpu.parallel import mesh as M

        rng = np.random.default_rng(int(time.time_ns()) % 2**32)
        block = rng.normal(size=(300, 4)).astype(np.float32)
        vec = rng.normal(size=300).astype(np.float32)
        before = M.placement_stats()
        with record_phases() as rec:
            M.place_rows_bucketed_cached(block)
            M.place_rows_bucketed_cached(block.copy())    # same content
            M.place_cached(vec, (M.DATA_AXIS,))
            M.place_cached(vec.copy(), (M.DATA_AXIS,))
        after = M.placement_stats()
        moved = {c: {k: after[c][k] - before[c][k] for k in after[c]}
                 for c in after}
        padded = M.bucket_size(300) * 4 * 4
        assert moved == {
            "rows": {"hits": 1, "misses": 1,
                     "bytes_stamped": 2 * block.nbytes,
                     "bytes_placed": padded},
            "aux": {"hits": 1, "misses": 1, "bytes_stamped": 2 * vec.nbytes,
                    "bytes_placed": vec.nbytes},
            # neither call is a fit's: nothing passed through, none derived
            "fit": {"passed_through": 0, "bytes_passed": 0, "derived": 0,
                    "bytes_derived": 0},
            # no mesh is active: nothing sharded, replicated or degraded
            "mesh": {"bytes_sharded": 0, "bytes_replicated": 0,
                     "degraded": 0, "bytes_degraded": 0}}
        # a miss records the host side of its transfer; a hit records none
        paths = [s.path for s in rec.spans]
        assert paths.count("host.h2d") == 2 and paths.count("host.stamp") == 4
        assert paths.count("host.pad") == 1
        assert [s.counts for s in rec.spans if s.path == "host.h2d"] == [
            {"nbytes": padded}, {"nbytes": vec.nbytes}]
        assert all(s.counts["hit"] is False for s in rec.spans
                   if s.path == "host.stamp")


@pytest.mark.parametrize("program,scopes", [
    ("irls", ["irls_hessian", "irls_solve"]),
    ("fista", ["fista_step"]),
    ("svc", ["svc_step", "fold_standardize", "eval_sort"]),
    ("eval", ["eval_sort"]),
])
def test_linear_programs_carry_stable_scope_names(program, scopes):
    """Metadata only: the scope names are in the lowered programs' debug
    info, for a reader of per-kernel time from a trace's op metadata."""
    import jax.numpy as jnp

    from transmogrifai_tpu.evaluators import metrics as M
    from transmogrifai_tpu.models import base, logistic, svm

    n, d, k, g = 64, 4, 2, 2
    x = jnp.ones((n, d + 1), jnp.float32)
    y = jnp.ones((n,), jnp.float32)
    w = jnp.ones((k, n), jnp.float32)
    regs = jnp.ones((g,), jnp.float32)
    metric = M.METRICS_BINARY["auPR"]
    if program == "irls":
        lowered = logistic._irls_sweep.lower(x, y, w, regs, max_iter=2)
    elif program == "fista":
        lowered = logistic._fista_sweep.lower(x, y, w, regs, regs,
                                              max_iter=2)
    elif program == "svc":
        lowered = svm._svc_cv_program.lower(
            x[:, :d], y, y, w, w, regs, max_iter=2, has_intercept=True,
            metric_fn=metric)
    else:
        lowered = base._eval_linear_sweep_for(None).lower(
            x, y, jnp.ones((g, k, d + 1), jnp.float32), w, metric_fn=metric,
            link="sigmoid")
    text = lowered.as_text(debug_info=True)
    for scope in scopes:
        assert scope in text, scope
    assert not any(scope in lowered.as_text() for scope in scopes)

class TestCompileProbe:
    def test_counts_new_compilations_only(self):
        import jax
        import jax.numpy as jnp

        salt = time.time_ns()  # unique program: never jit-cached before

        @jax.jit
        def f(v):
            return jnp.sin(v).sum() + salt % 7

        v = jnp.arange(8, dtype=jnp.float32)
        with measure_compiles() as c:
            f(v)
        assert c.backend_compiles >= 1
        with measure_compiles() as c2:
            f(v)
        assert c2.backend_compiles == 0

    def test_snapshot_monotone(self):
        a = compile_snapshot()
        b = compile_snapshot()
        assert b.backend_compiles >= a.backend_compiles


class TestExecutableCache:
    def test_compile_once_then_hits(self):
        import jax

        salt = time.time_ns()

        @jax.jit
        def g(v):
            return (v * 2).sum() + salt % 5

        v = np.ones(16, np.float32)
        run_cached(g, v, label="t/compile_once")
        before = {k: s.compiles for k, s in program_cache_entries().items()
                  if s.label == "t/compile_once"}
        assert sum(before.values()) == 1
        with measure_compiles() as c:
            run_cached(g, v, label="t/compile_once")
        assert c.backend_compiles == 0
        entry = [s for s in program_cache_entries().values()
                 if s.label == "t/compile_once"]
        assert len(entry) == 1 and entry[0].compiles == 1 \
            and entry[0].hits == 1

    def test_invalidation_on_statics_shapes_and_layout(self):
        """New statics, a new lane layout (fold-weight shape), or a flipped
        key_extras layout knob each get their own executable; repeats hit."""
        from functools import partial

        import jax

        salt = time.time_ns()

        @partial(jax.jit, static_argnames=("scale",))
        def h(v, w, scale=2):
            return (v[None, :] * w).sum() * scale

        def n_entries():
            return sum(1 for s in program_cache_entries().values()
                       if s.label == "t/invalidation")

        v = np.ones(32, np.float32) * (salt % 3 + 1)
        w2 = np.ones((2, 32), np.float32)
        w3 = np.ones((3, 32), np.float32)
        run_cached(h, v, w2, statics=dict(scale=2), label="t/invalidation")
        base = n_entries()
        run_cached(h, v, w2, statics=dict(scale=2), label="t/invalidation")
        assert n_entries() == base                      # repeat: pure hit
        run_cached(h, v, w2, statics=dict(scale=3), label="t/invalidation")
        assert n_entries() == base + 1                  # grid/static change
        run_cached(h, v, w3, statics=dict(scale=2), label="t/invalidation")
        assert n_entries() == base + 2                  # lane-layout change
        run_cached(h, v, w2, statics=dict(scale=2),
                   key_extras=dict(fold_vmap=True), label="t/invalidation")
        assert n_entries() == base + 3                  # layout knob change

    def test_key_fingerprint_stable_across_processes(self):
        """The content-addressed key must be identical in a fresh
        interpreter — shapes + statics + program source, no id()s."""
        script = (
            "import os; os.environ.setdefault('JAX_PLATFORMS','cpu')\n"
            "import numpy as np\n"
            "from transmogrifai_tpu.models.logistic import _irls_sweep\n"
            "from transmogrifai_tpu.perf import cache_key_fingerprint\n"
            "x=np.zeros((1024,9),np.float32); y=np.zeros(1024,np.float32)\n"
            "tw=np.zeros((3,1024),np.float32); r=np.zeros(4,np.float32)\n"
            "print(cache_key_fingerprint(_irls_sweep, x, y, tw, r,"
            " statics=dict(max_iter=30, has_intercept=True)))\n"
        )
        fps = []
        for _ in range(2):
            out = subprocess.run(
                [sys.executable, "-c", script], cwd=REPO, env={
                    **os.environ, "JAX_PLATFORMS": "cpu",
                    "XLA_FLAGS": "--xla_force_host_platform_device_count=8"},
                capture_output=True, text=True, timeout=180)
            assert out.returncode == 0, out.stderr[-2000:]
            fps.append(out.stdout.strip().splitlines()[-1])
        assert fps[0] == fps[1]
        # and the in-process fingerprint matches the subprocess ones
        from transmogrifai_tpu.models.logistic import _irls_sweep

        local = cache_key_fingerprint(
            _irls_sweep, np.zeros((1024, 9), np.float32),
            np.zeros(1024, np.float32), np.zeros((3, 1024), np.float32),
            np.zeros(4, np.float32),
            statics=dict(max_iter=30, has_intercept=True))
        assert local == fps[0]

    def test_persistent_cache_roundtrip(self, tmp_path):
        """Process A compiles a sweep program into the persistent cache;
        process B (fresh interpreter, same key) must HIT it instead of
        backend-compiling (satellite: key stability across processes).  The
        directory is placed from outside through JAX's own
        ``JAX_COMPILATION_CACHE_DIR``; the library persists every compile
        (minimum compile time 0), so this sub-second program round-trips
        with no knob of its own."""
        script = (
            "import numpy as np\n"
            "from transmogrifai_tpu.perf import (measure_compiles,"
            " compile_snapshot, run_cached, enable_persistent_cache)\n"
            "from transmogrifai_tpu.models.logistic import _irls_sweep\n"
            "rng=np.random.default_rng(0)\n"
            "x=rng.normal(size=(512,5)).astype(np.float32)\n"
            "y=(rng.random(512)<.5).astype(np.float32)\n"
            "tw=np.ones((2,512),np.float32); r=np.asarray([0.1,0.2],np.float32)\n"
            "with measure_compiles() as c:\n"
            "    run_cached(_irls_sweep, x, y, tw, r,"
            " statics=dict(max_iter=5, has_intercept=True))\n"
            "s=compile_snapshot()\n"
            "print('STATS', c.backend_compiles, s.persistent_cache_hits,"
            " s.persistent_cache_misses)\n"
            "print('DIR', enable_persistent_cache())\n"
        )
        env = {**os.environ, "JAX_PLATFORMS": "cpu",
               "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
               "JAX_COMPILATION_CACHE_DIR": str(tmp_path)}
        stats = []
        for _ in range(2):
            out = subprocess.run([sys.executable, "-c", script], cwd=REPO,
                                 env=env, capture_output=True, text=True,
                                 timeout=240)
            assert out.returncode == 0, out.stderr[-2000:]
            lines = out.stdout.splitlines()
            line = [ln for ln in lines if ln.startswith("STATS")][-1]
            stats.append([int(v) for v in line.split()[1:]])
            assert f"DIR {tmp_path}" in lines
        (compiles_a, _, miss_a), (compiles_b, hit_b, _) = stats
        assert miss_a >= 1          # first process wrote the cache
        assert hit_b >= 1           # second process read it back
        assert compiles_b < compiles_a
        assert os.listdir(tmp_path)  # entries actually landed on disk

    def test_cache_dir_is_placed_from_outside(self, monkeypatch):
        """With ``JAX_COMPILATION_CACHE_DIR`` (or an earlier
        ``jax.config.update``) holding a directory, the library sets NO
        directory of its own; with nothing set it uses the one fixed
        in-checkout path."""
        import jax

        from transmogrifai_tpu.perf import programs

        assert programs.DEFAULT_CACHE_DIR == os.path.join(REPO, ".jax_cache")
        updates = []
        real_update = jax.config.update

        def spy(name, value):
            updates.append(name)
            real_update(name, value)

        before = jax.config.jax_compilation_cache_dir
        monkeypatch.setattr(jax.config, "update", spy)
        try:
            real_update("jax_compilation_cache_dir", "/placed/from/outside")
            assert programs.enable_persistent_cache() \
                == "/placed/from/outside"
            assert "jax_compilation_cache_dir" not in updates
            real_update("jax_compilation_cache_dir", None)
            assert programs.enable_persistent_cache() \
                == programs.DEFAULT_CACHE_DIR
            assert updates.count("jax_compilation_cache_dir") == 1
        finally:
            real_update("jax_compilation_cache_dir", before)
        src = open(programs.__file__).read()
        assert src.count('update("jax_compilation_cache_dir"') == 1
        for stale in ("TMOG_XLA_CACHE_DIR", "expanduser"):
            assert stale not in src


class TestSweepCacheOnSelector:
    def test_second_fit_zero_compiles_and_once_per_family_bucket(self):
        """Acceptance: a second fit of the (4-family shape) selector sweep in
        the same process performs 0 new XLA compilations, and every sweep
        program compiled at most once per (family, bucket) key."""
        from transmogrifai_tpu.data.dataset import Column, Dataset
        from transmogrifai_tpu.models.selector import ModelSelector
        from transmogrifai_tpu.models.tuning import DataBalancer

        x, y = _binary(n=700)
        ds = Dataset({"label": Column.from_values(
            __import__("transmogrifai_tpu").types.RealNN, list(y.astype(float))),
            "v": Column.vector(x)})
        from transmogrifai_tpu import FeatureBuilder
        from transmogrifai_tpu.types import OPVector, RealNN

        label = FeatureBuilder.of("label", RealNN).extract_field().as_response()
        vec = FeatureBuilder.of("v", OPVector).extract_field().as_predictor()
        ev = BinaryClassificationEvaluator("auPR")
        sel = ModelSelector(models=_small_models(),
                            validator=CrossValidator(ev, num_folds=2, seed=3),
                            splitter=DataBalancer())
        label.transform_with(sel, vec)
        m1 = sel.fit(ds)
        with measure_compiles() as c:
            m2 = sel.fit(ds)
        assert c.backend_compiles == 0, \
            f"warm selector fit recompiled {c.backend_compiles} programs"
        assert m1.summary.best_model_name == m2.summary.best_model_name
        # compile-at-most-once per (program, operand-signature) key
        for key, s in program_cache_entries().items():
            assert s.compiles <= 1, (s.label, s.shapes, s.compiles)
        # the phase profile of the fit is recorded (chipbench's span readers
        # and chip_smoke.py read it)
        rep = sel.last_fit_profile.report()
        assert any(p.startswith("validate") for p in rep)
        assert "refit" in rep
        # ... and it splits the validate phase by family: one dispatch and
        # one gather span per family of the sweep, read off the ONE real fit
        # (nothing is re-run in isolation to get a per-family number)
        fams = {type(est).__name__ for est, _grids in _small_models()}
        for step in ("dispatch", "gather"):
            got = {p.split(".")[3] for p in rep
                   if p.startswith(f"validate.cv.{step}.")}
            assert got == fams, (step, got)
        # the process-wide counters saw the sweep's programs
        assert compile_snapshot().backend_compiles >= 1
        stats = program_cache_stats()
        assert stats["programs_compiled"] >= 1 and stats["cache_hits"] >= 1

    def test_bucket_padding_numerics_match_exact_fit(self):
        """Acceptance: padded-bucket sweep results match unpadded fits —
        same winner, metrics within 1e-6 — on the fixture sweep."""
        from transmogrifai_tpu.parallel import mesh as M

        x, y = _binary(n=777, d=5, seed=4)
        ev = BinaryClassificationEvaluator("auPR")
        cv = CrossValidator(ev, num_folds=2, seed=11)
        tw, vw = cv.fold_weights(y, np.ones_like(y))
        models = _small_models()
        metric = ev.metric_fn()

        def sweep_all():
            out = {}
            for est, grids in models:
                out[type(est).__name__] = est.cv_sweep(
                    x, y, tw, vw, grids, metric)
            return out

        bucketed = sweep_all()
        orig = M.bucket_size
        M.bucket_size = lambda n, minimum=1024: int(n)  # exact shapes
        # the placement cache keys on (shape, content, mesh) of the SOURCE
        # block — not on the bucket function — so the bucketed placement
        # must be dropped or the exact-shape run would reuse it
        M._PLACED_ROWS_CACHE.clear()
        M._PLACED_AUX_CACHE.clear()
        try:
            exact = sweep_all()
        finally:
            M.bucket_size = orig
            M._PLACED_ROWS_CACHE.clear()
            M._PLACED_AUX_CACHE.clear()
        for fam in bucketed:
            np.testing.assert_allclose(
                bucketed[fam], exact[fam], atol=1e-6, rtol=0,
                err_msg=f"bucket padding changed {fam} CV metrics")
        flat_b = np.concatenate([v.ravel() for v in bucketed.values()])
        flat_e = np.concatenate([v.ravel() for v in exact.values()])
        assert int(np.nanargmax(flat_b)) == int(np.nanargmax(flat_e))
