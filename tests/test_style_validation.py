"""Style/hygiene validation as a test (reference ScalaStyleValidationTest role).

Every module must import cleanly (the registry serde depends on import-time
class registration), public stages must be constructible without arguments or
declare explicit ctor contracts, and docstrings must carry reference citations
for parity auditing.
"""

import importlib
import os
import pkgutil

import transmogrifai_tpu

PKG_ROOT = os.path.dirname(transmogrifai_tpu.__file__)


def _all_modules():
    out = []
    walk_errors = []
    for info in pkgutil.walk_packages([PKG_ROOT], prefix="transmogrifai_tpu.",
                                      onerror=walk_errors.append):
        if info.name.endswith("__main__"):
            continue  # executing entry points under pytest argv is not the goal
        out.append(info.name)
    assert not walk_errors, walk_errors  # a subpackage failed during the walk
    return out


class TestStyleValidation:
    def test_every_module_imports(self):
        failures = {}
        for name in _all_modules():
            try:
                importlib.import_module(name)
            except Exception as e:  # noqa: BLE001 - collecting all failures
                failures[name] = repr(e)
        assert not failures, failures

    def test_no_syntax_errors_anywhere(self):
        import ast

        for root, _dirs, files in os.walk(PKG_ROOT):
            for f in files:
                if f.endswith(".py"):
                    path = os.path.join(root, f)
                    with open(path) as fh:
                        ast.parse(fh.read(), filename=path)

    def test_stage_registry_covers_fitted_models(self):
        """Every registered stage class must be reachable by the model loader:
        the class registry is populated at import time, so the package __init__
        must import every module defining stages used in saved pipelines."""
        from transmogrifai_tpu.stages.base import STAGE_REGISTRY

        # a healthy registry is large; a sudden drop means a module stopped
        # importing (and saved models referencing its stages stop loading)
        assert len(STAGE_REGISTRY) > 80, len(STAGE_REGISTRY)

    def test_self_hosted_jax_hazard_lint(self):
        """The repo must be clean of its own TM3xx JAX hazards.

        Runs the opcheck AST-lint analyzers (docs/static_analysis.md) over
        every transform_columns/fit_columns/device_transform body in the
        package.  Intentional host syncs are allowlisted INLINE at the
        offending line with an ``# opcheck: allow(TM301) <reason>`` marker
        (e.g. the single end-of-kernel fetches in SanityChecker.fit_columns
        and LDAModel.transform_columns); anything unmarked fails here.
        """
        from transmogrifai_tpu.checkers.opcheck import lint_file

        findings = []
        for root, _dirs, files in os.walk(PKG_ROOT):
            for f in sorted(files):
                if not f.endswith(".py"):
                    continue
                path = os.path.join(root, f)
                for fi in lint_file(path):
                    rel = os.path.relpath(path, PKG_ROOT)
                    findings.append(
                        f"{rel}:{fi.lineno} {fi.code} {fi.qualname}: {fi.message}")
        assert not findings, (
            "unallowlisted JAX hazards in the package (fix them, or mark "
            "intentional ones inline with '# opcheck: allow(TMxxx) reason'):\n"
            + "\n".join(findings))

    def test_serve_perf_full_function_lint(self):
        """serve/, perf/, checkers/, cli/, workflow/, and readers/ hold hot
        paths NOT named transform_columns/fit_columns/device_transform, so
        the default gate above never saw them.  Lint EVERY function there
        (``only_names=None``) plus the TM306 concurrency rule: module-level
        mutable caches (the executable caches, the plan cache, the analyzer
        memo, the source-fingerprint memo) must only be mutated under their
        locks, and jit construction in those layers must be memoized
        (marked inline where it is — workflow/plan.py, checkers/irsnap.py).
        readers/ joined the gate with the continual-training control plane:
        its offset caches and the serve-side swap state are exactly the
        shared-mutable-state shape TM306 exists to police; perf/kernels/
        joined with the Pallas dispatch layer (ISSUE 10) — kernel bodies and
        the dispatch-mode state are hot-path code the default gate never
        named; obs/ joined with the unified telemetry backbone (ISSUE 11) —
        the process-global tracer/recorder installs and the metrics
        registry are exactly the module-level-mutable-state pattern TM306
        exists for, and every span site is hot-path code; the multi-tenant
        fleet registry (serve/registry.py, ISSUE 12) rides the serve/ walk —
        its tenant table, admission/eviction controller, and the batcher's
        shed scan are concurrent control-plane state, so the gate asserts
        the module is actually in the linted set (a rename/move must not
        silently drop it); data/ joined with the out-of-core chunked store
        (ISSUE 13) — the spill store, the chunk-local gather, and the
        prefetch pipeline (readers/prefetch.py) are hot ingest paths with
        exactly the thread-shared state (the prefetch queue/worker, the
        chunk writers) TM306 polices, so the gate also asserts both ingest
        modules are in the linted set; parallel/ joined with the pod-scale
        dp x mp substrate (ISSUE 15) — the placement/stamp caches
        (mesh.py) and the distributed bootstrap are exactly the
        module-level-mutable-state and hot-path shape the gate exists for,
        and the sharding-constraint helpers sit inside every traced sweep;
        deploy/ joined with the AOT artifact store (ISSUE 17) — the
        hydrate path writes into the live plan's executable table and the
        process-wide hit/miss counters from whatever thread registers the
        tenant, exactly the locked-module-state shape TM306 polices."""
        from transmogrifai_tpu.checkers.opcheck import (
            lint_file,
            lint_file_concurrency,
        )

        findings = []
        linted = []
        for sub in ("serve", "perf", "perf/kernels", "checkers", "cli",
                    "workflow", "readers", "obs", "data", "parallel",
                    "deploy"):
            d = os.path.join(PKG_ROOT, sub)
            for f in sorted(os.listdir(d)):
                if not f.endswith(".py"):
                    continue
                path = os.path.join(d, f)
                rel = os.path.relpath(path, PKG_ROOT)
                linted.append(rel)
                for fi in list(lint_file(path, only_names=None)) \
                        + list(lint_file_concurrency(path)):
                    findings.append(
                        f"{rel}:{fi.lineno} {fi.code} {fi.qualname}: "
                        f"{fi.message}")
        assert os.path.join("serve", "registry.py") in linted, \
            "the fleet registry module left the lint gate"
        for ingest_mod in (os.path.join("data", "chunked.py"),
                           os.path.join("readers", "prefetch.py"),
                           os.path.join("workflow", "ooc.py")):
            assert ingest_mod in linted, \
                f"the ingest module {ingest_mod} left the lint gate"
        for pod_mod in (os.path.join("parallel", "mesh.py"),
                        os.path.join("parallel", "distributed.py"),
                        os.path.join("perf", "kernels", "routing.py")):
            assert pod_mod in linted, \
                f"the pod-scale module {pod_mod} left the lint gate"
        for dep_mod in (os.path.join("deploy", "store.py"),
                        os.path.join("deploy", "bundle.py")):
            assert dep_mod in linted, \
                f"the deploy module {dep_mod} left the lint gate"
        assert not findings, (
            "unallowlisted hazards in serve//perf/ (fix them, or mark "
            "intentional ones inline with '# opcheck: allow(TMxxx) reason'):\n"
            + "\n".join(findings))

    def test_self_hosted_threads_gate(self):
        """ISSUE 16 acceptance gate: the TM31x whole-program concurrency
        analyzer (checkers/threadcheck.py) runs over the full threaded
        surface — every finding is either fixed or suppressed inline with a
        justified ``# opcheck: allow(TM31x)`` marker, so the gate starts and
        stays green.  The thread-model assertions keep the gate honest: a
        discovery regression that stopped seeing the background threads
        would otherwise turn this into a green nothing."""
        from transmogrifai_tpu.checkers.threadcheck import analyze_files

        paths = []
        for sub in ("serve", "obs", "parallel", "perf", "perf/kernels",
                    "checkers", "deploy"):
            d = os.path.join(PKG_ROOT, sub)
            paths += sorted(os.path.join(d, f) for f in os.listdir(d)
                            if f.endswith(".py"))
        paths += [os.path.join(PKG_ROOT, "workflow", "continual.py"),
                  os.path.join(PKG_ROOT, "workflow", "resilience.py"),
                  os.path.join(PKG_ROOT, "readers", "prefetch.py"),
                  os.path.join(PKG_ROOT, "data", "chunked.py")]
        analysis = analyze_files(paths)
        findings = [f"{os.path.relpath(f.filename, PKG_ROOT)}:{f.lineno} "
                    f"{f.code} {f.qualname}: {f.message}"
                    for f in analysis.findings]
        assert not findings, (
            "unallowlisted TM31x concurrency findings (fix them, or mark "
            "justified ones inline with '# opcheck: allow(TM31x) reason'):\n"
            + "\n".join(findings))
        model = analysis.model.to_dict()
        targets = {t["target"] for t in model["threads"]}
        assert {"MicroBatcher._run", "SwappableScorer._shadow_worker",
                "ChunkPrefetcher._run"} <= targets, targets
        assert len(model["lockOrderEdges"]) >= 3, model["lockOrderEdges"]

    def test_concurrency_rule_sees_through_the_caches(self):
        """The TM306 heuristic itself must keep WORKING on the real caches:
        stripping the lock from a known-locked mutation makes it fire.  (A
        rule that silently stopped matching would green-light future races.)
        """
        from transmogrifai_tpu.checkers.opcheck import lint_module_concurrency

        src = (
            "_CACHE = {}\n"
            "_CACHE_LOCK = __import__('threading').Lock()\n"
            "def locked(k, v):\n"
            "    with _CACHE_LOCK:\n"
            "        _CACHE[k] = v\n"
            "def racy(k, v):\n"
            "    _CACHE[k] = v\n"
            "def racy_method(k):\n"
            "    _CACHE.pop(k, None)\n"
            "def allowed(k, v):\n"
            "    _CACHE[k] = v  # opcheck: allow(TM306) import-time only\n")
        found = lint_module_concurrency(src)
        assert sorted((f.qualname, f.code) for f in found) == [
            ("racy", "TM306"), ("racy_method", "TM306")]

    def test_inline_allow_markers_still_needed(self):
        """Stale-marker guard: every inline ``opcheck: allow`` marker must sit
        in a file whose unsuppressed lint would actually fire — a marker that
        no longer suppresses anything should be deleted.  Re-lints with the
        WIDEST rule set (every function + the TM306 concurrency rule + the
        TM31x thread analyzer), since serve//perf/ markers may suppress
        findings outside the default hazard-function gate."""
        import re

        from transmogrifai_tpu.checkers.opcheck import (
            lint_module_concurrency,
            lint_source,
        )
        from transmogrifai_tpu.checkers.threadcheck import analyze_source

        marker = re.compile(r"opcheck:\s*allow\(TM\d{3}")  # same shape _ALLOW_RE accepts
        for root, _dirs, files in os.walk(PKG_ROOT):
            for f in sorted(files):
                if not f.endswith(".py"):
                    continue
                path = os.path.join(root, f)
                with open(path) as fh:
                    src = fh.read()
                marked = [i + 1 for i, line in enumerate(src.splitlines())
                          if marker.search(line)
                          and not line.lstrip().startswith("#")]  # docs, not markers
                if not marked:
                    continue
                # strip the markers and re-lint: each marked line must fire
                stripped = "\n".join(
                    re.sub(r"#\s*opcheck:\s*allow\([^)]*\).*", "", line)
                    for line in src.splitlines())
                import ast

                tree = ast.parse(stripped, filename=path)  # parse ONCE
                fired = {fi.lineno for fi in
                         lint_source(stripped, filename=path,
                                     only_names=None, tree=tree)}
                fired |= {fi.lineno for fi in
                          lint_module_concurrency(stripped, filename=path,
                                                  tree=tree)}
                fired |= {fi.lineno for fi in
                          analyze_source(stripped, filename=path,
                                         tree=tree).findings}
                stale = [ln for ln in marked if ln not in fired]
                assert not stale, \
                    f"{path}: stale opcheck allow markers at lines {stale}"

    def test_ops_modules_cite_reference(self):
        """Parity auditability: ops/checkers/filters module docstrings must cite
        the reference implementation (file or SURVEY pointer)."""
        uncited = []
        for sub in ("ops", "checkers", "filters", "models"):
            d = os.path.join(PKG_ROOT, sub)
            for f in sorted(os.listdir(d)):
                if not f.endswith(".py") or f == "__init__.py":
                    continue
                with open(os.path.join(d, f)) as fh:
                    head = fh.read(2000)
                if "Reference" not in head and "reference" not in head \
                        and "SURVEY" not in head:
                    uncited.append(f"{sub}/{f}")
        assert not uncited, uncited
