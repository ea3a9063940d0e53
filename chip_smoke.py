"""chip_smoke.py — train -> score -> serve once on the TPU, and fail loudly.

The quickest proof that the system still starts on the chip.  One process
drives the main path through the entry points a user calls, at the full
width of the configuration the repo headlines (``BASELINE.json`` config 4:
D = 128 post-transmogrify columns, 32 bins, 3 folds, LR x6 + LinearSVC x2 +
RF 50 trees depth 3/6 + GBT 50 rounds depth 3 = 33 fold-models):

  seeded raw table (Real + PickList columns)
    -> FeatureBuilder -> transmogrify -> sanity_check
    -> BinaryClassificationModelSelector.with_cross_validation(3)
    -> Workflow().train()            (fused prep prefix + run_cached sweeps)
    -> model.score(ds)
    -> model.serving_plan().warm()   (compiled scoring plan)
    -> model.serve()                 (micro-batched single submits + one bulk)

Every phase asserts its own result; nothing here turns a failed phase into
a printed line.  The script exits non-zero unless JAX's default backend is
a TPU, every family's CV metrics are finite and above a floor derived from
how the data was generated, no fallback counter moved (planner, AOT->jit,
serving host path), a second train and score compile nothing, and what the
server returns equals what the plan returns.

Usage (docs: README "Tests & benchmarks"):

    python chip_smoke.py              # one chip — what the driver runs
    python chip_smoke.py --mesh 2x2   # the same train under use_mesh on 4 chips

The compile cache goes where ``JAX_COMPILATION_CACHE_DIR`` says, else
``<repo>/.jax_cache`` (perf/programs.py).  Wall seconds and compile counts
printed here are SET-UP FACTS of one cold or warm-cache process, not
metrics; the JSON summary (the second-to-last stdout line) claims nothing
(``"claim": null``).  The LAST stdout line is the verdict the driver parses,
one object with exactly these keys:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

#: post-sanity-check feature width (``BASELINE.json`` config 4)
WIDTH = 128
FOLDS = 3
BINS = 32
#: rows >= 131072 so the chunked histogram path (n > 2 * _HIST_CHUNK) and
#: the premade bin one-hot run with many chunks; rows only lengthen the
#: chunk loop, every kernel shape is fixed by WIDTH / BINS / FOLDS / grids
ROWS = 131_072
N_REAL = 56            # 56 x (value, null indicator) = 112 columns
PICKLISTS = (7, 7)     # levels; each pivots to 7 levels + null = 8 live
                       # columns (the empty OTHER column is dropped by
                       # sanity_check) -> 112 + 16 = 128
MISSING = 0.03         # null rate: keeps every null indicator alive
SEED = 20260926

SERVE_SINGLES = 256
SERVE_BULK = 1024


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check(cond: bool, what: str) -> None:
    """A raising check (``assert`` vanishes under ``python -O``)."""
    if not cond:
        raise SystemExit(f"[chip_smoke] FAILED: {what}")


# ---------------------------------------------------------------------------
# Data: LR is the generating model
# ---------------------------------------------------------------------------

def make_table(rows: int, seed: int = SEED):
    """Seeded raw table + the generating model's probabilities.

    Reals are standard normal with a ``MISSING`` null rate; the label is
    Bernoulli(sigmoid(x . beta + picklist effects)) with a geometrically
    decaying ``beta`` so a handful of columns carry most of the signal
    (trees at depth 3 can find them; LR recovers all of it)."""
    import pandas as pd

    rng = np.random.default_rng(seed)
    x = rng.normal(size=(rows, N_REAL)).astype(np.float32)
    beta = (1.6 * 0.88 ** np.arange(N_REAL)).astype(np.float32)
    beta *= np.where(np.arange(N_REAL) % 2 == 0, 1.0, -1.0)
    logit = x @ beta
    cols: Dict[str, Any] = {}
    for j, n_levels in enumerate(PICKLISTS):
        codes = rng.integers(0, n_levels, rows)
        absent = rng.random(rows) < MISSING
        effect = np.linspace(-0.8, 0.8, n_levels)
        logit = logit + np.where(absent, 0.0, effect[codes])
        levels = np.array([f"lv{c}" for c in range(n_levels)], dtype=object)
        cols[f"p{j}"] = np.where(absent, None, levels[codes])
    missing = rng.random((rows, N_REAL)) < MISSING
    # a missing value contributes nothing to the label (mean-fill is then
    # the right imputation and the null indicator is uninformative noise)
    logit = logit - (np.where(missing, x, 0.0) @ beta)
    prob = 1.0 / (1.0 + np.exp(-logit))
    y = (rng.random(rows) < prob).astype(np.float64)
    xs = np.where(missing, np.nan, x).astype(np.float64)
    for j in range(N_REAL):
        cols[f"x{j}"] = xs[:, j]
    cols["y"] = y
    return pd.DataFrame(cols), y, prob


def aupr(score: np.ndarray, y: np.ndarray) -> float:
    """Area under the precision-recall curve (step interpolation)."""
    order = np.argsort(-score, kind="stable")
    hit = y[order]
    tp = np.cumsum(hit)
    precision = tp / np.arange(1, len(hit) + 1)
    return float((precision * hit).sum() / max(hit.sum(), 1.0))


def build_workflow(df, rf_trees: int, gbt_rounds: int, rf_depths=(3, 6)):
    from transmogrifai_tpu import (BinaryClassificationModelSelector,
                                   FeatureBuilder, Workflow, transmogrify)
    from transmogrifai_tpu.models.logistic import LogisticRegression
    from transmogrifai_tpu.models.svm import LinearSVC
    from transmogrifai_tpu.models.trees import (
        GradientBoostedTreesClassifier, RandomForestClassifier)
    from transmogrifai_tpu.readers.files import DataReaders

    label = FeatureBuilder.RealNN("y").extract_field().as_response()
    predictors = [FeatureBuilder.Real(f"x{j}").extract_field().as_predictor()
                  for j in range(N_REAL)]
    predictors += [
        FeatureBuilder.PickList(f"p{j}").extract_field().as_predictor()
        for j in range(len(PICKLISTS))]
    checked = label.sanity_check(transmogrify(predictors))
    # the headline grids (``BASELINE.json`` config 4)
    models = [
        (LogisticRegression(), [{"reg_param": r, "elastic_net": e}
                                for r in (0.001, 0.01, 0.1)
                                for e in (0.0, 0.5)]),
        (LinearSVC(), [{"reg_param": r} for r in (0.01, 0.1)]),
        (RandomForestClassifier(), [{"num_trees": rf_trees, "max_depth": d}
                                    for d in rf_depths]),
        (GradientBoostedTreesClassifier(),
         [{"num_rounds": gbt_rounds, "max_depth": 3}]),
    ]
    selector = BinaryClassificationModelSelector.with_cross_validation(
        num_folds=FOLDS, seed=7, models=models)
    prediction = label.transform_with(selector, checked)
    reader = DataReaders.Simple.dataframe(df)
    wf = Workflow().set_reader(reader).set_result_features(label, prediction)
    return wf, reader, label, prediction, checked


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------

def phase_environment(require_tpu: bool, mesh_shape) -> Dict[str, Any]:
    import jax
    import jaxlib

    backend = jax.default_backend()
    if require_tpu and backend != "tpu":
        raise SystemExit(
            f"[chip_smoke] no chip: jax.default_backend() is {backend!r}, "
            "not 'tpu' — this script measures nothing on a CPU")
    devices = jax.devices()
    if mesh_shape is not None:
        need = mesh_shape[0] * mesh_shape[1]
        if require_tpu and len(devices) < need:
            raise SystemExit(
                f"[chip_smoke] --mesh {mesh_shape[0]}x{mesh_shape[1]} needs "
                f"{need} TPU devices, found {len(devices)}")
    from transmogrifai_tpu import native
    from transmogrifai_tpu.perf import enable_persistent_cache
    from transmogrifai_tpu.perf.kernels.dispatch import kernel_provenance

    try:
        import libtpu

        libtpu_version = getattr(libtpu, "__version__", "unknown")
    except ImportError:
        libtpu_version = None
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    env = {
        "device": device,
        "versions": {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                     "libtpu": libtpu_version},
        "kernels": kernel_provenance(),
        "native_host_kernels": bool(native.available()),
        "compile_cache_dir": enable_persistent_cache(),
    }
    for k, v in env.items():
        log(f"{k}: {json.dumps(v, sort_keys=True, default=str)}")
    return env


def family_floor(y: np.ndarray, prob: np.ndarray) -> Dict[str, float]:
    """CV-metric (auPR) floor from the generating model: a family must beat
    the no-skill line (the positive rate) by at least a third of the gap to
    the oracle that knows the true probabilities."""
    no_skill = float(y.mean())
    oracle = aupr(prob, y)
    return {"no_skill": no_skill, "oracle": oracle,
            "floor": no_skill + (oracle - no_skill) / 3.0}


def phase_train(wf, floor: Dict[str, float], mesh=None):
    """Cold train + the checks on what it used; then a warm train that must
    compile nothing."""
    from contextlib import nullcontext

    from transmogrifai_tpu.parallel.mesh import use_mesh
    from transmogrifai_tpu.perf import (measure_compiles, program_cache_stats)
    from transmogrifai_tpu.perf.programs import program_cache_entries
    from transmogrifai_tpu.workflow.plan import planner_fallbacks

    ctx = (lambda: use_mesh(mesh)) if mesh is not None else nullcontext
    # counters are process-wide: judge this run by what IT moved
    keys_before = set(program_cache_entries())
    aot_before = program_cache_stats()["fallbacks"]
    plan_before = planner_fallbacks()

    def fallbacks():
        return (program_cache_stats()["fallbacks"] - aot_before,
                planner_fallbacks() - plan_before)

    t0 = time.perf_counter()
    with ctx(), measure_compiles() as cold:
        model = wf.train()
    cold_s = time.perf_counter() - t0

    summary = model.summary()
    check(summary is not None, "trained model carries no selector summary")
    check(list(summary.failed_models) == [],
          f"failed_models == {summary.failed_models}")
    families: Dict[str, List[float]] = {}
    for ev in summary.validation_results:
        families.setdefault(ev.model_name, []).extend(ev.metric_values)
    expect = {"LogisticRegression", "LinearSVC", "RandomForestClassifier",
              "GradientBoostedTreesClassifier"}
    check(set(families) == expect, f"families swept: {sorted(families)}")
    best_by_family = {}
    for name, vals in sorted(families.items()):
        vals = np.asarray(vals, np.float64)
        check(bool(np.isfinite(vals).all()),
              f"{name}: non-finite CV metric in {vals.tolist()}")
        best_by_family[name] = float(vals.max())
        check(best_by_family[name] > floor["floor"],
              f"{name}: best CV auPR {best_by_family[name]:.4f} is under the "
              f"floor {floor['floor']:.4f} (no-skill {floor['no_skill']:.4f},"
              f" oracle {floor['oracle']:.4f})")
    # the selector swallows evaluator failures into an empty dict
    train_eval = {k: v for k, v in summary.train_evaluation.items()
                  if isinstance(v, (int, float))}
    check("auPR" in train_eval
          and bool(np.isfinite(list(train_eval.values())).all()),
          f"winner's train evaluation is missing or non-finite: {train_eval}")
    log(f"cv auPR by family: {json.dumps(best_by_family, sort_keys=True)}; "
        f"floor {floor['floor']:.4f}, oracle {floor['oracle']:.4f}; "
        f"winner {summary.best_model_name} {summary.best_grid}")

    entries = program_cache_entries()
    new_keys = [k for k in entries if k not in keys_before]
    labels = sorted({entries[k].label for k in new_keys})
    check(any(lb.startswith("transform_plan/") for lb in labels),
          f"fused prep prefix never dispatched; run_cached labels: {labels}")
    check(fallbacks() == (0, 0),
          "fallbacks (run_cached AOT->jit, fused planner -> host path): "
          f"{fallbacks()}")

    t0 = time.perf_counter()
    with ctx(), measure_compiles() as warm:
        wf.train()
    warm_s = time.perf_counter() - t0
    check(warm.backend_compiles == 0,
          f"second train() compiled {warm.backend_compiles} programs")
    check(fallbacks() == (0, 0),
          f"a fallback counter moved during the second train(): {fallbacks()}")
    facts = {
        "cold_train_wall_s": round(cold_s, 1),
        "cold_train_backend_compiles": cold.backend_compiles,
        "cold_train_compile_s": round(cold.compile_seconds, 1),
        "cold_train_persistent_cache_hits": cold.persistent_cache_hits,
        "second_train_wall_s": round(warm_s, 1),
        "second_train_backend_compiles": warm.backend_compiles,
        "run_cached_programs": len(new_keys),
        "winner": summary.best_model_name,
    }
    log(f"set-up facts (train): {json.dumps(facts, sort_keys=True)}")
    return model, facts, new_keys


def _on_accelerator(platform: str) -> None:
    """Every live device buffer sits on the platform JAX reported — nothing
    was quietly placed on a host backend."""
    import jax

    live = jax.live_arrays()
    check(len(live) > 0, "no live device arrays after train + score")
    platforms = {d.platform for a in live for d in a.devices()}
    check(platforms == {platform},
          f"live arrays on {sorted(platforms)}, expected only {platform!r}")


def phase_score(model, reader, label, prediction, checked, platform: str):
    from transmogrifai_tpu.workflow.workflow import dedup_raw_features
    from transmogrifai_tpu.perf import measure_compiles

    ds = reader.generate_dataset(dedup_raw_features(model.result_features))
    t0 = time.perf_counter()
    scored = model.score(ds, keep_intermediate=True)
    cold_s = time.perf_counter() - t0
    with measure_compiles() as warm:
        again = model.score(ds)
    width = int(np.asarray(scored[checked.name].data).shape[1])
    check(width == WIDTH, f"sanity-checked width is {width}, not {WIDTH}")
    check(warm.backend_compiles == 0,
          f"second score() compiled {warm.backend_compiles} programs")
    pred = scored[prediction.name]
    block = np.asarray(pred.data, np.float64)   # prediction | raw | prob
    check(block.shape[0] == ds.n_rows and block.shape[1] >= 1,
          f"prediction block shape {block.shape}")
    check(bool(np.isfinite(block).all()), "non-finite scored prediction")
    if pred.prob is not None:
        check(bool(np.allclose(pred.prob.sum(axis=1), 1.0, atol=1e-5)),
              "scored class probabilities do not sum to 1")
    check(np.array_equal(block, np.asarray(again[prediction.name].data,
                                           np.float64)),
          "second score() differs from the first")
    _on_accelerator(platform)
    y = np.asarray(scored[label.name].data, np.float64).reshape(-1)
    train_aupr = aupr(np.asarray(pred.score, np.float64), y)
    log(f"set-up facts (score): cold wall {cold_s:.1f}s over {ds.n_rows} rows; "
        f"train-set auPR {train_aupr:.4f}")
    return pred, train_aupr


def _records(df, rows: Sequence[int]) -> List[Dict[str, Any]]:
    """Request payloads: the raw predictors only (no label at serving time);
    missing values go out as None the way a JSON client sends them."""
    part = df.drop(columns="y").iloc[list(rows)].astype(object)
    return part.where(part.notna(), None).to_dict("records")


def phase_serve(model, df, prediction, scored_pred):
    """Plan warm-up, single submits through the micro-batcher, one bulk
    batch; served rows == plan rows (bitwise, the parity tier-1 asserts),
    every failure counter zero."""
    from transmogrifai_tpu.perf import measure_compiles

    t0 = time.perf_counter()
    plan = model.serving_plan()
    plan.warm()
    singles = _records(df, range(SERVE_SINGLES))
    bulk = _records(df, range(SERVE_SINGLES, SERVE_SINGLES + SERVE_BULK))
    want_singles = plan.score(singles)
    want_bulk = plan.score(bulk)

    with model.serve() as server, measure_compiles() as serving:
        futures = [server.submit(r) for r in singles]
        got_singles = [f.result(timeout=120) for f in futures]
        got_bulk = server.score_batch(bulk)
        metrics = server.metrics()
    wall = time.perf_counter() - t0

    check(got_singles == want_singles,
          "served single-record rows differ from plan.score")
    check(got_bulk == want_bulk, "served bulk rows differ from plan.score")
    n = SERVE_SINGLES + SERVE_BULK
    served = np.asarray([list(r[prediction.name].values())
                         for r in got_singles + got_bulk], np.float64)
    ref = np.asarray(scored_pred.data[:n], np.float64)
    check(served.shape == ref.shape,
          f"served block {served.shape} vs scored block {ref.shape}")
    check(bool(np.isfinite(served).all()), "non-finite served prediction")
    delta = float(np.abs(served - ref).max())
    check(delta <= 1e-5, f"serving vs model.score prediction delta {delta}")

    res, bat = metrics["resilience"], metrics["batcher"]
    check(res["device_failures"] == 0 and res["fallback_batches"] == 0
          and res["quarantined"] == 0 and res["retries"] == 0,
          f"serving resilience counters moved: {res}")
    check(res["breaker"]["state"] == "closed",
          f"breaker is {res['breaker']['state']}")
    check(bat["submitted"] == bat["completed"] == SERVE_SINGLES
          and bat["failed"] == 0,
          f"batcher submitted/completed/failed: {bat['submitted']}/"
          f"{bat['completed']}/{bat['failed']}")
    check(serving.backend_compiles == 0,
          f"warm serving compiled {serving.backend_compiles} programs")
    log(f"set-up facts (serve): wall {wall:.1f}s incl. plan warm-up; "
        f"{SERVE_SINGLES} submits + {SERVE_BULK}-row bulk; served vs "
        f"model.score max |delta| {delta:.2e} "
        f"({'bitwise' if delta == 0.0 else 'not bitwise'}); "
        f"scored_batches {metrics['plan']['scored_batches']}")
    return {"serve_wall_s": round(wall, 1), "serve_vs_score_max_delta": delta}


def phase_kernels(mode: str, rf_depth: int) -> Dict[str, str]:
    """Compile each Pallas kernel ``auto`` can select at the shapes this
    run's train produced and compare it with its XLA twin, bitwise on
    seeded integer fixtures."""
    import jax
    import jax.numpy as jnp

    from transmogrifai_tpu.perf.kernels import encode as KE
    from transmogrifai_tpu.perf.kernels import histogram as KH
    from transmogrifai_tpu.perf.kernels import splitscan as KS

    interpret = mode != "pallas"
    rng = np.random.default_rng(17)
    B = BINS + 1

    def split(lanes: int, nodes: int):
        # a histogram the grower could have built: EVERY feature's bins sum
        # to the node totals (independent draws per feature leave negative
        # right-child hessians, where the gain formula's eps guard — and
        # with it the comparison — degenerates to 1/0 vs 1/1e-12)
        cells = (lanes, nodes, 1, WIDTH)

        def spread(totals):
            return rng.multinomial(
                np.broadcast_to(totals[..., None], cells), np.full(B, 1 / B))

        pos, neg = (rng.integers(0, 120, (lanes, nodes, 1)) for _ in range(2))
        tot_h = rng.integers(B, 8 * B, (lanes, nodes, 1))
        hists = [jnp.asarray(h.astype(np.float32))
                 for h in (spread(pos) - spread(neg), spread(tot_h),
                           pos - neg, tot_h)]
        mask = jnp.ones((lanes, WIDTH), jnp.float32)
        params = [jnp.float32(v) for v in (1.0, 0.5, 0.1, 1.0)]
        kernel = jax.jit(lambda *a: KS.split_scan_pallas(
            *a[:5], BINS, *a[5:], interpret=interpret))
        return (kernel(*hists, mask, *params),
                KS.split_scan_xla(*hists, mask, BINS, *params))

    def hist(rows: int, lanes: int, nodes: int):
        local = jnp.asarray(
            rng.integers(-1, nodes, (lanes, rows)).astype(np.int32))
        ghT = jnp.asarray(
            rng.integers(-3, 4, (lanes, 2, rows)).astype(np.int8))
        binned = jnp.asarray(
            rng.integers(0, B, (rows, WIDTH)).astype(np.int32))
        # the VMEM admission guard keeps the default 2048-row chunk on the
        # XLA scan at this width; a 512-row chunk is what it admits
        kernel = jax.jit(lambda *a: KH.hist_level_pallas(
            *a, nodes, BINS, int_exact=True, interpret=interpret, chunk=512))
        return (kernel(local, ghT, binned),
                KH.hist_level_xla(local, ghT, binned, nodes, BINS,
                                  int_exact=True))

    def encode(rows: int, width: int):
        codes = jnp.asarray(
            rng.integers(-1, width + 1, rows).astype(np.int32))
        kernel = jax.jit(lambda c: KE.onehot_codes(
            c, width, interpret=interpret))
        return kernel(codes), jax.nn.one_hot(codes, width, dtype=jnp.float32)

    # lanes only lengthen a kernel's grid, so the RF-shaped split block (one
    # lane x 2^(depth-1) nodes per step; binary RF has one class channel)
    # runs at 8 lanes instead of FOLDS x trees; rows likewise
    pairs = {
        f"split_scan@lanes{FOLDS}-nodes4": lambda: split(FOLDS, 4),
        f"split_scan@lanes8-nodes{2 ** (rf_depth - 1)}":
            lambda: split(8, 2 ** (rf_depth - 1)),
        # +OTHER+null
        f"onehot_codes@width{PICKLISTS[0] + 2}":
            lambda: encode(8192, PICKLISTS[0] + 2),
        "hist_level@chunk512": lambda: hist(8192, FOLDS, 2),
    }
    verdicts = {}
    for name, run in pairs.items():
        got, want = run()
        same = all(np.array_equal(np.asarray(a), np.asarray(b))
                   for a, b in zip(jax.tree.leaves(got),
                                   jax.tree.leaves(want)))
        check(same, f"kernel {name} ({mode}) differs from its XLA twin")
        verdicts[name] = "matches"
    log(f"kernels vs XLA twins ({mode}): {len(verdicts)} shapes match")
    return verdicts


def phase_mesh(mesh, new_keys: Sequence[tuple], df_rows: int
               ) -> Dict[str, Any]:
    """--mesh: the row block was really split over the data axis."""
    import jax

    from transmogrifai_tpu.parallel.mesh import mesh_token, use_mesh

    with use_mesh(mesh):
        token = mesh_token()
    keyed = [k for k in new_keys if token in k]
    check(len(keyed) > 0, f"no run_cached key carries the mesh token {token}")
    n_dev = int(np.prod(list(mesh.shape.values())))
    sharded = [a for a in jax.live_arrays()
               if a.ndim == 2 and a.shape[0] >= df_rows
               and len(a.addressable_shards) == n_dev
               and a.addressable_shards[0].data.shape[0] < a.shape[0]]
    check(len(sharded) > 0,
          f"no live (rows, d) array is split over {n_dev} devices")
    sharded.sort(key=lambda a: -a.nbytes)   # report the widest block
    # every device did real work: its peak memory is above its own shard of
    # the row block (the CPU backend reports no memory stats; a TPU does)
    per_dev_rows = sharded[0].addressable_shards[0].data.nbytes
    peaks = {}
    for d in mesh.devices.flat:
        stats = d.memory_stats()
        check(stats is not None or d.platform != "tpu",
              f"device {d.id} reports no memory stats")
        if stats is not None:
            peaks[str(d.id)] = int(stats["peak_bytes_in_use"])
            check(peaks[str(d.id)] > per_dev_rows,
                  f"device {d.id} peak memory {peaks[str(d.id)]} is at idle "
                  f"level (its row shard alone is {per_dev_rows} bytes)")
    log(f"mesh: token {token}; {len(keyed)} keyed programs; row block "
        f"{sharded[0].shape} split {n_dev} ways; peak bytes {peaks}")
    return {"mesh_token": str(token), "peak_bytes_in_use": peaks}


# ---------------------------------------------------------------------------
# Driver
# ---------------------------------------------------------------------------

def run(rows: int = ROWS, rf_trees: int = 50, gbt_rounds: int = 50,
        rf_depths=(3, 6), require_tpu: bool = True,
        mesh_shape: Optional[Sequence[int]] = None) -> Dict[str, Any]:
    """The smoke body.  The size and ``require_tpu`` arguments exist for the
    tier-1 guard, which runs this at a tiny size on the CPU
    (tests/test_chip_smoke.py); the command line always runs the full size
    and always requires the chip."""
    t_start = time.perf_counter()
    env = phase_environment(require_tpu, mesh_shape)
    platform = env["device"]["platform"]

    t0 = time.perf_counter()
    df, y, prob = make_table(rows)
    floor = family_floor(y, prob)
    wf, reader, label, prediction, checked = build_workflow(
        df, rf_trees, gbt_rounds, rf_depths)
    data_s = time.perf_counter() - t0
    log(f"set-up facts (data): {rows} rows x {len(df.columns) - 1} raw "
        f"columns in {data_s:.1f}s; positive rate {floor['no_skill']:.3f}")

    mesh = None
    if mesh_shape is not None:
        import jax

        from transmogrifai_tpu.parallel.mesh import make_mesh

        mesh = make_mesh(
            n_data=mesh_shape[0], n_model=mesh_shape[1],
            devices=jax.devices()[:mesh_shape[0] * mesh_shape[1]])
    model, facts, new_keys = phase_train(wf, floor, mesh=mesh)
    facts["data_wall_s"] = round(data_s, 1)
    result: Dict[str, Any] = {"ok": True, "device": env["device"]}
    if mesh is not None:
        result["mesh"] = phase_mesh(mesh, new_keys, rows)
    else:
        scored_pred, train_aupr = phase_score(
            model, reader, label, prediction, checked, platform)
        check(len(scored_pred) == rows, "scored row count")
        result["width"] = WIDTH     # asserted in phase_score
        facts["train_set_aupr"] = round(train_aupr, 4)
        facts.update(phase_serve(model, df, prediction, scored_pred))
    from transmogrifai_tpu.perf import compile_snapshot
    from transmogrifai_tpu.perf.kernels.dispatch import kernel_selections

    selected = kernel_selections()
    mode = env["kernels"]["kernel_mode"]
    if mesh is None and mode != "xla":
        result["kernels"] = phase_kernels(mode, max(rf_depths))
        # ... and the train/serve programs really contained the kernels the
        # dispatch table says this mode selects at these shapes
        for kernel in ("split", "encode"):
            check(selected.get(f"{kernel}:{mode}", 0) > 0,
                  f"{kernel} kernel was never selected as {mode}: {selected}")
        # ... but for routing, which the grower runs as the XLA compare-reduce
        # in every mode
        check(selected.get("route:xla", 0) > 0,
              f"the grower's routing was never counted as xla: {selected}")
    snap = compile_snapshot()
    facts.update({
        "total_wall_s": round(time.perf_counter() - t_start, 1),
        "process_backend_compiles": snap.backend_compiles,
        "process_compile_s": round(snap.compile_seconds, 1),
        "process_persistent_cache_hits": snap.persistent_cache_hits,
        "compile_cache_dir": env["compile_cache_dir"],
    })
    result.update({
        "rows": rows,
        "versions": env["versions"],
        "kernels_selected": selected,
        "setup_facts": facts,
        "claim": None,
    })
    return result


def verdict_line(result: Dict[str, Any]) -> str:
    """The last stdout line: exactly ``ok`` and ``device`` (``platform``,
    ``kind``, ``count`` as JAX reports them) — the driver refuses any other
    shape.  Everything else the run learned is on the summary line above."""
    device = result["device"]
    return json.dumps({
        "ok": bool(result["ok"]),
        "device": {"platform": str(device["platform"]),
                   "kind": str(device["kind"]),
                   "count": int(device["count"])}})


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--mesh", default=None, metavar="DxM",
                    help="run the train under use_mesh(make_mesh(n_data=D, "
                         "n_model=M)), e.g. 2x2 (needs D*M TPU devices)")
    args = ap.parse_args(argv)
    mesh_shape = None
    if args.mesh:
        mesh_shape = tuple(int(v) for v in args.mesh.lower().split("x"))
        if len(mesh_shape) != 2:
            ap.error("--mesh takes DxM, e.g. 2x2")
    result = run(mesh_shape=mesh_shape)
    print(json.dumps(result, default=str), flush=True)   # ends "claim": null
    print(verdict_line(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
