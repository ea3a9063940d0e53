"""BASELINE config: 1M-row synthetic FULL pipeline — transmogrify + SanityChecker
+ 3-fold CV model selection, end to end through the real Workflow.

Prints one JSON line: rows/sec through train() normalized to the row count.
Override rows with BENCH_ROWS (CPU dev boxes want ~50k).

Run:  python benchmarks/full_pipeline_1m.py
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def build(n_rows: int, seed: int = 0):
    from transmogrifai_tpu import (
        BinaryClassificationModelSelector, Dataset, FeatureBuilder, transmogrify)
    from transmogrifai_tpu.models.logistic import LogisticRegression
    from transmogrifai_tpu.types import PickList, Real, RealNN

    rng = np.random.default_rng(seed)
    num = {f"n{i}": rng.normal(size=n_rows) for i in range(8)}
    cats = rng.choice(["a", "b", "c", "d", "e"], size=(n_rows, 2))
    z = sum(v * rng.normal() for v in num.values()) / 3 + (cats[:, 0] == "a")
    y = (rng.random(n_rows) < 1 / (1 + np.exp(-z))).astype(float)

    cols = {k: v.tolist() for k, v in num.items()}
    cols["c0"], cols["c1"] = cats[:, 0].tolist(), cats[:, 1].tolist()
    cols["label"] = y.tolist()
    ftypes = {**{k: Real for k in num}, "c0": PickList, "c1": PickList,
              "label": RealNN}
    ds = Dataset.from_features(cols, ftypes)

    label = FeatureBuilder.of("label", RealNN).extract_field().as_response()
    feats = ([FeatureBuilder.of(k, Real).extract_field().as_predictor()
              for k in num]
             + [FeatureBuilder.of(c, PickList).extract_field().as_predictor()
                for c in ("c0", "c1")])
    checked = label.sanity_check(transmogrify(feats))
    sel = BinaryClassificationModelSelector.with_cross_validation(
        num_folds=3,
        models=[(LogisticRegression(),
                 [{"reg_param": r} for r in (0.001, 0.01, 0.1, 1.0)])])
    pred = label.transform_with(sel, checked)
    return ds, label, pred


def main():
    import jax

    from transmogrifai_tpu import Workflow

    platform = jax.default_backend()
    n_rows = int(os.environ.get(
        "BENCH_ROWS", 1_000_000 if platform in ("tpu", "gpu") else 50_000))
    ds, label, pred = build(n_rows)

    t0 = time.perf_counter()
    model = Workflow().set_input_dataset(ds).set_result_features(label, pred).train()
    dt = time.perf_counter() - t0
    aupr = model.summary().train_evaluation.get("auPR")
    print(json.dumps({
        "metric": "full_pipeline_rows_per_sec",
        "value": round(n_rows / dt, 1),
        "unit": f"rows/sec (transmogrify+sanity+3fold-CV, n={n_rows}, {platform})",
        "train_seconds": round(dt, 2),
        "auPR": round(aupr, 4) if aupr is not None else None,
    }))


if __name__ == "__main__":
    main()
