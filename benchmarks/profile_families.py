"""Per-family wall time of the bench selector sweep at 1M rows (TPU)."""
import os
import sys
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import time

import numpy as np

from bench import D, FOLDS, LR_GRIDS, SVC_GRIDS, RF_GRIDS, GBT_GRIDS, synth


def main():
    from transmogrifai_tpu.evaluators.base import Evaluators
    from transmogrifai_tpu.models.logistic import LogisticRegression
    from transmogrifai_tpu.models.svm import LinearSVC
    from transmogrifai_tpu.models.trees import (
        GradientBoostedTreesClassifier, RandomForestClassifier)
    from transmogrifai_tpu.models.tuning import CrossValidator

    n = int(os.environ.get("ROWS", 1_000_000))
    x, y = synth(n, D)
    ev = Evaluators.binary_classification()
    cv = CrossValidator(ev, num_folds=FOLDS, seed=7)
    w = np.ones_like(y, dtype=np.float32)
    tw, vw = cv.fold_weights(y, w)
    mf = ev.metric_fn()

    fams = [("LR", LogisticRegression(), LR_GRIDS),
            ("SVC", LinearSVC(), SVC_GRIDS),
            ("RF", RandomForestClassifier(), RF_GRIDS),
            ("GBT", GradientBoostedTreesClassifier(), GBT_GRIDS)]

    for rep in range(2):
        print(f"--- pass {rep} ---")
        t_all = time.perf_counter()
        for name, est, grids in fams:
            t0 = time.perf_counter()
            gather = est.cv_sweep_async(x, y, tw, vw, grids, mf)
            t1 = time.perf_counter()
            scores = gather()
            t2 = time.perf_counter()
            print(f"{name:4s} dispatch {t1-t0:6.2f}s gather {t2-t1:6.2f}s "
                  f"mean={np.nanmean(scores):.3f}")
        print(f"total {time.perf_counter()-t_all:.2f}s (serialized this pass)")


if __name__ == "__main__":
    main()
