"""Single-core C-library competitor baseline for the tree benchmarks.

The north-star (BASELINE.json) compares against the reference's 32-core
Spark + native XGBoost stack, but this build host exposes ONE physical
core (``nproc`` = 1), so a real multi-core run is impossible here.
This harness produces the honest substitute: scikit-learn's
HistGradientBoosting / RandomForest (C/Cython cores, the same
histogram-tree algorithm class as LightGBM/XGBoost) on the synthetic
table of the ``synth100_gbt`` benchmark configuration (BASELINE.json
config 4: a fifth of the columns standard normal, the rest binary at
15 %), pinned to ONE thread on every host. Comparing a TPU row against
``single_thread_seconds / 32`` bounds a PERFECT-scaling 32-core run of
the competitor — a denominator that can only flatter the competitor,
never this framework.

  python examples/competitor_bench.py [--rows 1000000] [--cols 100]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def make_data(rows: int, cols: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    n_num = max(cols // 5, 1)
    X_num = rng.normal(size=(rows, n_num))
    X_bin = (rng.uniform(size=(rows, cols - n_num)) < 0.15).astype(float)
    X = np.concatenate([X_num, X_bin], axis=1)
    logits = X_num[:, 0] + X_bin[:, :3].sum(axis=1) - 0.5
    y = (logits + rng.logistic(size=rows) * 0.5 > 0).astype(float)
    return X, y


def main() -> None:
    # pin the competitor to ONE thread regardless of host width: the
    # rows are labeled single-core, and the 32x perfect-scaling bound
    # below is only valid when derived from a true 1-thread time (must
    # be set before sklearn/OpenMP load)
    os.environ["OMP_NUM_THREADS"] = "1"

    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=200_000)
    ap.add_argument("--cols", type=int, default=100)
    args = ap.parse_args()

    from sklearn.ensemble import (HistGradientBoostingClassifier,
                                  RandomForestClassifier)

    X, y = make_data(args.rows, args.cols)
    cores = len(os.sched_getaffinity(0))

    # shape-matched to the synth100_gbt.fit cell's GBT(20 rounds, d6, 32
    # bins, step 0.1) and RF(50 trees, d6, min 10 rows/leaf-split)
    for name, est in [
        ("sklearn_histgbt_20iter_d6",
         HistGradientBoostingClassifier(
             max_iter=20, max_depth=6, max_bins=32, learning_rate=0.1,
             early_stopping=False)),
        ("sklearn_rf_50trees_d6",
         RandomForestClassifier(
             n_estimators=50, max_depth=6, min_samples_split=10,
             n_jobs=1)),
    ]:
        t0 = time.perf_counter()
        est.fit(X, y)
        fit_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        pred = est.predict(X[:50_000])
        score_s = time.perf_counter() - t0
        print(json.dumps({
            "model": name, "rows": args.rows, "cols": args.cols,
            "fit_seconds": round(fit_s, 2),
            "fit_rows_per_sec": round(args.rows / fit_s),
            "score_rows_per_sec": round(50_000 / max(score_s, 1e-9)),
            "train_subset_acc": round(
                float(np.mean(pred == y[:50_000])), 4),
            "physical_cores": cores,
            "threads_used": 1,
            "perfect_scaling_32core_fit_seconds": round(fit_s / 32, 2),
        }))


if __name__ == "__main__":
    main()
