"""Bin-edge leakage audit at scale.

The batched tree fold x grid kernels default to quantile bin edges from
the WHOLE prepared matrix (standard histogram-GBM CV practice); the
documented concern is that validation rows influence where splits CAN
fall. ``TX_TREE_EDGES=fold`` computes edges from each fold's train rows
only. This audit runs the same GBT + RF grids under both protocols on a
synthetic wide matrix (default 200k x 100 — BASELINE config-4 shape,
heavy-tailed features so edges actually move between row subsets) and
reports per-candidate CV metrics, winners, and the max metric delta.

  python examples/edges_audit.py [--rows 200000] [--cols 100]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=200_000)
    ap.add_argument("--cols", type=int, default=100)
    ap.add_argument("--folds", type=int, default=3)
    args = ap.parse_args()

    from transmogrifai_tpu.utils.jax_setup import enable_compilation_cache
    enable_compilation_cache()
    import numpy as np

    from transmogrifai_tpu.evaluators import BinaryClassificationEvaluator
    from transmogrifai_tpu.models.trees import (GBTClassifier,
                                                RandomForestClassifier)
    from transmogrifai_tpu.models.trees.fold_grid import (_forest_fold_grid,
                                                          _gbt_fold_grid)

    rng = np.random.default_rng(0)
    n, d, F = args.rows, args.cols, args.folds
    # heavy-tailed features: quantile edges move with the row subset
    X = rng.standard_t(df=3, size=(n, d))
    logits = X[:, 0] + 0.5 * X[:, 1] - 0.5 * X[:, 2] \
        + 0.3 * X[:, 3] * (X[:, 4] > 0)
    y = (logits + rng.logistic(size=n) > 0).astype(np.float64)

    masks = np.ones((F, n))
    for f in range(F):
        masks[f, f::F] = 0.0
    nv = n // F
    Xv = np.stack([X[masks[f] == 0][:nv] for f in range(F)])
    yv = np.stack([y[masks[f] == 0][:nv] for f in range(F)])
    spec = BinaryClassificationEvaluator().device_metric_spec()

    grid_gbt = [{"max_depth": 6, "gamma": g, "min_child_weight": m}
                for g in (0.0, 0.1) for m in (1.0, 10.0)]
    grid_rf = [{"max_depth": 6, "min_instances_per_node": m,
                "min_info_gain": g}
               for m in (10, 100) for g in (0.001, 0.1)]

    out = {"rows": n, "cols": d, "folds": F}
    mats = {}
    for mode in ("matrix", "fold"):
        os.environ["TX_TREE_EDGES"] = mode
        t0 = time.perf_counter()
        mm_gbt = _gbt_fold_grid(
            GBTClassifier(num_rounds=10), X, y, masks, grid_gbt, None,
            "logistic", eval_ctx=(Xv, yv, spec))
        mm_rf = _forest_fold_grid(
            RandomForestClassifier(num_trees=20), X, y, masks, grid_rf,
            None, True, eval_ctx=(Xv, yv, spec))
        mats[mode] = (mm_gbt, mm_rf)
        out[f"{mode}_seconds"] = round(time.perf_counter() - t0, 1)
        out[f"{mode}_gbt_mean_aupr"] = [round(float(v), 5)
                                        for v in mm_gbt.mean(axis=0)]
        out[f"{mode}_rf_mean_aupr"] = [round(float(v), 5)
                                       for v in mm_rf.mean(axis=0)]
        out[f"{mode}_gbt_winner"] = int(np.argmax(mm_gbt.mean(axis=0)))
        out[f"{mode}_rf_winner"] = int(np.argmax(mm_rf.mean(axis=0)))
    os.environ.pop("TX_TREE_EDGES", None)
    out["gbt_winner_agrees"] = (out["matrix_gbt_winner"]
                                == out["fold_gbt_winner"])
    out["rf_winner_agrees"] = (out["matrix_rf_winner"]
                               == out["fold_rf_winner"])
    out["max_abs_metric_delta"] = round(max(
        float(np.abs(mats["matrix"][i] - mats["fold"][i]).max())
        for i in range(2)), 6)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
