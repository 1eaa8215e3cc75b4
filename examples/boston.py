"""Boston housing regression example.

TPU-native equivalent of the reference OpBoston
(helloworld/src/main/scala/com/salesforce/hw/boston/OpBoston.scala:86):
typed features over the Boston housing data,
RegressionModelSelector with cross-validation and a DataSplitter
holding out a test fraction.

Run:  python examples/boston.py
"""
from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from transmogrifai_tpu.features.builder import FeatureBuilder
from transmogrifai_tpu.ops import transmogrify
from transmogrifai_tpu.selector import RegressionModelSelector
from transmogrifai_tpu.selector.splitters import DataSplitter
from transmogrifai_tpu.types import Binary, Real, RealNN
from transmogrifai_tpu.workflow import Workflow

BOSTON_PATHS = [
    os.environ.get("BOSTON_CSV", ""),
    "/root/reference/helloworld/src/main/resources/BostonDataset/"
    "housing.data",
]
#: whitespace-separated columns (reference BostonHouse case class)
COLUMNS = ["crim", "zn", "indus", "chas", "nox", "rm", "age", "dis",
           "rad", "tax", "ptratio", "b", "lstat", "medv"]


def load_boston(path: str = None):
    path = path or next((p for p in BOSTON_PATHS
                         if p and os.path.exists(p)), None)
    if path is None:
        raise FileNotFoundError("housing.data not found; set BOSTON_CSV")
    records = []
    with open(path) as fh:
        for line in fh:
            parts = line.split()
            if len(parts) != len(COLUMNS):
                continue
            records.append({c: float(v) for c, v in zip(COLUMNS, parts)})
    return records


def build_features():
    def real(name):
        return FeatureBuilder.of(name, Real).extract(
            lambda r, n=name: r.get(n)).as_predictor()
    chas = FeatureBuilder.of("chas", Binary).extract(
        lambda r: bool(r.get("chas"))).as_predictor()
    feats = [real(c) for c in COLUMNS if c not in ("chas", "medv")]
    feats.append(chas)
    label = FeatureBuilder.of("medv", RealNN).extract(
        lambda r: r.get("medv")).as_response()
    return feats, label


def run(verbose: bool = True, seed: int = 42):
    records = load_boston()
    feats, label = build_features()
    vec = transmogrify(feats)
    selector = RegressionModelSelector.with_cross_validation(
        num_folds=3, seed=seed,
        splitter=DataSplitter(reserve_test_fraction=0.2, seed=seed))
    pred = selector.set_input(label, vec).get_output()

    t0 = time.perf_counter()
    model = (Workflow()
             .set_result_features(pred)
             .set_input_records(records)
             .train())
    fit_seconds = time.perf_counter() - t0

    sel_model = model.result_features[0].origin_stage
    summary = sel_model.summary
    metrics = summary.holdout_evaluation or summary.train_evaluation
    if verbose:
        print(summary.pretty())
        print(f"holdout RMSE={metrics.RootMeanSquaredError:.3f} "
              f"R2={metrics.R2:.3f} ({fit_seconds:.1f}s)")
    return metrics, fit_seconds, model


if __name__ == "__main__":
    run()
