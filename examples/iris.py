"""Iris multiclass classification example.

TPU-native equivalent of the reference OpIris
(helloworld/src/main/scala/com/salesforce/hw/iris/OpIris.scala:62-80):
typed features over the classic Iris data, label indexed from the
species string, MultiClassificationModelSelector with CV and a
DataCutter holding out a test fraction.

Run:  python examples/iris.py
"""
from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from transmogrifai_tpu.evaluators import MultiClassificationEvaluator
from transmogrifai_tpu.features.builder import FeatureBuilder
from transmogrifai_tpu.ops import transmogrify
from transmogrifai_tpu.selector import MultiClassificationModelSelector
from transmogrifai_tpu.selector.splitters import DataCutter
from transmogrifai_tpu.types import Real, RealNN
from transmogrifai_tpu.workflow import Workflow

IRIS_PATHS = [
    os.environ.get("IRIS_CSV", ""),
    "/root/reference/helloworld/src/main/resources/IrisDataset/iris.data",
]
SPECIES = ["Iris-setosa", "Iris-versicolor", "Iris-virginica"]


def load_iris(path: str = None):
    path = path or next((p for p in IRIS_PATHS if p and os.path.exists(p)),
                        None)
    if path is None:
        raise FileNotFoundError("iris.data not found; set IRIS_CSV")
    records = []
    with open(path) as fh:
        for line in fh:
            parts = line.strip().split(",")
            if len(parts) != 5 or parts[4] not in SPECIES:
                continue
            records.append({
                "sepal_length": float(parts[0]),
                "sepal_width": float(parts[1]),
                "petal_length": float(parts[2]),
                "petal_width": float(parts[3]),
                "label": float(SPECIES.index(parts[4])),
            })
    return records


def build_features():
    def real(name):
        return FeatureBuilder.of(name, Real).extract(
            lambda r, n=name: r.get(n)).as_predictor()
    feats = [real("sepal_length"), real("sepal_width"),
             real("petal_length"), real("petal_width")]
    label = FeatureBuilder.of("label", RealNN).extract(
        lambda r: r.get("label")).as_response()
    return feats, label


def run(verbose: bool = True, seed: int = 42):
    records = load_iris()
    feats, label = build_features()
    vec = transmogrify(feats)
    selector = MultiClassificationModelSelector.with_cross_validation(
        num_folds=3, seed=seed,
        splitter=DataCutter(reserve_test_fraction=0.2, seed=seed),
        # default pool (LR/RF/NB/DT) + the softmax XGBoost opt-in
        # (reference xgboost4j multi:softprob, OpXGBoostClassifier)
        model_types_to_use=["LogisticRegression",
                            "RandomForestClassifier", "NaiveBayes",
                            "DecisionTreeClassifier",
                            "XGBoostClassifier"])
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
        print(f"holdout error={metrics.Error:.4f} "
              f"f1={metrics.F1:.4f} ({fit_seconds:.1f}s)")
    return metrics, fit_seconds, model


if __name__ == "__main__":
    run()
