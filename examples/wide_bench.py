"""Wide high-cardinality categorical throughput (BASELINE.json config 5).

Generates records with many high-cardinality categorical fields plus a
numeric block, runs the REAL feature path — typed features,
``transmogrify`` (one-hot topK + hashing decisions via
SmartTextVectorizer semantics) — then times an MLP deep-selector fit on
the resulting wide matrix. Reports feature-engineering rows/sec, final
matrix width, and MLP models×folds/sec.

Run:  python examples/wide_bench.py [--rows 20000] [--cats 40] [--card 500]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def make_records(rows: int, cats: int, card: int, numerics: int = 10,
                 seed: int = 0):
    rng = np.random.default_rng(seed)
    # skewed category popularity (Zipf-ish) like real id-type columns
    weights = 1.0 / np.arange(1, card + 1)
    weights /= weights.sum()
    cat_vals = [rng.choice(card, size=rows, p=weights) for _ in range(cats)]
    num_vals = [rng.normal(size=rows) for _ in range(numerics)]
    logits = (num_vals[0]
              + (cat_vals[0] % 7 == 0) * 1.5
              + (cat_vals[1] % 11 == 0) * 1.0
              - 0.5)
    y = (logits + rng.logistic(size=rows) * 0.7 > 0).astype(float)
    records = []
    for i in range(rows):
        r = {f"c{j}": f"v{cat_vals[j][i]}" for j in range(cats)}
        r.update({f"n{j}": float(num_vals[j][i]) for j in range(numerics)})
        r["label"] = float(y[i])
        records.append(r)
    return records


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=20_000)
    ap.add_argument("--cats", type=int, default=40)
    ap.add_argument("--card", type=int, default=500)
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args()
    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    from transmogrifai_tpu.utils.jax_setup import enable_compilation_cache
    enable_compilation_cache()

    from transmogrifai_tpu.evaluators import BinaryClassificationEvaluator
    from transmogrifai_tpu.features.builder import FeatureBuilder
    from transmogrifai_tpu.models import MultilayerPerceptronClassifier
    from transmogrifai_tpu.ops import transmogrify
    from transmogrifai_tpu.selector import ModelSelector, CrossValidation
    from transmogrifai_tpu.utils import WorkflowListener
    from transmogrifai_tpu.workflow import Workflow

    records = make_records(args.rows, args.cats, args.card)
    feats = [FeatureBuilder.pick_list(f"c{j}")
             .extract(lambda r, j=j: r.get(f"c{j}")).as_predictor()
             for j in range(args.cats)]
    feats += [FeatureBuilder.real(f"n{j}")
              .extract(lambda r, j=j: r.get(f"n{j}")).as_predictor()
              for j in range(10)]
    label = (FeatureBuilder.real_nn("label")
             .extract(lambda r: r.get("label")).as_response())

    fv = transmogrify(feats)

    # feature engineering timing: train the feature DAG alone first
    t0 = time.perf_counter()
    wf = Workflow().set_result_features(fv).set_input_records(records)
    model = wf.train()
    feat_s = time.perf_counter() - t0
    ds = model.compute_data_up_to(fv, records)
    width = ds[fv.name].data.shape[1]

    grid = [{"hidden_layers": (64, 32)}, {"hidden_layers": (128, 64)}]
    num_folds = 3
    selector = ModelSelector(
        validator=CrossValidation(BinaryClassificationEvaluator(),
                                  num_folds=num_folds, seed=7),
        models=[(MultilayerPerceptronClassifier(max_iter=60), grid)])
    pred = selector.set_input(label, fv).get_output()
    listener = WorkflowListener()
    m2 = (Workflow().set_result_features(pred)
          .set_input_records(records).with_listener(listener).train())
    # selector stage time alone (the feature DAG refit inside this
    # train is already reported as feature_eng_seconds above)
    sel_s = sum(m.seconds for m in listener.metrics.stage_metrics
                if "ModelSelector" in m.stage_name)
    if not sel_s:
        raise SystemExit("no ModelSelector stage timed by the listener; "
                         "cannot report a selector rate")
    mf = len(grid) * num_folds
    print(json.dumps({
        "config": "wide_hicard_mlp", "rows": args.rows,
        "cat_features": args.cats, "cardinality": args.card,
        "vector_width": int(width),
        "feature_eng_seconds": round(feat_s, 2),
        "feature_eng_rows_per_sec": round(args.rows / feat_s),
        "mlp_selector_seconds": round(sel_s, 2),
        "mlp_models_x_folds_per_sec": round(mf / sel_s, 3),
    }))


if __name__ == "__main__":
    main()
