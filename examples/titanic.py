"""Titanic survival — the framework's hello-world classification app.

TPU-native equivalent of the reference example
(helloworld/src/main/scala/com/salesforce/hw/OpTitanicSimple.scala:152 and
the README.md:61-89 workflow whose holdout AuPR of 0.8225 is the parity
target). Feature engineering mirrors OpTitanicSimple: typed raw features,
familySize / estimatedCostOfTickets arithmetic, pivoted sex, age group,
normalized age, then ``transmogrify`` + a model over the combined vector.

Run:  python examples/titanic.py
"""
from __future__ import annotations

import csv
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from transmogrifai_tpu.evaluators import BinaryClassificationEvaluator
from transmogrifai_tpu.features.builder import FeatureBuilder
from transmogrifai_tpu.models import LogisticRegression
from transmogrifai_tpu.ops import transmogrify
from transmogrifai_tpu.types import PickList
from transmogrifai_tpu.workflow import Workflow

#: headerless CSV schema (reference test-data/PassengerDataAll.avsc)
CSV_COLUMNS = ["id", "survived", "pClass", "name", "sex", "age",
               "sibSp", "parCh", "ticket", "fare", "cabin", "embarked"]

#: rows of the reference CSV; the seeded stand-in is generated this long
TITANIC_ROWS = 1309


def load_titanic(path: str = None):
    """Parse the Titanic CSV at ``path`` (default: ``TITANIC_CSV``) into
    typed records (dicts). The reference CSV is not part of this
    checkout: without a path the flagship runs on
    :func:`synthetic_titanic`, and only the 0.8225 parity assertions
    need the real rows."""
    csv_path = path or os.environ.get("TITANIC_CSV")
    if not csv_path:
        raise FileNotFoundError(
            "no Titanic CSV given; pass a path or set TITANIC_CSV")

    def _f(v):
        return float(v) if v not in ("", None) else None

    def _i(v):
        return int(v) if v not in ("", None) else None

    def _s(v):
        return v if v not in ("", None) else None

    records = []
    with open(csv_path, newline="") as fh:
        for row in csv.reader(fh):
            rec = dict(zip(CSV_COLUMNS, row))
            records.append({
                "id": _i(rec["id"]),
                "survived": _f(rec["survived"]),
                "pClass": _s(rec["pClass"]),
                "name": _s(rec["name"]),
                "sex": _s(rec["sex"]),
                "age": _f(rec["age"]),
                "sibSp": _i(rec["sibSp"]),
                "parCh": _i(rec["parCh"]),
                "ticket": _s(rec["ticket"]),
                "fare": _f(rec["fare"]),
                "cabin": _s(rec["cabin"]),
                "embarked": _s(rec["embarked"]),
            })
    return records


def synthetic_titanic(n: int = 1000, seed: int = 42):
    """Titanic-SHAPED records (same schema, plausible marginals),
    seeded: what the flagship, the benchmarks and ``chip_smoke.py``
    train on — the exact production DAG and grid; only
    parity-vs-0.8225 assertions need the real data."""
    rng = np.random.default_rng(seed)
    classes = np.asarray(["1", "2", "3"])
    sexes = np.asarray(["male", "female"])
    ports = np.asarray(["S", "C", "Q", None], dtype=object)
    records = []
    for i in range(n):
        sex = str(rng.choice(sexes))
        p_class = str(rng.choice(classes, p=[0.24, 0.21, 0.55]))
        age = None if rng.uniform() < 0.2 else float(
            np.clip(rng.normal(29, 14), 0.5, 80))
        fare = None if rng.uniform() < 0.02 else float(
            np.round(rng.gamma(2.0, 16.0), 4))
        logit = (1.2 * (sex == "female") - 0.5 * (p_class == "3")
                 - 0.01 * (age or 29) + 0.004 * (fare or 32) - 0.4)
        records.append({
            "id": i,
            "survived": float(rng.uniform() < 1 / (1 + np.exp(-logit))),
            "pClass": p_class,
            "name": f"Passenger {i} {'Mrs' if sex == 'female' else 'Mr'}",
            "sex": sex,
            "age": age,
            "sibSp": int(rng.poisson(0.5)),
            "parCh": int(rng.poisson(0.4)),
            "ticket": f"T{rng.integers(1000, 9999)}",
            "fare": fare,
            "cabin": None if rng.uniform() < 0.77
            else f"{'ABCDEF'[int(rng.integers(6))]}{rng.integers(1, 99)}",
            "embarked": rng.choice(ports, p=[0.72, 0.19, 0.08, 0.01]),
        })
    return records


#: one servable passenger record (the save+serve demo below and the
#: parity test's round-trip share it so they cannot drift apart)
SAMPLE_PASSENGER = {"pClass": "1", "sex": "female", "age": 29.0,
                    "sibSp": 0, "parCh": 0, "fare": 100.0,
                    "embarked": "S", "name": "Test Passenger",
                    "ticket": "t", "cabin": "C1"}


def demo_serve(model, path: str) -> dict:
    """Persist ``model`` to ``path``, reload via the local serving
    entry point, and score :data:`SAMPLE_PASSENGER` — the reference
    helloworld's save+serve story. Returns the served prediction dict."""
    from transmogrifai_tpu.local import load_score_function
    model.save(path)
    score = load_score_function(path)
    row = score(dict(SAMPLE_PASSENGER))
    pred_key = next(f.name for f in model.result_features
                    if f.name != "survived")
    return row[pred_key]


def age_to_group(a) -> PickList:
    """Binned age (module-level so the stage survives model save/load —
    closures can't; reference checkSerializable)."""
    return PickList(None if a.is_empty
                    else ("adult" if a.value > 18 else "child"))


def build_features():
    """Raw + engineered features (OpTitanicSimple.scala:103-131)."""
    survived = FeatureBuilder.real_nn("survived").extract(
        lambda r: r["survived"]).as_response()
    p_class = FeatureBuilder.pick_list("pClass").extract(
        lambda r: r["pClass"]).as_predictor()
    name = FeatureBuilder.text("name").extract(
        lambda r: r["name"]).as_predictor()
    sex = FeatureBuilder.pick_list("sex").extract(
        lambda r: r["sex"]).as_predictor()
    age = FeatureBuilder.real("age").extract(
        lambda r: r["age"]).as_predictor()
    sib_sp = FeatureBuilder.integral("sibSp").extract(
        lambda r: r["sibSp"]).as_predictor()
    par_ch = FeatureBuilder.integral("parCh").extract(
        lambda r: r["parCh"]).as_predictor()
    ticket = FeatureBuilder.pick_list("ticket").extract(
        lambda r: r["ticket"]).as_predictor()
    fare = FeatureBuilder.real("fare").extract(
        lambda r: r["fare"]).as_predictor()
    cabin = FeatureBuilder.pick_list("cabin").extract(
        lambda r: r["cabin"]).as_predictor()
    embarked = FeatureBuilder.pick_list("embarked").extract(
        lambda r: r["embarked"]).as_predictor()

    # engineered features (OpTitanicSimple.scala:119-124)
    family_size = (sib_sp + par_ch + 1).alias("familySize")
    ticket_cost = (family_size * fare).alias("estimatedCostOfTickets")
    pivoted_sex = sex.pivot()
    normed_age = age.fill_missing_with_mean().z_normalize()
    age_group = age.map(age_to_group, PickList).alias("ageGroup")

    passenger_features = transmogrify([
        p_class, name, age, sib_sp, par_ch, ticket, cabin, embarked,
        family_size, ticket_cost, pivoted_sex, age_group, normed_age,
    ])
    return survived, passenger_features


def stratified_split(records, label_key="survived", test_fraction=0.25,
                     seed=42):
    """Seeded stratified holdout split (reference tuning/Splitter.scala:56)."""
    rng = np.random.default_rng(seed)
    y = np.array([r[label_key] for r in records])
    idx = np.arange(len(records))
    test_idx = []
    for cls in np.unique(y):
        cls_idx = idx[y == cls]
        perm = rng.permutation(cls_idx)
        n_test = int(round(len(cls_idx) * test_fraction))
        test_idx.extend(perm[:n_test])
    test_mask = np.zeros(len(records), dtype=bool)
    test_mask[test_idx] = True
    train = [records[i] for i in idx[~test_mask]]
    test = [records[i] for i in idx[test_mask]]
    return train, test


def default_selector(num_folds: int = 3, seed: int = 42,
                     validation: str = "exact", eta: int = 3,
                     min_fidelity: float = None):
    """BinaryClassificationModelSelector with CV over the default model
    pool (the reference README.md:61-63 runs 3 LR + 16 RF under 3-fold
    CV; our pool is whatever ``default_binary_models`` currently
    registers — linear families always, tree families once present).
    ``validation="racing"`` races the pool under successive halving
    (docs/selection.md) instead of training all of it to completion."""
    from transmogrifai_tpu.selector import BinaryClassificationModelSelector
    return BinaryClassificationModelSelector.with_cross_validation(
        num_folds=num_folds, seed=seed, stratify=True,
        validation=validation, eta=eta, min_fidelity=min_fidelity)


def run(csv_path: str = None, model_stage=None, verbose: bool = True,
        workflow_cv: bool = False, listener=None,
        validation: str = "exact", min_fidelity: float = None,
        records=None):
    """Train on a 75% split, evaluate on the 25% holdout.

    ``workflow_cv=True`` enables leakage-free workflow-level CV (every
    label-consuming selector ancestor refit per fold; reference
    withWorkflowCV). ``listener`` (a WorkflowListener) collects the
    per-stage profile. ``validation="racing"`` runs the selector search
    under successive halving. Data: ``records`` (pre-parsed dicts) if
    given, else the CSV at ``csv_path`` / ``TITANIC_CSV``, else the
    seeded ``synthetic_titanic(TITANIC_ROWS)``.
    Returns (metrics, wall_clock_seconds, model).
    """
    real_data = records is None and bool(
        csv_path or os.environ.get("TITANIC_CSV"))
    if real_data:
        records = load_titanic(csv_path)
    elif records is None:
        records = synthetic_titanic(TITANIC_ROWS)
    train, test = stratified_split(records)
    survived, features = build_features()
    stage = (model_stage if model_stage is not None
             else default_selector(validation=validation,
                                   min_fidelity=min_fidelity))
    prediction = stage.set_input(survived, features).get_output()

    t0 = time.perf_counter()
    wf = (Workflow()
          .set_result_features(survived, prediction)
          .set_input_records(train))
    if workflow_cv:
        wf = wf.with_workflow_cv()
    if listener is not None:
        wf = wf.with_listener(listener)
    model = wf.train()
    evaluator = BinaryClassificationEvaluator(
        label_col="survived", prediction_col=prediction.name)
    _, metrics = model.score_and_evaluate(test, evaluator)
    elapsed = time.perf_counter() - t0

    if verbose:
        from transmogrifai_tpu.selector import SelectedModel
        for s in model.stages():
            if isinstance(s, SelectedModel) and s.summary is not None:
                print(s.summary.pretty())
        print(f"Train rows: {len(train)}, holdout rows: {len(test)}")
        # the reference's figures are for the real CSV only
        print(f"Holdout AuPR:   {metrics.AuPR:.4f}"
              + ("  (reference 0.8225)" if real_data else ""))
        print(f"Holdout AuROC:  {metrics.AuROC:.4f}"
              + ("  (reference 0.8822)" if real_data else ""))
        if not real_data:
            print("Data: seeded synthetic_titanic rows")
        print(f"Holdout F1:     {metrics.F1:.4f}")
        print(f"Holdout Error:  {metrics.Error:.4f}")
        print(f"Wall clock: {elapsed:.2f}s")
    return metrics, elapsed, model


if __name__ == "__main__":
    metrics, _, model = run(
        csv_path=sys.argv[1] if len(sys.argv) > 1 else None)
    # the reference helloworld's full story: persist the trained
    # selector model and serve single records from the saved dir
    # (kept OUT of run(), which callers time as train + eval only)
    import tempfile
    path = os.path.join(tempfile.mkdtemp(prefix="titanic_"), "model")
    served = demo_serve(model, path)
    print(f"saved -> {path}; served one record: "
          f"P(survived)={served['probability_1']:.3f}")
