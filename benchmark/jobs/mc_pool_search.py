"""Job kind ``mc_pool_search``: a closed loop of one client running
``Workflow.train()`` back to back over a K-class selector's DEFAULT pool
(``benchmark/configs/covtype_mc_pool.py``: no ``models`` argument), every
family through the validator's family dispatch.

The loop is ``jobs/pool_search.py``'s: every repetition trains on a fresh
``Dataset`` over fresh copies of the same seeded table and ends when
``train()`` returns the refitted winner; the job first holds the package's
default pool to the configuration file. What a K-class pool asks besides:
no family may have taken the validator's host path (the package's counter
``host_path_families``), the winner is scored by the selector's weighted F1
against the argmax of the true P(y | x), and the references are K-class
(``benchmark/reference/``: ``multinomial_plain``, ``bayes_plain``,
``forest_plain`` as it stands, ``f1_plain``). After the window what the last
train returned (:func:`readings`) is held to them by :func:`check_readings`:
the winner's (K, d) coefficients and K intercepts against the same steps
towards the same objective taken in float64 on the same rows, and one lane of
EVERY family against its reference on the same fold (the logistic lane's: the
objective's minimiser). ``benchmark/controls_mc.py`` runs the same
function with a reference made wrong on purpose.

**What this job needs from a configuration module**, so that another pool of
the same kind (the regression selector's) is a configuration and a traffic
file and no code: ``check_pool(config)`` (the package's default pool against
the file; empty when they agree), ``tiny_pool``, ``resolved``, ``families``,
``grid``, ``make_table(config, seed, rows, part)`` returning (X, y, the true
model's scores per class), ``class_counts(config, rows)`` (what the
generator pins, so fold shapes do not follow the seed), ``dataset``,
``workflow(config, seed, columns, models)`` and ``lane_shapes(config, rows)``
with the class count in every lane's shape (``benchmark/costs_mc.py``). The
metric and the references are this file's: a regression pool needs its own
``PLAIN`` table and score.
"""
from __future__ import annotations

import json
import time
from typing import Any, Dict, List, Optional

import numpy as np

from benchmark import harness
from benchmark.jobs.pool_search import design
from benchmark.jobs.search import TRAIN_ZERO, _summary
from benchmark.reference.bayes_plain import PlainNaiveBayes
from benchmark.reference.f1_plain import predicted_class, weighted_f1
from benchmark.reference.folds_plain import stratified_folds
from benchmark.reference.forest_plain import PlainForest
from benchmark.reference.multinomial_plain import (PlainMultinomial,
                                                    to_bfloat16)

HOST_PATH = "host_path_families"


def run(ctx: harness.Context, watch: harness.CompileWatch) -> harness.Outcome:
    import jax

    from transmogrifai_tpu.observability import trace as package_trace
    from transmogrifai_tpu.runtime import telemetry
    from transmogrifai_tpu.selector import validator
    from transmogrifai_tpu.utils import WorkflowListener
    cfg = ctx.config_module
    drift = cfg.check_pool(ctx.config)
    if drift:
        raise harness.BenchFailure(
            "the package's default pool is no longer the configuration's: "
            + "; ".join(drift))
    from transmogrifai_tpu.parallel.cv import LINEAR_KERNELS
    if "softmax" not in LINEAR_KERNELS:
        # before anything is compiled. The parent of PR 32 runs this pool
        # with its logistic lanes on the validator's host path and counts
        # nothing there (tried once on the chip: PERF.md, PR 32): a result
        # that the cell's first condition could not judge is no result
        raise harness.BenchFailure(
            "the package has no fold-grid program for a logistic family "
            "over K classes (parallel/cv.py LINEAR_KERNELS has no "
            "\"softmax\" kind): this cell holds every family of the pool "
            "to a device program")
    config = cfg.resolved(ctx.config, ctx.dry_run)
    models = cfg.tiny_pool(config) if ctx.dry_run else None
    rows = ctx.size("rows")
    X, y, _ = (np.asarray(a) for a in
               cfg.make_table(config, ctx.seed, rows))
    counts = np.bincount(y.astype(np.int64)).tolist()
    if counts != cfg.class_counts(config, rows):
        raise harness.BenchFailure(f"the table's class counts {counts} are "
                                   f"not the configuration's")
    spans: List[tuple] = []

    class StageSpans(WorkflowListener):
        """Per-stage seconds of one train, and a host span for each."""

        def on_stage_completed(self, stage, phase, seconds, n_rows,
                               compile_seconds=0.0):
            super().on_stage_completed(stage, phase, seconds, n_rows,
                                       compile_seconds)
            now = time.monotonic()
            spans.append((f"stage.{stage.stage_name()}", now - seconds, now))

    last: Dict[str, Any] = {}

    def train(ds) -> Dict[str, Any]:
        workflow, prediction = cfg.workflow(config, ctx.seed, X.shape[1],
                                            models=models)
        workflow = workflow.set_input_dataset(ds)
        listener = StageSpans() if ctx.trace else None
        if listener is not None:
            workflow = workflow.with_listener(listener)
        before = telemetry.counters()
        model = workflow.train()
        jax.block_until_ready(model.train_dataset[prediction].data)
        after = telemetry.counters()
        summary = _summary(model)
        if summary.quarantined:
            raise RuntimeError(f"families quarantined: {summary.quarantined}")
        if after.get("retries", 0) != before.get("retries", 0):
            raise RuntimeError("the train retried a dispatch")
        last.update(model=model, prediction=prediction)
        stages = {} if listener is None else {
            f"{m.stage_name}/{m.phase}": m.seconds
            for m in listener.metrics.stage_metrics}
        return {"stages": stages,
                HOST_PATH: after[HOST_PATH] - before.get(HOST_PATH, 0)}

    train(cfg.dataset(X, y))                   # compiles, or loads the cache
    validator.reset_family_profile()
    if ctx.trace:
        package_trace.configure(True)          # host spans, in memory
    window = harness.run_window(ctx, watch, lambda: cfg.dataset(X, y), train,
                                spans)
    families = validator.family_profile()
    harness.say(f"family threads over the window: {families}")
    if ctx.trace:
        recorded = [s for s in package_trace.spans() if s["dur"] is not None]
        spans.extend((s["name"], s["t0"], s["t0"] + s["dur"])
                     for s in recorded)
        harness.say("search.dispatch spans: " + str(
            [dict(s["attrs"], seconds=round(s["dur"], 4)) for s in recorded
             if s["name"] == "search.dispatch"]))
        harness.say("search.family spans on the host path: " + str(
            [dict(s["attrs"], seconds=round(s["dur"], 4)) for s in recorded
             if s["name"] == "search.family"
             and s["attrs"].get("path") == "host"]))
        package_trace.configure(False)

    expected = config["selector"]["models_x_folds"]
    problems = _check(ctx, cfg, config, last, expected)
    if not problems:
        got = readings(last["model"], ctx.seed, rows)
        harness.say("readings: " + json.dumps(got))
        problems = check_readings(cfg, config, ctx.size("check_lanes"), got,
                                  X, y)
    counters = telemetry.counters()
    problems += [f"counter {name} = {counters[name]} (must be 0)"
                 for name in TRAIN_ZERO if counters.get(name, 0)]
    on_host = [r[HOST_PATH] for r in window.reps if r.get("ok")]
    harness.say(f"families on the validator's host path, by train: {on_host}")
    if any(on_host):
        problems.append(f"families took the validator's host path (by train: "
                        f"{on_host}): every family of the default pool is "
                        f"due as a fold-grid device program")
    return window.outcome({"search_mf_per_s": window.rate(expected)},
                          problems, spans, family_profile=families,
                          pool_lane_shapes=cfg.lane_shapes(config, rows),
                          matrix_rows=rows)


def _check(ctx, cfg, config, last, expected) -> List[str]:
    """Every candidate evaluated with a finite metric, and the winner's
    hold-out weighted F1 beside that of the argmax of the true P(y | x) on
    the same rows."""
    from transmogrifai_tpu.selector.selector import models_x_folds
    if "model" not in last:
        return ["no train completed"]
    model, ref = last["model"], config["reference"]
    summary = _summary(model)
    problems = []
    evaluated = models_x_folds(model)
    if evaluated != expected:
        problems.append(f"the search evaluated {evaluated} models x folds, "
                        f"expected {expected}")
    if not all(np.isfinite(r.metric_values).all()
               for r in summary.validation_results):
        problems.append("a candidate's cross-validation metric is not finite")
    X_hold, y_hold, bayes = (np.asarray(a) for a in cfg.make_table(
        config, ctx.seed, ctx.size("holdout_rows"), part=1))
    scored = model.score(cfg.dataset(X_hold, y_hold).drop(["label"]))
    got = weighted_f1(y_hold, predicted_class(np.asarray(
        scored[last["prediction"]].probability)))
    best = weighted_f1(y_hold, predicted_class(bayes))
    harness.say(f"winner: {summary.best_model_name} "
                f"{summary.best_model_params}  cv {summary.evaluation_metric}="
                f"{summary.best_validation_metric:.4f}  models_x_folds: "
                f"{evaluated}  hold-out weighted F1 on {len(y_hold)} rows: "
                f"{got:.4f} (the argmax of the true P(y|x) scores "
                f"{best:.4f})")
    low, high = ref["search_f1_below_bayes"], ref["search_f1_above_bayes"]
    if not best - low <= got <= best + high:
        problems.append(f"winner's hold-out weighted F1 {got:.4f} is outside "
                        f"[{best - low:.4f}, {best + high:.4f}]")
    return problems


def readings(model, seed: int, rows: int) -> Dict[str, Any]:
    """What the last train returned, as plain data (one line of the log, so
    that ``benchmark/controls_mc.py`` can hold the same numbers to a
    reference made wrong): every lane's validation metric by family and grid
    index, and the refitted winner with its (K, d) coefficients and K
    intercepts where it has any."""
    from transmogrifai_tpu.selector import SelectedModel
    summary = _summary(model)
    inner = next(s.inner for s in model.stages()
                 if isinstance(s, SelectedModel) and s.summary is not None)
    winner = {"family": summary.best_model_name,
              "params": dict(summary.best_model_params)}
    if hasattr(inner, "coefficients"):
        winner.update(
            coefficients=np.asarray(inner.coefficients, np.float64).tolist(),
            intercept=np.asarray(inner.intercept, np.float64).tolist())
    lanes: Dict[str, Dict[str, Any]] = {}
    for r in summary.validation_results:
        lanes.setdefault(r.model_name, {})[str(r.grid_index)] = {
            "params": dict(r.params),
            "folds": [float(v) for v in r.metric_values]}
    return {"seed": seed, "rows": rows, "metric": summary.evaluation_metric,
            "winner": winner, "lanes": lanes}


def _plain(family: Dict[str, Any], point: Dict[str, Any],
           override: Optional[Dict[str, Any]], **more):
    """A family's plain reference at a grid point; a control's ``override``
    (family class -> constructor arguments) makes it wrong on purpose. The
    single tree is the plain forest with one tree, no bagging and every
    feature, where nothing is drawn."""
    name, params = family["class"], family["params"]
    kwargs = dict(point, **more)
    if name == "RandomForestClassifier":
        cls = PlainForest
        kwargs.update(
            num_trees=params["num_trees"], max_bins=params["max_bins"],
            feature_subset_strategy=params["feature_subset_strategy"])
    elif name == "DecisionTreeClassifier":
        cls = PlainForest
        kwargs.update(num_trees=1, bootstrap=False,
                      max_bins=params["max_bins"])
    elif name == "NaiveBayes":
        cls = PlainNaiveBayes
    else:
        cls = PlainMultinomial
    kwargs.update((override or {}).get(name, {}))
    return cls(**kwargs)


def _plain_lane(config, family: Dict[str, Any], point: Dict[str, Any],
                X: np.ndarray, y: np.ndarray, train: np.ndarray,
                held: np.ndarray, seed: int,
                override: Optional[Dict[str, Any]]) -> float:
    """One lane as its family's plain reference scores it: fitted on the
    whole table under the fold's training mask, weighted F1 of the argmax on
    the fold's own rows. The logistic reference and the single tree take the
    table's 54 columns for the selector's 108 (a constant column gets no
    coefficient and no split). Naive Bayes normalises over the number of
    columns and the forest's pool sizes follow it, so both get all 108; the
    forest's draws are its own, so its reading is the mean over
    ``forest_reference_seeds`` forests, as in ``jobs/pool_search.py``."""
    name = family["class"]
    lower = (override or {}).get("tree_data")
    if lower and name in ("RandomForestClassifier", "DecisionTreeClassifier"):
        X = (to_bfloat16(X) if lower == "bfloat16"  # a control: rounded table
             else X.astype(lower)).astype(np.float64)

    def f1(plain, table) -> float:
        fitted = plain.fit(table, y, mask=train)
        scores = (fitted.votes(table[held]) if isinstance(plain, PlainForest)
                  else fitted.scores(table[held]))
        return weighted_f1(y[held], predicted_class(scores))

    if name == "RandomForestClassifier":
        table = design(X)
        return float(np.mean([
            f1(_plain(family, point, override, seed=seed + k), table)
            for k in range(config["reference"]["forest_reference_seeds"])]))
    return f1(_plain(family, point, override),
              design(X) if name == "NaiveBayes" else X)


def coefficient_distance(have_w, have_b, plain: PlainMultinomial) -> float:
    """The largest absolute difference between two fits of one objective, in
    the space the objective is posed in: the standardized coefficients (a raw
    coefficient times its column's deviation) and the intercepts at the
    columns' means. Raw coefficients span five orders of magnitude here (a
    metre of elevation against a soil-type indicator), and a raw intercept is
    a difference of terms of size 10 (elevation's mean is ten deviations from
    zero): in the raw space the largest coordinate would show the intercepts'
    cancellation and nothing of the other 756 numbers."""
    have_w = np.asarray(have_w, np.float64)
    have_b = np.asarray(have_b, np.float64)
    mu = np.asarray(plain.mu, np.float64)
    sigma = np.asarray(plain.sigma, np.float64)
    want_w = np.asarray(plain.coefficients, np.float64)
    want_b = np.asarray(plain.intercept, np.float64)
    return float(max(np.max(np.abs((have_w - want_w) * sigma)),
                     np.max(np.abs(have_b + have_w @ mu
                                   - want_b - want_w @ mu))))


#: the refit's stopping rule (``models/linear.py``: ``fista_minimize`` under
#: solver "auto" with the L1 term on), part of the schedule the reference takes
REFIT_STOP = 1e-7


def _check_winner(cfg, config, got: Dict[str, Any], X, y,
                  override) -> List[str]:
    """The refitted winner's coefficients against the same steps towards the
    same objective taken by the plain reference in float64 on the same rows
    (all of them: the refit has no fold; ``multinomial_plain``'s ``schedule``:
    the package's step, ``5 * max_iter`` accelerated steps, its stopping
    rule). The one limit of the cell that tells float32 from less: a lane's
    F1 counts argmaxes, and argmaxes survive a bfloat16 fit. Held to the
    schedule and not to the minimiser because 250 steps do not reach the
    minimiser at the least regularised grid points (``controls_mc.py`` prints
    how far short they stop); the logistic LANE below is held to the
    minimiser, by its score."""
    winner = got["winner"]
    name, limit = winner["family"], config["reference"][
        "winner_coefficients_within"]
    by_class = {family["class"]: family for family in cfg.families(config)}
    if name != "LogisticRegression" or "coefficients" not in winner:
        return [f"the winner is {name} {winner['params']}: the label is "
                f"multinomial-logistic, so the logistic family is due, and "
                f"only its refit has coefficients to hold to a reference"]
    t0 = time.perf_counter()
    family = by_class[name]
    plain = _plain(family, winner["params"], override, schedule={
        "steps": 5 * family["params"]["max_iter"], "stop": REFIT_STOP}).fit(
            design(X), y)
    have_w = np.asarray(winner["coefficients"], np.float64)
    if have_w.shape != np.shape(plain.coefficients):
        return [f"the winner's coefficients are {have_w.shape}, the "
                f"reference's {np.shape(plain.coefficients)}"]
    off = coefficient_distance(have_w, winner["intercept"], plain)
    harness.say(f"winner {name} {winner['params']}: standardized "
                f"coefficients and intercepts within {off:.3e} of the plain "
                f"reference's after the same {plain.steps} steps (largest "
                f"{np.max(np.abs(plain.coefficients * plain.sigma)):.3f}; "
                f"{time.perf_counter() - t0:.1f} s), limit {limit}")
    if not off <= limit:
        return [f"the winner's coefficients are {off:.3e} from the plain "
                f"reference's, limit {limit}"]
    return []


def check_readings(cfg, config, check_lanes, got: Dict[str, Any],
                   X: np.ndarray, y: np.ndarray,
                   override: Optional[Dict[str, Any]] = None,
                   only: Optional[set] = None) -> List[str]:
    """``got`` (:func:`readings`) against the plain references: the winner's
    coefficients, then the lanes the traffic file samples (``check_lanes``,
    [family, grid index, fold, tolerance] each; a tolerance is a number or
    [below, above] for system minus reference): the same folds by the plain
    rule, the family's plain reference under the fold's training mask, and
    the selector's weighted F1 on the fold's own rows. ``override`` and
    ``only`` (family classes) are for the controls. One check after another,
    on one thread: side by side on threads, NumPy's matrix products read
    wrong by 4e-3 and more on the builder's sandbox (PERF.md, PR 32)."""
    sel, seed = config["selector"], got["seed"]
    by_class = {family["class"]: family for family in cfg.families(config)}
    fold_of = stratified_folds(
        y, sel["num_folds"], (override or {}).get("fold_seed", seed))

    def lane_check(name, index, fold, tol) -> List[str]:
        t0 = time.perf_counter()
        point = cfg.grid(by_class[name])[index]
        lane = got["lanes"].get(name, {}).get(str(index))
        if lane is None or lane["params"] != point:
            return [f"{name} grid point {index} ran as "
                    f"{lane and lane['params']}, not {point}"]
        below, above = tol if isinstance(tol, list) else (tol, tol)
        train, held = (fold_of >= 0) & (fold_of != fold), fold_of == fold
        want = _plain_lane(config, by_class[name], point, X, y, train, held,
                           seed, override)
        have = lane["folds"][fold]
        harness.say(f"lane {name} {point} fold {fold}: "
                    f"{got['metric']} {have:.6f}, plain "
                    f"cross-validation {want:.6f} (fitted in "
                    f"{time.perf_counter() - t0:.1f} s), tolerance "
                    f"-{below} / +{above}")
        if not -below <= have - want <= above:
            return [f"lane {name} {point} fold {fold} scores {have:.6f}, "
                    f"the plain cross-validation {want:.6f}"]
        return []

    problems = []
    if only is None or got["winner"]["family"] in only:
        problems += _check_winner(cfg, config, got, X, y, override)
    for lane in check_lanes:
        if only is None or lane[0] in only:
            problems += lane_check(*lane)
    return problems
