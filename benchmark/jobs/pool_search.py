"""Job kind ``pool_search``: a closed loop of one client running
``Workflow.train()`` back to back over the binary selector's DEFAULT pool
(``benchmark/configs/synth100_pool.py``: no ``models`` argument), four
families through the validator's family dispatch.

As ``jobs/search.py`` (whose summary and winner checks are used as they are):
every repetition trains on a fresh ``Dataset`` over fresh copies of the same
seeded table and ends when ``train()`` returns the refitted winner. What
differs: the job first holds the package's default pool to the configuration
file, and results are keyed by (family, grid index). After the window what
the last train returned (:func:`readings`: every lane's metric, the winner's
refitted coefficients) is held to the plain references
(``benchmark/reference/``) by :func:`check_readings`: the winner's
coefficients against the same objective minimised in float64 on the same
rows, and one lane of EVERY family against its reference on the same fold.
``benchmark/controls_pool.py`` runs the same function with a reference made
wrong on purpose (float16, another ``reg_param``, ten trees): what comes out
not correct there is what the cell's limits can show.
"""
from __future__ import annotations

import json
import time
from typing import Any, Dict, List, Optional

import numpy as np

from benchmark import harness
from benchmark.jobs.search import TRAIN_ZERO, _check, _summary
from benchmark.reference.folds_plain import stratified_folds
from benchmark.reference.forest_plain import PlainForest
from benchmark.reference.gbt_plain import PlainGBT
from benchmark.reference.linear_plain import PlainLogistic, PlainSVC
from benchmark.reference.metrics_plain import aupr

LINEAR = {"LogisticRegression": PlainLogistic, "LinearSVC": PlainSVC}


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
            "the package's default binary pool is no longer the "
            "configuration's: " + "; ".join(drift))
    config = cfg.resolved(ctx.config, ctx.dry_run)
    models = cfg.tiny_pool(config) if ctx.dry_run else None
    rows = ctx.size("rows")
    X, y, _ = (np.asarray(a) for a in
               cfg.make_table(config, ctx.seed, rows))
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
        retries = telemetry.counters().get("retries", 0)
        model = workflow.train()
        jax.block_until_ready(model.train_dataset[prediction].data)
        summary = _summary(model)
        if summary.quarantined:
            raise RuntimeError(f"families quarantined: {summary.quarantined}")
        if telemetry.counters().get("retries", 0) != retries:
            raise RuntimeError("the train retried a dispatch")
        last.update(model=model, prediction=prediction)
        stages = {} if listener is None else {
            f"{m.stage_name}/{m.phase}": m.seconds
            for m in listener.metrics.stage_metrics}
        return {"stages": stages}

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
    shapes = cfg.lane_shapes(config, rows)
    return window.outcome({"search_mf_per_s": window.rate(expected)},
                          problems, spans, family_profile=families,
                          pool_lane_shapes=shapes,
                          lane_shapes=shapes["GBTClassifier"],
                          matrix_rows=rows)


def readings(model, seed: int, rows: int) -> Dict[str, Any]:
    """What the last train returned, as plain data (one line of the log, so
    that ``benchmark/controls_pool.py`` can hold the same numbers to a
    reference made wrong): every lane's validation metric by family and grid
    index, and the refitted winner with its coefficients where it has any."""
    from transmogrifai_tpu.selector import SelectedModel
    summary = _summary(model)
    inner = next(s.inner for s in model.stages()
                 if isinstance(s, SelectedModel) and s.summary is not None)
    winner = {"family": summary.best_model_name,
              "params": dict(summary.best_model_params)}
    if hasattr(inner, "coefficients"):
        winner.update(
            coefficients=np.asarray(inner.coefficients, np.float64).tolist(),
            intercept=float(inner.intercept))
    lanes: Dict[str, Dict[str, Any]] = {}
    for r in summary.validation_results:
        lanes.setdefault(r.model_name, {})[str(r.grid_index)] = {
            "params": dict(r.params),
            "folds": [float(v) for v in r.metric_values]}
    return {"seed": seed, "rows": rows, "metric": summary.evaluation_metric,
            "winner": winner, "lanes": lanes}


def design(X: np.ndarray) -> np.ndarray:
    """The selector's columns behind ``transmogrify()``: each nullable Real
    column as (value, null indicator). No value is missing here, so every
    indicator is a constant 0: never split on, coefficient 0."""
    out = np.zeros((X.shape[0], 2 * X.shape[1]), X.dtype)
    out[:, 0::2] = X
    return out


def _plain(family: Dict[str, Any], point: Dict[str, Any],
           override: Optional[Dict[str, Any]], **more):
    """A family's plain reference at a grid point; a control's ``override``
    (family class -> constructor arguments) makes it wrong on purpose."""
    name, params = family["class"], family["params"]
    kwargs = dict(point, **more)
    if name == "GBTClassifier":
        cls = PlainGBT
        kwargs.update(max_bins=params["max_bins"],
                      num_rounds=params["num_rounds"])
    elif name == "RandomForestClassifier":
        cls = PlainForest
        kwargs.update(
            num_trees=params["num_trees"], max_bins=params["max_bins"],
            feature_subset_strategy=params["feature_subset_strategy"])
    else:
        cls = LINEAR[name]
    kwargs.update((override or {}).get(name, {}))
    return cls(**kwargs)


def _plain_lane(cfg, config, family: Dict[str, Any], point: Dict[str, Any],
                X: np.ndarray, y: np.ndarray, train: np.ndarray,
                held: np.ndarray, seed: int,
                override: Optional[Dict[str, Any]]) -> float:
    """One lane as its family's plain reference scores it: fitted on the
    whole table under the fold's training mask, AuPR on the fold's own rows.
    The boosted and the linear references take the table's 100 columns for
    the selector's 200 (a constant column changes neither). The forest's pool
    sizes follow the number of columns, so it gets all 200; its draws are its
    own, so its reading is the mean over ``forest_reference_seeds`` forests:
    the system's forest draws from one fixed key (the estimator's ``seed``,
    42) whatever the table, so the noise of the difference is the
    reference's, and the mean cuts it."""
    name = family["class"]
    lower = (override or {}).get("tree_data")
    if lower and name not in LINEAR:    # a control: the trees' table rounded
        X = X.astype(lower).astype(np.float64)
    if name == "RandomForestClassifier":
        table = design(X)
        return float(np.mean([
            aupr(y[held], _plain(family, point, override, seed=seed + k)
                 .fit(table, y, mask=train).predict_proba(table[held]))
            for k in range(config["reference"]["forest_reference_seeds"])]))
    plain = _plain(family, point, override).fit(X, y, mask=train)
    if name == "GBTClassifier":
        return aupr(y[held], plain.predict_proba(X[held]))
    return aupr(y[held], np.asarray(plain.decision(X[held]), np.float64))


def _check_winner(cfg, config, got: Dict[str, Any], X, y,
                  override) -> List[str]:
    """The refitted winner's coefficients against the same objective
    minimised by the plain reference on the same rows (all of them: the
    refit has no fold). The one limit of the cell that tells float32 from
    less: a lane's AuPR ranks, and ranks survive float16."""
    winner = got["winner"]
    name, limit = winner["family"], config["reference"][
        "winner_coefficients_within"]
    by_class = {family["class"]: family for family in cfg.families(config)}
    if name not in LINEAR or "coefficients" not in winner:
        return [f"the winner is {name} {winner['params']}: the label is "
                f"logistic in four columns, so a linear family is due, and "
                f"only its refit has coefficients to hold to a reference"]
    t0 = time.perf_counter()
    plain = _plain(by_class[name], winner["params"], override).fit(
        design(X), y)
    want = np.append(np.asarray(plain.coefficients, np.float64),
                     plain.intercept)
    have = np.append(winner["coefficients"], winner["intercept"])
    if have.shape != want.shape:
        return [f"the winner has {have.size - 1} coefficients, the design "
                f"{want.size - 1} columns"]
    off = float(np.max(np.abs(have - want)))
    harness.say(f"winner {name} {winner['params']}: coefficients within "
                f"{off:.3e} of the plain reference's (largest "
                f"{np.max(np.abs(want)):.3f}; {plain.steps} steps, "
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
    the selector's AuPR on the fold's own rows. ``override`` and ``only``
    (family classes) are for the controls."""
    sel, seed = config["selector"], got["seed"]
    by_class = {family["class"]: family for family in cfg.families(config)}
    fold_of = stratified_folds(
        y, sel["num_folds"], (override or {}).get("fold_seed", seed))
    problems = []
    if only is None or got["winner"]["family"] in only:
        problems += _check_winner(cfg, config, got, X, y, override)
    for name, index, fold, tol in check_lanes:
        if only is not None and name not in only:
            continue
        t0 = time.perf_counter()
        point = cfg.grid(by_class[name])[index]
        lane = got["lanes"].get(name, {}).get(str(index))
        if lane is None or lane["params"] != point:
            problems.append(f"{name} grid point {index} ran as "
                            f"{lane and lane['params']}, not {point}")
            continue
        below, above = tol if isinstance(tol, list) else (tol, tol)
        train, held = (fold_of >= 0) & (fold_of != fold), fold_of == fold
        want = _plain_lane(cfg, config, by_class[name], point, X, y, train,
                           held, seed, override)
        have = lane["folds"][fold]
        harness.say(f"lane {name} {point} fold {fold}: "
                    f"{got['metric']} {have:.6f}, plain "
                    f"cross-validation {want:.6f} (fitted in "
                    f"{time.perf_counter() - t0:.1f} s), tolerance "
                    f"-{below} / +{above}")
        if not -below <= have - want <= above:
            problems.append(f"lane {name} {point} fold {fold} scores "
                            f"{have:.6f}, the plain cross-validation "
                            f"{want:.6f}")
    return problems
