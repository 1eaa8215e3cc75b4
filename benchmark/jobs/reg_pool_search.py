"""Job kind ``reg_pool_search``: a closed loop of one client running
``Workflow.train()`` back to back over the REGRESSION selector's DEFAULT pool
(``benchmark/configs/msd_reg_pool.py``: no ``models`` argument; LinReg, RF,
GBT, GLM), every family through the validator's family dispatch.

The loop is ``jobs/pool_search.py``'s (its ``readings`` and ``design`` are
used as they are, ``jobs/search.py``'s summary and counters too): every
repetition trains on a fresh ``Dataset`` over fresh copies of the same seeded
table and ends when ``train()`` returns the refitted winner; the job first
holds the package's default pool to the configuration file and refuses,
before anything is compiled, a package without what this cell checks: the
IRLS lanes as the fold-grid program ``jit_glm_batched`` and regression trees
whose statistics survive the chip's histogram (PR 34). What a regression pool
asks besides: no family on the validator's host path (the counter
``host_path_families``), the winner picked by the SMALLEST metric (RMSE) and
scored against the RMSE of the true model m(x) on the same hold-out rows,
folds that are not stratified, and references that predict a number
(``benchmark/reference/``: ``linreg_plain``, ``glm_plain``,
``forest_reg_plain`` over ``tree_reg_plain``, ``gbt_reg_plain``,
``rmse_plain``). After the window what the last train returned is held to
them by :func:`check_readings`: the winner's coefficients against its own
family's float64 reference on the same rows, and one lane of EVERY family
against its reference on the same fold. ``benchmark/controls_reg.py`` runs
the same function with a reference made wrong on purpose.
"""
from __future__ import annotations

import json
import time
from typing import Any, Dict, List, Optional

import numpy as np

from benchmark import harness
from benchmark.jobs.pool_search import design, readings
from benchmark.jobs.search import TRAIN_ZERO, _summary
from benchmark.reference.folds_plain import stratified_folds
from benchmark.reference.forest_reg_plain import PlainForestRegressor
from benchmark.reference.gbt_reg_plain import PlainGBTRegressor
from benchmark.reference.glm_plain import PlainGLM
from benchmark.reference.linreg_plain import PlainLinearRegression
from benchmark.reference.rmse_plain import rmse

HOST_PATH = "host_path_families"
LINEAR = ("LinearRegression", "GeneralizedLinearRegression")
#: the refit's stopping rule (``models/linear.py``: ``fista_minimize`` under
#: solver "auto" with the L1 term on), part of the schedule the reference takes
REFIT_STOP = 1e-7


def package_lacks() -> List[str]:
    """What of PR 34 the package under the job does not have (read from its
    modules, nothing compiled): empty for a package the cell can judge."""
    from transmogrifai_tpu.models import trees
    lacks = []
    if not {"fg.glm", "glm.gram", "glm.solve"} <= set(trees.SCOPES):
        lacks.append("the IRLS lanes as a fold-grid program of their own "
                     "(jit_glm_batched under the scopes fg.glm, glm.gram, "
                     "glm.solve; models/glm.py)")
    if not hasattr(trees, "_variance_stats"):
        lacks.append("regression tree statistics that survive the chip's "
                     "level histogram (models/trees.py _variance_stats: the "
                     "label centred, w*y in two pieces)")
    return lacks


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
    lacks = package_lacks()
    if lacks:
        # before anything is compiled. The parent of PR 34 runs this pool
        # with forest splits that are rounding noise and IRLS lanes nobody
        # can find in a trace: a result that the cell's limits were not set
        # for is no result
        raise harness.BenchFailure("the package lacks " + "; ".join(lacks))
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
    glm_calls: List[Dict[str, Any]] = []
    if ctx.trace:
        recorded = [s for s in package_trace.spans() if s["dur"] is not None]
        spans.extend((s["name"], s["t0"], s["t0"] + s["dur"])
                     for s in recorded)
        harness.say("search.dispatch spans: " + str(
            [dict(s["attrs"], seconds=round(s["dur"], 4)) for s in recorded
             if s["name"] == "search.dispatch"]))
        glm_calls = [dict(s["attrs"], seconds=round(s["dur"], 4))
                     for s in recorded if s["name"] == "search.fetch"
                     and "irls_iterations" in s["attrs"]]
        harness.say(f"search.fetch spans of jit_glm_batched: {glm_calls}")
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
    shapes = cfg.lane_shapes(config, rows)
    return window.outcome({"search_mf_per_s": window.rate(expected)},
                          problems, spans, family_profile=families,
                          pool_lane_shapes=shapes,
                          lane_shapes=shapes["GBTRegressor"],
                          glm_calls=glm_calls, matrix_rows=rows)


def _check(ctx, cfg, config, last, expected) -> List[str]:
    """Every candidate evaluated with a finite metric, the winner the lane of
    the smallest mean RMSE, and its hold-out RMSE beside that of the true
    model m(x) on the same rows."""
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
    smallest = min(float(np.mean(r.metric_values))
                   for r in summary.validation_results)
    if not summary.best_validation_metric <= smallest + 1e-12:
        problems.append(f"the winner cross-validates "
                        f"{summary.best_validation_metric:.6f}, the smallest "
                        f"mean RMSE of the search is {smallest:.6f}")
    X_hold, y_hold, truth = (np.asarray(a) for a in cfg.make_table(
        config, ctx.seed, ctx.size("holdout_rows"), part=1))
    scored = model.score(cfg.dataset(X_hold, y_hold).drop(["label"]))
    got = rmse(y_hold, np.asarray(scored[last["prediction"]].data))
    best = rmse(y_hold, truth)
    harness.say(f"winner: {summary.best_model_name} "
                f"{summary.best_model_params}  cv {summary.evaluation_metric}="
                f"{summary.best_validation_metric:.4f}  models_x_folds: "
                f"{evaluated}  hold-out RMSE on {len(y_hold)} rows: "
                f"{got:.4f} (the true model m(x) scores {best:.4f})")
    low, high = ref["search_rmse_below_truth"], ref["search_rmse_above_truth"]
    if not best - low <= got <= best + high:
        problems.append(f"winner's hold-out RMSE {got:.4f} is outside "
                        f"[{best - low:.4f}, {best + high:.4f}]")
    return problems


def _plain(family: Dict[str, Any], point: Dict[str, Any],
           override: Optional[Dict[str, Any]], **more):
    """A family's plain reference at a grid point; a control's ``override``
    (family class -> constructor arguments) makes it wrong on purpose."""
    name, params = family["class"], family["params"]
    kwargs = dict(point, **more)
    if name == "GBTRegressor":
        cls = PlainGBTRegressor
        kwargs.update(max_bins=params["max_bins"],
                      num_rounds=params["num_rounds"])
    elif name == "RandomForestRegressor":
        cls = PlainForestRegressor
        kwargs.update(
            num_trees=params["num_trees"], max_bins=params["max_bins"],
            feature_subset_strategy=params["feature_subset_strategy"])
    elif name == "LinearRegression":
        cls = PlainLinearRegression
    else:
        cls = PlainGLM
    kwargs.update((override or {}).get(name, {}))
    return cls(**kwargs)


def _plain_lane(config, family: Dict[str, Any], point: Dict[str, Any],
                X: np.ndarray, y: np.ndarray, train: np.ndarray,
                held: np.ndarray, seed: int,
                override: Optional[Dict[str, Any]]) -> float:
    """One lane as its family's plain reference scores it: fitted on the
    whole table under the fold's training mask, RMSE on the fold's own rows.
    Every reference gets the selector's 180 columns (``design``): a forest
    node's subset is a third of them, and the linear references' coefficients
    are compared column for column. The forest's draws are its own, so its
    reading is the mean over ``forest_reference_seeds`` forests, as in
    ``jobs/pool_search.py``."""
    name, table = family["class"], design(X)
    if name == "RandomForestRegressor":
        return float(np.mean([
            rmse(y[held], _plain(family, point, override, seed=seed + k)
                 .fit(table, y, mask=train).predict(table[held]))
            for k in range(config["reference"]["forest_reference_seeds"])]))
    return rmse(y[held], _plain(family, point, override)
                .fit(table, y, mask=train).predict(table[held]))


def coefficient_distance(have_w, have_b, plain) -> float:
    """The largest absolute difference between two fits of one objective in
    the space the objective is posed in (standardized coefficients = a raw
    coefficient times its column's deviation, the intercept taken at the
    columns' means), as a share of the reference's largest standardized
    coefficient. Raw coefficients span three orders of magnitude here (a
    timbre average of deviation 6 against a covariance of deviation 2,000),
    and the poisson lanes' live on the log scale, a two-thousandth of the
    others': a share reads alike for every family."""
    have_w = np.asarray(have_w, np.float64)
    mu = np.asarray(plain.mu, np.float64)
    sigma = np.asarray(plain.sigma, np.float64)
    want_w = np.asarray(plain.coefficients, np.float64)
    off = max(np.max(np.abs((have_w - want_w) * sigma)),
              abs(float(have_b) + have_w @ mu - plain.intercept - want_w @ mu))
    return float(off / np.max(np.abs(want_w * sigma)))


def _check_winner(cfg, config, got: Dict[str, Any], X, y,
                  override) -> List[str]:
    """The refitted winner's coefficients against its own family's plain
    reference in float64 on the same rows (all of them: the refit has no
    fold): ``LinearRegression`` against the same steps towards the same
    objective (``linreg_plain``'s ``schedule``), ``GeneralizedLinearRegression``
    against IRLS to convergence. The one limit of the cell that tells float32
    from less: a lane's RMSE is flat at the minimiser, and stays within its
    tolerance under a bfloat16 fit."""
    winner = got["winner"]
    name, limit = winner["family"], config["reference"][
        "winner_coefficients_within"]
    by_class = {family["class"]: family for family in cfg.families(config)}
    if name not in LINEAR or "coefficients" not in winner:
        return [f"the winner is {name} {winner['params']}: the label is "
                f"linear in eighteen columns, so a linear family is due, and "
                f"only its refit has coefficients to hold to a reference"]
    t0 = time.perf_counter()
    family = by_class[name]
    more = {"schedule": {"steps": 5 * family["params"]["max_iter"],
                         "stop": REFIT_STOP}} \
        if name == "LinearRegression" else {}
    plain = _plain(family, winner["params"], override, **more).fit(
        design(X), y)
    have_w = np.asarray(winner["coefficients"], np.float64)
    if have_w.shape != np.shape(plain.coefficients):
        return [f"the winner's coefficients are {have_w.shape}, the "
                f"reference's {np.shape(plain.coefficients)}"]
    off = coefficient_distance(have_w, winner["intercept"], plain)
    harness.say(f"winner {name} {winner['params']}: standardized "
                f"coefficients and intercept within {off:.3e} (as a share of "
                f"the largest, "
                f"{np.max(np.abs(plain.coefficients * plain.sigma)):.4g}) of "
                f"the plain reference's ("
                f"{getattr(plain, 'steps', None) or plain.iterations} "
                f"steps; {time.perf_counter() - t0:.1f} s), limit {limit}")
    if not off <= limit:
        return [f"the winner's coefficients are {off:.3e} from the plain "
                f"reference's, limit {limit}"]
    return []


def check_readings(cfg, config, check_lanes, got: Dict[str, Any],
                   X: np.ndarray, y: np.ndarray,
                   override: Optional[Dict[str, Any]] = None,
                   only: Optional[set] = None) -> List[str]:
    """``got`` (``pool_search.readings``) against the plain references: the
    winner's coefficients, then the lanes the traffic file samples
    (``check_lanes``, [family, grid index, fold, tolerance] each; a tolerance
    is a number or [below, above] for system minus reference): the same folds
    by the plain rule (not stratified: ``folds_plain`` over one class), the
    family's plain reference under the fold's training mask, and the
    selector's RMSE on the fold's own rows. ``override`` and ``only`` (family
    classes) are for the controls. One check after another, on one thread
    (PERF.md, PR 32)."""
    sel, seed = config["selector"], got["seed"]
    by_class = {family["class"]: family for family in cfg.families(config)}
    fold_of = stratified_folds(
        np.zeros(len(y), np.int64), sel["num_folds"],
        (override or {}).get("fold_seed", seed))
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
        want = _plain_lane(config, by_class[name], point, X, y, train, held,
                           seed, override)
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
