"""Job kind ``typed_pool_search``: a closed loop of one client running
``Workflow.train()`` back to back over TYPED columns (``Integral`` and
``PickList``, ``benchmark/configs/criteo_bin_pool.py``) through
``transmogrify()`` and ``.sanity_check(label)`` into the binary selector's
DEFAULT pool: the reference README's flow.

The loop is ``jobs/pool_search.py``'s: every repetition trains on a fresh
columnar ``Dataset`` over fresh copies of the same seeded table and ends when
``train()`` returns the refitted winner; the job first holds the package's
default pool to the configuration file and refuses, before anything is
compiled, a package whose sanity checker counts its contingency tables on
the host (the prepare layer this cell holds to the device). Every repetition
records the families the validator left on its host path (the counter
``host_path_families``) and the bytes the prepare plan and the checkers
pulled from the device (``prepare_host_pull_bytes``).

After the window what the last train built is held to the plain references
(``benchmark/reference/``), outside every timing:

- the design: ``transmogrify_plain`` fitted on the training table gives the
  column list (parent, indicator value) of the workflow's vector, and its
  transform of the hold-out rows equals the workflow's, value for value;
  ``sanity_plain`` on that design gives the kept columns, and the Cramer's V
  of every indicator group within ``cramers_v_within`` of the checker's; the
  planted near-duplicate of the label is dropped, for its Cramer's V;
- the checker's tables: the same SanityChecker fitted on the workflow's
  device matrix through ``fit_device`` (the tables counted on the device)
  and through ``fit_columns`` (float64 on the host) gives the same summary,
  field for field;
- the pool, as ``jobs/pool_search.py`` holds it, on the kept design: the
  winner's hold-out AuPR against the true logit's, its coefficients against
  ``PlainLogistic`` (``winner_coefficients_within``), one lane of every
  family against its plain reference on the same fold, ``TRAIN_ZERO``
  counters zero, no family on the host path.

``benchmark/controls_typed.py`` holds one run's readings to references made
wrong on purpose.
"""
from __future__ import annotations

import json
import time
from typing import Any, Dict, List, Optional

import numpy as np

from benchmark import harness
from benchmark.jobs.pool_search import LINEAR, _plain, readings
from benchmark.jobs.search import TRAIN_ZERO, _summary
from benchmark.reference.folds_plain import stratified_folds
from benchmark.reference.metrics_plain import aupr
from benchmark.reference.sanity_plain import sanity_check
from benchmark.reference.transmogrify_plain import PlainTransmogrify

HOST_PATH = "host_path_families"
PULL = "prepare_host_pull_bytes"
#: processes the plain references are fitted in after the window: the
#: winner's, one a checked lane and one a plain forest
REFERENCE_WORKERS = 8


def package_lacks() -> List[str]:
    """What the package under the job lacks of what this cell holds it to
    (read from its modules, nothing compiled): empty for a package the cell
    can judge."""
    from transmogrifai_tpu.checkers import sanity_checker
    if not hasattr(sanity_checker, "SCOPES"):
        return ["the sanity checker's contingency tables counted on the "
                "device (checkers/sanity_checker.py SCOPES, fit_device)"]
    return []


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
    lacks = package_lacks()
    if lacks:
        raise harness.BenchFailure("the package lacks " + "; ".join(lacks))
    config = cfg.resolved(ctx.config, ctx.dry_run)
    models = cfg.tiny_pool(config) if ctx.dry_run else None
    rows = ctx.size("rows")
    table, y, _ = cfg.make_table(config, ctx.seed, rows)
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
        workflow, prediction, vector, checked = cfg.workflow(
            config, ctx.seed, models=models)
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
        last.update(model=model, prediction=prediction, vector=vector,
                    checked=checked)
        stages = {} if listener is None else {
            f"{m.stage_name}/{m.phase}": m.seconds
            for m in listener.metrics.stage_metrics}
        return {"stages": stages,
                HOST_PATH: after[HOST_PATH] - before.get(HOST_PATH, 0),
                PULL: after[PULL] - before.get(PULL, 0)}

    train(cfg.dataset(table, y))               # compiles, or loads the cache
    validator.reset_family_profile()
    if ctx.trace:
        package_trace.configure(True)          # host spans, in memory
    window = harness.run_window(ctx, watch, lambda: cfg.dataset(table, y),
                                train, spans)
    families = validator.family_profile()
    harness.say(f"family threads over the window: {families}")
    if ctx.trace:
        recorded = [s for s in package_trace.spans() if s["dur"] is not None]
        spans.extend((s["name"], s["t0"], s["t0"] + s["dur"])
                     for s in recorded)
        harness.say("prepare.encode spans: " + str(
            [dict(s["attrs"], seconds=round(s["dur"], 4)) for s in recorded
             if s["name"] == "prepare.encode"][:8]))
        package_trace.configure(False)
    pulled = [r[PULL] for r in window.reps if r.get("ok")]
    harness.say(f"bytes pulled from the device by prepare, by train: "
                f"{pulled}")

    expected = config["selector"]["models_x_folds"]
    problems = _check(ctx, cfg, config, last, expected)
    design = None
    if not problems:
        built = design_readings(ctx, cfg, config, last)
        harness.say("design readings: " + json.dumps(built))
        problems, design = check_design(cfg, config, built, table, y)
    if not problems:
        problems = check_device_tables(last)
    if not problems:
        got = readings(last["model"], ctx.seed, rows)
        harness.say("readings: " + json.dumps(got))
        problems = check_readings(cfg, config, ctx.size("check_lanes"), got,
                                  design, y, workers=REFERENCE_WORKERS)
    counters = telemetry.counters()
    problems += [f"counter {name} = {counters[name]} (must be 0)"
                 for name in TRAIN_ZERO if counters.get(name, 0)]
    on_host = [r[HOST_PATH] for r in window.reps if r.get("ok")]
    harness.say(f"families on the validator's host path, by train: {on_host}")
    if any(on_host):
        problems.append(f"families took the validator's host path (by train: "
                        f"{on_host}): every family of the default pool is "
                        f"due as a fold-grid device program")
    observed: Dict[str, Any] = {}
    if "model" in last:
        widths, shape = _design_shape(config, last)
        shapes = cfg.lane_shapes(config, rows, widths)
        observed = dict(pool_lane_shapes=shapes,
                        lane_shapes=shapes["GBTClassifier"],
                        sanity_shape=shape)
    return window.outcome({"search_mf_per_s": window.rate(expected)},
                          problems, spans, family_profile=families,
                          matrix_rows=rows, **observed)


def _check(ctx, cfg, config, last, expected) -> List[str]:
    """Every candidate evaluated with a finite metric, and the winner's
    hold-out AuPR beside that of the true logit on the same rows (as
    ``jobs/search.py`` checks it, over the typed hold-out table)."""
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
    hold, y_hold, logit = cfg.make_table(config, ctx.seed,
                                         ctx.size("holdout_rows"), part=1)
    scored = model.score(cfg.dataset(hold, y_hold).drop(["label"]))
    got = aupr(y_hold, np.asarray(
        scored[last["prediction"]].probability)[:, 1])
    best = aupr(y_hold, logit)
    harness.say(f"winner: {summary.best_model_name} "
                f"{summary.best_model_params}  cv {summary.evaluation_metric}="
                f"{summary.best_validation_metric:.4f}  models_x_folds: "
                f"{evaluated}  hold-out AuPR on {len(y_hold)} rows: {got:.4f} "
                f"(the true logit scores {best:.4f})")
    low, high = ref["search_aupr_below_bayes"], ref["search_aupr_above_bayes"]
    if not best - low <= got <= best + high:
        problems.append(f"winner's hold-out AuPR {got:.4f} is outside "
                        f"[{best - low:.4f}, {best + high:.4f}]")
    return problems


def _checker_model(model):
    from transmogrifai_tpu.checkers import SanityCheckerModel
    return next(s for s in model.stages()
                if isinstance(s, SanityCheckerModel))


def _design_shape(config, last):
    """(bins of each of the selector's columns: ``max_bins`` for a value, 2
    for an indicator; the sanity checker's work: sampled rows, columns,
    indicator columns, labels)."""
    checker = _checker_model(last["model"])
    kept = checker.output_metadata.columns
    widths = [2 if c.indicator_value is not None else config["max_bins"]
              for c in kept]
    stats = checker.summary.column_stats
    shape = {"rows": checker.summary.sample_size, "columns": len(stats),
             "indicators": sum(c.indicator_value is not None for c in stats),
             "labels": 2}
    return widths, shape


def design_readings(ctx, cfg, config, last) -> Dict[str, Any]:
    """What the workflow's transmogrify and sanity check built, as plain
    data (one line of the log, so that ``benchmark/controls_typed.py`` can
    hold the same numbers to references made wrong): the design's columns
    (parent, indicator value), the SHA-1 of its hold-out rows (float64,
    scored through the fitted workflow), the columns the checker kept and
    the Cramer's V of every indicator group."""
    import hashlib
    model = last["model"]
    checker = _checker_model(model)
    stats = checker.summary.column_stats
    hold, _, _ = cfg.make_table(config, ctx.seed, ctx.size("holdout_rows"),
                                part=1)
    scored = model.score(cfg.dataset(hold, np.zeros(len(hold["I1"])))
                         .drop(["label"]), keep_intermediate=True)
    held = np.ascontiguousarray(scored[last["vector"]].data, np.float64)
    cramers: Dict[str, Optional[float]] = {}
    for c in stats:
        if c.indicator_value is not None:
            cramers.setdefault(c.parent_feature_name, c.cramers_v
                               if np.isfinite(c.cramers_v) else None)
    return {"seed": ctx.seed, "holdout_rows": ctx.size("holdout_rows"),
            "columns": [[c.parent_feature_name, c.indicator_value]
                        for c in stats],
            "holdout_sha1": hashlib.sha1(held.tobytes()).hexdigest(),
            "kept": list(checker.kept_indices), "cramers_v": cramers}


def check_design(cfg, config, got: Dict[str, Any], table, y,
                 override: Optional[Dict[str, Any]] = None):
    """``got`` (:func:`design_readings`) against ``transmogrify_plain`` and
    ``sanity_plain`` fitted on the training table; returns (problems, the
    kept design of the training rows, float64). ``override`` is for the
    controls: arguments of the plain transmogrify (``transmogrify``) and
    thresholds of the plain checker (``sanity``), ``tables_dtype``."""
    import hashlib
    t0 = time.perf_counter()
    plain, X, pruned = plain_design(config, table, y, override)
    have = [tuple(c) for c in got["columns"]]
    want = plain.columns()
    if have != want:
        diff = next((j for j, (a, b) in enumerate(zip(have, want))
                     if a != b), min(len(have), len(want)))
        harness.say(f"design: {len(have)} columns, transmogrify_plain's "
                    f"{len(want)}")
        return [f"the workflow's design has {len(have)} columns, "
                f"transmogrify_plain's {len(want)}; the first to differ, "
                f"column {diff}: {have[diff:diff + 1]} against "
                f"{want[diff:diff + 1]}"], None
    problems = []
    hold, _, _ = cfg.make_table(config, got["seed"], got["holdout_rows"],
                                part=1)
    held = np.ascontiguousarray(plain.transform(hold), np.float64)
    same_hold = hashlib.sha1(held.tobytes()).hexdigest() \
        == got["holdout_sha1"]
    if not same_hold:
        problems.append("the workflow's design of the hold-out rows is not "
                        "transmogrify_plain's")
    planted = config["planted"]["column"]
    gaps = [abs(got["cramers_v"][g] - v) if got["cramers_v"][g] is not None
            and np.isfinite(v) else (0.0 if got["cramers_v"][g] is None
                                     and not np.isfinite(v)
                                     else float("inf"))
            for g, v in pruned["cramers_v"].items()]
    gap = max(gaps, default=0.0)
    limit = config["reference"]["cramers_v_within"]
    harness.say(f"design: {len(want)} columns as transmogrify_plain's, the "
                f"hold-out rows' {'the same' if same_hold else 'NOT the same'}"
                f"; sanity_plain keeps {len(pruned['kept'])}, the checker "
                f"{len(got['kept'])}; Cramer's V within {gap:.3e} (limit "
                f"{limit}), {planted}'s "
                f"{pruned['cramers_v'].get(planted, float('nan')):.6f} "
                f"({time.perf_counter() - t0:.1f} s)")
    if pruned["kept"] != got["kept"]:
        gone = sorted(set(pruned["kept"]) ^ set(got["kept"]))
        problems.append(f"the checker keeps {len(got['kept'])} columns, "
                        f"sanity_plain {len(pruned['kept'])}; they differ at "
                        f"{[want[j] for j in gone[:6]]}")
    if not gap <= limit:
        problems.append(f"Cramer's V of the indicator groups {gap:.3e} from "
                        f"sanity_plain's, limit {limit}")
    planted_cols = [j for j, (p, _) in enumerate(want) if p == planted]
    if any("cramers_v" not in pruned["reasons"][j] for j in planted_cols) \
            or set(planted_cols) & set(got["kept"]):
        problems.append(f"the planted near-duplicate {planted} is not "
                        f"dropped for its Cramer's V")
    return problems, X[:, pruned["kept"]]


def check_device_tables(last) -> List[str]:
    """The workflow's SanityChecker refitted on its own device matrix, once
    through ``fit_device`` (the tables counted on the device) and once
    through ``fit_columns`` (float64 on the host): the summaries agree field
    for field, at the cell's size."""
    from transmogrifai_tpu.checkers import SanityChecker
    ds = last["model"].train_dataset
    label, vector = ds["label"], ds[last["vector"]]
    checker = SanityChecker()
    on_device = checker.fit_device([label.data, vector.data], [label, vector])
    on_host = checker.fit_columns([label, vector])

    def fields(m):
        return json.dumps([c.to_json() for c in m.summary.column_stats]
                          + [m.kept_indices], sort_keys=True)
    same = fields(on_device) == fields(on_host)
    harness.say(f"the checker's tables counted on the device and on the host "
                f"over {vector.data.shape}: "
                f"{'the same summary' if same else 'DIFFERENT summaries'}")
    return [] if same else ["the checker's device tables give another "
                            "summary than its float64 host tables"]


def _reference(family, point, override, **more):
    """A family's plain reference at a grid point (``pool_search._plain``);
    a control may name another class for a family under ``classes``."""
    cls = ((override or {}).get("classes") or {}).get(family["class"])
    if cls is None:
        return _plain(family, point, override, **more)
    return cls(**dict(point, **more, **override.get(family["class"], {})))


def plain_design(config, table, y, override=None):
    """(``PlainTransmogrify`` fitted on ``table``, its design of ``table``,
    ``sanity_plain``'s result on that design): what the references are
    fitted on."""
    override = override or {}
    plain = PlainTransmogrify(
        config["integral"]["names"], config["picklist"]["names"],
        **dict(config["transmogrify"], **override.get("transmogrify", {}))
    ).fit(table)
    X = plain.transform(table)
    pruned = sanity_check(X, y, plain.columns(),
                          dict(config["sanity"], **override.get("sanity", {})),
                          tables_dtype=override.get("tables_dtype"))
    return plain, X, pruned


def _value(task, D, y, config, override):
    """One plain reference's reading: ``("winner", family, point)`` its
    coefficients and intercept fitted on every row (and its steps);
    ``("lane", family, point, fold, fold seed, forest seed)`` the AuPR of
    the fold's rows, fitted under the fold's training mask (a forest on its
    own ``forest seed``; None for the other families)."""
    what, family, point = task[:3]
    if what == "winner":
        plain = _reference(family, point, override).fit(D, y)
        return np.append(plain.coefficients, plain.intercept), plain.steps
    fold, fold_seed, forest_seed = task[3:]
    fold_of = stratified_folds(y, config["selector"]["num_folds"],
                               fold_seed)
    train, held = (fold_of >= 0) & (fold_of != fold), fold_of == fold
    more = {} if forest_seed is None else {"seed": forest_seed}
    plain = _reference(family, point, override, **more).fit(D, y, mask=train)
    if family["class"] in LINEAR:
        return aupr(y[held], np.asarray(plain.decision(D[held]),
                                        np.float64))
    return aupr(y[held], plain.predict_proba(D[held]))


_WORKER: Dict[str, Any] = {}


def _worker_start(cfg_name: str, config, seed: int, rows: int) -> None:
    """A reference worker's start: the same table and kept design as the
    job's, made again from the seed (cheaper than sending them)."""
    import importlib
    cfg = importlib.import_module(cfg_name)
    table, y, _ = cfg.make_table(config, seed, rows)
    _, X, pruned = plain_design(config, table, y)
    _WORKER.update(D=X[:, pruned["kept"]], y=y, config=config)


def _worker_value(task):
    return _value(task, _WORKER["D"], _WORKER["y"], _WORKER["config"], None)


def reference_values(cfg, config, tasks, D, y, seed: int, override=None,
                     workers: int = 1) -> list:
    """:func:`_value` of every task: one after another on this thread, or,
    with ``workers`` over 1 and no override, in that many spawned processes
    (NumPy only: no worker touches JAX or the chip), each of which makes
    the design again from the seed."""
    if workers <= 1 or override:
        return [_value(t, D, y, config, override) for t in tasks]
    import multiprocessing
    with multiprocessing.get_context("spawn").Pool(
            min(workers, len(tasks)), initializer=_worker_start,
            initargs=(cfg.__name__, config, seed, len(y))) as pool:
        return pool.map(_worker_value, tasks, chunksize=1)


def check_readings(cfg, config, check_lanes, got: Dict[str, Any],
                   D: np.ndarray, y: np.ndarray,
                   override: Optional[Dict[str, Any]] = None,
                   only: Optional[set] = None, workers: int = 1
                   ) -> List[str]:
    """``got`` (``pool_search.readings``) against the plain references on
    the kept design ``D``: the winner's coefficients, then the lanes the
    traffic file samples ([family, grid index, fold, tolerance]; a tolerance
    is a number or [below, above] for system minus reference). A forest
    lane is held to the median of ``forest_reference_seeds`` plain forests,
    each on its own seed: the system's forest draws from one fixed key and
    the reference's from others, so they agree in distribution only, and
    the median cuts the reference's draw-to-draw spread (a forest whose
    pools miss the label's columns moves it least). ``override`` and
    ``only`` (family classes) are for the controls; ``workers`` fits the
    references side by side."""
    seed = got["seed"]
    by_class = {family["class"]: family for family in cfg.families(config)}
    fold_seed = (override or {}).get("fold_seed", seed)
    problems, tasks, lanes = [], [], []
    winner = got["winner"]
    if only is None or winner["family"] in only:
        if winner["family"] not in LINEAR or "coefficients" not in winner:
            problems.append(f"the winner is {winner['family']} "
                            f"{winner['params']}: the label is logistic in "
                            f"seven design columns, so a linear family is "
                            f"due, and only its refit has coefficients")
        else:
            tasks.append(("winner", by_class[winner["family"]],
                          winner["params"]))
    for name, index, fold, tol in check_lanes:
        if only is not None and name not in only:
            continue
        point = cfg.grid(by_class[name])[index]
        lane = got["lanes"].get(name, {}).get(str(index))
        if lane is None or lane["params"] != point:
            problems.append(f"{name} grid point {index} ran as "
                            f"{lane and lane['params']}, not {point}")
            continue
        seeds = ([seed + k for k in range(
            config["reference"]["forest_reference_seeds"])]
            if name == "RandomForestClassifier" else [None])
        lanes.append((name, point, fold, tol, lane, len(seeds)))
        tasks += [("lane", by_class[name], point, fold, fold_seed, s)
                  for s in seeds]
    t0 = time.perf_counter()
    values = reference_values(cfg, config, tasks, D, y, seed, override,
                              workers)
    harness.say(f"{len(tasks)} plain references fitted in "
                f"{time.perf_counter() - t0:.1f} s on {workers} worker(s)")
    if tasks and tasks[0][0] == "winner":
        limit = config["reference"]["winner_coefficients_within"]
        want, steps = values.pop(0)
        have = np.append(winner["coefficients"], winner["intercept"])
        off = (float(np.max(np.abs(have - want)))
               if have.shape == want.shape else float("inf"))
        harness.say(f"winner {winner['family']} {winner['params']}: "
                    f"coefficients within {off:.3e} of the plain "
                    f"reference's (largest {np.max(np.abs(want)):.3f}; "
                    f"{steps} steps), limit {limit}")
        if not off <= limit:
            problems.append(f"the winner's coefficients are {off:.3e} from "
                            f"the plain reference's, limit {limit}")
    for name, point, fold, tol, lane, count in lanes:
        fitted, values = values[:count], values[count:]
        want = float(np.median(fitted))
        below, above = tol if isinstance(tol, list) else (tol, tol)
        have = lane["folds"][fold]
        each = ("" if count == 1 else " (the median of "
                + ", ".join(f"{v:.6f}" for v in fitted) + ")")
        harness.say(f"lane {name} {point} fold {fold}: {got['metric']} "
                    f"{have:.6f}, plain cross-validation {want:.6f}{each}, "
                    f"tolerance -{below} / +{above}")
        if not -below <= have - want <= above:
            problems.append(f"lane {name} {point} fold {fold} scores "
                            f"{have:.6f}, the plain cross-validation "
                            f"{want:.6f}")
    return problems
