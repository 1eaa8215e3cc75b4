"""Job kind ``search``: a closed loop of one client running the
configuration's workflow, ``Workflow.train()`` back to back.

Every repetition trains on a fresh ``Dataset`` over fresh copies of the same
seeded table (new object identities, same content and shapes, so
identity-keyed memos miss and nothing recompiles). It ends when ``train()``
returns, which is after the selector has fetched every candidate's metrics and
refitted the winner. With more than one device visible the selector shards
the candidates over them by its own default. After the window the last
train is checked: its winner on hold-out rows against the true P(y=1|x), and
a sample of its (grid point, fold) lanes against a plain cross-validation
(``benchmark/reference/``), outside every timing.
"""
from __future__ import annotations

import time
from typing import Any, Dict, List

import numpy as np

from benchmark import harness
from benchmark.reference.folds_plain import stratified_folds
from benchmark.reference.gbt_plain import PlainGBT
from benchmark.reference.metrics_plain import aupr

#: telemetry counters of the package that a clean train leaves at zero
#: (copied from chip_smoke.py TRAIN_ZERO, PR 23)
TRAIN_ZERO = ("quarantines", "retries", "prepare_plan_fallbacks",
              "prepare_fallbacks", "plan_fallbacks")


def run(ctx: harness.Context, watch: harness.CompileWatch) -> harness.Outcome:
    import jax

    from transmogrifai_tpu.observability import trace as package_trace
    from transmogrifai_tpu.parallel.cv import resolve_search_mesh
    from transmogrifai_tpu.runtime import telemetry
    from transmogrifai_tpu.selector import validator
    from transmogrifai_tpu.utils import WorkflowListener
    cfg = ctx.config_module
    config = cfg.resolved(ctx.config, ctx.dry_run)
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
        workflow, prediction = cfg.workflow(config, ctx.seed, X.shape[1])
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
    mesh = resolve_search_mesh("auto")
    harness.say(f"search mesh: {None if mesh is None else dict(mesh.shape)}")
    validator.reset_family_profile()
    if ctx.trace:
        package_trace.configure(True)          # host spans, in memory
    window = harness.run_window(ctx, watch, lambda: cfg.dataset(X, y), train,
                                spans)
    families = validator.family_profile()
    if ctx.trace:
        spans.extend((s["name"], s["t0"], s["t0"] + s["dur"])
                     for s in package_trace.spans() if s["dur"] is not None)
        package_trace.configure(False)

    expected = config["selector"]["models_x_folds"]
    problems = _check(ctx, cfg, config, last, expected)
    if not problems:
        problems = _check_lanes(ctx, cfg, config, _summary(last["model"]),
                                X, y)
    counters = telemetry.counters()
    problems += [f"counter {name} = {counters[name]} (must be 0)"
                 for name in TRAIN_ZERO if counters.get(name, 0)]
    return window.outcome({"search_mf_per_s": window.rate(expected)},
                          problems, spans, family_profile=families,
                          lane_shapes=cfg.lane_shapes(config, rows))


def _summary(model):
    """The selector's summary on a trained workflow model."""
    from transmogrifai_tpu.selector import SelectedModel
    return next(s.summary for s in model.stages()
                if isinstance(s, SelectedModel) and s.summary is not None)


def _check(ctx, cfg, config, last, expected) -> List[str]:
    """Every candidate evaluated with a finite metric, and the winner's
    hold-out AuPR beside that of the true P(y=1|x) on the same rows."""
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
    hold = cfg.dataset(X_hold, y_hold)
    scored = model.score(hold.drop(["label"]))
    got = aupr(y_hold, np.asarray(
        scored[last["prediction"]].probability)[:, 1])
    best = aupr(y_hold, bayes)
    harness.say(f"winner: {summary.best_model_name} "
                f"{summary.best_model_params}  cv {summary.evaluation_metric}="
                f"{summary.best_validation_metric:.4f}  models_x_folds: "
                f"{evaluated}  hold-out AuPR on {len(y_hold)} rows: {got:.4f} "
                f"(true P(y=1|x) scores {best:.4f})")
    low, high = ref["search_aupr_below_bayes"], ref["search_aupr_above_bayes"]
    if not best - low <= got <= best + high:
        problems.append(f"winner's hold-out AuPR {got:.4f} is outside "
                        f"[{best - low:.4f}, {best + high:.4f}]")
    return problems


def _check_lanes(ctx, cfg, config, summary, X, y) -> List[str]:
    """The fold-grid program against a plain cross-validation, on the lanes
    the traffic file samples (``check_lanes``, [grid index, fold, tolerance]
    each; a plain fit takes seconds to half a minute): the same folds by the
    plain rule,
    ``PlainGBT`` on the whole table under the fold's training mask, and the
    selector's AuPR on the fold's own rows. The table's columns stand for the
    selector's design: the null indicators ``transmogrify()`` adds are
    constant here and cannot be split on."""
    sel = config["selector"]
    family = {k: v for k, v in sel["family"].items() if k != "class"}
    grid = cfg.grid(config)
    results = {r.grid_index: r for r in summary.validation_results}
    fold_of = stratified_folds(y, sel["num_folds"], ctx.seed)
    problems = []
    for index, fold, tol in ctx.size("check_lanes"):
        t0 = time.perf_counter()
        if results[index].params != grid[index]:
            problems.append(f"grid point {index} ran as "
                            f"{results[index].params}, not {grid[index]}")
            continue
        train, held = (fold_of >= 0) & (fold_of != fold), fold_of == fold
        plain = PlainGBT(max_bins=config["max_bins"], **family,
                         **grid[index]).fit(X, y, mask=train)
        want = aupr(y[held], plain.predict_proba(X[held]))
        got = results[index].metric_values[fold]
        harness.say(f"lane {grid[index]} fold {fold}: "
                    f"{summary.evaluation_metric} {got:.6f}, plain "
                    f"cross-validation {want:.6f} (fitted in "
                    f"{time.perf_counter() - t0:.1f} s), tolerance {tol}")
        if not abs(got - want) <= tol:
            problems.append(f"lane {grid[index]} fold {fold} scores "
                            f"{got:.6f}, the plain cross-validation "
                            f"{want:.6f}")
    return problems
