"""Job kind ``fit``: a closed loop of one client fitting the configuration's
estimator on the whole device-resident table, back to back.

A repetition is what a user's ``fit_arrays`` call costs when the matrix is
already on the device: the memoized binned design is dropped first
(``clear_design_cache``), so every repetition bins and then fits, and it ends
with the fitted model on the host. After the window the fit is checked against
the plain reference (``benchmark/reference/gbt_plain.py``) on a seeded
subsample, outside every timing.
"""
from __future__ import annotations

import time
from typing import Any, Dict, List

import numpy as np

from benchmark import harness
from benchmark.reference.gbt_plain import PlainGBT
from benchmark.reference.metrics_plain import log_loss


def run(ctx: harness.Context, watch: harness.CompileWatch) -> harness.Outcome:
    import jax

    from transmogrifai_tpu.models.trees import clear_design_cache
    cfg = ctx.config_module
    config = cfg.resolved(ctx.config, ctx.dry_run)
    rows = ctx.size("rows")
    X, y_device, _ = cfg.make_table(config, ctx.seed, rows)
    y = np.asarray(y_device)
    jax.block_until_ready(X)
    estimator = cfg.estimator(config)
    last: Dict[str, Any] = {}

    def fit(_inputs=None) -> Dict[str, Any]:
        clear_design_cache()
        last["model"] = estimator.fit_arrays(X, y)
        return {}

    fit()                                      # compiles, or loads the cache
    spans: List[tuple] = []
    window = harness.run_window(ctx, watch, lambda: None, fit, spans)

    est = config["estimator"]
    return window.outcome(
        {"fit_rows_per_s": window.rate(rows)},
        _check(ctx, cfg, config, estimator, X, y, last["model"]), spans,
        fit_shape={"rows": rows, "total_bins": cfg.total_bins(config, False),
                   "depth": est["max_depth"], "rounds": est["num_rounds"]})


def _check(ctx, cfg, config, estimator, X, y, model) -> List[str]:
    """The fit against the plain reference, on ``check_rows`` seeded rows and
    as many hold-out rows of the same distribution."""
    from transmogrifai_tpu.models.trees import clear_design_cache
    ref = config["reference"]
    n, tol = ref["check_rows"], ref["fit_logloss_tolerance"]
    params = {k: v for k, v in config["estimator"].items()
              if k not in ("class", "subsample")}
    if config["estimator"]["subsample"] != 1.0:
        return ["the plain reference has no row subsampling"]
    X_hold, y_hold, _ = (np.asarray(a) for a in
                         cfg.make_table(config, ctx.seed, n, part=1))
    clear_design_cache()
    X_sub = X[:n]
    system = estimator.fit_arrays(X_sub, y[:n])

    def loss(m) -> float:
        return log_loss(y_hold, m.raw_to_probability(
            m.predict_raw(X_hold))[:, 1])

    t0 = time.perf_counter()
    plain = log_loss(y_hold, PlainGBT(**params).fit(
        np.asarray(X_sub), y[:n]).predict_proba(X_hold))
    got, full = loss(system), loss(model)
    harness.say(f"hold-out log-loss on {n} rows: plain reference "
                f"{plain:.6f} (fitted in {time.perf_counter() - t0:.1f} s)  "
                f"system on the same {n} rows {got:.6f}  system on all "
                f"{X.shape[0]} rows {full:.6f}  tolerance {tol}")
    problems = []
    if not abs(got - plain) <= tol:
        problems.append(f"fit differs from the plain reference: hold-out "
                        f"log-loss {got:.6f} vs {plain:.6f}")
    if not (np.isfinite(full) and full <= got + tol):
        problems.append(f"the whole-table fit scores {full:.6f} on the "
                        f"hold-out, worse than the {n}-row fit's {got:.6f}")
    return problems
