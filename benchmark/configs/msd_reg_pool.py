"""msd_reg_pool as it is run: a seeded stand-in for the UCI YearPredictionMSD
table (90 real-valued columns, the label a release year 1922-2011) under the
regression selector's DEFAULT pool. The real run hands the selector no
``models`` argument, so what is searched is whatever
``models/registry.default_regression_models()`` holds; :func:`check_pool`
fails the job when that is no longer what ``msd_reg_pool.json`` states.
Every size, scale and weight comes from the JSON file; this file holds what a
JSON file cannot (the generator and the pipeline declaration). A regression
table pins nothing but its row count: the selector's folds are not stratified
here, so their sizes follow the rows alone.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

from benchmark.configs.synth100_gbt import (  # noqa: F401  (the job's API)
    _column, dataset, resolved)
from benchmark.configs.synth100_pool import families, grid  # noqa: F401


def _registry_pool():
    from transmogrifai_tpu.models import registry
    return registry.default_regression_models()


def check_pool(config: Dict[str, Any]) -> List[str]:
    """What differs between the package's default regression pool and the
    configuration file: family classes in order, the stated constructor
    parameters, every grid point. Empty when they agree."""
    pool, want = _registry_pool(), families(config)
    if [type(est).__name__ for est, _ in pool] != [f["class"] for f in want]:
        return [f"the default pool is {[type(e).__name__ for e, _ in pool]}, "
                f"the file states {[f['class'] for f in want]}"]
    problems = []
    for (est, points), family in zip(pool, want):
        problems += [f"{family['class']}.{name} is {getattr(est, name)!r}, "
                     f"the file states {value!r}"
                     for name, value in family["params"].items()
                     if getattr(est, name) != value]
        if [dict(p) for p in points] != grid(family):
            problems.append(f"{family['class']}: the default grid is no "
                            f"longer the file's {len(grid(family))} points")
    return problems


def tiny_pool(config: Dict[str, Any]) -> list:
    """The CPU dry run's pool: the package's default estimators with the
    ``tiny`` parameters and grids of the file put on them."""
    by_class = {type(est).__name__: est for est, _ in _registry_pool()}
    return [(by_class[f["class"]].with_params(**f["params"]), grid(f))
            for f in families(config)]


@functools.lru_cache(maxsize=None)
def _table_fn(spec: str):
    """The jitted generator of one configuration (``spec`` is its ``columns``
    and ``label`` sections as JSON, so the cache has a hashable key)."""
    import json

    import jax
    import jax.numpy as jnp
    columns, label = json.loads(spec)
    at = jnp.asarray(columns["at"], jnp.float32)
    scale = jnp.asarray(columns["scale"], jnp.float32)
    draws = columns["draw"]
    terms = label["terms"]
    picked = jnp.asarray([t["column"] for t in terms])
    center = jnp.asarray([t["center"] for t in terms], jnp.float32)
    unit = jnp.asarray([t["scale"] for t in terms], jnp.float32)
    weights = jnp.asarray([t["weight"] for t in terms], jnp.float32)

    @functools.partial(jax.jit, static_argnames=("rows",))
    def table(key, rows: int):
        kz, kl, kn = jax.random.split(key, 3)
        z = jax.random.normal(kz, (rows, len(draws)), jnp.float32)
        heavy = jax.random.laplace(kl, (rows, len(draws)), jnp.float32)
        by_draw = {"normal": z, "laplace": heavy / jnp.sqrt(2.0),
                   "lognormal": jnp.exp(columns["lognormal_sigma"] * z)}
        X = at + scale * jnp.stack(
            [by_draw[name][:, j] for j, name in enumerate(draws)], axis=1)
        # float32 in full on the chip too: m(x) is the true model the
        # winner's hold-out error is held to, and the labels come from it
        m = label["intercept"] + jnp.matmul(
            (X[:, picked] - center) / unit, weights,
            precision=jax.lax.Precision.HIGHEST)
        noise = label["noise_deviation"] * jax.random.normal(
            kn, (rows,), jnp.float32)
        y = jnp.clip(jnp.round(m + noise), label["low"], label["high"])
        return X, y, m
    return table


def make_table(config: Dict[str, Any], seed: int, rows: int, part: int = 0
               ) -> Tuple[Any, Any, Any]:
    """(X (rows, 90) float32, y (rows,) whole years in [1922, 2011], the true
    model m(x) (rows,)) as device arrays, made on the device in one jitted
    call from the seed. ``part`` draws an independent table of the same
    distribution (hold-out rows). Nothing is pinned but the row count: the
    selector's folds are not stratified, so the shapes it compiles for follow
    the rows and not the seed."""
    import json

    import jax
    key = jax.random.fold_in(jax.random.PRNGKey(seed), part)
    return _table_fn(json.dumps([config["columns"], config["label"]],
                                sort_keys=True))(key, rows=rows)


def workflow(config: Dict[str, Any], seed: int, columns: int,
             models: Optional[list] = None) -> Tuple[Any, str]:
    """(Workflow without input, prediction feature name): every column a
    nullable Real predictor, ``transmogrify()``, then the regression selector
    under plain (not stratified) cross-validation with its default evaluator,
    RMSE. ``models`` stays None in a real run: the selector then searches the
    package's default pool."""
    from transmogrifai_tpu.features.builder import FeatureBuilder
    from transmogrifai_tpu.ops import transmogrify
    from transmogrifai_tpu.selector import RegressionModelSelector
    from transmogrifai_tpu.utils.uid import reset as reset_uids
    from transmogrifai_tpu.workflow import Workflow
    reset_uids(deterministic=True)      # the same feature names every time
    sel = config["selector"]
    label = FeatureBuilder.real_nn("label").extract(
        lambda record: record["label"]).as_response()
    predictors = [FeatureBuilder.real(f"c{j}").extract(_column(j))
                  .as_predictor() for j in range(columns)]
    more = {} if models is None else {"models": models}
    selector = RegressionModelSelector.with_cross_validation(
        num_folds=sel["num_folds"], seed=seed, stratify=sel["stratify"],
        **more)
    prediction = selector.set_input(label, transmogrify(predictors)
                                    ).get_output()
    return (Workflow().set_result_features(label, prediction),
            prediction.name)


def design_widths(config: Dict[str, Any]) -> List[int]:
    """Bins of each of the selector's columns behind ``transmogrify()``:
    ``max_bins`` for a table column (all real-valued) and 2 for each column's
    null indicator."""
    columns = len(config["columns"]["draw"])
    return [config["max_bins"]] * columns + [2] * columns


def lane_shapes(config: Dict[str, Any], rows: int) -> Dict[str, list]:
    """Per family, the arguments of its cost function for every (grid point,
    fold) lane of the search on ``rows`` rows (``benchmark/costs.py``,
    ``costs_pool.py``, ``costs_reg.py``). A lane trains on a fold's training
    rows. The regression forest's ``auto`` subset is a third of the columns,
    whose pool of four times that is every column: a forest tree's histogram
    spans the whole design's bins, as a boosted tree's does."""
    sel = config["selector"]
    folds = sel["num_folds"]
    train_rows = rows * (folds - 1) // folds
    widths = design_widths(config)
    out: Dict[str, list] = {}
    for family in families(config):
        params, name = family["params"], family["class"]
        for point in grid(family):
            if name == "GBTRegressor":
                shape = {"rows": train_rows, "depth": point["max_depth"],
                         "total_bins": int(sum(widths)),
                         "rounds": params["num_rounds"]}
            elif name == "RandomForestRegressor":
                shape = {"rows": train_rows, "depth": point["max_depth"],
                         "pooled_bins": int(sum(widths)),
                         "trees": params["num_trees"], "classes": 3}
            elif name == "LinearRegression":
                shape = {"rows": train_rows, "columns": len(widths),
                         "steps": 5 * params["max_iter"]}
            else:
                shape = {"rows": train_rows, "columns": len(widths),
                         "family": point["family"],
                         "max_iter": params["max_iter"]}
            out.setdefault(name, []).extend([shape] * folds)
    return out
