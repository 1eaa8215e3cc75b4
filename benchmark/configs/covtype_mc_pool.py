"""covtype_mc_pool as it is run: a seeded stand-in for the UCI Covertype table
(54 columns, 7 classes at Covertype's shares, class counts pinned) under the
multiclass selector's DEFAULT pool. The real run hands the selector no
``models`` argument, so what is searched is whatever
``models/registry.default_multiclass_models()`` holds; :func:`check_pool`
fails the job when that is no longer what ``covtype_mc_pool.json`` states.
Every size, share and coefficient comes from the JSON file; this file holds
what a JSON file cannot (the generator and the pipeline declaration).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from benchmark.configs.synth100_gbt import (  # noqa: F401  (the job's API)
    _column, dataset, resolved)
from benchmark.configs.synth100_pool import families, grid  # noqa: F401
from benchmark.reference.forest_plain import pool_sizes, subset_size


def _registry_pool():
    from transmogrifai_tpu.models import registry
    return registry.default_multiclass_models()


def check_pool(config: Dict[str, Any]) -> List[str]:
    """What differs between the package's default multiclass pool and the
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


def class_counts(config: Dict[str, Any], rows: int) -> List[int]:
    """Rows of each class in a table of ``rows`` rows, whatever the seed (the
    K-class form of ``positive_share``): a third of the rows shared out by
    ``class_shares`` (largest remainders), times three, so that three
    stratified folds drop no row; what ``rows % 3`` leaves goes to the
    largest class."""
    shares = np.asarray(config["class_shares"], np.float64)
    exact = shares / shares.sum() * (rows // 3)
    counts = np.floor(exact).astype(np.int64)
    short = rows // 3 - int(counts.sum())
    counts[np.argsort(-(exact - counts), kind="stable")[:short]] += 1
    counts *= 3
    counts[int(np.argmax(counts))] += rows % 3
    return [int(c) for c in counts]


@functools.lru_cache(maxsize=None)
def _table_fn(spec: str):
    """The jitted generator of one configuration (``spec`` is its ``columns``
    and ``label`` sections as JSON, so the cache has a hashable key)."""
    import json

    import jax
    import jax.numpy as jnp
    columns, label = json.loads(spec)
    quantitative = columns["quantitative"]
    groups = columns["one_hot_groups"]
    terms = label["terms"]
    weights = jnp.asarray([t["weights"] for t in terms], jnp.float32)
    intercepts = jnp.asarray(label["intercepts"], jnp.float32)

    @functools.partial(jax.jit, static_argnames=("rows", "counts"))
    def table(key, rows: int, counts: tuple):
        candidates = rows + rows // 2 + 8192
        kq, kg, ky = jax.random.split(key, 3)
        cols = []
        for j, (q, k) in enumerate(zip(
                quantitative, jax.random.split(kq, len(quantitative)))):
            if q["draw"] == "uniform":
                x = jax.random.uniform(k, (candidates,), jnp.float32)
            else:
                x = jax.random.normal(k, (candidates,), jnp.float32)
                if q["draw"] == "half_normal":
                    x = jnp.abs(x)
            cols.append(jnp.clip(jnp.round(q["at"] + q["scale"] * x),
                                 q["low"], q["high"]))
        for group, k in zip(groups, jax.random.split(kg, len(groups))):
            levels = group["levels"]
            p = jnp.arange(1, levels + 1, dtype=jnp.float32) ** -group["skew"] \
                if "skew" in group else jnp.asarray(group["shares"])
            level = jax.random.categorical(k, jnp.log(p / p.sum()),
                                           shape=(candidates,))
            cols.extend(jax.nn.one_hot(level, levels, dtype=jnp.float32).T)
        X = jnp.stack(cols, axis=1)
        z = jnp.stack([(X[:, t["column"]] - t["center"]) / t["scale"]
                       for t in terms], axis=1)
        # (candidates, classes), exactly float32 on the chip too: these are
        # the logits the labels are drawn from AND the true model's
        logits = intercepts + jnp.matmul(
            z, weights, precision=jax.lax.Precision.HIGHEST)
        y = jnp.argmax(logits + jax.random.gumbel(
            ky, logits.shape, jnp.float32), axis=1)
        # in their order, the first counts[c] candidates of every class c:
        # every seed gives the same class counts, and x given y keeps its law
        mine = jax.nn.one_hot(y, len(counts), dtype=jnp.int32)
        keep = jnp.take_along_axis(jnp.cumsum(mine, axis=0), y[:, None],
                                   axis=1)[:, 0] <= jnp.asarray(counts)[y]
        rows_kept = jnp.nonzero(keep, size=rows)[0]
        # pinning the counts moves the class shares from the sampler's own
        # (the mean of its probabilities) to counts / rows: the posterior of
        # the table as made carries that ratio
        natural = jnp.mean(jax.nn.softmax(logits, axis=1), axis=0)
        bayes = logits + jnp.log(jnp.asarray(counts, jnp.float32) / rows
                                 / natural)
        return (X[rows_kept], y[rows_kept].astype(jnp.float32),
                bayes[rows_kept], jnp.sum(keep))
    return table


def make_table(config: Dict[str, Any], seed: int, rows: int, part: int = 0
               ) -> Tuple[Any, Any, Any]:
    """(X (rows, 54) float32, y (rows,) in 0..6, the logits of the true
    P(y | x) (rows, 7)) as device arrays, made on the device in one jitted
    call from the seed. ``part`` draws an independent table of the same
    distribution (hold-out rows). The class counts are
    :func:`class_counts` exactly, whatever the seed: the selector's
    stratified folds, and with them the shapes it compiles for, must not
    depend on the seed."""
    import json

    import jax
    counts = tuple(class_counts(config, rows))
    key = jax.random.fold_in(jax.random.PRNGKey(seed), part)
    X, y, bayes, kept = _table_fn(json.dumps(
        [config["columns"], config["label"]], sort_keys=True))(
            key, rows=rows, counts=counts)
    if int(kept) != rows:
        raise ValueError(f"the generator found {int(kept)} of {rows} rows: "
                         f"too few candidates of one class")
    return X, y, bayes


def workflow(config: Dict[str, Any], seed: int, columns: int,
             models: Optional[list] = None) -> Tuple[Any, str]:
    """(Workflow without input, prediction feature name): every column a
    nullable Real predictor, ``transmogrify()``, then the multiclass selector
    under stratified cross-validation. ``models`` stays None in a real run:
    the selector then searches the package's default pool."""
    from transmogrifai_tpu.features.builder import FeatureBuilder
    from transmogrifai_tpu.ops import transmogrify
    from transmogrifai_tpu.selector import MultiClassificationModelSelector
    from transmogrifai_tpu.utils.uid import reset as reset_uids
    from transmogrifai_tpu.workflow import Workflow
    reset_uids(deterministic=True)      # the same feature names every time
    sel = config["selector"]
    label = FeatureBuilder.real_nn("label").extract(
        lambda record: record["label"]).as_response()
    predictors = [FeatureBuilder.real(f"c{j}").extract(_column(j))
                  .as_predictor() for j in range(columns)]
    more = {} if models is None else {"models": models}
    selector = MultiClassificationModelSelector.with_cross_validation(
        num_folds=sel["num_folds"], seed=seed, stratify=sel["stratify"],
        **more)
    prediction = selector.set_input(label, transmogrify(predictors)
                                    ).get_output()
    return (Workflow().set_result_features(label, prediction),
            prediction.name)


def design_widths(config: Dict[str, Any]) -> List[int]:
    """Bins of each of the selector's columns behind ``transmogrify()``:
    ``max_bins`` for a quantitative column, 2 for an indicator and 2 for each
    column's null indicator."""
    c = config["columns"]
    indicators = sum(g["levels"] for g in c["one_hot_groups"])
    quantitative = len(c["quantitative"])
    return ([config["max_bins"]] * quantitative + [2] * indicators
            + [2] * (quantitative + indicators))


def pooled_bins(config: Dict[str, Any]) -> int:
    """Histogram bins of one forest tree: its feature pool's, by the rule of
    ``benchmark/reference/forest_plain.py`` (each class of columns at its
    widest member's bins)."""
    widths = np.asarray(design_widths(config))
    sizes = pool_sizes(widths, subset_size("sqrt", len(widths)))
    if sizes is None:
        return int(widths.sum())
    narrow, wide = widths[widths <= 4], widths[widths > 4]
    return int(sizes[0] * (narrow.max() if sizes[0] else 0)
               + sizes[1] * (wide.max() if sizes[1] else 0))


def lane_shapes(config: Dict[str, Any], rows: int) -> Dict[str, list]:
    """Per family, the arguments of its cost function for every (grid point,
    fold) lane of the search on ``rows`` rows, the class count among them
    (``benchmark/costs_mc.py``; the forest's are ``costs_pool.forest_fit_cost``
    's own arguments, so that reader takes them as they are). A lane trains
    on a fold's training rows; the single tree sees every column."""
    sel = config["selector"]
    folds, classes = sel["num_folds"], len(config["class_shares"])
    train_rows = rows * (folds - 1) // folds
    widths = design_widths(config)
    out: Dict[str, list] = {}
    for family in families(config):
        params, name = family["params"], family["class"]
        for point in grid(family):
            if name == "RandomForestClassifier":
                shape = {"rows": train_rows, "depth": point["max_depth"],
                         "pooled_bins": pooled_bins(config),
                         "trees": params["num_trees"], "classes": classes}
            elif name == "DecisionTreeClassifier":
                shape = {"rows": train_rows, "depth": point["max_depth"],
                         "pooled_bins": int(sum(widths)), "trees": 1,
                         "classes": classes}
            elif name == "LogisticRegression":
                shape = {"rows": train_rows, "columns": len(widths),
                         "classes": classes, "steps": 5 * params["max_iter"]}
            else:
                shape = {"rows": train_rows, "columns": len(widths),
                         "classes": classes}
            out.setdefault(name, []).extend([shape] * folds)
    return out
