"""synth100_gbt as it is run: the seeded table, the estimator and the
selector's workflow. Every size comes from ``synth100_gbt.json``; this file
holds what a JSON file cannot (the generator and the pipeline declaration),
copied here so that the yardstick does not move when ``examples/`` does.
"""
from __future__ import annotations

import copy
import functools
import itertools
from typing import Any, Dict, Tuple

import numpy as np


def resolved(config: Dict[str, Any], dry_run: bool) -> Dict[str, Any]:
    """The configuration, with its ``tiny`` overrides merged in for the CPU
    dry run (one level of nesting is all the file uses)."""
    if not dry_run:
        return config
    out = copy.deepcopy(config)
    for section, override in config["tiny"].items():
        for key, value in override.items():
            if isinstance(value, dict):
                out[section][key].update(value)
            else:
                out[section][key] = value
    return out


@functools.lru_cache(maxsize=None)
def _table_fn(numeric: int, binary: int, density: float, share: tuple):
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, static_argnames=("rows",))
    def table(key, rows: int):
        # an eighth more candidates than rows, then the first `positives`
        # rows of class 1 and the first `rows - positives` of class 0, in
        # their order: every seed gives the same class counts
        candidates = rows + rows // 8
        positives = rows * share[0] // share[1]
        kn, kb, kl = jax.random.split(key, 3)
        x_num = jax.random.normal(kn, (candidates, numeric), jnp.float32)
        x_bin = (jax.random.uniform(kb, (candidates, binary)) < density
                 ).astype(jnp.float32)
        logit = x_num[:, 0] + x_bin[:, :3].sum(axis=1) - 0.5
        noise = 0.5 * jax.random.logistic(kl, (candidates,), jnp.float32)
        y = logit + noise > 0
        keep = jnp.where(y, jnp.cumsum(y) <= positives,
                         jnp.cumsum(~y) <= rows - positives)
        rows_kept = jnp.nonzero(keep, size=rows)[0]
        X = jnp.concatenate([x_num, x_bin], axis=1)[rows_kept]
        return (X, y[rows_kept].astype(jnp.float32), 2.0 * logit[rows_kept],
                jnp.sum(keep))
    return table


def make_table(config: Dict[str, Any], seed: int, rows: int, part: int = 0
               ) -> Tuple[Any, Any, Any]:
    """(X (rows, 100) float32, y (rows,), Bayes logit (rows,)) as device
    arrays, made on the device in one jitted call from the seed. ``part``
    draws an independent table of the same distribution (hold-out rows).
    The class counts are the configuration's ``positive_share`` exactly,
    whatever the seed: the selector's stratified folds, and with them the
    shapes it compiles for, must not depend on the seed."""
    import jax
    c = config["columns"]
    share = tuple(config["positive_share"])
    if rows * share[0] % share[1]:
        raise ValueError(f"{rows} rows do not split {share[0]}/{share[1]}")
    key = jax.random.fold_in(jax.random.PRNGKey(seed), part)
    X, y, bayes, kept = _table_fn(c["numeric"], c["binary"],
                                  c["binary_density"], share)(key, rows=rows)
    if int(kept) != rows:
        raise ValueError(f"the generator found {int(kept)} of {rows} rows: "
                         f"too few candidates of one class")
    return X, y, bayes


def total_bins(config: Dict[str, Any], null_indicators: bool) -> int:
    """Packed histogram bins of the design: ``max_bins`` per numeric column,
    2 per binary column and, behind ``transmogrify()``, 2 per null
    indicator."""
    c = config["columns"]
    bins = c["numeric"] * config["max_bins"] + c["binary"] * 2
    if null_indicators:
        bins += (c["numeric"] + c["binary"]) * 2
    return bins


def lane_shapes(config: Dict[str, Any], rows: int) -> list:
    """``costs.gbt_fit_cost`` arguments of every (grid point, fold) lane of
    the search on ``rows`` rows: the fold's training rows, the design behind
    ``transmogrify()``, the grid point's own depth."""
    sel = config["selector"]
    folds = sel["num_folds"]
    return [{"rows": rows * (folds - 1) // folds,
             "total_bins": total_bins(config, True),
             "depth": point["max_depth"],
             "rounds": sel["family"]["num_rounds"]}
            for point in grid(config) for _ in range(folds)]


def estimator(config: Dict[str, Any]):
    """The fit cell's estimator, through the package's public class."""
    from transmogrifai_tpu import models
    params = dict(config["estimator"])
    return getattr(models, params.pop("class"))(**params)


def grid(config: Dict[str, Any]) -> list:
    g = config["selector"]["grid"]
    return [dict(zip(g, values)) for values in itertools.product(*g.values())]


def dataset(X: np.ndarray, y: np.ndarray):
    """A columnar Dataset of fresh objects over fresh copies: one nullable
    Real column per table column and the label."""
    from transmogrifai_tpu.features.columns import Dataset, FeatureColumn
    from transmogrifai_tpu.types import Real, RealNN
    cols = {f"c{j}": FeatureColumn(Real, np.array(X[:, j], np.float64))
            for j in range(X.shape[1])}
    cols["label"] = FeatureColumn(RealNN, np.array(y, np.float64))
    return Dataset(cols)


def _column(j: int):
    return lambda record: record[f"c{j}"]


def workflow(config: Dict[str, Any], seed: int, columns: int):
    """(Workflow without input, prediction feature name): every column a
    nullable Real predictor, ``transmogrify()``, then the selector's boosted
    grid under stratified cross-validation."""
    from transmogrifai_tpu import models
    from transmogrifai_tpu.features.builder import FeatureBuilder
    from transmogrifai_tpu.ops import transmogrify
    from transmogrifai_tpu.selector import BinaryClassificationModelSelector
    from transmogrifai_tpu.utils.uid import reset as reset_uids
    from transmogrifai_tpu.workflow import Workflow
    reset_uids(deterministic=True)      # the same feature names every time
    sel = config["selector"]
    label = FeatureBuilder.real_nn("label").extract(
        lambda record: record["label"]).as_response()
    predictors = [FeatureBuilder.real(f"c{j}").extract(_column(j))
                  .as_predictor() for j in range(columns)]
    family = dict(sel["family"])
    candidate = getattr(models, family.pop("class"))(**family)
    selector = BinaryClassificationModelSelector.with_cross_validation(
        num_folds=sel["num_folds"], seed=seed, stratify=sel["stratify"],
        models=[(candidate, grid(config))])
    prediction = selector.set_input(label, transmogrify(predictors)
                                    ).get_output()
    return (Workflow().set_result_features(label, prediction),
            prediction.name)
