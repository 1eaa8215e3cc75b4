"""synth100_pool as it is run: the seeded table of ``synth100_gbt`` (generator,
columnar ``dataset()`` and ``resolved()`` imported from beside this file, not
copied) under the binary selector's DEFAULT pool. The real run hands the
selector no ``models`` argument, so what is searched is whatever
``models/registry.default_binary_models()`` holds; :func:`check_pool` fails the
job when that is no longer what ``synth100_pool.json`` states.
"""
from __future__ import annotations

import itertools
from typing import Any, Dict, List, Optional, Tuple

from benchmark.configs.synth100_gbt import (  # noqa: F401  (the job's API)
    _column, dataset, make_table, resolved, total_bins)
from benchmark.reference.forest_plain import pool_sizes, subset_size


def families(config: Dict[str, Any]) -> List[Dict[str, Any]]:
    return config["selector"]["families"]


def grid(family: Dict[str, Any]) -> List[Dict[str, Any]]:
    """A family's grid points in the order the registry lists them: the
    product of the file's value lists, the first key slowest."""
    g = family["grid"]
    return [dict(zip(g, values)) for values in itertools.product(*g.values())]


def check_pool(config: Dict[str, Any]) -> List[str]:
    """What differs between the package's default binary pool and the
    configuration file: family classes in order, the stated constructor
    parameters, every grid point. Empty when they agree."""
    from transmogrifai_tpu.models import registry
    pool = registry.default_binary_models()
    want = families(config)
    problems = []
    if [type(est).__name__ for est, _ in pool] != [f["class"] for f in want]:
        return [f"the default pool is {[type(e).__name__ for e, _ in pool]}, "
                f"the file states {[f['class'] for f in want]}"]
    for (est, points), family in zip(pool, want):
        for name, value in family["params"].items():
            if getattr(est, name) != value:
                problems.append(f"{family['class']}.{name} is "
                                f"{getattr(est, name)!r}, the file states "
                                f"{value!r}")
        if [dict(p) for p in points] != grid(family):
            problems.append(f"{family['class']}: the default grid is no "
                            f"longer the file's {len(grid(family))} points")
    return problems


def tiny_pool(config: Dict[str, Any]) -> list:
    """The CPU dry run's pool: the package's default estimators with the
    ``tiny`` parameters and grids of the file put on them."""
    from transmogrifai_tpu.models import registry
    by_class = {type(est).__name__: est
                for est, _ in registry.default_binary_models()}
    return [(by_class[f["class"]].with_params(**f["params"]), grid(f))
            for f in families(config)]


def workflow(config: Dict[str, Any], seed: int, columns: int,
             models: Optional[list] = None) -> Tuple[Any, str]:
    """(Workflow without input, prediction feature name): every column a
    nullable Real predictor, ``transmogrify()``, then the binary selector
    under stratified cross-validation. ``models`` stays None in a real run:
    the selector then searches the package's default pool."""
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
    more = {} if models is None else {"models": models}
    selector = BinaryClassificationModelSelector.with_cross_validation(
        num_folds=sel["num_folds"], seed=seed, stratify=sel["stratify"],
        **more)
    prediction = selector.set_input(label, transmogrify(predictors)
                                    ).get_output()
    return (Workflow().set_result_features(label, prediction),
            prediction.name)


def design_widths(config: Dict[str, Any]) -> List[int]:
    """Bins of each of the selector's columns behind ``transmogrify()``:
    ``max_bins`` for a numeric column, 2 for a binary one and 2 for each
    column's null indicator."""
    c = config["columns"]
    return ([config["max_bins"]] * c["numeric"] + [2] * c["binary"]
            + [2] * (c["numeric"] + c["binary"]))


def pooled_bins(config: Dict[str, Any]) -> int:
    """Histogram bins of one forest tree: its feature pool's, by the rule of
    ``benchmark/reference/forest_plain.py`` (each class of columns at its
    widest member's bins)."""
    import numpy as np
    widths = np.asarray(design_widths(config))
    sizes = pool_sizes(widths, subset_size("sqrt", len(widths)))
    if sizes is None:
        return int(widths.sum())
    narrow, wide = widths[widths <= 4], widths[widths > 4]
    return int(sizes[0] * (narrow.max() if sizes[0] else 0)
               + sizes[1] * (wide.max() if sizes[1] else 0))


def lane_shapes(config: Dict[str, Any], rows: int) -> Dict[str, list]:
    """Per family, the arguments of its cost function
    (``benchmark/costs_pool.py``) for every (grid point, fold) lane of the
    search on ``rows`` rows; a lane trains on a fold's training rows."""
    sel = config["selector"]
    folds = sel["num_folds"]
    train_rows = rows * (folds - 1) // folds
    columns = len(design_widths(config))
    out: Dict[str, list] = {}
    for family in families(config):
        params, name = family["params"], family["class"]
        for point in grid(family):
            if name == "GBTClassifier":
                shape = {"rows": train_rows, "depth": point["max_depth"],
                         "total_bins": total_bins(config, True),
                         "rounds": params["num_rounds"]}
            elif name == "RandomForestClassifier":
                shape = {"rows": train_rows, "depth": point["max_depth"],
                         "pooled_bins": pooled_bins(config),
                         "trees": params["num_trees"], "classes": 2}
            else:
                shape = {"rows": train_rows, "columns": columns,
                         "steps": 5 * params["max_iter"]}
            out.setdefault(name, []).extend([shape] * folds)
    return out
