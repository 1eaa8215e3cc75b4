"""criteo_bin_pool as it is run: the seeded Criteo-shaped click log, made on
the host from the seed (its PickList values are strings), and the reference
README's flow over it: 13 ``Integral`` and 26 ``PickList`` predictors,
``transmogrify()``, ``.sanity_check(label)``, then the binary selector with
no ``models`` argument. Every size and share comes from
``criteo_bin_pool.json``; the pool helpers are ``synth100_pool``'s.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from benchmark.configs.synth100_gbt import resolved  # noqa: F401
from benchmark.configs.synth100_pool import (  # noqa: F401  (the job's API)
    check_pool, families, grid, tiny_pool)
from benchmark.reference.forest_plain import pool_sizes, subset_size

MASK32 = 0xFFFFFFFF


def _field(name: str):
    return lambda record: record[name]


@functools.lru_cache(maxsize=None)
def _zipf_norm(cardinality: int, exponent: float) -> float:
    """sum of k^-exponent over k = 1..cardinality, in float64 chunks."""
    total = 0.0
    for start in range(1, cardinality + 1, 1 << 20):
        k = np.arange(start, min(start + (1 << 20), cardinality + 1),
                      dtype=np.float64)
        total += float(np.sum(k ** -exponent))
    return total


def _tail_ranks(rng, count: int, low: int, high: int, exponent: float
                ) -> np.ndarray:
    """``count`` ranks in [low, high] with P(k) ~ k^-exponent, by the
    continuous inverse CDF."""
    u = rng.random(count)
    a, b = low, high + 1.0
    if exponent == 1.0:
        k = np.exp(np.log(a) + u * (np.log(b) - np.log(a)))
    else:
        e = 1.0 - exponent
        k = (a ** e + u * (b ** e - a ** e)) ** (1.0 / e)
    return np.clip(np.floor(k).astype(np.int64), low, high)


def _hex(ranks: np.ndarray, key: int) -> np.ndarray:
    """8-hex-digit strings of ranks: a bijection of 32 bits keyed by
    ``key``, so one column's categories never collide."""
    x = (ranks.astype(np.uint64) * np.uint64(0x9E3779B1)
         + np.uint64(key)) & np.uint64(MASK32)
    x ^= x >> np.uint64(16)
    x = (x * np.uint64(0x85EBCA6B)) & np.uint64(MASK32)
    x ^= x >> np.uint64(13)
    out = np.empty(len(x), dtype=object)
    out[:] = [f"{int(v):08x}" for v in x]
    return out


def _strings(ranks: np.ndarray, key: int) -> np.ndarray:
    """Object array of the ranks' strings (0 = missing -> None), one string
    object a distinct rank."""
    uniq, inverse = np.unique(ranks, return_inverse=True)
    values = _hex(uniq, key)
    if len(uniq) and uniq[0] == 0:
        values[0] = None
    return values[inverse]


def _head_counts(present: int, cardinality: int, exponent: float
                 ) -> np.ndarray:
    """Pinned rows of the ranks 1..K whose expected count is at least 1."""
    norm = _zipf_norm(cardinality, exponent)
    # the head ends where present * k^-s / norm falls under 1
    last = min(cardinality, int((present / norm) ** (1.0 / exponent)) + 1)
    k = np.arange(1, last + 1, dtype=np.float64)
    counts = np.floor(present * k ** -exponent / norm).astype(np.int64)
    return counts[counts > 0]


def _picklist_ranks(rng, rows: int, cardinality: int, missing: float,
                    exponent: float) -> np.ndarray:
    """(rows,) category ranks, 0 where missing: pinned head, drawn tail."""
    n_missing = int(round(missing * rows))
    present = rows - n_missing
    head = _head_counts(present, cardinality, exponent)
    ranks = np.repeat(np.arange(1, len(head) + 1), head)
    left = present - len(ranks)
    if len(head) >= cardinality:        # every rank pinned: one more each
        extra = np.arange(1, left + 1) % cardinality
        ranks = np.concatenate([ranks, np.where(extra, extra, cardinality)])
    elif left:
        ranks = np.concatenate([ranks, _tail_ranks(
            rng, left, len(head) + 1, cardinality, exponent)])
    out = np.zeros(rows, dtype=np.int64)
    out[rng.permutation(rows)[:present]] = rng.permutation(ranks)
    return out


def _integral(rng, rows: int, scale: float, alpha: float, missing: float
              ) -> np.ndarray:
    u = 1.0 - rng.random(rows)                  # (0, 1]
    x = np.floor(scale * (u ** (-1.0 / alpha) - 1.0))
    x[rng.permutation(rows)[:int(round(missing * rows))]] = np.nan
    return x


def _keys(seed: int, columns: int) -> List[int]:
    """One 32-bit string key a PickList column, from the seed alone: the
    training and hold-out parts share their categories."""
    rng = np.random.default_rng([seed, 0xC4])
    return [int(k) for k in rng.integers(0, 1 << 32, size=columns,
                                         dtype=np.uint64)]


def positives(config: Dict[str, Any], rows: int) -> int:
    """Click rows of a table of ``rows``: pinned, so the selector's
    stratified folds, and the shapes it compiles for, are seed-free."""
    num, den = config["label"]["positive_share"]
    return rows * num // den


def make_table(config: Dict[str, Any], seed: int, rows: int, part: int = 0
               ) -> Tuple[Dict[str, np.ndarray], np.ndarray, np.ndarray]:
    """({column name: values}, y (rows,) float64 0/1, the true logit) on the
    host. An Integral column is float64 with NaN for missing, a PickList
    column an object array of 8-hex-digit strings and None. ``part`` draws
    an independent table of the same distribution (hold-out rows) over the
    same categories."""
    ints, picks = config["integral"], config["picklist"]
    rng = np.random.default_rng([seed, part])
    table: Dict[str, np.ndarray] = {}
    for j, name in enumerate(ints["names"]):
        table[name] = _integral(rng, rows, ints["scale"][j],
                                ints["alpha"][j], ints["missing"][j])
    neg = table[ints["negative_column"]]
    chosen = rng.permutation(rows)[:int(round(ints["negative_share"]
                                              * rows))]
    neg[chosen] = -rng.integers(1, 1 - ints["negative_low"], len(chosen))
    keys = _keys(seed, len(picks["names"]))
    planted = config["planted"]["column"]
    ranks: Dict[str, np.ndarray] = {}
    for j, name in enumerate(picks["names"]):
        if name != planted:
            ranks[name] = _picklist_ranks(
                rng, rows, picks["cardinality"][j], picks["missing"][j],
                picks["zipf_exponent"])
    label = config["label"]
    logit = np.full(rows, float(label["intercept"]))
    for term in label["integral_terms"]:
        logit += term["weight"] * np.nan_to_num(table[term["column"]])
    for term in label["picklist_terms"]:
        logit += term["weight"] * (ranks[term["column"]] == term["rank"])
    noisy = logit + rng.logistic(size=rows)
    y = np.zeros(rows)
    y[np.argsort(-noisy, kind="stable")[:positives(config, rows)]] = 1.0
    ranks[planted] = _planted_ranks(config, rng, y)
    for j, name in enumerate(picks["names"]):
        table[name] = _strings(ranks[name], keys[j])
    return table, y, logit


def _planted_ranks(config: Dict[str, Any], rng, y: np.ndarray) -> np.ndarray:
    """The near-duplicate of the label (``planted`` in the file)."""
    spec, picks = config["planted"], config["picklist"]
    cardinality = picks["cardinality"][picks["names"].index(spec["column"])]
    rows = len(y)
    pos, neg = spec["levels_of_positive"], spec["levels_of_negative"]
    side = y.astype(bool)
    order = rng.permutation(rows)
    flips = int(round(spec["flip_share"] * rows))
    rare = int(round(spec["rare_share"] * rows))
    side[order[:flips]] = ~side[order[:flips]]
    ranks = np.where(side, 1 + rng.integers(0, pos, rows),
                     1 + pos + rng.integers(0, neg, rows))
    ranks[order[flips:flips + rare]] = rng.integers(
        pos + neg + 1, cardinality + 1, rare)
    return ranks


def dataset(table: Dict[str, np.ndarray], y: np.ndarray):
    """A columnar Dataset of fresh objects over fresh copies: one Integral
    column per I, one PickList column per C (the strings themselves are
    shared), and the label."""
    from transmogrifai_tpu.features.columns import Dataset, FeatureColumn
    from transmogrifai_tpu.types import Integral, PickList, RealNN
    cols = {name: FeatureColumn(PickList if values.dtype == object
                                else Integral, values.copy())
            for name, values in table.items()}
    cols["label"] = FeatureColumn(RealNN, np.array(y, np.float64))
    return Dataset(cols)


def workflow(config: Dict[str, Any], seed: int,
             models: Optional[list] = None) -> Tuple[Any, str, str, str]:
    """(Workflow without input, prediction feature name, transmogrified
    vector name, sanity-checked vector name): every I an Integral predictor,
    every C a PickList predictor, ``transmogrify()``, ``.sanity_check(label)``
    with its defaults, then the binary selector under stratified
    cross-validation. ``models`` stays None in a real run: the selector
    then searches the package's default pool."""
    from transmogrifai_tpu.features.builder import FeatureBuilder
    from transmogrifai_tpu.ops import transmogrify
    from transmogrifai_tpu.selector import BinaryClassificationModelSelector
    from transmogrifai_tpu.utils.uid import reset as reset_uids
    from transmogrifai_tpu.workflow import Workflow
    reset_uids(deterministic=True)      # the same feature names every time
    sel = config["selector"]
    label = FeatureBuilder.real_nn("label").extract(
        _field("label")).as_response()
    predictors = (
        [FeatureBuilder.integral(name).extract(_field(name)).as_predictor()
         for name in config["integral"]["names"]]
        + [FeatureBuilder.pick_list(name).extract(_field(name))
           .as_predictor() for name in config["picklist"]["names"]])
    vector = transmogrify(predictors)
    checked = vector.sanity_check(label)
    more = {} if models is None else {"models": models}
    selector = BinaryClassificationModelSelector.with_cross_validation(
        num_folds=sel["num_folds"], seed=seed, stratify=sel["stratify"],
        **more)
    prediction = selector.set_input(label, checked).get_output()
    return (Workflow().set_result_features(label, prediction),
            prediction.name, vector.name, checked.name)


def lane_shapes(config: Dict[str, Any], rows: int, widths: List[int]
                ) -> Dict[str, list]:
    """Per family, the arguments of its cost function
    (``benchmark/costs_pool.py``) for every (grid point, fold) lane of the
    search on ``rows`` rows over the selector's columns of ``widths`` bins
    (``max_bins`` for an integer value, 2 for an indicator)."""
    sel = config["selector"]
    folds = sel["num_folds"]
    train_rows = rows * (folds - 1) // folds
    widths = np.asarray(widths)
    sizes = pool_sizes(widths, subset_size("sqrt", len(widths)))
    if sizes is None:
        pooled = int(widths.sum())
    else:
        narrow, wide = widths[widths <= 4], widths[widths > 4]
        pooled = int(sizes[0] * (narrow.max() if sizes[0] else 0)
                     + sizes[1] * (wide.max() if sizes[1] else 0))
    out: Dict[str, list] = {}
    for family in families(config):
        params, name = family["params"], family["class"]
        for point in grid(family):
            if name == "GBTClassifier":
                shape = {"rows": train_rows, "depth": point["max_depth"],
                         "total_bins": int(widths.sum()),
                         "rounds": params["num_rounds"]}
            elif name == "RandomForestClassifier":
                shape = {"rows": train_rows, "depth": point["max_depth"],
                         "pooled_bins": pooled,
                         "trees": params["num_trees"], "classes": 2}
            else:
                shape = {"rows": train_rows, "columns": len(widths),
                         "steps": 5 * params["max_iter"]}
            out.setdefault(name, []).extend([shape] * folds)
    return out
