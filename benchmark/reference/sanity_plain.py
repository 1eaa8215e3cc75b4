"""Plain ``sanityCheck``, NumPy float64: the reference the typed pool cell's
pruning is decided against (``criteo_bin_pool.search``).

The package's ``SanityChecker`` (``checkers/sanity_checker.py``) written
straight from its description with nothing of the package:

- the sample: ``ceil(rows * check_sample)`` rows, at most ``sample_limit``;
  where that is fewer than the rows, those of ``numpy.random.default_rng(
  sample_seed).choice(rows, size, replace=False)``, in row order (the
  reference's checkSample / sampleLimit, the package's draw). Everything
  below is over the sample;
- every column: sample variance (``n - 1``) and Pearson correlation with the
  label (population form; NaN for a constant column). A column is dropped
  when its variance is under ``min_variance``, or when a finite correlation
  is over ``max_correlation`` in absolute value or under ``min_correlation``;
- every indicator group (the columns of one parent that carry an indicator
  value: a PickList's block, an Integral's null indicator alone): its
  contingency table, levels x labels, counts of the rows where the level's
  column is 1. Cramer's V = sqrt(chi2 / (n k)) over the table without its
  empty rows and columns, ``k`` = min(levels, labels) - 1, NaN where ``k``
  or ``n`` is 0. A level's rule confidence is its largest label share, its
  support its rows over the table's. The whole group is dropped when a
  finite V is over ``max_cramers_v``, or when a level's confidence is at
  least ``max_rule_confidence`` with support at least
  ``min_required_rule_support``;
- the label is categorical here (two values), so every group is checked.

``tables_dtype`` rounds the contingency tables before anything is computed
from them, for a control (``benchmark/controls_typed.py``): in bfloat16 a
count over 256 loses its last bits.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from benchmark.reference.multinomial_plain import to_bfloat16


def cramers_v(table: np.ndarray) -> float:
    t = table[table.sum(axis=1) > 0][:, table.sum(axis=0) > 0]
    n = t.sum()
    k = min(t.shape) - 1 if t.size else 0
    if n <= 0 or k <= 0:
        return float("nan")
    expected = t.sum(axis=1, keepdims=True) * t.sum(axis=0, keepdims=True) / n
    return float(np.sqrt(np.sum((t - expected) ** 2 / expected) / (n * k)))


def sanity_check(X: np.ndarray, y: np.ndarray,
                 columns: Sequence[Tuple[str, Optional[str]]],
                 params: Dict[str, float],
                 tables_dtype: Optional[str] = None) -> Dict:
    """``{"kept": [column index], "cramers_v": {parent: V}, "reasons":
    {column index: [reason]}}`` of the design ``X`` (float64) against the
    label ``y``; ``columns`` names each column (parent, indicator value or
    None) and ``params`` holds the checker's thresholds by their names."""
    X = np.asarray(X, np.float64)
    y = np.asarray(y, np.float64)
    size = min(int(np.ceil(len(X) * params["check_sample"])),
               params["sample_limit"])
    if size < len(X):
        rows = np.sort(np.random.default_rng(params["sample_seed"]).choice(
            len(X), size, replace=False))
        X, y = X[rows], y[rows]
    n, d = X.shape
    reasons: Dict[int, List[str]] = {j: [] for j in range(d)}
    variance = X.var(axis=0, ddof=1)
    xc, yc = X - X.mean(axis=0), y - y.mean()
    sd = np.sqrt((xc * xc).mean(axis=0) * (yc * yc).mean())
    with np.errstate(invalid="ignore", divide="ignore"):
        corr = np.where(sd > 0, (yc @ xc) / n / np.where(sd > 0, sd, 1.0),
                        np.nan)
    for j in range(d):
        if variance[j] < params["min_variance"]:
            reasons[j].append("variance")
        if np.isfinite(corr[j]):
            if abs(corr[j]) > params["max_correlation"]:
                reasons[j].append("correlation above")
            elif abs(corr[j]) < params["min_correlation"]:
                reasons[j].append("correlation below")
    labels = np.unique(y)
    onehot = (y[:, None] == labels[None, :]).astype(np.float64)
    groups: Dict[str, List[int]] = {}
    for j, (parent, indicator) in enumerate(columns):
        if indicator is not None:
            groups.setdefault(parent, []).append(j)
    by_group: Dict[str, float] = {}
    for parent, idx in groups.items():
        table = X[:, idx].T @ onehot
        if tables_dtype == "bfloat16":
            table = to_bfloat16(table).astype(np.float64)
        elif tables_dtype is not None:
            table = table.astype(tables_dtype).astype(np.float64)
        v = cramers_v(table)
        by_group[parent] = v
        level = table.sum(axis=1)
        total = table.sum()
        with np.errstate(invalid="ignore", divide="ignore"):
            confidence = np.where(level[:, None] > 0,
                                  table / level[:, None], 0.0).max(axis=1)
        support = level / total if total > 0 else level
        bad = []
        if np.isfinite(v) and v > params["max_cramers_v"]:
            bad.append("cramers_v")
        if np.any((confidence >= params["max_rule_confidence"])
                  & (support >= params["min_required_rule_support"])):
            bad.append("rule confidence")
        for j in idx:
            reasons[j] += bad
    return {"kept": [j for j in range(d) if not reasons[j]],
            "cramers_v": by_group, "reasons": reasons}
