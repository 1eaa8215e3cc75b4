"""Statistics kernels used by SanityChecker / ModelInsights.

TPU-native port of the reference ``OpStatistics``
(utils/src/main/scala/com/salesforce/op/utils/stats/OpStatistics.scala:39-346):
Cramér's V, chi-squared, pointwise/plain mutual information, association-rule
max confidence + support, plus weighted column stats and label correlation
computed as XLA matrix ops (the reference used Spark's colStats + a
RowMatrix correlation — on TPU one fused matmul pass does it).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import jax.numpy as jnp
import numpy as np

__all__ = ["ColStats", "col_stats", "column_moments",
           "correlation_with_label", "label_correlation",
           "correlation_matrix", "ContingencyStats", "contingency_stats",
           "chi_square", "cramers_v"]


@dataclass
class ColStats:
    """Per-column moments (reference: Spark MultivariateStatisticalSummary
    usage in SanityChecker.fitFn:535)."""
    count: int
    mean: np.ndarray
    variance: np.ndarray
    min: np.ndarray
    max: np.ndarray
    num_nonzeros: np.ndarray


def col_stats(X, w: Optional[np.ndarray] = None) -> ColStats:
    """Weighted column statistics in one device pass."""
    X = jnp.asarray(X)
    w = jnp.ones((X.shape[0],), X.dtype) if w is None \
        else jnp.asarray(w, X.dtype)
    mean, var, mn, mx = column_moments(X, w)
    live = w > 0
    nnz = jnp.sum((X != 0) & live[:, None], axis=0)
    return ColStats(count=int(jnp.sum(live)), mean=np.asarray(mean),
                    variance=np.asarray(var), min=np.asarray(mn),
                    max=np.asarray(mx), num_nonzeros=np.asarray(nnz))


def column_moments(X, w):
    """(mean, sample variance, min, max) of every column under row weights
    ``w``, as traceable jnp arrays: the body of :func:`col_stats`, for
    callers that fuse it into a program of their own."""
    wsum = jnp.sum(w)
    mean = (w @ X) / wsum
    var = (w @ (X - mean) ** 2) / jnp.maximum(wsum - 1.0, 1.0)
    live = w > 0
    big = jnp.where(live[:, None], X, jnp.inf)
    small = jnp.where(live[:, None], X, -jnp.inf)
    return mean, var, jnp.min(big, axis=0), jnp.max(small, axis=0)


def correlation_matrix(X, w: Optional[np.ndarray] = None) -> np.ndarray:
    """Weighted Pearson correlation matrix via one gram matmul (MXU)."""
    X = jnp.asarray(X, jnp.float64 if X.dtype == np.float64 else jnp.float32)
    n = X.shape[0]
    w = jnp.ones((n,), X.dtype) if w is None else jnp.asarray(w, X.dtype)
    wsum = jnp.sum(w)
    mean = (w @ X) / wsum
    Xc = (X - mean) * jnp.sqrt(w)[:, None]
    # population normalization; the 1/wsum factor cancels in corr = cov/sd²,
    # so this matches col_stats' sample variance convention for correlations
    cov = (Xc.T @ Xc) / wsum
    sd = jnp.sqrt(jnp.diag(cov))
    denom = jnp.outer(sd, sd)
    corr = jnp.where(denom > 0, cov / jnp.where(denom > 0, denom, 1.0),
                     jnp.nan)
    return np.asarray(corr)


def correlation_with_label(X, y, w: Optional[np.ndarray] = None
                           ) -> np.ndarray:
    """Pearson correlation of each feature column with the label
    (the reference appends the label to the matrix and takes the last
    correlation row, SanityChecker.scala:535).

    Computed DIRECTLY per column — O(n·d) — with the same weighted
    population normalization as :func:`correlation_matrix`. The former
    append-and-gram implementation built the full (d+1)² correlation
    matrix to read one row: O(n·d²), the dominant SanityChecker fit
    cost on wide matrices (last-ulp differences vs the gram path are
    possible; only this column of it was ever consumed)."""
    # canonicalize first (as the former gram path did): under x64-off
    # this lands on f32 without requesting — and warning about — f64
    X = jnp.asarray(X)
    y = jnp.asarray(y, X.dtype).reshape(-1)
    w = jnp.ones((X.shape[0],), X.dtype) if w is None \
        else jnp.asarray(w, X.dtype)
    return np.asarray(label_correlation(X, y, w))


def label_correlation(X, y, w):
    """Pearson correlation of every column of ``X`` with ``y`` under row
    weights ``w``, as a traceable jnp array: the body of
    :func:`correlation_with_label`, for callers that fuse it into a
    program of their own."""
    wsum = jnp.sum(w)
    sw = jnp.sqrt(w)
    Xc = (X - (w @ X) / wsum) * sw[:, None]
    yc = (y - jnp.sum(w * y) / wsum) * sw
    cov = (yc @ Xc) / wsum
    sd = jnp.sqrt((jnp.sum(Xc * Xc, axis=0) / wsum)
                  * (jnp.sum(yc * yc) / wsum))
    return jnp.where(sd > 0, cov / jnp.where(sd > 0, sd, 1.0), jnp.nan)


@dataclass
class ContingencyStats:
    """Results of contingency-table analysis for one categorical group
    (reference OpStatistics.contingencyStats:117)."""
    chi2: float
    p_value: float
    cramers_v: float
    mutual_info: float
    pointwise_mutual_info: np.ndarray  # shape (n_levels, n_labels)
    max_rule_confidences: np.ndarray   # per categorical level
    supports: np.ndarray               # per categorical level


def chi_square(table: np.ndarray) -> Tuple[float, float, int]:
    """Pearson chi-squared statistic, p-value, dof for a contingency table."""
    t = np.asarray(table, dtype=np.float64)
    rows = t.sum(axis=1, keepdims=True)
    cols = t.sum(axis=0, keepdims=True)
    total = t.sum()
    if total <= 0:
        return 0.0, 1.0, 0
    keep_r = rows.ravel() > 0
    keep_c = cols.ravel() > 0
    t = t[keep_r][:, keep_c]
    rows, cols = rows[keep_r], cols[:, keep_c]
    expected = rows * cols / total
    with np.errstate(divide="ignore", invalid="ignore"):
        stat = float(np.nansum((t - expected) ** 2 / expected))
    dof = max((t.shape[0] - 1) * (t.shape[1] - 1), 0)
    if dof == 0:
        return stat, 1.0, 0
    from scipy.stats import chi2 as _chi2  # scipy ships with sklearn image
    p = float(_chi2.sf(stat, dof))
    return stat, p, dof


def cramers_v(table: np.ndarray) -> float:
    """Cramér's V (reference OpStatistics.cramersV:300, no bias correction
    beyond min-dimension normalization)."""
    t = np.asarray(table, dtype=np.float64)
    t = t[t.sum(axis=1) > 0][:, t.sum(axis=0) > 0]
    if t.size == 0:
        return float("nan")
    stat, _, _ = chi_square(t)
    n = t.sum()
    k = min(t.shape[0] - 1, t.shape[1] - 1)
    if n <= 0 or k <= 0:
        return float("nan")
    return float(np.sqrt(stat / (n * k)))


def contingency_stats(table: np.ndarray) -> ContingencyStats:
    """All association stats for one (categorical level x label) table
    (reference OpStatistics.contingencyStats:117-133)."""
    t = np.asarray(table, dtype=np.float64)
    total = t.sum()
    stat, p, _ = chi_square(t)
    cv = cramers_v(t)
    # mutual information (natural log base 2, matching reference log2 usage)
    with np.errstate(divide="ignore", invalid="ignore"):
        pxy = t / total if total > 0 else t
        px = pxy.sum(axis=1, keepdims=True)
        py = pxy.sum(axis=0, keepdims=True)
        pmi = np.log2(pxy / (px * py))
        pmi[~np.isfinite(pmi)] = 0.0
        mi = float(np.nansum(np.where(pxy > 0, pxy * pmi, 0.0)))
    row_tot = t.sum(axis=1)
    with np.errstate(divide="ignore", invalid="ignore"):
        conf = np.where(row_tot[:, None] > 0, t / row_tot[:, None], 0.0)
    max_conf = conf.max(axis=1) if t.size else np.zeros(0)
    support = row_tot / total if total > 0 else row_tot
    return ContingencyStats(chi2=stat, p_value=p, cramers_v=cv,
                            mutual_info=mi, pointwise_mutual_info=pmi,
                            max_rule_confidences=max_conf, supports=support)
