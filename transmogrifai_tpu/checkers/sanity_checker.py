"""SanityChecker: automated feature validation and pruning.

TPU-native port of the reference SanityChecker
(core/src/main/scala/com/salesforce/op/stages/impl/preparators/
SanityChecker.scala:236, fitFn:535, params :61-206, metadata
SanityCheckerMetadata.scala): a BinaryEstimator over (RealNN label,
OPVector features) that computes per-column statistics, label
correlations and categorical association stats, prunes problematic
columns, and emits the full summary. The heavy math runs as XLA kernels
(utils/stats.py): one fused pass for moments + label correlation, and
per-group contingency tables for Cramér's V / chi² / mutual info /
association-rule confidence. A device matrix (the compiled prepare plan's)
stays on the device: its tables are one contraction of the indicator
columns with the one-hot label there, and only the (columns x labels)
counts come back (docs/prepare.md).

Pruning rules (same thresholds as the reference defaults):
- variance < ``min_variance``                       -> drop column
- |corr(label)| > ``max_correlation``               -> drop (leakage)
- |corr(label)| < ``min_correlation``               -> drop (noise)
- group Cramér's V > ``max_cramers_v``              -> drop whole group
- association rule confidence >= ``max_rule_confidence`` with support
  >= ``min_required_rule_support``                  -> drop whole group

Categorical groups come from the vector metadata's indicator groups —
the one-hot columns of a parent feature form one group and are kept or
removed together (reference group-aware removal).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..features.columns import FeatureColumn
from ..runtime.telemetry import host_pull
from ..stages.base import AllowLabelAsInput, BinaryEstimator, BinaryModel
from ..types import OPVector, RealNN
from ..utils.stats import column_moments, contingency_stats, label_correlation
from ..utils.vector_meta import VectorMetadata

__all__ = ["SanityChecker", "SanityCheckerModel", "SanityCheckerSummary",
           "ColumnStatistics", "SCOPES"]

#: labels with more distinct values than this are treated as continuous and
#: categorical association stats are skipped (reference categoricalLabel
#: heuristic in SanityChecker.fitFn)
MAX_LABEL_CARDINALITY = 100

#: ``jax.named_scope`` names of the statistics programs, as
#: ``models/trees.SCOPES`` names the tree kernels' (docs/observability.md):
#: the moments and label correlation of every column, and the contraction
#: that counts the indicator columns' contingency tables on the device
SCOPES = ("sanity.stats", "sanity.contingency")

#: a float32 count is exact up to 2**24: past it the tables are counted on
#: the host
_EXACT_ROWS = 2 ** 24


@jax.jit
def _column_statistics(X, y):
    """(mean, variance, min, max, label correlation) of every column, one
    program for either placement of ``X``."""
    with jax.named_scope(SCOPES[0]):
        w = jnp.ones((X.shape[0],), X.dtype)
        moments = column_moments(X, w)
        return moments + (label_correlation(X, y.astype(X.dtype), w),)


@jax.jit
def _indicator_tables(X, onehot_label, indicator):
    """Every column's (label) counts, ``X.T @ onehot_label``, and whether
    each ``indicator`` column holds only 0 and 1: for those columns the
    counts are integers, exact in float32 in any summation order."""
    with jax.named_scope(SCOPES[1]):
        tables = jnp.matmul(X.T, onehot_label.astype(X.dtype),
                            precision=jax.lax.Precision.HIGHEST)
        binary = jnp.all((X == 0) | (X == 1) | ~indicator[None, :])
        return tables, binary


@dataclass
class ColumnStatistics:
    """Per-column record in the summary (reference SanityCheckerMetadata)."""
    name: str
    column_index: int
    variance: float
    mean: float
    min: float
    max: float
    corr_label: float
    cramers_v: Optional[float] = None
    max_rule_confidence: Optional[float] = None
    support: Optional[float] = None
    is_dropped: bool = False
    reasons: List[str] = field(default_factory=list)
    #: provenance from the vector metadata (stable across index
    #: renumbering after pruning; used by ModelInsights matching)
    parent_feature_name: Optional[str] = None
    grouping: Optional[str] = None
    indicator_value: Optional[str] = None
    descriptor_value: Optional[str] = None

    def provenance_key(self) -> tuple:
        return (self.parent_feature_name, self.grouping,
                self.indicator_value, self.descriptor_value)

    def to_json(self) -> dict:
        return {"name": self.name, "columnIndex": self.column_index,
                "variance": self.variance, "mean": self.mean,
                "min": self.min, "max": self.max,
                "corrLabel": self.corr_label, "cramersV": self.cramers_v,
                "maxRuleConfidence": self.max_rule_confidence,
                "support": self.support, "isDropped": self.is_dropped,
                "reasons": list(self.reasons),
                "parentFeatureName": self.parent_feature_name,
                "grouping": self.grouping,
                "indicatorValue": self.indicator_value,
                "descriptorValue": self.descriptor_value}


@dataclass
class SanityCheckerSummary:
    """(reference SanityCheckerSummary metadata)"""
    column_stats: List[ColumnStatistics] = field(default_factory=list)
    dropped: List[str] = field(default_factory=list)
    kept_indices: List[int] = field(default_factory=list)
    sample_size: int = 0

    def to_json(self) -> dict:
        return {"columnStats": [c.to_json() for c in self.column_stats],
                "dropped": list(self.dropped),
                "keptIndices": list(self.kept_indices),
                "sampleSize": self.sample_size}


class SanityChecker(AllowLabelAsInput, BinaryEstimator):
    """(reference SanityChecker.scala:236)"""

    input_types = (RealNN, OPVector)
    output_type = OPVector

    def __init__(self, check_sample: float = 1.0, sample_seed: int = 42,
                 sample_limit: int = 100_000, max_correlation: float = 0.95,
                 min_correlation: float = 0.0, min_variance: float = 1e-5,
                 max_cramers_v: float = 0.95,
                 min_required_rule_support: float = 0.001,
                 max_rule_confidence: float = 1.0,
                 remove_bad_features: bool = True,
                 uid: Optional[str] = None):
        super().__init__(operation_name="sanityChecker", uid=uid)
        self.check_sample = check_sample
        self.sample_seed = sample_seed
        self.sample_limit = sample_limit
        self.max_correlation = max_correlation
        self.min_correlation = min_correlation
        self.min_variance = min_variance
        self.max_cramers_v = max_cramers_v
        self.min_required_rule_support = min_required_rule_support
        self.max_rule_confidence = max_rule_confidence
        self.remove_bad_features = remove_bad_features

    def check_input_constraints(self, features) -> None:
        label, vec = features
        if not label.is_response:
            raise ValueError("SanityChecker input 1 must be the response")
        if vec.is_response:
            raise ValueError("SanityChecker input 2 must not be a response")

    # -- fitting -----------------------------------------------------------
    def fit_columns(self, cols: List[FeatureColumn]) -> "SanityCheckerModel":
        y = host_pull(cols[0].data, np.float64)
        X = host_pull(cols[1].data, np.float64)
        meta = cols[1].metadata or VectorMetadata(name="features")
        return self._fit_stats(y, X, meta)

    def fit_device(self, arrays, protos) -> "SanityCheckerModel":
        """Compiled-prepare fit (plans/prepare.py): the feature matrix
        arrives as the device array the fused vectorize→combine program
        produced and stays there: the moments and label correlations are
        the programs ``fit_columns`` runs, and the contingency tables one
        contraction on the device, of which only the (columns x labels)
        counts come back — WITHOUT the host materialization
        ``fit_columns`` pays. Identical fitted state: the tables are
        integer counts (one-hot indicator sums), exact in any order."""
        y = host_pull(arrays[0], np.float64)  # labels are tiny;
        X = arrays[1]                # the group logic walks them host-side
        meta = (protos[1].metadata if protos and protos[1] is not None
                else None) or VectorMetadata(name="features")
        return self._fit_stats(y, X, meta)

    def _fit_stats(self, y: np.ndarray, X, meta: VectorMetadata
                   ) -> "SanityCheckerModel":
        """Shared fit body; ``X`` may be host numpy OR a device (jax)
        array — the statistics run through the same XLA programs and
        produce the same model either way."""
        n, d = X.shape

        # sampling (reference checkSample/sampleLimit, fitFn:535)
        target = min(int(np.ceil(n * self.check_sample)), self.sample_limit)
        if target < n:
            rng = np.random.default_rng(self.sample_seed)
            idx = np.sort(rng.choice(n, target, replace=False))
            Xs, ys = X[idx], y[idx]
            sample_size = int(target)
        else:
            Xs, ys = X, y
            sample_size = int(n)

        mean, variance, mins, maxs, corr = (
            host_pull(v) for v in _column_statistics(jnp.asarray(Xs), ys))

        names = meta.column_names() if meta.size == d else \
            [f"f{i}" for i in range(d)]
        col_recs = []
        for j in range(d):
            rec = ColumnStatistics(
                name=names[j], column_index=j,
                variance=float(variance[j]), mean=float(mean[j]),
                min=float(mins[j]), max=float(maxs[j]),
                corr_label=float(corr[j]))
            if meta.size == d:
                mc = meta.columns[j]
                rec.parent_feature_name = mc.parent_feature_name
                rec.grouping = mc.grouping
                rec.indicator_value = mc.indicator_value
                rec.descriptor_value = mc.descriptor_value
            col_recs.append(rec)

        def drop(j: int, reason: str):
            col_recs[j].is_dropped = True
            col_recs[j].reasons.append(reason)

        # per-column rules
        for j in range(d):
            if col_recs[j].variance < self.min_variance:
                drop(j, f"variance {col_recs[j].variance:.3g} below "
                        f"minVariance {self.min_variance}")
            c = col_recs[j].corr_label
            if np.isfinite(c):
                if abs(c) > self.max_correlation:
                    drop(j, f"label correlation {c:.3f} above "
                            f"maxCorrelation {self.max_correlation}")
                elif abs(c) < self.min_correlation:
                    drop(j, f"label correlation {c:.3f} below "
                            f"minCorrelation {self.min_correlation}")

        # categorical association rules per indicator group
        labels = np.unique(ys)
        if meta.size == d and 2 <= len(labels) <= MAX_LABEL_CARDINALITY:
            onehot_label = ys[:, None] == labels[None, :]
            groups = meta.indicator_groups()
            # every group's table from ONE contraction of the indicator
            # columns with the one-hot label, counted where X lives
            all_idx = sorted({j for idxs in groups.values()
                              for j in idxs})
            local = {j: k for k, j in enumerate(all_idx)}
            tables_all = self._contingency_counts(Xs, onehot_label, all_idx)
            for group_key, indices in groups.items():
                # contingency: level rows x label cols
                table = tables_all[[local[j] for j in indices], :]
                cs = contingency_stats(table)
                for k, j in enumerate(indices):
                    col_recs[j].cramers_v = cs.cramers_v
                    col_recs[j].max_rule_confidence = \
                        float(cs.max_rule_confidences[k]) \
                        if k < len(cs.max_rule_confidences) else None
                    col_recs[j].support = float(cs.supports[k]) \
                        if k < len(cs.supports) else None
                group_bad = []
                if np.isfinite(cs.cramers_v) and \
                        cs.cramers_v > self.max_cramers_v:
                    group_bad.append(
                        f"group Cramér's V {cs.cramers_v:.3f} above "
                        f"maxCramersV {self.max_cramers_v}")
                strong_rule = (
                    (cs.max_rule_confidences >= self.max_rule_confidence)
                    & (cs.supports >= self.min_required_rule_support))
                if strong_rule.any():
                    group_bad.append(
                        "association rule confidence above "
                        f"maxRuleConfidence {self.max_rule_confidence}")
                for reason in group_bad:
                    for j in indices:
                        drop(j, reason)

        kept = [j for j in range(d) if not col_recs[j].is_dropped] \
            if self.remove_bad_features else list(range(d))
        if not kept:
            raise ValueError(
                "SanityChecker dropped every feature column — relax the "
                "thresholds (minVariance/maxCorrelation/maxCramersV)")
        summary = SanityCheckerSummary(
            column_stats=col_recs,
            dropped=[col_recs[j].name for j in range(d)
                     if col_recs[j].is_dropped],
            kept_indices=kept, sample_size=sample_size)
        model = SanityCheckerModel(
            kept_indices=kept,
            output_metadata=(meta.select(kept) if meta.size == d else None))
        model.summary = summary
        return model

    @staticmethod
    def _contingency_counts(Xs, onehot_label: np.ndarray,
                          all_idx: List[int]) -> np.ndarray:
        """(indicator columns x labels) counts, float64. A device ``Xs``
        is counted where it lives and only the counts come back; a host
        one (or a device one with more rows than float32 counts exactly,
        or an indicator column that is not 0/1) as float64 on the host:
        the same integers either way."""
        onehot = onehot_label.astype(np.float64)
        if not all_idx:
            return np.zeros((0, onehot.shape[1]))
        if not isinstance(Xs, np.ndarray) and len(Xs) < _EXACT_ROWS:
            indicator = np.zeros(Xs.shape[1], bool)
            indicator[all_idx] = True
            tables, binary = _indicator_tables(Xs, onehot, indicator)
            if bool(host_pull(binary)):
                return host_pull(tables, np.float64)[all_idx]
        # ALL groups' tables in one matmul: indicator columns are
        # exactly 0/1, so every entry is an integer count — exact in any
        # summation order
        Xind = host_pull(Xs[:, np.asarray(all_idx)], np.float64)
        return Xind.T @ onehot


class SanityCheckerModel(AllowLabelAsInput, BinaryModel):
    """Vector slice by kept indices (reference: the fitted SanityChecker
    model behaves like DropIndicesByTransformer)."""

    input_types = (RealNN, OPVector)
    output_type = OPVector
    summary: Optional[SanityCheckerSummary] = None

    def __init__(self, kept_indices: Sequence[int],
                 output_metadata: Optional[VectorMetadata] = None,
                 uid: Optional[str] = None):
        super().__init__(operation_name="sanityChecker", uid=uid)
        self.kept_indices = [int(i) for i in kept_indices]
        self.output_metadata = output_metadata

    def transform_columns(self, cols: List[FeatureColumn]) -> FeatureColumn:
        vec = cols[-1]
        data = np.asarray(vec.data, dtype=np.float64)[:, self.kept_indices]
        meta = self.output_metadata
        if meta is None:
            src = vec.metadata
            meta = (src.select(self.kept_indices) if src is not None
                    and src.size == np.asarray(vec.data).shape[1] else None)
        if meta is None:
            from ..utils.vector_meta import VectorColumnMetadata
            meta = VectorMetadata(
                name=self.get_output().name if self.input_features else "v",
                columns=tuple(VectorColumnMetadata(
                    parent_feature_name="features",
                    parent_feature_type="OPVector")
                    for _ in self.kept_indices))
        return FeatureColumn.vector(data, meta)

    def transform_value(self, *values):
        vec = values[-1]
        arr = np.asarray(vec.value if hasattr(vec, "value") else vec,
                         dtype=np.float64).reshape(1, -1)
        return OPVector(arr[0, self.kept_indices])

    def transform_arrays(self, arrays):
        # column slice by kept indices; the (ignored) label lane rides
        # along so serve-time NaN labels never touch the output
        import jax.numpy as jnp
        return jnp.take(arrays[-1], jnp.asarray(self.kept_indices,
                                                dtype=jnp.int32), axis=1)
