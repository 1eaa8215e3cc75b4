"""Numeric bucketizers, percentile calibration, and scaling.

TPU-native ports of the reference numeric transforms
(core/src/main/scala/com/salesforce/op/stages/impl/feature/
{NumericBucketizer.scala, DecisionTreeNumericBucketizer.scala,
PercentileCalibrator.scala, ScalerTransformer.scala}):

- :class:`NumericBucketizer` — fixed split points -> one-hot bucket
  membership (+ optional null/invalid tracking).
- :class:`DecisionTreeNumericBucketizer` — label-aware buckets from the
  split thresholds of a single-feature decision tree (the reference
  fits a Spark DecisionTree; here it's the histogram tree builder of
  models/trees/grower.py, so the whole fit is one XLA program).
- :class:`PercentileCalibrator` — maps values onto [0, buckets-1] by
  training-set quantiles (reference PercentileCalibrator with
  ``expectedDistribution`` uniform).
- :class:`ScalerTransformer` / :class:`DescalerTransformer` — invertible
  linear/log scaling; the descaler looks up the scaler's params through
  its input feature's origin stage (reference ScalerMetadata dance).
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..features.columns import FeatureColumn
from ..stages.base import (AllowLabelAsInput, BinaryEstimator, BinaryModel,
                           BinaryTransformer, UnaryEstimator, UnaryModel,
                           UnaryTransformer)
from ..types import OPNumeric, OPVector, Real, RealNN
from .vector_utils import NULL_INDICATOR, VectorColumnMetadata, vector_output

__all__ = ["NumericBucketizer", "DecisionTreeNumericBucketizer",
           "DecisionTreeNumericBucketizerModel", "PercentileCalibrator",
           "PercentileCalibratorModel", "ScalerTransformer",
           "DescalerTransformer", "ScalingType"]


class ScalingType:
    LINEAR = "linear"
    LOGARITHMIC = "logarithmic"


def _bucket_block(vals: np.ndarray, splits: Sequence[float],
                  feature, track_nulls: bool,
                  bucket_labels: Optional[Sequence[str]] = None,
                  grouping: Optional[str] = None
                  ) -> Tuple[List[np.ndarray], List[VectorColumnMetadata]]:
    """One-hot bucket membership columns for ascending ``splits``
    (buckets are [s_i, s_{i+1}) as in the reference/Spark Bucketizer)."""
    splits = list(splits)
    n_buckets = len(splits) - 1
    isnan = np.isnan(vals)
    idx = np.clip(np.searchsorted(splits, vals, side="right") - 1,
                  0, n_buckets - 1)
    block = np.zeros((len(vals), n_buckets))
    block[np.arange(len(vals))[~isnan], idx[~isnan]] = 1.0
    labels = list(bucket_labels) if bucket_labels else [
        f"{splits[i]}-{splits[i + 1]}" for i in range(n_buckets)]
    group = grouping if grouping is not None else feature.name
    metas = [VectorColumnMetadata(
        parent_feature_name=feature.name,
        parent_feature_type=feature.ftype.__name__,
        grouping=group, indicator_value=lab) for lab in labels]
    blocks = [block]
    if track_nulls:
        blocks.append(isnan.astype(np.float64))
        metas.append(VectorColumnMetadata(
            parent_feature_name=feature.name,
            parent_feature_type=feature.ftype.__name__,
            grouping=group, indicator_value=NULL_INDICATOR))
    return blocks, metas


class NumericBucketizer(UnaryTransformer):
    """(reference NumericBucketizer.scala)"""

    input_types = (OPNumeric,)
    output_type = OPVector

    def __init__(self, split_points: Sequence[float],
                 bucket_labels: Optional[Sequence[str]] = None,
                 track_nulls: bool = True, uid: Optional[str] = None):
        super().__init__(operation_name="numBucket", uid=uid)
        splits = [float(s) for s in split_points]
        if sorted(splits) != splits or len(splits) < 2:
            raise ValueError("split_points must be >= 2 ascending values")
        self.split_points = splits
        self.bucket_labels = list(bucket_labels) if bucket_labels else None
        if self.bucket_labels is not None and \
                len(self.bucket_labels) != len(splits) - 1:
            raise ValueError("need one label per bucket")
        self.track_nulls = track_nulls

    def transform_columns(self, cols: List[FeatureColumn]) -> FeatureColumn:
        vals = np.asarray(cols[0].data, dtype=np.float64)
        blocks, metas = _bucket_block(
            vals, self.split_points, self.input_features[0],
            self.track_nulls, self.bucket_labels)
        return vector_output(self.get_output().name, blocks, metas)


class DecisionTreeNumericBucketizerModel(AllowLabelAsInput, BinaryModel):
    input_types = (RealNN, OPNumeric)
    output_type = OPVector

    def __init__(self, split_points: Sequence[float],
                 track_nulls: bool = True, uid: Optional[str] = None):
        super().__init__(operation_name="dtNumBucket", uid=uid)
        self.split_points = [float(s) for s in split_points]
        self.track_nulls = track_nulls

    @property
    def should_split(self) -> bool:
        return len(self.split_points) > 2

    def transform_columns(self, cols: List[FeatureColumn]) -> FeatureColumn:
        vals = np.asarray(cols[-1].data, dtype=np.float64)
        blocks, metas = _bucket_block(
            vals, self.split_points, self.input_features[-1],
            self.track_nulls)
        return vector_output(self.get_output().name, blocks, metas)

    def transform_value(self, *values):
        vals = np.asarray([
            float("nan") if values[-1].value is None
            else float(values[-1].value)])
        blocks, metas = _bucket_block(
            vals, self.split_points, self.input_features[-1],
            self.track_nulls)
        out = vector_output("row", blocks, metas)
        return out.boxed(0)


class DecisionTreeNumericBucketizer(AllowLabelAsInput, BinaryEstimator):
    """Label-aware buckets from single-feature decision-tree thresholds
    (reference DecisionTreeNumericBucketizer.scala)."""

    input_types = (RealNN, OPNumeric)
    output_type = OPVector

    def __init__(self, max_depth: int = 2, max_bins: int = 32,
                 min_info_gain: float = 0.01,
                 min_instances_per_node: int = 1,
                 track_nulls: bool = True, uid: Optional[str] = None):
        super().__init__(operation_name="dtNumBucket", uid=uid)
        self.max_depth = max_depth
        self.max_bins = max_bins
        self.min_info_gain = min_info_gain
        self.min_instances_per_node = min_instances_per_node
        self.track_nulls = track_nulls

    def fit_columns(self, cols: List[FeatureColumn]
                    ) -> DecisionTreeNumericBucketizerModel:
        from ..models.trees import DecisionTreeClassifier
        y = np.asarray(cols[0].data, dtype=np.float64)
        x = np.asarray(cols[1].data, dtype=np.float64)
        ok = ~np.isnan(x) & ~np.isnan(y)
        splits: List[float] = []
        if ok.sum() >= 2 and len(np.unique(y[ok])) >= 2:
            tree = DecisionTreeClassifier(
                max_depth=self.max_depth, max_bins=self.max_bins,
                min_info_gain=self.min_info_gain,
                min_instances_per_node=self.min_instances_per_node,
            ).fit_arrays(x[ok].reshape(-1, 1), y[ok])
            thresholds = tree.thrs[np.isfinite(tree.thrs)]
            splits = sorted(set(float(t) for t in thresholds.ravel()))
        return DecisionTreeNumericBucketizerModel(
            split_points=[-math.inf] + splits + [math.inf],
            track_nulls=self.track_nulls)


class PercentileCalibratorModel(UnaryModel):
    input_types = (OPNumeric,)
    output_type = RealNN

    def __init__(self, quantiles: Sequence[float], buckets: int = 100,
                 uid: Optional[str] = None):
        super().__init__(operation_name="percentileCalibrator", uid=uid)
        self.quantiles = [float(q) for q in quantiles]
        self.buckets = buckets

    def transform_columns(self, cols: List[FeatureColumn]) -> FeatureColumn:
        vals = np.asarray(cols[0].data, dtype=np.float64)
        q = np.asarray(self.quantiles)
        ranks = np.searchsorted(q, np.nan_to_num(vals, nan=q[0]),
                                side="right") - 1
        out = np.clip(ranks, 0, self.buckets - 1).astype(np.float64)
        return FeatureColumn(ftype=RealNN, data=out)


class PercentileCalibrator(UnaryEstimator):
    """Map values to their training-set percentile bucket [0, buckets-1]
    (reference PercentileCalibrator.scala)."""

    input_types = (OPNumeric,)
    output_type = RealNN

    def __init__(self, buckets: int = 100, uid: Optional[str] = None):
        super().__init__(operation_name="percentileCalibrator", uid=uid)
        self.buckets = buckets

    def fit_columns(self, cols: List[FeatureColumn]
                    ) -> PercentileCalibratorModel:
        vals = np.asarray(cols[0].data, dtype=np.float64)
        ok = vals[~np.isnan(vals)]
        if ok.size == 0:
            qs = np.zeros(self.buckets)
        else:
            qs = np.quantile(ok, np.linspace(0, 1, self.buckets,
                                             endpoint=False))
        return PercentileCalibratorModel(quantiles=list(qs),
                                         buckets=self.buckets)


class ScalerTransformer(UnaryTransformer):
    """Invertible scaling (reference ScalerTransformer.scala +
    ScalingType enum): linear ``slope * x + intercept`` or logarithmic
    ``log(x)``."""

    input_types = (OPNumeric,)
    output_type = Real

    def __init__(self, scaling_type: str = ScalingType.LINEAR,
                 slope: float = 1.0, intercept: float = 0.0,
                 uid: Optional[str] = None):
        super().__init__(operation_name="scaler", uid=uid)
        if scaling_type not in (ScalingType.LINEAR,
                                ScalingType.LOGARITHMIC):
            raise ValueError(f"Unknown scaling type {scaling_type!r}")
        self.scaling_type = scaling_type
        self.slope = slope
        self.intercept = intercept

    def _scale(self, vals: np.ndarray) -> np.ndarray:
        if self.scaling_type == ScalingType.LINEAR:
            return self.slope * vals + self.intercept
        return np.log(vals)

    def _descale(self, vals: np.ndarray) -> np.ndarray:
        if self.scaling_type == ScalingType.LINEAR:
            return (vals - self.intercept) / self.slope
        return np.exp(vals)

    def transform_columns(self, cols: List[FeatureColumn]) -> FeatureColumn:
        vals = np.asarray(cols[0].data, dtype=np.float64)
        return FeatureColumn(ftype=Real, data=self._scale(vals))


class DescalerTransformer(BinaryTransformer):
    """Invert a ScalerTransformer: input 1 is the value to descale,
    input 2 any feature produced by the scaler whose transform to invert
    (reference DescalerTransformer.scala reads ScalerMetadata)."""

    input_types = (OPNumeric, OPNumeric)
    output_type = Real

    def __init__(self, uid: Optional[str] = None):
        super().__init__(operation_name="descaler", uid=uid)

    def _scaler(self) -> ScalerTransformer:
        origin = self.input_features[1].origin_stage
        if not isinstance(origin, ScalerTransformer):
            raise ValueError(
                "DescalerTransformer input 2 must be the output of a "
                "ScalerTransformer")
        return origin

    def transform_columns(self, cols: List[FeatureColumn]) -> FeatureColumn:
        vals = np.asarray(cols[0].data, dtype=np.float64)
        return FeatureColumn(ftype=Real, data=self._scaler()._descale(vals))


class DecisionTreeNumericMapBucketizerModel(AllowLabelAsInput, BinaryModel):
    from ..types import NumericMap as _NM
    input_types = (RealNN, _NM)
    output_type = OPVector

    def __init__(self, keys: Sequence[str],
                 split_points: Dict[str, Sequence[float]],
                 track_nulls: bool = True, uid: Optional[str] = None):
        super().__init__(operation_name="dtNumMapBucket", uid=uid)
        self.keys = list(keys)
        self.split_points = {k: [float(s) for s in v]
                             for k, v in split_points.items()}
        self.track_nulls = track_nulls

    def transform_columns(self, cols: List[FeatureColumn]) -> FeatureColumn:
        col = cols[-1]
        n = col.n_rows
        blocks, metas = [], []
        for k in self.keys:
            vals = np.full(n, np.nan)
            for i, m in enumerate(col.data):
                if m and k in m and m[k] is not None:
                    vals[i] = float(m[k])
            b, me = _bucket_block(vals, self.split_points[k],
                                  self.input_features[-1],
                                  self.track_nulls, grouping=k)
            blocks.extend(b)
            metas.extend(me)
        return vector_output(self.get_output().name, blocks, metas)


class DecisionTreeNumericMapBucketizer(AllowLabelAsInput, BinaryEstimator):
    """Per-KEY label-aware buckets for numeric maps
    (reference DecisionTreeNumericMapBucketizer.scala) — each key gets
    its own single-feature decision-tree split points."""

    from ..types import NumericMap as _NM
    input_types = (RealNN, _NM)
    output_type = OPVector

    def __init__(self, max_depth: int = 2, max_bins: int = 32,
                 min_info_gain: float = 0.01,
                 min_instances_per_node: int = 1,
                 track_nulls: bool = True,
                 allow_keys: Optional[Sequence[str]] = None,
                 uid: Optional[str] = None):
        super().__init__(operation_name="dtNumMapBucket", uid=uid)
        self.max_depth = max_depth
        self.max_bins = max_bins
        self.min_info_gain = min_info_gain
        self.min_instances_per_node = min_instances_per_node
        self.track_nulls = track_nulls
        self.allow_keys = list(allow_keys) if allow_keys else None

    def fit_columns(self, cols: List[FeatureColumn]
                    ) -> DecisionTreeNumericMapBucketizerModel:
        from .maps import _sorted_keys
        y = np.asarray(cols[0].data, dtype=np.float64)
        keys = _sorted_keys([cols[1]], self.allow_keys)[0]
        scalar = DecisionTreeNumericBucketizer(
            max_depth=self.max_depth, max_bins=self.max_bins,
            min_info_gain=self.min_info_gain,
            min_instances_per_node=self.min_instances_per_node,
            track_nulls=self.track_nulls)
        scalar.input_features = self.input_features
        splits: Dict[str, List[float]] = {}
        for k in keys:
            vals = np.full(len(y), np.nan)
            for i, m in enumerate(cols[1].data):
                if m and k in m and m[k] is not None:
                    vals[i] = float(m[k])
            vcol = FeatureColumn(ftype=self.input_types[1], data=vals)
            sub = scalar.fit_columns([cols[0], vcol])
            splits[k] = sub.split_points
        return DecisionTreeNumericMapBucketizerModel(
            keys=keys, split_points=splits, track_nulls=self.track_nulls)
