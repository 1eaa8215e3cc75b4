"""The tree estimators: ``DecisionTree*``, ``RandomForest*``, ``GBT*`` and
the ``XGBoost*`` facades (reference OpDecisionTreeClassifier.scala,
OpRandomForestClassifier.scala, OpGBTClassifier.scala,
OpXGBoostClassifier.scala:47 and their regressors).

A single fit bins its matrix (``design.py``) and calls a jitted program of
``bodies.py``; a row-sharded fit its ``shard_map`` kernel; a search the
fold-grid drivers of ``fold_grid.py``.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..base import Predictor, check_fold_classes, num_classes, subset_grid
from ...parallel.mesh import to_host
from . import forms as _forms
from .bodies import (_fit_forest_classifier, _fit_forest_regressor,
                     _fit_gbt, _fit_gbt_softmax, _forest_sharded_kernel,
                     _gbt_sharded_kernel, _pad_rows)
from .design import _design_args, _pool_plan, _resolve_max_features
from .fitted import (GBTClassifierModel, GBTMulticlassClassifierModel,
                     GBTRegressorModel, TreeEnsembleClassifierModel,
                     TreeEnsembleRegressorModel)
from .fold_grid import (_forest_fold_grid, _gbt_fold_grid,
                        _gbt_softmax_fold_grid)


def _gbt_fit_sharded(est, X, y, mesh, axis: str, objective: str):
    """Shared driver for the row-sharded GBT fits (see
    _forest_sharded_kernel notes on replication and padding)."""
    shards = mesh.shape[axis]
    design, _ = _design_args(X, est.max_bins)
    packed, feat_of, block_start, packed_thr = design[:4]
    (packed_p, y_p), mask = _pad_rows(
        [np.asarray(packed), np.asarray(y)], shards)
    row_total = len(mask)
    statics = (est.max_depth, est.num_rounds, objective,
               _forms._hist_mode(row_total // shards, int(feat_of.shape[0])),
               row_total)
    fn = _gbt_sharded_kernel(statics, mesh, axis)
    feats, thrs, leaves, base = fn(
        jnp.asarray(packed_p), jnp.asarray(y_p), jnp.asarray(mask),
        feat_of, block_start, packed_thr,
        jax.random.PRNGKey(est.seed),
        jnp.asarray(float(est.step_size)),
        jnp.asarray(float(est.reg_lambda)),
        jnp.asarray(float(est.gamma)),
        jnp.asarray(float(est.min_child_weight)),
        jnp.asarray(float(est.subsample)))
    model_cls = (GBTClassifierModel if objective == "logistic"
                 else GBTRegressorModel)
    return model_cls(to_host(feats), to_host(thrs), to_host(leaves),
                     depth=est.max_depth, base=float(to_host(base)),
                     n_features=X.shape[1])


class _ForestClassifierBase(Predictor):
    num_trees = 1
    bootstrap = False

    def fit_fold_grid_arrays(self, X, y, masks, grid, mesh=None):
        """Validator fast path: all (fold, grid) candidates in one
        vmapped program per static group, mesh-shardable over the
        candidate axis (reference OpValidator.scala:270 parallelism)."""
        return _forest_fold_grid(self, X, y, masks, grid, mesh, True)

    def eval_fold_grid_arrays(self, X, y, masks, grid, X_val, y_val,
                              spec, mesh=None, cand_idx=None,
                              val_rows=None):
        """Device-resident search: fused fit + validation metric, (F, G)
        matrix out (see _forest_fold_grid eval_ctx). ``cand_idx``
        (racing rungs) restricts to a candidate subset — traced
        hyperparameters stay dynamic lanes; static groups a rung prunes
        entirely simply stop being compiled.

        ``val_rows`` ((F, nv) int, optional): where ``X_val[f]`` is
        ``X[val_rows[f]]``, rows of the fitted table itself, say so here
        and the validation matrix is NOT read: each candidate is scored
        from the leaves its fit already routed those rows to (the same
        leaves, no second walk; see fold_grid._candidate_scores). Without it
        ``X_val`` is read, uploaded and walked down the finished trees —
        the form for validation rows foreign to ``X``."""
        if spec[0] == "binary" and num_classes(y) != 2:
            raise NotImplementedError(
                "binary device eval needs binary labels")
        if spec[0] not in ("binary", "multiclass"):
            raise NotImplementedError(
                "forest-classifier device eval needs a classification "
                "metric")
        return _forest_fold_grid(
            self, X, y, masks, subset_grid(grid, cand_idx), mesh, True,
            eval_ctx=(X_val, y_val, spec, val_rows))

    def fit_arrays_sharded(self, X, y, mesh, axis: str = "data"
                           ) -> TreeEnsembleClassifierModel:
        """Row-sharded (data-parallel) fit: each ``mesh[axis]`` shard
        holds a contiguous row block; per-level histograms psum over
        ICI (_grow_tree axis_name — the Rabit-allreduce role, SURVEY
        §2.9). Identical trees to fit_arrays when the row count divides
        the shard count (same bootstrap draws via _row_draw)."""
        k = num_classes(y)
        d = X.shape[1]
        shards = mesh.shape[axis]
        mf = _resolve_max_features(self.feature_subset_strategy, d, True) \
            if self.bootstrap else None
        design, widths = _design_args(X, self.max_bins)
        (narrow, wide), pool_cfg, mf = _pool_plan(widths, mf)
        packed, feat_of, block_start, packed_thr, binned, col_thr = design
        (packed_p, binned_p, y_p), mask = _pad_rows(
            [np.asarray(packed), np.asarray(binned), np.asarray(y)],
            shards)
        row_total = len(mask)
        statics = ("cls", self.max_depth, k, self.num_trees, mf,
                   pool_cfg, self.impurity, self.bootstrap,
                   _forms._hist_mode(row_total // shards,
                                     int(feat_of.shape[0])),
                   row_total)
        fn = _forest_sharded_kernel(statics, mesh, axis)
        feats, thrs, leaves = fn(
            jnp.asarray(packed_p), jnp.asarray(binned_p),
            jnp.asarray(y_p), jnp.asarray(mask), feat_of, block_start,
            packed_thr, col_thr, narrow, wide,
            jax.random.PRNGKey(self.seed),
            jnp.asarray(float(self.min_instances_per_node)),
            jnp.asarray(float(self.min_info_gain)),
            jnp.asarray(float(self.subsampling_rate)))
        return TreeEnsembleClassifierModel(
            to_host(feats), to_host(thrs), to_host(leaves),
            depth=self.max_depth, n_features=d)

    def fit_arrays(self, X: np.ndarray, y: np.ndarray
                   ) -> TreeEnsembleClassifierModel:
        k = num_classes(y)
        d = X.shape[1]
        mf = _resolve_max_features(self.feature_subset_strategy, d, True) \
            if self.bootstrap else None
        design, widths = _design_args(X, self.max_bins)
        (narrow, wide), pool_cfg, mf = _pool_plan(widths, mf)
        feats, thrs, leaves = _fit_forest_classifier(
            *design, narrow, wide, jnp.asarray(y),
            jax.random.PRNGKey(self.seed), depth=self.max_depth,
            num_classes=k, num_trees=self.num_trees, max_features=mf,
            pool_cfg=pool_cfg, impurity=self.impurity,
            min_instances=float(self.min_instances_per_node),
            min_info_gain=self.min_info_gain,
            subsample=self.subsampling_rate, bootstrap=self.bootstrap,
            hist_mode=_forms._hist_mode(X.shape[0], int(design[1].shape[0])))
        return TreeEnsembleClassifierModel(feats, thrs, leaves,
                                           depth=self.max_depth,
                                           n_features=d)


class _ForestRegressorBase(Predictor):
    num_trees = 1
    bootstrap = False

    def fit_fold_grid_arrays(self, X, y, masks, grid, mesh=None):
        """See _ForestClassifierBase.fit_fold_grid_arrays."""
        return _forest_fold_grid(self, X, y, masks, grid, mesh, False)

    def eval_fold_grid_arrays(self, X, y, masks, grid, X_val, y_val,
                              spec, mesh=None, cand_idx=None,
                              val_rows=None):
        """See _ForestClassifierBase.eval_fold_grid_arrays (``val_rows``
        given: ``X_val`` is not read, only those row indices are)."""
        if spec[0] != "regression":
            raise NotImplementedError(
                "forest-regressor device eval needs a regression metric")
        return _forest_fold_grid(
            self, X, y, masks, subset_grid(grid, cand_idx), mesh, False,
            eval_ctx=(X_val, y_val, spec, val_rows))

    def fit_arrays_sharded(self, X, y, mesh, axis: str = "data"
                           ) -> TreeEnsembleRegressorModel:
        """See _ForestClassifierBase.fit_arrays_sharded."""
        d = X.shape[1]
        shards = mesh.shape[axis]
        mf = _resolve_max_features(self.feature_subset_strategy, d,
                                   False) if self.bootstrap else None
        design, widths = _design_args(X, self.max_bins)
        (narrow, wide), pool_cfg, mf = _pool_plan(widths, mf)
        packed, feat_of, block_start, packed_thr, binned, col_thr = design
        (packed_p, binned_p, y_p), mask = _pad_rows(
            [np.asarray(packed), np.asarray(binned), np.asarray(y)],
            shards)
        row_total = len(mask)
        statics = ("reg", self.max_depth, 0, self.num_trees, mf,
                   pool_cfg, "", self.bootstrap,
                   _forms._hist_mode(row_total // shards,
                                     int(feat_of.shape[0])),
                   row_total)
        fn = _forest_sharded_kernel(statics, mesh, axis)
        feats, thrs, leaves = fn(
            jnp.asarray(packed_p), jnp.asarray(binned_p),
            jnp.asarray(y_p), jnp.asarray(mask), feat_of, block_start,
            packed_thr, col_thr, narrow, wide,
            jax.random.PRNGKey(self.seed),
            jnp.asarray(float(self.min_instances_per_node)),
            jnp.asarray(float(self.min_info_gain)),
            jnp.asarray(float(self.subsampling_rate)))
        return TreeEnsembleRegressorModel(
            to_host(feats), to_host(thrs), to_host(leaves),
            depth=self.max_depth, n_features=d)

    def fit_arrays(self, X: np.ndarray, y: np.ndarray
                   ) -> TreeEnsembleRegressorModel:
        d = X.shape[1]
        mf = _resolve_max_features(self.feature_subset_strategy, d, False) \
            if self.bootstrap else None
        design, widths = _design_args(X, self.max_bins)
        (narrow, wide), pool_cfg, mf = _pool_plan(widths, mf)
        feats, thrs, leaves = _fit_forest_regressor(
            *design, narrow, wide, jnp.asarray(y),
            jax.random.PRNGKey(self.seed), depth=self.max_depth,
            num_trees=self.num_trees, max_features=mf,
            pool_cfg=pool_cfg,
            min_instances=float(self.min_instances_per_node),
            min_info_gain=self.min_info_gain,
            subsample=self.subsampling_rate, bootstrap=self.bootstrap,
            hist_mode=_forms._hist_mode(X.shape[0], int(design[1].shape[0])))
        return TreeEnsembleRegressorModel(feats, thrs, leaves,
                                          depth=self.max_depth,
                                          n_features=d)


class DecisionTreeClassifier(_ForestClassifierBase):
    """Single CART tree, gini/entropy impurity
    (reference OpDecisionTreeClassifier.scala)."""

    def __init__(self, max_depth: int = 5, max_bins: int = 32,
                 min_instances_per_node: int = 1, min_info_gain: float = 0.0,
                 impurity: str = "gini", seed: int = 42,
                 uid: Optional[str] = None):
        super().__init__(uid=uid)
        self.max_depth = max_depth
        self.max_bins = max_bins
        self.min_instances_per_node = min_instances_per_node
        self.min_info_gain = min_info_gain
        self.impurity = impurity
        self.seed = seed
        self.num_trees = 1
        self.bootstrap = False
        self.subsampling_rate = 1.0
        self.feature_subset_strategy = "all"


class DecisionTreeRegressor(_ForestRegressorBase):
    """(reference OpDecisionTreeRegressor.scala)"""

    def __init__(self, max_depth: int = 5, max_bins: int = 32,
                 min_instances_per_node: int = 1, min_info_gain: float = 0.0,
                 seed: int = 42, uid: Optional[str] = None):
        super().__init__(uid=uid)
        self.max_depth = max_depth
        self.max_bins = max_bins
        self.min_instances_per_node = min_instances_per_node
        self.min_info_gain = min_info_gain
        self.seed = seed
        self.num_trees = 1
        self.bootstrap = False
        self.subsampling_rate = 1.0
        self.feature_subset_strategy = "all"


class RandomForestClassifier(_ForestClassifierBase):
    """Bagged gini trees with per-node feature subsampling
    (reference OpRandomForestClassifier.scala). Bootstrap resampling uses
    Poisson(subsamplingRate) row weights — the same approximation Spark
    MLlib's BaggedPoint uses for sampling with replacement."""

    def __init__(self, num_trees: int = 20, max_depth: int = 5,
                 max_bins: int = 32, min_instances_per_node: int = 1,
                 min_info_gain: float = 0.0, subsampling_rate: float = 1.0,
                 feature_subset_strategy: str = "auto", impurity: str = "gini",
                 seed: int = 42, uid: Optional[str] = None):
        super().__init__(uid=uid)
        self.num_trees = num_trees
        self.max_depth = max_depth
        self.max_bins = max_bins
        self.min_instances_per_node = min_instances_per_node
        self.min_info_gain = min_info_gain
        self.subsampling_rate = subsampling_rate
        self.feature_subset_strategy = feature_subset_strategy
        self.impurity = impurity
        self.seed = seed
        self.bootstrap = True


class RandomForestRegressor(_ForestRegressorBase):
    """(reference OpRandomForestRegressor.scala)"""

    def __init__(self, num_trees: int = 20, max_depth: int = 5,
                 max_bins: int = 32, min_instances_per_node: int = 1,
                 min_info_gain: float = 0.0, subsampling_rate: float = 1.0,
                 feature_subset_strategy: str = "auto", seed: int = 42,
                 uid: Optional[str] = None):
        super().__init__(uid=uid)
        self.num_trees = num_trees
        self.max_depth = max_depth
        self.max_bins = max_bins
        self.min_instances_per_node = min_instances_per_node
        self.min_info_gain = min_info_gain
        self.subsampling_rate = subsampling_rate
        self.feature_subset_strategy = feature_subset_strategy
        self.seed = seed
        self.bootstrap = True


class GBTClassifier(Predictor):
    """Gradient-boosted binary classifier with second-order (XGBoost-style)
    split gains on the logistic objective (reference OpGBTClassifier.scala;
    MLlib GBT uses first-order residual fitting — the second-order variant
    strictly dominates and is the XGBoost parity path, SURVEY §2.9)."""

    def __init__(self, num_rounds: int = 20, max_depth: int = 5,
                 step_size: float = 0.1, max_bins: int = 32,
                 reg_lambda: float = 1.0, gamma: float = 0.0,
                 min_child_weight: float = 1.0, subsample: float = 1.0,
                 seed: int = 42, uid: Optional[str] = None):
        super().__init__(uid=uid)
        self.num_rounds = num_rounds
        self.max_depth = max_depth
        self.step_size = step_size
        self.max_bins = max_bins
        self.reg_lambda = reg_lambda
        self.gamma = gamma
        self.min_child_weight = min_child_weight
        self.subsample = subsample
        self.seed = seed

    def fit_fold_grid_arrays(self, X, y, masks, grid, mesh=None):
        """See _ForestClassifierBase.fit_fold_grid_arrays."""
        bad = np.setdiff1d(np.unique(y), [0.0, 1.0])
        if bad.size:
            # NotImplementedError (not ValueError): the validator then
            # takes the sequential fallback, where the per-fold handler
            # drops this family out of the race instead of killing the
            # whole search
            raise NotImplementedError(
                "batched GBT kernel requires binary labels {0, 1}")
        return _gbt_fold_grid(self, X, y, masks, grid, mesh, "logistic")

    def eval_fold_grid_arrays(self, X, y, masks, grid, X_val, y_val,
                              spec, mesh=None, cand_idx=None,
                              val_rows=None):
        """Device-resident search: fused fit + validation metric, (F, G)
        matrix out (see _gbt_fold_grid eval_ctx). ``val_rows`` as in
        _ForestClassifierBase.eval_fold_grid_arrays: given, ``X_val`` is
        not read and the held-out rows are scored from the margins the
        fit already carries for them; without it ``X_val`` is walked."""
        if spec[0] != "binary":
            raise NotImplementedError(
                "GBT-classifier device eval is binary-only")
        bad = np.setdiff1d(np.unique(y), [0.0, 1.0])
        if bad.size:
            raise NotImplementedError(
                "batched GBT kernel requires binary labels {0, 1}")
        return _gbt_fold_grid(
            self, X, y, masks, subset_grid(grid, cand_idx), mesh,
            "logistic", eval_ctx=(X_val, y_val, spec, val_rows))

    def fit_arrays_sharded(self, X, y, mesh, axis: str = "data"
                           ) -> GBTClassifierModel:
        """Row-sharded (data-parallel) boosting — see
        _ForestClassifierBase.fit_arrays_sharded."""
        bad = np.setdiff1d(np.unique(y), [0.0, 1.0])
        if bad.size:
            raise ValueError(
                "GBTClassifier supports binary labels {0, 1} only")
        return _gbt_fit_sharded(self, X, y, mesh, axis, "logistic")

    def fit_arrays(self, X: np.ndarray, y: np.ndarray) -> GBTClassifierModel:
        bad = np.setdiff1d(np.unique(y), [0.0, 1.0])
        if bad.size:
            raise ValueError(
                f"GBTClassifier supports binary labels {{0, 1}} only "
                f"(as MLlib GBTClassifier does); got extra labels "
                f"{bad.tolist()} — use RandomForestClassifier or "
                f"LogisticRegression for multiclass")
        design, _ = _design_args(X, self.max_bins)
        feats, thrs, leaves, base = _fit_gbt(
            *design[:4], jnp.asarray(y),
            jax.random.PRNGKey(self.seed), depth=self.max_depth,
            num_rounds=self.num_rounds,
            step_size=self.step_size, reg_lambda=self.reg_lambda,
            gamma=self.gamma, min_child_weight=self.min_child_weight,
            subsample=self.subsample, objective="logistic",
            hist_mode=_forms._hist_mode(X.shape[0], int(design[1].shape[0])))
        return GBTClassifierModel(feats, thrs, leaves, depth=self.max_depth,
                                  base=float(base), n_features=X.shape[1])


class GBTRegressor(Predictor):
    """Gradient-boosted regressor, squared loss
    (reference OpGBTRegressor.scala)."""

    def __init__(self, num_rounds: int = 20, max_depth: int = 5,
                 step_size: float = 0.1, max_bins: int = 32,
                 reg_lambda: float = 1.0, gamma: float = 0.0,
                 min_child_weight: float = 1.0, subsample: float = 1.0,
                 seed: int = 42, uid: Optional[str] = None):
        super().__init__(uid=uid)
        self.num_rounds = num_rounds
        self.max_depth = max_depth
        self.step_size = step_size
        self.max_bins = max_bins
        self.reg_lambda = reg_lambda
        self.gamma = gamma
        self.min_child_weight = min_child_weight
        self.subsample = subsample
        self.seed = seed

    def fit_fold_grid_arrays(self, X, y, masks, grid, mesh=None):
        """See _ForestClassifierBase.fit_fold_grid_arrays."""
        return _gbt_fold_grid(self, X, y, masks, grid, mesh, "squared")

    def eval_fold_grid_arrays(self, X, y, masks, grid, X_val, y_val,
                              spec, mesh=None, cand_idx=None,
                              val_rows=None):
        """See GBTClassifier.eval_fold_grid_arrays (``val_rows`` given:
        ``X_val`` is not read, only those row indices are)."""
        if spec[0] != "regression":
            raise NotImplementedError(
                "GBT-regressor device eval needs a regression metric")
        return _gbt_fold_grid(
            self, X, y, masks, subset_grid(grid, cand_idx), mesh,
            "squared", eval_ctx=(X_val, y_val, spec, val_rows))

    def fit_arrays_sharded(self, X, y, mesh, axis: str = "data"
                           ) -> GBTRegressorModel:
        """See GBTClassifier.fit_arrays_sharded."""
        return _gbt_fit_sharded(self, X, y, mesh, axis, "squared")

    def fit_arrays(self, X: np.ndarray, y: np.ndarray) -> GBTRegressorModel:
        design, _ = _design_args(X, self.max_bins)
        feats, thrs, leaves, base = _fit_gbt(
            *design[:4], jnp.asarray(y),
            jax.random.PRNGKey(self.seed), depth=self.max_depth,
            num_rounds=self.num_rounds,
            step_size=self.step_size, reg_lambda=self.reg_lambda,
            gamma=self.gamma, min_child_weight=self.min_child_weight,
            subsample=self.subsample, objective="squared",
            hist_mode=_forms._hist_mode(X.shape[0], int(design[1].shape[0])))
        return GBTRegressorModel(feats, thrs, leaves, depth=self.max_depth,
                                 base=float(base), n_features=X.shape[1])


class XGBoostClassifier(GBTClassifier):
    """XGBoost-parameter-named facade over the same histogram booster
    (reference OpXGBoostClassifier.scala:47 — the reference's only native
    C++ component, xgboost4j + Rabit; here the booster IS the second-order
    histogram GBT above, with multi-chip reduction via psum, SURVEY §2.9).
    Unlike GBTClassifier (MLlib parity: binary-only), this facade also
    fits K-class problems via the softmax objective — the
    ``multi:softprob`` path xgboost4j takes."""

    def __init__(self, eta: float = 0.3, max_depth: int = 6,
                 num_round: int = 100, reg_lambda: float = 1.0,
                 gamma: float = 0.0, min_child_weight: float = 1.0,
                 subsample: float = 1.0, max_bins: int = 256,
                 seed: int = 42, uid: Optional[str] = None):
        GBTClassifier.__init__(
            self, num_rounds=num_round, max_depth=max_depth, step_size=eta,
            max_bins=max_bins, reg_lambda=reg_lambda, gamma=gamma,
            min_child_weight=min_child_weight, subsample=subsample,
            seed=seed, uid=uid)
        self.eta = eta
        self.num_round = num_round

    @staticmethod
    def _check_multiclass_labels(y, k: int) -> None:
        bad = np.setdiff1d(np.unique(y), np.arange(k, dtype=np.float64))
        if bad.size:
            raise NotImplementedError(
                f"softmax booster needs integer class labels 0..{k - 1};"
                f" got {bad.tolist()}")

    def fit_fold_grid_arrays(self, X, y, masks, grid, mesh=None):
        """Multiclass grids run the fused softmax fold×grid kernel
        (binary falls through to the GBT driver)."""
        k = num_classes(y)
        if k <= 2:
            return GBTClassifier.fit_fold_grid_arrays(
                self, X, y, masks, grid, mesh=mesh)
        self._check_multiclass_labels(y, k)
        check_fold_classes(y, masks)
        return _gbt_softmax_fold_grid(self, X, y, masks, grid, mesh, k)

    def eval_fold_grid_arrays(self, X, y, masks, grid, X_val, y_val,
                              spec, mesh=None, cand_idx=None,
                              val_rows=None):
        """Device-resident multiclass search: fused softmax fit +
        metric, (F, G) matrix out (_gbt_softmax_eval_kernel).
        ``val_rows`` as in GBTClassifier.eval_fold_grid_arrays: given,
        ``X_val`` is not read, only those row indices are."""
        k = num_classes(y)
        if k <= 2:
            return GBTClassifier.eval_fold_grid_arrays(
                self, X, y, masks, grid, X_val, y_val, spec, mesh=mesh,
                cand_idx=cand_idx, val_rows=val_rows)
        if spec[0] != "multiclass":
            raise NotImplementedError(
                "softmax-GBT device eval needs a multiclass metric")
        self._check_multiclass_labels(y, k)
        check_fold_classes(y, masks)
        return _gbt_softmax_fold_grid(
            self, X, y, masks, subset_grid(grid, cand_idx), mesh, k,
            eval_ctx=(X_val, y_val, spec, val_rows))

    def fit_arrays(self, X: np.ndarray, y: np.ndarray):
        k = num_classes(y)
        if k <= 2:
            return GBTClassifier.fit_arrays(self, X, y)
        bad = np.setdiff1d(np.unique(y), np.arange(k, dtype=np.float64))
        if bad.size:
            raise ValueError(
                f"XGBoostClassifier needs integer class labels 0..{k - 1};"
                f" got {bad.tolist()}")
        design, _ = _design_args(X, self.max_bins)
        feats, thrs, leaves, base = _fit_gbt_softmax(
            *design[:4], jnp.asarray(y), jax.random.PRNGKey(self.seed),
            depth=self.max_depth, num_rounds=self.num_rounds,
            num_classes=k, step_size=self.step_size,
            reg_lambda=self.reg_lambda, gamma=self.gamma,
            min_child_weight=self.min_child_weight,
            subsample=self.subsample,
            hist_mode=_forms._hist_mode(X.shape[0], int(design[1].shape[0])))
        return GBTMulticlassClassifierModel(
            to_host(feats), to_host(thrs), to_host(leaves),
            depth=self.max_depth, base=to_host(base),
            n_features=X.shape[1])


class XGBoostRegressor(GBTRegressor):
    """(reference OpXGBoostRegressor.scala)"""

    def __init__(self, eta: float = 0.3, max_depth: int = 6,
                 num_round: int = 100, reg_lambda: float = 1.0,
                 gamma: float = 0.0, min_child_weight: float = 1.0,
                 subsample: float = 1.0, max_bins: int = 256,
                 seed: int = 42, uid: Optional[str] = None):
        GBTRegressor.__init__(
            self, num_rounds=num_round, max_depth=max_depth, step_size=eta,
            max_bins=max_bins, reg_lambda=reg_lambda, gamma=gamma,
            min_child_weight=min_child_weight, subsample=subsample,
            seed=seed, uid=uid)
        self.eta = eta
        self.num_round = num_round
