"""The fitted tree models (forests and single trees, binary and regression
boosters, the K-class softmax booster) and ``batch_predict_raw``, which
scores many of them against one matrix in one program a shape group.

Every model scores by the walks of ``walk.py``.
"""
from __future__ import annotations

from typing import Dict, Optional

import jax.numpy as jnp
import numpy as np

from ..base import ClassifierModel, RegressionModel
from .walk import _batched_tree_raw, _predict_leaves


class TreeEnsembleClassifierModel(ClassifierModel):
    """RF/DT classifier model: averages per-tree leaf class distributions
    (reference RandomForestClassificationModel normalized vote averaging)."""

    def __init__(self, feats, thrs, leaves, depth: int,
                 n_features: int = 0, uid: Optional[str] = None):
        super().__init__(uid=uid)
        self.feats = np.asarray(feats, dtype=np.int32)
        self.thrs = np.asarray(thrs, dtype=np.float64)
        self.leaves = np.asarray(leaves, dtype=np.float64)  # (T, L, K)
        self.depth = int(depth)
        self.n_features = int(n_features)

    def predict_raw(self, X: np.ndarray) -> np.ndarray:
        leaf_idx = np.asarray(_predict_leaves(
            jnp.asarray(X), jnp.asarray(self.feats),
            jnp.asarray(self.thrs), self.depth))              # (T, n)
        probs = self.leaves[np.arange(len(self.feats))[:, None], leaf_idx]
        return np.mean(probs, axis=0)                          # (n, K)

    def raw_arrays(self, X):
        leaf_idx = _predict_leaves(X, jnp.asarray(self.feats),
                                   jnp.asarray(self.thrs, X.dtype),
                                   self.depth)
        probs = jnp.asarray(self.leaves, X.dtype)[
            jnp.arange(len(self.feats))[:, None], leaf_idx]
        return jnp.mean(probs, axis=0)

    def raw_to_probability(self, raw: np.ndarray) -> np.ndarray:
        s = np.sum(raw, axis=1, keepdims=True)
        return raw / np.where(s > 0, s, 1.0)

    @property
    def feature_importances(self) -> np.ndarray:
        return _split_count_importances(self.feats, self.thrs, self.n_features)


class TreeEnsembleRegressorModel(RegressionModel):
    def __init__(self, feats, thrs, leaves, depth: int,
                 n_features: int = 0, uid: Optional[str] = None):
        super().__init__(uid=uid)
        self.feats = np.asarray(feats, dtype=np.int32)
        self.thrs = np.asarray(thrs, dtype=np.float64)
        self.leaves = np.asarray(leaves, dtype=np.float64)  # (T, L)
        self.depth = int(depth)
        self.n_features = int(n_features)

    def predict_values(self, X: np.ndarray) -> np.ndarray:
        leaf_idx = np.asarray(_predict_leaves(
            jnp.asarray(X), jnp.asarray(self.feats),
            jnp.asarray(self.thrs), self.depth))
        vals = self.leaves[np.arange(len(self.feats))[:, None], leaf_idx]
        return np.mean(vals, axis=0)

    def raw_arrays(self, X):
        leaf_idx = _predict_leaves(X, jnp.asarray(self.feats),
                                   jnp.asarray(self.thrs, X.dtype),
                                   self.depth)
        vals = jnp.asarray(self.leaves, X.dtype)[
            jnp.arange(len(self.feats))[:, None], leaf_idx]
        return jnp.mean(vals, axis=0)

    @property
    def feature_importances(self) -> np.ndarray:
        return _split_count_importances(self.feats, self.thrs, self.n_features)


class GBTClassifierModel(ClassifierModel):
    """Boosted binary classifier: sigmoid over summed leaf margins."""

    def __init__(self, feats, thrs, leaves, depth: int, base: float = 0.0,
                 n_features: int = 0, uid: Optional[str] = None):
        super().__init__(uid=uid)
        self.feats = np.asarray(feats, dtype=np.int32)
        self.thrs = np.asarray(thrs, dtype=np.float64)
        self.leaves = np.asarray(leaves, dtype=np.float64)
        self.depth = int(depth)
        self.base = float(base)
        self.n_features = int(n_features)

    def margins(self, X: np.ndarray) -> np.ndarray:
        leaf_idx = np.asarray(_predict_leaves(
            jnp.asarray(X), jnp.asarray(self.feats),
            jnp.asarray(self.thrs), self.depth))
        vals = self.leaves[np.arange(len(self.feats))[:, None], leaf_idx]
        return self.base + np.sum(vals, axis=0)

    def raw_from_margin(self, m: np.ndarray) -> np.ndarray:
        """Margin vector -> raw-prediction pair; the single place that
        defines this model's raw layout (batch_predict_raw reuses it so
        the batched path cannot diverge from predict_raw)."""
        return np.stack([-m, m], axis=1)

    def predict_raw(self, X: np.ndarray) -> np.ndarray:
        return self.raw_from_margin(self.margins(X))

    def raw_arrays(self, X):
        leaf_idx = _predict_leaves(X, jnp.asarray(self.feats),
                                   jnp.asarray(self.thrs, X.dtype),
                                   self.depth)
        vals = jnp.asarray(self.leaves, X.dtype)[
            jnp.arange(len(self.feats))[:, None], leaf_idx]
        m = self.base + jnp.sum(vals, axis=0)
        return jnp.stack([-m, m], axis=1)

    def raw_to_probability(self, raw: np.ndarray) -> np.ndarray:
        p = 1.0 / (1.0 + np.exp(-raw[:, 1]))
        return np.stack([1 - p, p], axis=1)

    @property
    def feature_importances(self) -> np.ndarray:
        return _split_count_importances(self.feats, self.thrs, self.n_features)


class GBTMulticlassClassifierModel(ClassifierModel):
    """K-class softmax booster model (see _gbt_softmax_body): raw
    predictions are the per-class margins; the default max-shifted
    softmax of ClassifierModel turns them into ``multi:softprob``
    probabilities (parity with xgboost4j's multiclass output,
    OpXGBoostClassifier.scala:47)."""

    def __init__(self, feats, thrs, leaves, depth: int, base,
                 n_features: int = 0, uid: Optional[str] = None):
        super().__init__(uid=uid)
        self.feats = np.asarray(feats, dtype=np.int32)     # (R, K, H)
        self.thrs = np.asarray(thrs, dtype=np.float64)
        self.leaves = np.asarray(leaves, dtype=np.float64)  # (R, K, L)
        self.depth = int(depth)
        self.base = np.asarray(base, dtype=np.float64)      # (K,)
        self.n_features = int(n_features)

    def predict_raw(self, X: np.ndarray) -> np.ndarray:
        rounds, k, heap = self.feats.shape
        flat_f = self.feats.reshape(rounds * k, heap)
        flat_t = self.thrs.reshape(rounds * k, heap)
        leaf_idx = np.asarray(_predict_leaves(
            jnp.asarray(X), jnp.asarray(flat_f), jnp.asarray(flat_t),
            self.depth))                                   # (R*K, n)
        flat_l = self.leaves.reshape(rounds * k, -1)
        vals = flat_l[np.arange(rounds * k)[:, None], leaf_idx]
        margins = vals.reshape(rounds, k, -1).sum(axis=0).T  # (n, K)
        return self.base + margins

    def raw_arrays(self, X):
        rounds, k, heap = self.feats.shape
        leaf_idx = _predict_leaves(
            X, jnp.asarray(self.feats.reshape(rounds * k, heap)),
            jnp.asarray(self.thrs.reshape(rounds * k, heap), X.dtype),
            self.depth)                                      # (R*K, n)
        flat_l = jnp.asarray(self.leaves.reshape(rounds * k, -1), X.dtype)
        vals = flat_l[jnp.arange(rounds * k)[:, None], leaf_idx]
        margins = vals.reshape(rounds, k, -1).sum(axis=0).T  # (n, K)
        return jnp.asarray(self.base, X.dtype) + margins

    @property
    def feature_importances(self) -> np.ndarray:
        rounds, k, heap = self.feats.shape
        return _split_count_importances(
            self.feats.reshape(rounds * k, heap),
            self.thrs.reshape(rounds * k, heap), self.n_features)


class GBTRegressorModel(RegressionModel):
    def __init__(self, feats, thrs, leaves, depth: int, base: float = 0.0,
                 n_features: int = 0, uid: Optional[str] = None):
        super().__init__(uid=uid)
        self.feats = np.asarray(feats, dtype=np.int32)
        self.thrs = np.asarray(thrs, dtype=np.float64)
        self.leaves = np.asarray(leaves, dtype=np.float64)
        self.depth = int(depth)
        self.base = float(base)
        self.n_features = int(n_features)

    def predict_values(self, X: np.ndarray) -> np.ndarray:
        leaf_idx = np.asarray(_predict_leaves(
            jnp.asarray(X), jnp.asarray(self.feats),
            jnp.asarray(self.thrs), self.depth))
        vals = self.leaves[np.arange(len(self.feats))[:, None], leaf_idx]
        return self.base + np.sum(vals, axis=0)

    def raw_arrays(self, X):
        leaf_idx = _predict_leaves(X, jnp.asarray(self.feats),
                                   jnp.asarray(self.thrs, X.dtype),
                                   self.depth)
        vals = jnp.asarray(self.leaves, X.dtype)[
            jnp.arange(len(self.feats))[:, None], leaf_idx]
        return self.base + jnp.sum(vals, axis=0)

    @property
    def feature_importances(self) -> np.ndarray:
        return _split_count_importances(self.feats, self.thrs, self.n_features)


def _split_count_importances(feats: np.ndarray, thrs: np.ndarray,
                             n_features: int) -> np.ndarray:
    """Normalized real-split counts per feature, aligned with the training
    feature columns (a threshold of +inf marks a dead/no-split node)."""
    real = np.isfinite(thrs)
    if feats.size == 0 or not real.any():
        return np.zeros(n_features)
    counts = np.bincount(feats[real].ravel(),
                         minlength=n_features).astype(np.float64)
    total = counts.sum()
    return counts / total if total > 0 else counts


def batch_predict_raw(models, X) -> dict:
    """Batched validator evaluation: raw predictions for every tree-
    family model in ``models`` (list entries of other families are
    skipped), grouped by static shape so each group is one XLA call.

    Returns {index in models: raw ndarray} matching each model's own
    ``predict_raw``/``predict_values`` contract, to be fed through its
    ``prediction_from_raw``.
    """
    groups: Dict[tuple, list] = {}
    for i, m in enumerate(models):
        if isinstance(m, (TreeEnsembleClassifierModel,
                          TreeEnsembleRegressorModel)):
            key = ("forest", m.depth, m.feats.shape, m.leaves.shape)
        elif isinstance(m, (GBTClassifierModel, GBTRegressorModel)):
            key = ("gbt", m.depth, m.feats.shape, m.leaves.shape)
        else:
            continue
        groups.setdefault(key, []).append(i)
    out: dict = {}
    if not groups:          # no tree-family models: no device transfer
        return out
    X_j = jnp.asarray(np.asarray(X, dtype=np.float64))
    for (kind, depth, _, _), idxs in groups.items():
        feats = jnp.asarray(np.stack([models[i].feats for i in idxs]))
        thrs = jnp.asarray(np.stack([models[i].thrs for i in idxs]))
        leaves = jnp.asarray(np.stack([models[i].leaves for i in idxs]))
        bases = jnp.asarray(np.array(
            [getattr(models[i], "base", 0.0) for i in idxs]))
        res = np.asarray(_batched_tree_raw(
            X_j, feats, thrs, leaves, bases, depth=depth, kind=kind))
        for j, i in enumerate(idxs):
            r = res[j]
            if isinstance(models[i], GBTClassifierModel):
                r = models[i].raw_from_margin(r)
            out[i] = r
    return out
