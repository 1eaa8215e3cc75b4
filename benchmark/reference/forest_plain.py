"""Plain random forest for classification, NumPy float64: the reference the
pool cell's forest lane is decided against (``synth100_pool.search``).

Same semantics as the system's ``RandomForestClassifier``
(``models/trees.py``), written straight from its description with no
kernels, no batching and no JAX; binning is ``gbt_plain.bin_edges``, and the
level step (per-(node, bin) sums, running sums inside a feature's block, the
cap on a level's nodes, "no split sends every row left") is that of
``gbt_plain.PlainGBT`` with one sum per class instead of gradient and hessian:

- ``num_trees`` bagged trees. Tree ``t`` weighs row ``i`` by ``Poisson(
  subsampling_rate)`` times the fold mask (MLlib's ``BaggedPoint``
  approximation of sampling with replacement); with ``bootstrap=False`` every
  weight is the mask;
- a per-tree feature POOL, then a per-node subset within it. With
  ``feature_subset_strategy`` ``sqrt`` on ``d`` columns a node samples ``m =
  floor(sqrt(d))`` features; the tree first draws a pool of ``min(d, max(4 m,
  8))`` columns, stratified over the narrow columns (at most 4 bins) and the
  wide ones in proportion to their number, every non-empty class keeping one
  place; each node then takes ``m`` of the pool's columns, uniformly without
  replacement. MLlib samples each node's subset from all ``d`` columns; the
  package documents the pool as its departure (histogram work scales with the
  pooled bins, and fifty pools cover the columns many times over), and the
  reference follows the package. With ``all`` there is no pool and no subset;
- gini gain of a split: ``(I(total) - I(left) - I(right)) / w(total)`` with
  ``I(s) = w - sum_c s_c^2 / w`` over the weighted class sums ``s``, valid only
  when both children weigh at least ``min_instances_per_node``; the best
  (pooled column, bin) in pool-then-bin order wins ties; a node splits when
  its best gain is at least ``max(min_info_gain, 1e-12)``;
- at most ``node_cap`` (256) nodes of a level hold rows, by the rule of
  ``gbt_plain`` (a row of weight 0 is routed and counted like any other);
- a leaf's value is its weighted class shares (uniform for a leaf without
  weight); the forest's score of a row is the mean of its leaves' shares over
  the trees, and P(y = 1) is class 1's part of that mean.

The random draws are this file's own (``numpy.random.default_rng(seed)``), so
it agrees with the system in distribution, not draw for draw: the comparison
is a tolerance on a fold's AuPR set from this reference's own seed-to-seed
spread, except with ``bootstrap=False`` and ``all`` features, where nothing
is drawn and the trees must agree split for split.
"""
from __future__ import annotations

from typing import Optional

import numpy as np

from benchmark.reference.gbt_plain import bin_edges

NARROW_WIDTH = 4


def gini(sums: np.ndarray) -> np.ndarray:
    """Weighted gini impurity ``w - sum_c s_c^2 / w`` of class sums."""
    weight = sums.sum(axis=-1)
    return weight - (sums * sums).sum(axis=-1) / np.maximum(weight, 1e-12)


def subset_size(strategy: str, d: int) -> Optional[int]:
    """Features a node samples; None for all of them."""
    if strategy == "all":
        return None
    if strategy in ("sqrt", "auto"):            # auto = sqrt for a classifier
        return max(1, int(np.sqrt(d)))
    raise ValueError(f"feature_subset_strategy {strategy!r}")


def pool_sizes(widths: np.ndarray, m: Optional[int]):
    """(narrow places, wide places) of a tree's pool, or None when the pool
    would hold every column."""
    d = len(widths)
    if m is None or m >= d:
        return None
    pool = min(d, max(4 * m, 8))
    if pool >= d:
        return None
    narrow = int(np.sum(widths <= NARROW_WIDTH))
    wide = d - narrow
    p_n = min(narrow, int(round(pool * narrow / d)))
    if narrow:
        p_n = max(p_n, 1)
    p_w = min(wide, pool - p_n)
    if wide:
        p_w = max(p_w, 1)
    p_n = min(narrow, max(pool - p_w, 1 if narrow else 0))
    return p_n, p_w


class PlainForest:
    """``fit(X, y)`` then ``predict_proba(X)``; every parameter is the
    system estimator's of the same name."""

    def __init__(self, num_trees: int = 20, max_depth: int = 5,
                 max_bins: int = 32, min_instances_per_node: float = 1,
                 min_info_gain: float = 0.0, subsampling_rate: float = 1.0,
                 feature_subset_strategy: str = "auto",
                 bootstrap: bool = True, seed: int = 42,
                 node_cap: int = 256):
        self.num_trees, self.max_depth = num_trees, max_depth
        self.max_bins = max_bins
        self.min_instances_per_node = float(min_instances_per_node)
        self.min_info_gain = min_info_gain
        self.subsampling_rate = subsampling_rate
        self.feature_subset_strategy = feature_subset_strategy
        self.bootstrap, self.seed, self.node_cap = bootstrap, seed, node_cap

    def fit(self, X: np.ndarray, y: np.ndarray,
            mask: np.ndarray = None) -> "PlainForest":
        X = np.asarray(X, np.float64)
        y = np.asarray(y, np.int64)
        n, d = X.shape
        mask = np.ones(n) if mask is None else np.asarray(mask, np.float64)
        classes = int(y.max()) + 1
        onehot = np.eye(classes)[y]
        edges = [bin_edges(X[:, f], self.max_bins) for f in range(d)]
        binned = np.stack([np.searchsorted(edges[f], X[:, f], side="left")
                           for f in range(d)], axis=1)
        widths = np.asarray([len(e) + 1 for e in edges])
        m = subset_size(self.feature_subset_strategy, d) \
            if self.bootstrap else None
        sizes = pool_sizes(widths, m)
        narrow = np.nonzero(widths <= NARROW_WIDTH)[0]
        wide = np.nonzero(widths > NARROW_WIDTH)[0]
        rng = np.random.default_rng(self.seed)
        self.trees = []
        for _ in range(self.num_trees):
            weight = mask * (rng.poisson(self.subsampling_rate, n)
                             if self.bootstrap else 1.0)
            if sizes is None:
                pool = np.arange(d)
            else:
                pool = np.concatenate(
                    [rng.choice(narrow, sizes[0], replace=False),
                     rng.choice(wide, sizes[1], replace=False)])
            per_node = None if m is None else min(m, len(pool))
            self.trees.append(self._grow(
                binned[:, pool], [edges[f] for f in pool], pool,
                onehot * weight[:, None], per_node, rng))
        return self

    def _grow(self, binned, edges, pool, stats, per_node, rng):
        """One tree on the pooled columns; ``stats`` (n, classes) are the
        weighted class indicators. Returns per-level (feature, threshold)
        arrays in the raw columns' numbering and the leaves' class shares."""
        n, p = binned.shape
        classes = stats.shape[1]
        widths = np.asarray([len(e) + 1 for e in edges])
        start = np.concatenate([[0], np.cumsum(widths)])
        total = int(start[-1])
        col_of = np.repeat(np.arange(p), widths)        # pooled column of a bin
        thr_of = np.concatenate([np.append(e, np.inf) for e in edges])
        first_of = start[col_of]
        packed = binned + start[:-1][None, :]
        heavy = np.nonzero(stats.sum(axis=1) > 0)[0]    # rows that add a sum
        node = np.zeros(n, np.int64)
        feats, thrs = [], []
        for level in range(self.max_depth):
            nodes = 2 ** level
            cell = (node[heavy, None] * total + packed[heavy]).ravel()
            hist = np.stack([np.bincount(
                cell, np.repeat(stats[heavy, c], p), nodes * total
            ).reshape(nodes, total) for c in range(classes)], axis=2)
            running = np.cumsum(hist, axis=1)
            before = np.where((first_of > 0)[None, :, None],
                              running[:, np.maximum(first_of - 1, 0), :], 0.0)
            left = running - before
            whole = np.stack([np.bincount(node, stats[:, c], nodes)
                              for c in range(classes)], axis=1)[:, None, :]
            right = whole - left
            gain = (gini(whole) - gini(left) - gini(right)
                    ) / np.maximum(whole.sum(axis=-1), 1e-12)
            ok = ((left.sum(axis=-1) >= self.min_instances_per_node)
                  & (right.sum(axis=-1) >= self.min_instances_per_node)
                  & np.isfinite(thr_of)[None, :])
            if per_node is not None and per_node < p:
                draw = rng.uniform(size=(nodes, p))
                kth = np.sort(draw, axis=1)[:, per_node - 1:per_node]
                ok &= (draw <= kth)[:, col_of]
            gain = np.where(ok, gain, -np.inf)
            best = np.argmax(gain, axis=1)
            split = gain[np.arange(nodes), best] >= max(self.min_info_gain,
                                                        1e-12)
            budget = min(2 * nodes, self.node_cap)
            if level + 1 < self.max_depth and budget < 2 * nodes:
                held = np.bincount(node, minlength=nodes) > 0
                split &= held & (np.cumsum(held) - 1 < budget - held.sum())
            feats.append(np.where(split, pool[col_of[best]], 0))
            thrs.append(np.where(split, thr_of[best], np.inf))
            best_bin = np.where(split, best - first_of[best], total)
            go_left = (binned[np.arange(n), col_of[best][node]]
                       <= best_bin[node])
            node = 2 * node + (1 - go_left)
        leaves = 2 ** self.max_depth
        sums = np.stack([np.bincount(node, stats[:, c], leaves)
                         for c in range(classes)], axis=1)
        weight = sums.sum(axis=1, keepdims=True)
        shares = np.where(weight > 0, sums / np.maximum(weight, 1e-12),
                          1.0 / classes)
        return feats, thrs, shares

    def votes(self, X: np.ndarray) -> np.ndarray:
        """(rows, classes): the mean over trees of each row's leaf shares."""
        X = np.asarray(X, np.float64)
        rows = np.arange(X.shape[0])
        out = 0.0
        for feats, thrs, shares in self.trees:
            node = np.zeros(X.shape[0], np.int64)
            for f, t in zip(feats, thrs):
                node = 2 * node + (X[rows, f[node]] > t[node])
            out = out + shares[node]
        return out / len(self.trees)

    def predict_proba(self, X: np.ndarray) -> np.ndarray:
        """P(y = 1) per row (binary labels)."""
        votes = self.votes(X)
        total = votes.sum(axis=1)
        return votes[:, 1] / np.where(total > 0, total, 1.0)
