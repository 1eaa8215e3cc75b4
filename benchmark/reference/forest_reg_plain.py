"""Plain random forest for regression, NumPy float64: the reference the
regression pool cell's forest lane is decided against (``msd_reg_pool.search``).

``forest_plain.PlainForest``'s bagging, pools and draws (imported, not copied:
Poisson row weights times the fold mask, a per-tree pool stratified over
narrow and wide columns, a per-node subset within it, ``numpy.random
.default_rng(seed)``) around ``tree_reg_plain.grow``. What differs from the
classifier: a node samples ``auto`` = a THIRD of the ``d`` columns (MLlib's
rule for regression), whose pool of four times that is every column from 8
columns on, so a regression forest of the default pool has no pool; a leaf's
value is its weighted mean label and the forest's prediction the mean over
its trees. With ``bootstrap=False`` (the single tree) nothing is drawn and the
trees must agree with the system's split for split.
"""
from __future__ import annotations

from typing import Optional

import numpy as np

from benchmark.reference.forest_plain import NARROW_WIDTH, pool_sizes
from benchmark.reference.gbt_plain import bin_edges
from benchmark.reference.tree_reg_plain import grow, walk


def subset_size(strategy: str, d: int) -> Optional[int]:
    """Features a node of a REGRESSION tree samples; None for all."""
    if strategy == "all":
        return None
    if strategy in ("onethird", "auto"):        # auto = a third, regression
        return max(1, d // 3)
    if strategy == "sqrt":
        return max(1, int(np.sqrt(d)))
    raise ValueError(f"feature_subset_strategy {strategy!r}")


class PlainForestRegressor:
    """``fit(X, y)`` then ``predict(X)``; every parameter is the system
    estimator's of the same name."""

    def __init__(self, num_trees: int = 20, max_depth: int = 5,
                 max_bins: int = 32, min_instances_per_node: float = 1,
                 min_info_gain: float = 0.0, subsampling_rate: float = 1.0,
                 feature_subset_strategy: str = "auto",
                 bootstrap: bool = True, seed: int = 42,
                 node_cap: int = 256,
                 round_stats: Optional[str] = None):
        self.num_trees, self.max_depth = num_trees, max_depth
        self.max_bins = max_bins
        self.min_instances_per_node = float(min_instances_per_node)
        self.min_info_gain = min_info_gain
        self.subsampling_rate = subsampling_rate
        self.feature_subset_strategy = feature_subset_strategy
        self.bootstrap, self.seed, self.node_cap = bootstrap, seed, node_cap
        self.round_stats = round_stats

    def fit(self, X: np.ndarray, y: np.ndarray,
            mask: np.ndarray = None) -> "PlainForestRegressor":
        X = np.asarray(X, np.float64)
        y = np.asarray(y, np.float64)
        n, d = X.shape
        mask = np.ones(n) if mask is None else np.asarray(mask, np.float64)
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
            self.trees.append(grow(
                binned[:, pool], [edges[f] for f in pool], pool, weight, y,
                max_depth=self.max_depth,
                min_instances_per_node=self.min_instances_per_node,
                min_info_gain=self.min_info_gain, node_cap=self.node_cap,
                per_node=None if m is None else min(m, len(pool)), rng=rng,
                round_stats=self.round_stats))
        return self

    def predict(self, X: np.ndarray) -> np.ndarray:
        """(rows,): the mean over the trees of each row's leaf value."""
        X = np.asarray(X, np.float64)
        return np.mean([walk(X, *tree) for tree in self.trees], axis=0)
