"""Plain histogram gradient boosting for regression, NumPy float64: the
reference the regression pool cell's boosted lane is decided against
(``msd_reg_pool.search``). Beside ``gbt_plain.py``, whose binning and level
step it follows and whose ``PlainGBT`` it subclasses: squared loss from the
fold's weighted mean label (the base margin), per round ``g = margin - y``
and ``h = 1`` under the fold mask, the same second-order gain, node cap and
leaf value ``-step_size * G / (H + lambda)``; the prediction is the margin.
"""
from __future__ import annotations

import numpy as np

from benchmark.reference.gbt_plain import PlainGBT, bin_edges


class PlainGBTRegressor(PlainGBT):
    """``fit(X, y)`` then ``predict(X)``; the constructor and its
    parameters are ``PlainGBT``'s. The rounds are written out again for the
    squared loss, with a level's histogram as one ``bincount`` over all
    columns (the classifier's loops over them: 180 columns a level here)."""

    def fit(self, X, y, mask=None):
        X = np.asarray(X, np.float64)
        y = np.asarray(y, np.float64)
        n, d = X.shape
        mask = np.ones(n) if mask is None else np.asarray(mask, np.float64)
        edges = [bin_edges(X[:, f], self.max_bins) for f in range(d)]
        bins = [np.searchsorted(edges[f], X[:, f], side="left")
                for f in range(d)]
        binned = np.stack(bins, axis=1)
        widths = np.asarray([len(e) + 1 for e in edges])
        start = np.concatenate([[0], np.cumsum(widths)])
        total = int(start[-1])
        feat_of = np.repeat(np.arange(d), widths)
        thr_of = np.concatenate([np.append(e, np.inf) for e in edges])
        first_of = start[feat_of]
        packed = binned + start[:-1][None, :]
        self.base = float((mask * y).sum() / max(mask.sum(), 1.0))
        margin = np.full(n, self.base)
        self.trees = []
        lam = self.reg_lambda
        held_rows = np.nonzero(mask > 0)[0]
        # a column with one occupied bin (a constant null indicator) splits
        # nowhere: its bins stay empty, and an empty side is no valid split
        live = np.nonzero(binned.min(axis=0) != binned.max(axis=0))[0]
        for _ in range(self.num_rounds):
            g, h = mask * (margin - y), mask * np.ones(n)
            node = np.zeros(n, np.int64)
            feats, thrs = [], []
            for level in range(self.max_depth):
                nodes = 2 ** level
                cell = (node[held_rows, None] * total
                        + packed[held_rows][:, live]).ravel()
                G = np.bincount(cell, np.repeat(g[held_rows], len(live)),
                                nodes * total).reshape(nodes, total)
                H = np.bincount(cell, np.repeat(h[held_rows], len(live)),
                                nodes * total).reshape(nodes, total)
                cg, ch = np.cumsum(G, axis=1), np.cumsum(H, axis=1)
                before_g = np.where(first_of > 0,
                                    cg[:, np.maximum(first_of - 1, 0)], 0.0)
                before_h = np.where(first_of > 0,
                                    ch[:, np.maximum(first_of - 1, 0)], 0.0)
                GL, HL = cg - before_g, ch - before_h
                Gt = np.bincount(node, g, nodes)[:, None]
                Ht = np.bincount(node, h, nodes)[:, None]
                GR, HR = Gt - GL, Ht - HL
                with np.errstate(divide="ignore", invalid="ignore"):
                    gain = 0.5 * (GL ** 2 / (HL + lam) + GR ** 2 / (HR + lam)
                                  - Gt ** 2 / (Ht + lam)) - self.gamma
                ok = ((HL >= self.min_child_weight)
                      & (HR >= self.min_child_weight)
                      & np.isfinite(thr_of)[None, :])
                gain = np.where(ok, gain, -np.inf)
                best = np.argmax(gain, axis=1)
                split = gain[np.arange(nodes), best] >= 1e-12
                budget = min(2 * nodes, self.node_cap)
                if level + 1 < self.max_depth and budget < 2 * nodes:
                    held = np.bincount(node, minlength=nodes) > 0
                    split &= held & (np.cumsum(held) - 1
                                     < budget - held.sum())
                feats.append(np.where(split, feat_of[best], 0))
                thrs.append(np.where(split, thr_of[best], np.inf))
                best_bin = np.where(split, best - first_of[best], total)
                left = (binned[np.arange(n), feat_of[best][node]]
                        <= best_bin[node])
                node = 2 * node + (1 - left)
            leaves = 2 ** self.max_depth
            Gl, Hl = np.bincount(node, g, leaves), np.bincount(node, h, leaves)
            value = np.where(np.abs(Gl) + Hl > 0,
                             -self.step_size * Gl / (Hl + lam), 0.0)
            margin = margin + value[node]
            self.trees.append((feats, thrs, value))
        return self

    def predict(self, X: np.ndarray) -> np.ndarray:
        X = np.asarray(X, np.float64)
        rows = np.arange(X.shape[0])
        margin = np.full(X.shape[0], self.base)
        for feats, thrs, value in self.trees:
            node = np.zeros(X.shape[0], np.int64)
            for f, t in zip(feats, thrs):
                node = 2 * node + (X[rows, f[node]] > t[node])
            margin += value[node]
        return margin
