"""Plain histogram gradient boosting, NumPy float64: the reference the fit
cell's ``correct``, and the search cells' sampled lanes, are decided against.

Same semantics as the system's binary GBT (``models/trees.py``), written
straight from its description with no kernels, no batching and no JAX:

- binning: a column with at most two distinct values gets one edge (its
  minimum) and two bins; any other column gets ``min(max_bins, next power of
  two >= distinct values, at least 4)`` bins whose interior edges are the
  de-duplicated quantiles at ``k / bins``; a row's bin is the number of edges
  strictly below its value, so "bin <= b" is "x <= edge[b]";
- boosting: logistic loss, base margin ``logit(mean y)``, per round
  ``g = p - y``, ``h = max(p (1 - p), 1e-12)``, one complete tree of ``depth``
  levels grown level by level from per-(node, feature, bin) sums of g and h;
- split gain ``0.5 (GL^2/(HL+lambda) + GR^2/(HR+lambda) - G^2/(H+lambda)) -
  gamma``, valid only when both children hold ``min_child_weight`` of hessian;
  the best (feature, bin) in feature-then-bin order wins ties; a node splits
  when its best gain is at least 1e-12, else all its rows go left;
- at most ``node_cap`` (256) nodes of a level hold rows: from the first level
  whose successor could hold more (level 8) to the last but one, the nodes
  that hold rows are counted in node order and only the first ``min(2^(level
  + 1), node_cap) - count`` of them may split, each split adding one node;
- leaf value ``-step_size * G / (H + lambda)``, 0 for a leaf whose sums are 0.

Departures from the system: float64 throughout (the chip runs float32 storage
with bf16-pass matmuls) and quantiles by ``np.quantile`` in float64 (the chip
sorts float32 columns). Both move near-tied splits, which is why the
comparison is a tolerance on hold-out log-loss and not equality of trees.
"""
from __future__ import annotations

import numpy as np


def bin_edges(col: np.ndarray, max_bins: int) -> np.ndarray:
    """Interior edges of one column (ascending, no padding)."""
    uniq = np.unique(col)
    if uniq.size <= 2:
        return uniq[:1]
    bins = max(4, min(max_bins, 1 << int(np.ceil(np.log2(uniq.size)))))
    return np.unique(np.quantile(col, np.arange(1, bins) / bins))


class PlainGBT:
    """``fit(X, y)`` then ``predict_proba(X)``; every parameter is the
    system estimator's of the same name."""

    def __init__(self, num_rounds: int, max_depth: int, max_bins: int,
                 step_size: float = 0.1, reg_lambda: float = 1.0,
                 gamma: float = 0.0, min_child_weight: float = 1.0,
                 node_cap: int = 256):
        self.num_rounds, self.max_depth = num_rounds, max_depth
        self.node_cap = node_cap
        self.max_bins, self.step_size = max_bins, step_size
        self.reg_lambda, self.gamma = reg_lambda, gamma
        self.min_child_weight = min_child_weight

    def fit(self, X: np.ndarray, y: np.ndarray,
            mask: np.ndarray = None) -> "PlainGBT":
        """``mask`` (0/1 per row) is how the selector trains a fold: a row
        with 0 is binned, routed and counted among the rows a node holds like
        any other, and adds nothing to a sum."""
        X = np.asarray(X, np.float64)
        y = np.asarray(y, np.float64)
        n, d = X.shape
        mask = np.ones(n) if mask is None else np.asarray(mask, np.float64)
        edges = [bin_edges(X[:, f], self.max_bins) for f in range(d)]
        bins = [np.searchsorted(edges[f], X[:, f], side="left")
                for f in range(d)]
        binned = np.stack(bins, axis=1)
        # the bins of feature f occupy [start[f], start[f+1]) of one flat axis;
        # a feature's last bin holds "above every edge": not a split
        widths = np.asarray([len(e) + 1 for e in edges])
        start = np.concatenate([[0], np.cumsum(widths)])
        total = int(start[-1])
        feat_of = np.repeat(np.arange(d), widths)
        thr_of = np.concatenate([np.append(e, np.inf) for e in edges])
        first_of = start[feat_of]                # first bin of the feature

        p0 = np.clip((mask * y).sum() / max(mask.sum(), 1.0), 1e-6, 1 - 1e-6)
        self.base = float(np.log(p0 / (1 - p0)))
        margin = np.full(n, self.base)
        self.trees = []
        lam = self.reg_lambda
        for _ in range(self.num_rounds):
            p = 1.0 / (1.0 + np.exp(-margin))
            g, h = mask * (p - y), mask * np.maximum(p * (1 - p), 1e-12)
            node = np.zeros(n, np.int64)
            feats, thrs = [], []
            for level in range(self.max_depth):
                nodes = 2 ** level
                G, H = np.zeros((nodes, total)), np.zeros((nodes, total))
                for f in range(d):
                    cell = node * widths[f] + bins[f]
                    block = slice(start[f], start[f + 1])
                    G[:, block] = np.bincount(
                        cell, g, nodes * widths[f]).reshape(nodes, -1)
                    H[:, block] = np.bincount(
                        cell, h, nodes * widths[f]).reshape(nodes, -1)
                # sums over bins <= b of the same feature
                cg, ch = np.cumsum(G, axis=1), np.cumsum(H, axis=1)
                before_g = np.where(first_of > 0, cg[:, first_of - 1], 0.0)
                before_h = np.where(first_of > 0, ch[:, first_of - 1], 0.0)
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
                # a node that does not split sends all its rows left
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

    def predict_proba(self, X: np.ndarray) -> np.ndarray:
        """P(y = 1) per row."""
        X = np.asarray(X, np.float64)
        rows = np.arange(X.shape[0])
        margin = np.full(X.shape[0], self.base)
        for feats, thrs, value in self.trees:
            node = np.zeros(X.shape[0], np.int64)
            for f, t in zip(feats, thrs):
                node = 2 * node + (X[rows, f[node]] > t[node])
            margin += value[node]
        return 1.0 / (1.0 + np.exp(-margin))
