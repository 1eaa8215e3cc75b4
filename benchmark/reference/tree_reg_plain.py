"""Plain regression tree by level histograms, NumPy float64: the level step
the regression pool cell's forest lane, and the package's single regression
tree on the CPU, are decided against (``msd_reg_pool.search``,
``tests/test_regression_pool.py``).

Same semantics as the system's regression trees (``models/trees.py``
``_grow_tree`` under ``_variance_gain``), written straight from their
description with no kernels, no batching and no JAX; binning is
``gbt_plain.bin_edges`` and the level step (per-(node, bin) sums, running sums
inside a column's block, the cap on a level's nodes, "no split sends every row
left") is ``forest_plain.PlainForest._grow``'s with MLlib's variance impurity
in place of gini:

- per-row statistics ``[w, w y, w y^2]`` with the label AS IT CAME (nothing is
  centred here: float64 does not need it, and the comparison is what shows
  that the system, which centres, is shift-invariant);
- gain of a split: ``(SSE(total) - SSE(left) - SSE(right)) / w(total)`` with
  ``SSE(s) = s_2 - s_1^2 / s_0``, valid only when both children weigh at least
  ``min_instances_per_node``; the best (column, bin) in column-then-bin order
  wins ties; a node splits when its best gain is at least ``max(min_info_gain,
  1e-12)``;
- at most ``node_cap`` (256) nodes of a level hold rows, by the rule of
  ``gbt_plain`` (a row of weight 0 is routed and counted like any other);
- a leaf's value is its weighted mean label (the weighted mean of the whole
  tree's rows for a leaf without weight, which no row of the table reaches).

``round_stats`` is for a control (``benchmark/controls_reg.py``): the precision
(``"bfloat16"``, or a NumPy dtype's name) the three statistic columns are
rounded to before the level HISTOGRAMS are formed, which is what the chip's
contraction did to the
package's uncentred statistics before PR 34. A node's whole sums and the
leaves' values are formed from the statistics as they are, as the package's
were (float32-exact): ``right = whole - left`` then mixes the two, and that is
the fault.
"""
from __future__ import annotations

from typing import List, Optional

import numpy as np

from benchmark.reference.multinomial_plain import to_bfloat16


def sse(sums: np.ndarray) -> np.ndarray:
    """Weighted sum of squared deviations from the mean, of ``[w, wy, wyy]``
    sums."""
    return sums[..., 2] - sums[..., 1] ** 2 / np.maximum(sums[..., 0], 1e-12)


def grow(binned: np.ndarray, edges: List[np.ndarray], pool: np.ndarray,
         weight: np.ndarray, y: np.ndarray, *, max_depth: int,
         min_instances_per_node: float, min_info_gain: float,
         node_cap: int = 256, per_node: Optional[int] = None, rng=None,
         round_stats: Optional[str] = None):
    """One tree on the pooled columns ``binned`` (n, p) with their ``edges``;
    ``pool`` gives the raw column of each. Returns per-level (feature,
    threshold) arrays in the raw columns' numbering and the leaves' values."""
    n, p = binned.shape
    stats = np.stack([weight, weight * y, weight * y * y], axis=1)
    binned_stats = stats                    # what the histograms add up
    if round_stats is not None:
        binned_stats = np.asarray(
            to_bfloat16(stats) if round_stats == "bfloat16"
            else stats.astype(round_stats), np.float64)
    widths = np.asarray([len(e) + 1 for e in edges])
    start = np.concatenate([[0], np.cumsum(widths)])
    total = int(start[-1])
    col_of = np.repeat(np.arange(p), widths)        # pooled column of a bin
    thr_of = np.concatenate([np.append(e, np.inf) for e in edges])
    first_of = start[col_of]
    packed = binned + start[:-1][None, :]
    heavy = np.nonzero(stats[:, 0] > 0)[0]          # rows that add a sum
    # a column with one occupied bin (a constant null indicator) holds every
    # node's whole sums in that bin: written there, not counted row by row
    flat = binned.min(axis=0) == binned.max(axis=0)
    live = np.nonzero(~flat)[0]
    flat_bin = start[:-1][flat] + binned[0, flat]
    node = np.zeros(n, np.int64)
    feats, thrs = [], []
    for level in range(max_depth):
        nodes = 2 ** level
        whole = np.stack([np.bincount(node, stats[:, c], nodes)
                          for c in range(3)], axis=1)[:, None, :]
        cell = (node[heavy, None] * total + packed[heavy][:, live]).ravel()
        hist = np.stack([np.bincount(
            cell, np.repeat(binned_stats[heavy, c], len(live)), nodes * total
        ).reshape(nodes, total) for c in range(3)], axis=2)
        hist[:, flat_bin, :] = whole
        running = np.cumsum(hist, axis=1)
        before = np.where((first_of > 0)[None, :, None],
                          running[:, np.maximum(first_of - 1, 0), :], 0.0)
        left = running - before
        right = whole - left
        gain = (sse(whole) - sse(left) - sse(right)
                ) / np.maximum(whole[..., 0], 1e-12)
        ok = ((left[..., 0] >= min_instances_per_node)
              & (right[..., 0] >= min_instances_per_node)
              & np.isfinite(thr_of)[None, :])
        if per_node is not None and per_node < p:
            draw = rng.uniform(size=(nodes, p))
            kth = np.sort(draw, axis=1)[:, per_node - 1:per_node]
            ok &= (draw <= kth)[:, col_of]
        gain = np.where(ok, gain, -np.inf)
        best = np.argmax(gain, axis=1)
        split = gain[np.arange(nodes), best] >= max(min_info_gain, 1e-12)
        budget = min(2 * nodes, node_cap)
        if level + 1 < max_depth and budget < 2 * nodes:
            held = np.bincount(node, minlength=nodes) > 0
            split &= held & (np.cumsum(held) - 1 < budget - held.sum())
        feats.append(np.where(split, pool[col_of[best]], 0))
        thrs.append(np.where(split, thr_of[best], np.inf))
        best_bin = np.where(split, best - first_of[best], total)
        go_left = (binned[np.arange(n), col_of[best][node]]
                   <= best_bin[node])
        node = 2 * node + (1 - go_left)
    leaves = 2 ** max_depth
    sums = np.stack([np.bincount(node, stats[:, c], leaves)
                     for c in range(2)], axis=1)
    everywhere = stats[:, 1].sum() / max(stats[:, 0].sum(), 1e-12)
    values = np.where(sums[:, 0] > 0,
                      sums[:, 1] / np.maximum(sums[:, 0], 1e-12), everywhere)
    return feats, thrs, values


def walk(X: np.ndarray, feats, thrs, values) -> np.ndarray:
    """Each row's leaf value: raw ``X`` down the finished tree."""
    rows = np.arange(X.shape[0])
    node = np.zeros(X.shape[0], np.int64)
    for f, t in zip(feats, thrs):
        node = 2 * node + (X[rows, f[node]] > t[node])
    return values[node]
