"""The selector's cross-validation folds, written straight from their
description: the rule the search cells' ``correct`` holds the system to.

``k`` folds of exactly equal size, stratified by class: one NumPy
``default_rng(seed)``; for each class in ascending order, its row indices are
permuted, the ``len % k`` last of the permutation are dropped, and the rest go
to folds ``0, 1, ..., k-1, 0, 1, ...`` in permuted order. Fold ``f`` validates
on its own rows and trains on every other kept row.
"""
from __future__ import annotations

import numpy as np


def stratified_folds(y: np.ndarray, k: int, seed: int) -> np.ndarray:
    """Fold of each row, -1 for a dropped row."""
    rng = np.random.default_rng(seed)
    fold = np.full(len(y), -1, np.int64)
    for cls in np.unique(y):
        rows = rng.permutation(np.nonzero(y == cls)[0])
        kept = len(rows) // k * k
        fold[rows[:kept]] = np.arange(kept) % k
    return fold
