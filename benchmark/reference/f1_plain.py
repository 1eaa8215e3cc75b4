"""Plain NumPy weighted F1, as the reference's multiclass evaluator (Spark
``MulticlassMetrics.weightedFMeasure``) defines it, which is the multiclass
selector's metric; nothing here is the package's evaluator.

For every class ``c`` that occurs among the labels: precision ``tp / (tp +
fp)`` and recall ``tp / (tp + fn)`` of "predicted ``c``" against "is ``c``"
(0 where the denominator is 0), ``F1 = 2 p r / (p + r)`` (0 where both are 0),
weighted by the class's share of the LABELS. A class that is predicted but
never occurs among the labels has no weight; its predictions count as the
other classes' misses. The predicted class of a row is the argmax of its
scores, the FIRST index on ties.
"""
from __future__ import annotations

import numpy as np


def predicted_class(scores: np.ndarray) -> np.ndarray:
    """Argmax over the classes of a (rows, classes) score matrix, the first
    index on ties (``numpy.argmax``'s rule, stated because it is part of the
    metric)."""
    return np.argmax(np.asarray(scores), axis=1)


def weighted_f1(y: np.ndarray, predicted: np.ndarray) -> float:
    y = np.asarray(y).astype(np.int64)
    predicted = np.asarray(predicted).astype(np.int64)
    total = 0.0
    for c in np.unique(y):
        tp = float(np.sum((predicted == c) & (y == c)))
        fp = float(np.sum((predicted == c) & (y != c)))
        fn = float(np.sum((predicted != c) & (y == c)))
        p = tp / (tp + fp) if tp + fp > 0 else 0.0
        r = tp / (tp + fn) if tp + fn > 0 else 0.0
        f = 2.0 * p * r / (p + r) if p + r > 0 else 0.0
        total += f * float(np.sum(y == c)) / max(len(y), 1)
    return total
