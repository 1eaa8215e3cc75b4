"""Plain NumPy metrics the benchmark decides ``correct`` with; nothing here
is the package's evaluator."""
from __future__ import annotations

import numpy as np


def log_loss(y: np.ndarray, p: np.ndarray) -> float:
    p = np.clip(np.asarray(p, np.float64), 1e-12, 1 - 1e-12)
    y = np.asarray(y, np.float64)
    return float(-np.mean(y * np.log(p) + (1 - y) * np.log(1 - p)))


def aupr(y: np.ndarray, score: np.ndarray) -> float:
    """Area under the precision-recall curve as the reference's evaluator
    (Spark ``BinaryClassificationMetrics.pr``) defines it, which is the
    selector's AuPR: one (recall, precision) point per distinct score,
    highest first, tied scores entering together; the point (0, first
    precision) in front; trapezoids between the points."""
    y = np.asarray(y, np.float64)
    order = np.argsort(-np.asarray(score, np.float64), kind="stable")
    s, y = np.asarray(score, np.float64)[order], y[order]
    last_of_tie = np.append(s[1:] != s[:-1], True)
    tp = np.cumsum(y)[last_of_tie]
    seen = np.nonzero(last_of_tie)[0] + 1.0
    recall = np.append(0.0, tp / max(y.sum(), 1.0))
    precision = tp / seen
    precision = np.append(precision[:1], precision)
    return float(np.sum(np.diff(recall)
                        * (precision[1:] + precision[:-1]) / 2.0))
