"""Root mean squared error, NumPy float64: the regression selector's default
metric (Spark ``RegressionEvaluator`` ``rmse``), smaller is better. Nothing
here is the package's evaluator."""
from __future__ import annotations

import numpy as np


def rmse(y: np.ndarray, predicted: np.ndarray) -> float:
    error = np.asarray(predicted, np.float64) - np.asarray(y, np.float64)
    return float(np.sqrt(np.mean(error * error)))
