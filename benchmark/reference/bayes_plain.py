"""Plain multinomial naive Bayes, NumPy float64: the reference the multiclass
pool cell's ``NaiveBayes`` lane is decided against (``covtype_mc_pool.search``).

MLlib's multinomial model with additive smoothing, written from its formulas
with no JAX and nothing of the package. With row weights ``w`` (1 on a fold's
training rows, 0 elsewhere), ``N_c = sum_i w_i [y_i = c]`` and ``T_cj = sum_i
w_i [y_i = c] x_ij`` for non-negative features ``x``:

- prior ``pi_c = log N_c - log sum_c N_c`` (MLlib smooths the prior too; the
  package does not, and the reference follows the package: at smoothing 1 and
  hundreds of rows in the rarest class the two differ in the fourth digit of
  a log);
- ``theta_cj = log(T_cj + s) - log(sum_j T_cj + s d)`` over the ``d`` columns;
- a row's score for class ``c`` is ``pi_c + sum_j x_ij theta_cj``, its
  predicted class the argmax.

A closed form: the system's float32 sums differ from these only by rounding,
which is why the lane's tolerance is tight (configs/covtype_mc_pool.json).
"""
from __future__ import annotations

import numpy as np


class PlainNaiveBayes:
    def __init__(self, smoothing: float = 1.0, dtype=np.float64):
        self.smoothing = smoothing
        self.dtype = np.dtype(dtype)

    def fit(self, X: np.ndarray, y: np.ndarray, mask: np.ndarray = None
            ) -> "PlainNaiveBayes":
        X = np.asarray(X, self.dtype)
        if (X < 0).any():
            raise ValueError("multinomial naive Bayes needs non-negative "
                             "features")
        y = np.asarray(y).astype(np.int64)
        w = (np.ones(len(y), self.dtype) if mask is None
             else np.asarray(mask, self.dtype))
        onehot = (np.eye(int(y.max()) + 1, dtype=self.dtype)[y]
                  * w[:, None])                              # (n, classes)
        counts = onehot.sum(axis=0)
        sums = onehot.T @ X                                   # (classes, d)
        s = self.dtype.type(self.smoothing)
        self.pi = np.log(counts) - np.log(counts.sum())
        self.theta = (np.log(sums + s) - np.log(
            sums.sum(axis=1, keepdims=True) + s * X.shape[1]))
        return self

    def scores(self, X: np.ndarray) -> np.ndarray:
        """(rows, classes) log-joint scores."""
        return self.pi + np.asarray(X, self.dtype) @ self.theta.T
