"""Plain logistic regression and linear SVC, NumPy float64: the reference the
pool cell's linear lanes are decided against (``synth100_pool.search``).

Same objectives as the system's linear family (``models/linear.py``), written
straight from their description with no JAX, no batching and nothing of the
package:

- standardization: with row weights ``w`` (1 on a fold's training rows, 0
  elsewhere), every column is centred on its weighted mean and divided by its
  weighted standard deviation (the population form, ``sum w (x - mu)^2 / sum
  w``); a constant column (deviation at most ``1e-9 max(|mu|, 1)``) is centred
  and left unscaled. The penalty applies to the standardized coefficients;
  the coefficients handed back are mapped to the raw columns (``w / sigma``,
  ``b - sum(w mu / sigma)``);
- logistic: ``mean_w log(1 + exp(-s (Xs w + b))) + 0.5 reg (1 - a) |w|^2 +
  reg a |w|_1`` with ``s = 2 y - 1``, the intercept unpenalised;
- SVC: ``mean_w max(0, 1 - s (Xs w + b))^2 + 0.5 reg |w|^2``. This is the
  package's documented departure from MLlib's ``LinearSVC`` (hinge loss by
  OWL-QN): the squared hinge is smooth, and the package states that the
  decision boundaries are near-identical. The reference follows the package,
  not MLlib, because it is the package's objective that the cell checks;
- solver: proximal gradient (soft threshold for the L1 term) with the fixed
  step ``1 / L``, ``L = c sigma_max([Xs, 1])^2 / sum w + l2`` from the
  design's largest singular value (``c`` = 1/4 for the logistic loss, 2 for
  the squared hinge), Nesterov momentum restarted whenever the objective
  rises, run until an iterate moves by less than ``tol`` (1e-9) in the largest
  coordinate. The system stops after a fixed 250 accelerated steps in float32
  instead, so it is held to this reference by a tolerance, not by equality.

``dtype`` is the precision everything is computed in, float64 unless a control
asks for less: with ``numpy.float16`` the table, the standardization, every
product and every sum are float16 (the one exception is the solver's step
size, a constant of the method and not of the objective, taken from the
float64 design because NumPy has no float16 singular values). That is the
control the pool cell's limits are set against (``benchmark/controls_pool.py``):
a limit this reference passes in float16 does not tell float32 from less.
"""
from __future__ import annotations

from typing import Tuple

import numpy as np


def standardize(X: np.ndarray, w: np.ndarray
                ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(Xs, mu, sigma): weighted standardization on the rows ``w`` marks;
    ``sigma`` is 1 for a constant column."""
    total = max(w.sum(), 1e-12)
    mu = (w[:, None] * X).sum(axis=0) / total
    sigma = np.sqrt((w[:, None] * (X - mu) ** 2).sum(axis=0) / total)
    sigma = np.where(sigma > 1e-9 * np.maximum(np.abs(mu), 1.0), sigma, 1.0)
    return (X - mu) / sigma, mu, sigma


def _minimise(loss_grad, curvature: float, Xs: np.ndarray, w: np.ndarray,
              l2: float, l1: float, tol: float, max_iter: int
              ) -> Tuple[np.ndarray, int]:
    """Proximal gradient on ``[coefficients, intercept]``; returns the
    parameters and the steps taken."""
    n, d = Xs.shape
    dtype = Xs.dtype.type
    w64 = w.astype(np.float64)
    design = np.sqrt(w64)[:, None] * np.concatenate(
        [Xs.astype(np.float64), np.ones((n, 1))], axis=1)
    top = np.linalg.norm(design, 2) ** 2 / max(w64.sum(), 1e-12)
    step = dtype(1.0 / (curvature * top + l2))
    penalised = np.concatenate([np.ones(d, dtype), np.zeros(1, dtype)])

    def objective(p):
        value, _ = loss_grad(p)
        return (value + 0.5 * l2 * np.sum(p[:d] ** 2)
                + l1 * np.sum(np.abs(p[:d])))

    p = np.zeros(d + 1, dtype)
    z, t, last = p.copy(), 1.0, objective(p)
    for it in range(1, max_iter + 1):
        _, grad = loss_grad(z)
        grad = grad + l2 * penalised * z
        q = z - step * grad
        q = np.where(penalised > 0,
                     np.sign(q) * np.maximum(np.abs(q) - step * l1, 0.0), q)
        value = objective(q)
        if value > last and t > 1.0:     # momentum overshot: restart from p
            z, t = p.copy(), 1.0
            continue
        t_next = (1.0 + float(np.sqrt(1.0 + 4.0 * t * t))) / 2.0
        z = q + (t - 1.0) / t_next * (q - p)
        moved = np.max(np.abs(q - p))
        p, t, last = q, t_next, value
        if moved < tol:
            return p, it
    return p, max_iter


class _PlainLinear:
    curvature = 1.0

    def __init__(self, reg_param: float = 0.0,
                 elastic_net_param: float = 0.0, standardization: bool = True,
                 tol: float = 1e-9, max_iter: int = 50000,
                 dtype=np.float64):
        self.reg_param, self.elastic_net_param = reg_param, elastic_net_param
        self.standardization = standardization
        self.tol, self.max_iter = tol, max_iter
        self.dtype = np.dtype(dtype)

    def _loss_grad(self, Xs, s, w, total):
        raise NotImplementedError

    def fit(self, X: np.ndarray, y: np.ndarray, mask: np.ndarray = None):
        """``mask`` (0/1 per row) is how the selector trains a fold: a row
        with 0 adds nothing to the standardization or to the loss."""
        X = np.asarray(X, self.dtype)
        y = np.asarray(y, self.dtype)
        n, d = X.shape
        w = (np.ones(n, self.dtype) if mask is None
             else np.asarray(mask, self.dtype))
        if self.standardization:
            Xs, mu, sigma = standardize(X, w)
        else:
            Xs, mu, sigma = X, np.zeros(d, self.dtype), np.ones(d, self.dtype)
        l2 = self.reg_param * (1.0 - self.elastic_net_param)
        l1 = self.reg_param * self.elastic_net_param
        p, self.steps = _minimise(
            self._loss_grad(Xs, 2.0 * y - 1.0, w, max(w.sum(), 1e-12)),
            self.curvature, Xs, w, l2, l1, self.tol, self.max_iter)
        assert p.dtype == Xs.dtype == self.dtype    # nothing was widened
        self.coefficients = p[:d] / sigma
        self.intercept = float(p[d] - self.coefficients @ mu)
        return self

    def decision(self, X: np.ndarray) -> np.ndarray:
        """The margin ``x . w + b`` per row: what either family is ranked
        by (the logistic probability is monotone in it)."""
        return (np.asarray(X, self.dtype) @ self.coefficients
                + self.coefficients.dtype.type(self.intercept))


class PlainLogistic(_PlainLinear):
    """Binomial logistic regression with the elastic-net penalty."""
    curvature = 0.25

    def _loss_grad(self, Xs, s, w, total):
        def loss_grad(p):
            m = s * (Xs @ p[:-1] + p[-1])
            value = np.sum(w * np.logaddexp(0.0, -m)) / total
            slope = -s * w * np.exp(-np.logaddexp(0.0, m)) / total
            return value, np.append(Xs.T @ slope, slope.sum())
        return loss_grad

    def predict_proba(self, X: np.ndarray) -> np.ndarray:
        return 1.0 / (1.0 + np.exp(-self.decision(X)))


class PlainSVC(_PlainLinear):
    """L2-regularised squared-hinge classifier (no L1 term: the system's
    ``LinearSVC`` has none)."""
    curvature = 2.0

    def __init__(self, reg_param: float = 0.0, standardization: bool = True,
                 tol: float = 1e-9, max_iter: int = 50000,
                 dtype=np.float64):
        super().__init__(reg_param, 0.0, standardization, tol, max_iter,
                         dtype)

    def _loss_grad(self, Xs, s, w, total):
        def loss_grad(p):
            violation = np.maximum(0.0, 1.0 - s * (Xs @ p[:-1] + p[-1]))
            value = np.sum(w * violation ** 2) / total
            slope = -2.0 * s * w * violation / total
            return value, np.append(Xs.T @ slope, slope.sum())
        return loss_grad
