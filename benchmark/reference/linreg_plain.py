"""Plain elastic-net linear regression, NumPy float64: the reference the
regression pool cell's ``LinearRegression`` lanes and winner are decided
against (``msd_reg_pool.search``).

Same objective as the system's (``models/linear.py``
``linear_regression_core``), written straight from its description with no
JAX, no batching and nothing of the package:

- standardization as ``linear_plain.standardize`` (imported): with row weights
  ``w`` (1 on a fold's training rows, 0 elsewhere) every column is centred on
  its weighted mean and divided by its weighted deviation, a constant column
  left unscaled; the label is centred on its weighted mean and NOT scaled
  (MLlib scales it too: the package's ``reg_param`` is in the label's units,
  docs/MIGRATION.md);
- objective over the standardized coefficients ``v``: ``sum_w (Xs v - yc)^2 /
  (2 sum w) + 0.5 reg (1 - a) |v|^2 + reg a |v|_1``; the coefficients handed
  back are ``v / sigma`` with the intercept ``ybar - sum(v mu / sigma)``;
- the MINIMISER (``schedule=None``): proximal gradient with the step ``1 /
  (sigma_max(Xs)^2 / sum w + l2)``, Nesterov momentum restarted whenever the
  objective rises, until an iterate moves by less than ``tol`` (1e-10) in the
  largest coordinate;
- the package's SCHEDULE (``schedule={"steps": 250, "stop": 1e-7}``): the step
  ``1 / (top + l2 + 1e-3)`` with ``top`` the package's estimate of the largest
  eigenvalue of ``Xs^T diag(w) Xs / sum w`` (sixteen normalised products from
  the constant unit vector, then the Rayleigh quotient), plain FISTA from
  zero with no restart, stopped when an iterate moves by less than ``stop`` in
  the Euclidean norm or after ``steps``: what the refit of a winner runs
  (the lanes of a search run all ``steps``).

``dtype="bfloat16"`` is for a control (``benchmark/controls_reg.py``): the
table, the standardized matrix, the centred label, the parameters and every
product are rounded to bfloat16 (``multinomial_plain.to_bfloat16``), sums in
float32: the nearest precision below float32 that holds this table.
"""
from __future__ import annotations

from typing import Dict, Optional

import numpy as np

from benchmark.reference.linear_plain import standardize
from benchmark.reference.multinomial_plain import to_bfloat16


class PlainLinearRegression:
    def __init__(self, reg_param: float = 0.0,
                 elastic_net_param: float = 0.0, tol: float = 1e-10,
                 max_iter: int = 50000, dtype="float64",
                 schedule: Optional[Dict] = None):
        self.reg_param, self.elastic_net_param = reg_param, elastic_net_param
        self.tol, self.max_iter, self.schedule = tol, max_iter, schedule
        self.bfloat16 = str(dtype) == "bfloat16"
        self.dtype = np.dtype(np.float32 if self.bfloat16 else dtype)

    def _r(self, a):
        return to_bfloat16(a) if self.bfloat16 else a

    def fit(self, X: np.ndarray, y: np.ndarray, mask: np.ndarray = None
            ) -> "PlainLinearRegression":
        r, dtype = self._r, self.dtype.type
        X = r(np.asarray(X, self.dtype))
        n, d = X.shape
        w = (np.ones(n, self.dtype) if mask is None
             else np.asarray(mask, self.dtype))
        total = dtype(max(float(w.sum(dtype=np.float64)), 1e-12))
        Xs, mu, sigma = standardize(X, w)
        Xs, mu, sigma = (r(a.astype(self.dtype)) for a in (Xs, mu, sigma))
        y = np.asarray(y, self.dtype)
        ybar = dtype(np.sum(w * y, dtype=np.float64) / float(total))
        yc = r(y - ybar)
        l2 = dtype(self.reg_param * (1.0 - self.elastic_net_param))
        l1 = dtype(self.reg_param * self.elastic_net_param)
        share = r(w / total)

        def gradient(v):
            return r(Xs.T @ r(share * (r(Xs @ v) - yc))) + l2 * v

        def objective(v):
            residual = Xs @ v - yc
            return (0.5 * np.sum(share * residual * residual)
                    + 0.5 * l2 * np.sum(v * v) + l1 * np.sum(np.abs(v)))

        def proximal_step(z, step):
            q = r(z - step * gradient(z))
            return r(np.sign(q) * np.maximum(np.abs(q) - step * l1,
                                             dtype(0.0)))

        v = np.zeros(d, self.dtype)
        z, t = v.copy(), 1.0
        if self.schedule is not None:
            step = dtype(1.0 / (self._power_iteration(Xs, w, total)
                                + float(l2) + 1e-3))
            self.steps = self.schedule["steps"]
            for it in range(1, self.schedule["steps"] + 1):
                q = proximal_step(z, step)
                t_next = (1.0 + float(np.sqrt(1.0 + 4.0 * t * t))) / 2.0
                z = r(q + dtype((t - 1.0) / t_next) * (q - v))
                moved = float(np.sqrt(np.sum((q - v) ** 2, dtype=np.float64)))
                v, t = q, t_next
                if moved < self.schedule["stop"]:
                    self.steps = it
                    break
        else:
            w64 = w.astype(np.float64)
            top = np.linalg.norm(np.sqrt(w64)[:, None]
                                 * Xs.astype(np.float64), 2) ** 2 \
                / max(w64.sum(), 1e-12)
            step = dtype(1.0 / (top + float(l2)))
            last, self.steps = objective(v), self.max_iter
            for it in range(1, self.max_iter + 1):
                q = proximal_step(z, step)
                value = objective(q)
                if value > last and t > 1.0:    # overshot: restart at v
                    z, t = v.copy(), 1.0
                    continue
                t_next = (1.0 + float(np.sqrt(1.0 + 4.0 * t * t))) / 2.0
                z = r(q + dtype((t - 1.0) / t_next) * (q - v))
                moved = float(np.max(np.abs(q - v)))
                v, t, last = q, t_next, value
                if moved < self.tol:
                    self.steps = it
                    break
        self.mu, self.sigma = mu, sigma
        self.coefficients = v / sigma
        self.intercept = float(ybar - self.coefficients @ mu)
        return self

    def _power_iteration(self, Xs, w, total, iterations: int = 16) -> float:
        r, d = self._r, Xs.shape[1]

        def product(u):
            return r(Xs.T @ r(w * r(Xs @ u))) / total

        u = np.full(d, 1.0 / np.sqrt(d), self.dtype)
        for _ in range(iterations):
            p = product(u)
            u = r(p / (np.sqrt(np.sum(p * p)) + self.dtype.type(1e-12)))
        return float(np.vdot(u, product(u)))

    def predict(self, X: np.ndarray) -> np.ndarray:
        """Predicted labels, in float64 whatever the fit's precision: the
        score is the metric's, not the fit's."""
        return (np.asarray(X, np.float64)
                @ np.asarray(self.coefficients, np.float64) + self.intercept)
