"""Plain multinomial (softmax) logistic regression, NumPy float64: the
reference the multiclass pool cell's logistic lanes and its winner's
coefficients are decided against (``covtype_mc_pool.search``).

The same objective as the system's ``multinomial_logistic_core``
(``models/linear.py``), written from its description with no JAX, no batching
and nothing of the package, over ``k`` classes with parameters ``W`` (k, d)
and ``b`` (k,):

- standardization: with row weights ``w`` (1 on a fold's training rows, 0
  elsewhere) every column is centred on its weighted mean and divided by its
  weighted population deviation; a constant column (deviation at most ``1e-9
  max(|mu|, 1)``) is centred and left unscaled. The penalty applies to the
  standardized coefficients; what is handed back is mapped to the raw columns
  (``W / sigma``, ``b - (W / sigma) mu``);
- ``-mean_w log softmax(Xs W^T + b)[y] + 0.5 reg (1 - a) |W|^2 + reg a |W|_1``,
  the intercepts unpenalised. With ``reg (1 - a) > 0`` the minimiser is unique:
  the penalty fixes the one direction the data leave free, a shift common to
  all rows of ``W``; the gradient in ``b`` sums to zero over the classes, so
  ``sum_c b_c`` stays 0 from the zero start every solver here takes.
  Coefficients are compared as they are, with no centring.

Two solvers, because the system is two things at once, a minimiser of that
objective and a fixed number of float32 steps towards it:

- ``schedule=None``, **to convergence**: proximal gradient with the fixed step
  ``1 / L``, ``L = sigma_max([Xs, 1])^2 / (2 sum w) + l2`` (the softmax Hessian
  in the logits is at most 1/2), Nesterov momentum restarted whenever the
  objective rises, until an iterate moves by less than ``tol`` in the largest
  coordinate: the minimiser, which the lanes' validation scores are held to;
- ``schedule={"steps": n, "stop": t}``, **the package's documented schedule**
  (``models/solvers.py``): the step ``1 / (L16 / 2 + l2 + 1/2)`` with ``L16``
  sixteen power iterations on ``Xs^T diag(w) Xs / sum w`` from the constant
  vector, then plain FISTA (no restart) for at most ``n`` steps, stopping
  early once an iterate moves by less than ``t`` in Euclidean norm (``t = 0``:
  exactly ``n`` steps, the fold-grid lanes; ``t = 1e-7``: the winner's refit).
  At the cell's least regularised grid points 250 such steps stop a fifth of a
  standardized unit short of the minimiser (PERF.md, PR 32), so what tells
  float32 from less is the system against THIS solver in float64: the same
  steps, where only the arithmetic differs.

``dtype`` is the precision everything is computed in, float64 unless a control
asks for less. ``"bfloat16"`` is the nearest precision below the
configuration's float32 that can hold this table (float16 cannot: 98,304 rows
and a squared elevation pass its largest number, 65,504) and the one the chip
would use if left to its default: the table, the standardized matrix, the
parameters, every product, quotient and exponential are rounded to bfloat16
(round to nearest even on a float32's upper 16 bits) and sums are carried in
float32, as the chip's multiplier does. That is the control the cell's
coefficient limit is set against (``benchmark/controls_mc.py``).
"""
from __future__ import annotations

from typing import Dict, Optional

import numpy as np


def to_bfloat16(a) -> np.ndarray:
    """``a`` rounded to bfloat16 (nearest, ties to even), held as float32."""
    a = np.ascontiguousarray(a, np.float32)
    bits = a.reshape(-1).view(np.uint32)
    bits = (bits + np.uint32(0x7FFF) + ((bits >> np.uint32(16))
                                        & np.uint32(1))) & np.uint32(0xFFFF0000)
    return bits.view(np.float32).reshape(a.shape)


class PlainMultinomial:
    def __init__(self, reg_param: float = 0.0,
                 elastic_net_param: float = 0.0, standardization: bool = True,
                 tol: float = 1e-9, max_iter: int = 50000,
                 dtype="float64", schedule: Optional[Dict] = None):
        self.reg_param, self.elastic_net_param = reg_param, elastic_net_param
        self.standardization = standardization
        self.tol, self.max_iter = tol, max_iter
        self.schedule = schedule
        self.bfloat16 = str(dtype) == "bfloat16"
        self.dtype = np.dtype(np.float32 if self.bfloat16 else dtype)

    def _r(self, a):
        """What every stored array and every product goes through: nothing in
        float64 or float32, a rounding in bfloat16."""
        return to_bfloat16(a) if self.bfloat16 else a

    def _standardize(self, X, w, total):
        r = self._r
        mu = r((r(w[:, None] * X)).sum(axis=0) / total)
        dev = r(X - mu)
        sigma = r(np.sqrt(r(w[:, None] * r(dev * dev)).sum(axis=0) / total))
        sigma = np.where(sigma > 1e-9 * np.maximum(np.abs(mu), 1.0), sigma,
                         self.dtype.type(1.0))
        return r(dev / sigma), mu, sigma

    def fit(self, X: np.ndarray, y: np.ndarray, mask: np.ndarray = None
            ) -> "PlainMultinomial":
        """``mask`` (0/1 per row) is how the selector trains a fold: a row
        with 0 adds nothing to the standardization or to the loss."""
        r, dtype = self._r, self.dtype.type
        X = r(np.asarray(X, self.dtype))
        y = np.asarray(y).astype(np.int64)
        n, d = X.shape
        k = int(y.max()) + 1
        w = (np.ones(n, self.dtype) if mask is None
             else np.asarray(mask, self.dtype))
        total = dtype(max(float(w.sum(dtype=np.float64)), 1e-12))
        if self.standardization:
            Xs, mu, sigma = self._standardize(X, w, total)
        else:
            Xs, mu, sigma = X, np.zeros(d, self.dtype), np.ones(d, self.dtype)
        onehot = np.eye(k, dtype=self.dtype)[y]
        l2 = dtype(self.reg_param * (1.0 - self.elastic_net_param))
        l1 = dtype(self.reg_param * self.elastic_net_param)
        share = r(w / total)

        def loss_grad(P):
            logits = r(r(Xs @ P[:, :d].T) + P[:, d])
            logits = logits - logits.max(axis=1, keepdims=True)
            e = r(np.exp(logits))
            z = e.sum(axis=1, keepdims=True)
            value = -np.sum(share * np.sum(onehot * (logits - np.log(z)),
                                           axis=1))
            slope = r((r(e / z) - onehot) * share[:, None])        # (n, k)
            return value, r(np.concatenate(
                [slope.T @ Xs, slope.sum(axis=0)[:, None]], axis=1))

        def objective(P):
            return (loss_grad(P)[0] + dtype(0.5) * l2 * np.sum(P[:, :d] ** 2)
                    + l1 * np.sum(np.abs(P[:, :d])))

        penalised = np.concatenate([np.ones((k, d), self.dtype),
                                    np.zeros((k, 1), self.dtype)], axis=1)

        def proximal_step(Z, step):
            Q = r(Z - step * (loss_grad(Z)[1] + l2 * penalised * Z))
            return r(np.where(penalised > 0, np.sign(Q) * np.maximum(
                np.abs(Q) - step * l1, dtype(0.0)), Q))

        P = np.zeros((k, d + 1), self.dtype)
        Z, t = P.copy(), 1.0
        if self.schedule is not None:
            step = dtype(1.0 / (0.5 * self._power_iteration(Xs, w, total)
                                + float(l2) + 0.5))
            self.steps = self.schedule["steps"]
            for it in range(1, self.schedule["steps"] + 1):
                Q = proximal_step(Z, step)
                t_next = (1.0 + float(np.sqrt(1.0 + 4.0 * t * t))) / 2.0
                Z = r(Q + dtype((t - 1.0) / t_next) * (Q - P))
                moved = float(np.sqrt(np.sum((Q - P) ** 2, dtype=np.float64)))
                P, t = Q, t_next
                if moved < self.schedule["stop"]:
                    self.steps = it
                    break
        else:
            w64 = w.astype(np.float64)
            design = np.sqrt(w64)[:, None] * np.concatenate(
                [Xs.astype(np.float64), np.ones((n, 1))], axis=1)
            top = np.linalg.norm(design, 2) ** 2 / max(w64.sum(), 1e-12)
            step = dtype(1.0 / (0.5 * top + float(l2)))
            last, self.steps = objective(P), self.max_iter
            for it in range(1, self.max_iter + 1):
                Q = proximal_step(Z, step)
                value = objective(Q)
                if value > last and t > 1.0:    # overshot: restart at P
                    Z, t = P.copy(), 1.0
                    continue
                t_next = (1.0 + float(np.sqrt(1.0 + 4.0 * t * t))) / 2.0
                Z = r(Q + dtype((t - 1.0) / t_next) * (Q - P))
                moved = float(np.max(np.abs(Q - P)))
                P, t, last = Q, t_next, value
                if moved < self.tol:
                    self.steps = it
                    break
        assert P.dtype == Xs.dtype == self.dtype    # nothing was widened
        self.mu, self.sigma = mu, sigma
        self.coefficients = P[:, :d] / sigma                       # (k, d)
        self.intercept = P[:, d] - self.coefficients @ mu          # (k,)
        return self

    def _power_iteration(self, Xs, w, total, iterations: int = 16) -> float:
        """The largest eigenvalue of ``Xs^T diag(w) Xs / sum w`` as the
        package estimates it: from the constant unit vector, ``iterations``
        normalised products, then the Rayleigh quotient."""
        r, d = self._r, Xs.shape[1]

        def product(v):
            return r(Xs.T @ r(w * r(Xs @ v))) / total

        v = np.full(d, 1.0 / np.sqrt(d), self.dtype)
        for _ in range(iterations):
            u = product(v)
            v = r(u / (np.sqrt(np.sum(u * u)) + self.dtype.type(1e-12)))
        return float(np.vdot(v, product(v)))

    def scores(self, X: np.ndarray) -> np.ndarray:
        """(rows, classes) logits: their argmax is the predicted class."""
        return (np.asarray(X, self.dtype) @ self.coefficients.T
                + self.intercept)
