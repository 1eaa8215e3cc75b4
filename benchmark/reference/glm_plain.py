"""Plain generalized linear regression by IRLS to convergence, NumPy float64:
the reference the regression pool cell's ``GeneralizedLinearRegression`` lanes
and winner are decided against (``msd_reg_pool.search``). The two families of
the default pool: gaussian with the identity link and poisson with the log
link.

Same fit as the system's (``models/glm.py`` ``_glm_irls_core``), written
straight from its description with no JAX, no batching and nothing of the
package:

- with row weights ``m`` (1 on a fold's training rows, 0 elsewhere) and
  ``standardize=True``, every column is centred on its weighted mean and
  divided by its weighted deviation (``linear_plain.standardize``, a constant
  column left as zeros), a column of ones is appended, and the L2 penalty
  ``reg_param`` sits on the standardized coefficients, the intercept
  unpenalised: what the package does since PR 34. ``standardize=False`` is the
  package as found before it, and MLlib's IRLS families: the raw columns, the
  penalty on the raw coefficients;
- one IRLS step from ``beta``: ``eta = Xa beta``, ``mu = g^-1(eta)``, working
  response ``z = eta + (y - mu) g'(mu)``, weights ``w = m / (V(mu) g'(mu)^2)``,
  then ``beta = solve(Xa^T diag(w) Xa / sum m + diag(penalty), Xa^T (w z) /
  sum m)``; gaussian / identity: ``V = 1``, ``g' = 1`` (one step is the ridge
  solution); poisson / log: ``V = mu``, ``g' = 1 / mu``, so ``w = m mu``;
- the start is the masked least-squares fit of ``g(mu0)``, ``mu0 = y``
  (gaussian) or ``max(y, 0.1)`` (poisson), as the package's; the iteration
  runs until the parameters move by less than ``tol`` (1e-12) of their norm
  (the package stops at 1e-6 or after 25 steps, in float32);
- the coefficients handed back are mapped to the raw columns.

``dtype="bfloat16"`` is for a control (``benchmark/controls_reg.py``): the
table, the standardized matrix, the parameters and every product are rounded
to bfloat16, sums in float32; the solve itself stays float64 (NumPy has no
bfloat16 solver, and it is the products that the chip's multiplier rounds).
"""
from __future__ import annotations

import numpy as np

from benchmark.reference.linear_plain import standardize
from benchmark.reference.multinomial_plain import to_bfloat16


class PlainGLM:
    def __init__(self, family: str = "gaussian", reg_param: float = 0.0,
                 standardize: bool = True, tol: float = 1e-12,
                 max_iter: int = 200, dtype="float64"):
        if family not in ("gaussian", "poisson"):
            raise ValueError(f"family {family!r}: gaussian or poisson")
        self.family, self.reg_param = family, reg_param
        self.standardize, self.tol, self.max_iter = standardize, tol, max_iter
        self.bfloat16 = str(dtype) == "bfloat16"
        self.dtype = np.dtype(np.float32 if self.bfloat16 else dtype)

    def _r(self, a):
        return to_bfloat16(a) if self.bfloat16 else a

    def fit(self, X: np.ndarray, y: np.ndarray, mask: np.ndarray = None
            ) -> "PlainGLM":
        r = self._r
        X = r(np.asarray(X, self.dtype))
        y = np.asarray(y, self.dtype)
        n, d = X.shape
        m = (np.ones(n, self.dtype) if mask is None
             else np.asarray(mask, self.dtype))
        total = max(float(m.sum(dtype=np.float64)), 1.0)
        if self.standardize:
            Xs, mu_x, sigma = standardize(X.astype(np.float64),
                                          m.astype(np.float64))
            constant = np.all(Xs == 0.0, axis=0)
        else:
            Xs, mu_x, sigma = X, np.zeros(d), np.ones(d)
        Xa = r(np.concatenate([Xs, np.ones((n, 1))], axis=1)
               .astype(self.dtype))
        penalty = np.append(np.full(d, self.reg_param), 0.0)
        log_link = self.family == "poisson"

        def solve(w, z, ridge):
            Xw = r(Xa * w[:, None])
            A = (Xw.T @ Xa).astype(np.float64) / total + np.diag(ridge)
            b = (Xw.T @ r(z)).astype(np.float64) / total
            return r(np.linalg.solve(A, b).astype(self.dtype))

        eta0 = np.log(np.maximum(y, 0.1)) if log_link else y
        beta = solve(m, np.where(m > 0, eta0, 0.0), penalty + 1e-10)
        self.iterations = self.max_iter
        for it in range(1, self.max_iter + 1):
            eta = r(Xa @ beta)
            if log_link:
                mu = np.exp(eta)
                z, w = eta + (y - mu) / np.maximum(mu, 1e-10), m * mu
            else:
                z, w = eta + (y - eta), m
            z, w = np.where(m > 0, z, 0.0), np.where(m > 0, w, 0.0)
            beta_next = solve(w.astype(self.dtype), z.astype(self.dtype),
                              penalty)
            moved = float(np.linalg.norm(beta_next.astype(np.float64) - beta)
                          / max(np.linalg.norm(beta), 1.0))
            beta = beta_next
            if moved < self.tol:
                self.iterations = it
                break
        beta = beta.astype(np.float64)
        self.mu, self.sigma = mu_x, sigma
        self.coefficients = beta[:d] / sigma
        self.intercept = float(beta[d] - self.coefficients @ mu_x)
        return self

    def predict(self, X: np.ndarray) -> np.ndarray:
        """Predicted means, in float64 whatever the fit's precision."""
        eta = (np.asarray(X, np.float64) @ self.coefficients
               + self.intercept)
        return np.exp(eta) if self.family == "poisson" else eta
