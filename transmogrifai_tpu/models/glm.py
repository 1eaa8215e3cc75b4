"""Generalized linear regression via IRLS.

TPU-native replacement for the reference's OpGeneralizedLinearRegression
(core/.../regression/OpGeneralizedLinearRegression.scala), wrapping
MLlib GeneralizedLinearRegression (families gaussian/binomial/poisson/
gamma/tweedie, canonical + explicit links, IRLS solver, L2 penalty).

IRLS here is a ``lax.fori_loop`` of weighted ridge solves — each
iteration is one (d+1, d+1) MXU solve, so the whole fit is a single
static-shape XLA program.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
import numpy as np

from .base import Predictor, RegressionModel, subset_grid

__all__ = ["GeneralizedLinearRegression",
           "GeneralizedLinearRegressionModel"]

_DEFAULT_LINK = {"gaussian": "identity", "binomial": "logit",
                 "poisson": "log", "gamma": "inverse", "tweedie": "log"}

_EPS = 1e-10


def _link_fns(link: str):
    """(g(mu), g^{-1}(eta), g'(mu))"""
    if link == "identity":
        return (lambda mu: mu, lambda eta: eta, lambda mu: jnp.ones_like(mu))
    if link == "log":
        return (lambda mu: jnp.log(jnp.maximum(mu, _EPS)),
                lambda eta: jnp.exp(eta),
                lambda mu: 1.0 / jnp.maximum(mu, _EPS))
    if link == "logit":
        return (lambda mu: jnp.log(mu / (1 - mu)),
                jax.nn.sigmoid,
                lambda mu: 1.0 / jnp.maximum(mu * (1 - mu), _EPS))
    if link == "inverse":
        return (lambda mu: 1.0 / jnp.maximum(mu, _EPS),
                lambda eta: 1.0 / jnp.where(jnp.abs(eta) > _EPS, eta, _EPS),
                lambda mu: -1.0 / jnp.maximum(mu * mu, _EPS))
    if link == "sqrt":
        return (lambda mu: jnp.sqrt(jnp.maximum(mu, 0.0)),
                lambda eta: eta * eta,
                lambda mu: 0.5 / jnp.sqrt(jnp.maximum(mu, _EPS)))
    raise ValueError(f"Unknown link {link!r}")


def _variance_fn(family: str, var_power: float):
    if family == "gaussian":
        return lambda mu: jnp.ones_like(mu)
    if family == "binomial":
        return lambda mu: jnp.maximum(mu * (1 - mu), _EPS)
    if family == "poisson":
        return lambda mu: jnp.maximum(mu, _EPS)
    if family == "gamma":
        return lambda mu: jnp.maximum(mu * mu, _EPS)
    if family == "tweedie":
        return lambda mu: jnp.maximum(mu, _EPS) ** var_power
    raise ValueError(f"Unknown family {family!r}")


def _init_mu(family: str, y):
    if family == "binomial":
        return (y + 0.5) / 2.0
    if family in ("poisson", "gamma", "tweedie"):
        return jnp.maximum(y, 0.1)
    return y


def _glm_irls_core(X, y, mask, reg, var_power, tol, *, family: str,
                   link: str, max_iter: int, fit_intercept: bool):
    """Masked weighted IRLS (the one GLM fit definition): ``mask`` of
    ones is the plain fit; 0/1 fold masks batch through vmap (each lane
    fits exactly its fold's rows — masked rows carry zero IRLS weight).
    Vmapped lanes run the while_loop in lockstep until all converge;
    each iteration is one tiny (d+1, d+1) solve, so lockstep is cheap
    (unlike L-BFGS line searches)."""
    n, d = X.shape
    g, ginv, gprime = _link_fns(link)
    var = _variance_fn(family, var_power)
    msum = jnp.maximum(jnp.sum(mask), 1.0)
    if fit_intercept:
        Xa = jnp.concatenate([X, jnp.ones((n, 1), X.dtype)], axis=1)
        pen = jnp.concatenate([jnp.full((d,), reg, X.dtype),
                               jnp.zeros((1,), X.dtype)])
    else:
        Xa, pen = X, jnp.full((d,), reg, X.dtype)

    def irls_step(beta):
        eta = Xa @ beta
        mu = ginv(eta)
        gp = gprime(mu)
        z = eta + (y - mu) * gp
        w = mask / jnp.maximum(var(mu) * gp * gp, _EPS)
        # masked (held-out) rows still flow through the nonlinearities
        # above and can produce inf/NaN (e.g. exp overflow under a log
        # link); 0 * NaN = NaN would poison the gram matrix, so zero
        # them EXPLICITLY. ONLY masked rows: a non-finite TRAIN row
        # must keep poisoning the lane, because the sequential per-fold
        # fit sees that row too — parity both ways
        w = jnp.where(mask > 0, w, 0.0)
        z = jnp.where(mask > 0, z, 0.0)
        A = (Xa * w[:, None]).T @ Xa / msum + jnp.diag(pen)
        b = (Xa * w[:, None]).T @ z / msum
        return jnp.linalg.solve(A, b)

    def body(carry):
        beta, _, it = carry
        beta_next = irls_step(beta)
        delta = jnp.linalg.norm(beta_next - beta) \
            / jnp.maximum(jnp.linalg.norm(beta), 1.0)
        return beta_next, delta, it + 1

    def continuing(carry):
        _, delta, it = carry
        return (it == 0) | ((it < max_iter) & (delta >= tol))

    mu0 = _init_mu(family, y)
    eta0 = g(mu0)
    eta0 = jnp.where(mask > 0, eta0, 0.0)
    # start from the masked weighted LS fit of eta0
    beta0 = jnp.linalg.solve(
        (Xa * mask[:, None]).T @ Xa / msum + jnp.diag(pen + _EPS),
        (Xa * mask[:, None]).T @ eta0 / msum)
    beta, _, _ = jax.lax.while_loop(
        continuing, body,
        (beta0, jnp.asarray(jnp.inf, X.dtype), jnp.asarray(0)))
    if fit_intercept:
        return beta[:d], beta[d]
    return beta, jnp.asarray(0.0, X.dtype)


@functools.partial(jax.jit, static_argnames=("family", "link", "max_iter",
                                             "fit_intercept"))
def _fit_glm_irls(X, y, reg, var_power, tol, *, family: str, link: str,
                  max_iter: int, fit_intercept: bool):
    return _glm_irls_core(X, y, jnp.ones_like(y), reg, var_power, tol,
                          family=family, link=link, max_iter=max_iter,
                          fit_intercept=fit_intercept)


def _glm_predict(beta, intercept, link: str, Xv):
    """Device twin of GeneralizedLinearRegressionModel.predict_values."""
    _, ginv, _ = _link_fns(link)
    return ginv(Xv @ beta + intercept)


@functools.partial(jax.jit, static_argnames=("family", "link", "max_iter",
                                             "fit_intercept"))
def _fit_glm_folds(X, y, masks, regs, var_powers, tol, *, family: str,
                   link: str, max_iter: int, fit_intercept: bool):
    return jax.vmap(
        lambda m, r, vp: _glm_irls_core(
            X, y, m, r, vp, tol, family=family, link=link,
            max_iter=max_iter, fit_intercept=fit_intercept)
    )(masks, regs, var_powers)


@functools.partial(jax.jit, static_argnames=("family", "link", "max_iter",
                                             "fit_intercept", "spec"))
def _eval_glm_folds(X, y, masks, regs, var_powers, fidx, Xv, yv, tol, *,
                    family: str, link: str, max_iter: int,
                    fit_intercept: bool, spec: tuple):
    from ..evaluators.device_metrics import metric_fn
    mfn = metric_fn(*spec)

    def one(m, r, vp, fi):
        beta, b0 = _glm_irls_core(
            X, y, m, r, vp, tol, family=family, link=link,
            max_iter=max_iter, fit_intercept=fit_intercept)
        return mfn(yv[fi], _glm_predict(beta, b0, link, Xv[fi]))

    return jax.vmap(one)(masks, regs, var_powers, fidx)


@functools.lru_cache(maxsize=32)
def _glm_fit_mesh_kernel(family: str, link: str, max_iter: int,
                         fit_intercept: bool, mesh):
    """Candidate axis sharded over the mesh ``models`` axis (same
    mapping as the sibling family kernels); X/y replicate."""
    from jax.sharding import PartitionSpec as P

    def batched(masks, regs, vps, X, y, tol):
        return jax.vmap(
            lambda m, r, vp: _glm_irls_core(
                X, y, m, r, vp, tol, family=family, link=link,
                max_iter=max_iter, fit_intercept=fit_intercept)
        )(masks, regs, vps)

    return jax.jit(shard_map(
        batched, mesh=mesh,
        in_specs=(P("models", None), P("models"), P("models"),
                  P(), P(), P()),
        out_specs=(P("models", None), P("models")), check_vma=False))


@functools.lru_cache(maxsize=32)
def _glm_eval_mesh_kernel(family: str, link: str, max_iter: int,
                          fit_intercept: bool, spec: tuple, mesh):
    from jax.sharding import PartitionSpec as P
    from ..evaluators.device_metrics import metric_fn
    mfn = metric_fn(*spec)

    def batched(masks, regs, vps, fidx, X, y, Xv, yv, tol):
        def one(m, r, vp, fi):
            beta, b0 = _glm_irls_core(
                X, y, m, r, vp, tol, family=family, link=link,
                max_iter=max_iter, fit_intercept=fit_intercept)
            return mfn(yv[fi], _glm_predict(beta, b0, link, Xv[fi]))
        return jax.vmap(one)(masks, regs, vps, fidx)

    return jax.jit(shard_map(
        batched, mesh=mesh,
        in_specs=(P("models", None), P("models"), P("models"),
                  P("models"), P(), P(), P(), P(), P()),
        out_specs=P("models"), check_vma=False))


class GeneralizedLinearRegression(Predictor):
    """GLM with IRLS (reference OpGeneralizedLinearRegression.scala)."""

    def __init__(self, family: str = "gaussian", link: Optional[str] = None,
                 reg_param: float = 0.0, max_iter: int = 25,
                 tol: float = 1e-6, fit_intercept: bool = True,
                 variance_power: float = 1.5, uid: Optional[str] = None):
        super().__init__(uid=uid)
        self.family = family
        self.link = link or _DEFAULT_LINK[family]
        self.reg_param = reg_param
        self.max_iter = max_iter
        self.tol = tol
        self.fit_intercept = fit_intercept
        self.variance_power = variance_power

    def fit_arrays(self, X: np.ndarray, y: np.ndarray
                   ) -> "GeneralizedLinearRegressionModel":
        w, b = _fit_glm_irls(
            jnp.asarray(X), jnp.asarray(y), self.reg_param,
            self.variance_power, self.tol, family=self.family,
            link=self.link, max_iter=self.max_iter,
            fit_intercept=self.fit_intercept)
        return GeneralizedLinearRegressionModel(
            coefficients=np.asarray(w), intercept=float(b), link=self.link)

    _GRID_ALLOWED = {"family", "link", "reg_param", "variance_power"}

    def _grid_groups(self, grid):
        """Group grid points by their static (family/link/intercept)
        config; reg/var_power trace. NotImplementedError on params the
        kernels can't handle (validator falls back sequential)."""
        grid = [dict(p) for p in (list(grid) or [{}])]
        for p in grid:
            extra = set(p) - self._GRID_ALLOWED
            if extra:
                raise NotImplementedError(
                    f"batched GLM kernel cannot vary {sorted(extra)}")
        groups = {}
        for gi, p in enumerate(grid):
            cand = self.with_params(**p)
            key = (cand.family, cand.link, cand.fit_intercept,
                   cand.max_iter)
            groups.setdefault(key, []).append((gi, cand))
        return grid, groups

    def _batched_groups(self, grid, masks, mesh):
        """One definition of the fold-major candidate layout shared by
        the fit and eval paths (change together): yields per static
        group (key, members, masks_c, regs, vps, fidx, count) with the
        candidate axis padded to the mesh shard count when sharding."""
        from .trees import _pad_candidates
        grid, groups = self._grid_groups(grid)
        masks = np.asarray(masks, dtype=np.float64)
        F = masks.shape[0]
        out = []
        for key, members in groups.items():
            gk = len(members)
            regs = np.tile([float(c.reg_param) for _, c in members], F)
            vps = np.tile([float(c.variance_power) for _, c in members],
                          F)
            masks_c = np.repeat(masks, gk, axis=0)   # fold-major
            fidx = np.repeat(np.arange(F, dtype=np.int32), gk)
            (masks_c, regs, vps), count = _pad_candidates(
                mesh, [masks_c, regs, vps], masks_c.shape[1])
            fidx = np.concatenate(
                [fidx, np.zeros(len(regs) - count, dtype=np.int32)])
            out.append((key, members, masks_c, regs, vps, fidx, count))
        return grid, F, out

    def fit_fold_grid_arrays(self, X, y, masks, grid, mesh=None):
        """Validator fast path: fold x grid candidates of each
        (family, link) group as one vmapped IRLS program, shardable
        over a mesh ``models`` axis."""
        from ..parallel.mesh import to_host
        X_j, y_j = jnp.asarray(X), jnp.asarray(y)
        grid, F, batches = self._batched_groups(grid, masks, mesh)
        models = [[None] * len(grid) for _ in range(F)]
        for (family, link, fit_int, mi), members, masks_c, regs, vps, \
                _, count in batches:
            gk = len(members)
            if mesh is not None:
                fn = _glm_fit_mesh_kernel(family, link, mi, fit_int,
                                          mesh)
                W, B = fn(jnp.asarray(masks_c), jnp.asarray(regs),
                          jnp.asarray(vps), X_j, y_j,
                          jnp.asarray(self.tol))
            else:
                W, B = _fit_glm_folds(
                    X_j, y_j, jnp.asarray(masks_c), jnp.asarray(regs),
                    jnp.asarray(vps), self.tol, family=family,
                    link=link, max_iter=mi, fit_intercept=fit_int)
            W, B = to_host(W)[:count], to_host(B)[:count]
            for f in range(F):
                for j, (gi, _) in enumerate(members):
                    c = f * gk + j
                    models[f][gi] = GeneralizedLinearRegressionModel(
                        coefficients=W[c], intercept=float(B[c]),
                        link=link)
        return models

    def eval_fold_grid_arrays(self, X, y, masks, grid, X_val, y_val,
                              spec, mesh=None, cand_idx=None):
        """Device-resident search: fused IRLS fit + validation metric,
        (F, G) matrix out."""
        from ..parallel.mesh import to_host
        if spec[0] != "regression":
            raise NotImplementedError(
                "GLM device eval needs a regression metric")
        X_j, y_j = jnp.asarray(X), jnp.asarray(y)
        Xv_j = jnp.asarray(np.asarray(X_val, dtype=np.float64))
        yv_j = jnp.asarray(np.asarray(y_val, dtype=np.float64))
        grid, F, batches = self._batched_groups(
            subset_grid(grid, cand_idx), masks, mesh)
        metric_mat = np.full((F, len(grid)), np.nan)
        for (family, link, fit_int, mi), members, masks_c, regs, vps, \
                fidx, count in batches:
            gk = len(members)
            if mesh is not None:
                fn = _glm_eval_mesh_kernel(family, link, mi, fit_int,
                                           spec, mesh)
                mm = fn(jnp.asarray(masks_c), jnp.asarray(regs),
                        jnp.asarray(vps), jnp.asarray(fidx), X_j, y_j,
                        Xv_j, yv_j, jnp.asarray(self.tol))
            else:
                mm = _eval_glm_folds(
                    X_j, y_j, jnp.asarray(masks_c), jnp.asarray(regs),
                    jnp.asarray(vps), jnp.asarray(fidx), Xv_j, yv_j,
                    self.tol, family=family, link=link, max_iter=mi,
                    fit_intercept=fit_int, spec=spec)
            mm = to_host(mm)[:count]
            for f in range(F):
                for j, (gi, _) in enumerate(members):
                    metric_mat[f, gi] = mm[f * gk + j]
        return metric_mat


class GeneralizedLinearRegressionModel(RegressionModel):
    def __init__(self, coefficients, intercept: float = 0.0,
                 link: str = "identity", uid: Optional[str] = None):
        super().__init__(uid=uid)
        self.coefficients = np.asarray(coefficients, dtype=np.float64)
        self.intercept = float(intercept)
        self.link = link

    def predict_values(self, X: np.ndarray) -> np.ndarray:
        eta = X @ self.coefficients + self.intercept
        if self.link == "identity":
            return eta
        if self.link == "log":
            return np.exp(eta)
        if self.link == "logit":
            return 1.0 / (1.0 + np.exp(-eta))
        if self.link == "inverse":
            return 1.0 / np.where(np.abs(eta) > _EPS, eta, _EPS)
        if self.link == "sqrt":
            return eta * eta
        raise ValueError(f"Unknown link {self.link!r}")

    def raw_arrays(self, X):
        eta = X @ jnp.asarray(self.coefficients, X.dtype) + self.intercept
        if self.link == "identity":
            return eta
        if self.link == "log":
            return jnp.exp(eta)
        if self.link == "logit":
            return 1.0 / (1.0 + jnp.exp(-eta))
        if self.link == "inverse":
            return 1.0 / jnp.where(jnp.abs(eta) > _EPS, eta, _EPS)
        if self.link == "sqrt":
            return eta * eta
        raise ValueError(f"Unknown link {self.link!r}")
