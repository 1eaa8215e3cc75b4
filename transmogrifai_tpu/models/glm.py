"""Generalized linear regression via IRLS.

TPU-native replacement for the reference's OpGeneralizedLinearRegression
(core/.../regression/OpGeneralizedLinearRegression.scala), wrapping
MLlib GeneralizedLinearRegression (families gaussian/binomial/poisson/
gamma/tweedie, canonical + explicit links, IRLS solver, L2 penalty).

IRLS here is a ``lax.while_loop`` of weighted ridge solves on the
standardized matrix — each iteration is one (d+1, d+1) Gram product and
solve, so the whole fit is a single static-shape XLA program; the fold x
grid lanes of a search are the program ``jit_glm_batched``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
import numpy as np

from ..observability import trace as _trace
from .base import Predictor, RegressionModel, subset_grid
from .linear import _weighted_standardize

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


#: precision of the IRLS products: the (d+1, d+1) Gram matrix, its right-hand
#: side and the linear predictor. Float32 in full on the chip too, whose
#: default is ONE bf16 pass of the multiplier. At that default the package
#: as found read 1.8e-2 (gaussian) and 9.5e-2 (poisson) of the largest
#: coefficient from the float64 IRLS on the v5e; since an iteration solves
#: for the increment (see irls_step) the Gram matrix's precision no longer
#: shows in the fixed point (1.2e-5 / 1.1e-3 either way), but the
#: predictor and the right-hand side DEFINE that point, and whether a
#: batched matrix-vector product reaches the multiplier is the compiler's
#: choice: 0.04 chip seconds a train (PERF.md section 6, PR 34)
_GLM_PRECISION = jax.lax.Precision.HIGHEST


def _dot(a, b):
    return jnp.matmul(a, b, precision=_GLM_PRECISION)


def _glm_irls_core(X, y, mask, reg, var_power, tol, *, family: str,
                   link: str, max_iter: int, fit_intercept: bool):
    """Masked weighted IRLS (the one GLM fit definition): ``mask`` of
    ones is the plain fit; 0/1 fold masks batch through vmap (each lane
    fits exactly its fold's rows — masked rows carry zero IRLS weight).
    Vmapped lanes run the while_loop in lockstep until all converge;
    each iteration is one tiny (d+1, d+1) solve, so lockstep is cheap
    (unlike L-BFGS line searches).

    The system is posed on the STANDARDIZED matrix (the mask-weighted
    mean and deviation of every column, ``linear._weighted_standardize``,
    a constant column left as zeros; without an intercept the columns are
    scaled and not centred) and the L2 penalty ``reg`` sits on the
    standardized coefficients, as the linear cores' does
    (docs/MIGRATION.md): raw columns whose variances span six orders of
    magnitude give a float32 solve a matrix it cannot resolve. The
    coefficients are mapped back to the raw columns at the end.

    Returns (coefficients (d,), intercept, IRLS iterations run)."""
    n, d = X.shape
    g, ginv, gprime = _link_fns(link)
    var = _variance_fn(family, var_power)
    msum = jnp.maximum(jnp.sum(mask), 1.0)
    with jax.named_scope("lin.standardize"):
        Xs, mu, sigma, _ = _weighted_standardize(X, mask)
        if not fit_intercept:
            Xs, mu = X / sigma, jnp.zeros_like(mu)
    if fit_intercept:
        Xa = jnp.concatenate([Xs, jnp.ones((n, 1), X.dtype)], axis=1)
        pen = jnp.concatenate([jnp.full((d,), reg, X.dtype),
                               jnp.zeros((1,), X.dtype)])
    else:
        Xa, pen = Xs, jnp.full((d,), reg, X.dtype)

    def normal_equations(w, rhs, ridge, shrink):
        """``solve(Xa^T diag(w) Xa / n + diag(ridge), Xa^T (w rhs) / n -
        shrink)``."""
        with jax.named_scope("glm.gram"):
            Xw = Xa * w[:, None]
            A = _dot(Xw.T, Xa) / msum + jnp.diag(ridge)
            b = _dot(Xw.T, rhs) / msum - shrink
        with jax.named_scope("glm.solve"):
            return jnp.linalg.solve(A, b)

    def irls_step(beta):
        # the IRLS step in its increment form: with the working response
        # z = eta + r, ``solve(A, Xa^T (w z) / n)`` is ``beta + solve(A,
        # Xa^T (w r) / n - pen * beta)``. The same iterate in exact
        # arithmetic; in float32 the solve's error scales with what it
        # solves for, and an intercept of 7.6 beside coefficients of 1e-3
        # (a label near 2,000 under the log link) cost the whole step two
        # digits where the increment costs them of a correction that
        # vanishes (PERF.md section 6, PR 34)
        eta = _dot(Xa, beta)
        mu_ = ginv(eta)
        gp = gprime(mu_)
        r = (y - mu_) * gp
        w = mask / jnp.maximum(var(mu_) * gp * gp, _EPS)
        # masked (held-out) rows still flow through the nonlinearities
        # above and can produce inf/NaN (e.g. exp overflow under a log
        # link); 0 * NaN = NaN would poison the gram matrix, so zero
        # them EXPLICITLY. ONLY masked rows: a non-finite TRAIN row
        # must keep poisoning the lane, because the sequential per-fold
        # fit sees that row too — parity both ways
        w = jnp.where(mask > 0, w, 0.0)
        r = jnp.where(mask > 0, r, 0.0)
        return beta + normal_equations(w, r, pen, pen * beta)

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
    beta0 = normal_equations(mask, eta0, pen + _EPS, 0.0)
    beta, _, iterations = jax.lax.while_loop(
        continuing, body,
        (beta0, jnp.asarray(jnp.inf, X.dtype), jnp.asarray(0)))
    coef = beta[:d] / sigma
    if fit_intercept:
        return coef, beta[d] - _dot(coef, mu), iterations
    return coef, jnp.asarray(0.0, X.dtype), iterations


@functools.partial(jax.jit, static_argnames=("family", "link", "max_iter",
                                             "fit_intercept"))
def _fit_glm_irls(X, y, reg, var_power, tol, *, family: str, link: str,
                  max_iter: int, fit_intercept: bool):
    return _glm_irls_core(X, y, jnp.ones_like(y), reg, var_power, tol,
                          family=family, link=link, max_iter=max_iter,
                          fit_intercept=fit_intercept)[:2]


def _glm_predict(beta, intercept, link: str, Xv):
    """Device twin of GeneralizedLinearRegressionModel.predict_values."""
    _, ginv, _ = _link_fns(link)
    return ginv(_dot(Xv, beta) + intercept)


# The two kernels below are programs named ``jit_glm_batched`` (the function
# a ``jax.jit`` wraps names the program), locally and on a mesh, whose bodies
# trace under the scope ``fg.glm``: the IRLS lanes are a fold-grid program
# with a name and a scope of its own, as every other family's
# (docs/observability.md). Both return the lanes' IRLS iterations besides:
# vmapped lanes run the while_loop in lockstep, so the largest is the trip
# count the whole program ran.

@functools.lru_cache(maxsize=32)
def _glm_fit_kernel(statics: tuple, mesh=None):
    """(coefficients, intercepts, iterations) of every candidate. With a
    mesh the candidate axis is sharded over its ``models`` axis (same
    mapping as the sibling family kernels); X/y replicate."""
    family, link, max_iter, fit_intercept = statics

    def glm_batched(masks, regs, vps, X, y, tol):
        with jax.named_scope("fg.glm"):
            return jax.vmap(
                lambda m, r, vp: _glm_irls_core(
                    X, y, m, r, vp, tol, family=family, link=link,
                    max_iter=max_iter, fit_intercept=fit_intercept)
            )(masks, regs, vps)

    if mesh is None:
        return jax.jit(glm_batched)
    from jax.sharding import PartitionSpec as P
    return jax.jit(shard_map(
        glm_batched, mesh=mesh,
        in_specs=(P("models", None), P("models"), P("models"),
                  P(), P(), P()),
        out_specs=(P("models", None), P("models"), P("models")),
        check_vma=False))


@functools.lru_cache(maxsize=32)
def _glm_eval_kernel(statics: tuple, spec: tuple, mesh=None,
                     in_fit: bool = False):
    """Fused IRLS fit + validation metric: (metric, iterations) of every
    candidate. ``val`` is the stacked validation matrix (F, nv, d) or,
    with ``in_fit``, the (F, nv) int32 positions of the validation rows in
    the fitted table (the validator's ``val_rows``): their predictions are
    then picked from the table's, and no second matrix goes to the
    device."""
    family, link, max_iter, fit_intercept = statics
    from ..evaluators.device_metrics import metric_fn
    mfn = metric_fn(*spec)

    def glm_batched(masks, regs, vps, fidx, X, y, val, yv, tol):
        def one(m, r, vp, fi):
            beta, b0, iterations = _glm_irls_core(
                X, y, m, r, vp, tol, family=family, link=link,
                max_iter=max_iter, fit_intercept=fit_intercept)
            with jax.named_scope("fg.metric"):
                pred = (_glm_predict(beta, b0, link, X)[val[fi]] if in_fit
                        else _glm_predict(beta, b0, link, val[fi]))
                return mfn(yv[fi], pred), iterations
        with jax.named_scope("fg.glm"):
            return jax.vmap(one)(masks, regs, vps, fidx)

    if mesh is None:
        return jax.jit(glm_batched)
    from jax.sharding import PartitionSpec as P
    return jax.jit(shard_map(
        glm_batched, mesh=mesh,
        in_specs=(P("models", None), P("models"), P("models"),
                  P("models"), P(), P(), P(), P(), P()),
        out_specs=(P("models"), P("models")), check_vma=False))


def _note_iterations(rec, iterations) -> None:
    """The largest of the lanes' IRLS iterations (the trip count the
    lockstep program ran) as the attribute ``irls_iterations`` of the call's
    ``search.fetch`` span ``rec`` (None while tracing is off)."""
    if rec is not None:
        rec["attrs"]["irls_iterations"] = int(np.max(iterations))


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
        (family, link) group as one vmapped IRLS program
        (``jit_glm_batched``), shardable over a mesh ``models`` axis."""
        from ..parallel.mesh import to_host
        X_j, y_j = jnp.asarray(X), jnp.asarray(y)
        grid, F, batches = self._batched_groups(grid, masks, mesh)
        models = [[None] * len(grid) for _ in range(F)]
        for (family, link, fit_int, mi), members, masks_c, regs, vps, \
                _, count in batches:
            gk = len(members)
            fn = _glm_fit_kernel((family, link, mi, fit_int), mesh)
            with _trace.span("search.fetch", family=family,
                             lanes=count) as rec:
                W, B, iterations = fn(
                    jnp.asarray(masks_c), jnp.asarray(regs),
                    jnp.asarray(vps), X_j, y_j, jnp.asarray(self.tol))
                W, B = to_host(W)[:count], to_host(B)[:count]
                _note_iterations(rec, to_host(iterations)[:count])
            for f in range(F):
                for j, (gi, _) in enumerate(members):
                    c = f * gk + j
                    models[f][gi] = GeneralizedLinearRegressionModel(
                        coefficients=W[c], intercept=float(B[c]),
                        link=link)
        return models

    def eval_fold_grid_arrays(self, X, y, masks, grid, X_val, y_val,
                              spec, mesh=None, cand_idx=None,
                              val_rows=None):
        """Device-resident search: fused IRLS fit + validation metric,
        (F, G) matrix out. ``val_rows`` ((F, nv) positions of the
        validation rows in ``X``, from the validator) takes the place of
        ``X_val``, which is then never read: the lanes' predictions for
        those rows are picked from the table's."""
        from ..parallel.mesh import to_host
        if spec[0] != "regression":
            raise NotImplementedError(
                "GLM device eval needs a regression metric")
        in_fit = val_rows is not None
        with _trace.span("search.head"):
            X_j, y_j = jnp.asarray(X), jnp.asarray(y)
            val_j = (jnp.asarray(np.asarray(val_rows, dtype=np.int32))
                     if in_fit else
                     jnp.asarray(np.asarray(X_val, dtype=np.float64)))
            yv_j = jnp.asarray(np.asarray(y_val, dtype=np.float64))
            grid, F, batches = self._batched_groups(
                subset_grid(grid, cand_idx), masks, mesh)
        metric_mat = np.full((F, len(grid)), np.nan)
        for (family, link, fit_int, mi), members, masks_c, regs, vps, \
                fidx, count in batches:
            gk = len(members)
            fn = _glm_eval_kernel((family, link, mi, fit_int), spec, mesh,
                                  in_fit)
            with _trace.span("search.fetch", family=family,
                             lanes=count) as rec:
                mm, iterations = fn(
                    jnp.asarray(masks_c), jnp.asarray(regs),
                    jnp.asarray(vps), jnp.asarray(fidx), X_j, y_j, val_j,
                    yv_j, jnp.asarray(self.tol))
                mm = to_host(mm)[:count]
                _note_iterations(rec, to_host(iterations)[:count])
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
