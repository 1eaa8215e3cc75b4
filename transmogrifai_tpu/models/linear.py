"""Linear model family: logistic regression, linear regression, linear SVC.

TPU-native replacements for the reference's Spark MLlib wrappers:
- OpLogisticRegression  (core/.../classification/OpLogisticRegression.scala:45)
- OpLinearRegression    (core/.../regression/OpLinearRegression.scala)
- OpLinearSVC           (core/.../classification/OpLinearSVC.scala)

Semantics follow MLlib where it matters for metric parity:
- optional internal standardization of features (penalty applied in the
  standardized space, coefficients mapped back),
- elastic-net penalty  regParam * (a*L1 + (1-a)/2 * L2),
- binary problems use binomial logistic loss, multiclass uses multinomial
  softmax (MLlib family="auto").

The optimizer is optax L-BFGS (or FISTA when L1 > 0) fully inside XLA —
see models/solvers.py. Fitting is a single jitted program per (shape)
so a hyperparameter grid can ``vmap`` over (reg_param, elastic_net).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..features.columns import PredictionColumn
from .base import ClassifierModel, Predictor, RegressionModel, subset_grid
from .solvers import design_lipschitz, fista_minimize, lbfgs_minimize

__all__ = ["LogisticRegression", "LogisticRegressionModel",
           "LinearRegression", "LinearRegressionModel",
           "LinearSVC", "LinearSVCModel"]

# ---------------------------------------------------------------------------
# shared weighted-fit cores
#
# Every linear-family fit is expressed over ROW WEIGHTS ``w`` (1 for
# training rows, 0 otherwise) with reductions routed through ``_psum``:
# - single fit: w = ones — identical math to a plain fit;
# - fold x grid CV: w = fold masks, the whole grid batched with vmap
#   (parallel/cv.py uses exactly these cores, so the mesh path selects
#   the same winner as the sequential path);
# - multi-chip: ``axis_name`` set inside shard_map — row reductions
#   cross the mesh data axis via psum over ICI.
# ---------------------------------------------------------------------------

def _psum(x, axis_name: Optional[str]):
    return jax.lax.psum(x, axis_name) if axis_name else x


#: precision of every product of a linear lane with its design: the margins
#: or the multinomial's (n, d) x (d, k) logits (the gradient's product
#: inherits it), the power iteration's, the fold statistics, the lanes'
#: validation logits and a K-class model's scores. Float32 in full on the
#: chip too, whose default is one bf16 pass, coarser than a float16 table:
#: with it the multinomial refit read 2e-3 to 5e-3 from the same steps in
#: float64 where this reads 2e-5 to 1e-4 (PERF.md). Under vmap a
#: step's products are (n, d) x (d, L) on the multiplier (see _LaneDesign),
#: where the default would round every step's coefficients and residuals to
#: bf16 as well
_LANE_PRECISION = jax.lax.Precision.HIGHEST


def _dot(a, b):
    return jnp.matmul(a, b, precision=_LANE_PRECISION)


def _weighted_standardize(X, w, axis_name=None):
    """Weighted mean/std standardization (subset stats when w is a 0/1
    mask — matches fitting on the gathered rows exactly)."""
    wsum = jnp.maximum(_psum(jnp.sum(w), axis_name), 1e-12)
    mu = _psum(jnp.sum(X * w[:, None], axis=0), axis_name) / wsum
    var = _psum(jnp.sum(w[:, None] * (X - mu) ** 2, axis=0),
                axis_name) / wsum
    sigma = jnp.sqrt(var)
    # constant columns must be treated as such: float reduction noise
    # makes their variance ~1e-32 rather than exactly 0, and dividing
    # by sigma~1e-16 back-transforms into a gigantic coefficient whose
    # cancellation against the intercept quantizes every margin (seen
    # as 1/256-grid logits on one-hot OTHER columns). A RELATIVE floor
    # catches them; genuinely informative columns sit far above it.
    floor = 1e-9 * jnp.maximum(jnp.abs(mu), 1.0)
    safe = jnp.where(sigma > floor, sigma, 1.0)
    return (X - mu) / safe, mu, safe, wsum


def _lane_design_form() -> str:
    """How a linear core builds its lane's design: "shared" (one
    standardized matrix for every lane of a program, the lane's fold
    statistics folded into its coefficients; a step's products under vmap
    are (n, d) x (d, L)) or "per_lane" (the lane's own weighted
    standardization of the design, one (n, d) copy a lane; batched
    products). Chosen at trace time from the backend: "per_lane" on a CPU,
    whose small products take a different emitter by width, so a shared
    product's lane would not keep its bits across the lane counts of a
    search mesh's shards (``resolve_search_mesh``); "shared" on an
    accelerator. The two forms agree to rounding."""
    return "per_lane" if jax.default_backend() == "cpu" else "shared"


class _LaneDesign(NamedTuple):
    """A lane's standardized design ``Xs = (Z - c) * scale``, kept as its
    factors. In the "shared" form ``Z = (X - m) / s`` depends on ``X``
    alone, so the lanes that ``vmap`` batches share ONE (n, d) array;
    ``c`` and ``scale`` are the lane's (d,) fold statistics in Z's units,
    and a product with ``Xs`` is one with ``Z`` and a (d,) correction:
    under vmap, (n, d) x (d, L). In the "per_lane" form ``Z`` is the lane's
    own ``Xs``, ``c`` 0 and ``scale`` 1."""
    Z: jnp.ndarray       # (n, d)
    c: jnp.ndarray       # (d,) the fold's weighted mean of Z
    scale: jnp.ndarray   # (d,) 1 / the fold's deviation; 0 = constant
    m: jnp.ndarray       # (d,) Z's origin, raw units
    s: jnp.ndarray       # (d,) Z's unit, raw units
    wsum: jnp.ndarray    # the lane's total row weight

    @property
    def shape(self):
        return self.Z.shape

    @property
    def dtype(self):
        return self.Z.dtype

    def matvec(self, v):
        """``Xs @ v``: (n,) for ``v`` (d,), (n, k) for ``v`` (d, k)."""
        u = (v.T * self.scale).T
        return _dot(self.Z, u) - _dot(self.c, u)

    def rmatvec(self, r):
        """``Xs.T @ r`` for ``r`` (n,), shard-local under a mesh."""
        return self.scale * (_dot(r, self.Z) - self.c * jnp.sum(r))

    def dense(self):
        """``Xs`` itself, one (n, d) array a lane: for the normal
        equations only."""
        return (self.Z - self.c) * self.scale

    def to_original(self, v, b):
        """Coefficients ``v`` ((d,) or (k, d)) and intercept ``b`` fitted on
        ``Xs``, mapped to the raw columns: ``v / sigma`` and ``b - (v /
        sigma) . mu``, where the fold's ``mu = m + c s`` and ``sigma = s /
        scale``; a constant column's coefficient is 0."""
        w_orig = v * self.scale / self.s
        return w_orig, b - w_orig @ (self.m + self.c * self.s)


def _lane_design(X, w, standardize: bool, axis_name) -> _LaneDesign:
    """The lane of weights ``w``'s design over ``X`` (see _LaneDesign), in
    the form of _lane_design_form.

    "shared": ``m`` and ``s`` are every row's mean and deviation,
    unweighted (psum'd over a mesh data axis, so every shard holds the same
    origin; ``s = 1`` where it is 0). The lane's statistics come in one
    pass in Z's units: ``c = w @ Z / wsum``, ``var = w @ (Z * Z) / wsum - c
    * c``. Because Z's origin is the all-rows mean, ``c`` is small and the
    subtraction keeps the digits raw columns far from 0 would lose. A
    column constant on the lane's training rows (a one-hot category missing
    from the fold's rows) keeps a ``var`` of rounding noise, a share of
    order ``eps`` of its second moment ``w @ (Z * Z) / wsum``; every share
    under ``sqrt(eps)`` counts as constant, and its ``scale`` 0 holds its
    coefficient at 0. "per_lane": ``_weighted_standardize``. Without
    ``standardize``, ``Z`` is ``X`` and the statistics 0 and 1."""
    n, d = X.shape
    form = _lane_design_form()
    with jax.named_scope("lin.standardize"):
        wsum = jnp.maximum(_psum(jnp.sum(w), axis_name), 1e-12)
        zeros, ones = jnp.zeros(d, X.dtype), jnp.ones(d, X.dtype)
        if not standardize:
            return _LaneDesign(X, zeros, ones, zeros, ones, wsum)
        if form == "per_lane":
            Xs, mu, sigma, _ = _weighted_standardize(X, w, axis_name)
            return _LaneDesign(Xs, zeros, ones, mu, sigma, wsum)
        rows = _psum(jnp.asarray(n, X.dtype), axis_name)
        m = _psum(jnp.sum(X, axis=0), axis_name) / rows
        s = jnp.sqrt(_psum(jnp.sum((X - m) ** 2, axis=0), axis_name) / rows)
        s = jnp.where(s > 0, s, 1.0)
        Z = (X - m) / s
        c = _psum(_dot(w, Z), axis_name) / wsum
        second = _psum(_dot(w, Z * Z), axis_name) / wsum
        var = second - c * c
        varies = var > float(np.finfo(X.dtype).eps) ** 0.5 * second
        scale = jnp.where(varies,
                          jax.lax.rsqrt(jnp.where(varies, var, 1.0)), 0.0)
        return _LaneDesign(Z, c, scale, m, s, wsum)


def binary_logistic_core(X, y, w, reg, alpha, *, fit_intercept: bool,
                         standardize: bool, max_iter: int, use_l1: bool,
                         axis_name: Optional[str] = None,
                         solver: str = "auto"):
    """Weighted binomial logistic fit -> (coefficients, intercept).

    solver="auto" uses L-BFGS for smooth penalties and FISTA when L1 is
    active; under a mesh (``axis_name``) or solver="fista" everything
    runs FISTA with a STATIC trip count — optax L-BFGS's data-dependent
    linesearch loops de-sync collective rendezvous across shards.
    """
    D = _lane_design(X, w, standardize, axis_name)
    d = D.shape[1]
    s = 2.0 * y - 1.0  # {0,1} -> {-1,+1}
    l2 = reg * (1.0 - alpha)
    l1 = reg * alpha
    # SHARD-LOCAL objective: the data term sums local rows only (global
    # wsum), the reg term is divided across shards — so an explicit psum
    # of the gradient reconstructs the exact global gradient. Autodiff
    # therefore never transposes a collective (see fista_minimize).
    nshards = _psum(jnp.asarray(1.0, D.dtype), axis_name)

    def smooth(params):
        wv, b = params[:d], params[d]
        m = D.matvec(wv) + (b if fit_intercept else 0.0)
        return (jnp.sum(w * jnp.logaddexp(0.0, -s * m)) / D.wsum
                + 0.5 * l2 * jnp.sum(wv * wv) / nshards)

    w0 = jnp.zeros(d + 1, D.dtype)
    force_fista = solver == "fista" or axis_name is not None
    with jax.named_scope("lin.solve"):
        if use_l1 or force_fista:
            mask = jnp.concatenate([jnp.ones(d, D.dtype),
                                    jnp.zeros(1, D.dtype)])
            lip = design_lipschitz(D, l2, curvature_bound=0.25, w=w,
                                   axis_name=axis_name) + 0.25
            params = fista_minimize(smooth, l1, w0, lip,
                                    max_iter=max_iter * 5,
                                    tol=0.0 if force_fista else 1e-7,
                                    l1_mask=mask, grad_psum_axis=axis_name)
        else:
            params = lbfgs_minimize(smooth, w0, max_iter=max_iter)
    wv, b = params[:d], jnp.where(fit_intercept, params[d], 0.0)
    return D.to_original(wv, b)


def multinomial_logistic_core(X, y, w, reg, alpha, *, k: int,
                              fit_intercept: bool, standardize: bool,
                              max_iter: int, use_l1: bool,
                              axis_name: Optional[str] = None,
                              solver: str = "auto"):
    """Weighted multinomial (softmax) logistic fit over ``k`` classes ->
    (coefficients (k, d), intercepts (k,)). The objective is
    ``-sum_i w_i log softmax(Xs_i W^T + b)[y_i] / sum(w) + 0.5 l2 ||W||^2
    (+ l1 ||W||_1 by the proximal step)``, the intercepts unpenalised: with
    ``l2 > 0`` its minimiser is unique, so no centring of the rows of ``W``
    is needed. Solver choice, the shard-local objective and the static trip
    count under a mesh or solver="fista" are ``binary_logistic_core``'s."""
    D = _lane_design(X, w, standardize, axis_name)
    d = D.shape[1]
    onehot = jax.nn.one_hot(y.astype(jnp.int32), k, dtype=D.dtype)
    l2 = reg * (1.0 - alpha)
    l1 = reg * alpha
    nshards = _psum(jnp.asarray(1.0, D.dtype), axis_name)

    def smooth(params):     # shard-local; the solver psums the gradient
        W = params[:, :d]
        logits = D.matvec(W.T) + (params[:, d] if fit_intercept else 0.0)
        ll = jnp.sum(w * jnp.sum(onehot * jax.nn.log_softmax(logits),
                                 axis=1)) / D.wsum
        return -ll + 0.5 * l2 * jnp.sum(W * W) / nshards

    W0 = jnp.zeros((k, d + 1), D.dtype)
    force_fista = solver == "fista" or axis_name is not None
    with jax.named_scope("lin.solve"):
        if use_l1 or force_fista:
            mask = jnp.concatenate([jnp.ones((k, d), D.dtype),
                                    jnp.zeros((k, 1), D.dtype)], axis=1)
            # the softmax Hessian in the logits, diag(p) - p p^T, is <= 1/2
            lip = design_lipschitz(D, l2, curvature_bound=0.5, w=w,
                                   axis_name=axis_name) + 0.5
            params = fista_minimize(smooth, l1, W0, lip,
                                    max_iter=max_iter * 5,
                                    tol=0.0 if force_fista else 1e-7,
                                    l1_mask=mask, grad_psum_axis=axis_name)
        else:
            params = lbfgs_minimize(smooth, W0, max_iter=max_iter)
    W = params[:, :d]
    b = params[:, d] if fit_intercept else jnp.zeros(k, D.dtype)
    return D.to_original(W, b)


def linear_regression_core(X, y, w, reg, alpha, *, fit_intercept: bool,
                           standardize: bool, max_iter: int, use_l1: bool,
                           axis_name: Optional[str] = None,
                           solver: str = "auto"):
    """Weighted OLS/ridge/elastic-net fit -> (coefficients, intercept).
    Non-L1 solves closed-form normal equations (loop-free, mesh-safe), on
    a lane's own copy of its design; L1 runs FISTA over the shared one,
    with a static trip count under a mesh."""
    D = _lane_design(X, w, standardize, axis_name)
    d = D.shape[1]
    ybar = (_psum(jnp.sum(w * y), axis_name) / D.wsum if fit_intercept
            else jnp.asarray(0.0, D.dtype))
    yc = y - ybar
    l2 = reg * (1.0 - alpha)
    l1 = reg * alpha

    if not use_l1:
        # ridge normal equations on the MXU (reference: MLlib "normal"
        # solver / breeze L-BFGS; one (d,d) psum-reduced solve here)
        with jax.named_scope("lin.solve"):
            Xs = D.dense()
            A = (_psum(Xs.T @ (w[:, None] * Xs), axis_name) / D.wsum
                 + l2 * jnp.eye(d, dtype=D.dtype))
            wv = jnp.linalg.solve(
                A, _psum(Xs.T @ (w * yc), axis_name) / D.wsum)
    else:
        nshards = _psum(jnp.asarray(1.0, D.dtype), axis_name)

        def smooth(wv):     # shard-local; solver psums the gradient
            r = D.matvec(wv) - yc
            return (jnp.sum(w * r * r) / (2.0 * D.wsum)
                    + 0.5 * l2 * jnp.sum(wv * wv) / nshards)
        with jax.named_scope("lin.solve"):
            lip = design_lipschitz(D, l2, curvature_bound=1.0, w=w,
                                   axis_name=axis_name) + 1e-3
            wv = fista_minimize(smooth, l1, jnp.zeros(d, D.dtype), lip,
                                max_iter=max_iter * 5,
                                tol=0.0 if (solver == "fista"
                                            or axis_name is not None)
                                else 1e-7,
                                grad_psum_axis=axis_name)
    w_orig, b = D.to_original(wv, ybar)
    return w_orig, b if fit_intercept else jnp.asarray(0.0, D.dtype)


def linear_svc_core(X, y, w, reg, alpha, *, fit_intercept: bool,
                    standardize: bool, max_iter: int, use_l1: bool = False,
                    axis_name: Optional[str] = None, solver: str = "auto"):
    """Weighted L2 squared-hinge SVM fit -> (coefficients, intercept).
    The reference's LinearSVC uses hinge + OWL-QN; squared hinge is the
    smooth TPU-friendly variant with near-identical decision boundaries
    (documented deviation). ``alpha``/``use_l1`` accepted for kernel-
    signature uniformity; L1 is not part of MLlib LinearSVC."""
    D = _lane_design(X, w, standardize, axis_name)
    d = D.shape[1]
    s = 2.0 * y - 1.0
    nshards = _psum(jnp.asarray(1.0, D.dtype), axis_name)

    def loss(params):       # shard-local; solver psums the gradient
        wv, b = params[:d], params[d]
        m = D.matvec(wv) + (b if fit_intercept else 0.0)
        viol = jnp.maximum(0.0, 1.0 - s * m)
        return (jnp.sum(w * viol * viol) / D.wsum
                + 0.5 * reg * jnp.sum(wv * wv) / nshards)

    w0 = jnp.zeros(d + 1, D.dtype)
    with jax.named_scope("lin.solve"):
        if solver == "fista" or axis_name is not None:
            # squared hinge has phi'' <= 2
            lip = design_lipschitz(D, reg, curvature_bound=2.0, w=w,
                                   axis_name=axis_name) + 2.0
            params = fista_minimize(loss, 0.0, w0, lip,
                                    max_iter=max_iter * 5, tol=0.0,
                                    grad_psum_axis=axis_name)
        else:
            params = lbfgs_minimize(loss, w0, max_iter=max_iter)
    wv, b = params[:d], jnp.where(fit_intercept, params[d], 0.0)
    return D.to_original(wv, b)


# ---------------------------------------------------------------------------
# logistic regression
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("fit_intercept", "standardize",
                                             "max_iter", "use_l1"))
def _fit_binary_logistic(X, y, reg, alpha, *, fit_intercept: bool,
                         standardize: bool, max_iter: int, use_l1: bool):
    return binary_logistic_core(
        X, y, jnp.ones(X.shape[0], X.dtype), reg, alpha,
        fit_intercept=fit_intercept, standardize=standardize,
        max_iter=max_iter, use_l1=use_l1)


@functools.partial(jax.jit, static_argnames=("fit_intercept", "standardize",
                                             "max_iter", "use_l1", "k"))
def _fit_multinomial_logistic(X, y, reg, alpha, *, k: int,
                              fit_intercept: bool, standardize: bool,
                              max_iter: int, use_l1: bool):
    return multinomial_logistic_core(
        X, y, jnp.ones(X.shape[0], X.dtype), reg, alpha, k=k,
        fit_intercept=fit_intercept, standardize=standardize,
        max_iter=max_iter, use_l1=use_l1)


def _grid_to_reg_alpha(estimator, grid,
                       allowed=("reg_param", "elastic_net_param")):
    """(G, 2) [reg, alpha] array from grid dicts; params a dict omits
    fall back to the ESTIMATOR's configured values — matching what the
    sequential path's ``with_params`` produces. NotImplementedError for
    params the batched kernel can't trace (validator falls back to the
    sequential per-candidate path)."""
    out = np.zeros((len(grid), 2))
    for i, params in enumerate(grid):
        extra = set(params) - set(allowed)
        if extra:
            raise NotImplementedError(
                f"batched kernel cannot vary {sorted(extra)}")
        out[i, 0] = params.get("reg_param", getattr(estimator, "reg_param",
                                                    0.0))
        out[i, 1] = params.get("elastic_net_param",
                               getattr(estimator, "elastic_net_param", 0.0))
    return out


class LogisticRegression(Predictor):
    """Binomial/multinomial logistic regression
    (reference OpLogisticRegression.scala:45)."""

    def __init__(self, reg_param: float = 0.0, elastic_net_param: float = 0.0,
                 max_iter: int = 100, tol: float = 1e-6,
                 fit_intercept: bool = True, standardization: bool = True,
                 uid: Optional[str] = None):
        super().__init__(uid=uid)
        self.reg_param = reg_param
        self.elastic_net_param = elastic_net_param
        self.max_iter = max_iter
        self.tol = tol
        self.fit_intercept = fit_intercept
        self.standardization = standardization

    def fit_arrays(self, X: np.ndarray, y: np.ndarray
                   ) -> "LogisticRegressionModel":
        Xj = jnp.asarray(X)
        yj = jnp.asarray(y)
        k = int(np.max(y)) + 1 if len(y) else 2
        use_l1 = self.reg_param * self.elastic_net_param > 0
        if k <= 2:
            w, b = _fit_binary_logistic(
                Xj, yj, self.reg_param, self.elastic_net_param,
                fit_intercept=self.fit_intercept,
                standardize=self.standardization,
                max_iter=self.max_iter, use_l1=use_l1)
        else:
            w, b = _fit_multinomial_logistic(
                Xj, yj, self.reg_param, self.elastic_net_param, k=k,
                fit_intercept=self.fit_intercept,
                standardize=self.standardization,
                max_iter=self.max_iter, use_l1=use_l1)
        return LogisticRegressionModel(coefficients=np.asarray(w),
                                       intercept=np.asarray(b))

    def _fold_grid_kind(self, y) -> dict:
        """The fold-grid kernel of these labels (parallel/cv.py
        LINEAR_KERNELS): the binomial core for two classes, as
        ``fit_arrays`` takes it, else the multinomial one over ``k``."""
        k = int(np.max(y)) + 1 if len(y) else 2
        return ({"kind": "logistic"} if k <= 2
                else {"kind": "softmax", "k": k})

    def fit_fold_grid_arrays(self, X, y, masks, grid, mesh=None):
        """All (fold, grid point) candidates in one batched XLA program
        (optionally sharded over a ("models", "data") mesh) — reference
        OpValidator.scala:270-310 task parallelism. More than two classes
        fit the multinomial lanes (``jit_softmax_batched``)."""
        from ..parallel.cv import fit_linear_fold_grid
        ga = _grid_to_reg_alpha(self, grid)
        params = fit_linear_fold_grid(
            X=X, y=y, masks=masks, grid=ga, mesh=mesh,
            fit_intercept=self.fit_intercept,
            standardize=self.standardization, max_iter=self.max_iter,
            **self._fold_grid_kind(y))
        d = X.shape[1]
        return [[LogisticRegressionModel(p[..., :d], p[..., d]) for p in row]
                for row in params]

    def eval_fold_grid_arrays(self, X, y, masks, grid, X_val, y_val,
                              spec, mesh=None, cand_idx=None):
        """Device-resident search: fit + validation metric for every
        candidate in one program, (F, G) metric matrix out (see
        parallel/cv.eval_linear_fold_grid): binary margins, or the
        softmax of K logits under a multiclass metric.
        ``cand_idx`` (racing rungs) restricts to a candidate subset —
        the (reg, alpha) vectors stay traced values, so subsetting is a
        shape change, never a retrace of new statics."""
        kind = self._fold_grid_kind(y)
        if spec[0] not in ("binary", "multiclass"):
            raise NotImplementedError(
                "logistic device eval needs a classification metric")
        if spec[0] == "binary" and "k" in kind:
            raise NotImplementedError(
                "binary device eval needs binary labels")
        from ..parallel.cv import eval_linear_fold_grid
        ga = _grid_to_reg_alpha(self, subset_grid(grid, cand_idx))
        return eval_linear_fold_grid(
            X=X, y=y, masks=masks, grid=ga, X_val=X_val, y_val=y_val,
            spec=spec, mesh=mesh, fit_intercept=self.fit_intercept,
            standardize=self.standardization, max_iter=self.max_iter,
            **kind)


class LogisticRegressionModel(ClassifierModel):
    def __init__(self, coefficients, intercept, uid: Optional[str] = None):
        super().__init__(uid=uid)
        self.coefficients = np.asarray(coefficients, dtype=np.float64)
        self.intercept = np.asarray(intercept, dtype=np.float64)

    def predict_raw(self, X: np.ndarray) -> np.ndarray:
        if self.coefficients.ndim == 1:
            m = X @ self.coefficients + float(self.intercept)
            return np.stack([-m, m], axis=1)
        return X @ self.coefficients.T + self.intercept

    def raw_arrays(self, X):
        c = jnp.asarray(self.coefficients, X.dtype)
        if self.coefficients.ndim == 1:
            m = X @ c + float(self.intercept)
            return jnp.stack([-m, m], axis=1)
        return _dot(X, c.T) + jnp.asarray(self.intercept, X.dtype)


# ---------------------------------------------------------------------------
# linear regression
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("fit_intercept", "standardize",
                                             "max_iter", "use_l1"))
def _fit_linear_regression(X, y, reg, alpha, *, fit_intercept: bool,
                           standardize: bool, max_iter: int, use_l1: bool):
    return linear_regression_core(
        X, y, jnp.ones(X.shape[0], X.dtype), reg, alpha,
        fit_intercept=fit_intercept, standardize=standardize,
        max_iter=max_iter, use_l1=use_l1)


class LinearRegression(Predictor):
    """OLS / ridge / elastic-net linear regression
    (reference OpLinearRegression.scala)."""

    def __init__(self, reg_param: float = 0.0, elastic_net_param: float = 0.0,
                 max_iter: int = 100, tol: float = 1e-6,
                 fit_intercept: bool = True, standardization: bool = True,
                 uid: Optional[str] = None):
        super().__init__(uid=uid)
        self.reg_param = reg_param
        self.elastic_net_param = elastic_net_param
        self.max_iter = max_iter
        self.tol = tol
        self.fit_intercept = fit_intercept
        self.standardization = standardization

    def fit_arrays(self, X: np.ndarray, y: np.ndarray
                   ) -> "LinearRegressionModel":
        use_l1 = self.reg_param * self.elastic_net_param > 0
        w, b = _fit_linear_regression(
            jnp.asarray(X), jnp.asarray(y), self.reg_param,
            self.elastic_net_param, fit_intercept=self.fit_intercept,
            standardize=self.standardization, max_iter=self.max_iter,
            use_l1=use_l1)
        return LinearRegressionModel(coefficients=np.asarray(w),
                                     intercept=float(b))

    def fit_fold_grid_arrays(self, X, y, masks, grid, mesh=None):
        """All (fold, grid point) candidates in one batched XLA program
        (optionally mesh-sharded); same core as fit_arrays."""
        from ..parallel.cv import fit_linear_fold_grid
        ga = _grid_to_reg_alpha(self, grid)
        params = fit_linear_fold_grid(
            "squared", X, y, masks, ga, mesh=mesh,
            fit_intercept=self.fit_intercept,
            standardize=self.standardization, max_iter=self.max_iter)
        d = X.shape[1]
        return [[LinearRegressionModel(p[:d], float(p[d])) for p in row]
                for row in params]

    def eval_fold_grid_arrays(self, X, y, masks, grid, X_val, y_val,
                              spec, mesh=None, cand_idx=None):
        """Device-resident search (see LogisticRegression); predicted
        values feed the regression metric kernel."""
        if spec[0] != "regression":
            raise NotImplementedError(
                "linear-regression device eval needs a regression metric")
        from ..parallel.cv import eval_linear_fold_grid
        ga = _grid_to_reg_alpha(self, subset_grid(grid, cand_idx))
        return eval_linear_fold_grid(
            "squared", X, y, masks, ga, X_val, y_val, spec, mesh=mesh,
            fit_intercept=self.fit_intercept,
            standardize=self.standardization, max_iter=self.max_iter)


class LinearRegressionModel(RegressionModel):
    def __init__(self, coefficients, intercept: float = 0.0,
                 uid: Optional[str] = None):
        super().__init__(uid=uid)
        self.coefficients = np.asarray(coefficients, dtype=np.float64)
        self.intercept = float(intercept)

    def predict_values(self, X: np.ndarray) -> np.ndarray:
        return X @ self.coefficients + self.intercept

    def raw_arrays(self, X):
        return X @ jnp.asarray(self.coefficients, X.dtype) + self.intercept


# ---------------------------------------------------------------------------
# linear SVC
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("fit_intercept", "standardize",
                                             "max_iter"))
def _fit_linear_svc(X, y, reg, *, fit_intercept: bool, standardize: bool,
                    max_iter: int):
    return linear_svc_core(
        X, y, jnp.ones(X.shape[0], X.dtype), reg, 0.0,
        fit_intercept=fit_intercept, standardize=standardize,
        max_iter=max_iter)


class LinearSVC(Predictor):
    """Linear support-vector classifier (reference OpLinearSVC.scala)."""

    def __init__(self, reg_param: float = 0.0, max_iter: int = 100,
                 tol: float = 1e-6, fit_intercept: bool = True,
                 standardization: bool = True, uid: Optional[str] = None):
        super().__init__(uid=uid)
        self.reg_param = reg_param
        self.max_iter = max_iter
        self.tol = tol
        self.fit_intercept = fit_intercept
        self.standardization = standardization

    def fit_arrays(self, X: np.ndarray, y: np.ndarray) -> "LinearSVCModel":
        w, b = _fit_linear_svc(
            jnp.asarray(X), jnp.asarray(y), self.reg_param,
            fit_intercept=self.fit_intercept,
            standardize=self.standardization, max_iter=self.max_iter)
        return LinearSVCModel(coefficients=np.asarray(w), intercept=float(b))

    def fit_fold_grid_arrays(self, X, y, masks, grid, mesh=None):
        """All (fold, grid point) candidates in one batched XLA program
        (optionally mesh-sharded); same core as fit_arrays."""
        from ..parallel.cv import fit_linear_fold_grid
        ga = _grid_to_reg_alpha(self, grid, allowed=("reg_param",))
        params = fit_linear_fold_grid(
            "svc", X, y, masks, ga, mesh=mesh,
            fit_intercept=self.fit_intercept,
            standardize=self.standardization, max_iter=self.max_iter)
        d = X.shape[1]
        return [[LinearSVCModel(p[:d], float(p[d])) for p in row]
                for row in params]

    def eval_fold_grid_arrays(self, X, y, masks, grid, X_val, y_val,
                              spec, mesh=None, cand_idx=None):
        """Device-resident search (see LogisticRegression); SVC margins
        rank identically to the host raw-prediction score."""
        if spec[0] != "binary":
            raise NotImplementedError("SVC device eval is binary-only")
        from ..parallel.cv import eval_linear_fold_grid
        ga = _grid_to_reg_alpha(self, subset_grid(grid, cand_idx),
                                allowed=("reg_param",))
        return eval_linear_fold_grid(
            "svc", X, y, masks, ga, X_val, y_val, spec, mesh=mesh,
            fit_intercept=self.fit_intercept,
            standardize=self.standardization, max_iter=self.max_iter)


class LinearSVCModel(ClassifierModel):
    """SVC model: rawPrediction only, no probability (as in MLlib)."""

    def __init__(self, coefficients, intercept: float = 0.0,
                 uid: Optional[str] = None):
        super().__init__(uid=uid)
        self.coefficients = np.asarray(coefficients, dtype=np.float64)
        self.intercept = float(intercept)

    def predict_raw(self, X: np.ndarray) -> np.ndarray:
        m = X @ self.coefficients + self.intercept
        return np.stack([-m, m], axis=1)

    def raw_arrays(self, X):
        m = X @ jnp.asarray(self.coefficients, X.dtype) + self.intercept
        return jnp.stack([-m, m], axis=1)

    def prediction_from_raw(self, raw: np.ndarray) -> PredictionColumn:
        raw = np.asarray(raw, dtype=np.float64)
        pred = (raw[:, 1] > 0).astype(np.float64)
        return PredictionColumn.from_arrays(pred, raw_prediction=raw)

    def predict_arrays(self, X: np.ndarray) -> PredictionColumn:
        return self.prediction_from_raw(self.predict_raw(X))
