"""Fold x grid x data sharded model fitting — the multi-chip CV kernel.

This is the TPU mapping of the reference's model-selection parallelism
(SURVEY §2.9): the per-fold / per-estimator ``Future`` loop of
core/src/main/scala/com/salesforce/op/tuning/OpValidator.scala:270-310 and
OpCrossValidation.scala:100-117 becomes one SPMD program over a
``("models", "data")`` mesh:

- every (fold, grid point) candidate of a linear family becomes one slot
  on the flattened ``models`` axis (task parallelism: each chip trains
  its own chunk of candidates, vmapped into one batched XLA program on
  the MXU),
- the feature matrix is sharded over the ``data`` axis (row parallelism;
  gradient/covariance reductions are ``psum`` over ICI — the role Rabit
  allreduce plays for the reference's XGBoost),
- fold membership is a 0/1 row-weight mask, which makes every candidate
  the same static shape — the XLA-friendly equivalent of materializing k
  train/validation splits.

Crucially the per-candidate fit is the SAME weighted core the sequential
``models/linear.py`` estimators use (``binary_logistic_core`` etc.), so
the mesh path selects the same winner as the one-candidate-at-a-time
path.
"""
from __future__ import annotations

import functools
import os
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import shard_map
import numpy as np

from .mesh import to_host
from jax.sharding import Mesh, PartitionSpec as P

from ..observability import trace as _trace

from ..models.linear import (_LANE_PRECISION, binary_logistic_core,
                             lane_designs, linear_regression_core,
                             linear_svc_core, multinomial_logistic_core)

__all__ = ["fold_masks", "fit_linear_fold_grid", "eval_linear_fold_grid",
           "models_mesh", "resolve_search_mesh", "mesh_model_shards",
           "LINEAR_KERNELS"]

#: kind -> weighted fit core (all share the signature
#: (X, y, w, reg, alpha, *, fit_intercept, standardize, max_iter,
#:  use_l1, axis_name) -> (coefficients, intercept)); "softmax" takes the
#: class count ``k`` besides and hands back (k, d) and (k,)
LINEAR_KERNELS = {
    "logistic": binary_logistic_core,
    "squared": linear_regression_core,
    "svc": linear_svc_core,
    "softmax": multinomial_logistic_core,
}


def fold_masks(n: int, n_folds: int, seed: int = 42,
               y: Optional[np.ndarray] = None) -> np.ndarray:
    """(n_folds, n) float masks: mask[f, i] = 1 if row i is in fold f's
    TRAIN set (i.e. row i's held-out fold != f). Stratified by ``y`` when
    given (reference OpCrossValidation.createTrainValidationSplits:139)."""
    rng = np.random.default_rng(seed)
    assign = np.empty(n, dtype=np.int64)
    if y is None:
        assign[:] = rng.permutation(n) % n_folds
    else:
        for cls in np.unique(y):
            idx = np.nonzero(y == cls)[0]
            assign[idx] = rng.permutation(len(idx)) % n_folds
    return (assign[None, :] != np.arange(n_folds)[:, None]).astype(np.float64)


def models_mesh(devices: Optional[Sequence] = None,
                data_shards: int = 1) -> Mesh:
    """Mesh for candidate-parallel model selection: ``models`` x ``data``.

    ``models`` carries the flattened fold x grid candidate axis (the
    reference's per-estimator Future pool, OpValidator.scala:270-310);
    ``data`` carries row parallelism within each candidate fit."""
    from .mesh import make_mesh
    devices = list(devices if devices is not None else jax.devices())
    nd = len(devices)
    if nd % data_shards:
        raise ValueError(f"data_shards={data_shards} must divide {nd}")
    return make_mesh({"models": nd // data_shards, "data": data_shards},
                     devices)


#: resolved (platform, n_devices, data_shards) -> Mesh — one mesh per
#: process configuration, so every search (and every lru_cache'd kernel
#: keyed by it) shares ONE mesh object instead of churning the kernel
#: caches with per-search instances
_SEARCH_MESH_CACHE: Dict[tuple, Mesh] = {}


def resolve_search_mesh(policy="auto") -> Optional[Mesh]:
    """The mesh the selector shards the fold x grid candidate axis over.

    ``policy`` is what ``_ValidatorBase(mesh=...)`` was given:

    - a ``jax.sharding.Mesh`` — used as-is,
    - ``None`` — force the local single-device path,
    - ``"auto"`` (the default) — consult ``TX_SEARCH_MESH``:
      ``"auto"``/unset shards over every visible device (local path when
      only one is visible), ``"off"``/``"0"``/``"local"`` disables
      sharding, an integer uses that many devices.

    The ``data`` axis defaults to 1 shard (``TX_SEARCH_DATA_SHARDS``
    overrides): row sharding changes gradient-psum reduction order, and
    the search's contract is BITWISE invariance across device counts —
    candidate-axis sharding keeps every candidate's arithmetic identical
    to the single-device program, so a 1-chip and an 8-chip search pick
    the same winner to the last bit (tests/test_sharded_search.py).

    Resolution is lazy and cheap to repeat, but callers should invoke it
    only at search time — touching ``jax.devices()`` initializes the
    backend, which must not happen while a workflow DAG is merely being
    constructed.
    """
    if policy is None or isinstance(policy, Mesh):
        return policy
    spec = str(policy).strip().lower()
    if spec in ("auto", ""):
        spec = os.environ.get("TX_SEARCH_MESH", "auto").strip().lower() \
            or "auto"
    if spec in ("off", "none", "local", "0", "1"):
        return None
    devices = jax.devices()
    if spec == "auto":
        n = len(devices)
    else:
        try:
            n = int(spec)
        except ValueError:
            raise ValueError(
                f"TX_SEARCH_MESH / mesh policy must be 'auto', 'off' or "
                f"a device count, got {policy!r}")
        n = min(n, len(devices))
    if n < 2:
        return None
    data = int(os.environ.get("TX_SEARCH_DATA_SHARDS", "1") or "1")
    if data < 1 or n % data:
        data = 1
    key = (devices[0].platform, n, data)
    mesh = _SEARCH_MESH_CACHE.get(key)
    if mesh is None:
        mesh = models_mesh(devices[:n], data_shards=data)
        _SEARCH_MESH_CACHE[key] = mesh
    return mesh


def mesh_model_shards(mesh: Optional[Mesh]) -> int:
    """Shard count of the candidate (``models``) axis — 1 without a
    mesh. The racing scheduler pads each rung's candidate subset to a
    multiple of this so rung programs stay shape-stable across alive
    counts (models/base.pad_cand_idx)."""
    if mesh is None:
        return 1
    return int(mesh.shape.get("models", 1))


def _device_float(a):
    """``a`` as the device array the kernels take, in the canonical float
    dtype (float32 unless x64 is on): a device array is converted where it
    lives, never round-tripped through the host; a host one goes through
    float64 first, as it always has. The same values, dtype and shape
    either way, so the same compiled program."""
    if isinstance(a, jax.Array):
        return a.astype(jax.dtypes.canonicalize_dtype(np.float64))
    return jnp.asarray(np.asarray(a, dtype=np.float64))


def _fetch_span(lanes: int):
    """The ``search.fetch`` span of the linear fold-grid functions,
    carrying :func:`linear.lane_designs` as ``design_shared`` /
    ``design_per_lane`` (see trace.counted_span) and ``lanes``, the call's
    (fold, grid point) lanes."""
    return _trace.counted_span("search.fetch", (("design_", lane_designs),),
                               lanes=lanes)


def fit_linear_fold_grid(kind: str, X: np.ndarray, y: np.ndarray,
                         masks: np.ndarray, grid: np.ndarray, *,
                         mesh: Optional[Mesh] = None,
                         fit_intercept: bool = True,
                         standardize: bool = True,
                         max_iter: int = 100,
                         k: Optional[int] = None) -> np.ndarray:
    """Fit every (fold, grid point) candidate of one linear family.

    kind   : "logistic" | "squared" | "svc" | "softmax" (see
             LINEAR_KERNELS); "softmax" needs the class count ``k``
    masks  : (F, n) 0/1 train-row masks (1 = row in the fold's train set)
    grid   : (G, 2) columns (reg_param, elastic_net_param)
    mesh   : optional ("models", "data") mesh — without one, the whole
             fold x grid batch still runs as ONE vmapped XLA program on
             the local device.

    Returns (F, G, d+1) parameters, [..., :d] coefficients + [..., d]
    intercept, in the ORIGINAL feature space; (F, G, k, d+1) for "softmax".
    """
    with _trace.span("search.head"):
        X = _device_float(X)
        y = np.asarray(y, dtype=np.float64)
        masks = np.asarray(masks, dtype=np.float64)
        grid = np.asarray(grid, dtype=np.float64).reshape(-1, 2)
        F, n = masks.shape
        G, d = grid.shape[0], X.shape[1]
        use_l1 = bool(np.any(grid[:, 0] * grid[:, 1] > 0))
        cfg = _kernel_cfg(kind, use_l1, fit_intercept, standardize,
                          max_iter, k)
        lane = (d + 1,) if k is None else (k, d + 1)

        # flatten candidates fold-major: slot f*G + g = (fold f, grid g)
        regs = np.tile(grid[:, 0], F)
        alphas = np.tile(grid[:, 1], F)
        wmat = np.repeat(masks, G, axis=0)            # (F*G, n)

    if mesh is None:
        fn = _local_kernel(cfg)
        with _fetch_span(F * G):
            params = fn(jnp.asarray(wmat), jnp.asarray(regs),
                        jnp.asarray(alphas), jnp.asarray(X), jnp.asarray(y))
            return np.asarray(params).reshape(F, G, *lane)

    m_shards = mesh.shape["models"]
    d_shards = mesh.shape.get("data", 1)
    FG = F * G
    pad_c = (-FG) % m_shards                       # pad candidate axis
    if pad_c:
        wmat = np.concatenate([wmat, np.ones((pad_c, n))], axis=0)
        regs = np.concatenate([regs, np.zeros(pad_c)])
        alphas = np.concatenate([alphas, np.zeros(pad_c)])
    pad_r = (-n) % d_shards                        # pad row axis
    if pad_r:
        X = jnp.concatenate([X, jnp.zeros((pad_r, d), X.dtype)], axis=0)
        y = np.concatenate([y, np.zeros(pad_r)])
        wmat = np.concatenate(
            [wmat, np.zeros((wmat.shape[0], pad_r))], axis=1)

    fn = _mesh_kernel(cfg, mesh)
    with _fetch_span(F * G):
        params = fn(jnp.asarray(wmat), jnp.asarray(regs),
                    jnp.asarray(alphas), jnp.asarray(X), jnp.asarray(y))
        return to_host(params)[:FG].reshape(F, G, *lane)


def eval_linear_fold_grid(kind: str, X: np.ndarray, y: np.ndarray,
                          masks: np.ndarray, grid: np.ndarray,
                          X_val: np.ndarray, y_val: np.ndarray,
                          spec: tuple, *,
                          mesh: Optional[Mesh] = None,
                          fit_intercept: bool = True,
                          standardize: bool = True,
                          max_iter: int = 100,
                          k: Optional[int] = None) -> np.ndarray:
    """Fit AND evaluate every (fold, grid point) candidate in ONE device
    program, returning only the (F, G) validation-metric matrix.

    This is the device-resident replacement for the reference's
    fit-then-evaluate grid loop (OpValidator.scala:293-295): fitted
    parameters never leave the device — the selector refits only the
    winner afterwards — so a remote-TPU search transfers a few hundred
    bytes instead of every candidate's coefficients.

    X_val : (F, nv, d) per-fold validation rows (equal-sized folds,
            see _ValidatorBase._assignments)
    y_val : (F, nv) validation labels
    spec  : (kind, metric) for evaluators.device_metrics.metric_fn —
            "binary" uses decision margins, "regression" raw values,
            "multiclass" the softmax of the lane's logits.
    """
    with _trace.span("search.head"):
        X = _device_float(X)
        y = np.asarray(y, dtype=np.float64)
        masks = np.asarray(masks, dtype=np.float64)
        grid = np.asarray(grid, dtype=np.float64).reshape(-1, 2)
        F, n = masks.shape
        G, d = grid.shape[0], X.shape[1]
        use_l1 = bool(np.any(grid[:, 0] * grid[:, 1] > 0))
        cfg = _kernel_cfg(kind, use_l1, fit_intercept, standardize,
                          max_iter, k)

        regs = np.tile(grid[:, 0], F)
        alphas = np.tile(grid[:, 1], F)
        wmat = np.repeat(masks, G, axis=0)            # (F*G, n)
        fidx = np.repeat(np.arange(F, dtype=np.int32), G)
        Xv = _device_float(X_val)
        yv = jnp.asarray(np.asarray(y_val, dtype=np.float64))

    if mesh is None:
        fn = _local_eval_kernel(cfg, spec)
        with _fetch_span(F * G):
            mm = fn(jnp.asarray(wmat), jnp.asarray(regs),
                    jnp.asarray(alphas), jnp.asarray(fidx), jnp.asarray(X),
                    jnp.asarray(y), Xv, yv)
            return np.asarray(mm).reshape(F, G)

    m_shards = mesh.shape["models"]
    d_shards = mesh.shape.get("data", 1)
    FG = F * G
    pad_c = (-FG) % m_shards
    if pad_c:
        wmat = np.concatenate([wmat, np.ones((pad_c, n))], axis=0)
        regs = np.concatenate([regs, np.zeros(pad_c)])
        alphas = np.concatenate([alphas, np.zeros(pad_c)])
        fidx = np.concatenate([fidx, np.zeros(pad_c, dtype=np.int32)])
    pad_r = (-n) % d_shards
    if pad_r:
        X = jnp.concatenate([X, jnp.zeros((pad_r, d), X.dtype)], axis=0)
        y = np.concatenate([y, np.zeros(pad_r)])
        wmat = np.concatenate(
            [wmat, np.zeros((wmat.shape[0], pad_r))], axis=1)
    fn = _mesh_eval_kernel(cfg, spec, mesh)
    with _fetch_span(F * G):
        mm = fn(jnp.asarray(wmat), jnp.asarray(regs), jnp.asarray(alphas),
                jnp.asarray(fidx), jnp.asarray(X), jnp.asarray(y), Xv, yv)
        return to_host(mm)[:FG].reshape(F, G)


def _kernel_cfg(kind, use_l1, fit_intercept, standardize, max_iter, k):
    """The statics a fold-grid kernel is cached by; ``k`` (classes) is part
    of them for the "softmax" kind and of no other."""
    if (kind == "softmax") != (k is not None):
        raise ValueError(f"kind {kind!r} with k={k!r}: the class count "
                         f"belongs to the softmax kind, and only to it")
    cfg = (kind, use_l1, fit_intercept, standardize, max_iter)
    return cfg if k is None else cfg + (k,)


def _candidate_eval(cfg, spec, params, fi, Xv, yv):
    """Validation metric for one fitted candidate against its fold's
    validation rows, using the host model's exact score semantics:
    logistic ranks by softmax probability of the [-m, m] raw pair, SVC
    by the raw margin (no probability, as in MLlib), regression by the
    predicted values; a multiclass metric takes the softmax of the
    lane's raw scores (K logits, or the [-m, m] pair of a two-class
    lane), as ``LogisticRegressionModel.predict_raw`` hands them on."""
    from ..evaluators.device_metrics import (binary_from_raw_pair,
                                             metric_fn,
                                             softmax_probability)
    d = Xv.shape[-1]
    with jax.named_scope("fg.metric"):
        if cfg[0] == "softmax":
            W = params.reshape(cfg[5], d + 1)
            raw = jnp.matmul(Xv[fi], W[:, :d].T,
                             precision=_LANE_PRECISION) + W[:, d]
        else:
            m = Xv[fi] @ params[:d] + params[d]
        if spec[0] == "multiclass":
            scores = softmax_probability(
                raw if cfg[0] == "softmax" else jnp.stack([-m, m], axis=1))
        elif spec[0] == "binary":
            if cfg[0] == "svc":
                scores = (m, (m > 0).astype(m.dtype))
            else:
                scores = binary_from_raw_pair(jnp.stack([-m, m], axis=1))
        else:
            scores = m
        return metric_fn(*spec)(yv[fi], scores)


# The four programs below are named ``jit_linear_batched`` (the function a
# ``jax.jit`` wraps names the program), so a profile tells the linear
# fold-grid programs from the tree families' ``jit_batched`` and
# ``jit_forest_batched``; their bodies trace under the scope ``fg.linear``.
# The multinomial lanes' are ``jit_softmax_batched`` under ``fg.softmax``:
# what reads the binary kinds' programs by name goes on reading only those.

def _program(cfg, body):
    """``body`` under its kind's scope, in a function of its program's
    name."""
    if cfg[0] == "softmax":
        def softmax_batched(*args):
            with jax.named_scope("fg.softmax"):
                return body(*args)
        return softmax_batched

    def linear_batched(*args):
        with jax.named_scope("fg.linear"):
            return body(*args)
    return linear_batched


@functools.lru_cache(maxsize=32)
def _local_eval_kernel(cfg, spec):
    def one(w, r, a, fi, X_, y_, Xv, yv):
        params = _candidate_fit(cfg, w, r, a, X_, y_)
        return _candidate_eval(cfg, spec, params, fi, Xv, yv)

    return jax.jit(_program(cfg, jax.vmap(
        one, in_axes=(0, 0, 0, 0, None, None, None, None))))


@functools.lru_cache(maxsize=32)
def _mesh_eval_kernel(cfg, spec, mesh):
    data_ax = "data" if "data" in mesh.axis_names else None

    def body(w_loc, r_loc, a_loc, fi_loc, X_loc, y_loc, Xv, yv):
        def one(w, r, a, fi):
            params = _candidate_fit(cfg, w, r, a, X_loc, y_loc,
                                    axis_name=data_ax)
            # params are psum-complete (identical on every data shard),
            # and Xv/yv replicate — the metric is data-axis-invariant
            return _candidate_eval(cfg, spec, params, fi, Xv, yv)
        return jax.vmap(one)(w_loc, r_loc, a_loc, fi_loc)

    return jax.jit(shard_map(
        _program(cfg, body), mesh=mesh,
        in_specs=(P("models", data_ax), P("models"), P("models"),
                  P("models"), P(data_ax, None), P(data_ax), P(), P()),
        out_specs=P("models"), check_vma=False))


def _candidate_fit(cfg, w, reg, alpha, X_, y_, axis_name=None):
    """One lane's parameters, flat: ``(d + 1,)`` coefficients then
    intercept, or the softmax kind's ``(k, d + 1)`` rows one after another
    (``(k * (d + 1),)``)."""
    kind, use_l1, fit_intercept, standardize, max_iter = cfg[:5]
    more = {"k": cfg[5]} if kind == "softmax" else {}
    # solver="fista": static trip count so the mesh and local batched
    # paths are bit-identical and collectives stay in lockstep
    coef, b = LINEAR_KERNELS[kind](
        X_, y_, w, reg, alpha, fit_intercept=fit_intercept,
        standardize=standardize, max_iter=max_iter,
        use_l1=use_l1, axis_name=axis_name, solver="fista", **more)
    if kind == "softmax":
        return jnp.concatenate([coef, b[:, None]], axis=1).reshape(-1)
    return jnp.concatenate([jnp.reshape(coef, (-1,)),
                            jnp.reshape(b, (1,))])


# jitted-kernel caches: one compiled program per (config, shapes) — NOT
# per fit_linear_fold_grid call (a fresh closure per call would defeat
# the jit cache and recompile every fold of a workflow-CV search).
# Bounded (here and in the other family kernels) so long-lived processes
# that recreate meshes per workflow don't pin every mesh's device
# handles forever via cache keys.

@functools.lru_cache(maxsize=32)
def _local_kernel(cfg):
    return jax.jit(_program(cfg, jax.vmap(
        lambda w, r, a, X_, y_: _candidate_fit(cfg, w, r, a, X_, y_),
        in_axes=(0, 0, 0, None, None))))


@functools.lru_cache(maxsize=32)
def _mesh_kernel(cfg, mesh):
    # a mesh may be candidate-only (no "data" axis): rows then stay
    # unsharded and the fit cores run without a psum axis
    data_ax = "data" if "data" in mesh.axis_names else None

    def body(w_loc, r_loc, a_loc, X_loc, y_loc):
        # w_loc: (FG_local, n_local) — vmap candidates, psum row shards
        return jax.vmap(
            lambda w, r, a: _candidate_fit(cfg, w, r, a, X_loc, y_loc,
                                           axis_name=data_ax)
        )(w_loc, r_loc, a_loc)

    # check_vma=False because solver state inits (zeros) are axis-
    # invariant; gradient correctness under it comes from the SHARD-LOCAL
    # objective + explicit grad psum in fista_minimize — autodiff never
    # transposes a collective (silently wrong with vma checking off)
    return jax.jit(shard_map(
        _program(cfg, body), mesh=mesh,
        in_specs=(P("models", data_ax), P("models"), P("models"),
                  P(data_ax, None), P(data_ax)),
        out_specs=P("models", None), check_vma=False))
