"""Naive Bayes classifier.

TPU-native replacement for the reference's OpNaiveBayes
(core/.../classification/OpNaiveBayes.scala), wrapping MLlib NaiveBayes
(multinomial or bernoulli model type, additive smoothing). The fit is a
pair of segment-sums over class labels — one XLA program, no iteration.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
import numpy as np

from ..observability import trace as _trace
from .base import (ClassifierModel, FamilyPreconditionError,
                   Predictor, check_fold_classes, num_classes,
                   subset_grid)

__all__ = ["NaiveBayes", "NaiveBayesModel"]


def _nb_closed_form(X, labels, mask, sm, num_classes: int,
                    model_type: str):
    """The one definition of the NB closed form (MLlib formulas):
    mask-weighted class counts + feature sums; ``mask`` of ones is the
    plain (sequential) fit. ``X`` must already be binarized for the
    bernoulli model type."""
    counts = jax.ops.segment_sum(mask, labels, num_segments=num_classes)
    pi = jnp.log(counts) - jnp.log(jnp.sum(counts))
    feat = jax.ops.segment_sum(X * mask[:, None], labels,
                               num_segments=num_classes)       # (K, d)
    if model_type == "bernoulli":
        theta = (jnp.log(feat + sm)
                 - jnp.log(counts[:, None] + 2.0 * sm))
    else:  # multinomial
        theta = (jnp.log(feat + sm)
                 - jnp.log(jnp.sum(feat, axis=1, keepdims=True)
                           + sm * X.shape[1]))
    return pi, theta


@functools.partial(jax.jit, static_argnames=("num_classes", "model_type"))
def _fit_nb(X, y, smoothing, *, num_classes: int, model_type: str):
    labels = y.astype(jnp.int32)
    if model_type == "bernoulli":
        X = (X != 0).astype(X.dtype)
    return _nb_closed_form(X, labels, jnp.ones_like(y), smoothing,
                           num_classes, model_type)


def _nb_masked_body(X, y, masks, smoothing, *, num_classes: int,
                    model_type: str):
    """Fold x grid candidates as one vmapped program: candidate =
    (fold mask, traced smoothing); mask-weighted class/feature sums
    equal the per-fold subset sums, so each lane reproduces the
    sequential fit up to summation order."""
    labels = y.astype(jnp.int32)
    if model_type == "bernoulli":
        X = (X != 0).astype(X.dtype)

    def one(mask, sm):
        return _nb_closed_form(X, labels, mask, sm, num_classes,
                               model_type)

    with jax.named_scope("fg.bayes"):
        return jax.vmap(one)(masks, smoothing)


def _nb_raw(pi, theta, Xv, model_type: str):
    """(nv, K) log-joint scores — the device twin of
    NaiveBayesModel.predict_raw. The products are float32 in full on a
    TPU too: a log-joint is a sum of raw feature values times log-shares
    (thousands times ten), and the one bf16 pass of the chip's default
    rounds a feature of 2,960 to a multiple of 16, which moved 0.7 % of a
    Covertype-shaped fold's argmaxes (PERF.md, PR 32)."""
    def product(a, b):
        return jnp.matmul(a, b.T, precision=jax.lax.Precision.HIGHEST)

    if model_type == "bernoulli":
        Xb = (Xv != 0).astype(theta.dtype)
        neg = jnp.log1p(-jnp.minimum(jnp.exp(theta), 1 - 1e-12))
        return pi + product(Xb, theta) + product(1.0 - Xb, neg)
    return pi + product(Xv, theta)


def _nb_eval_body(X, y, masks, smoothing, fidx, Xv, yv, *,
                  num_classes: int, model_type: str, spec: tuple):
    """Fused fit + validation metric per candidate (device-resident
    search — see evaluators/device_metrics.py). Binary margins are the
    log-joint difference (argmax parity with the host softmax)."""
    from ..evaluators.device_metrics import (binary_from_raw_pair,
                                             metric_fn,
                                             softmax_probability)
    mfn = metric_fn(*spec)
    labels = y.astype(jnp.int32)
    Xf = (X != 0).astype(X.dtype) if model_type == "bernoulli" else X

    def one(mask, sm, fi):
        pi, theta = _nb_closed_form(Xf, labels, mask, sm, num_classes,
                                    model_type)
        with jax.named_scope("fg.metric"):
            raw = _nb_raw(pi, theta, Xv[fi], model_type)
            # host NaiveBayesModel ranks by the softmax of the log-joints
            scores = (binary_from_raw_pair(raw) if spec[0] == "binary"
                      else softmax_probability(raw))
            return mfn(yv[fi], scores)

    with jax.named_scope("fg.bayes"):
        return jax.vmap(one)(masks, smoothing, fidx)


# The two kernels below are programs named ``jit_bayes_batched`` (the
# function a ``jax.jit`` wraps names the program) whose bodies trace under
# the scope ``fg.bayes``, as the other families' fold-grid programs have a
# name and a scope of their own (docs/observability.md).

@functools.lru_cache(maxsize=32)
def _nb_eval_kernel(num_classes: int, model_type: str, spec: tuple,
                    mesh=None):
    """Fit + metric of every candidate; with a mesh the candidate axis is
    sharded over its ``models`` axis and X/y replicate."""
    def bayes_batched(masks, smoothing, fidx, X, y, Xv, yv):
        return _nb_eval_body(X, y, masks, smoothing, fidx, Xv, yv,
                             num_classes=num_classes,
                             model_type=model_type, spec=spec)

    if mesh is None:
        return jax.jit(bayes_batched)
    from jax.sharding import PartitionSpec as P
    return jax.jit(shard_map(
        bayes_batched, mesh=mesh,
        in_specs=(P("models", None), P("models"), P("models"),
                  P(), P(), P(), P()),
        out_specs=P("models"), check_vma=False))


@functools.lru_cache(maxsize=32)
def _nb_fit_kernel(num_classes: int, model_type: str, mesh=None):
    """(pi, theta) of every candidate (same mapping onto a mesh as the
    other family kernels)."""
    def bayes_batched(masks, smoothing, X, y):
        return _nb_masked_body(X, y, masks, smoothing,
                               num_classes=num_classes,
                               model_type=model_type)

    if mesh is None:
        return jax.jit(bayes_batched)
    from jax.sharding import PartitionSpec as P
    return jax.jit(shard_map(
        bayes_batched, mesh=mesh,
        in_specs=(P("models", None), P("models"), P(), P()),
        out_specs=(P("models", None), P("models", None, None)),
        check_vma=False))


class NaiveBayes(Predictor):
    """Multinomial/Bernoulli naive Bayes (reference OpNaiveBayes.scala).
    Requires non-negative features, as in MLlib."""

    def __init__(self, smoothing: float = 1.0,
                 model_type: str = "multinomial",
                 uid: Optional[str] = None):
        super().__init__(uid=uid)
        self.smoothing = smoothing
        self.model_type = model_type

    def fit_fold_grid_arrays(self, X, y, masks, grid, mesh=None):
        """Validator fast path (see _ValidatorBase.validate): smoothing
        is traced, model_type groups statically; fold x grid candidates
        shard over the mesh ``models`` axis when a mesh is supplied
        (padded with all-ones masks)."""
        if (np.asarray(X) < 0).any():
            raise FamilyPreconditionError(
                "NaiveBayes requires non-negative features")
        grid = [dict(p) for p in (list(grid) or [{}])]
        allowed = {"smoothing", "model_type"}
        for p in grid:
            extra = set(p) - allowed
            if extra:
                raise NotImplementedError(
                    f"batched NaiveBayes kernel cannot vary {sorted(extra)}")
        masks = np.asarray(masks, dtype=np.float64)
        check_fold_classes(y, masks)
        k = num_classes(y)
        F = masks.shape[0]
        models = [[None] * len(grid) for _ in range(F)]
        groups = {}
        for gi, p in enumerate(grid):
            cand = self.with_params(**p)
            groups.setdefault(cand.model_type, []).append((gi, cand))
        X_j, y_j = jnp.asarray(X), jnp.asarray(y)
        from ..parallel.mesh import pad_candidates, to_host
        for model_type, members in groups.items():
            gk = len(members)
            sm = np.tile([float(c.smoothing) for _, c in members], F)
            masks_c = np.repeat(masks, gk, axis=0)   # fold-major
            (masks_c, sm), _ = pad_candidates(
                mesh, [masks_c, sm], masks_c.shape[1])
            pi, theta = _nb_fit_kernel(k, model_type, mesh)(
                jnp.asarray(masks_c), jnp.asarray(sm), X_j, y_j)
            pi, theta = to_host(pi), to_host(theta)
            for f in range(F):
                for j, (gi, _) in enumerate(members):
                    c = f * gk + j
                    models[f][gi] = NaiveBayesModel(
                        pi=pi[c], theta=theta[c], model_type=model_type)
        return models

    def eval_fold_grid_arrays(self, X, y, masks, grid, X_val, y_val,
                              spec, mesh=None, cand_idx=None):
        """Device-resident search: fused fit + validation metric, (F, G)
        matrix out (candidate grouping mirrors fit_fold_grid_arrays)."""
        if spec[0] not in ("binary", "multiclass"):
            raise NotImplementedError(
                "NaiveBayes device eval needs a classification metric")
        if (np.asarray(X) < 0).any():
            raise FamilyPreconditionError(
                "NaiveBayes requires non-negative features")
        k = num_classes(y)
        if spec[0] == "binary" and k != 2:
            raise NotImplementedError(
                "binary device eval needs binary labels")
        grid = [dict(p) for p in subset_grid(grid, cand_idx)]
        allowed = {"smoothing", "model_type"}
        for p in grid:
            extra = set(p) - allowed
            if extra:
                raise NotImplementedError(
                    f"batched NaiveBayes kernel cannot vary {sorted(extra)}")
        masks = np.asarray(masks, dtype=np.float64)
        check_fold_classes(y, masks)
        F = masks.shape[0]
        metric_mat = np.full((F, len(grid)), np.nan)
        groups = {}
        for gi, p in enumerate(grid):
            cand = self.with_params(**p)
            groups.setdefault(cand.model_type, []).append((gi, cand))
        X_j, y_j = jnp.asarray(X), jnp.asarray(y)
        Xv_j = jnp.asarray(np.asarray(X_val, dtype=np.float64))
        yv_j = jnp.asarray(np.asarray(y_val, dtype=np.float64))
        from ..parallel.mesh import pad_candidates, to_host
        for model_type, members in groups.items():
            gk = len(members)
            sm = np.tile([float(c.smoothing) for _, c in members], F)
            masks_c = np.repeat(masks, gk, axis=0)   # fold-major
            fidx = np.repeat(np.arange(F, dtype=np.int32), gk)
            (masks_c, sm), count = pad_candidates(
                mesh, [masks_c, sm], masks_c.shape[1])
            fidx = np.concatenate(
                [fidx, np.zeros(len(sm) - count, dtype=np.int32)])
            with _trace.span("search.fetch"):
                mm = to_host(_nb_eval_kernel(k, model_type, spec, mesh)(
                    jnp.asarray(masks_c), jnp.asarray(sm),
                    jnp.asarray(fidx), X_j, y_j, Xv_j, yv_j))[:count]
            for f in range(F):
                for j, (gi, _) in enumerate(members):
                    metric_mat[f, gi] = mm[f * gk + j]
        return metric_mat

    def fit_arrays(self, X: np.ndarray, y: np.ndarray) -> "NaiveBayesModel":
        if (X < 0).any():
            raise FamilyPreconditionError(
                "NaiveBayes requires non-negative features")
        k = num_classes(y)
        pi, theta = _fit_nb(jnp.asarray(X), jnp.asarray(y),
                            jnp.asarray(self.smoothing, dtype=jnp.float64),
                            num_classes=k, model_type=self.model_type)
        return NaiveBayesModel(pi=np.asarray(pi), theta=np.asarray(theta),
                               model_type=self.model_type)


class NaiveBayesModel(ClassifierModel):
    def __init__(self, pi, theta, model_type: str = "multinomial",
                 uid: Optional[str] = None):
        super().__init__(uid=uid)
        self.pi = np.asarray(pi, dtype=np.float64)          # (K,)
        self.theta = np.asarray(theta, dtype=np.float64)    # (K, d)
        self.model_type = model_type

    def predict_raw(self, X: np.ndarray) -> np.ndarray:
        if self.model_type == "bernoulli":
            Xb = (X != 0).astype(np.float64)
            neg = np.log1p(-np.minimum(np.exp(self.theta), 1 - 1e-12))
            return (self.pi + Xb @ self.theta.T
                    + (1.0 - Xb) @ neg.T)
        return self.pi + X @ self.theta.T

    def raw_arrays(self, X):
        return _nb_raw(jnp.asarray(self.pi, X.dtype),
                       jnp.asarray(self.theta, X.dtype), X, self.model_type)
