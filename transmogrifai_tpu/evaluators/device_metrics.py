"""On-device (XLA) validation metrics for the selector search.

The reference's CV grid loop evaluates every candidate on the driver
with a per-model ``evaluator.evaluate`` pass
(core/src/main/scala/com/salesforce/op/tuning/OpValidator.scala:295).
A literal port of that shape moves every candidate's fitted parameters
and predictions across the host<->device boundary, one round trip per
candidate. These kernels instead compute the metric IN
the same XLA program that fitted and predicted the candidates, so a
whole fold x grid search transfers one (folds, grid) float matrix per
family and nothing else.

Semantics match the host evaluators exactly (tie-aware Spark
``BinaryClassificationMetrics`` curves — see ``evaluators/binary.py``
``_curve_points`` — and label-frequency-weighted multiclass PRF):
the tie-grouped curve is reproduced with static shapes by REPLACING
every position's cumulative counts with the counts at its score-run's
end (computed by a reversed ``cummin`` over end-of-run indices); the
duplicated curve points then contribute zero-width trapezoids, which is
arithmetically the host's distinct-point curve plus exact zeros.

Everything here is pure ``jnp`` on traced values — safe to call inside
``jit`` / ``vmap`` / ``shard_map`` from the family fold x grid kernels.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

__all__ = ["BINARY_METRICS", "MULTICLASS_METRICS", "REGRESSION_METRICS",
           "binary_metric", "multiclass_metric", "regression_metric",
           "metric_fn", "softmax_probability", "binary_from_raw_pair",
           "binary_from_sigmoid", "binary_from_votes"]

BINARY_METRICS = ("AuPR", "AuROC", "Precision", "Recall", "F1", "Error")
MULTICLASS_METRICS = ("F1", "Precision", "Recall", "Error")
REGRESSION_METRICS = ("RootMeanSquaredError", "MeanSquaredError", "R2",
                      "MeanAbsoluteError")


# ---------------------------------------------------------------------------
# host-twin score transforms
#
# The host evaluators rank by the model's POSITIVE-CLASS PROBABILITY
# (evaluators/binary.positive_class_score), not by raw margins. That
# distinction matters: sigmoid/softmax saturate in float, collapsing
# distinct margins into tied scores, and the tie-grouped Spark curve then
# differs from the margin curve. Each transform below reproduces its host
# model's raw->probability arithmetic operation for operation so the
# device metric sees bit-identical scores (same dtype caveats as the
# fit itself). Each returns (score, plabel): the ranking score and the
# 0/1 hard label (host = argmax of the probability vector).
# ---------------------------------------------------------------------------

def softmax_probability(raw):
    """(n, K) max-shifted softmax — ClassifierModel.raw_to_probability
    twin (models/base.py)."""
    shifted = raw - jnp.max(raw, axis=1, keepdims=True)
    e = jnp.exp(shifted)
    return e / jnp.sum(e, axis=1, keepdims=True)


def binary_from_raw_pair(raw):
    """(score, plabel) from an (n, 2) raw-prediction pair via the
    default softmax (LogisticRegression / NaiveBayes / MLP hosts)."""
    p = softmax_probability(raw)
    return p[:, 1], (p[:, 1] > p[:, 0]).astype(raw.dtype)


def binary_from_sigmoid(margin):
    """(score, plabel) from GBT margins — GBTClassifierModel
    raw_to_probability twin (p = sigmoid(margin), label = argmax of
    [1-p, p])."""
    p = 1.0 / (1.0 + jnp.exp(-margin))
    return p, (p > 1.0 - p).astype(margin.dtype)


def binary_from_votes(votes):
    """(score, plabel) from (n, 2) non-negative vote masses —
    TreeEnsembleClassifierModel raw_to_probability twin (normalize by
    the row sum)."""
    s = jnp.sum(votes, axis=1, keepdims=True)
    p = votes / jnp.where(s > 0, s, 1.0)
    return p[:, 1], (p[:, 1] > p[:, 0]).astype(votes.dtype)


def vote_probability(votes):
    """(n, K) normalized votes (multiclass forest host twin)."""
    s = jnp.sum(votes, axis=1, keepdims=True)
    return votes / jnp.where(s > 0, s, 1.0)


def _tie_grouped_curve(pos, margin):
    """Cumulative (tp, fp) per position with each position's counts
    taken at the END of its score-tie run (descending order), plus the
    positive/negative totals. ``pos`` is the 0/1 positive indicator."""
    n = margin.shape[0]
    order = jnp.argsort(-margin)
    ys = pos[order]
    ss = margin[order]
    tp = jnp.cumsum(ys)
    fp = jnp.cumsum(1.0 - ys)
    idx = jnp.arange(n)
    is_end = jnp.concatenate(
        [ss[1:] != ss[:-1], jnp.ones((1,), bool)])
    # smallest j >= i with is_end[j]: reversed running minimum
    run_end = jax.lax.associative_scan(
        jnp.minimum, jnp.where(is_end, idx, n - 1), reverse=True)
    return tp[run_end], fp[run_end], tp[-1], fp[-1]


def binary_metric(y, score, plabel, metric: str):
    """Scalar binary metric from the RANKING SCORE (the host's
    positive-class probability — see the transforms above) and the 0/1
    hard label.

    Matches ``evaluators.binary.binary_metrics``: curve metrics are 0
    for single-class ``y``; point metrics use the same guarded ratios.
    """
    if metric not in BINARY_METRICS:
        raise ValueError(f"unsupported binary device metric {metric!r}")
    pos = (y == 1).astype(score.dtype)
    n = y.shape[0]
    if metric in ("AuPR", "AuROC"):
        tp_a, fp_a, npos, nneg = _tie_grouped_curve(pos, score)
        tpr = tp_a / jnp.maximum(npos, 1.0)
        if metric == "AuROC":
            fpr = fp_a / jnp.maximum(nneg, 1.0)
            xs = jnp.concatenate([jnp.zeros(1, tpr.dtype), fpr,
                                  jnp.ones(1, tpr.dtype)])
            ys_ = jnp.concatenate([jnp.zeros(1, tpr.dtype), tpr,
                                   jnp.ones(1, tpr.dtype)])
        else:
            prec = tp_a / jnp.maximum(tp_a + fp_a, 1.0)
            xs = jnp.concatenate([jnp.zeros(1, tpr.dtype), tpr])
            ys_ = jnp.concatenate([prec[:1], prec])
        area = jnp.sum(jnp.diff(xs) * (ys_[1:] + ys_[:-1]) * 0.5)
        return jnp.where((npos > 0) & (nneg > 0), area,
                         jnp.zeros((), area.dtype))
    predicted = (plabel == 1).astype(score.dtype)
    tp = jnp.sum(predicted * pos)
    fp = jnp.sum(predicted * (1.0 - pos))
    fn = jnp.sum((1.0 - predicted) * pos)
    if metric == "Error":
        return (fp + fn) / max(n, 1)
    precision = jnp.where(tp + fp > 0, tp / jnp.maximum(tp + fp, 1.0), 0.0)
    recall = jnp.where(tp + fn > 0, tp / jnp.maximum(tp + fn, 1.0), 0.0)
    if metric == "Precision":
        return precision
    if metric == "Recall":
        return recall
    return jnp.where(precision + recall > 0,
                     2.0 * precision * recall
                     / jnp.maximum(precision + recall, 1e-300), 0.0)


def multiclass_metric(y, prob, metric: str):
    """Scalar multiclass metric from the (n, K) PROBABILITY matrix (use
    the host-twin transforms above; hard label = argmax, first index on
    ties — same as the host ``np.argmax``). Weighted PRF over all K
    classes; classes absent from ``y`` carry zero label-frequency
    weight, reproducing the host loop over ``np.unique(y)`` exactly."""
    if metric not in MULTICLASS_METRICS:
        raise ValueError(f"unsupported multiclass device metric {metric!r}")
    k = prob.shape[1]
    raw = prob
    pred = jnp.argmax(raw, axis=1)
    yi = y.astype(jnp.int32)
    n = max(y.shape[0], 1)
    if metric == "Error":
        return jnp.mean((pred != yi).astype(raw.dtype))
    y_oh = jax.nn.one_hot(yi, k, dtype=raw.dtype)
    p_oh = jax.nn.one_hot(pred, k, dtype=raw.dtype)
    tp = jnp.sum(y_oh * p_oh, axis=0)
    fp = jnp.sum(p_oh, axis=0) - tp
    fn = jnp.sum(y_oh, axis=0) - tp
    weight = jnp.sum(y_oh, axis=0) / n
    p = jnp.where(tp + fp > 0, tp / jnp.maximum(tp + fp, 1.0), 0.0)
    r = jnp.where(tp + fn > 0, tp / jnp.maximum(tp + fn, 1.0), 0.0)
    if metric == "Precision":
        return jnp.sum(weight * p)
    if metric == "Recall":
        return jnp.sum(weight * r)
    f = jnp.where(p + r > 0, 2.0 * p * r / jnp.maximum(p + r, 1e-300), 0.0)
    return jnp.sum(weight * f)


def regression_metric(y, pred, metric: str):
    """Scalar regression metric (``evaluators.regression`` parity)."""
    if metric not in REGRESSION_METRICS:
        raise ValueError(f"unsupported regression device metric {metric!r}")
    err = pred - y
    if metric == "MeanAbsoluteError":
        return jnp.mean(jnp.abs(err))
    mse = jnp.mean(err * err)
    if metric == "MeanSquaredError":
        return mse
    if metric == "RootMeanSquaredError":
        return jnp.sqrt(mse)
    ss_tot = jnp.sum((y - jnp.mean(y)) ** 2)
    return jnp.where(ss_tot > 0, 1.0 - jnp.sum(err * err) / ss_tot, 0.0)


def metric_fn(kind: str, metric: str) -> Callable:
    """(y_val, scores) -> scalar kernel for a validator metric spec.

    kind "binary"     : scores are a (score, plabel) pair from one of
                        the host-twin transforms above
    kind "multiclass" : scores are the (n, K) probability matrix
    kind "regression" : scores are (n,) predicted values
    """
    if kind == "binary":
        return lambda y, s: binary_metric(y, s[0], s[1], metric)
    if kind == "multiclass":
        return lambda y, s: multiclass_metric(y, s, metric)
    if kind == "regression":
        return lambda y, s: regression_metric(y, s, metric)
    raise ValueError(f"unknown metric kind {kind!r}")
