"""Full-batch convex solvers shared by the linear model family.

The reference delegates optimization to Spark MLlib's breeze L-BFGS /
OWL-QN (e.g. LogisticRegression inside
core/src/main/scala/com/salesforce/op/stages/impl/classification/
OpLogisticRegression.scala:45). TPU-native equivalents:

- :func:`lbfgs_minimize` — optax L-BFGS with zoom linesearch inside a
  ``lax.while_loop``; fully jittable and vmappable (grid points of a
  hyperparameter sweep batch through ``vmap``), so a whole regularization
  path fits in one XLA program on the MXU.
- :func:`fista_minimize` — proximal gradient with Nesterov acceleration
  for elastic-net (L1) penalties, replacing breeze OWL-QN.

(The non-convex MLP's BATCHED fold x grid path uses a fixed-trip
mini-batch Adam loop instead — it needs per-step data slicing, so it
lives next to the model in models/mlp.py:_mlp_batched_fit.)

Everything is static-shape: no data-dependent Python control flow, only
``lax.while_loop`` with scalar convergence predicates (or fixed-length
``lax.scan``).
"""
from __future__ import annotations

from typing import Callable, Tuple

import jax
import jax.numpy as jnp
import optax
import optax.tree_utils as otu

__all__ = ["lbfgs_minimize", "fista_minimize"]


def lbfgs_minimize(loss_fn: Callable, w0, max_iter: int = 100,
                   tol: float = 1e-6):
    """Minimize a smooth loss with L-BFGS; returns the final params.

    ``loss_fn`` must be a pure scalar function of the params pytree.
    """
    opt = optax.lbfgs()
    value_and_grad = optax.value_and_grad_from_state(loss_fn)

    def step(carry):
        params, state = carry
        value, grad = value_and_grad(params, state=state)
        updates, state = opt.update(grad, state, params, value=value,
                                    grad=grad, value_fn=loss_fn)
        params = optax.apply_updates(params, updates)
        return params, state

    def continuing(carry):
        _, state = carry
        count = otu.tree_get(state, "count")
        grad = otu.tree_get(state, "grad")
        err = otu.tree_norm(grad)
        return (count == 0) | ((count < max_iter) & (err >= tol))

    final_params, _ = jax.lax.while_loop(
        continuing, step, (w0, opt.init(w0)))
    return final_params


def _power_iteration_sq_norm(design, w: jnp.ndarray, iters: int = 16,
                             axis_name: str | None = None) -> jnp.ndarray:
    """Largest eigenvalue of A^T diag(w) A / sum(w) (Lipschitz constant
    scale) via power iteration — static iteration count for XLA. ``design``
    is the operator A: ``design.matvec(v)`` is A v, ``design.rmatvec(r)``
    A^T r, ``design.shape`` (n, d) (a lane's design, ``linear._LaneDesign``,
    never materialises A). With ``axis_name`` set, A's rows and ``w`` are
    row shards of a mesh data axis and the matvec reductions cross it via
    psum."""
    d = design.shape[1]
    v0 = jnp.ones((d,), design.dtype) / jnp.sqrt(d)

    def psum(x):
        return jax.lax.psum(x, axis_name) if axis_name else x

    wsum = jnp.maximum(psum(jnp.sum(w)), 1e-12)

    def matvec(v):
        return psum(design.rmatvec(w * design.matvec(v))) / wsum

    def body(_, v):
        u = matvec(v)       # u is replicated across the data axis
        return u / (jnp.linalg.norm(u) + 1e-12)

    v = jax.lax.fori_loop(0, iters, body, v0)
    return jnp.vdot(v, matvec(v))


def fista_minimize(smooth_loss: Callable, l1: float, w0: jnp.ndarray,
                   lipschitz: jnp.ndarray, max_iter: int = 500,
                   tol: float = 1e-7,
                   l1_mask: jnp.ndarray | None = None,
                   grad_psum_axis: str | None = None) -> jnp.ndarray:
    """FISTA: minimize ``smooth_loss(w) + l1 * ||mask * w||_1``.

    ``lipschitz`` bounds the smooth gradient's Lipschitz constant (use
    :func:`_power_iteration_sq_norm` on the design matrix plus the L2
    penalty strength). ``l1_mask`` excludes entries (e.g. the intercept)
    from the penalty.

    Mesh execution (shard_map data axis): pass a SHARD-LOCAL loss plus
    ``grad_psum_axis`` — the gradient is psum'd explicitly across the
    axis, so autodiff never has to transpose a collective (which is
    silently wrong under check_vma=False). ``tol <= 0`` runs EXACTLY
    ``max_iter`` iterations via ``fori_loop`` — required under a mesh so
    every shard hits the same collectives in lockstep.
    """
    mask = jnp.ones_like(w0) if l1_mask is None else l1_mask
    step = 1.0 / jnp.maximum(lipschitz, 1e-12)
    local_grad = jax.grad(smooth_loss)
    if grad_psum_axis is None:
        grad_fn = local_grad
    else:
        def grad_fn(w):
            return jax.lax.psum(local_grad(w), grad_psum_axis)

    def prox(w):
        return jnp.where(
            mask > 0,
            jnp.sign(w) * jnp.maximum(jnp.abs(w) - step * l1, 0.0), w)

    def body(carry):
        w, z, t, _, it = carry
        w_next = prox(z - step * grad_fn(z))
        t_next = (1.0 + jnp.sqrt(1.0 + 4.0 * t * t)) / 2.0
        z_next = w_next + ((t - 1.0) / t_next) * (w_next - w)
        delta = jnp.linalg.norm(w_next - w)
        return w_next, z_next, t_next, delta, it + 1

    init = (w0, w0, jnp.asarray(1.0, w0.dtype),
            jnp.asarray(jnp.inf, w0.dtype), jnp.asarray(0))
    if tol <= 0:
        w, *_ = jax.lax.fori_loop(0, max_iter, lambda _, c: body(c), init)
        return w

    def continuing(carry):
        _, _, _, delta, it = carry
        return (it == 0) | ((it < max_iter) & (delta >= tol))

    w, *_ = jax.lax.while_loop(continuing, body, init)
    return w


def design_lipschitz(design, l2: float, curvature_bound: float = 0.25, *,
                     w: jnp.ndarray, axis_name: str | None = None
                     ) -> jnp.ndarray:
    """Lipschitz bound for losses of the form
    sum(w*phi(x.b))/sum(w) + l2/2 ||b||^2 where phi'' <= curvature_bound
    (0.25 for logistic, 1.0 for squared), over the rows of the operator
    ``design`` (see _power_iteration_sq_norm). ``w`` are the row weights
    (fold masks); ``axis_name`` enables mesh data-axis psum."""
    return (curvature_bound
            * _power_iteration_sq_norm(design, w, axis_name=axis_name) + l2)
