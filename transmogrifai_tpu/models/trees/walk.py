"""Walks of finished heaps: every row's leaf in each tree, the scoring
programs' (``jit__predict_leaves``, ``jit__batched_tree_raw``) and the
traverse form of a fused fit+metric kernel's.

``_traverse`` takes the form ``forms._traverse_form`` gives it: per-row
gathers on a CPU, selects over a level's nodes and over the columns on an
accelerator; both give every row the same leaf.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import forms as _forms


def _traverse(X: jnp.ndarray, feat_heap: jnp.ndarray, thr_heap: jnp.ndarray,
              depth: int) -> jnp.ndarray:
    """Leaf index in [0, 2^depth) for every row; static-depth descent in
    the form of _traverse_form. A row goes left where ``x <= t`` in the
    dtype both promote to (a NaN walks right). The dense form moves ``x``
    and ``t`` as integer bits, so the compare sees the gather's operands:
    level ``l``'s nodes are the static heap slice ``[2^l - 1, 2^(l+1) -
    1)``; a row's feature id and threshold are selected over that slice
    (nodes on the major axis, rows along the lanes), its value over the
    columns of ``X.T``."""
    n, d = X.shape
    form = _forms._traverse_form(d, depth)
    node = jnp.zeros((n,), jnp.int32)
    with jax.named_scope("tree.traverse"):
        if form == "gather":
            rows = jnp.arange(n)
            for level in range(depth):
                heap = 2 ** level - 1 + node  # levels concatenate
                f = feat_heap[heap]
                t = thr_heap[heap]
                go_left = X[rows, f] <= t
                node = 2 * node + (1 - go_left.astype(jnp.int32))
            return node
        dtype = jnp.result_type(X.dtype, thr_heap.dtype)      # a float
        bits = jnp.int64 if jnp.dtype(dtype).itemsize == 8 else jnp.int32
        x_bits = jax.lax.bitcast_convert_type(X.astype(dtype), bits).T
        t_bits = jax.lax.bitcast_convert_type(thr_heap.astype(dtype), bits)
        column = jnp.arange(d, dtype=jnp.int32)[:, None]
        for level in range(depth):
            first, m = 2 ** level - 1, 2 ** level
            mine = node[None, :] == jnp.arange(m, dtype=jnp.int32)[:, None]
            f = jnp.sum(jnp.where(mine, feat_heap[first:first + m, None], 0),
                        axis=0, dtype=feat_heap.dtype)
            t = jnp.sum(jnp.where(mine, t_bits[first:first + m, None], 0),
                        axis=0, dtype=bits)
            x = jnp.sum(jnp.where(f[None, :] == column, x_bits, 0), axis=0,
                        dtype=bits)
            go_left = (jax.lax.bitcast_convert_type(x, dtype)
                       <= jax.lax.bitcast_convert_type(t, dtype))
            node = 2 * node + (1 - go_left.astype(jnp.int32))
        return node


@functools.partial(jax.jit, static_argnames=("depth",))
def _predict_leaves(X, feats, thrs, depth: int):
    """(T, n) leaf index per tree via vmapped static-depth traversal (see
    _traverse_form). The dense form's selects fuse into their reductions
    under the ``vmap``: compiled for a v5e at 20 trees of depth 12 and 200
    columns, 0 MB of temporaries at 49,152 rows and 157 MB, the (T, n)
    carries, at 196,608 (PR 40)."""
    return jax.vmap(lambda f, t: _traverse(X, f, t, depth))(feats, thrs)


@functools.partial(jax.jit, static_argnames=("depth", "kind"))
def _batched_tree_raw(X, feats, thrs, leaves, bases, *, depth: int,
                      kind: str):
    """(C, ...) raw outputs for C same-shape fitted tree models against
    one matrix: vmapped static-depth traversal + leaf gather + tree
    reduction, ONE program instead of C dispatch/sync round trips (the
    per-candidate path costs a full host<->device round trip per model,
    which dominates small-data selector searches on a remote TPU)."""
    def per_candidate(f, t, l, b):
        leaf = jax.vmap(lambda fh, th: _traverse(X, fh, th, depth))(f, t)
        vals = l[jnp.arange(l.shape[0])[:, None], leaf]   # (T, n[, K])
        if kind == "forest":
            return jnp.mean(vals, axis=0)                 # probs or values
        return b + jnp.sum(vals, axis=0)                  # GBT margin
    return jax.vmap(per_candidate)(feats, thrs, leaves, bases)
