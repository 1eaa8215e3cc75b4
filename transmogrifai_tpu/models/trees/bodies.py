"""The jitted fit programs of the tree families: the bodies a forest, a
boosted fit and a K-class softmax booster share between a single fit, the
lanes of a fold-grid program (``fold_grid.py``) and a row-sharded fit, the
jitted single fits (``jit__fit_gbt`` and its siblings) and the row-sharded
kernels (``shard_map`` over a mesh axis, every cross-row reduction a
``psum``: the Rabit-allreduce role of the reference's XGBoost).

Every body grows its trees with ``grower.py``.
"""
from __future__ import annotations

import functools
import logging
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
import numpy as np

from . import grower as _grower
from .design import _tree_pool
from .grower import (_TreeGrower, _entropy_gain, _fold_indicator,
                     _gini_gain, _grow_blocks, _variance_gain,
                     _variance_stats, _xgb_gain)

_log = logging.getLogger(__name__)


def _row_draw(draw_fn, wkey, n: int, axis_name: Optional[str],
              row_total: Optional[int]):
    """Per-row random draw that is SHARD-POSITION-STABLE: under row
    sharding the draw is generated over the GLOBAL row count (identical
    on every shard — the key replicates) and each shard slices its own
    contiguous block, so a sharded fit resamples exactly the rows the
    single-device fit would (mesh ≡ local parity). The global vector is
    O(rows) scalars — negligible next to the (rows, features) design."""
    if not axis_name:
        return draw_fn(wkey, n)
    full = draw_fn(wkey, row_total)
    start = jax.lax.axis_index(axis_name) * n
    return jax.lax.dynamic_slice(full, (start,), (n,))


#: transient-memory budget (MB) for batching independent forest trees
#: with vmap on an accelerator. Trees of a bagged forest
#: are embarrassingly parallel — a lax.scan over them serializes
#: hundreds of tiny per-level ops (the dominant cost of small-data
#: selector searches, where dispatch/latency beats FLOPs), so trees are
#: fit in vmapped BLOCKS as large as the budget allows: small data ->
#: the whole forest in one program step; huge data -> block size 1,
#: which is exactly the old scan.
_TREE_BLOCK_BUDGET_MB = 256


def _tree_block_size(n: int, total_bins: int, depth: int, s_dim: int,
                     num_trees: int, hist_mode: str, pooled: bool,
                     outer_batch: int = 1) -> int:
    """How many forest trees one vmapped block fits (decided at trace
    time, like _hist_mode): as many as _TREE_BLOCK_BUDGET_MB holds on an
    accelerator, where a lax.scan of tiny per-level ops is launch-
    latency-bound; 1 (the plain scan) on a CPU, where batching measured
    a ~9% Titanic regression on one core."""
    if jax.default_backend() == "cpu":
        return 1
    budget = _TREE_BLOCK_BUDGET_MB * 1024 * 1024
    cap = min(n, _grower._DEFAULT_NODE_CAP)
    c_max = min(2 ** max(depth - 1, 0), cap)
    per_tree = 2 * n * 8 + 2 * c_max * total_bins * s_dim * 8
    if hist_mode.partition("+")[0] != "scatter":
        # the (n, c_max) slot one-hot is the dominant per-tree transient
        # of the einsum strategy at depth
        per_tree += n * c_max * 8
        if pooled:
            per_tree += n * total_bins * 8  # per-tree pooled bin indicator
    if pooled:
        per_tree += 3 * n * 8               # per-tree gathered design cols
    b = max(1, int(budget // max(per_tree * outer_batch, 1)))
    return min(b, num_trees)


def _forest_body(packed, feat_of, block_start, packed_thr,
                 binned, col_thr, narrow_idx, wide_idx, y, key, mask,
                 min_instances, min_info_gain, subsample, *, kind: str,
                 depth, num_classes: int, num_trees: int,
                 max_features: Optional[int], pool_cfg: Optional[tuple],
                 impurity: str, bootstrap: bool,
                 hist_mode: Optional[str],
                 axis_name: Optional[str] = None,
                 row_total: Optional[int] = None,
                 val_rows=None, lanes: Optional[tuple] = None,
                 hist_rows: Optional[int] = None):
    """Shared forest program: ``mask`` (n,) row weights let one body
    serve the single fit (mask=ones), the fold x grid batched kernel
    (mask = fold membership, traced per-candidate hyperparams), and the
    "models"-axis mesh path — masked rows contribute nothing to
    histograms or leaves, which is exactly fitting on the subset.
    ``axis_name`` row-shards the fit: every cross-row reduction psums
    over that mesh axis (see _TreeGrower) and bootstrap draws slice a
    global-shaped sample (_row_draw). Independent trees are fit in
    vmapped blocks (see _tree_block_size).

    One fit (``lanes`` None: ``mask`` (n,), scalar hyperparameters, an int
    ``depth``), or the lanes of a fold-grid program in depth blocks
    (``lanes[b]`` lanes that grow to ``depth[b]``, ascending; ``mask``
    (L, n), the hyperparameters (L,) and ``val_rows`` (L, nv) hold the
    blocks one after another): every per-lane step then runs under
    ``vmap`` over all the lanes, a tree's feature pool and bin indicator
    are made once for all of them, and the trees grow through
    :func:`_grow_blocks`.

    ``val_rows`` ((nv,) int32 positions in the fitted table; the fused
    fit+metric kernel's in-fit form, see fold_grid._candidate_scores) adds
    a fourth output: the leaf the grower routed each of those rows to in
    every tree, (T, nv) int32: ``walk._traverse``'s leaf, without the
    walk. Without it the body returns (feats, thrs, leaves) and traces what
    it always did. With ``lanes`` it returns a tuple a block of those,
    lanes first.

    ``hist_rows`` (with ``lanes``; instead of ``val_rows``): the body is
    one FOLD's, mapped over the folds of the program (see _by_fold): the
    design, ``binned`` and ``y`` are the table in the fold's own order,
    the rows it trains on first (``hist_rows`` of them, the rows ``mask``
    may weigh) and its held-out rows last (see _fold_order). The trees
    contract the head alone (see _TreeGrower) and the fourth output is the
    tail of every tree's final nodes, a slice. The bootstrap weights are
    drawn in that order: one stream a tree for every fold, keyed by the
    row's position in the lane's order."""
    assert val_rows is None or axis_name is None, \
        "val_rows index the whole table: not under row sharding"
    assert hist_rows is None or (val_rows is None and axis_name is None
                                 and lanes is not None)
    n, d = packed.shape
    dtype = packed_thr.dtype
    over = _over_lanes(lanes)
    depths = (depth,) if lanes is None else depth
    if kind == "cls":
        onehot = jax.nn.one_hot(y.astype(jnp.int32), num_classes,
                                dtype=dtype)
        y_mean = jnp.zeros(() if lanes is None else (sum(lanes),), dtype)

        def gain_of(min_instances, min_info_gain):
            return (_gini_gain(min_instances) if impurity == "gini"
                    else _entropy_gain(min_instances)), min_info_gain
    else:
        # a regression lane's statistics carry the label CENTRED on the
        # lane's masked mean, which re-enters at the leaves: the gain does
        # not see the shift (see _variance_gain), and a label of 1998 +- 11
        # would spend its significant bits on the 1998 (_variance_stats)
        def _gsum(v):
            return jax.lax.psum(v, axis_name) if axis_name else v
        y_mean = over(lambda mask: _gsum(jnp.sum(mask * y)) / jnp.maximum(
            _gsum(jnp.sum(mask)), 1.0))(mask)

        def gain_of(min_instances, min_info_gain):
            return _variance_gain(min_instances), min_info_gain

    held_oh = None if pool_cfg is not None else _fold_indicator(
        packed, feat_of, dtype, hist_mode, hist_rows)

    def one_tree(tkey):
        pkey, wkey, fkey = jax.random.split(tkey, 3)

        def tree_stats(mask, subsample, y_mean):
            if bootstrap:
                w = _row_draw(
                    lambda k, m: jax.random.poisson(k, subsample,
                                                    (m,)).astype(dtype),
                    wkey, n, axis_name, row_total)
            else:
                w = jnp.ones((n,), dtype)
            w = w * mask
            return (onehot * w[:, None] if kind == "cls"
                    else _variance_stats(w, y - y_mean))

        def tree_leaves(leaf_stats, y_mean):
            if kind == "cls":
                lw = jnp.sum(leaf_stats, axis=-1, keepdims=True)
                return jnp.where(lw > 0,
                                 leaf_stats / jnp.maximum(lw, 1e-12),
                                 1.0 / num_classes)
            return y_mean + (leaf_stats[:, 1] + leaf_stats[:, 2]
                             ) / jnp.maximum(leaf_stats[:, 0], 1e-12)
        with jax.named_scope("tree.bootstrap"):
            stats = over(tree_stats)(mask, subsample, y_mean)
        design, pool = (packed, feat_of, block_start, packed_thr), None
        if pool_cfg is not None:
            with jax.named_scope("tree.pool"):
                pool, *design = _tree_pool(
                    pkey, binned, col_thr, narrow_idx, wide_idx, pool_cfg)
        grower = _TreeGrower(
            *design, dtype, max_depth=max(depths), feat_key=fkey,
            max_features=max_features, feat_map=pool, hist_mode=hist_mode,
            axis_name=axis_name, row_total=row_total, hist_rows=hist_rows,
            bin_oh=held_oh)
        if lanes is None:
            state = grower.levels(None, stats, *gain_of(
                min_instances, min_info_gain), 0, depth, depth)
            tree = (state.feat_heap, state.thr_heap, tree_leaves(
                grower.leaves(state, stats, depth), y_mean))
            return tree if val_rows is None else (
                *tree, state.node[val_rows])
        grown = _grow_blocks(grower, depths, lanes, stats,
                             (min_instances, min_info_gain), gain_of)
        at, trees = 0, []
        for k, (feat, thr, leaf_stats, state) in zip(lanes, grown):
            mine = slice(at, at + k)
            tree = (feat, thr, jax.vmap(tree_leaves)(leaf_stats,
                                                     y_mean[mine]))
            if val_rows is not None:
                tree += (jax.vmap(lambda nd, rows: nd[rows])(
                    state.node, val_rows[mine]),)
            elif hist_rows is not None:
                tree += (state.node[:, hist_rows:],)
            trees.append(tree)
            at += k
        return tuple(trees)

    keys = jax.random.split(key, num_trees)
    # full-design TB is a safe upper bound for the pooled design's; the
    # lanes of a block share the budget (a fold's body holds its own lanes
    # and indicator, one fold at a time), and the blocks one block size
    tb = min(_tree_block_size(
        row_total if row_total is not None else n,
        int(feat_of.shape[0]), block_depth,
        num_classes if kind == "cls" else 3, num_trees,
        hist_mode or "scatter", pool_cfg is not None, block_lanes)
        for block_depth, block_lanes in zip(depths, lanes or (1,)))
    if tb >= num_trees:
        outs = jax.vmap(one_tree)(keys)
    elif tb == 1:
        _, outs = jax.lax.scan(lambda c, k: (c, one_tree(k)), None, keys)
    else:
        pad = (-num_trees) % tb
        keys_p = jnp.concatenate([keys, keys[:pad]], axis=0)
        _, outs = jax.lax.scan(
            lambda c, kb: (c, jax.vmap(one_tree)(kb)), None,
            keys_p.reshape(-1, tb, *keys.shape[1:]))
        outs = jax.tree_util.tree_map(
            lambda a: a.reshape((-1,) + a.shape[2:])[:num_trees], outs)
    if lanes is None:
        return outs
    # a block's trees come out trees first: lanes first
    return jax.tree_util.tree_map(lambda a: jnp.moveaxis(a, 0, 1), outs)


@functools.partial(
    jax.jit, static_argnames=("depth", "num_classes", "num_trees",
                              "max_features", "pool_cfg", "impurity",
                              "bootstrap", "hist_mode"))
def _fit_forest_classifier(packed, feat_of, block_start, packed_thr,
                           binned, col_thr, narrow_idx, wide_idx, y, key,
                           *, depth: int, num_classes: int, num_trees: int,
                           max_features: Optional[int],
                           pool_cfg: Optional[tuple], impurity: str,
                           min_instances: float, min_info_gain: float,
                           subsample: float, bootstrap: bool,
                           hist_mode: Optional[str]):
    return _forest_body(
        packed, feat_of, block_start, packed_thr, binned, col_thr,
        narrow_idx, wide_idx, y, key, jnp.ones_like(y), min_instances,
        min_info_gain, subsample, kind="cls", depth=depth,
        num_classes=num_classes, num_trees=num_trees,
        max_features=max_features, pool_cfg=pool_cfg, impurity=impurity,
        bootstrap=bootstrap, hist_mode=hist_mode)


@functools.partial(
    jax.jit, static_argnames=("depth", "num_trees", "max_features",
                              "pool_cfg", "bootstrap", "hist_mode"))
def _fit_forest_regressor(packed, feat_of, block_start, packed_thr,
                          binned, col_thr, narrow_idx, wide_idx, y, key,
                          *, depth: int, num_trees: int,
                          max_features: Optional[int],
                          pool_cfg: Optional[tuple],
                          min_instances: float, min_info_gain: float,
                          subsample: float, bootstrap: bool,
                          hist_mode: Optional[str]):
    return _forest_body(
        packed, feat_of, block_start, packed_thr, binned, col_thr,
        narrow_idx, wide_idx, y, key, jnp.ones_like(y), min_instances,
        min_info_gain, subsample, kind="reg", depth=depth, num_classes=0,
        num_trees=num_trees, max_features=max_features, pool_cfg=pool_cfg,
        impurity="", bootstrap=bootstrap, hist_mode=hist_mode)


def _over_lanes(lanes: Optional[tuple]):
    """How a tree family's body runs a per-lane step (see _forest_body,
    _gbt_body): as it is for one fit (``lanes`` None), under ``jax.vmap``
    over the leading axis of its arguments for the lanes of a fold-grid
    program."""
    return (lambda step: step) if lanes is None else jax.vmap


def _leaf_values(leaf_stats, step_size, reg_lambda):
    """A boosted tree's (2^depth,) leaf values from its (2^depth, 2) leaf
    sums of gradients and hessians; 0 where a leaf holds no row."""
    vals = -step_size * leaf_stats[:, 0] / (leaf_stats[:, 1] + reg_lambda)
    return jnp.where(jnp.sum(jnp.abs(leaf_stats), axis=1) > 0, vals, 0.0)


def _gbt_body(packed, feat_of, block_start, packed_thr, y, key, mask,
              step_size, reg_lambda, gamma, min_child_weight, subsample,
              *, depth, num_rounds: int, objective: str,
              hist_mode: Optional[str],
              axis_name: Optional[str] = None,
              row_total: Optional[int] = None,
              lanes: Optional[tuple] = None,
              hist_rows: Optional[int] = None):
    """Shared boosting program with row-mask semantics (see
    _forest_body): masked rows get zero grad/hess weight; the base
    margin is the mask-weighted mean. ``axis_name`` row-shards the fit
    (psum'd histograms/means, global-sliced subsampling).

    One fit (``lanes`` None: ``mask`` (n,), scalar hyperparameters, an int
    ``depth``), or the lanes of a fold-grid program in depth blocks
    (``lanes[b]`` lanes that grow to ``depth[b]``, ascending; ``mask``
    (L, n) and the hyperparameters (L,) hold the blocks one after
    another): every per-lane step then runs under ``vmap`` over all the
    lanes, and the trees grow through :func:`_grow_blocks`.

    Returns (feats, thrs, leaves, base, margins), a tuple a block of them
    with ``lanes``: ``margins`` (n,) is the scan's final carry, the
    finished margin of EVERY row of the table, masked or not (the grower
    routes them all, and each round adds its leaf value to all of them):
    what the in-fit form of the fused fit+metric kernel scores the
    held-out rows from (see fold_grid._candidate_scores). It is the
    sequential float32 sum over rounds, not ``base + sum(vals)``: equal to
    a few ulp. A caller that drops it compiles what it compiled without
    it.

    ``hist_rows``: one FOLD's body over the table in the fold's own order,
    as in _forest_body: the rounds' trees contract the leading
    ``hist_rows`` rows, the held-out rows' margins are the tail of
    ``margins``, and a round's subsample is drawn in that order."""
    n, d = packed.shape
    dtype = packed_thr.dtype
    over = _over_lanes(lanes)
    depths = (depth,) if lanes is None else depth

    def _gsum(v):
        return jax.lax.psum(v, axis_name) if axis_name else v

    def start(mask):
        msum = jnp.maximum(_gsum(jnp.sum(mask)), 1.0)
        mean_y = _gsum(jnp.sum(mask * y)) / msum
        if objective == "logistic":
            p0 = jnp.clip(mean_y, 1e-6, 1 - 1e-6)
            base = jnp.log(p0 / (1 - p0))
        else:
            base = mean_y
        return base, jnp.broadcast_to(base.astype(dtype), (n,))
    base, margins0 = over(start)(mask)

    def gain_of(reg_lambda, gamma, min_child_weight):
        return _xgb_gain(reg_lambda, gamma, min_child_weight), 0.0
    held_oh = _fold_indicator(packed, feat_of, dtype, hist_mode, hist_rows)

    def one_round(margins, rkey):
        def round_stats(margins, mask, subsample):
            if objective == "logistic":
                p = jax.nn.sigmoid(margins)
                g, h = p - y, jnp.maximum(p * (1 - p), 1e-12)
            else:
                g, h = margins - y, jnp.ones_like(y)
            m = _row_draw(
                lambda k, mm: jax.random.bernoulli(k, subsample,
                                                   (mm,)).astype(dtype),
                rkey, n, axis_name, row_total) * mask
            return jnp.stack([g * m, h * m], axis=1)

        with jax.named_scope("gbt.round"):
            stats = over(round_stats)(margins, mask, subsample)
            grower = _TreeGrower(
                packed, feat_of, block_start, packed_thr, dtype,
                max_depth=max(depths), hist_mode=hist_mode,
                axis_name=axis_name, row_total=row_total,
                hist_rows=hist_rows, bin_oh=held_oh)
            if lanes is None:
                state = grower.levels(
                    None, stats, *gain_of(reg_lambda, gamma,
                                          min_child_weight), 0, depth, depth)
                vals = _leaf_values(grower.leaves(state, stats, depth),
                                    step_size, reg_lambda)
                return (grower.add_leaf_values(margins, state, vals, depth),
                        (state.feat_heap, state.thr_heap, vals))
            grown = _grow_blocks(grower, depths, lanes, stats,
                                 (reg_lambda, gamma, min_child_weight),
                                 gain_of)
            at, new_margins, trees = 0, [], []
            for k, block_depth, (feat, thr, leaf_stats, state) in zip(
                    lanes, depths, grown):
                mine = slice(at, at + k)
                vals = jax.vmap(_leaf_values)(leaf_stats, step_size[mine],
                                              reg_lambda[mine])
                new_margins.append(jax.vmap(functools.partial(
                    grower.add_leaf_values, depth=block_depth))(
                    margins[mine], state, vals))
                trees.append((feat, thr, vals))
                at += k
            return jnp.concatenate(new_margins), tuple(trees)
    margins, trees = jax.lax.scan(
        one_round, margins0, jax.random.split(key, num_rounds))
    if lanes is None:
        return (*trees, base, margins)
    ends = np.cumsum(lanes)
    # a block's trees come out of the scan rounds first: lanes first
    return tuple(
        tuple(jnp.moveaxis(a, 0, 1) for a in block_trees)
        + (base[e - k:e], margins[e - k:e])
        for block_trees, e, k in zip(trees, ends, lanes))


@functools.partial(
    jax.jit, static_argnames=("depth", "num_rounds", "objective",
                              "hist_mode"))
def _fit_gbt(packed, feat_of, block_start, packed_thr, y, key, *, depth: int,
             num_rounds: int, step_size: float, reg_lambda: float,
             gamma: float, min_child_weight: float, subsample: float,
             objective: str, hist_mode: Optional[str]):
    return _gbt_body(packed, feat_of, block_start, packed_thr, y, key,
                     jnp.ones_like(y), step_size, reg_lambda, gamma,
                     min_child_weight, subsample, depth=depth,
                     num_rounds=num_rounds, objective=objective,
                     hist_mode=hist_mode)[:4]


def _gbt_softmax_body(packed, feat_of, block_start, packed_thr, y, key,
                      mask, step_size, reg_lambda, gamma,
                      min_child_weight, subsample, *, depth,
                      num_rounds: int, num_classes: int,
                      hist_mode: Optional[str],
                      axis_name: Optional[str] = None,
                      row_total: Optional[int] = None,
                      lanes: Optional[tuple] = None,
                      hist_rows: Optional[int] = None):
    """K-class softmax boosting: each round fits one tree PER CLASS on
    the softmax gradients/hessians (g_k = p_k - 1[y=k],
    h_k = p_k(1-p_k)) — the ``multi:softprob`` objective the reference
    reaches through xgboost4j (OpXGBoostClassifier.scala:47; MLlib GBT
    itself has no multiclass mode). The K trees of a round see the same
    fixed margins, so they vmap as one batched program (histogram width
    x K, sequential depth unchanged). Base margins are the log class
    priors. One fit, or with ``lanes`` the lanes of a fold-grid program in
    depth blocks, as in _gbt_body: the K trees of a lane are then K lanes
    of :func:`_grow_blocks`. Returns (feats (R,K,H), thrs (R,K,H), leaves
    (R,K,L), base (K,), margins (n,K)), a tuple a block of them with
    ``lanes``: the last is the scan's final carry, every row's finished
    margins (see _gbt_body). ``hist_rows`` as in _gbt_body."""
    n, d = packed.shape
    dtype = packed_thr.dtype
    K = num_classes
    over = _over_lanes(lanes)
    depths = (depth,) if lanes is None else depth

    def _gsum(v):
        return jax.lax.psum(v, axis_name) if axis_name else v

    onehot = jax.nn.one_hot(y.astype(jnp.int32), K, dtype=dtype)

    def start(mask):
        counts = _gsum(jnp.sum(mask[:, None] * onehot, axis=0))
        priors = jnp.clip(counts / jnp.maximum(jnp.sum(counts), 1.0),
                          1e-6, 1.0)
        base = jnp.log(priors)
        return base, jnp.broadcast_to(base, (n, K)).astype(dtype)
    base, margins0 = over(start)(mask)

    def gain_of(reg_lambda, gamma, min_child_weight):
        return _xgb_gain(reg_lambda, gamma, min_child_weight), 0.0
    held_oh = _fold_indicator(packed, feat_of, dtype, hist_mode, hist_rows)

    def one_round(margins, rkey):
        def round_stats(margins, mask, subsample):
            p = jax.nn.softmax(margins, axis=1)
            g = p - onehot                                  # (n, K)
            h = jnp.maximum(p * (1.0 - p), 1e-12)
            m = _row_draw(
                lambda k, mm: jax.random.bernoulli(k, subsample,
                                                   (mm,)).astype(dtype),
                rkey, n, axis_name, row_total) * mask
            return jnp.stack([g.T * m, h.T * m], axis=2)    # (K, n, 2)

        with jax.named_scope("gbt.round"):
            stats = over(round_stats)(margins, mask, subsample)
            grower = _TreeGrower(
                packed, feat_of, block_start, packed_thr, dtype,
                max_depth=max(depths), hist_mode=hist_mode,
                axis_name=axis_name, row_total=row_total,
                hist_rows=hist_rows, bin_oh=held_oh)
            if lanes is None:
                def per_class(class_stats, class_margins):
                    state = grower.levels(
                        None, class_stats, *gain_of(
                            reg_lambda, gamma, min_child_weight), 0, depth,
                        depth)
                    vals = _leaf_values(
                        grower.leaves(state, class_stats, depth), step_size,
                        reg_lambda)
                    return (grower.add_leaf_values(class_margins, state,
                                                   vals, depth),
                            state.feat_heap, state.thr_heap, vals)
                class_margins, feats, thrs, vals = jax.vmap(per_class)(
                    stats, margins.T)
                return class_margins.T, (feats, thrs, vals)
            # a lane's K trees are K lanes of the grower, a block's lanes
            # still one after another
            grown = _grow_blocks(
                grower, depths, tuple(k * K for k in lanes),
                stats.reshape((-1,) + stats.shape[2:]),
                tuple(jnp.repeat(a, K) for a in (reg_lambda, gamma,
                                                 min_child_weight)),
                gain_of)
            at, new_margins, trees = 0, [], []
            for k, block_depth, (feat, thr, leaf_stats, state) in zip(
                    lanes, depths, grown):
                mine = slice(at, at + k)
                vals = jax.vmap(_leaf_values)(
                    leaf_stats, jnp.repeat(step_size[mine], K),
                    jnp.repeat(reg_lambda[mine], K))
                # a lane's K class margins as K lanes of the grower
                class_margins = jax.vmap(functools.partial(
                    grower.add_leaf_values, depth=block_depth))(
                    jnp.swapaxes(margins[mine], 1, 2).reshape(k * K, n),
                    state, vals)
                new_margins.append(jnp.swapaxes(
                    class_margins.reshape(k, K, n), 1, 2))
                trees.append(tuple(a.reshape((k, K) + a.shape[1:])
                                   for a in (feat, thr, vals)))
                at += k
            return jnp.concatenate(new_margins), tuple(trees)
    margins, trees = jax.lax.scan(
        one_round, margins0, jax.random.split(key, num_rounds))
    if lanes is None:
        return (*trees, base, margins)
    ends = np.cumsum(lanes)
    # a block's trees come out of the scan rounds first: lanes first
    return tuple(
        tuple(jnp.moveaxis(a, 0, 1) for a in block_trees)
        + (base[e - k:e], margins[e - k:e])
        for block_trees, e, k in zip(trees, ends, lanes))


@functools.partial(
    jax.jit, static_argnames=("depth", "num_rounds", "num_classes",
                              "hist_mode"))
def _fit_gbt_softmax(packed, feat_of, block_start, packed_thr, y, key, *,
                     depth: int, num_rounds: int, num_classes: int,
                     step_size: float, reg_lambda: float, gamma: float,
                     min_child_weight: float, subsample: float,
                     hist_mode: Optional[str]):
    return _gbt_softmax_body(
        packed, feat_of, block_start, packed_thr, y, key,
        jnp.ones_like(y), step_size, reg_lambda, gamma, min_child_weight,
        subsample, depth=depth, num_rounds=num_rounds,
        num_classes=num_classes, hist_mode=hist_mode)[:4]


# ---------------------------------------------------------------------------
# row-sharded (data-parallel) single fits — the Rabit-allreduce role
# ---------------------------------------------------------------------------
#
# The fold x grid kernels (fold_grid.py) shard CANDIDATES (task
# parallelism); the kernels here shard ROWS of one fit over a mesh axis:
# each chip holds a contiguous block of the binned design and psums
# per-level histograms over ICI (see _grow_tree axis_name). This is the
# promised data-parallel path of the package docstring — how one model's
# training scales past a single chip's HBM/FLOPs, the role Rabit allreduce
# plays for the reference's XGBoost (core/build.gradle:27, SURVEY §2.9).

@functools.lru_cache(maxsize=32)
def _forest_sharded_kernel(statics: tuple, mesh, axis: str):
    (kind, depth, num_classes, num_trees, max_features, pool_cfg,
     impurity, bootstrap, hist_mode, row_total) = statics
    from jax.sharding import PartitionSpec as P

    def body(packed, binned, y, mask, feat_of, block_start, packed_thr,
             col_thr, narrow, wide, key, mi, mg, sr):
        return _forest_body(
            packed, feat_of, block_start, packed_thr, binned, col_thr,
            narrow, wide, y, key, mask, mi, mg, sr, kind=kind,
            depth=depth, num_classes=num_classes, num_trees=num_trees,
            max_features=max_features, pool_cfg=pool_cfg,
            impurity=impurity, bootstrap=bootstrap, hist_mode=hist_mode,
            axis_name=axis, row_total=row_total)

    # outputs replicate: every shard reaches identical split decisions
    # from the psum'd reductions
    return jax.jit(shard_map(
        body, mesh=mesh,
        in_specs=(P(axis, None), P(axis, None), P(axis), P(axis))
        + (P(),) * 10,
        out_specs=(P(), P(), P()), check_vma=False))


@functools.lru_cache(maxsize=32)
def _gbt_sharded_kernel(statics: tuple, mesh, axis: str):
    depth, num_rounds, objective, hist_mode, row_total = statics
    from jax.sharding import PartitionSpec as P

    def body(packed, y, mask, feat_of, block_start, packed_thr, key,
             ss, rl, ga, mcw, sub):
        return _gbt_body(packed, feat_of, block_start, packed_thr, y,
                         key, mask, ss, rl, ga, mcw, sub, depth=depth,
                         num_rounds=num_rounds, objective=objective,
                         hist_mode=hist_mode, axis_name=axis,
                         row_total=row_total)[:4]

    return jax.jit(shard_map(
        body, mesh=mesh,
        in_specs=(P(axis, None), P(axis), P(axis)) + (P(),) * 9,
        out_specs=(P(), P(), P(), P()), check_vma=False))


def _pad_rows(arrays, shards: int):
    """Pad each array's leading (row) axis to a multiple of ``shards``
    by repeating row 0 (padded rows carry mask 0, so they contribute
    nothing — repeating a real row keeps every bin index in range).
    Returns (padded arrays, mask (n_padded,))."""
    n = arrays[0].shape[0]
    pad = (-n) % shards
    mask = np.concatenate([np.ones(n), np.zeros(pad)])
    if not pad:
        return list(arrays), mask
    # padding changes the global bootstrap-draw vector length, so a
    # sharded fit is no longer bit-identical to the local fit (both
    # remain valid draws) — surface it instead of silently diverging
    _log.debug("_pad_rows: %d rows padded to %d for %d shards; sharded "
               "bootstrap draws will differ from an unpadded local fit",
               n, n + pad, shards)
    out = []
    for a in arrays:
        a = np.asarray(a)
        fill = np.repeat(a[:1], pad, axis=0)
        out.append(np.concatenate([a, fill], axis=0))
    return out, mask
