"""The fold x grid programs of the tree families and their drivers: every
(fold, grid point) candidate of a family as a lane of one vmapped program a
static shape group, fit alone (``fit_fold_grid_arrays``) or fused with its
validation metric (``eval_fold_grid_arrays``, the device-resident search),
optionally sharded over a mesh's ``models`` axis.

The drivers lay the grid out in depth blocks (``_candidate_groups``), bin
the matrix once (``design._design_args``), and run each program under the
``search.fetch`` span, whose attributes are the call's own: ``hist_mode``,
the histogram mode the program was compiled with (the route, node-sum and
pick forms follow from it by ``forms``' rules), ``depth_blocks`` /
``depth_lane_levels`` and ``hist_row_share``. This is the one module of the
tree package that opens spans.
"""
from __future__ import annotations

import functools
import itertools
from typing import Dict, Iterable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import shard_map
import numpy as np

from ..base import num_classes
from ...observability import trace as _trace
from ...parallel.mesh import pad_candidates, to_host
from ...utils.jax_setup import with_frame_room
from . import forms as _forms
from . import walk as _walk
from .bodies import _forest_body, _gbt_body, _gbt_softmax_body
from .design import _design_args, _pool_plan, _resolve_max_features
from .fitted import (GBTClassifierModel, GBTMulticlassClassifierModel,
                     GBTRegressorModel, TreeEnsembleClassifierModel,
                     TreeEnsembleRegressorModel)


# The reference's per-fold/per-grid Future pool (OpValidator.scala:270)
# maps for tree families onto ONE vmapped program per static shape group
# (depth/trees/rounds/bins): each candidate = (fold mask, traced
# hyperparams). With a ("models", "data") mesh the candidate axis shards
# over chips (data replicated — trees are task-parallel here, like the
# reference's executor model). Documented deviation from the sequential
# path: bin edges come from the WHOLE prepared matrix rather than each
# fold's train rows (feature-distribution information only — standard
# for histogram-GBM cross-validation).

def _all_lanes(blocks: tuple):
    """The per-lane arguments of a fold-grid program's DEPTH BLOCKS (see
    _candidate_groups) as the tree bodies take them (see _forest_body,
    _gbt_body ``lanes``): each argument with the blocks' lanes one block
    after another on its leading axis, and the lane count a block."""
    return (tuple(jnp.concatenate(arg) for arg in zip(*blocks)),
            tuple(block[0].shape[0] for block in blocks))


def _fold_order(masks: np.ndarray, val_rows: np.ndarray
                ) -> Optional[np.ndarray]:
    """(F, n) int32 ``order``: the rows of the fitted table in each fold's
    OWN ORDER, the rows the fold trains on first (ascending) and its
    held-out rows ``val_rows[f]`` last, as they were given. A function of
    ``val_rows`` alone, and its shapes are the program's own (``n``,
    ``nv``, ``F``): k folds, the one split of a train-validation split and
    a racing rung's subset of folds alike. In that order the rows that can
    carry weight are the static head ``[0, n - nv)`` and a lane's held-out
    rows the static tail: the level histograms contract the head and not
    the whole table (see _TreeGrower ``hist_rows``), and the held-out
    rows' leaves or margins are a slice, not a per-row gather.

    None where the folds do not allow it: a fold names a row twice, or
    ``masks`` (F, n) gives weight to a row its fold holds out, whose
    statistics the head form would drop. Rows of the head may carry weight
    zero (the rows the validator drops to make the folds equal, a racing
    rung's thinned rows)."""
    F, n = masks.shape
    order = np.empty((F, n), dtype=np.int32)
    for f, held_rows in enumerate(val_rows):
        held = np.zeros(n, dtype=bool)
        held[held_rows] = True
        if held.sum() != len(held_rows) or masks[f, held].any():
            return None
        order[f] = np.concatenate([np.nonzero(~held)[0], held_rows])
    return order


def _by_fold(fit, blocks: tuple, order, tables: tuple):
    """A fold-grid program's fits a FOLD at a time: ``fit(fold_tables,
    lane_args, lanes)``, one fold's body (see _forest_body ``hist_rows``),
    mapped over the folds one after another (``lax.map``). A fold's body
    sees ``tables`` (per-row arrays of the fitted table) in the fold's own
    order, ``table[order[f]]``: row gathers once a call of the program, not
    once a tree or a level; and the fold's lanes: the per-lane arguments of
    the depth ``blocks`` (each fold-major, lane ``f * gk + j``, see
    _candidate_groups) as (F, lanes a fold) with the blocks side by side,
    ``lanes`` counting a block's lanes a fold. The body is traced once, as
    the lanes-only form is, and a fold's lanes share its design and
    indicator as the lanes of the whole table share theirs there.

    Why a map and not a ``vmap`` over the folds, which would run the
    folds' contractions as one batched one: on the chip the mapped form is
    the faster (my chip runs, PR 37, PERF.md section 6: ``.search`` 24.6
    against 22.4 models x folds/s, the binary pool 33.0 against 30.8; a
    third of the temporaries), and a second batching pass over the trees'
    or rounds' ``scan`` body costs what the first trace of it cost:
    tracing was 40-50 % slower here and ``setup_s`` went over its bound.
    Returns what the bodies return, a tuple a block, with the lanes back on
    one leading axis in the blocks' own order."""
    F = order.shape[0]
    lane_args = tuple(
        jnp.concatenate([a.reshape((F, -1) + a.shape[1:]) for a in arg],
                        axis=1) for arg in zip(*blocks))
    lanes = tuple(block[0].shape[0] // F for block in blocks)
    with jax.named_scope("fg.order"):
        fold_tables = tuple(table[order] for table in tables)
    fitted = jax.lax.map(lambda fold: fit(*fold, lanes),
                         (fold_tables, lane_args))
    return jax.tree_util.tree_map(
        lambda a: a.reshape((-1,) + a.shape[2:]), fitted)


def _lane_metrics(mfn, yv, blocks: tuple, scores: tuple):
    """The validation metric of every lane of a fused fit+metric program,
    a vector a depth block: ``mfn(yv[fold], scores)`` with ``fold`` the
    last of a block's per-lane arguments and ``scores[b]`` the validation
    scores of block ``b``'s lanes. ONE ``lax.map`` over the lanes of all
    the blocks and not a ``vmap``: the chip's executable of the binary
    curve metrics (a sort, cumulative sums and a running minimum over the
    validation rows, each unrolled into some hundred small operations)
    grows with the lanes it is batched over, 22 MB at 18 lanes x 16,384
    rows and 44 MB at 54, where one lane at a time is 4 MB (compiles for a
    v5e, PERF.md section 6, PR 35): what a process pays when it loads the
    program, against some hundred small operations a lane when it runs
    (``fg.metric`` of the boosted program on the chip: 0.045 s a train
    batched, 0.037 s mapped)."""
    folds = jnp.concatenate([block[-1] for block in blocks])
    lanes = jax.tree_util.tree_map(lambda *a: jnp.concatenate(a), *scores)
    with jax.named_scope("fg.metric"):
        metrics = jax.lax.map(lambda lane: mfn(yv[lane[0]], lane[1]),
                              (folds, lanes))
    ends = np.cumsum([block[-1].shape[0] for block in blocks])
    return tuple(jnp.split(metrics, ends[:-1]))


def _shard_blocks(batched, mesh, blocks: int, traced: int, shared: int,
                  out_spec):
    """``batched(blocks, *shared_args)``, a tree family's fold-grid
    program over its depth blocks (see _candidate_groups), as it is
    jitted: itself without a mesh; with one, under ``shard_map`` with every
    leaf of every block sharded over ``models`` (a block's (lanes, n) row
    masks, then its ``traced`` per-lane vectors; each block's result
    ``out_spec``) and the ``shared`` arguments replicated: a chip holds
    its share of EVERY depth, which is why each block is padded to the
    shard count on its own."""
    if mesh is None:
        return batched
    from jax.sharding import PartitionSpec as P
    lane_specs = (P("models", None),) + (P("models"),) * traced
    return shard_map(
        batched, mesh=mesh,
        in_specs=((lane_specs,) * blocks,) + (P(),) * shared,
        out_specs=(out_spec,) * blocks, check_vma=False)


@functools.lru_cache(maxsize=32)
def _forest_fg_kernel(statics: tuple, mesh=None):
    (kind, depths, num_classes, num_trees, max_features, pool_cfg,
     impurity, bootstrap, hist_mode) = statics
    from jax.sharding import PartitionSpec as P

    # named apart from the boosted programs' ``batched``: the function a
    # ``jax.jit`` wraps names the program (``jit_forest_batched``)
    def forest_batched(blocks, packed, feat_of, block_start, packed_thr,
                       binned, col_thr, narrow, wide, y, key):
        (mask, mi, mg, sr), lanes = _all_lanes(blocks)
        with jax.named_scope("fg.forest"):
            return _forest_body(
                packed, feat_of, block_start, packed_thr, binned, col_thr,
                narrow, wide, y, key, mask, mi, mg, sr, kind=kind,
                depth=depths, num_classes=num_classes,
                num_trees=num_trees, max_features=max_features,
                pool_cfg=pool_cfg, impurity=impurity, bootstrap=bootstrap,
                hist_mode=hist_mode, lanes=lanes)

    leaves_spec = (P("models", None, None, None) if kind == "cls"
                   else P("models", None, None))
    return jax.jit(_shard_blocks(
        forest_batched, mesh, len(depths), 3, 10,
        (P("models", None, None), P("models", None, None), leaves_spec)))


@functools.lru_cache(maxsize=32)
def _gbt_fg_kernel(statics: tuple, mesh=None):
    depths, num_rounds, objective, hist_mode = statics
    from jax.sharding import PartitionSpec as P

    def batched(blocks, packed, feat_of, block_start, packed_thr, y, key):
        (mask, ss, rl, ga, mcw, sub), lanes = _all_lanes(blocks)
        with jax.named_scope("fg.gbt"):
            return tuple(fitted[:4] for fitted in _gbt_body(
                packed, feat_of, block_start, packed_thr, y, key, mask,
                ss, rl, ga, mcw, sub, depth=depths, num_rounds=num_rounds,
                objective=objective, hist_mode=hist_mode, lanes=lanes))

    return jax.jit(_shard_blocks(
        batched, mesh, len(depths), 5, 6,
        (P("models", None, None), P("models", None, None),
         P("models", None, None), P("models"))))


def _gbt_scores(spec_kind, margin):
    """A boosted candidate's validation scores from its (nv,) margins:
    the HOST model's exact score transform (evaluators/device_metrics.py
    host twins), so the device metric ranks candidates identically to
    the host evaluator."""
    from ...evaluators.device_metrics import binary_from_sigmoid
    if spec_kind == "binary":
        return binary_from_sigmoid(margin)
    return margin                           # regression values


def _candidate_scores(kind, spec_kind, depth, feats, thrs, leaves, base,
                      Xv, leaf=None):
    """Validation scores for ONE fitted tree candidate, on device: each
    validation row's leaf in every tree, leaf gather + tree reduction,
    then the host model's score transform (_gbt_scores; the forests' vote
    normalization, evaluators/device_metrics.py host twins).

    The leaf has two sources, the two forms of a fused fit+metric kernel
    (its static ``in_fit``). "traverse", ``leaf`` None: the raw validation
    matrix ``Xv`` (nv, d) is READ here and walked down every finished heap
    (``walk._traverse``, in its forms._traverse_form); the form for
    validation rows that are not rows of the fitted table
    (``validate_prepared``, direct callers). "in_fit", ``leaf`` (T, nv)
    given (_forest_body ``val_rows``): ``Xv`` is not read; the rows were
    routed by the fit itself. The same leaf, because a row's bin is the
    count of its column's edges strictly below ``x``, so ``packed[i, f]
    <= b`` is ``X[i, f] <= packed_thr[b]`` (exact where table and heap
    share a dtype: device binning, or host binning under x64; with
    float64 HOST binning and a float32 heap a value within one float32
    ulp above an edge bins right and walks left, and the in-fit leaf is
    the one the training rows got. A NaN at a denied split walks right;
    in the fit it stays left)."""
    from ...evaluators.device_metrics import (binary_from_votes,
                                              vote_probability)
    if leaf is None:
        leaf = jax.vmap(lambda fh, th: _walk._traverse(Xv, fh, th, depth)
                        )(feats, thrs)
    if leaves.ndim == 3:
        # a classification forest's votes, added up a tree at a time (the
        # sum the mean of the picked (T, nv, K) leaf rows makes, in its
        # order: tests/test_tree_models.py pins the bits). One pick of all
        # the rows is laid out by the chip's compiler, for a shallow tree,
        # with the K classes along a 128-wide tile: 64x the bytes at K = 2,
        # 7.7 GB for 18 lanes x 50 trees x 16,384 rows (PERF.md section 6,
        # PR 35)
        def add_tree(votes, tree):
            tree_leaves, tree_leaf = tree
            return votes + tree_leaves[tree_leaf], None
        votes, _ = jax.lax.scan(
            add_tree, jnp.zeros((leaf.shape[1], leaves.shape[2]),
                                leaves.dtype), (leaves, leaf))
        agg = votes / leaves.shape[0]                   # (nv, K) votes
    else:
        vals = leaves[jnp.arange(leaves.shape[0])[:, None], leaf]
        if kind == "gbt":
            return _gbt_scores(spec_kind, base + jnp.sum(vals, axis=0))
        agg = jnp.mean(vals, axis=0)                    # (nv,) values
    if spec_kind == "binary":
        return binary_from_votes(agg)
    if spec_kind == "multiclass":
        return vote_probability(agg)
    return agg


@functools.lru_cache(maxsize=32)
def _forest_eval_kernel(statics: tuple, spec: tuple, mesh=None,
                        in_fit: bool = False, head: bool = False):
    """Fit + validation-metric fusion of _forest_fg_kernel: candidates
    never materialize on host — the program returns one metric scalar
    per candidate, a vector a depth block (see evaluators/device_metrics.py
    for why).

    ``val`` is the stacked validation matrix (F, nv, d) in the "traverse"
    form and, with ``in_fit``, the (F, nv) int32 positions of the
    validation rows in the fitted table instead (see _candidate_scores): the
    program then holds no ``_traverse`` and never sees ``X_val``. With
    ``head`` (an in-fit form; see _fold_grid_head) ``val`` is the (F, n)
    ``order`` of _fold_order, whose tail those positions are, and ``mask``
    lies in that order: every fold's lanes fit on the table in the fold's
    own order (see _by_fold) and the leaves of the held-out rows are the
    tail of every tree's nodes."""
    (kind, depths, num_classes, num_trees, max_features, pool_cfg,
     impurity, bootstrap, hist_mode) = statics
    from jax.sharding import PartitionSpec as P
    from ...evaluators.device_metrics import metric_fn
    mfn = metric_fn(*spec)
    pooled = pool_cfg is not None

    def forest_batched(blocks, val, yv, packed, feat_of, block_start,
                       packed_thr, binned, col_thr, narrow, wide, y, key):
        body = functools.partial(
            _forest_body, kind=kind, depth=depths, num_classes=num_classes,
            num_trees=num_trees, max_features=max_features,
            pool_cfg=pool_cfg, impurity=impurity, bootstrap=bootstrap,
            hist_mode=hist_mode)

        def fold_fit(tables, lane_args, lanes):
            # a pooled tree reads ``binned`` alone, any other ``packed``
            rows, fold_y = tables
            return body(
                packed if pooled else rows, feat_of, block_start,
                packed_thr, rows if pooled else binned, col_thr, narrow,
                wide, fold_y, key, *lane_args[:4], lanes=lanes,
                hist_rows=val.shape[1] - yv.shape[1])
        with jax.named_scope("fg.forest"):
            if head:
                fitted = _by_fold(fold_fit, blocks, val,
                                  (binned if pooled else packed, y))
            else:
                (mask, mi, mg, sr, fi), lanes = _all_lanes(blocks)
                fitted = body(
                    packed, feat_of, block_start, packed_thr, binned,
                    col_thr, narrow, wide, y, key, mask, mi, mg, sr,
                    lanes=lanes, val_rows=val[fi] if in_fit else None)
            with jax.named_scope("fg.metric"):
                scores = tuple(
                    jax.vmap(lambda fold, feats, thrs, leaves, *in_fit_leaf:
                             _candidate_scores(
                                 "forest", spec[0], depth, feats, thrs,
                                 leaves, 0.0,
                                 None if in_fit else val[fold],
                                 *in_fit_leaf))(block[-1], *trees)
                    for depth, block, trees in zip(depths, blocks, fitted))
            return _lane_metrics(mfn, yv, blocks, scores)

    return jax.jit(_shard_blocks(
        forest_batched, mesh, len(depths), 4, 12,
        P("models")))


def _boosted_fits(body, head: bool, blocks: tuple, val, yv, packed,
                  feat_of, block_start, packed_thr, y, key):
    """The fits of a boosted fold-grid program's lanes, a tuple a depth
    block (``body``: _gbt_body or _gbt_softmax_body with its statics
    bound): the lanes one after another over the shared table, or with
    ``head`` every fold's lanes over the table in the fold's own order
    (see _by_fold; ``val`` is then the (F, n) ``order``)."""
    if not head:
        lane_args, lanes = _all_lanes(blocks)
        return body(packed, feat_of, block_start, packed_thr, y, key,
                    *lane_args[:6], lanes=lanes)
    return _by_fold(
        lambda tables, lane_args, lanes: body(
            tables[0], feat_of, block_start, packed_thr, tables[1], key,
            *lane_args[:6], lanes=lanes,
            hist_rows=val.shape[1] - yv.shape[1]),
        blocks, val, (packed, y))


@functools.lru_cache(maxsize=32)
def _gbt_eval_kernel(statics: tuple, spec: tuple, mesh=None,
                     in_fit: bool = False, head: bool = False):
    """Fit + validation-metric fusion of _gbt_fg_kernel; ``val``,
    ``in_fit`` and ``head`` as in _forest_eval_kernel (the in-fit form
    scores the validation rows from the fit's final margins, _gbt_body:
    picked at their positions, or with ``head`` the tail of the lane's
    rows)."""
    depths, num_rounds, objective, hist_mode = statics
    from jax.sharding import PartitionSpec as P
    from ...evaluators.device_metrics import metric_fn
    mfn = metric_fn(*spec)

    def batched(blocks, val, yv, packed, feat_of, block_start, packed_thr,
                y, key):
        with jax.named_scope("fg.gbt"):
            fitted = _boosted_fits(
                functools.partial(
                    _gbt_body, depth=depths, num_rounds=num_rounds,
                    objective=objective, hist_mode=hist_mode),
                head, blocks, val, yv, packed, feat_of, block_start,
                packed_thr, y, key)

            def lane_scores(depth, fold, feats, thrs, leaves, base,
                            margins):
                if head:
                    return _gbt_scores(spec[0], margins[-yv.shape[1]:])
                if in_fit:
                    return _gbt_scores(spec[0], margins[val[fold]])
                return _candidate_scores("gbt", spec[0], depth, feats,
                                         thrs, leaves, base, val[fold])
            with jax.named_scope("fg.metric"):
                scores = tuple(
                    jax.vmap(functools.partial(lane_scores, depth))(
                        block[-1], *trees)
                    for depth, block, trees in zip(depths, blocks, fitted))
            return _lane_metrics(mfn, yv, blocks, scores)

    return jax.jit(_shard_blocks(
        batched, mesh, len(depths), 6, 8, P("models")))


@functools.lru_cache(maxsize=32)
def _gbt_softmax_fg_kernel(statics: tuple, mesh=None):
    """Fold×grid kernel for K-class softmax boosting (the multiclass
    XGBoost path, _gbt_softmax_body) — mirrors _gbt_fg_kernel's
    candidate contract."""
    depths, num_rounds, num_classes, hist_mode = statics
    from jax.sharding import PartitionSpec as P

    def batched(blocks, packed, feat_of, block_start, packed_thr, y, key):
        (mask, ss, rl, ga, mcw, sub), lanes = _all_lanes(blocks)
        with jax.named_scope("fg.gbt_softmax"):
            return tuple(fitted[:4] for fitted in _gbt_softmax_body(
                packed, feat_of, block_start, packed_thr, y, key, mask,
                ss, rl, ga, mcw, sub, depth=depths, num_rounds=num_rounds,
                num_classes=num_classes, hist_mode=hist_mode, lanes=lanes))

    return jax.jit(_shard_blocks(
        batched, mesh, len(depths), 5, 6,
        (P("models", None, None, None), P("models", None, None, None),
         P("models", None, None, None), P("models", None))))


def _softmax_margins(feats, thrs, leaves, base, depth: int, Xv):
    """(nv, K) margins of one softmax-boosted candidate on device —
    the exact twin of GBTMulticlassClassifierModel.predict_raw."""
    R, K, H = feats.shape
    flat_f = feats.reshape(R * K, H)
    flat_t = thrs.reshape(R * K, H)
    leaf = jax.vmap(lambda fh, th: _walk._traverse(Xv, fh, th, depth)
                    )(flat_f, flat_t)                     # (R*K, nv)
    flat_l = leaves.reshape(R * K, -1)
    vals = flat_l[jnp.arange(R * K)[:, None], leaf]
    return base + vals.reshape(R, K, -1).sum(axis=0).T    # (nv, K)


@functools.lru_cache(maxsize=32)
def _gbt_softmax_eval_kernel(statics: tuple, spec: tuple, mesh=None,
                             in_fit: bool = False, head: bool = False):
    """Fit + validation-metric fusion of _gbt_softmax_fg_kernel: the
    multiclass metric consumes softmax probabilities, matching the host
    ClassifierModel.raw_to_probability ranking exactly. ``val``,
    ``in_fit`` and ``head`` as in _gbt_eval_kernel."""
    depths, num_rounds, num_classes, hist_mode = statics
    from jax.sharding import PartitionSpec as P
    from ...evaluators.device_metrics import metric_fn
    mfn = metric_fn(*spec)

    def batched(blocks, val, yv, packed, feat_of, block_start, packed_thr,
                y, key):
        with jax.named_scope("fg.gbt_softmax"):
            fitted = _boosted_fits(
                functools.partial(
                    _gbt_softmax_body, depth=depths, num_rounds=num_rounds,
                    num_classes=num_classes, hist_mode=hist_mode),
                head, blocks, val, yv, packed, feat_of, block_start,
                packed_thr, y, key)

            def lane_scores(depth, fold, feats, thrs, leaves, base,
                            margins):
                if head:
                    margins = margins[-yv.shape[1]:]
                elif in_fit:
                    margins = margins[val[fold]]
                else:
                    margins = _softmax_margins(feats, thrs, leaves, base,
                                               depth, val[fold])
                return jax.nn.softmax(margins, axis=1)
            with jax.named_scope("fg.metric"):
                scores = tuple(
                    jax.vmap(functools.partial(lane_scores, depth))(
                        block[-1], *trees)
                    for depth, block, trees in zip(depths, blocks, fitted))
            return _lane_metrics(mfn, yv, blocks, scores)

    return jax.jit(_shard_blocks(
        batched, mesh, len(depths), 6, 8, P("models")))


#: (kernel kind, statics, call shape) triples seen — each is one XLA
#: compile (the jit caches on shapes too, so factory-cache hits with new
#: lane counts still compile)
_COMPILE_KEYS: set = set()


def _note_compile(kind: str, statics: tuple, shape: tuple) -> None:
    _COMPILE_KEYS.add((kind, statics, shape))


def tree_kernel_compiles() -> int:
    """Distinct compiled fold×grid tree programs so far in this process
    (the compile-count diagnostic chip_smoke.py reports)."""
    return len(_COMPILE_KEYS)


#: grid params the batched forest kernel traces per candidate vs the
#: statics that partition the grid into shape groups
_FOREST_TRACED = ("min_instances_per_node", "min_info_gain",
                  "subsampling_rate")
_FOREST_STATIC = ("max_depth", "num_trees", "max_bins", "impurity",
                  "feature_subset_strategy", "seed")
_GBT_TRACED = ("step_size", "reg_lambda", "gamma", "min_child_weight",
               "subsample", "eta")
_GBT_STATIC = ("max_depth", "num_rounds", "max_bins", "seed", "num_round")
#: the kernel-facing subsets ("eta"/"num_round" are facade aliases of
#: step_size/num_rounds — valid in grids, not separate lanes/keys)
_GBT_TILED = ("step_size", "reg_lambda", "gamma", "min_child_weight",
              "subsample")
_GBT_SKEY = ("max_depth", "num_rounds", "max_bins", "seed")


class _DepthBlock(NamedTuple):
    """The lanes of a fold-grid group that grow to one ``depth`` (see
    _candidate_groups): ``members`` the block's (grid index, candidate)
    pairs; ``lanes`` its per-lane arguments ``(masks, *traced_vecs)``,
    fold-major over the members and padded to the mesh's shard count;
    ``fidx`` each lane's fold index (0 on a padding lane), which the fused
    fit+metric kernels take as one more per-lane argument; ``count`` the
    lanes before padding."""
    depth: int
    members: list
    lanes: tuple
    fidx: np.ndarray
    count: int


def tree_depth_blocks(blocks) -> dict:
    """The depth blocks of one fold-grid group (see _candidate_groups),
    ``{"blocks": b, "lane_levels": m}``: ``b`` the distinct depths of the
    group, each a block of lanes in its program; ``m`` the sum over the
    group's lanes, padding left out, of the depth each grows to (a default
    grid's 18 points x 3 folds: 3 blocks and 18 x (3 + 6 + 12) = 378, where
    every lane run to depth 12 would be 648)."""
    return {"blocks": len(blocks),
            "lane_levels": sum(b.count * b.depth for b in blocks)}


def _candidate_groups(est, grid, masks, mesh, traced_fields, skey_fields):
    """The shared fold-major candidate-batching contract of the three
    fold×grid drivers (forest / binary-GBT / softmax-GBT): partition
    grid points into static shape groups, one program each, and lay a
    group's lanes out as DEPTH BLOCKS: one block a distinct ``max_depth``
    of the group, ascending, each block fold-major over its own members
    (lane ``f * len(members) + j``), with the traced hyperparameter
    vectors tiled to its lanes, and padded to the mesh shard count on its
    own, so that every chip holds its share of every depth. Under the
    "static" depth mode ``max_depth`` is part of the group's key and every
    group is one block; under "blocks" it is not (see _depth_mode).

    Yields (cand0, blocks) per group, ``blocks`` a tuple of
    :class:`_DepthBlock`, whose depths are the static the kernels take."""
    static_depth = _forms._depth_mode() == "static"
    F, n = masks.shape
    groups: Dict[tuple, list] = {}
    for gi, p in enumerate(grid):
        cand = est.with_params(**p)
        key = tuple(None if f == "max_depth" and not static_depth
                    else getattr(cand, f, "") for f in skey_fields)
        groups.setdefault(key, []).append((gi, cand))
    for group in groups.values():
        blocks = []
        for depth in sorted({c.max_depth for _, c in group}):
            members = [m for m in group if m[1].max_depth == depth]
            gk = len(members)
            vecs = [np.tile([float(getattr(c, f)) for _, c in members], F)
                    for f in traced_fields]
            lanes, count = pad_candidates(
                mesh, [np.repeat(masks, gk, axis=0), *vecs], n)
            fidx = np.zeros(len(lanes[0]), dtype=np.int32)
            fidx[:count] = np.repeat(np.arange(F, dtype=np.int32), gk)
            blocks.append(_DepthBlock(depth, members, tuple(lanes), fidx,
                                      count))
        yield group[0][1], tuple(blocks)


def _eval_ctx_parts(eval_ctx):
    """(X_val, y_val, spec, val_rows) of a fold-grid driver's ``eval_ctx``:
    ``(X_val (F, nv, d), y_val (F, nv), spec[, val_rows])``. ``val_rows``
    ((F, nv) positions of each fold's validation rows in the fitted table;
    absent or None: they are foreign to it) selects the fused kernels' form
    (see _candidate_scores): given, ``X_val`` is never read (and may be
    None); else ``X_val`` is uploaded and walked."""
    X_val, y_val, spec, *rest = eval_ctx
    val_rows = rest[0] if rest else None
    if val_rows is not None:
        val_rows = np.asarray(val_rows, dtype=np.int32)
        if val_rows.shape != np.shape(y_val):
            raise ValueError(
                f"val_rows {val_rows.shape} must name one row of the "
                f"fitted table per validation label {np.shape(y_val)}")
    return X_val, y_val, spec, val_rows


class _FoldGridHead(NamedTuple):
    """What :func:`_fold_grid_head` hands a fold-grid driver: the labels
    ``y`` on the device; with an ``eval_ctx`` the fused kernels' ``val``
    (the validation matrix, the validation rows' positions, or every
    fold's row order: see ``form``), the validation labels ``yv`` and the
    metric ``spec``, else None; ``in_fit`` (see _candidate_scores);
    ``hist_rows``, the leading rows of a lane that its level histograms
    contract where the lanes see the table in their fold's own order (see
    _fold_order), else None; ``rows``, the table's; and the candidate
    ``groups``."""
    y: jnp.ndarray
    val: Optional[jnp.ndarray]
    yv: Optional[jnp.ndarray]
    spec: Optional[tuple]
    in_fit: bool
    hist_rows: Optional[int]
    rows: int
    groups: Iterable

    @property
    def form(self) -> tuple:
        """The fused kernels' ``(in_fit, head)``."""
        return self.in_fit, self.hist_rows is not None

    @property
    def hist_row_share(self) -> float:
        """The rows a lane's histograms contract over the rows it holds."""
        return (self.hist_rows or self.rows) / self.rows


def _fold_grid_head(y, eval_ctx, masks, mesh, groups_of) -> _FoldGridHead:
    """What a fold-grid driver does on the host before its first design, under
    the span ``search.head``: the labels and (with ``eval_ctx``) the stacked
    validation folds go to the device, and the first candidate group is laid
    out (``groups_of(masks)``: _candidate_groups over the driver's fields;
    the later groups stay lazy: a group's masks are lanes x rows).
    Of the validation folds the labels always go; the matrix ``X_val`` only
    in the "traverse" form (see _eval_ctx_parts): with ``val_rows`` the
    (F, nv) int32 row indices go in its place and the matrix is not read.

    With ``val_rows``, no mesh (under one a chip's lanes mix folds, see
    _shard_blocks) and a histogram mode of the ``matmul`` family (a question
    of the backend alone, see _hist_mode) the form is ``head``: every lane
    sees the table in its fold's own order where the folds allow it (see
    _fold_order). The (F, n) order goes to the device instead of the (F, nv)
    positions, which are its tail, and the groups' masks are laid out in it,
    here on the host."""
    with _trace.span("search.head"):
        y_j = jnp.asarray(y)
        val_j = yv_j = spec = hist_rows = None
        in_fit = False
        n = masks.shape[1]
        if eval_ctx is not None:
            X_val, y_val, spec, val_rows = _eval_ctx_parts(eval_ctx)
            in_fit = val_rows is not None
            order = None
            if (in_fit and mesh is None
                    and _forms._hist_mode(n, 1) != "scatter"):
                order = _fold_order(masks, val_rows)
            if order is not None:
                hist_rows = n - val_rows.shape[1]
                masks = np.take_along_axis(masks, order, axis=1)
                val_j = jnp.asarray(order)
            else:
                val_j = (jnp.asarray(val_rows) if in_fit else
                         jnp.asarray(np.asarray(X_val, dtype=np.float64)))
            yv_j = jnp.asarray(np.asarray(y_val, dtype=np.float64))
        groups = groups_of(masks)
        first = next(groups, None)
        if first is not None:
            groups = itertools.chain([first], groups)
        return _FoldGridHead(y_j, val_j, yv_j, spec, in_fit, hist_rows, n,
                             groups)


def _run_blocks(fn, blocks, fused: bool, *shared, hist_mode: str,
                hist_row_share: float = 1.0) -> list:
    """One call of a fold-grid program (see _shard_blocks) under its
    ``search.fetch`` span, and its result on the host, an entry a depth
    block (a metric vector, or a tuple of tree arrays), each block's
    padding lanes cut. A ``fused`` fit+metric kernel takes each lane's
    fold index after the block's other per-lane arguments.

    The span's attributes are the call's own: ``hist_mode``, the histogram
    mode the program was compiled with (a static of every tree kernel; the
    route, node-sum and pick forms follow from it, see forms._route_form
    and forms._sums_form), ``depth_blocks`` / ``depth_lane_levels`` (see
    tree_depth_blocks) and ``hist_row_share`` (see _FoldGridHead)."""
    counts = tree_depth_blocks(blocks)
    with _trace.span("search.fetch", hist_mode=hist_mode,
                     depth_blocks=counts["blocks"],
                     depth_lane_levels=counts["lane_levels"],
                     hist_row_share=hist_row_share):
        lanes = tuple(tuple(jnp.asarray(a) for a in
                            b.lanes + ((b.fidx,) if fused else ()))
                      for b in blocks)
        # the first call traces and lowers: a quarter of a million small
        # Python calls, which must not sit at the end of a frame-stack chunk
        out = with_frame_room(lambda: fn(lanes, *shared))
        return [jax.tree_util.tree_map(lambda a: to_host(a)[:b.count], res)
                for b, res in zip(blocks, out)]


def _scatter_block_metrics(metric_mat, blocks, fetched) -> None:
    """Write a group's metric vectors, one a depth block (fold-major over
    the block's members), back into the (F, G) matrix."""
    F = metric_mat.shape[0]
    for b, mm in zip(blocks, fetched):
        metric_mat[:, [gi for gi, _ in b.members]] = mm.reshape(
            F, len(b.members))


def _block_lanes(blocks, fetched, F: int):
    """``(f, gi, candidate, the lane's arrays)`` of every fitted lane of
    a model-materializing fold-grid call: a block's heaps are at its
    lanes' own depth."""
    for b, arrays in zip(blocks, fetched):
        for f in range(F):
            for j, (gi, cand) in enumerate(b.members):
                c = f * len(b.members) + j
                yield f, gi, cand, tuple(a[c] for a in arrays)


def _fold_edge_recurse(fold_grid_fn, est, X, y, masks, grid, mesh,
                       eval_ctx, **kw):
    """TX_TREE_EDGES=fold driver: one recursive single-fold call per
    fold, each binning with edges from THAT fold's train rows only.
    Returns the same (F, G) matrix / per-fold model lists the fold-major
    call would. Costs one extra compile per static group (single-fold
    candidate shape) but removes the only place validation rows could
    influence training (quantile edges)."""
    F = masks.shape[0]
    outs = []
    for f in range(F):
        rows = np.nonzero(masks[f] > 0)[0]
        sub_eval = None
        if eval_ctx is not None:
            # one fold of the SAME table a call: its val_rows stay valid
            X_val, y_val, spec, val_rows = _eval_ctx_parts(eval_ctx)
            sub_eval = (X_val if X_val is None else X_val[f:f + 1],
                        y_val[f:f + 1], spec,
                        val_rows if val_rows is None
                        else val_rows[f:f + 1])
        outs.append(fold_grid_fn(est, X, y, masks[f:f + 1], grid, mesh,
                                 eval_ctx=sub_eval, edge_rows=rows, **kw))
    if eval_ctx is not None:
        return np.concatenate(outs, axis=0)
    return [o[0] for o in outs]


def _forest_fold_grid(est, X, y, masks, grid, mesh, classification: bool,
                      eval_ctx=None, edge_rows=None):
    """All (fold, grid point) forest candidates in vmapped programs (one
    per static shape group), optionally sharded over a mesh ``models``
    axis — see the kernel docstrings for the bin-edge deviation.

    With ``eval_ctx`` (see _eval_ctx_parts: ``(X_val (F,nv,d), y_val
    (F,nv), spec[, val_rows])``) the fused fit+metric kernels run instead
    and the return value is the (F, G) validation-metric matrix — fitted
    trees never reach the host. ``val_rows`` given: only those row indices are
    read, the validation scores come from the leaves the fit put the rows
    in; None: ``X_val`` is uploaded and walked down the finished trees."""
    masks = np.asarray(masks, dtype=np.float64)
    if edge_rows is None and _forms._fold_edges_mode():
        return _fold_edge_recurse(
            _forest_fold_grid, est, X, y, masks, grid, mesh, eval_ctx,
            classification=classification)
    grid = [dict(p) for p in (list(grid) or [{}])]
    allowed = set(_FOREST_TRACED) | set(_FOREST_STATIC)
    for p in grid:
        extra = set(p) - allowed
        if extra:
            raise NotImplementedError(
                f"batched tree kernel cannot vary {sorted(extra)}")
    F, n = masks.shape
    G = len(grid)
    d = X.shape[1]
    k = num_classes(y)
    models = [[None] * G for _ in range(F)]
    metric_mat = np.full((F, G), np.nan)
    head = _fold_grid_head(
        y, eval_ctx, masks, mesh, lambda m: _candidate_groups(
            est, grid, m, mesh, _FOREST_TRACED, _FOREST_STATIC))
    model_cls = (TreeEnsembleClassifierModel if classification
                 else TreeEnsembleRegressorModel)
    for cand0, blocks in head.groups:
        with _trace.span("search.design"):
            design, widths = _design_args(X, cand0.max_bins,
                                          edge_rows=edge_rows)
        mf = _resolve_max_features(cand0.feature_subset_strategy, d,
                                   classification) \
            if cand0.bootstrap else None
        (narrow, wide), pool_cfg, mf = _pool_plan(widths, mf)
        statics = ("cls" if classification else "reg",
                   tuple(b.depth for b in blocks),
                   k if classification else 0, cand0.num_trees, mf,
                   pool_cfg, getattr(cand0, "impurity", ""),
                   cand0.bootstrap,
                   _forms._hist_mode(head.hist_rows or n,
                                     int(design[1].shape[0])))
        _note_compile("forest", statics,
                      tuple(b.lanes[0].shape for b in blocks))
        key = jax.random.PRNGKey(cand0.seed)
        if eval_ctx is not None:
            fetched = _run_blocks(
                _forest_eval_kernel(statics, head.spec, mesh, *head.form),
                blocks, True, head.val, head.yv, *design, narrow, wide,
                head.y, key, hist_mode=statics[-1],
                hist_row_share=head.hist_row_share)
            _scatter_block_metrics(metric_mat, blocks, fetched)
            continue
        fetched = _run_blocks(_forest_fg_kernel(statics, mesh), blocks,
                              False, *design, narrow, wide, head.y, key,
                              hist_mode=statics[-1])
        for f, gi, cand, (fe, th, le) in _block_lanes(blocks, fetched, F):
            models[f][gi] = model_cls(fe, th, le, depth=cand.max_depth,
                                      n_features=d)
    return metric_mat if eval_ctx is not None else models


def _gbt_fold_grid(est, X, y, masks, grid, mesh, objective: str,
                   eval_ctx=None, edge_rows=None):
    # mirrors _forest_fold_grid's candidate contract (fold-major
    # flattening, static-group partitioning, padding, eval_ctx fusion,
    # TX_TREE_EDGES=fold recursion) — change both together
    masks = np.asarray(masks, dtype=np.float64)
    if edge_rows is None and _forms._fold_edges_mode():
        return _fold_edge_recurse(
            _gbt_fold_grid, est, X, y, masks, grid, mesh, eval_ctx,
            objective=objective)
    grid = [dict(p) for p in (list(grid) or [{}])]
    allowed = set(_GBT_TRACED) | set(_GBT_STATIC)
    for p in grid:
        extra = set(p) - allowed
        if extra:
            raise NotImplementedError(
                f"batched GBT kernel cannot vary {sorted(extra)}")
    F, n = masks.shape
    G = len(grid)
    d = X.shape[1]
    models = [[None] * G for _ in range(F)]
    metric_mat = np.full((F, G), np.nan)
    model_cls = (GBTClassifierModel if objective == "logistic"
                 else GBTRegressorModel)
    head = _fold_grid_head(
        y, eval_ctx, masks, mesh, lambda m: _candidate_groups(
            est, grid, m, mesh, _GBT_TILED, _GBT_SKEY))
    for cand0, blocks in head.groups:
        with _trace.span("search.design"):
            design, _ = _design_args(X, cand0.max_bins,
                                     edge_rows=edge_rows)
        statics = (tuple(b.depth for b in blocks), cand0.num_rounds,
                   objective,
                   _forms._hist_mode(head.hist_rows or n,
                                     int(design[1].shape[0])))
        _note_compile("gbt", statics,
                      tuple(b.lanes[0].shape for b in blocks))
        key = jax.random.PRNGKey(cand0.seed)
        if eval_ctx is not None:
            fetched = _run_blocks(
                _gbt_eval_kernel(statics, head.spec, mesh, *head.form),
                blocks, True, head.val, head.yv, *design[:4], head.y, key,
                hist_mode=statics[-1], hist_row_share=head.hist_row_share)
            _scatter_block_metrics(metric_mat, blocks, fetched)
            continue
        fetched = _run_blocks(_gbt_fg_kernel(statics, mesh), blocks, False,
                              *design[:4], head.y, key,
                              hist_mode=statics[-1])
        for f, gi, cand, (fe, th, le, base) in _block_lanes(
                blocks, fetched, F):
            models[f][gi] = model_cls(fe, th, le, depth=cand.max_depth,
                                      base=float(base), n_features=d)
    return metric_mat if eval_ctx is not None else models


def _gbt_softmax_fold_grid(est, X, y, masks, grid, mesh, num_classes_k,
                           eval_ctx=None, edge_rows=None):
    # mirrors _gbt_fold_grid's candidate contract for the K-class
    # softmax objective — change all three drivers together
    masks = np.asarray(masks, dtype=np.float64)
    if edge_rows is None and _forms._fold_edges_mode():
        return _fold_edge_recurse(
            _gbt_softmax_fold_grid, est, X, y, masks, grid, mesh,
            eval_ctx, num_classes_k=num_classes_k)
    grid = [dict(p) for p in (list(grid) or [{}])]
    allowed = set(_GBT_TRACED) | set(_GBT_STATIC)
    for p in grid:
        extra = set(p) - allowed
        if extra:
            raise NotImplementedError(
                f"batched softmax-GBT kernel cannot vary {sorted(extra)}")
    F, n = masks.shape
    G = len(grid)
    d = X.shape[1]
    models = [[None] * G for _ in range(F)]
    metric_mat = np.full((F, G), np.nan)
    head = _fold_grid_head(
        y, eval_ctx, masks, mesh, lambda m: _candidate_groups(
            est, grid, m, mesh, _GBT_TILED, _GBT_SKEY))
    for cand0, blocks in head.groups:
        with _trace.span("search.design"):
            design, _ = _design_args(X, cand0.max_bins,
                                     edge_rows=edge_rows)
        statics = (tuple(b.depth for b in blocks), cand0.num_rounds,
                   num_classes_k,
                   _forms._hist_mode(head.hist_rows or n,
                                     int(design[1].shape[0])))
        _note_compile("gbt_softmax", statics,
                      tuple(b.lanes[0].shape for b in blocks))
        key = jax.random.PRNGKey(cand0.seed)
        if eval_ctx is not None:
            fetched = _run_blocks(
                _gbt_softmax_eval_kernel(statics, head.spec, mesh,
                                         *head.form),
                blocks, True, head.val, head.yv, *design[:4], head.y, key,
                hist_mode=statics[-1], hist_row_share=head.hist_row_share)
            _scatter_block_metrics(metric_mat, blocks, fetched)
            continue
        fetched = _run_blocks(_gbt_softmax_fg_kernel(statics, mesh), blocks,
                              False, *design[:4], head.y, key,
                              hist_mode=statics[-1])
        for f, gi, cand, (fe, th, le, base) in _block_lanes(
                blocks, fetched, F):
            models[f][gi] = GBTMulticlassClassifierModel(
                fe, th, le, depth=cand.max_depth, base=base, n_features=d)
    return metric_mat if eval_ctx is not None else models
