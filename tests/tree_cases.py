"""Shared cases and spies of the tree tests (not a test module).

``spy_on`` wraps a function of the tree package so that a test sees each call
of it: called while JAX traces, each call is a trace. The tree programs carry
no count of their own forms; where a test proves that a form ran, it counts
the calls of the function that form calls."""
import copy

import jax
import jax.numpy as jnp
import numpy as np

from transmogrifai_tpu.evaluators import (BinaryClassificationEvaluator,
                                          MultiClassificationEvaluator,
                                          RegressionEvaluator)
from transmogrifai_tpu.models import (GBTClassifier, GBTRegressor,
                                      RandomForestClassifier,
                                      RandomForestRegressor,
                                      XGBoostClassifier)
from transmogrifai_tpu.models.trees import fold_grid, grower
from transmogrifai_tpu.models.trees import design as tree_design
from transmogrifai_tpu.selector import CrossValidation


def spy_on(monkeypatch, owner, name, record=None):
    """Replace ``owner.name`` (a module's function, or a class's method)
    with a wrapper that calls it and appends to the list this returns what
    it returned, or ``record(*args, **kwargs)`` of each call."""
    seen = []
    real = getattr(owner, name)

    def spy(*args, **kwargs):
        out = real(*args, **kwargs)
        seen.append(out if record is None else record(*args, **kwargs))
        return out
    monkeypatch.setattr(owner, name, spy)
    return seen


def eval_forms(monkeypatch):
    """The list of the ``(in_fit, head)`` statics of every fused fit+metric
    program the fold-grid drivers ask ``fold_grid``'s three kernel factories
    for, in the order asked: the form each call's program holds (see
    fold_grid._candidate_scores and fold_grid._fold_grid_head)."""
    seen = []
    for name in ("_forest_eval_kernel", "_gbt_eval_kernel",
                 "_gbt_softmax_eval_kernel"):
        real = getattr(fold_grid, name)

        def factory(statics, spec, mesh=None, in_fit=False, head=False,
                    real=real):
            seen.append((in_fit, head))
            return real(statics, spec, mesh, in_fit, head)
        monkeypatch.setattr(fold_grid, name, factory)
    return seen


# ---------------------------------------------------------------------------
# one ``_grow_tree`` call: the cases of the grower and form tests
# ---------------------------------------------------------------------------

def route_case(name):
    """(args, kwargs, lane masks or None) of one ``_grow_tree`` call. The
    stats are one-hot class counts times whole-number row weights, so every
    histogram sum is exact in either histogram mode and the two calls can
    differ in nothing but the routing form."""
    rng = np.random.default_rng(27)
    n, d, bins = 600, 10, 16
    if name == "wide_bins":
        d, bins = 24, 32            # TB = 768: indices a bf16 pass rounds
    if name == "pooled":
        d = 40
    X = rng.normal(size=(n, d))
    y = ((X[:, 0] > 0.1) ^ (X[:, 3] < -0.2) | (X[:, 7] > 1.0)).astype(int)
    design = tree_design._PackedDesign(X, max_bins=bins)
    packed, feat_of, block_start, thr = (
        jnp.asarray(design.packed), jnp.asarray(design.feat_of),
        jnp.asarray(design.block_start), jnp.asarray(design.packed_thr))
    onehot = jax.nn.one_hot(jnp.asarray(y), 2, dtype=thr.dtype)
    kwargs = dict(depth=4, gain_fn=grower._gini_gain(2.0), min_info_gain=1e-9)
    masks = None
    if name == "compressed":        # 2^3 > 7: levels 2-5 rank-compress and
        kwargs.update(depth=6, node_cap=7)      # the budget mask binds
    elif name == "denied_levels":   # a child holds 151 rows or more, so
        # no node of level 2 (298 rows at most) splits: all rows go left
        kwargs.update(depth=5, gain_fn=grower._gini_gain(151.0))
    elif name == "wide_bins":
        assert int(feat_of.shape[0]) > 256
        kwargs.update(depth=5)
    elif name == "pooled":          # as _forest_body passes a tree's pool
        (narrow, wide), cfg, mf = tree_design._pool_plan(design.widths, 2)
        assert cfg is not None
        pool, packed, feat_of, block_start, thr = tree_design._tree_pool(
            jax.random.PRNGKey(3), jnp.asarray(design.binned),
            jnp.asarray(design.col_thr), narrow, wide, cfg)
        kwargs.update(depth=5, feat_map=pool, max_features=mf,
                      feat_key=jax.random.PRNGKey(4))
    elif name == "vmap_lanes":
        masks = jnp.asarray(rng.integers(0, 3, size=(3, n)), thr.dtype)
        kwargs.update(depth=6, node_cap=7)
    return (packed, feat_of, block_start, thr, onehot), kwargs, masks


def grow(case_args, kwargs, masks, mode):
    """One ``_grow_tree`` call under ``hist_mode=mode``, freshly jitted (so
    it traces), vmapped over the lanes' row masks where the case has any."""
    packed, feat_of, block_start, thr, stats = case_args

    def one(st):
        return grower._grow_tree(packed, feat_of, block_start, thr, st,
                                 hist_mode=mode, **kwargs)
    if masks is None:
        return jax.jit(one)(stats)
    return jax.jit(jax.vmap(lambda m: one(stats * m[:, None])))(masks)


def sums_case(name):
    """``route_case`` plus two with S = 3 real-valued regression statistics
    (``_variance_stats``: weights and w*y in two pieces, under the variance
    gain): every level an identity level, and a ``node_cap=7`` tree whose
    last level is compressed, so its leaves are summed by (slot, side) and
    placed by the slots' node ids. Returns the case and whether its sums are
    exact in any order (whole-number statistics)."""
    if not name.startswith("real_"):
        return route_case(name) + (True,)
    (packed, feat_of, block_start, thr, _), kwargs, masks = route_case(
        "compressed" if name == "real_compressed" else "identity")
    rng = np.random.default_rng(31)
    n = packed.shape[0]
    w = rng.uniform(0.5, 2.0, size=n)
    target = (np.asarray(packed[:, 0]) / 4.0 - np.asarray(packed[:, 3]) % 5
              + rng.normal(size=n))
    stats = grower._variance_stats(jnp.asarray(w, thr.dtype),
                                   jnp.asarray(target, thr.dtype))
    # a node of 20 rows a side has no second split that mirrors its best
    kwargs.update(gain_fn=grower._variance_gain(20.0))
    return (packed, feat_of, block_start, thr, stats), kwargs, masks, False


def carry_case(name):
    """(args, kwargs, lane masks or None) of a ``_grow_tree`` call whose
    deep levels carry their slots (``_carry_slots``)."""
    args, kwargs, masks = route_case(
        "vmap_lanes" if name == "vmap_lanes" else "compressed")
    kwargs = dict(kwargs, gain_fn=grower._gini_gain(1.0))
    if name == "after_identity":    # levels 0-2 identity, 3 and 4 carried
        kwargs.update(depth=5, node_cap=8)
    elif name == "denied_levels":   # a child holds 130 rows or more, so no
        # node of levels 3-5 (210 rows at most) splits
        kwargs.update(gain_fn=grower._gini_gain(130.0))
    elif name == "cap_one":         # level 0 is no identity level either
        kwargs.update(depth=3, node_cap=1)
    elif name == "zero_weight":     # a third of the rows weigh nothing
        packed, feat_of, block_start, thr, onehot = args
        weight = (np.arange(packed.shape[0]) % 3 > 0).astype(float)
        args = (packed, feat_of, block_start, thr,
                onehot * jnp.asarray(weight)[:, None])
    else:
        assert name in ("budget_mask", "vmap_lanes"), name
    return args, kwargs, masks


# ---------------------------------------------------------------------------
# the fused fit+metric kernels' tables
# ---------------------------------------------------------------------------

def host_only(evaluator):
    """Evaluator clone whose device spec is disabled — forces the host
    per-candidate path."""
    ev = copy.copy(evaluator)
    ev.device_metric_spec = lambda: None
    return ev


def infit_table(family, seed=29):
    """A noisy table whose values float32 holds exactly (device binning
    compares float32 with float32; the suite runs float64), wide enough
    for a pooled forest, with a label of the family's kind."""
    r = np.random.default_rng(seed)
    n, d = 360, (24 if family == "forest_pooled" else 10)
    X = r.normal(size=(n, d))
    X[:, 4:] = (X[:, 4:] > 0.8)
    X = X.astype(np.float32).astype(np.float64)
    z = X[:, 0] - 0.5 * X[:, 1] + X[:, 5] + r.normal(size=n)
    if family in ("forest_reg", "gbt_reg"):
        y = z
    elif family == "gbt_softmax":
        y = np.digitize(z, [-0.7, 0.7]).astype(np.float64)
    else:
        y = (z > 0).astype(np.float64)
    return X, y


def infit_family(family):
    """(estimator, grid, evaluator) of a family: depth 12 and a shallower
    lane, so that under the ``blocks`` depth mode one program runs a
    depth-3 block beside a depth-12 one with its carried slots
    (``_carry_slots``) and the budget mask."""
    deep = dict(max_depth=12, max_bins=16)
    if family in ("forest_cls", "forest_pooled"):
        est = RandomForestClassifier(
            num_trees=3, feature_subset_strategy=(
                "auto" if family == "forest_pooled" else "all"), **deep)
        grid = [{"min_instances_per_node": 1}, {"max_depth": 3}]
        return est, grid, BinaryClassificationEvaluator()
    if family == "forest_reg":
        est = RandomForestRegressor(num_trees=3,
                                    feature_subset_strategy="all", **deep)
        return (est, [{"min_instances_per_node": 1}, {"max_depth": 3}],
                RegressionEvaluator())
    grid = [{"min_child_weight": 0.0}, {"max_depth": 3}]
    if family == "gbt_bin":
        return (GBTClassifier(num_rounds=3, **deep), grid,
                BinaryClassificationEvaluator())
    if family == "gbt_reg":
        return (GBTRegressor(num_rounds=3, **deep), grid,
                RegressionEvaluator())
    assert family == "gbt_softmax"
    return (XGBoostClassifier(num_round=2, eta=0.3, **deep), grid,
            MultiClassificationEvaluator())


INFIT_FAMILIES = ("forest_cls", "forest_reg", "forest_pooled", "gbt_bin",
                  "gbt_reg", "gbt_softmax")


def fold_arrays(X, y, evaluator):
    """The validator's own fold arrays, whose validation rows are rows of
    the table: (masks, spec, X_val, y_val, val_rows)."""
    cv = CrossValidation(evaluator, num_folds=3, seed=7, mesh=None)
    _, masks, _, spec, X_val, y_val, val_rows = cv._build_fold_arrays(X, y)
    for f in range(3):
        np.testing.assert_array_equal(X_val[f], X[val_rows[f]])
        assert not masks[f, val_rows[f]].any()
    return masks, spec, X_val, y_val, val_rows
