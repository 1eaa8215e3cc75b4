"""The fused fit+metric kernels score the validation rows where the fit
put them (the "in_fit" form, ``val_rows`` given) and walk the raw validation
matrix only for rows foreign to the fitted table (the "traverse" form):
``models/trees/fold_grid.py`` ``_candidate_scores``."""
import numpy as np
import pytest

from transmogrifai_tpu.evaluators import BinaryClassificationEvaluator
from transmogrifai_tpu.models import (GBTClassifier, LogisticRegression,
                                      RandomForestClassifier)
from transmogrifai_tpu.models.trees import (bodies, fold_grid, forms, grower,
                                            walk)
from transmogrifai_tpu.models.trees import design as tree_design
from transmogrifai_tpu.selector import CrossValidation
from tree_cases import (eval_forms, fold_arrays, host_only, infit_family,
                        infit_table, INFIT_FAMILIES)


@pytest.mark.parametrize("binning", ("host", "device"))
@pytest.mark.parametrize("family", INFIT_FAMILIES)
def test_in_fit_leaves_are_the_heaps_leaves(infit_env, family, binning):
    """(a) What the in-fit form reads for the held-out rows is what
    ``_traverse`` finds for them in the finished heaps: leaf for leaf in
    every tree of a forest, and in every round of a boosted fit (the
    margin the fit carries for EVERY row of the table is the walked
    one)."""
    import functools

    import jax
    import jax.numpy as jnp

    infit_env(binning)
    X, y = infit_table(family)
    est, _, evaluator = infit_family(family)
    masks, _, _, _, val_rows = fold_arrays(X, y, evaluator)
    mask, va = jnp.asarray(masks[0]), val_rows[0]
    design, widths = tree_design._design_args(X, est.max_bins)
    hist = forms._hist_mode(X.shape[0], int(design[1].shape[0]))
    key = jax.random.PRNGKey(3)
    Xa = jnp.asarray(X)

    def walked(feats, thrs):
        return np.asarray(jax.vmap(
            lambda f, t: walk._traverse(Xa, f, t, 12))(feats, thrs))

    if family.startswith("forest"):
        cls = family != "forest_reg"
        mf = tree_design._resolve_max_features(
            est.feature_subset_strategy, X.shape[1], cls)
        (narrow, wide), pool_cfg, mf = tree_design._pool_plan(widths, mf)
        assert (pool_cfg is not None) == (family == "forest_pooled")
        body = jax.jit(functools.partial(
            bodies._forest_body, kind="cls" if cls else "reg", depth=12,
            num_classes=2 if cls else 0, num_trees=3, max_features=mf,
            pool_cfg=pool_cfg, impurity="gini", bootstrap=True,
            hist_mode=hist))
        feats, thrs, leaves, leaf = body(
            *design, narrow, wide, jnp.asarray(y), key, mask, 1.0, 0.0,
            1.0, val_rows=jnp.asarray(va))
        np.testing.assert_array_equal(np.asarray(leaf),
                                      walked(feats, thrs)[:, va])
        assert len(np.unique(np.asarray(leaf))) > 8
        # and without the rows the body returns what it always did
        assert len(body(*design, narrow, wide, jnp.asarray(y), key, mask,
                        1.0, 0.0, 1.0)) == 3
    elif family == "gbt_softmax":
        body = jax.jit(functools.partial(
            bodies._gbt_softmax_body, depth=12, num_rounds=2,
            num_classes=3, hist_mode=hist))
        feats, thrs, leaves, base, margins = body(
            *design[:4], jnp.asarray(y), key, mask, 0.3, 1.0, 0.0, 0.0,
            1.0)
        want = np.asarray(fold_grid._softmax_margins(
            feats, thrs, leaves, base, 12, Xa))
        np.testing.assert_allclose(np.asarray(margins), want, atol=1e-9)
        feats, thrs = (np.asarray(a).reshape(6, -1) for a in (feats, thrs))
    else:
        body = jax.jit(functools.partial(
            bodies._gbt_body, depth=12, num_rounds=3, hist_mode=hist,
            objective="logistic" if family == "gbt_bin" else "squared"))
        feats, thrs, leaves, base, margins = body(
            *design[:4], jnp.asarray(y), key, mask, 0.1, 1.0, 0.0, 0.0,
            1.0)
        leaf = walked(feats, thrs)
        want = np.asarray(base) + np.asarray(leaves)[
            np.arange(3)[:, None], leaf].sum(axis=0)
        # every row of the table, the masked ones included
        np.testing.assert_allclose(np.asarray(margins), want, atol=1e-9)
    # the trees are deep enough for the compressed levels to have run
    assert np.isfinite(np.asarray(thrs)[..., 2 ** 9 - 1:]).any()


@pytest.mark.parametrize("binning", ("host", "device"))
@pytest.mark.parametrize("design", ("packed", "pooled"))
def test_in_fit_leaf_index_is_traverse(infit_env, design, binning):
    """(a), by index: ``_grow_tree``'s final node of EVERY row, weighted
    or not, is ``_traverse``'s leaf in the heap it returns: with the slot
    compression and the budget mask of a depth-12 tree, on a pooled
    sub-design (``feat_map``) as on the packed one."""
    import functools

    import jax
    import jax.numpy as jnp

    infit_env(binning)
    X, y = infit_table("forest_pooled")
    masks, _, _, _, val_rows = fold_arrays(
        X, y, BinaryClassificationEvaluator())
    (packed, feat_of, block_start, packed_thr, binned, col_thr), widths = \
        tree_design._design_args(X, 16)
    stats = jnp.asarray(np.eye(2)[y.astype(int)] * masks[0][:, None])
    grow = functools.partial(
        grower._grow_tree, depth=12, gain_fn=grower._gini_gain(1.0),
        min_info_gain=0.0, hist_mode=forms._hist_mode(*X.shape))
    if design == "pooled":
        (narrow, wide), pool_cfg, _ = tree_design._pool_plan(widths, 5)
        pool, *sub = tree_design._tree_pool(jax.random.PRNGKey(1), binned,
                                            col_thr, narrow, wide, pool_cfg)
        feat, thr, _, node = jax.jit(grow)(*sub, stats, feat_map=pool)
    else:
        feat, thr, _, node = jax.jit(grow)(packed, feat_of, block_start,
                                           packed_thr, stats)
    want = walk._traverse(jnp.asarray(X), feat, thr, 12)
    np.testing.assert_array_equal(np.asarray(node), np.asarray(want))
    assert len(np.unique(np.asarray(node)[val_rows[0]])) > 8
    assert np.isfinite(np.asarray(thr)[2 ** 9 - 1:]).any()


@pytest.mark.parametrize("family", ("forest_cls", "gbt_bin"))
def test_racing_mask_needs_explicit_rows(infit_env, family, monkeypatch):
    """(c) A racing rung zeroes training rows that are no validation rows:
    the rows ``masks == 0`` are then not the fold's validation rows, so the
    form follows what the caller says, not the mask. Without ``val_rows``
    the kernel walks ``X_val`` as it always did; with them it gives the
    same matrix."""
    infit_env("host")
    X, y = infit_table(family)
    est, grid, evaluator = infit_family(family)
    masks, spec, X_val, y_val, val_rows = fold_arrays(X, y, evaluator)
    rung = masks.copy()
    r = np.random.default_rng(5)
    for f in range(3):
        train = np.nonzero(masks[f] > 0)[0]
        rung[f, r.choice(train, size=len(train) // 2, replace=False)] = 0.0
    assert ((rung == 0).sum(axis=1) > val_rows.shape[1]).all()
    asked = eval_forms(monkeypatch)
    walked = est.eval_fold_grid_arrays(X, y, rung, grid, X_val, y_val, spec)
    assert asked and not any(in_fit for in_fit, _ in asked)
    in_fit = est.eval_fold_grid_arrays(X, y, rung, grid, X_val, y_val,
                                       spec, val_rows=val_rows)
    np.testing.assert_allclose(in_fit, walked, atol=1e-6, rtol=0)
    full = est.eval_fold_grid_arrays(X, y, masks, grid, X_val, y_val, spec)
    assert np.abs(full - walked).max() > 1e-6      # the rung did thin the fit


def test_validate_prepared_still_walks(infit_env, monkeypatch):
    """(c) Workflow-level CV hands over validation rows that are NOT in the
    fitted table: no ``val_rows``, the "traverse" form, today's matrix
    (the host evaluator's)."""
    infit_env("host")
    X, y = infit_table("gbt_bin")
    idx = np.random.default_rng(0).permutation(len(y))
    folds = [(X[idx[:240]], y[idx[:240]], X[idx[240:]], y[idx[240:]]),
             (X[idx[120:]], y[idx[120:]], X[idx[:120]], y[idx[:120]])]
    pool = [(GBTClassifier(num_rounds=3, max_depth=4, max_bins=16),
             [{"step_size": 0.1}, {"step_size": 0.3}]),
            (RandomForestClassifier(num_trees=3, max_depth=4, max_bins=16),
             [{"min_instances_per_node": 1}])]
    ev = BinaryClassificationEvaluator()
    asked = eval_forms(monkeypatch)
    dev = CrossValidation(ev, num_folds=2).validate_prepared(pool, folds)
    # both families, every fold: the traverse form (``in_fit`` false)
    assert len(asked) >= 2 and not any(in_fit for in_fit, _ in asked)
    host = CrossValidation(host_only(ev), num_folds=2).validate_prepared(
        pool, folds)
    for rd, rh in zip(dev.results, host.results):
        np.testing.assert_allclose(rd.metric_values, rh.metric_values,
                                   atol=1e-9)


@pytest.mark.parametrize("form", ("in_fit", "traverse"))
def test_eval_form_is_counted_once_a_kernel_and_in_the_program(
        infit_env, form):
    """(d) The kernel's ``in_fit`` static names its form, and the compiled
    program shows it: the in-fit program has no validation matrix among its
    parameters (the (F, nv) row indices stand in its place), the walking
    one has."""
    infit_env("host")
    X, y = infit_table("gbt_bin", seed=31)
    X = X[:300, :7]                     # shapes no other case compiles
    y = y[:300]
    est = GBTClassifier(num_rounds=2, max_depth=3, max_bins=8)
    masks, spec, X_val, y_val, val_rows = fold_arrays(
        X, y, BinaryClassificationEvaluator())
    captured, keys = [], []
    real = fold_grid._gbt_eval_kernel

    def spy(*key):
        keys.append(key)
        fn = real(*key)

        def call(*args):
            captured.append(fn.lower(*args).compile().as_text())
            return fn(*args)
        return call

    kwargs = {"val_rows": val_rows} if form == "in_fit" else {}
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(fold_grid, "_gbt_eval_kernel", spy)
        est.eval_fold_grid_arrays(X, y, masks, [{"gamma": 0.0}], X_val,
                                  y_val, spec, **kwargs)
    # (statics, spec, mesh, in_fit, head): one program, of this form
    (key,) = keys
    assert key[3] is (form == "in_fit")
    (hlo,) = captured
    entry = hlo[hlo.index("ENTRY"):]
    params = [ln for ln in entry.splitlines() if " parameter(" in ln]
    matrix = [ln for ln in params if "f64[3,100,7]" in ln]
    rows = [ln for ln in params if "s32[3,100]" in ln]
    assert (len(matrix), len(rows)) == ((0, 1) if form == "in_fit"
                                        else (1, 0))
    assert "fg.metric" in hlo


def test_search_fetch_span_says_which_eval_form(infit_env, monkeypatch):
    """(d) ``Validator.validate`` names the validation rows, so its tree
    programs are the in-fit ones (a spy on the kernel factories reads
    their ``in_fit`` static). Every ``search.fetch`` span carries the
    call's own attributes and no count of traces: a tree family's the
    ``hist_mode`` its program was compiled with, ``scatter`` on a CPU; the
    linear family's the ``design`` of its lanes, ``per_lane`` on a CPU."""
    from transmogrifai_tpu.observability import trace
    infit_env("host")
    X, y = infit_table("gbt_bin", seed=37)
    X, y = X[:270, :6], y[:270]
    pool = [(GBTClassifier(num_rounds=2, max_depth=3, max_bins=8),
             [{"gamma": 0.0}, {"gamma": 0.1}]),
            (RandomForestClassifier(num_trees=2, max_depth=3, max_bins=8),
             [{"min_instances_per_node": 2}]),
            (LogisticRegression(), [{"reg_param": 0.1}])]
    asked = eval_forms(monkeypatch)
    trace.configure(True)
    trace.reset()                       # an earlier test's spans
    try:
        CrossValidation(BinaryClassificationEvaluator(), num_folds=3,
                        seed=3).validate(pool, X, y)
        spans = [s for s in trace.spans() if s["name"] == "search.fetch"]
    finally:
        trace.configure(False)
        trace.reset()
    assert len(asked) == 2 and all(in_fit for in_fit, _ in asked)
    trees = [s["attrs"] for s in spans if "hist_mode" in s["attrs"]]
    linear = [s["attrs"] for s in spans if "design" in s["attrs"]]
    assert len(trees) == 2                       # the two tree families
    for attrs in trees:
        assert attrs["hist_mode"] == "scatter"
        assert attrs["hist_row_share"] == 1.0    # no head form on a CPU
        assert not [k for k in attrs if k.startswith(("eval_", "route_"))]
    assert [attrs["design"] for attrs in linear] == ["per_lane"]
