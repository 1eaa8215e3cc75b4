"""In the in-fit form without a mesh, under the ``matmul`` family, every
lane sees the table in its fold's own order (training rows first, held-out
rows last): the level contraction runs over the head, the held-out rows'
leaves and margins are the tail (``models/trees/fold_grid.py``
``_fold_order``, ``_by_fold``)."""
import numpy as np
import pytest

from transmogrifai_tpu.evaluators import (BinaryClassificationEvaluator,
                                          MultiClassificationEvaluator,
                                          RegressionEvaluator)
from transmogrifai_tpu.models import (GBTClassifier, GBTRegressor,
                                      RandomForestClassifier,
                                      RandomForestRegressor)
from transmogrifai_tpu.models.trees import bodies, fold_grid, forms, walk
from transmogrifai_tpu.models.trees import design as tree_design
from transmogrifai_tpu.selector import CrossValidation
from tree_cases import eval_forms


HEAD_FAMILIES = ("tree_cls2", "tree_cls7", "tree_reg", "gbt_bin", "gbt_reg",
                 "gbt_softmax")


def _head_folds(X, y, evaluator, split):
    """The validator's own fold arrays (not stratified: the folds are a
    function of the seed and the row count alone): three equal folds, or
    the one split of a train-validation split."""
    from transmogrifai_tpu.selector import TrainValidationSplit
    if split == "one_split":
        v = TrainValidationSplit(evaluator, train_ratio=2 / 3, seed=7,
                                 mesh=None)
    else:
        v = CrossValidation(evaluator, num_folds=3, seed=7, mesh=None)
    _, masks, _, spec, _, y_val, val_rows = v._build_fold_arrays(X, y)
    assert val_rows is not None
    return masks, spec, y_val, val_rows


def _head_case(family, split="three_folds"):
    """(estimator, grid, evaluator, X, y, masks, spec, y_val, val_rows) of a
    family WITHOUT row draws (a single tree has no bootstrap, a boosted fit
    at ``subsample`` 1.0 draws all ones), depth 12 beside 3 and 6: one
    program of three depth blocks.

    The label is made for the folds so that every statistic of the first
    tree is a multiple of 1/64 or coarser and every sum of them exact in
    ANY order: then the head form and the all-rows form can differ in
    nothing, where real statistics would let the summation order flip the
    exact ties of a small node's mirrored splits. Class counts are such
    statistics as they are. A regression label is a multiple of 1/64 that
    adds up to zero over every fold, so the centred label is the label; a
    boosted fit gets ONE round, from a base margin of exactly zero (classes
    balanced over every fold: p = 1/2, or 1/4 at four classes)."""
    from transmogrifai_tpu.models import (DecisionTreeClassifier,
                                          DecisionTreeRegressor,
                                          XGBoostClassifier)
    r = np.random.default_rng(37)
    n, d = 540, 8
    X = r.normal(size=(n, d)).astype(np.float32).astype(np.float64)
    z = X[:, 0] - 0.5 * X[:, 1] + 0.7 * X[:, 5] + 0.8 * r.normal(size=n)
    _, _, _, val_rows = _head_folds(X, z, RegressionEvaluator(), split)
    held = np.zeros(n, dtype=bool)
    held[val_rows.ravel()] = True
    groups = list(val_rows) + ([np.nonzero(~held)[0]] if not held.all()
                               else [])

    def balanced(k):
        y = np.zeros(n)
        for rows in groups:
            assert len(rows) % k == 0
            y[rows[np.argsort(z[rows])]] = np.repeat(np.arange(k),
                                                     len(rows) // k)
        return y
    deep = dict(max_depth=12, max_bins=16)
    depths = [{"max_depth": 3}, {"max_depth": 6}]
    if family in ("tree_cls2", "tree_cls7"):
        k = 2 if family == "tree_cls2" else 7
        y = np.digitize(z, np.quantile(z, np.arange(1, k) / k)).astype(
            np.float64)
        est, grid = DecisionTreeClassifier(**deep), [
            {"min_instances_per_node": 1}] + depths
        evaluator = (BinaryClassificationEvaluator() if k == 2
                     else MultiClassificationEvaluator())
    elif family in ("tree_reg", "gbt_reg"):
        y = np.round(z * 64) / 64
        for rows in groups:
            y[rows[0]] -= y[rows].sum()
            assert y[rows].sum() == 0.0
        evaluator = RegressionEvaluator()
        if family == "tree_reg":
            est, grid = DecisionTreeRegressor(**deep), [
                {"min_instances_per_node": 1}] + depths
        else:
            est, grid = GBTRegressor(num_rounds=1, **deep), [
                {"min_child_weight": 1.0}] + depths
    elif family == "gbt_bin":
        y, evaluator = balanced(2), BinaryClassificationEvaluator()
        est, grid = GBTClassifier(num_rounds=1, **deep), [
            {"min_child_weight": 0.0}] + depths
    else:
        assert family == "gbt_softmax"
        y, evaluator = balanced(4), MultiClassificationEvaluator()
        est, grid = XGBoostClassifier(num_round=1, eta=0.3, **deep), [
            {"min_child_weight": 0.0}] + depths
    return (est, grid, evaluator, X, y,
            *_head_folds(X, y, evaluator, split))


def _all_rows_form(call):
    """``call()`` with the in-fit form as it was: no fold gets an order of
    its own."""
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(fold_grid, "_fold_order", lambda masks, val_rows: None)
        return call()


@pytest.mark.parametrize("split", ("three_folds", "one_split"))
@pytest.mark.parametrize("family", HEAD_FAMILIES)
def test_head_form_gives_the_all_rows_metrics(head_env, family, split,
                                              monkeypatch):
    """(a) Without row draws the new form is the old one on the same folds:
    the (F, G) validation metrics of the all-rows form, at depths that
    cross the slot cap, for k folds and for one split. Bit for bit where
    the metric reads class votes; to 1e-12 where it reads leaf values, which
    are quotients of sums that agree to the last bit. (A spy on the kernel
    factories reads the form of each call's programs.)"""
    est, grid, _, X, y, masks, spec, y_val, val_rows = _head_case(family,
                                                                  split)
    asked = eval_forms(monkeypatch)
    head = est.eval_fold_grid_arrays(X, y, masks, grid, None, y_val, spec,
                                     val_rows=val_rows)
    head_forms = asked[:]
    del asked[:]
    all_rows = _all_rows_form(lambda: est.eval_fold_grid_arrays(
        X, y, masks, grid, None, y_val, spec, val_rows=val_rows))
    assert head.shape == (len(masks), 3) and np.isfinite(head).all()
    if family.startswith("tree_cls"):
        np.testing.assert_array_equal(head, all_rows)
    else:
        np.testing.assert_allclose(head, all_rows, atol=1e-12, rtol=0)
    assert len(np.unique(head.round(6))) >= 3       # lanes that differ
    # (in_fit, head): each call's programs hold its own form
    assert head_forms and set(head_forms) == {(True, True)}
    assert asked and set(asked) == {(True, False)}


@pytest.mark.parametrize("family", ("tree_cls2", "tree_cls7", "tree_reg",
                                    "gbt_bin", "gbt_reg"))
def test_head_form_grows_the_same_heaps(head_env, family):
    """(a) The heaps themselves: a fold's body over the table in the fold's
    own order (``_by_fold``) grows the trees of the lanes body over the
    shared table, feature for feature and threshold for threshold, and the
    held-out rows' leaves (a forest's) or margins (a boosted fit's) are the
    tail of the lane's rows."""
    import functools

    import jax
    import jax.numpy as jnp

    est, grid, _, X, y, masks, _, _, val_rows = _head_case(family)
    order = fold_grid._fold_order(masks, val_rows)
    F, n = masks.shape
    h = n - val_rows.shape[1]
    for f in range(F):
        np.testing.assert_array_equal(order[f, h:], val_rows[f])
        np.testing.assert_array_equal(np.sort(order[f]), np.arange(n))
        assert (np.diff(order[f, :h]) > 0).all()
    design, _ = tree_design._design_args(X, est.max_bins)
    key = jax.random.PRNGKey(est.seed)
    boosted = family.startswith("gbt")
    fields = fold_grid._GBT_TILED if boosted else fold_grid._FOREST_TRACED
    skey = fold_grid._GBT_SKEY if boosted else fold_grid._FOREST_STATIC

    def blocks_of(m):
        (_, blocks), = fold_grid._candidate_groups(est, grid, m, None, fields,
                                               skey)
        return tuple(tuple(jnp.asarray(a) for a in b.lanes) for b in blocks)
    depths = (3, 6, 12)
    if boosted:
        body = functools.partial(
            bodies._gbt_body, depth=depths, num_rounds=1, hist_mode="matmul",
            objective="logistic" if family == "gbt_bin" else "squared")
        shared = (*design[1:4],)

        def fit(packed, yy, lane_args, **kw):
            return body(packed, *shared, yy, key, *lane_args, **kw)
        tables = (design[0], jnp.asarray(y))
    else:
        body = functools.partial(
            bodies._forest_body, kind="reg" if family == "tree_reg" else "cls",
            depth=depths, num_classes={"tree_cls2": 2, "tree_cls7": 7}.get(
                family, 0), num_trees=1, max_features=None, pool_cfg=None,
            impurity="gini", bootstrap=False, hist_mode="matmul")
        empty = jnp.zeros((0,), jnp.int32)

        def fit(packed, yy, lane_args, **kw):
            return body(packed, *design[1:], empty, empty, yy, key,
                        *lane_args, **kw)
        tables = (design[0], jnp.asarray(y))
    lane_args, lanes = fold_grid._all_lanes(blocks_of(masks))
    all_rows = jax.jit(lambda: fit(*tables, lane_args, lanes=lanes))()
    in_order = jax.jit(lambda: fold_grid._by_fold(
        lambda tabs, args, ln: fit(*tabs, args, lanes=ln, hist_rows=h),
        blocks_of(np.take_along_axis(masks, order, axis=1)),
        jnp.asarray(order), tables))()
    for depth, old, new in zip(depths, all_rows, in_order):
        np.testing.assert_array_equal(np.asarray(old[0]), np.asarray(new[0]))
        np.testing.assert_array_equal(np.asarray(old[1]), np.asarray(new[1]))
        np.testing.assert_allclose(np.asarray(old[2]), np.asarray(new[2]),
                                   atol=1e-9)
        gk = old[0].shape[0] // F
        for lane in range(old[0].shape[0]):
            rows = val_rows[lane // gk]
            if boosted:     # margins of every row: the held-out ones last
                np.testing.assert_allclose(
                    np.asarray(new[4][lane, h:]),
                    np.asarray(old[4][lane])[rows], atol=1e-9)
            else:           # the fourth output: the tail's leaves
                assert new[3].shape[-1] == n - h
                walked = np.asarray(walk._traverse(
                    jnp.asarray(X), old[0][lane, 0], old[1][lane, 0],
                    depth))
                np.testing.assert_array_equal(
                    np.asarray(new[3][lane, 0]), walked[rows])
    deep = np.asarray(in_order[2][1])
    assert np.isfinite(deep[..., 2 ** 8 - 1:]).any()


@pytest.mark.parametrize("family", ("forest_cls", "forest_reg", "gbt_bin"))
def test_head_form_draws_follow_the_seed(head_env, family):
    """(b) With the row draws on (a forest's Poisson weights, a boosted
    round's subsample) the lanes draw in their own row order: another
    stream of the same algorithm. The metric matrix is still a function of
    the seed, and within the tolerance of the draw of the all-rows form."""
    r = np.random.default_rng(41)
    n = 900
    X = r.normal(size=(n, 6)).astype(np.float32).astype(np.float64)
    z = X[:, 0] - 0.5 * X[:, 1] + 0.5 * r.normal(size=n)
    if family == "forest_cls":
        est = RandomForestClassifier(num_trees=12, max_depth=4, max_bins=16)
        ev, y, grid = BinaryClassificationEvaluator(), (z > 0).astype(
            float), [{"min_instances_per_node": 2}, {"max_depth": 6}]
    elif family == "forest_reg":
        est = RandomForestRegressor(num_trees=12, max_depth=4, max_bins=16)
        ev, y, grid = RegressionEvaluator(), z, [
            {"min_instances_per_node": 2}, {"max_depth": 6}]
    else:
        est = GBTClassifier(num_rounds=10, max_depth=3, max_bins=16,
                            subsample=0.7)
        ev, y, grid = BinaryClassificationEvaluator(), (z > 0).astype(
            float), [{"step_size": 0.1}, {"max_depth": 5}]
    masks, spec, y_val, val_rows = _head_folds(X, y, ev, "three_folds")

    def search(estimator):
        return estimator.eval_fold_grid_arrays(
            X, y, masks, grid, None, y_val, spec, val_rows=val_rows)
    head = search(est)
    np.testing.assert_array_equal(head, search(est))
    other_seed = search(est.with_params(seed=est.seed + 1))
    assert np.abs(other_seed - head).max() > 1e-6
    all_rows = _all_rows_form(lambda: search(est))
    assert np.abs(all_rows - head).max() > 1e-9     # another stream
    scale = np.abs(all_rows).max()
    assert np.abs(all_rows - head).max() < 0.05 * scale
    assert np.abs(other_seed - head).max() < 0.05 * scale


@pytest.mark.parametrize("case", ("val_rows", "no_val_rows", "mesh",
                                  "weighted_held_out", "scatter"))
def test_search_fetch_span_says_which_rows_were_contracted(head_env, case):
    """(d) The program's ``head`` static (a spy on the kernel factories) and
    the ``search.fetch`` span: the head form and a ``hist_row_share`` of
    (n - nv) / n where the caller names the validation rows of the fitted
    table; every row and 1.0 without them (the traverse form), under a
    search mesh (a chip's lanes mix folds), where a mask gives a held-out
    row weight, and under the ``scatter`` mode. The span carries the
    ``hist_mode`` the program was compiled with."""
    from transmogrifai_tpu.observability import trace
    r = np.random.default_rng(43)
    sizes = {"val_rows": 273, "no_val_rows": 276, "mesh": 279,
             "weighted_held_out": 282, "scatter": 285}
    n = sizes[case]                     # shapes no other case compiles
    X = r.normal(size=(n, 5)).astype(np.float32).astype(np.float64)
    y = (X[:, 0] + 0.5 * r.normal(size=n) > 0).astype(float)
    est = GBTClassifier(num_rounds=2, max_depth=3, max_bins=8)
    masks, spec, y_val, val_rows = _head_folds(
        X, y, BinaryClassificationEvaluator(), "three_folds")
    kwargs, X_val, mesh = {"val_rows": val_rows}, None, None
    if case == "no_val_rows":
        kwargs, X_val = {}, np.stack([X[rows] for rows in val_rows])
    elif case == "mesh":
        from transmogrifai_tpu.parallel import make_mesh
        mesh = make_mesh({"models": 2})
    elif case == "weighted_held_out":
        masks = masks.copy()
        masks[1, val_rows[1, 0]] = 1.0
    elif case == "scatter":
        head_env.setattr(forms, "_hist_mode", lambda n, tb: "scatter")
    asked = eval_forms(head_env)
    trace.configure(True)
    trace.reset()                       # an earlier test's spans
    try:
        mm = est.eval_fold_grid_arrays(X, y, masks, [{"gamma": 0.0}], X_val,
                                       y_val, spec, mesh=mesh, **kwargs)
        (span,) = [s for s in trace.spans() if s["name"] == "search.fetch"]
    finally:
        trace.configure(False)
        trace.reset()
    assert np.isfinite(mm).all()
    assert [head for _, head in asked] == [case == "val_rows"]
    attrs = span["attrs"]
    assert attrs["hist_mode"] == ("scatter" if case == "scatter"
                                  else "matmul")
    assert not [k for k in attrs if k in ("hist_head", "hist_all")]
    share = (n // 3 * 3 - n // 3) / n if case == "val_rows" else 1.0
    assert attrs["hist_row_share"] == pytest.approx(share)
    if case == "val_rows":
        assert attrs["hist_row_share"] == pytest.approx(2 / 3, abs=0.01)
