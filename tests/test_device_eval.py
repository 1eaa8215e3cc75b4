"""Device-resident selector search parity.

The fused fit+metric kernels (eval_fold_grid_arrays) must reproduce the
host evaluation path's per-candidate metrics and winner — the search is
only faster, never different (the property VERDICT r3 demanded of the
on-device metric redesign).
"""
import numpy as np
import pytest

from transmogrifai_tpu.evaluators import (BinaryClassificationEvaluator,
                                          MultiClassificationEvaluator,
                                          RegressionEvaluator)
from transmogrifai_tpu.models import (GBTClassifier, GBTRegressor,
                                      LinearRegression, LinearSVC,
                                      LogisticRegression, NaiveBayes,
                                      RandomForestClassifier,
                                      RandomForestRegressor)
from transmogrifai_tpu.selector import CrossValidation


def _host_only(evaluator):
    """Evaluator clone whose device spec is disabled — forces the host
    per-candidate path."""
    import copy
    ev = copy.copy(evaluator)
    ev.device_metric_spec = lambda: None
    return ev


def _assert_same_search(pool, X, y, evaluator, atol=1e-9):
    cv_dev = CrossValidation(evaluator, num_folds=3, seed=7)
    cv_host = CrossValidation(_host_only(evaluator), num_folds=3, seed=7)
    best_dev = cv_dev.validate(pool, X, y)
    best_host = cv_host.validate(pool, X, y)
    assert best_dev.name == best_host.name
    assert best_dev.params == best_host.params
    for rd, rh in zip(best_dev.results, best_host.results):
        assert rd.model_name == rh.model_name
        assert rd.params == rh.params
        np.testing.assert_allclose(rd.metric_values, rh.metric_values,
                                   atol=atol, err_msg=rd.model_name)
    return best_dev


class TestBinaryDeviceSearch:
    def test_full_binary_pool_parity(self, rng):
        X = rng.normal(size=(240, 6))
        X[:, 3] = np.abs(X[:, 3])               # keep NB viable? no: mixed
        y = ((X[:, 0] - 0.5 * X[:, 1] + 0.2 * rng.normal(size=240)) > 0
             ).astype(float)
        pool = [
            (LogisticRegression(),
             [{"reg_param": 0.0}, {"reg_param": 0.1,
                                   "elastic_net_param": 0.5}]),
            (LinearSVC(), [{"reg_param": 0.01}]),
            (RandomForestClassifier(num_trees=10, max_depth=4),
             [{"min_instances_per_node": 1},
              {"min_instances_per_node": 20}]),
            (GBTClassifier(num_rounds=8, max_depth=3),
             [{"step_size": 0.1}, {"step_size": 0.3}]),
            (NaiveBayes(), [{"smoothing": 1.0}]),  # negative X -> drops out
        ]
        best = _assert_same_search(pool, X, y,
                                   BinaryClassificationEvaluator())
        assert best.metric > 0.6

    def test_nonneg_pool_with_nb(self, rng):
        X = np.abs(rng.normal(size=(200, 5)))
        y = (X[:, 0] + X[:, 1] > 1.5).astype(float)
        pool = [
            (NaiveBayes(), [{"smoothing": 0.5}, {"smoothing": 2.0}]),
            (LogisticRegression(), [{"reg_param": 0.01}]),
        ]
        _assert_same_search(pool, X, y, BinaryClassificationEvaluator())

    def test_error_metric(self, rng):
        X = rng.normal(size=(150, 4))
        y = (X[:, 0] > 0).astype(float)
        pool = [(LogisticRegression(),
                 [{"reg_param": 0.0}, {"reg_param": 10.0}])]
        ev = BinaryClassificationEvaluator(default_metric="Error")
        _assert_same_search(pool, X, y, ev)


class TestMulticlassDeviceSearch:
    def test_multiclass_pool_parity(self, rng):
        X = np.abs(rng.normal(size=(240, 5)))
        y = rng.integers(0, 3, 240).astype(float)
        y[X[:, 0] > 1.0] = 2.0                   # some signal
        pool = [
            (RandomForestClassifier(num_trees=8, max_depth=4),
             [{"min_instances_per_node": 1}]),
            (NaiveBayes(), [{"smoothing": 1.0}]),
        ]
        _assert_same_search(pool, X, y, MultiClassificationEvaluator())


class TestRegressionDeviceSearch:
    def test_regression_pool_parity(self, rng):
        X = rng.normal(size=(240, 5))
        y = X @ np.array([1.0, -2.0, 0.5, 0.0, 0.3]) \
            + 0.1 * rng.normal(size=240)
        pool = [
            (LinearRegression(),
             [{"reg_param": 0.0}, {"reg_param": 1.0}]),
            (RandomForestRegressor(num_trees=8, max_depth=4),
             [{"min_instances_per_node": 5}]),
            (GBTRegressor(num_rounds=8, max_depth=3),
             [{"step_size": 0.2}]),
        ]
        best = _assert_same_search(pool, X, y, RegressionEvaluator())
        assert best.metric < 2.0


class TestDeviceSearchOnMesh:
    def test_mesh_matches_local_device_search(self, rng):
        from transmogrifai_tpu.parallel import make_mesh
        X = rng.normal(size=(160, 5))
        y = (X[:, 0] + X[:, 2] > 0).astype(float)
        pool = [
            (LogisticRegression(),
             [{"reg_param": 0.0}, {"reg_param": 0.1}]),
            (GBTClassifier(num_rounds=6, max_depth=3),
             [{"step_size": 0.1}, {"step_size": 0.3}]),
        ]
        ev = BinaryClassificationEvaluator()
        local = CrossValidation(ev, num_folds=2, seed=3).validate(
            pool, X, y)
        mesh = make_mesh({"models": 8})
        meshed = CrossValidation(ev, num_folds=2, seed=3,
                                 mesh=mesh).validate(pool, X, y)
        assert meshed.name == local.name
        assert meshed.params == local.params
        for rm, rl in zip(meshed.results, local.results):
            np.testing.assert_allclose(rm.metric_values, rl.metric_values,
                                       atol=1e-9)

    def test_mesh_with_data_axis(self, rng):
        from transmogrifai_tpu.parallel import make_mesh
        X = rng.normal(size=(160, 5))
        y = (X[:, 0] + X[:, 2] > 0).astype(float)
        pool = [(LogisticRegression(),
                 [{"reg_param": 0.0}, {"reg_param": 0.1}])]
        ev = BinaryClassificationEvaluator()
        local = CrossValidation(ev, num_folds=2, seed=3).validate(
            pool, X, y)
        mesh = make_mesh({"models": 2, "data": 4})
        meshed = CrossValidation(ev, num_folds=2, seed=3,
                                 mesh=mesh).validate(pool, X, y)
        for rm, rl in zip(meshed.results, local.results):
            np.testing.assert_allclose(rm.metric_values, rl.metric_values,
                                       atol=1e-7)


class TestWorkflowCVDeviceSearch:
    def test_validate_prepared_parity(self, rng):
        # per-fold prepared matrices (workflow-level CV entry) also run
        # the device path, one fold at a time
        X = rng.normal(size=(180, 5))
        y = (X[:, 0] - X[:, 1] > 0).astype(float)
        folds = []
        rngs = np.random.default_rng(0)
        for _ in range(3):
            idx = rngs.permutation(180)
            folds.append((X[idx[:120]], y[idx[:120]],
                          X[idx[120:]], y[idx[120:]]))
        pool = [(LogisticRegression(),
                 [{"reg_param": 0.0}, {"reg_param": 0.1}]),
                (GBTClassifier(num_rounds=6, max_depth=3),
                 [{"step_size": 0.1}])]
        ev = BinaryClassificationEvaluator()
        dev = CrossValidation(ev, num_folds=3).validate_prepared(
            pool, folds)
        host_ev = _host_only(ev)
        host = CrossValidation(host_ev, num_folds=3).validate_prepared(
            pool, folds)
        assert dev.name == host.name and dev.params == host.params
        for rd, rh in zip(dev.results, host.results):
            np.testing.assert_allclose(rd.metric_values, rh.metric_values,
                                       atol=1e-9)


class TestBinEdgeDeviationWinnerParity:
    def test_tree_winner_stable_vs_sequential_binning(self, rng):
        """Documented deviation check (VERDICT r3 weak #6): batched tree
        kernels compute bin edges from the WHOLE prepared matrix while
        the sequential path bins each fold's train rows — the winner
        must not flip between the two paths."""
        import unittest.mock as mock

        from transmogrifai_tpu.models import (GBTClassifier,
                                              RandomForestClassifier)
        X = rng.normal(size=(300, 8))
        y = ((X[:, 0] + 0.5 * X[:, 1] ** 2 - 0.3
              + 0.3 * rng.normal(size=300)) > 0).astype(float)
        pool = [
            (RandomForestClassifier(num_trees=10, max_depth=4),
             [{"min_instances_per_node": m} for m in (1, 30)]),
            (GBTClassifier(num_rounds=8, max_depth=3),
             [{"step_size": s} for s in (0.1, 0.3)]),
        ]
        ev = BinaryClassificationEvaluator()
        batched = CrossValidation(ev, num_folds=3, seed=11).validate(
            pool, X, y)
        # force the fully sequential path: per-fold fits (per-fold bin
        # edges), host metrics
        ev_host = _host_only(ev)
        with mock.patch.object(
                RandomForestClassifier, "fit_fold_grid_arrays",
                side_effect=NotImplementedError), \
             mock.patch.object(
                GBTClassifier, "fit_fold_grid_arrays",
                side_effect=NotImplementedError):
            seq = CrossValidation(ev_host, num_folds=3,
                                  seed=11).validate(pool, X, y)
        assert batched.name == seq.name
        assert batched.params == seq.params
        # per-candidate metrics land in the same band — they cannot be
        # exact: beyond the bin-edge deviation, the sequential path
        # also consumes bootstrap randomness over the fold's OWN rows
        # while the masked kernels draw over the full matrix
        for rb, rs in zip(batched.results, seq.results):
            np.testing.assert_allclose(rb.metric_values, rs.metric_values,
                                       atol=0.12, err_msg=rb.model_name)


class TestGLMDeviceSearch:
    def test_glm_pool_parity(self, rng):
        from transmogrifai_tpu.models.glm import (
            GeneralizedLinearRegression)
        X = np.abs(rng.normal(size=(240, 5))) + 0.1
        y = np.exp(0.3 * X[:, 0] - 0.2 * X[:, 1]) \
            + 0.05 * rng.normal(size=240)
        pool = [(GeneralizedLinearRegression(),
                 [{"family": f, "reg_param": r}
                  for f in ("gaussian", "poisson")
                  for r in (0.001, 0.1)])]
        best = _assert_same_search(pool, X, y, RegressionEvaluator(),
                                   atol=1e-7)
        assert np.isfinite(best.metric)

    def test_glm_batched_fit_matches_sequential(self, rng):
        from transmogrifai_tpu.models.glm import (
            GeneralizedLinearRegression)
        X = rng.normal(size=(150, 4))
        y = X @ np.array([1.0, -0.5, 0.2, 0.0]) \
            + 0.1 * rng.normal(size=150)
        est = GeneralizedLinearRegression(reg_param=0.01)
        masks = np.ones((2, 150))
        masks[0, :50] = 0.0
        masks[1, 50:100] = 0.0
        fitted = est.fit_fold_grid_arrays(
            X, y, masks, [{"reg_param": 0.01}])
        for f, mask in enumerate(masks):
            seq = est.fit_arrays(X[mask > 0], y[mask > 0])
            np.testing.assert_allclose(
                fitted[f][0].coefficients, seq.coefficients, atol=1e-8)

    def test_glm_mesh_matches_local(self, rng):
        from transmogrifai_tpu.models.glm import (
            GeneralizedLinearRegression)
        from transmogrifai_tpu.parallel import make_mesh
        X = rng.normal(size=(160, 4))
        y = X @ np.array([1.0, -0.5, 0.2, 0.0]) \
            + 0.1 * rng.normal(size=160)
        pool = [(GeneralizedLinearRegression(),
                 [{"reg_param": r} for r in (0.001, 0.1)])]
        ev = RegressionEvaluator()
        local = CrossValidation(ev, num_folds=2, seed=3).validate(
            pool, X, y)
        meshed = CrossValidation(ev, num_folds=2, seed=3,
                                 mesh=make_mesh({"models": 8})).validate(
            pool, X, y)
        assert meshed.params == local.params
        for rm, rl in zip(meshed.results, local.results):
            np.testing.assert_allclose(rm.metric_values, rl.metric_values,
                                       atol=1e-9)

    def test_glm_masked_rows_do_not_poison_log_link(self, rng):
        # a held-out outlier row overflows exp() under the log link;
        # the masked lane must still fit (the sequential per-fold fit
        # never sees that row)
        from transmogrifai_tpu.models.glm import (
            GeneralizedLinearRegression)
        X = rng.normal(size=(120, 3))
        X[0, 0] = 400.0                       # masked-out overflow row
        y = np.exp(np.clip(0.3 * X[:, 0], -5, 5)) \
            + 0.05 * rng.normal(size=120)
        y = np.maximum(y, 0.01)
        masks = np.ones((1, 120))
        masks[0, 0] = 0.0                     # row 0 held out
        est = GeneralizedLinearRegression(family="poisson",
                                          reg_param=0.01)
        fitted = est.fit_fold_grid_arrays(X, y, masks, [{}])
        coefs = fitted[0][0].coefficients
        assert np.all(np.isfinite(coefs)), coefs
        seq = est.fit_arrays(X[1:], y[1:])
        np.testing.assert_allclose(coefs, seq.coefficients, atol=1e-6)


# ---------------------------------------------------------------------------
# ISSUE 29: the fused kernels score the validation rows where the fit put
# them ("in_fit" form, ``val_rows`` given) and walk the raw validation
# matrix only for rows foreign to the fitted table ("traverse" form)
# ---------------------------------------------------------------------------

def _infit_table(family, seed=29):
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


def _infit_family(family):
    """(estimator, grid, evaluator) of a family: depth 12 and a shallower
    lane, so that under the ``blocks`` depth mode one program runs a
    depth-3 block beside a depth-12 one with its carried slots
    (``_carry_slots``) and the budget mask."""
    from transmogrifai_tpu.models import XGBoostClassifier
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


@pytest.fixture
def infit_env(monkeypatch):
    from transmogrifai_tpu.models import trees

    def configure(binning):
        monkeypatch.setattr(trees, "_bin_on_device",
                            lambda elems: binning == "device")
        monkeypatch.setattr(trees, "_depth_mode", lambda: "blocks")
        trees.clear_design_cache()
    yield configure
    trees.clear_design_cache()


def _fold_arrays(X, y, evaluator):
    cv = CrossValidation(evaluator, num_folds=3, seed=7, mesh=None)
    _, masks, _, spec, X_val, y_val, val_rows = cv._build_fold_arrays(X, y)
    for f in range(3):
        np.testing.assert_array_equal(X_val[f], X[val_rows[f]])
        assert not masks[f, val_rows[f]].any()
    return masks, spec, X_val, y_val, val_rows


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

    from transmogrifai_tpu.models import trees
    infit_env(binning)
    X, y = _infit_table(family)
    est, _, evaluator = _infit_family(family)
    masks, _, _, _, val_rows = _fold_arrays(X, y, evaluator)
    mask, va = jnp.asarray(masks[0]), val_rows[0]
    design, widths = trees._design_args(X, est.max_bins)
    hist = trees._hist_mode(X.shape[0], int(design[1].shape[0]))
    key = jax.random.PRNGKey(3)
    Xa = jnp.asarray(X)

    def walked(feats, thrs):
        return np.asarray(jax.vmap(
            lambda f, t: trees._traverse(Xa, f, t, 12))(feats, thrs))

    if family.startswith("forest"):
        cls = family != "forest_reg"
        mf = trees._resolve_max_features(est.feature_subset_strategy,
                                         X.shape[1], cls)
        (narrow, wide), pool_cfg, mf = trees._pool_plan(widths, mf)
        assert (pool_cfg is not None) == (family == "forest_pooled")
        body = jax.jit(functools.partial(
            trees._forest_body, kind="cls" if cls else "reg", depth=12,
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
            trees._gbt_softmax_body, depth=12, num_rounds=2,
            num_classes=3, hist_mode=hist))
        feats, thrs, leaves, base, margins = body(
            *design[:4], jnp.asarray(y), key, mask, 0.3, 1.0, 0.0, 0.0,
            1.0)
        want = np.asarray(trees._softmax_margins(
            feats, thrs, leaves, base, 12, Xa))
        np.testing.assert_allclose(np.asarray(margins), want, atol=1e-9)
        feats, thrs = (np.asarray(a).reshape(6, -1) for a in (feats, thrs))
    else:
        body = jax.jit(functools.partial(
            trees._gbt_body, depth=12, num_rounds=3, hist_mode=hist,
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

    from transmogrifai_tpu.models import trees
    infit_env(binning)
    X, y = _infit_table("forest_pooled")
    masks, _, _, _, val_rows = _fold_arrays(
        X, y, BinaryClassificationEvaluator())
    (packed, feat_of, block_start, packed_thr, binned, col_thr), widths = \
        trees._design_args(X, 16)
    stats = jnp.asarray(np.eye(2)[y.astype(int)] * masks[0][:, None])
    grow = functools.partial(
        trees._grow_tree, depth=12, gain_fn=trees._gini_gain(1.0),
        min_info_gain=0.0, hist_mode=trees._hist_mode(*X.shape))
    if design == "pooled":
        (narrow, wide), pool_cfg, _ = trees._pool_plan(widths, 5)
        pool, *sub = trees._tree_pool(jax.random.PRNGKey(1), binned,
                                      col_thr, narrow, wide, pool_cfg)
        feat, thr, _, node = jax.jit(grow)(*sub, stats, feat_map=pool)
    else:
        feat, thr, _, node = jax.jit(grow)(packed, feat_of, block_start,
                                           packed_thr, stats)
    want = trees._traverse(jnp.asarray(X), feat, thr, 12)
    np.testing.assert_array_equal(np.asarray(node), np.asarray(want))
    assert len(np.unique(np.asarray(node)[val_rows[0]])) > 8
    assert np.isfinite(np.asarray(thr)[2 ** 9 - 1:]).any()


@pytest.mark.parametrize("binning", ("host", "device"))
@pytest.mark.parametrize("family", INFIT_FAMILIES)
def test_val_rows_give_the_same_metric_matrix(infit_env, family, binning):
    """(b) The (F, G) matrix with ``val_rows`` is the one without: a
    forest's bit for bit, a boosted family's to 1e-6 (the carried margin
    is a sequential sum); the validation matrix is not even looked at."""
    from transmogrifai_tpu.models import trees
    infit_env(binning)
    X, y = _infit_table(family)
    est, grid, evaluator = _infit_family(family)
    masks, spec, X_val, y_val, val_rows = _fold_arrays(X, y, evaluator)
    before = trees.tree_eval_forms()
    walked = est.eval_fold_grid_arrays(X, y, masks, grid, X_val, y_val,
                                       spec)
    middle = trees.tree_eval_forms()
    in_fit = est.eval_fold_grid_arrays(X, y, masks, grid, None, y_val,
                                       spec, val_rows=val_rows)
    after = trees.tree_eval_forms()
    assert walked.shape == (3, len(grid)) and np.isfinite(walked).all()
    if family.startswith("forest"):
        np.testing.assert_array_equal(in_fit, walked)
    else:
        np.testing.assert_allclose(in_fit, walked, atol=1e-6, rtol=0)
    # each call traced its own form, if it traced at all (an earlier case
    # of the same shapes may have left the program behind)
    assert middle["in_fit"] == before["in_fit"]
    assert after["traverse"] == middle["traverse"]
    with pytest.raises(ValueError, match="val_rows"):
        est.eval_fold_grid_arrays(X, y, masks, grid, X_val, y_val, spec,
                                  val_rows=val_rows[:, :-1])


@pytest.mark.parametrize("family", ("forest_cls", "gbt_bin"))
def test_racing_mask_needs_explicit_rows(infit_env, family):
    """(c) A racing rung zeroes training rows that are no validation rows:
    the rows ``masks == 0`` are then not the fold's validation rows, so the
    form follows what the caller says, not the mask. Without ``val_rows``
    the kernel walks ``X_val`` as it always did; with them it gives the
    same matrix."""
    from transmogrifai_tpu.models import trees
    infit_env("host")
    X, y = _infit_table(family)
    est, grid, evaluator = _infit_family(family)
    masks, spec, X_val, y_val, val_rows = _fold_arrays(X, y, evaluator)
    rung = masks.copy()
    r = np.random.default_rng(5)
    for f in range(3):
        train = np.nonzero(masks[f] > 0)[0]
        rung[f, r.choice(train, size=len(train) // 2, replace=False)] = 0.0
    assert ((rung == 0).sum(axis=1) > val_rows.shape[1]).all()
    before = trees.tree_eval_forms()
    walked = est.eval_fold_grid_arrays(X, y, rung, grid, X_val, y_val, spec)
    after = trees.tree_eval_forms()
    assert after["in_fit"] == before["in_fit"]
    in_fit = est.eval_fold_grid_arrays(X, y, rung, grid, X_val, y_val,
                                       spec, val_rows=val_rows)
    np.testing.assert_allclose(in_fit, walked, atol=1e-6, rtol=0)
    full = est.eval_fold_grid_arrays(X, y, masks, grid, X_val, y_val, spec)
    assert np.abs(full - walked).max() > 1e-6      # the rung did thin the fit


def test_validate_prepared_still_walks(infit_env):
    """(c) Workflow-level CV hands over validation rows that are NOT in the
    fitted table: no ``val_rows``, the "traverse" form, today's matrix
    (the host evaluator's)."""
    from transmogrifai_tpu.models import trees
    infit_env("host")
    X, y = _infit_table("gbt_bin")
    idx = np.random.default_rng(0).permutation(len(y))
    folds = [(X[idx[:240]], y[idx[:240]], X[idx[240:]], y[idx[240:]]),
             (X[idx[120:]], y[idx[120:]], X[idx[:120]], y[idx[:120]])]
    pool = [(GBTClassifier(num_rounds=3, max_depth=4, max_bins=16),
             [{"step_size": 0.1}, {"step_size": 0.3}]),
            (RandomForestClassifier(num_trees=3, max_depth=4, max_bins=16),
             [{"min_instances_per_node": 1}])]
    ev = BinaryClassificationEvaluator()
    before = trees.tree_eval_forms()
    dev = CrossValidation(ev, num_folds=2).validate_prepared(pool, folds)
    after = trees.tree_eval_forms()
    assert after["in_fit"] == before["in_fit"]
    assert after["traverse"] >= before["traverse"] + 2
    host = CrossValidation(_host_only(ev), num_folds=2).validate_prepared(
        pool, folds)
    for rd, rh in zip(dev.results, host.results):
        np.testing.assert_allclose(rd.metric_values, rh.metric_values,
                                   atol=1e-9)


@pytest.mark.parametrize("form", ("in_fit", "traverse"))
def test_eval_form_is_counted_once_a_kernel_and_in_the_program(
        infit_env, form):
    """(d) ``tree_eval_forms()`` counts one form per traced kernel, and the
    compiled program shows it: the in-fit program has no validation matrix
    among its parameters (the (F, nv) row indices stand in its place), the
    walking one has."""
    from transmogrifai_tpu.models import trees
    infit_env("host")
    X, y = _infit_table("gbt_bin", seed=31)
    X = X[:300, :7]                     # shapes no other case compiles
    y = y[:300]
    est = GBTClassifier(num_rounds=2, max_depth=3, max_bins=8)
    masks, spec, X_val, y_val, val_rows = _fold_arrays(
        X, y, BinaryClassificationEvaluator())
    captured = []
    real = trees._gbt_eval_kernel

    def spy(*key):
        fn = real(*key)

        def call(*args):
            captured.append(fn.lower(*args).compile().as_text())
            return fn(*args)
        return call

    kwargs = {"val_rows": val_rows} if form == "in_fit" else {}
    before = trees.tree_eval_forms()
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(trees, "_gbt_eval_kernel", spy)
        est.eval_fold_grid_arrays(X, y, masks, [{"gamma": 0.0}], X_val,
                                  y_val, spec, **kwargs)
    after = trees.tree_eval_forms()
    other = "traverse" if form == "in_fit" else "in_fit"
    # the spy's lower() and the call itself share one trace cache entry or
    # trace twice: either way only this form moved
    assert after[form] > before[form] and after[other] == before[other]
    (hlo,) = captured
    entry = hlo[hlo.index("ENTRY"):]
    params = [ln for ln in entry.splitlines() if " parameter(" in ln]
    matrix = [ln for ln in params if "f64[3,100,7]" in ln]
    rows = [ln for ln in params if "s32[3,100]" in ln]
    assert (len(matrix), len(rows)) == ((0, 1) if form == "in_fit"
                                        else (1, 0))
    assert "fg.metric" in hlo


def test_search_fetch_span_says_which_eval_form(infit_env):
    """(d) ``Validator.validate`` names the validation rows, so its tree
    programs are the in-fit ones, and every ``search.fetch`` span carries
    the counts next to the routing forms."""
    from transmogrifai_tpu.models import trees
    from transmogrifai_tpu.observability import trace
    infit_env("host")
    X, y = _infit_table("gbt_bin", seed=37)
    X, y = X[:270, :6], y[:270]
    pool = [(GBTClassifier(num_rounds=2, max_depth=3, max_bins=8),
             [{"gamma": 0.0}, {"gamma": 0.1}]),
            (RandomForestClassifier(num_trees=2, max_depth=3, max_bins=8),
             [{"min_instances_per_node": 2}]),
            (LogisticRegression(), [{"reg_param": 0.1}])]
    before = trees.tree_eval_forms()
    trace.configure(True)
    try:
        CrossValidation(BinaryClassificationEvaluator(), num_folds=3,
                        seed=3).validate(pool, X, y)
        spans = [s for s in trace.spans() if s["name"] == "search.fetch"
                 and "eval_in_fit" in s["attrs"]]
    finally:
        trace.configure(False)
    after = trees.tree_eval_forms()
    assert after["traverse"] == before["traverse"]
    assert after["in_fit"] >= before["in_fit"] + 2
    assert len(spans) == 2                       # the two tree families
    for s in spans:
        assert s["attrs"]["eval_in_fit"] >= 1
        assert s["attrs"]["eval_traverse"] == after["traverse"]
        assert "route_gather" in s["attrs"]


# ---------------------------------------------------------------------------
# ISSUE 37: in the in-fit form without a mesh, under the ``matmul`` family,
# every lane sees the table in its fold's own order (training rows first,
# held-out rows last): the level contraction runs over the head, the held-out
# rows' leaves and margins are the tail
# ---------------------------------------------------------------------------

HEAD_FAMILIES = ("tree_cls2", "tree_cls7", "tree_reg", "gbt_bin", "gbt_reg",
                 "gbt_softmax")


@pytest.fixture
def head_env(monkeypatch):
    """The chip's choices on the CPU: the ``matmul`` histogram family (so
    the head form applies), depth blocks in one program, host binning."""
    from transmogrifai_tpu.models import trees
    monkeypatch.setattr(trees, "_hist_mode", lambda n, tb: "matmul")
    monkeypatch.setattr(trees, "_depth_mode", lambda: "blocks")
    monkeypatch.setattr(trees, "_bin_on_device", lambda elems: False)
    trees.clear_design_cache()
    yield monkeypatch
    trees.clear_design_cache()


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
    from transmogrifai_tpu.models import trees
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(trees, "_fold_order", lambda masks, val_rows: None)
        return call()


@pytest.mark.parametrize("split", ("three_folds", "one_split"))
@pytest.mark.parametrize("family", HEAD_FAMILIES)
def test_head_form_gives_the_all_rows_metrics(head_env, family, split):
    """(a) Without row draws the new form is the old one on the same folds:
    the (F, G) validation metrics of the all-rows form, at depths that
    cross the slot cap, for k folds and for one split. Bit for bit where
    the metric reads class votes; to 1e-12 where it reads leaf values, which
    are quotients of sums that agree to the last bit."""
    from transmogrifai_tpu.models import trees
    est, grid, _, X, y, masks, spec, y_val, val_rows = _head_case(family,
                                                                  split)
    before = trees.tree_hist_rows()
    head = est.eval_fold_grid_arrays(X, y, masks, grid, None, y_val, spec,
                                     val_rows=val_rows)
    middle = trees.tree_hist_rows()
    all_rows = _all_rows_form(lambda: est.eval_fold_grid_arrays(
        X, y, masks, grid, None, y_val, spec, val_rows=val_rows))
    after = trees.tree_hist_rows()
    assert head.shape == (len(masks), 3) and np.isfinite(head).all()
    if family.startswith("tree_cls"):
        np.testing.assert_array_equal(head, all_rows)
    else:
        np.testing.assert_allclose(head, all_rows, atol=1e-12, rtol=0)
    assert len(np.unique(head.round(6))) >= 3       # lanes that differ
    # each call traced its own growers, if it traced at all
    assert middle["all"] == before["all"]
    assert after["head"] == middle["head"]


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

    from transmogrifai_tpu.models import trees
    est, grid, _, X, y, masks, _, _, val_rows = _head_case(family)
    order = trees._fold_order(masks, val_rows)
    F, n = masks.shape
    h = n - val_rows.shape[1]
    for f in range(F):
        np.testing.assert_array_equal(order[f, h:], val_rows[f])
        np.testing.assert_array_equal(np.sort(order[f]), np.arange(n))
        assert (np.diff(order[f, :h]) > 0).all()
    design, _ = trees._design_args(X, est.max_bins)
    key = jax.random.PRNGKey(est.seed)
    boosted = family.startswith("gbt")
    fields = trees._GBT_TILED if boosted else trees._FOREST_TRACED
    skey = trees._GBT_SKEY if boosted else trees._FOREST_STATIC

    def blocks_of(m):
        (_, blocks), = trees._candidate_groups(est, grid, m, None, fields,
                                               skey)
        return tuple(tuple(jnp.asarray(a) for a in b.lanes) for b in blocks)
    depths = (3, 6, 12)
    if boosted:
        body = functools.partial(
            trees._gbt_body, depth=depths, num_rounds=1, hist_mode="matmul",
            objective="logistic" if family == "gbt_bin" else "squared")
        shared = (*design[1:4],)

        def fit(packed, yy, lane_args, **kw):
            return body(packed, *shared, yy, key, *lane_args, **kw)
        tables = (design[0], jnp.asarray(y))
    else:
        body = functools.partial(
            trees._forest_body, kind="reg" if family == "tree_reg" else "cls",
            depth=depths, num_classes={"tree_cls2": 2, "tree_cls7": 7}.get(
                family, 0), num_trees=1, max_features=None, pool_cfg=None,
            impurity="gini", bootstrap=False, hist_mode="matmul")
        empty = jnp.zeros((0,), jnp.int32)

        def fit(packed, yy, lane_args, **kw):
            return body(packed, *design[1:], empty, empty, yy, key,
                        *lane_args, **kw)
        tables = (design[0], jnp.asarray(y))
    lane_args, lanes = trees._all_lanes(blocks_of(masks))
    all_rows = jax.jit(lambda: fit(*tables, lane_args, lanes=lanes))()
    in_order = jax.jit(lambda: trees._by_fold(
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
                walked = np.asarray(trees._traverse(
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
    """(d) ``tree_hist_rows()`` and the ``search.fetch`` span: ``head`` and
    a ``hist_row_share`` of (n - nv) / n where the caller names the
    validation rows of the fitted table; ``all`` and 1.0 without them (the
    traverse form), under a search mesh (a chip's lanes mix folds), where
    a mask gives a held-out row weight, and under the ``scatter`` mode."""
    from transmogrifai_tpu.models import trees
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
        head_env.setattr(trees, "_hist_mode", lambda n, tb: "scatter")
    before = trees.tree_hist_rows()
    trace.configure(True)
    trace.reset()                       # an earlier test's spans
    try:
        mm = est.eval_fold_grid_arrays(X, y, masks, [{"gamma": 0.0}], X_val,
                                       y_val, spec, mesh=mesh, **kwargs)
        (span,) = [s for s in trace.spans() if s["name"] == "search.fetch"]
    finally:
        trace.configure(False)
        trace.reset()
    after = trees.tree_hist_rows()
    assert np.isfinite(mm).all()
    form, other = ("head", "all") if case == "val_rows" else ("all", "head")
    assert after[form] > before[form] and after[other] == before[other]
    attrs = span["attrs"]
    assert attrs["hist_head"] == after["head"]
    assert attrs["hist_all"] == after["all"]
    share = (n // 3 * 3 - n // 3) / n if case == "val_rows" else 1.0
    assert attrs["hist_row_share"] == pytest.approx(share)
    if case == "val_rows":
        assert attrs["hist_row_share"] == pytest.approx(2 / 3, abs=0.01)
