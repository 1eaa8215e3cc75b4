"""Tree model family tests (reference OpRandomForestClassifierTest,
OpGBTClassifierTest, OpDecisionTreeClassifierTest et al. in
core/src/test/.../classification/ and .../regression/)."""
import numpy as np
import pytest

from transmogrifai_tpu.models import (
    DecisionTreeClassifier, DecisionTreeRegressor, GBTClassifier,
    GBTRegressor, RandomForestClassifier, RandomForestRegressor,
    XGBoostClassifier, XGBoostRegressor)


@pytest.fixture(scope="module")
def binary_data():
    rng = np.random.default_rng(0)
    n = 400
    X = rng.normal(size=(n, 6))
    # axis-aligned interaction a tree can represent exactly
    y = ((X[:, 0] > 0.3) & (X[:, 2] < 0.5)).astype(np.float64)
    return X, y


@pytest.fixture(scope="module")
def regression_data():
    rng = np.random.default_rng(1)
    n = 400
    X = rng.normal(size=(n, 5))
    y = np.where(X[:, 0] > 0, 3.0, -1.0) + np.where(X[:, 1] > 1, 2.0, 0.0)
    y = y + 0.01 * rng.normal(size=n)
    return X, y


def _accuracy(model, X, y):
    pred = model.predict_arrays(X).data
    return float(np.mean(pred == y))


class TestDecisionTree:
    def test_classifier_learns_axis_aligned(self, binary_data):
        X, y = binary_data
        model = DecisionTreeClassifier(max_depth=3).fit_arrays(X, y)
        assert _accuracy(model, X, y) > 0.97

    def test_classifier_probabilities_valid(self, binary_data):
        X, y = binary_data
        model = DecisionTreeClassifier(max_depth=3).fit_arrays(X, y)
        prob = model.predict_arrays(X).probability
        assert prob.shape == (len(y), 2)
        np.testing.assert_allclose(prob.sum(axis=1), 1.0, atol=1e-9)
        assert (prob >= 0).all()

    def test_min_info_gain_prunes(self, binary_data):
        X, y = binary_data
        model = DecisionTreeClassifier(
            max_depth=3, min_info_gain=1e9).fit_arrays(X, y)
        # no split survives an impossible gain bar -> all thresholds +inf
        assert not np.isfinite(model.thrs).any()

    def test_regressor_learns_step(self, regression_data):
        X, y = regression_data
        model = DecisionTreeRegressor(max_depth=3).fit_arrays(X, y)
        pred = model.predict_values(X)
        assert np.mean((pred - y) ** 2) < 0.1

    def test_multiclass(self):
        rng = np.random.default_rng(3)
        X = rng.normal(size=(300, 4))
        y = (X[:, 0] > 0).astype(float) + (X[:, 1] > 0) * 1.0
        model = DecisionTreeClassifier(max_depth=4).fit_arrays(X, y)
        prob = model.predict_arrays(X).probability
        assert prob.shape[1] == 3
        assert _accuracy(model, X, y) > 0.9


class TestRandomForest:
    def test_classifier(self, binary_data):
        X, y = binary_data
        model = RandomForestClassifier(
            num_trees=20, max_depth=4, seed=7).fit_arrays(X, y)
        assert _accuracy(model, X, y) > 0.93

    def test_seed_determinism(self, binary_data):
        X, y = binary_data
        m1 = RandomForestClassifier(num_trees=5, seed=9).fit_arrays(X, y)
        m2 = RandomForestClassifier(num_trees=5, seed=9).fit_arrays(X, y)
        np.testing.assert_array_equal(m1.thrs, m2.thrs)

    def test_regressor(self, regression_data):
        X, y = regression_data
        model = RandomForestRegressor(
            num_trees=20, max_depth=4, seed=7).fit_arrays(X, y)
        pred = model.predict_values(X)
        assert np.mean((pred - y) ** 2) < 1.0

    def test_feature_importances(self, binary_data):
        X, y = binary_data
        model = RandomForestClassifier(
            num_trees=10, max_depth=3, seed=7,
            feature_subset_strategy="all").fit_arrays(X, y)
        imp = model.feature_importances
        assert imp.sum() == pytest.approx(1.0)
        # the two signal features should dominate
        assert imp[0] + imp[2] > 0.5


class TestGBT:
    def test_classifier_beats_depth_one(self, binary_data):
        X, y = binary_data
        model = GBTClassifier(num_rounds=30, max_depth=3).fit_arrays(X, y)
        assert _accuracy(model, X, y) > 0.97

    def test_classifier_probability_monotone_in_margin(self, binary_data):
        X, y = binary_data
        model = GBTClassifier(num_rounds=10, max_depth=3).fit_arrays(X, y)
        out = model.predict_arrays(X)
        m = model.margins(X)
        p = out.probability[:, 1]
        order = np.argsort(m)
        assert (np.diff(p[order]) >= -1e-12).all()

    def test_regressor(self, regression_data):
        X, y = regression_data
        model = GBTRegressor(num_rounds=100, max_depth=3).fit_arrays(X, y)
        pred = model.predict_values(X)
        assert np.mean((pred - y) ** 2) < 0.05

    def test_subsample(self, binary_data):
        X, y = binary_data
        model = GBTClassifier(num_rounds=20, max_depth=3,
                              subsample=0.7, seed=5).fit_arrays(X, y)
        assert _accuracy(model, X, y) > 0.9

    def test_xgboost_facade_param_names(self, binary_data):
        X, y = binary_data
        est = XGBoostClassifier(eta=0.3, num_round=20, max_depth=3)
        assert est.step_size == 0.3 and est.num_rounds == 20
        model = est.fit_arrays(X, y)
        assert _accuracy(model, X, y) > 0.95

    def test_xgboost_regressor(self, regression_data):
        X, y = regression_data
        model = XGBoostRegressor(num_round=40, max_depth=3).fit_arrays(X, y)
        assert np.mean((model.predict_values(X) - y) ** 2) < 0.05


class TestGridSupport:
    def test_with_params_copies(self):
        est = RandomForestClassifier()
        est2 = est.with_params(max_depth=9, num_trees=3)
        assert est2.max_depth == 9 and est2.num_trees == 3
        assert est.max_depth == 5  # original untouched
        assert type(est2) is RandomForestClassifier


class TestHistogramModes:
    """scatter / matmul / matmul_chunk histogram paths must produce
    IDENTICAL trees (models/trees._hist_mode chooses among them from the
    platform and the indicator's size; the matmul pair rides the MXU on
    TPU). The resolved mode is threaded as a STATIC jit argument — another
    answer of the resolver between fits in one process must retrace, not
    silently reuse the previous mode's program."""

    def test_modes_agree(self, rng, monkeypatch):
        import numpy as np
        import transmogrifai_tpu.models.trees as T
        from transmogrifai_tpu.models.trees import (GBTClassifier,
                                                    RandomForestClassifier)
        X = rng.normal(size=(300, 12))
        X[:, 6:] = (X[:, 6:] > 0).astype(float)   # binary block
        y = (X[:, 0] + X[:, 6] > 0.3).astype(float)
        fits = {}
        for mode in ("scatter", "matmul", "matmul_chunk"):
            monkeypatch.setattr(T, "_hist_mode", lambda n, tb, m=mode: m)
            fits[mode] = (
                GBTClassifier(num_rounds=8, max_depth=4).fit_arrays(X, y),
                RandomForestClassifier(num_trees=4, max_depth=6,
                                       min_instances_per_node=5
                                       ).fit_arrays(X, y))
        for other in ("matmul", "matmul_chunk"):
            for a, b in zip(fits["scatter"], fits[other]):
                np.testing.assert_allclose(a.thrs, b.thrs, rtol=1e-6,
                                           err_msg=other)
                np.testing.assert_allclose(a.feats, b.feats,
                                           err_msg=other)
                np.testing.assert_allclose(a.leaves, b.leaves, rtol=1e-5,
                                           err_msg=other)

    def test_hist_subtraction_matches_direct(self, rng, monkeypatch):
        """LightGBM-style histogram subtraction (a ``+sub`` suffix on the
        static ``hist_mode``, which only tests pass: models/trees.
        _grow_tree): identity levels build LEFT-child histograms only and
        derive right = parent - left. On data without exact gain ties the
        trees are identical to the direct build (ties may legitimately
        resolve to a different equal-gain split — the documented
        caveat)."""
        import numpy as np
        import transmogrifai_tpu.models.trees as T
        from transmogrifai_tpu.models.trees import (GBTClassifier,
                                                    RandomForestClassifier)
        X = rng.normal(size=(300, 12))
        y = (X[:, 0] * 2 - X[:, 1] > 0.2).astype(float)
        fits = {}
        for mode in ("scatter", "scatter+sub", "matmul", "matmul+sub"):
            monkeypatch.setattr(T, "_hist_mode", lambda n, tb, m=mode: m)
            fits[mode] = (
                # shallow + few rounds keeps every node large and every
                # residual strong: tiny nodes / flattened late-round
                # residuals carry exactly-tied gains whose argmax is
                # legitimately 1-ulp-sensitive under subtraction
                GBTClassifier(num_rounds=3, max_depth=3).fit_arrays(X, y),
                RandomForestClassifier(num_trees=4, max_depth=4,
                                       min_instances_per_node=25
                                       ).fit_arrays(X, y))
        # each base vs ITS OWN +sub variant (cross-base comparisons
        # already differ by summation order — test_modes_agree's job)
        for base in ("scatter", "matmul"):
            for a, b in zip(fits[base], fits[base + "+sub"]):
                np.testing.assert_array_equal(a.feats, b.feats,
                                              err_msg=base)
                np.testing.assert_allclose(a.thrs, b.thrs, rtol=1e-6,
                                           err_msg=base)
                np.testing.assert_allclose(a.leaves, b.leaves, rtol=1e-5,
                                           err_msg=base)

    def test_hist_subtraction_identity_any_assignment(self):
        """The subtraction identity holds for ARBITRARY level-l node
        assignments: hist(node) == interleave(hist_even,
        hist(node >> 1) - hist_even) up to float reassociation."""
        import jax.numpy as jnp
        import numpy as np
        from transmogrifai_tpu.models.trees import (_design_args,
                                                    _level_histograms)
        rng = np.random.default_rng(7)
        X = rng.normal(size=(500, 5))
        (packed, feat_of, *_), _ = _design_args(X, 16)
        TB = int(feat_of.shape[0])
        stats = jnp.asarray(rng.normal(size=(500, 2)))
        node = jnp.asarray(rng.integers(0, 8, size=500), jnp.int32)
        full = _level_histograms(packed, node, stats, 8, TB, None,
                                 mode="scatter", feat_of=feat_of)
        prev = _level_histograms(packed, node >> 1, stats, 4, TB, None,
                                 mode="scatter", feat_of=feat_of)
        even = _level_histograms(
            packed, jnp.where((node & 1) == 0, node >> 1, 8), stats, 4,
            TB, None, mode="scatter", feat_of=feat_of)
        sub = jnp.stack([even, prev - even], axis=1).reshape(8, TB, 2)
        np.testing.assert_allclose(np.asarray(full), np.asarray(sub),
                                   atol=1e-10)

    def test_mode_switch_retraces(self, rng, monkeypatch):
        """Regression test: the mode used to be read at trace time
        only, so the second fit in a process silently reused the first
        mode's compiled program (making in-process comparisons vacuous).
        The routing form follows the mode, so a retrace shows as one more
        traced ``_grow_tree`` of the other form."""
        import transmogrifai_tpu.models.trees as T
        X = rng.normal(size=(83, 4))     # a shape no other test fits
        y = (X[:, 0] > 0).astype(float)
        forms = []
        for mode in ("scatter", "matmul"):
            monkeypatch.setattr(T, "_hist_mode", lambda n, tb, m=mode: m)
            before = T.tree_route_forms()
            T.GBTClassifier(num_rounds=2, max_depth=2).fit_arrays(X, y)
            after = T.tree_route_forms()
            forms.append({k: after[k] - before[k] for k in after})
        assert forms[0]["gather"] > 0 and forms[0]["dense"] == 0
        assert forms[1]["dense"] > 0 and forms[1]["gather"] == 0

    def test_fold_grid_kernel_modes_agree(self, rng, monkeypatch):
        """The batched fold x grid kernels pin the mode into their
        static key too."""
        import numpy as np
        import transmogrifai_tpu.models.trees as T
        from transmogrifai_tpu.models.trees import GBTClassifier
        X = rng.normal(size=(200, 8))
        y = (X[:, 0] > 0).astype(float)
        masks = np.ones((2, 200))
        masks[0, :100] = 0.0
        masks[1, 100:] = 0.0
        grid = [{"max_depth": 3}, {"max_depth": 3, "step_size": 0.3}]
        outs = {}
        for mode in ("scatter", "matmul", "matmul_chunk"):
            monkeypatch.setattr(T, "_hist_mode", lambda n, tb, m=mode: m)
            models = GBTClassifier(num_rounds=4).fit_fold_grid_arrays(
                X, y, masks, grid)
            outs[mode] = models
        for other in ("matmul", "matmul_chunk"):
            for f in range(2):
                for g in range(2):
                    a, b = outs["scatter"][f][g], outs[other][f][g]
                    np.testing.assert_allclose(a.thrs, b.thrs, rtol=1e-6)
                    np.testing.assert_allclose(a.feats, b.feats)
                    np.testing.assert_allclose(a.leaves, b.leaves,
                                               rtol=1e-5)


@pytest.mark.parametrize("backend, x64, n, total_bins, expected", [
    ("cpu", False, 10 ** 9, 4096, "scatter"),      # a CPU never contracts
    ("tpu", False, 1_000_000, 800, "matmul"),      # the 1M-row fit: 2.98 GiB
    ("tpu", False, 2 ** 18, 4096, "matmul"),       # 4 GiB to the byte
    ("tpu", False, 2 ** 18 + 1, 4096, "matmul_chunk"),
    ("tpu", True, 2 ** 17 + 1, 4096, "matmul_chunk"),      # float64 stats
])
def test_hist_mode_is_worked_out_from_platform_and_size(
        monkeypatch, retired_tree_switches, backend, x64, n, total_bins,
        expected):
    """``_hist_mode`` owns the choice of histogram path: the platform
    picks the family, the (n, total_bins) indicator's bytes in the stats
    dtype pick ``matmul_chunk`` past 4 GiB. The variables that used to
    override it (``retired_tree_switches`` sets them all) are not read."""
    import jax
    import transmogrifai_tpu.models.trees as T
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    with jax.enable_x64(x64):
        assert T._hist_mode(n, total_bins) == expected


def _route_case(name):
    """(args, kwargs, lane masks or None) of one ``_grow_tree`` call. The
    stats are one-hot class counts times whole-number row weights, so every
    histogram sum is exact in either histogram mode and the two calls can
    differ in nothing but the routing form."""
    import jax
    import jax.numpy as jnp
    from transmogrifai_tpu.models import trees as T
    rng = np.random.default_rng(27)
    n, d, bins = 600, 10, 16
    if name == "wide_bins":
        d, bins = 24, 32            # TB = 768: indices a bf16 pass rounds
    if name == "pooled":
        d = 40
    X = rng.normal(size=(n, d))
    y = ((X[:, 0] > 0.1) ^ (X[:, 3] < -0.2) | (X[:, 7] > 1.0)).astype(int)
    design = T._PackedDesign(X, max_bins=bins)
    packed, feat_of, block_start, thr = (
        jnp.asarray(design.packed), jnp.asarray(design.feat_of),
        jnp.asarray(design.block_start), jnp.asarray(design.packed_thr))
    onehot = jax.nn.one_hot(jnp.asarray(y), 2, dtype=thr.dtype)
    kwargs = dict(depth=4, gain_fn=T._gini_gain(2.0), min_info_gain=1e-9)
    masks = None
    if name == "compressed":        # 2^3 > 7: levels 2-5 rank-compress and
        kwargs.update(depth=6, node_cap=7)      # the budget mask binds
    elif name == "denied_levels":   # a child holds 151 rows or more, so
        # no node of level 2 (298 rows at most) splits: all rows go left
        kwargs.update(depth=5, gain_fn=T._gini_gain(151.0))
    elif name == "wide_bins":
        assert int(feat_of.shape[0]) > 256
        kwargs.update(depth=5)
    elif name == "pooled":          # as _forest_body passes a tree's pool
        (narrow, wide), cfg, mf = T._pool_plan(design.widths, 2)
        assert cfg is not None
        pool, packed, feat_of, block_start, thr = T._tree_pool(
            jax.random.PRNGKey(3), jnp.asarray(design.binned),
            jnp.asarray(design.col_thr), narrow, wide, cfg)
        kwargs.update(depth=5, feat_map=pool, max_features=mf,
                      feat_key=jax.random.PRNGKey(4))
    elif name == "vmap_lanes":
        masks = jnp.asarray(rng.integers(0, 3, size=(3, n)), thr.dtype)
        kwargs.update(depth=6, node_cap=7)
    return (packed, feat_of, block_start, thr, onehot), kwargs, masks


def _grow(case_args, kwargs, masks, mode):
    """One ``_grow_tree`` call under ``hist_mode=mode``, freshly jitted (so
    it traces), vmapped over the lanes' row masks where the case has any."""
    import jax
    from transmogrifai_tpu.models import trees as T
    packed, feat_of, block_start, thr, stats = case_args

    def one(st):
        return T._grow_tree(packed, feat_of, block_start, thr, st,
                            hist_mode=mode, **kwargs)
    if masks is None:
        return jax.jit(one)(stats)
    return jax.jit(jax.vmap(lambda m: one(stats * m[:, None])))(masks)


class TestRouteForms:
    """The routing step of a level has two forms (models/trees._route_form):
    per-row gathers under the ``scatter`` family, selects over the slot and
    the column axis under the ``matmul`` family. They must route every row
    alike: the same integers, not close ones."""

    @pytest.mark.parametrize("case", [
        "identity", "compressed", "denied_levels", "pooled", "wide_bins",
        "vmap_lanes"])
    def test_dense_route_equals_gather_route(self, case):
        from transmogrifai_tpu.models import trees as T
        args, kwargs, masks = _route_case(case)
        before = T.tree_route_forms()
        gathered = _grow(args, kwargs, masks, "scatter")
        dense = _grow(args, kwargs, masks, "matmul")
        after = T.tree_route_forms()
        assert after["gather"] > before["gather"]
        assert after["dense"] > before["dense"]
        for name, a, b in zip(("feat_heap", "thr_heap", "leaf_stats",
                               "node"), gathered, dense):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=f"{case}: {name}")
        _, thr_heap, _, node = (np.asarray(a) for a in dense)
        assert np.isfinite(thr_heap).sum() >= 2     # a tree was grown
        if case == "denied_levels":  # the TB sentinel: nobody went right
            assert (node % 8 == 0).all() and len(np.unique(node)) > 1
        if case == "vmap_lanes":
            assert not np.array_equal(node[0], node[1])

    def test_wide_designs_keep_the_gather(self, monkeypatch):
        import jax
        from transmogrifai_tpu.models import trees as T
        assert T._route_form("scatter", 4) == "gather"
        for base in ("matmul", "matmul_chunk"):
            assert T._route_form(base, T._ROUTE_DENSE_MAX_D) == "dense"
            assert T._route_form(base, T._ROUTE_DENSE_MAX_D + 1) == "gather"
        # the rule is read while tracing: the same call takes the other form
        (packed, feat_of, block_start, thr, onehot), kwargs, _ = \
            _route_case("identity")

        def grow():     # a new jit each time, so each call traces
            return jax.jit(lambda stats: T._grow_tree(
                packed, feat_of, block_start, thr, stats,
                hist_mode="matmul", **kwargs))(onehot)
        dense = grow()
        monkeypatch.setattr(T, "_ROUTE_DENSE_MAX_D", 4)
        before = T.tree_route_forms()
        gathered = grow()
        after = T.tree_route_forms()
        assert (after["gather"], after["dense"]) \
            == (before["gather"] + 1, before["dense"])
        for a, b in zip(dense, gathered):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _sums_case(name):
    """``_route_case`` plus two with S = 3 real-valued regression statistics
    (``_variance_stats``: weights and w*y in two pieces, under the variance
    gain): every level an identity
    level, and a ``node_cap=7`` tree whose last level is compressed, so its
    leaves are summed by (slot, side) and placed by the slots' node ids.
    Returns the case and whether
    its sums are exact in any order (whole-number statistics)."""
    import jax.numpy as jnp
    from transmogrifai_tpu.models import trees as T
    if not name.startswith("real_"):
        return _route_case(name) + (True,)
    (packed, feat_of, block_start, thr, _), kwargs, masks = _route_case(
        "compressed" if name == "real_compressed" else "identity")
    rng = np.random.default_rng(31)
    n = packed.shape[0]
    w = rng.uniform(0.5, 2.0, size=n)
    target = (np.asarray(packed[:, 0]) / 4.0 - np.asarray(packed[:, 3]) % 5
              + rng.normal(size=n))
    stats = T._variance_stats(jnp.asarray(w, thr.dtype),
                              jnp.asarray(target, thr.dtype))
    # a node of 20 rows a side has no second split that mirrors its best
    kwargs.update(gain_fn=T._variance_gain(20.0))
    return (packed, feat_of, block_start, thr, stats), kwargs, masks, False


class TestSumForms:
    """The per-slot totals of a level and the per-leaf sums of a tree have
    two forms (models/trees._sums_form): ``segment_sum`` under the
    ``scatter`` family, a select over the slot axis reduced over the rows
    (``_slot_sums``) under the ``matmul`` family. The same sums: bit-equal
    where the statistics are whole numbers, summation order apart where
    they are real."""

    @pytest.mark.parametrize("case", [
        "identity", "compressed", "denied_levels", "pooled", "wide_bins",
        "vmap_lanes", "real_stats", "real_compressed"])
    def test_dense_sums_equal_scatter_sums(self, case):
        from transmogrifai_tpu.models import trees as T
        args, kwargs, masks, exact = _sums_case(case)
        before = T.tree_sum_forms()
        scattered = _grow(args, kwargs, masks, "scatter")
        middle = T.tree_sum_forms()
        dense = _grow(args, kwargs, masks, "matmul")
        after = T.tree_sum_forms()
        assert (middle["scatter"] - before["scatter"],
                middle["dense"] - before["dense"]) == (1, 0)
        assert (after["scatter"] - middle["scatter"],
                after["dense"] - middle["dense"]) == (0, 1)
        for name, a, b in zip(("feat_heap", "thr_heap", "leaf_stats",
                               "node"), scattered, dense):
            a, b = np.asarray(a), np.asarray(b)
            if exact or name != "leaf_stats":
                np.testing.assert_array_equal(a, b, err_msg=f"{case}: {name}")
            else:
                np.testing.assert_allclose(a, b, rtol=1e-6, atol=0,
                                           err_msg=f"{case}: {name}")
        _, thr_heap, leaf_stats, node = (np.asarray(a) for a in dense)
        assert np.isfinite(thr_heap).sum() >= 2     # a tree was grown
        # every row is in a leaf, and the leaves hold all of the statistics
        stats = np.asarray(args[4])
        if masks is None:
            np.testing.assert_allclose(leaf_stats.sum(axis=0),
                                       stats.sum(axis=0), rtol=1e-9)
            for leaf in np.unique(node)[:4]:
                np.testing.assert_allclose(
                    leaf_stats[leaf], stats[node == leaf].sum(axis=0),
                    rtol=1e-9)
            assert not leaf_stats[np.setdiff1d(
                np.arange(len(leaf_stats)), node)].any()
        if "compressed" in case:    # the last level outgrew the slot cap
            assert kwargs["node_cap"] < 2 ** (kwargs["depth"] - 1)

    def test_slot_sums_drop_rows_outside_the_slots(self):
        """A row whose slot is outside [0, C) adds nothing, as
        ``segment_sum`` drops it."""
        import jax
        import jax.numpy as jnp
        from transmogrifai_tpu.models import trees as T
        rng = np.random.default_rng(5)
        stats = jnp.asarray(rng.integers(0, 9, size=(200, 3)), jnp.float64)
        slot = jnp.asarray(rng.integers(-2, 9, size=200), jnp.int32)
        slot = slot.at[:3].set(T._SLOT_SENTINEL)
        np.testing.assert_array_equal(
            np.asarray(T._slot_sums(stats, slot, 6)),
            np.asarray(jax.ops.segment_sum(
                jnp.where(((slot >= 0) & (slot < 6))[:, None], stats, 0),
                jnp.clip(slot, 0, 5), num_segments=6)))

    def test_many_slots_keep_the_scatter(self, monkeypatch):
        import jax
        from transmogrifai_tpu.models import trees as T
        assert T._sums_form("scatter", 2) == "scatter"
        for base in ("matmul", "matmul_chunk"):
            assert T._sums_form(base, T._SUMS_DENSE_MAX_SLOTS) == "dense"
            assert T._sums_form(base, T._SUMS_DENSE_MAX_SLOTS + 1) \
                == "scatter"
        # the default cap's widest sum (two leaves under each of 256 slots)
        # is summed densely, by a wide margin
        assert 2 * T._DEFAULT_NODE_CAP * 4 <= T._SUMS_DENSE_MAX_SLOTS
        args, kwargs, _, _ = _sums_case("compressed")
        packed, feat_of, block_start, thr, onehot = args

        def adds(mode):     # the scatter-adds of a freshly traced grower
            jaxpr = jax.make_jaxpr(lambda st: T._grow_tree(
                packed, feat_of, block_start, thr, st, hist_mode=mode,
                **kwargs))(onehot)
            return str(jaxpr).count("scatter-add")
        # ``scatter`` keeps segment_sum (a level's histogram, its totals,
        # the leaves); the matmul family holds no scatter-add at all
        assert adds("scatter") >= 2 * kwargs["depth"] + 1
        assert adds("matmul") == 0
        dense = _grow(args, kwargs, None, "matmul")
        # the rule is read while tracing: the same call takes the other
        # form (the widest sum of this tree has 2 * 7 columns)
        monkeypatch.setattr(T, "_SUMS_DENSE_MAX_SLOTS", 13)
        before = T.tree_sum_forms()
        # (a level's totals and the leaves, and the row counts of the
        # columns of the four carried levels, 2 to 5: ``_carry_slots``)
        assert adds("matmul") == kwargs["depth"] + 1 + 4
        scattered = _grow(args, kwargs, None, "matmul")
        after = T.tree_sum_forms()
        assert (after["scatter"], after["dense"]) \
            == (before["scatter"] + 2, before["dense"])
        for a, b in zip(dense, scattered):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    @pytest.mark.parametrize("case", ["identity", "compressed"])
    def test_row_sharded_sums_equal_unsharded(self, case):
        """Under ``axis_name`` each shard sums its own rows densely and the
        ``psum`` adds the shards' (slots, S) tables; a compressed last
        level places the leaves by its slots' node ids, the same on every
        shard (the carried slots rank the ``psum`` of the occupancy)."""
        import jax
        import jax.numpy as jnp
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        from transmogrifai_tpu.models import trees as T
        from transmogrifai_tpu.parallel import make_mesh
        (packed, feat_of, block_start, thr, onehot), kwargs, _, _ = \
            _sums_case(case)
        n = packed.shape[0]
        mesh = make_mesh({"data": 8})
        assert n % 8 == 0

        def shard_fn(pk, st):
            return T._grow_tree(pk, feat_of, block_start, thr, st,
                                hist_mode="matmul", axis_name="data",
                                row_total=n, **kwargs)
        sharded = jax.jit(shard_map(
            shard_fn, mesh=mesh, in_specs=(P("data", None), P("data", None)),
            out_specs=(P(), P(), P(), P("data")), check_vma=False))(
            packed, onehot)
        whole = _grow((packed, feat_of, block_start, thr, onehot), kwargs,
                      None, "scatter")
        for name, a, b in zip(("feat_heap", "thr_heap", "leaf_stats",
                               "node"), whole, sharded):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=f"{case}: {name}")


def _carry_case(name):
    """(args, kwargs, lane masks or None) of a ``_grow_tree`` call whose
    deep levels carry their slots (``_carry_slots``)."""
    import jax.numpy as jnp
    from transmogrifai_tpu.models import trees as T
    args, kwargs, masks = _route_case(
        "vmap_lanes" if name == "vmap_lanes" else "compressed")
    kwargs = dict(kwargs, gain_fn=T._gini_gain(1.0))
    if name == "after_identity":    # levels 0-2 identity, 3 and 4 carried
        kwargs.update(depth=5, node_cap=8)
    elif name == "denied_levels":   # a child holds 130 rows or more, so no
        # node of levels 3-5 (210 rows at most) splits
        kwargs.update(gain_fn=T._gini_gain(130.0))
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


class TestCarriedSlots:
    """A level wider than the slot cap takes its slots from the level before
    (models/trees._carry_slots): the ranks of the occupied node ids in
    ascending order, worked out on the 2 * C (slot, side) columns and not
    on the rows. The same integers a sort of the rows' node ids gives."""

    @staticmethod
    def _grow_levels(monkeypatch, args, kwargs, masks, mode):
        """The tree and what every call of ``_carry_slots`` took and gave,
        returned out of the jitted (and vmapped) grower as outputs."""
        import jax
        from transmogrifai_tpu.models import trees as T
        packed, feat_of, block_start, thr, stats = args
        real = T._carry_slots
        seen = []

        def spy(slot, node_of_slot, went_right, *rest):
            out = real(slot, node_of_slot, went_right, *rest)
            seen.append((slot, node_of_slot, went_right) + tuple(out))
            return out
        monkeypatch.setattr(T, "_carry_slots", spy)

        def one(st):
            del seen[:]
            tree = T._grow_tree(packed, feat_of, block_start, thr, st,
                                hist_mode=mode, **kwargs)
            return tree, list(seen)
        if masks is None:
            tree, levels = jax.jit(one)(stats)
            lanes = [(tree, levels)]
        else:
            tree, levels = jax.jit(jax.vmap(
                lambda m: one(stats * m[:, None])))(masks)
            lanes = [jax.tree_util.tree_map(lambda a: a[i], (tree, levels))
                     for i in range(masks.shape[0])]
        return [(tuple(np.asarray(a) for a in t),
                 [tuple(np.asarray(a) for a in lv) for lv in ls])
                for t, ls in lanes]

    @pytest.mark.parametrize("mode", ["scatter", "matmul"])
    @pytest.mark.parametrize("case", [
        "after_identity", "budget_mask", "denied_levels", "cap_one",
        "vmap_lanes", "zero_weight"])
    def test_carried_slots_equal_a_ranking_of_the_rows(
            self, monkeypatch, case, mode):
        from transmogrifai_tpu.models import trees as T
        args, kwargs, masks = _carry_case(case)
        depth, node_cap = kwargs["depth"], kwargs["node_cap"]
        lanes = self._grow_levels(monkeypatch, args, kwargs, masks, mode)
        carried = [lv for lv in range(1, depth)
                   if not (2 ** lv <= node_cap
                           and (lv + 1 == depth or 2 ** (lv + 1) <= node_cap))]
        most = 0
        for (_, _, _, final_node), levels in lanes:
            assert len(levels) == len(carried)
            for i, (lv, (slot, node_of_slot, went_right, next_slot,
                         next_node_of_slot, active)) in enumerate(
                    zip(carried, levels)):
                C, C_next = (min(2 ** (lv - 1), node_cap),
                             min(2 ** lv, node_cap))
                assert node_of_slot.shape == (C,)
                assert next_node_of_slot.shape == (C_next,)
                if i == 0 and case != "cap_one":    # after an identity level
                    np.testing.assert_array_equal(node_of_slot, np.arange(C))
                if i > 0:       # a carried level hands on what it was given
                    np.testing.assert_array_equal(slot, levels[i - 1][3])
                    np.testing.assert_array_equal(node_of_slot,
                                                  levels[i - 1][4])
                # every row counts, whatever it weighs
                node = 2 * node_of_slot[slot] + went_right
                ids, ranks = np.unique(node, return_inverse=True)
                np.testing.assert_array_equal(next_slot, ranks,
                                              err_msg=f"level {lv}")
                assert int(active) == len(ids) <= C_next
                np.testing.assert_array_equal(next_node_of_slot[:len(ids)],
                                              ids)
                assert (next_node_of_slot[len(ids):]
                        == T._SLOT_SENTINEL).all()
                most = max(most, len(ids))
            # the last level's rows went on from the nodes it was handed
            np.testing.assert_array_equal(
                final_node >> 1, levels[-1][4][levels[-1][3]])
        if case == "cap_one":
            assert most == 1
        elif case == "denied_levels":
            # denied levels route every row left: the slots stop growing
            assert all(int(lv[5]) == int(levels[1][5]) for lv in levels[1:])
            assert 1 < most < node_cap
        else:           # the budget mask binds: a level filled its slots
            assert most == node_cap

    @pytest.mark.parametrize("case", ["compressed", "vmap_lanes",
                                      "real_compressed"])
    @pytest.mark.parametrize("mode", ["scatter", "matmul"])
    def test_compressed_trees_are_the_parents_bit_for_bit(self, case, mode):
        """Heaps, leaf sums and final nodes of the compressed cases against
        digests taken from the commit before the slots were carried (PR 32,
        70f468a: a sort of the rows' node ids and two n-update scatters a
        level): the same integers, so the same tree to the last bit. The
        leaf sums of real-valued statistics are left to TestSumForms: the
        dense form's order of summation is the machine's, and since PR 34
        the regression columns are ``_variance_stats``'s (w*y in two pieces,
        no squares), not the parent's; heaps and nodes still are."""
        import hashlib
        parent = {
            "compressed": ("2272c2a39ba0a78f", "72274102b81a71a9",
                           "38a673f73c47c907", "51e7d986991c8a40"),
            "vmap_lanes": ("42373553326e7e78", "cea6c946b36c138f",
                           "5d52f23c9a4e047a", "4a8e926c37aa7782"),
            "real_compressed": ("5533eac042618848", "7f1fd0bc25a76f14",
                                "1e0d09119b361401", "60b1544cfb992a8b"),
        }[case]
        args, kwargs, masks, exact = _sums_case(case)
        out = _grow(args, kwargs, masks, mode)
        for name, a, want in zip(("feat_heap", "thr_heap", "leaf_stats",
                                  "node"), out, parent):
            if name == "leaf_stats" and not exact:
                continue
            a = np.ascontiguousarray(np.asarray(a))
            got = hashlib.sha256(str((a.dtype.str, a.shape)).encode()
                                 + a.tobytes()).hexdigest()[:16]
            assert got == want, f"{case} {mode}: {name}"

    def test_no_sort_and_no_per_row_scatter_at_depth_12(self):
        """The traced program of a depth-12 tree at the default cap (levels
        8-11 carried) under the accelerator's mode: no ``sort`` anywhere,
        and no scatter with a row's worth of updates. The CPU's mode keeps
        ``segment_sum``, which is what the walk finds there."""
        import jax
        from transmogrifai_tpu.models import trees as T
        (packed, feat_of, block_start, thr, onehot), kwargs, _ = \
            _route_case("identity")
        n = packed.shape[0]
        kwargs = dict(kwargs, depth=12)

        def eqns(jaxpr):
            for e in jaxpr.eqns:
                yield e
                for v in e.params.values():
                    for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                        sub = getattr(sub, "jaxpr", sub)
                        if hasattr(sub, "eqns"):
                            yield from eqns(sub)

        def per_row(mode):
            before = T.tree_compress_levels()["carried"]
            jaxpr = jax.make_jaxpr(lambda st: T._grow_tree(
                packed, feat_of, block_start, thr, st, hist_mode=mode,
                **kwargs))(onehot).jaxpr
            assert T.tree_compress_levels()["carried"] == before + 4
            found = list(eqns(jaxpr))
            names = {e.primitive.name for e in found}
            assert "cumsum" in names            # the walk reaches the ranks
            assert "sort" not in names
            return [e.primitive.name for e in found
                    if e.primitive.name.startswith("scatter")
                    and n in e.invars[2].aval.shape]
        assert n not in (feat_of.shape[0], 2 ** 12, 2 ** 12 - 1, 256, 512)
        assert per_row("matmul") == []
        assert set(per_row("scatter")) == {"scatter-add"}

    def test_compress_levels_ride_on_the_fetch_span(self):
        """``tree_compress_levels()`` counts the carried levels a traced
        grower holds, and the ``search.fetch`` span carries the count as
        ``compress_carried``, read again when it closes: the program
        traced inside it."""
        from transmogrifai_tpu.models import trees as T
        from transmogrifai_tpu.observability import trace
        args, kwargs, _ = _route_case("compressed")     # levels 2-5
        flat, flat_kwargs, _ = _route_case("identity")
        before = T.tree_compress_levels()
        assert set(before) == {"carried"}
        _grow(flat, flat_kwargs, None, "matmul")
        assert T.tree_compress_levels() == before
        trace.configure(True)
        try:
            with T._fetch_span():
                _grow(args, kwargs, None, "matmul")
            (span,) = [s for s in trace.spans()
                       if s["name"] == "search.fetch"]
        finally:
            trace.configure(False)
            trace.reset()
        assert T.tree_compress_levels() == {"carried": before["carried"] + 4}
        assert span["attrs"]["compress_carried"] == before["carried"] + 4


class TestPoolPlan:
    """Stratified feature-pool planning edge cases (review findings)."""

    def test_minority_class_never_starved(self):
        import numpy as np
        from transmogrifai_tpu.models.trees import _pool_classes
        widths = np.array([2] * 3 + [32] * 997)
        (_, _), (p_n, p_w, b_n, b_w), _ = _pool_classes(widths, 124, 31)
        assert p_n >= 1 and p_w >= 1
        widths = np.array([32] + [2] * 999)
        (_, _), (p_n, p_w, _, _), _ = _pool_classes(widths, 124, 31)
        assert p_n >= 1 and p_w >= 1

    def test_full_coverage_pool_uses_exact_design(self):
        import numpy as np
        from transmogrifai_tpu.models.trees import _pool_plan
        (_, _), cfg, mf = _pool_plan(np.array([2] * 8), 2)
        assert cfg is None and mf == 2


class TestIdentitySlotFastPath:
    """The identity fast path (slots = node ids, no rank-compression
    sort) must produce the same tree as the compressed path whenever
    the budget mask cannot bind."""

    def test_identity_matches_compressed(self, binary_data):
        import jax.numpy as jnp
        import jax
        from transmogrifai_tpu.models.trees import (
            _PackedDesign, _gini_gain, _grow_tree)
        X, y = binary_data
        design = _PackedDesign(X, max_bins=32)
        onehot = jax.nn.one_hot(jnp.asarray(y, jnp.int32), 2)
        depth = 4
        # the target concept has <= 4 leaves, so active nodes per level
        # stay far below both caps and the budget mask never binds in
        # either configuration
        out_id = _grow_tree(
            jnp.asarray(design.packed), jnp.asarray(design.feat_of),
            jnp.asarray(design.block_start),
            jnp.asarray(design.packed_thr), onehot, depth=depth,
            gain_fn=_gini_gain(1.0), min_info_gain=1e-3)
        out_cmp = _grow_tree(
            jnp.asarray(design.packed), jnp.asarray(design.feat_of),
            jnp.asarray(design.block_start),
            jnp.asarray(design.packed_thr), onehot, depth=depth,
            gain_fn=_gini_gain(1.0), min_info_gain=1e-3,
            node_cap=7)  # 2^3 > 7 forces compression at level 3
        for a, b in zip(out_id[:2], out_cmp[:2]):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_allclose(np.asarray(out_id[2]),
                                   np.asarray(out_cmp[2]), rtol=1e-6)

    def test_identity_matches_compressed_with_feature_sampling(
            self, binary_data):
        """With per-node feature sampling, full-tree equality between
        the capped and uncapped runs is NOT a theorem (the budget mask
        can genuinely deny splits near capacity). What IS guaranteed —
        because the feature draw is node-keyed whenever 2^level <= cap
        and both runs split the PRNG key identically per level — is
        that every heap level strictly below the first compressed level
        matches exactly. Checked across many seeds."""
        import jax
        import jax.numpy as jnp
        from transmogrifai_tpu.models.trees import (
            _PackedDesign, _gini_gain, _grow_tree)
        X, y = binary_data
        design = _PackedDesign(X, max_bins=32)
        onehot = jax.nn.one_hot(jnp.asarray(y, jnp.int32), 2)
        args = (jnp.asarray(design.packed), jnp.asarray(design.feat_of),
                jnp.asarray(design.block_start),
                jnp.asarray(design.packed_thr), onehot)
        # node_cap=7, depth=4: levels 0-1 identity in both runs, level 2
        # is the first compressed level (2^3 > 7) -> heap[:3] must agree
        first_compressed = 2
        n_exact = 2 ** first_compressed - 1
        for seed in range(16):
            kw = dict(depth=4, gain_fn=_gini_gain(1.0),
                      min_info_gain=1e-3,
                      feat_key=jax.random.PRNGKey(seed), max_features=3)
            out_id = _grow_tree(*args, **kw)
            out_cmp = _grow_tree(*args, **kw, node_cap=7)
            for a, b in zip(out_id[:2], out_cmp[:2]):
                np.testing.assert_array_equal(
                    np.asarray(a)[:n_exact], np.asarray(b)[:n_exact],
                    err_msg=f"seed {seed}")

    def test_negative_gamma_empty_nodes_stay_leaves(self):
        """gamma < 0 with min_child_weight 0 must not fabricate splits
        on EMPTY nodes (identity slots materialize them)."""
        import jax.numpy as jnp
        from transmogrifai_tpu.models.trees import (
            _PackedDesign, _grow_tree, _xgb_gain)
        rng = np.random.default_rng(3)
        X = rng.normal(size=(64, 3))
        g = np.where(X[:, 0] > 0, 1.0, -1.0)
        h = np.ones(64)
        design = _PackedDesign(X, max_bins=8)
        stats = jnp.stack([jnp.asarray(g), jnp.asarray(h)], axis=1)
        feat, thr, _, _ = _grow_tree(
            jnp.asarray(design.packed), jnp.asarray(design.feat_of),
            jnp.asarray(design.block_start),
            jnp.asarray(design.packed_thr), stats, depth=3,
            gain_fn=_xgb_gain(reg_lambda=1.0, gamma=-0.1,
                              min_child_weight=0.0),
            min_info_gain=0.0)
        thr = np.asarray(thr)
        feat = np.asarray(feat)
        # heap positions whose PARENT did not split must stay route-left
        # leaves ((0, inf)); a spurious empty-node split writes a finite
        # threshold there
        parent = lambda i: (i - 1) // 2
        for i in range(3, 7):          # level-2 heap slots
            if not np.isfinite(thr[parent(i)]):
                assert not np.isfinite(thr[i]), (
                    f"empty node at heap {i} fabricated a split "
                    f"(feat={feat[i]}, thr={thr[i]})")


class TestFoldEdges:
    """TX_TREE_EDGES=fold: quantile edges from fold-train rows only
    (VERDICT r4 #6 — the whole-matrix default is a documented
    feature-distribution-only deviation; this mode removes it)."""

    def test_edge_rows_exclude_outliers(self):
        from transmogrifai_tpu.models.trees import _PackedDesign
        rng = np.random.default_rng(0)
        X = rng.normal(size=(200, 2))
        X[150:, 0] = 1e6           # "validation" rows carry outliers
        train_rows = np.arange(150)
        d_all = _PackedDesign(X, max_bins=16)
        d_fold = _PackedDesign(X, max_bins=16, edge_rows=train_rows)
        thr_all = d_all.col_thr[0][np.isfinite(d_all.col_thr[0])]
        thr_fold = d_fold.col_thr[0][np.isfinite(d_fold.col_thr[0])]
        # whole-matrix edges shift toward the outliers; fold edges don't
        assert thr_all.max() > 100
        assert thr_fold.max() < 100
        # every row still bins in-range against the fold edges
        assert d_fold.packed.max() < d_fold.total_bins

    def test_fold_mode_search_matches_api(self, monkeypatch):
        """The recursive per-fold driver returns the same-(F, G) shapes
        and finite metrics the fold-major path does."""
        from transmogrifai_tpu.evaluators import \
            BinaryClassificationEvaluator
        from transmogrifai_tpu.models.trees import (
            GBTClassifier, RandomForestClassifier, _forest_fold_grid,
            _gbt_fold_grid)
        rng = np.random.default_rng(1)
        n, d, F = 120, 4, 3
        X = rng.normal(size=(n, d))
        y = (X[:, 0] + 0.5 * rng.normal(size=n) > 0).astype(float)
        masks = np.ones((F, n))
        for f in range(F):
            masks[f, f::F] = 0.0
        Xv = np.stack([X[masks[f] == 0][:40] for f in range(F)])
        yv = np.stack([y[masks[f] == 0][:40] for f in range(F)])
        spec = BinaryClassificationEvaluator().device_metric_spec()
        grid_rf = [{"max_depth": 3, "min_info_gain": g}
                   for g in (0.001, 0.1)]
        grid_gbt = [{"max_depth": 3, "gamma": g} for g in (0.0, 0.1)]
        mm_default_rf = _forest_fold_grid(
            RandomForestClassifier(num_trees=5), X, y, masks, grid_rf,
            None, True, eval_ctx=(Xv, yv, spec))
        mm_default_gbt = _gbt_fold_grid(
            GBTClassifier(num_rounds=3), X, y, masks, grid_gbt, None,
            "logistic", eval_ctx=(Xv, yv, spec))
        monkeypatch.setenv("TX_TREE_EDGES", "fold")
        mm_fold_rf = _forest_fold_grid(
            RandomForestClassifier(num_trees=5), X, y, masks, grid_rf,
            None, True, eval_ctx=(Xv, yv, spec))
        mm_fold_gbt = _gbt_fold_grid(
            GBTClassifier(num_rounds=3), X, y, masks, grid_gbt, None,
            "logistic", eval_ctx=(Xv, yv, spec))
        for mm in (mm_fold_rf, mm_fold_gbt):
            assert mm.shape == (F, 2)
            assert np.isfinite(mm).all()
        # same data, different edge protocol: metrics stay in the same
        # ballpark (both are valid CV estimates)
        assert abs(mm_fold_rf.mean() - mm_default_rf.mean()) < 0.2
        assert abs(mm_fold_gbt.mean() - mm_default_gbt.mean()) < 0.2


def _depth_blocks_table(seed=4, n=150, d=4, F=2):
    """A small binary table, F folds that hold out every F-th row, and the
    validation folds in both forms: the rows' values and their positions."""
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d))
    y = (X[:, 0] + 0.5 * rng.normal(size=n) > 0).astype(float)
    masks = np.ones((F, n))
    for f in range(F):
        masks[f, f::F] = 0.0
    nv = min(int((masks[f] == 0).sum()) for f in range(F))
    rows = np.stack([np.nonzero(masks[f] == 0)[0][:nv] for f in range(F)])
    return X, y, masks, X[rows], y[rows], rows


class TestDepthBlocks:
    """The ``blocks`` depth mode (models/trees._depth_mode, the
    accelerator's side of the rule): one program per tree family, its
    lanes in static depth blocks, one a distinct ``max_depth`` of the grid
    (``_candidate_groups``, ``_vmap_blocks``). A block's body is
    what the per-depth ``static`` program traces, so metrics and fitted
    models must be BIT-identical to ``static`` on a CPU."""

    @staticmethod
    def _both(monkeypatch, call):
        import transmogrifai_tpu.models.trees as T
        out = []
        for mode in ("static", "blocks"):
            monkeypatch.setattr(T, "_depth_mode", lambda mode=mode: mode)
            out.append(call())
        return out

    @pytest.mark.parametrize("form", ["traverse", "in_fit"])
    def test_blocks_mode_metrics_identical(self, monkeypatch, form):
        """The fused fit+metric kernels, in both forms of ``fg.metric``."""
        from transmogrifai_tpu.evaluators import \
            BinaryClassificationEvaluator
        from transmogrifai_tpu.models.trees import (
            GBTClassifier, RandomForestClassifier, _forest_fold_grid,
            _gbt_fold_grid)
        X, y, masks, Xv, yv, rows = _depth_blocks_table()
        spec = BinaryClassificationEvaluator().device_metric_spec()
        ctx = ((Xv, yv, spec) if form == "traverse"
               else (None, yv, spec, rows))
        grid_rf = [{"max_depth": dd, "min_instances_per_node": m}
                   for dd in (2, 4) for m in (5, 20)]
        grid_gbt = [{"max_depth": dd} for dd in (2, 4)]
        mm_s_rf, mm_b_rf = self._both(monkeypatch, lambda: _forest_fold_grid(
            RandomForestClassifier(num_trees=5), X, y, masks, grid_rf,
            None, True, eval_ctx=ctx))
        mm_s_gbt, mm_b_gbt = self._both(monkeypatch, lambda: _gbt_fold_grid(
            GBTClassifier(num_rounds=3), X, y, masks, grid_gbt, None,
            "logistic", eval_ctx=ctx))
        assert np.isfinite(mm_s_rf).all() and np.isfinite(mm_s_gbt).all()
        assert len(np.unique(mm_s_rf)) > 4
        np.testing.assert_array_equal(mm_s_rf, mm_b_rf)
        np.testing.assert_array_equal(mm_s_gbt, mm_b_gbt)

    def test_blocks_mode_fitted_models_identical(self, monkeypatch):
        """The non-eval (model-materializing) path agrees too: a block's
        heaps come back at its lanes' own depth, and a depth-2 lane beside
        a depth-4 one predicts exactly like the static depth-2 program."""
        from transmogrifai_tpu.models.trees import (
            RandomForestClassifier, _forest_fold_grid)
        rng = np.random.default_rng(6)
        n = 120
        X = rng.normal(size=(n, 3))
        y = (X[:, 0] > 0).astype(float)
        masks = np.ones((1, n))
        grid = [{"max_depth": dd} for dd in (2, 4)]
        ms, mk = self._both(monkeypatch, lambda: _forest_fold_grid(
            RandomForestClassifier(num_trees=4), X, y, masks, grid, None,
            True))
        Xt = rng.normal(size=(50, 3))
        for gi, dd in enumerate((2, 4)):
            assert mk[0][gi].depth == dd
            assert mk[0][gi].feats.shape == (4, 2 ** dd - 1)
            np.testing.assert_array_equal(ms[0][gi].feats, mk[0][gi].feats)
            np.testing.assert_array_equal(ms[0][gi].leaves,
                                          mk[0][gi].leaves)
            ps = ms[0][gi].predict_arrays(Xt)
            pk = mk[0][gi].predict_arrays(Xt)
            np.testing.assert_array_equal(ps.data, pk.data)

    def test_scrambled_depths_land_in_their_own_cells(self, monkeypatch):
        """A grid whose depths come in no order (and repeat): the blocks
        are laid out ascending, each fold-major over its own members, and
        every metric still lands in its own (fold, grid point) cell."""
        import transmogrifai_tpu.models.trees as T
        from transmogrifai_tpu.evaluators import \
            BinaryClassificationEvaluator
        X, y, masks, Xv, yv, _ = _depth_blocks_table(seed=9, F=3)
        spec = BinaryClassificationEvaluator().device_metric_spec()
        grid = [{"max_depth": dd, "min_child_weight": w} for dd, w in (
            (4, 0.0), (2, 3.0), (3, 0.0), (2, 0.0), (4, 3.0), (3, 3.0),
            (2, 8.0))]
        est = T.GBTClassifier(num_rounds=3)
        monkeypatch.setattr(T, "_depth_mode", lambda: "blocks")
        (_, blocks), = list(T._candidate_groups(
            est, grid, masks, None, T._GBT_TILED, T._GBT_SKEY))
        assert [b.depth for b in blocks] == [2, 3, 4]
        assert [[gi for gi, _ in b.members] for b in blocks] \
            == [[1, 3, 6], [2, 5], [0, 4]]
        assert [b.count for b in blocks] == [9, 6, 6]
        lanes = blocks[0].lanes
        np.testing.assert_array_equal(blocks[0].fidx,
                                      np.repeat(np.arange(3), 3))
        np.testing.assert_array_equal(      # a traced vector, fold-major
            lanes[1 + T._GBT_TILED.index("min_child_weight")],
            [3.0, 0.0, 8.0] * 3)
        np.testing.assert_array_equal(lanes[0], np.repeat(masks, 3, axis=0))
        mm = T._gbt_fold_grid(est, X, y, masks, grid, None, "logistic",
                              eval_ctx=(Xv, yv, spec))
        # each grid point alone: one block of one depth, its own column
        for gi, point in enumerate(grid):
            alone = T._gbt_fold_grid(est, X, y, masks, [point], None,
                                     "logistic", eval_ctx=(Xv, yv, spec))
            np.testing.assert_array_equal(alone[:, 0], mm[:, gi],
                                          err_msg=str(point))
        assert len(np.unique(mm)) > 12

    def test_one_depth_grid_is_one_block_and_blocks_share_levels(
            self, monkeypatch):
        """A grid with one depth is one block whichever way
        ``_depth_mode`` answers: the same lanes, the same statics (so the
        same cached kernel). And a program of several blocks traces ONE
        grower and the levels of its deepest block only
        (``_grow_blocks``): a level runs once, over the lanes of every
        block still growing, so depths (2, 3, 4) trace 4 levels, not 9."""
        import jax
        import transmogrifai_tpu.models.trees as T
        X, y, masks, _, _, _ = _depth_blocks_table()
        grid = [{"max_depth": 3, "gamma": g} for g in (0.0, 0.5, 1.0)]
        est = T.GBTClassifier(num_rounds=2)
        laid = self._both(monkeypatch, lambda: list(T._candidate_groups(
            est, grid, masks, None, T._GBT_TILED, T._GBT_SKEY)))
        for (_, blocks), in laid:
            assert [b.depth for b in blocks] == [3]
            assert T.tree_depth_blocks(blocks) == {
                "blocks": 1, "lane_levels": 2 * 3 * 3}
        for a, b in zip(laid[0][0][1][0].lanes, laid[1][0][1][0].lanes):
            np.testing.assert_array_equal(a, b)
        T._gbt_fg_kernel.cache_clear()      # so that each lowering traces
        design, _ = T._design_args(X, est.max_bins)
        lanes = laid[1][0][1][0].lanes          # no fold index: not fused
        args = ((tuple(jax.numpy.asarray(a) for a in lanes),), *design[:4],
                jax.numpy.asarray(y), jax.random.PRNGKey(0))
        level_lanes = []
        hist = T._level_histograms
        monkeypatch.setattr(T, "_level_histograms", lambda *a, **kw: (
            level_lanes.append(a[1].aval.shape), hist(*a, **kw))[1])

        def traced(depths):
            before = T.tree_route_forms()["gather"]
            level_lanes.clear()
            T._gbt_fg_kernel((depths, 2, "logistic", "scatter")).lower(
                *((args[0] * len(depths),) + args[1:]))
            return (T.tree_route_forms()["gather"] - before,
                    len(level_lanes))
        assert traced((3,)) == (1, 3)       # one grower, three levels
        assert traced((2, 3, 4)) == (1, 4)  # one grower, the deepest's

    def test_blocks_are_padded_one_by_one_on_a_mesh(self, monkeypatch):
        """Under a 4-device ``models`` mesh each block is padded to the
        shard count on its own (every chip its share of every depth) and
        the (F, G) matrix equals the unsharded one."""
        import transmogrifai_tpu.models.trees as T
        from transmogrifai_tpu.evaluators import \
            BinaryClassificationEvaluator
        from transmogrifai_tpu.parallel import make_mesh
        X, y, masks, Xv, yv, rows = _depth_blocks_table(seed=11, F=3)
        spec = BinaryClassificationEvaluator().device_metric_spec()
        # 3 folds x (3, 1, 2) members = 9, 3 and 6 lanes: pads of 3, 1, 2
        grid = [{"max_depth": dd, "min_instances_per_node": m}
                for dd, m in ((2, 5), (4, 5), (2, 10), (3, 5), (2, 20),
                              (4, 20))]
        est = T.RandomForestClassifier(num_trees=3)
        mesh = make_mesh({"models": 4})
        monkeypatch.setattr(T, "_depth_mode", lambda: "blocks")
        (_, blocks), = list(T._candidate_groups(
            est, grid, masks, mesh, T._FOREST_TRACED, T._FOREST_STATIC))
        assert [b.depth for b in blocks] == [2, 3, 4]
        assert [b.count for b in blocks] == [9, 3, 6]
        assert [len(b.lanes[0]) for b in blocks] == [12, 4, 8]
        for b in blocks:
            assert all(len(a) == len(b.fidx) for a in b.lanes)
            assert (b.lanes[0][b.count:] == 1.0).all()  # all-ones masks
            assert (b.fidx[b.count:] == 0).all()
        assert T.tree_depth_blocks(blocks) == {
            "blocks": 3, "lane_levels": 3 * (2 * 3 + 3 + 4 * 2)}
        for ctx in ((Xv, yv, spec), (None, yv, spec, rows)):
            whole = T._forest_fold_grid(est, X, y, masks, grid, None, True,
                                        eval_ctx=ctx)
            sharded = T._forest_fold_grid(est, X, y, masks, grid, mesh,
                                          True, eval_ctx=ctx)
            assert np.isfinite(whole).all()
            np.testing.assert_array_equal(whole, sharded)
        ms = T._forest_fold_grid(est, X, y, masks, grid, None, True)
        mk = T._forest_fold_grid(est, X, y, masks, grid, mesh, True)
        for f in range(3):
            for gi in range(len(grid)):
                np.testing.assert_array_equal(ms[f][gi].feats,
                                              mk[f][gi].feats)
                np.testing.assert_array_equal(ms[f][gi].leaves,
                                              mk[f][gi].leaves)

    @pytest.mark.parametrize("K", [2, 7])
    def test_scanned_votes_are_the_mean_of_the_picked_leaves(self, K):
        """``_candidate_scores`` adds a classification forest's votes up a
        tree at a time (a (nv, K) pick a step, not one (T, nv, K) pick):
        the same bits as the mean over the trees of each row's leaf row,
        at K = 2 (binary scores) and K = 7 (vote probabilities)."""
        import jax
        import jax.numpy as jnp
        import transmogrifai_tpu.models.trees as T
        from transmogrifai_tpu.evaluators.device_metrics import (
            binary_from_votes, vote_probability)
        rng = np.random.default_rng(K)
        trees, depth, nv = 11, 4, 97
        leaves = rng.dirichlet(np.ones(K), size=(trees, 2 ** depth))
        leaf = rng.integers(0, 2 ** depth, size=(trees, nv))
        from_votes = binary_from_votes if K == 2 else vote_probability
        # both under ``jit``, as the kernel runs them: the compiler treats
        # the division by the tree count alike on both sides
        got = jax.jit(lambda le, lf: T._candidate_scores(
            "forest", "binary" if K == 2 else "multiclass", depth, None,
            None, le, 0.0, None, lf))(jnp.asarray(leaves), jnp.asarray(leaf))
        want = jax.jit(lambda le, lf: from_votes(jnp.mean(
            le[jnp.arange(trees)[:, None], lf], axis=0)))(
            jnp.asarray(leaves), jnp.asarray(leaf))
        for g, w in zip(got if K == 2 else (got,),
                        want if K == 2 else (want,)):
            assert g.shape == w.shape and g.dtype == w.dtype
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))

    @pytest.mark.parametrize("K", [2, 7])
    def test_fused_forest_metric_is_the_materialized_models(
            self, monkeypatch, K):
        """The fused kernel's metric of a lane (votes scanned on the
        device, the metric mapped over the lanes) is the metric of that
        lane's materialized model, blocks and all: of its ``raw_arrays``
        (the device twin of ``predict_arrays``, the mean of the picked
        leaf rows) under ``jit``, whose probabilities are
        ``predict_arrays``' to the last place or two (numpy divides by the
        tree count where the compiler multiplies by its reciprocal, which
        can part two scores that tie, so the METRIC is compared on the
        device's side)."""
        import jax
        import jax.numpy as jnp
        import transmogrifai_tpu.models.trees as T
        from transmogrifai_tpu.evaluators import (
            BinaryClassificationEvaluator, MultiClassificationEvaluator)
        from transmogrifai_tpu.evaluators.device_metrics import (
            binary_from_votes, metric_fn, vote_probability)
        X, y, masks, Xv, yv, _ = _depth_blocks_table(seed=K, n=240, F=2)
        if K == 7:
            y = np.digitize(X[:, 0] + 0.3 * X[:, 1],
                            [-1.2, -0.7, -0.2, 0.2, 0.7, 1.2]).astype(float)
            yv = np.stack([y[masks[f] == 0][:yv.shape[1]] for f in (0, 1)])
        spec = (BinaryClassificationEvaluator() if K == 2
                else MultiClassificationEvaluator()).device_metric_spec()
        grid = [{"max_depth": dd} for dd in (2, 4, 3)]
        est = T.RandomForestClassifier(num_trees=5)
        monkeypatch.setattr(T, "_depth_mode", lambda: "blocks")
        mm = T._forest_fold_grid(est, X, y, masks, grid, None, True,
                                 eval_ctx=(Xv, yv, spec))
        models = T._forest_fold_grid(est, X, y, masks, grid, None, True)
        mfn = metric_fn(*spec)
        from_votes = binary_from_votes if K == 2 else vote_probability
        for f in range(2):
            for gi in range(len(grid)):
                model = models[f][gi]
                metric, prob = jax.jit(lambda xv, yv: (
                    mfn(yv, from_votes(model.raw_arrays(xv))),
                    vote_probability(model.raw_arrays(xv))))(
                    jnp.asarray(Xv[f]), jnp.asarray(yv[f]))
                np.testing.assert_allclose(
                    mm[f, gi], float(metric), rtol=1e-12,
                    err_msg=f"fold {f} point {grid[gi]}")
                np.testing.assert_allclose(
                    model.predict_arrays(Xv[f]).probability,
                    np.asarray(prob), rtol=0, atol=1e-15)
        assert len(np.unique(mm)) > 3

    @pytest.mark.parametrize("kind", ["binary", "multiclass", "regression"])
    def test_lane_metrics_map_is_each_lanes_metric(self, kind):
        """``_lane_metrics`` runs the metric ONE lane at a time over the
        lanes of all the blocks (``lax.map``): each entry is the bits of
        the metric called on that lane alone, and every block gets the
        vector of its own lanes back."""
        import jax
        import jax.numpy as jnp
        import transmogrifai_tpu.models.trees as T
        from transmogrifai_tpu.evaluators import (
            BinaryClassificationEvaluator, MultiClassificationEvaluator,
            RegressionEvaluator)
        from transmogrifai_tpu.evaluators.device_metrics import metric_fn
        rng = np.random.default_rng(3)
        nv, sizes = 61, (3, 1, 4)
        ev = {"binary": BinaryClassificationEvaluator,
              "multiclass": MultiClassificationEvaluator,
              "regression": RegressionEvaluator}[kind]()
        mfn = metric_fn(*ev.device_metric_spec())
        classes = {"binary": 2, "multiclass": 4, "regression": 0}[kind]
        yv = jnp.asarray(rng.normal(size=(2, nv)) if kind == "regression"
                         else rng.integers(0, classes, size=(2, nv)
                                           ).astype(float))

        def scores(lanes):
            if kind == "regression":
                return jnp.asarray(rng.normal(size=(lanes, nv)))
            prob = jnp.asarray(rng.dirichlet(np.ones(classes),
                                             size=(lanes, nv)))
            if kind == "multiclass":
                return prob
            return prob[:, :, 1], (prob[:, :, 1] > 0.5).astype(float)
        blocks = tuple((None, jnp.asarray(rng.integers(0, 2, size=k)))
                       for k in sizes)
        per_block = tuple(scores(k) for k in sizes)
        got = jax.jit(lambda yv, fi, sc: T._lane_metrics(
            mfn, yv, tuple((None, f) for f in fi), sc))(
            yv, tuple(b[-1] for b in blocks), per_block)
        assert [g.shape for g in got] == [(k,) for k in sizes]
        alone = jax.jit(lambda y, sc: mfn(y, sc))
        for block, sc, g in zip(blocks, per_block, got):
            for lane, fold in enumerate(np.asarray(block[-1])):
                own = (tuple(a[lane] for a in sc) if kind == "binary"
                       else sc[lane])
                assert float(g[lane]) == float(alone(yv[fold], own))

    @pytest.mark.parametrize("family", ["forest", "gbt"])
    def test_default_grid_reads_3_blocks_378_lane_levels(
            self, monkeypatch, family):
        """The selector's default tree grids (18 points: depth 3 / 6 / 12,
        six each) under 3 folds: the ``search.fetch`` span of the family's
        one program carries ``depth_blocks`` = 3 and ``depth_lane_levels``
        = 18 x (3 + 6 + 12) = 378 (every lane to depth 12: 648)."""
        import transmogrifai_tpu.models.trees as T
        from transmogrifai_tpu.evaluators import \
            BinaryClassificationEvaluator
        from transmogrifai_tpu.models.registry import default_binary_models
        from transmogrifai_tpu.observability import trace
        _, (rf, grid_rf), (gbt, grid_gbt), _ = default_binary_models()
        X, y, masks, _, yv, rows = _depth_blocks_table(seed=13, n=90, F=3)
        ctx = (None, yv, BinaryClassificationEvaluator().device_metric_spec(),
               rows)
        monkeypatch.setattr(T, "_depth_mode", lambda: "blocks")
        trace.configure(True)
        try:
            if family == "forest":
                mm = T._forest_fold_grid(rf.with_params(num_trees=2), X, y,
                                         masks, grid_rf, None, True,
                                         eval_ctx=ctx)
            else:
                mm = T._gbt_fold_grid(gbt.with_params(num_rounds=1), X, y,
                                      masks, grid_gbt, None, "logistic",
                                      eval_ctx=ctx)
            (span,) = [s for s in trace.spans()
                       if s["name"] == "search.fetch"]
        finally:
            trace.configure(False)
            trace.reset()
        assert mm.shape == (3, 18) and np.isfinite(mm).all()
        assert span["attrs"]["depth_blocks"] == 3
        assert span["attrs"]["depth_lane_levels"] == 378


class TestMatmulChunk:
    """The ``matmul_chunk`` path: the MXU contraction with the bin
    indicator rebuilt per bin block by gather+compare — exact vs the
    whole-matrix modes even when multiple blocks are forced."""

    @pytest.mark.parametrize("family, reference", [
        ("gbt", "matmul"), ("tree", "scatter")])
    def test_multi_block_exact(self, rng, monkeypatch, family, reference):
        """The boosted fit against the whole-matrix ``matmul`` mode: real
        gradients, and against ``scatter`` its node totals differ in
        summation order (``_sums_form``), which flips the exact ties of
        mirrored splits in the small nodes of 300 rows. The classification
        tree against ``scatter``: class counts, exact in any order."""
        import transmogrifai_tpu.models.trees as T
        X = rng.normal(size=(300, 10))
        y = (X[:, 0] + 0.3 * X[:, 1] > 0).astype(float)

        def fit():
            if family == "gbt":
                return T.GBTClassifier(num_rounds=6, max_depth=4
                                       ).fit_arrays(X, y)
            return T.DecisionTreeClassifier(max_depth=4).fit_arrays(X, y)
        monkeypatch.setattr(T, "_hist_mode", lambda n, tb: reference)
        ref = fit()
        monkeypatch.setattr(T, "_hist_mode", lambda n, tb: "matmul_chunk")
        # force many bin blocks: step = max(8, 1000//300) = 8 bins per
        # block -> dozens of blocks over this design's packed bins
        monkeypatch.setattr(T, "_HIST_CHUNK_ELEMS", 1000)
        chk = fit()
        np.testing.assert_allclose(ref.thrs, chk.thrs, rtol=1e-6)
        np.testing.assert_array_equal(ref.feats, chk.feats)
        np.testing.assert_allclose(ref.leaves, chk.leaves, rtol=1e-5)


# ---------------------------------------------------------------------------
# ISSUE 37: a lane's rows in the lane's own order. With ``hist_rows`` only the
# leading rows of the design carry statistics: the level histograms contract
# them, the node sums add them up, and the rows behind them (a fold's held-out
# rows) are routed and nothing more
# ---------------------------------------------------------------------------

def _head_case(stats_kind, depth):
    """A design whose last third of rows is held out, with statistics that
    are multiples of 1/64 (every sum exact in float64 in ANY order, so the
    head form and the all-rows form may differ in nothing): class counts,
    ``[g, h]`` under the XGBoost gain, or ``[w, hi, lo]`` under the variance
    gain. Returns (design args, stats (n, S), head rows, grow kwargs)."""
    import jax.numpy as jnp
    from transmogrifai_tpu.models import trees as T
    rng = np.random.default_rng(37)
    n, d, h = 720, 8, 480
    X = rng.normal(size=(n, d))
    design = T._PackedDesign(X, max_bins=16)
    args = tuple(jnp.asarray(a) for a in (
        design.packed, design.feat_of, design.block_start,
        design.packed_thr))
    signal = X[:, 0] - 0.6 * X[:, 2] + 0.5 * rng.normal(size=n)
    w = rng.integers(0, 3, size=n).astype(np.float64)  # zero weights inside
    if stats_kind == "counts":
        stats = np.eye(3)[np.digitize(signal, [-0.5, 0.5])] * w[:, None]
        gain = T._gini_gain(1.0)
    elif stats_kind == "xgb":
        stats = np.stack([np.round(signal * 64) / 64 * w, w], axis=1)
        gain = T._xgb_gain(1.0, 0.0, 1.0)
    else:
        v = np.round(signal * 64) / 64 * w
        hi = np.round(v * 4) / 4
        stats = np.stack([w, hi, v - hi], axis=1)
        gain = T._variance_gain(1.0)
    kwargs = dict(depth=depth, gain_fn=gain, min_info_gain=0.0)
    if depth == 6:
        kwargs.update(node_cap=7)       # compressed levels, the budget mask
    return args, stats, h, kwargs


class TestHeadRows:
    @pytest.mark.parametrize("depth", [3, 6, 12])
    @pytest.mark.parametrize("stats_kind", ["counts", "xgb", "variance"])
    @pytest.mark.parametrize("mode", ["matmul", "matmul_chunk", "scatter"])
    def test_held_out_rows_never_reach_the_histograms(self, mode,
                                                      stats_kind, depth):
        """(c) The tree over the head rows alone is the tree over all rows
        with the tail at weight zero: heaps, leaf sums and every row's leaf,
        bit for bit, at depths that cross the slot cap; and what the tail's
        statistics hold is never read: an ``inf`` planted there leaves the
        tree unchanged, where the all-rows form turns it to NaN."""
        import jax
        import jax.numpy as jnp
        from transmogrifai_tpu.models import trees as T
        args, stats, h, kwargs = _head_case(stats_kind, depth)
        zero_tail = stats.copy()
        zero_tail[h:] = 0.0
        planted = stats.copy()
        planted[h:] = np.inf

        def grow(st, **kw):
            return [np.asarray(a) for a in jax.jit(
                lambda s: T._grow_tree(*args, s, hist_mode=mode, **kwargs,
                                       **kw))(jnp.asarray(st))]
        before = T.tree_hist_rows()
        all_rows = grow(zero_tail)
        middle = T.tree_hist_rows()
        head = grow(planted, hist_rows=h)
        after = T.tree_hist_rows()
        assert (middle["all"], middle["head"]) \
            == (before["all"] + 1, before["head"])
        assert (after["all"], after["head"]) \
            == (middle["all"], middle["head"] + 1)
        for name, a, b in zip(("feat_heap", "thr_heap", "leaf_stats",
                               "node"), all_rows, head):
            np.testing.assert_array_equal(a, b, err_msg=name)
        assert np.isfinite(head[1]).sum() >= 5      # a tree was grown
        assert len(np.unique(head[3][h:])) > 2      # the tail was routed
        if depth == 12:                 # splits below the identity levels
            assert np.isfinite(head[1][2 ** 8 - 1:]).any()
        assert not np.isfinite(grow(planted)[2]).all()

    @pytest.mark.parametrize("mode", ["matmul", "matmul_chunk", "scatter"])
    def test_a_head_grower_holds_the_head_alone(self, mode):
        """What a grower with ``hist_rows`` hands ``_level_histograms`` is the
        first h rows of every per-row array, whatever lies behind them, in
        every mode: a held indicator holds those rows only, and ``head`` of
        a grower over all rows is the array itself (nothing is traced)."""
        import jax.numpy as jnp
        from transmogrifai_tpu.models import trees as T
        (packed, feat_of, block_start, thr), stats, h, _ = _head_case("xgb",
                                                                      3)
        TB = int(feat_of.shape[0])
        planted = stats.copy()
        planted[h:] = np.inf
        planted = jnp.asarray(planted)
        slot = jnp.asarray(np.random.default_rng(3).integers(0, 4, 720),
                           jnp.int32)
        grower = T._TreeGrower(packed, feat_of, block_start, thr,
                               jnp.float64, max_depth=3, hist_mode=mode,
                               hist_rows=h)
        whole = T._TreeGrower(packed, feat_of, block_start, thr,
                              jnp.float64, max_depth=3, hist_mode=mode)
        assert whole.head(planted) is planted
        assert grower.head(planted).shape == (h, 2)
        if mode == "matmul":
            assert grower.bin_oh.shape == (h, TB)
            assert whole.bin_oh.shape == (720, TB)
        else:
            assert grower.bin_oh is None
        got = T._level_histograms(
            grower.head(packed), grower.head(slot), grower.head(planted), 4,
            TB, grower.bin_oh, mode=mode, feat_of=feat_of)
        want = T._level_histograms(
            packed, slot, jnp.asarray(np.where(np.arange(720)[:, None] < h,
                                               stats, 0.0)), 4, TB,
            whole.bin_oh, mode=mode, feat_of=feat_of)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        assert np.isfinite(np.asarray(got)).all()

    @pytest.mark.parametrize("family", ["gbt", "forest", "tree"])
    def test_a_single_fit_contracts_all_rows(self, family):
        """(d) A single fit has no held-out rows: ``tree_hist_rows()``
        counts its growers under ``all``, whatever the histogram mode."""
        import transmogrifai_tpu.models.trees as T
        rng = np.random.default_rng(5)
        X = rng.normal(size=(93, 4))            # shapes of this test alone
        y = (X[:, 0] > 0).astype(float)
        est = {"gbt": T.GBTClassifier(num_rounds=2, max_depth=2),
               "forest": T.RandomForestClassifier(num_trees=2, max_depth=2),
               "tree": T.DecisionTreeClassifier(max_depth=2)}[family]
        before = T.tree_hist_rows()
        est.fit_arrays(X, y)
        after = T.tree_hist_rows()
        assert after["all"] > before["all"]
        assert after["head"] == before["head"]


class TestFrameRoom:
    """``utils.jax_setup.with_frame_room``: the first call of a fold-grid
    program runs below a frame with a chunk of the interpreter's frame stack
    of its own, so that tracing never sits at a chunk's end (CPython 3.11 /
    3.12 map and unmap a chunk on every call there)."""

    def test_it_calls_through(self):
        from transmogrifai_tpu.utils.jax_setup import with_frame_room
        assert with_frame_room(lambda: 7) == 7
        with pytest.raises(ZeroDivisionError):
            with_frame_room(lambda: 1 / 0)

    def test_callees_meet_no_chunk_end(self):
        """A tiny call repeated at every depth: somewhere a plain stack is
        many times slower than its median (where the loop straddles a
        chunk's end; interpreters without chunks show nothing, which is
        fine); below the roomy frame no depth is."""
        import time
        from transmogrifai_tpu.utils.jax_setup import with_frame_room

        def tiny():
            return 0

        def hot():
            t0 = time.perf_counter()
            for _ in range(20000):
                tiny()
            return time.perf_counter() - t0

        def nest(d):
            return hot() if d == 0 else nest(d - 1)
        roomy = [with_frame_room(lambda: nest(d)) for d in range(0, 400)]
        assert max(roomy) < 25 * sorted(roomy)[200]

    @pytest.mark.parametrize("family", ["forest", "gbt"])
    def test_fold_grid_programs_run_below_it(self, monkeypatch, family):
        import transmogrifai_tpu.models.trees as T
        seen = []
        real = T.with_frame_room
        monkeypatch.setattr(T, "with_frame_room",
                            lambda fn: seen.append(1) or real(fn))
        rng = np.random.default_rng(3)
        X = rng.normal(size=(60, 3))
        y = (X[:, 0] > 0).astype(float)
        masks = np.ones((2, 60))
        masks[0, :30] = 0
        masks[1, 30:] = 0
        est = (T.RandomForestClassifier(num_trees=2, max_depth=2)
               if family == "forest" else
               T.GBTClassifier(num_rounds=2, max_depth=2))
        models = est.fit_fold_grid_arrays(X, y, masks, [{}])
        assert len(models) == 2 and seen
