"""The fold-grid drivers of ``models/trees/fold_grid.py``: a depth sweep
in depth blocks of one program (``forms._depth_mode`` "blocks") against one
program a depth, the fold-edge protocol (``TX_TREE_EDGES=fold``), and the
frame room a fold-grid program's first call runs in."""
import numpy as np
import pytest

from transmogrifai_tpu.models import trees
from transmogrifai_tpu.models.trees import fold_grid, forms, grower
from transmogrifai_tpu.models.trees import design as tree_design
from tree_cases import spy_on


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
    """The ``blocks`` depth mode (models/trees/forms.py _depth_mode, the
    accelerator's side of the rule): one program per tree family, its
    lanes in static depth blocks, one a distinct ``max_depth`` of the grid
    (``_candidate_groups``, ``_vmap_blocks``). A block's body is
    what the per-depth ``static`` program traces, so metrics and fitted
    models must be BIT-identical to ``static`` on a CPU."""

    @staticmethod
    def _both(monkeypatch, call):
        out = []
        for mode in ("static", "blocks"):
            monkeypatch.setattr(forms, "_depth_mode", lambda mode=mode: mode)
            out.append(call())
        return out

    @pytest.mark.parametrize("form", ["traverse", "in_fit"])
    def test_blocks_mode_metrics_identical(self, monkeypatch, form):
        """The fused fit+metric kernels, in both forms of ``fg.metric``."""
        from transmogrifai_tpu.evaluators import \
            BinaryClassificationEvaluator
        from transmogrifai_tpu.models.trees import (GBTClassifier,
                                                    RandomForestClassifier)
        from transmogrifai_tpu.models.trees.fold_grid import (
            _forest_fold_grid, _gbt_fold_grid)
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
        from transmogrifai_tpu.models.trees import RandomForestClassifier
        from transmogrifai_tpu.models.trees.fold_grid import _forest_fold_grid
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
        from transmogrifai_tpu.evaluators import \
            BinaryClassificationEvaluator
        X, y, masks, Xv, yv, _ = _depth_blocks_table(seed=9, F=3)
        spec = BinaryClassificationEvaluator().device_metric_spec()
        grid = [{"max_depth": dd, "min_child_weight": w} for dd, w in (
            (4, 0.0), (2, 3.0), (3, 0.0), (2, 0.0), (4, 3.0), (3, 3.0),
            (2, 8.0))]
        est = trees.GBTClassifier(num_rounds=3)
        monkeypatch.setattr(forms, "_depth_mode", lambda: "blocks")
        (_, blocks), = list(fold_grid._candidate_groups(
            est, grid, masks, None, fold_grid._GBT_TILED,
            fold_grid._GBT_SKEY))
        assert [b.depth for b in blocks] == [2, 3, 4]
        assert [[gi for gi, _ in b.members] for b in blocks] \
            == [[1, 3, 6], [2, 5], [0, 4]]
        assert [b.count for b in blocks] == [9, 6, 6]
        lanes = blocks[0].lanes
        np.testing.assert_array_equal(blocks[0].fidx,
                                      np.repeat(np.arange(3), 3))
        np.testing.assert_array_equal(      # a traced vector, fold-major
            lanes[1 + fold_grid._GBT_TILED.index("min_child_weight")],
            [3.0, 0.0, 8.0] * 3)
        np.testing.assert_array_equal(lanes[0], np.repeat(masks, 3, axis=0))
        mm = fold_grid._gbt_fold_grid(est, X, y, masks, grid, None,
                                      "logistic", eval_ctx=(Xv, yv, spec))
        # each grid point alone: one block of one depth, its own column
        for gi, point in enumerate(grid):
            alone = fold_grid._gbt_fold_grid(est, X, y, masks, [point],
                                             None, "logistic",
                                             eval_ctx=(Xv, yv, spec))
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
        X, y, masks, _, _, _ = _depth_blocks_table()
        grid = [{"max_depth": 3, "gamma": g} for g in (0.0, 0.5, 1.0)]
        est = trees.GBTClassifier(num_rounds=2)
        laid = self._both(monkeypatch, lambda: list(
            fold_grid._candidate_groups(est, grid, masks, None,
                                        fold_grid._GBT_TILED,
                                        fold_grid._GBT_SKEY)))
        for (_, blocks), in laid:
            assert [b.depth for b in blocks] == [3]
            assert fold_grid.tree_depth_blocks(blocks) == {
                "blocks": 1, "lane_levels": 2 * 3 * 3}
        for a, b in zip(laid[0][0][1][0].lanes, laid[1][0][1][0].lanes):
            np.testing.assert_array_equal(a, b)
        fold_grid._gbt_fg_kernel.cache_clear()  # so that each lowering traces
        design, _ = tree_design._design_args(X, est.max_bins)
        lanes = laid[1][0][1][0].lanes          # no fold index: not fused
        args = ((tuple(jax.numpy.asarray(a) for a in lanes),), *design[:4],
                jax.numpy.asarray(y), jax.random.PRNGKey(0))
        level_lanes = []
        hist = grower._level_histograms
        monkeypatch.setattr(grower, "_level_histograms", lambda *a, **kw: (
            level_lanes.append(a[1].aval.shape), hist(*a, **kw))[1])

        def traced(depths):     # the growers (a spy on their routing form)
            level_lanes.clear()
            with pytest.MonkeyPatch.context() as patch:
                growers = spy_on(patch, forms, "_route_form")
                fold_grid._gbt_fg_kernel(
                    (depths, 2, "logistic", "scatter")).lower(
                        *((args[0] * len(depths),) + args[1:]))
            return len(growers), len(level_lanes)
        assert traced((3,)) == (1, 3)       # one grower, three levels
        assert traced((2, 3, 4)) == (1, 4)  # one grower, the deepest's

    def test_blocks_are_padded_one_by_one_on_a_mesh(self, monkeypatch):
        """Under a 4-device ``models`` mesh each block is padded to the
        shard count on its own (every chip its share of every depth) and
        the (F, G) matrix equals the unsharded one."""
        from transmogrifai_tpu.evaluators import \
            BinaryClassificationEvaluator
        from transmogrifai_tpu.parallel import make_mesh
        X, y, masks, Xv, yv, rows = _depth_blocks_table(seed=11, F=3)
        spec = BinaryClassificationEvaluator().device_metric_spec()
        # 3 folds x (3, 1, 2) members = 9, 3 and 6 lanes: pads of 3, 1, 2
        grid = [{"max_depth": dd, "min_instances_per_node": m}
                for dd, m in ((2, 5), (4, 5), (2, 10), (3, 5), (2, 20),
                              (4, 20))]
        est = trees.RandomForestClassifier(num_trees=3)
        mesh = make_mesh({"models": 4})
        monkeypatch.setattr(forms, "_depth_mode", lambda: "blocks")
        (_, blocks), = list(fold_grid._candidate_groups(
            est, grid, masks, mesh, fold_grid._FOREST_TRACED,
            fold_grid._FOREST_STATIC))
        assert [b.depth for b in blocks] == [2, 3, 4]
        assert [b.count for b in blocks] == [9, 3, 6]
        assert [len(b.lanes[0]) for b in blocks] == [12, 4, 8]
        for b in blocks:
            assert all(len(a) == len(b.fidx) for a in b.lanes)
            assert (b.lanes[0][b.count:] == 1.0).all()  # all-ones masks
            assert (b.fidx[b.count:] == 0).all()
        assert fold_grid.tree_depth_blocks(blocks) == {
            "blocks": 3, "lane_levels": 3 * (2 * 3 + 3 + 4 * 2)}
        for ctx in ((Xv, yv, spec), (None, yv, spec, rows)):
            whole = fold_grid._forest_fold_grid(est, X, y, masks, grid,
                                                None, True, eval_ctx=ctx)
            sharded = fold_grid._forest_fold_grid(est, X, y, masks, grid,
                                                  mesh, True, eval_ctx=ctx)
            assert np.isfinite(whole).all()
            np.testing.assert_array_equal(whole, sharded)
        ms = fold_grid._forest_fold_grid(est, X, y, masks, grid, None, True)
        mk = fold_grid._forest_fold_grid(est, X, y, masks, grid, mesh, True)
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
        from transmogrifai_tpu.evaluators.device_metrics import (
            binary_from_votes, vote_probability)
        rng = np.random.default_rng(K)
        trees, depth, nv = 11, 4, 97
        leaves = rng.dirichlet(np.ones(K), size=(trees, 2 ** depth))
        leaf = rng.integers(0, 2 ** depth, size=(trees, nv))
        from_votes = binary_from_votes if K == 2 else vote_probability
        # both under ``jit``, as the kernel runs them: the compiler treats
        # the division by the tree count alike on both sides
        got = jax.jit(lambda le, lf: fold_grid._candidate_scores(
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
        est = trees.RandomForestClassifier(num_trees=5)
        monkeypatch.setattr(forms, "_depth_mode", lambda: "blocks")
        mm = fold_grid._forest_fold_grid(est, X, y, masks, grid, None, True,
                                         eval_ctx=(Xv, yv, spec))
        models = fold_grid._forest_fold_grid(est, X, y, masks, grid, None,
                                             True)
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
        got = jax.jit(lambda yv, fi, sc: fold_grid._lane_metrics(
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
        from transmogrifai_tpu.evaluators import \
            BinaryClassificationEvaluator
        from transmogrifai_tpu.models.registry import default_binary_models
        from transmogrifai_tpu.observability import trace
        _, (rf, grid_rf), (gbt, grid_gbt), _ = default_binary_models()
        X, y, masks, _, yv, rows = _depth_blocks_table(seed=13, n=90, F=3)
        ctx = (None, yv, BinaryClassificationEvaluator().device_metric_spec(),
               rows)
        monkeypatch.setattr(forms, "_depth_mode", lambda: "blocks")
        trace.configure(True)
        try:
            if family == "forest":
                mm = fold_grid._forest_fold_grid(
                    rf.with_params(num_trees=2), X, y, masks, grid_rf, None,
                    True, eval_ctx=ctx)
            else:
                mm = fold_grid._gbt_fold_grid(
                    gbt.with_params(num_rounds=1), X, y, masks, grid_gbt,
                    None, "logistic", eval_ctx=ctx)
            (span,) = [s for s in trace.spans()
                       if s["name"] == "search.fetch"]
        finally:
            trace.configure(False)
            trace.reset()
        assert mm.shape == (3, 18) and np.isfinite(mm).all()
        assert span["attrs"]["depth_blocks"] == 3
        assert span["attrs"]["depth_lane_levels"] == 378


class TestFoldEdges:
    """TX_TREE_EDGES=fold: quantile edges from fold-train rows only
    (VERDICT r4 #6 — the whole-matrix default is a documented
    feature-distribution-only deviation; this mode removes it)."""

    def test_edge_rows_exclude_outliers(self):
        from transmogrifai_tpu.models.trees.design import _PackedDesign
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
        from transmogrifai_tpu.models.trees import (GBTClassifier,
                                                    RandomForestClassifier)
        from transmogrifai_tpu.models.trees.fold_grid import (
            _forest_fold_grid, _gbt_fold_grid)
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
        seen = []
        real = fold_grid.with_frame_room
        monkeypatch.setattr(fold_grid, "with_frame_room",
                            lambda fn: seen.append(1) or real(fn))
        rng = np.random.default_rng(3)
        X = rng.normal(size=(60, 3))
        y = (X[:, 0] > 0).astype(float)
        masks = np.ones((2, 60))
        masks[0, :30] = 0
        masks[1, 30:] = 0
        est = (trees.RandomForestClassifier(num_trees=2, max_depth=2)
               if family == "forest" else
               trees.GBTClassifier(num_rounds=2, max_depth=2))
        models = est.fit_fold_grid_arrays(X, y, masks, [{}])
        assert len(models) == 2 and seen
