"""A lane's rows in the lane's own order (``models/trees/grower.py``
``_TreeGrower`` ``hist_rows``): only the leading rows of the design carry
statistics, the level histograms contract them, the node sums add them up,
and the rows behind them (a fold's held-out rows) are routed and nothing
more."""
import numpy as np
import pytest

from transmogrifai_tpu.models import trees
from transmogrifai_tpu.models.trees import grower
from transmogrifai_tpu.models.trees import design as tree_design
from tree_cases import spy_on


def _head_case(stats_kind, depth):
    """A design whose last third of rows is held out, with statistics that
    are multiples of 1/64 (every sum exact in float64 in ANY order, so the
    head form and the all-rows form may differ in nothing): class counts,
    ``[g, h]`` under the XGBoost gain, or ``[w, hi, lo]`` under the variance
    gain. Returns (design args, stats (n, S), head rows, grow kwargs)."""
    import jax.numpy as jnp
    rng = np.random.default_rng(37)
    n, d, h = 720, 8, 480
    X = rng.normal(size=(n, d))
    design = tree_design._PackedDesign(X, max_bins=16)
    args = tuple(jnp.asarray(a) for a in (
        design.packed, design.feat_of, design.block_start,
        design.packed_thr))
    signal = X[:, 0] - 0.6 * X[:, 2] + 0.5 * rng.normal(size=n)
    w = rng.integers(0, 3, size=n).astype(np.float64)  # zero weights inside
    if stats_kind == "counts":
        stats = np.eye(3)[np.digitize(signal, [-0.5, 0.5])] * w[:, None]
        gain = grower._gini_gain(1.0)
    elif stats_kind == "xgb":
        stats = np.stack([np.round(signal * 64) / 64 * w, w], axis=1)
        gain = grower._xgb_gain(1.0, 0.0, 1.0)
    else:
        v = np.round(signal * 64) / 64 * w
        hi = np.round(v * 4) / 4
        stats = np.stack([w, hi, v - hi], axis=1)
        gain = grower._variance_gain(1.0)
    kwargs = dict(depth=depth, gain_fn=gain, min_info_gain=0.0)
    if depth == 6:
        kwargs.update(node_cap=7)       # compressed levels, the budget mask
    return args, stats, h, kwargs


class TestHeadRows:
    @pytest.mark.parametrize("depth", [3, 6, 12])
    @pytest.mark.parametrize("stats_kind", ["counts", "xgb", "variance"])
    @pytest.mark.parametrize("mode", ["matmul", "matmul_chunk", "scatter"])
    def test_held_out_rows_never_reach_the_histograms(self, mode,
                                                      stats_kind, depth,
                                                      monkeypatch):
        """(c) The tree over the head rows alone is the tree over all rows
        with the tail at weight zero: heaps, leaf sums and every row's leaf,
        bit for bit, at depths that cross the slot cap; and what the tail's
        statistics hold is never read: an ``inf`` planted there leaves the
        tree unchanged, where the all-rows form turns it to NaN. (A spy on
        ``_level_histograms`` records the rows each traced level
        contracts.)"""
        import jax
        import jax.numpy as jnp
        args, stats, h, kwargs = _head_case(stats_kind, depth)
        zero_tail = stats.copy()
        zero_tail[h:] = 0.0
        planted = stats.copy()
        planted[h:] = np.inf

        def grow(st, **kw):
            return [np.asarray(a) for a in jax.jit(
                lambda s: grower._grow_tree(*args, s, hist_mode=mode,
                                            **kwargs, **kw))(
                    jnp.asarray(st))]
        rows = spy_on(monkeypatch, grower, "_level_histograms",
                      lambda packed, *rest, **kw: packed.shape[0])
        all_rows = grow(zero_tail)
        assert rows and set(rows) == {720}
        del rows[:]
        head = grow(planted, hist_rows=h)
        assert rows and set(rows) == {h}
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
        (packed, feat_of, block_start, thr), stats, h, _ = _head_case(
            "xgb", 3)
        TB = int(feat_of.shape[0])
        planted = stats.copy()
        planted[h:] = np.inf
        planted = jnp.asarray(planted)
        slot = jnp.asarray(np.random.default_rng(3).integers(0, 4, 720),
                           jnp.int32)
        held = grower._TreeGrower(packed, feat_of, block_start, thr,
                                  jnp.float64, max_depth=3, hist_mode=mode,
                                  hist_rows=h)
        whole = grower._TreeGrower(packed, feat_of, block_start, thr,
                                   jnp.float64, max_depth=3, hist_mode=mode)
        assert whole.head(planted) is planted
        assert held.head(planted).shape == (h, 2)
        if mode == "matmul":
            assert held.bin_oh.shape == (h, TB)
            assert whole.bin_oh.shape == (720, TB)
        else:
            assert held.bin_oh is None
        got = grower._level_histograms(
            held.head(packed), held.head(slot), held.head(planted), 4,
            TB, held.bin_oh, mode=mode, feat_of=feat_of)
        want = grower._level_histograms(
            packed, slot, jnp.asarray(np.where(np.arange(720)[:, None] < h,
                                               stats, 0.0)), 4, TB,
            whole.bin_oh, mode=mode, feat_of=feat_of)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        assert np.isfinite(np.asarray(got)).all()

    @pytest.mark.parametrize("family", ["gbt", "forest", "tree"])
    def test_a_single_fit_contracts_all_rows(self, family, monkeypatch):
        """(d) A single fit has no held-out rows: every level histogram its
        growers trace contracts every row (a spy on ``_level_histograms``
        records the rows), whatever the histogram mode."""
        rng = np.random.default_rng(5)
        X = rng.normal(size=(93, 4))            # shapes of this test alone
        y = (X[:, 0] > 0).astype(float)
        est = {"gbt": trees.GBTClassifier(num_rounds=2, max_depth=2),
               "forest": trees.RandomForestClassifier(num_trees=2,
                                                      max_depth=2),
               "tree": trees.DecisionTreeClassifier(max_depth=2)}[family]
        rows = spy_on(monkeypatch, grower, "_level_histograms",
                      lambda packed, *rest, **kw: packed.shape[0])
        est.fit_arrays(X, y)
        assert rows and set(rows) == {93}
