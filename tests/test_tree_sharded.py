"""Row-sharded (data-parallel) tree training parity.

The promised psum-of-histograms path (models/trees.py module docstring;
SURVEY §2.9 Rabit-allreduce mapping): a fit whose rows are sharded over
the virtual 8-device mesh must reproduce the single-device fit exactly
— same splits, same thresholds, same leaves — because every cross-row
reduction is a psum of the same partial sums and the bootstrap draws
are shard-position-stable (models/trees._row_draw).
"""
import numpy as np
import pytest

from transmogrifai_tpu.models import (GBTClassifier, GBTRegressor,
                                      RandomForestClassifier,
                                      RandomForestRegressor)
from transmogrifai_tpu.parallel import make_mesh


@pytest.fixture(scope="module")
def mesh():
    return make_mesh({"data": 8})


def _data(n=640, d=12, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d))
    yc = ((X[:, 0] + 0.5 * X[:, 1] - X[:, 2] ** 2
           + 0.3 * rng.normal(size=n)) > 0).astype(float)
    yr = X @ rng.normal(size=d) + 0.1 * rng.normal(size=n)
    return X, yc, yr


class TestShardedForestParity:
    def test_rf_classifier_exact_trees(self, mesh):
        X, yc, _ = _data()
        est = RandomForestClassifier(num_trees=10, max_depth=4, seed=3)
        local = est.fit_arrays(X, yc)
        sharded = est.fit_arrays_sharded(X, yc, mesh)
        np.testing.assert_array_equal(sharded.feats, local.feats)
        np.testing.assert_allclose(sharded.thrs, local.thrs)
        np.testing.assert_allclose(sharded.leaves, local.leaves,
                                   atol=1e-12)

    def test_rf_regressor_predictions(self, mesh):
        X, _, yr = _data()
        est = RandomForestRegressor(num_trees=8, max_depth=4, seed=5)
        local = est.fit_arrays(X, yr)
        sharded = est.fit_arrays_sharded(X, yr, mesh)
        np.testing.assert_allclose(
            sharded.predict_values(X), local.predict_values(X),
            atol=1e-9)

    @pytest.mark.parametrize("max_depth", [11, 12])
    def test_rf_deep_tree_compressed_slots(self, mesh, max_depth):
        # depth > 9 carries compressed slots from level to level (the
        # identity fast path stops covering every level past the slot
        # cap): the columns' occupancy is psum-ed over the row shards
        # (trees._carry_slots), so every shard gives a node the same slot
        X, yc, _ = _data(n=960)
        est = RandomForestClassifier(num_trees=3, max_depth=max_depth,
                                     seed=2, min_instances_per_node=1)
        local = est.fit_arrays(X, yc)
        sharded = est.fit_arrays_sharded(X, yc, mesh)
        np.testing.assert_array_equal(sharded.feats, local.feats)
        np.testing.assert_allclose(sharded.leaves, local.leaves,
                                   atol=1e-12)

    def test_rf_unaligned_rows_padded(self, mesh):
        # n not divisible by 8: padded rows carry zero mask; quality
        # (not bit-parity — bootstrap draws shift) must hold
        X, yc, _ = _data(n=637)
        est = RandomForestClassifier(num_trees=8, max_depth=4, seed=3)
        sharded = est.fit_arrays_sharded(X, yc, mesh)
        pred = sharded.predict_arrays(X)
        acc = float(np.mean(pred.data == yc))
        assert acc > 0.85


class TestShardedGBTParity:
    def test_gbt_classifier_exact(self, mesh):
        X, yc, _ = _data()
        est = GBTClassifier(num_rounds=10, max_depth=3, seed=7)
        local = est.fit_arrays(X, yc)
        sharded = est.fit_arrays_sharded(X, yc, mesh)
        np.testing.assert_array_equal(sharded.feats, local.feats)
        np.testing.assert_allclose(sharded.leaves, local.leaves,
                                   atol=1e-9)
        assert sharded.base == pytest.approx(local.base)

    def test_gbt_regressor_predictions(self, mesh):
        X, _, yr = _data()
        est = GBTRegressor(num_rounds=10, max_depth=3, seed=7)
        local = est.fit_arrays(X, yr)
        sharded = est.fit_arrays_sharded(X, yr, mesh)
        np.testing.assert_allclose(
            sharded.predict_values(X), local.predict_values(X),
            atol=1e-8)

    def test_gbt_subsampled_draw_stability(self, mesh):
        # subsample < 1 exercises the global-sliced bernoulli draw
        X, yc, _ = _data()
        est = GBTClassifier(num_rounds=6, max_depth=3, subsample=0.7,
                            seed=11)
        local = est.fit_arrays(X, yc)
        sharded = est.fit_arrays_sharded(X, yc, mesh)
        np.testing.assert_array_equal(sharded.feats, local.feats)
        np.testing.assert_allclose(sharded.leaves, local.leaves,
                                   atol=1e-9)


class TestVmappedTreeBlocks:
    def test_blocks_equal_scan(self, monkeypatch):
        """The vmapped-block forest path (what _tree_block_size gives an
        accelerator) against the lax.scan path a CPU takes; trees must
        be equal (same per-tree keys, independent lanes). The block size
        is read while tracing, so the jitted fit's cache is dropped on
        either side of the switch."""
        from transmogrifai_tpu.models import trees
        X, yc, _ = _data(n=320)
        est = RandomForestClassifier(num_trees=12, max_depth=4, seed=9)
        scan_model = est.fit_arrays(X, yc)
        monkeypatch.setattr(trees, "_tree_block_size",
                            lambda *args: 5)        # 12 trees: a ragged tail
        trees._fit_forest_classifier.clear_cache()
        try:
            block_model = est.fit_arrays(X, yc)
        finally:
            trees._fit_forest_classifier.clear_cache()
        np.testing.assert_array_equal(block_model.feats,
                                      scan_model.feats)
        np.testing.assert_allclose(block_model.leaves,
                                   scan_model.leaves, atol=1e-12)

    @pytest.mark.parametrize("backend, blocks", [("cpu", False),
                                                 ("tpu", True)])
    def test_block_size_follows_the_platform(
            self, monkeypatch, retired_tree_switches, backend, blocks):
        """A CPU scans tree by tree; an accelerator batches as many trees
        as the fixed budget holds. No variable overrides either
        (``retired_tree_switches``)."""
        import jax
        from transmogrifai_tpu.models.trees import _tree_block_size
        monkeypatch.setattr(jax, "default_backend", lambda: backend)
        size = _tree_block_size(1_000, 100, 4, 2, 50, "matmul", False)
        assert (size > 1) is blocks
        if blocks:      # the budget is shared by an enclosing vmap's lanes
            assert _tree_block_size(1_000, 100, 4, 2, 50, "matmul", False,
                                    54) < size
