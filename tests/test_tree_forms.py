"""The two forms of a level's routing and of its node sums
(``models/trees/forms.py`` ``_route_form`` and ``_sums_form``): gathers and
``segment_sum`` under the ``scatter`` family, selects over the slot axis
under the ``matmul`` family. The same integers, and the same sums."""
import numpy as np
import pytest

from transmogrifai_tpu.models.trees import forms, grower
from tree_cases import grow, route_case, sums_case, spy_on


class TestRouteForms:
    """The routing step of a level has two forms (models/trees/forms.py
    _route_form): per-row gathers under the ``scatter`` family, selects over
    the slot and the column axis under the ``matmul`` family. They must route every row
    alike: the same integers, not close ones."""

    @pytest.mark.parametrize("case", [
        "identity", "compressed", "denied_levels", "pooled", "wide_bins",
        "vmap_lanes"])
    def test_dense_route_equals_gather_route(self, case, monkeypatch):
        args, kwargs, masks = route_case(case)
        routes = spy_on(monkeypatch, forms, "_route_form")
        gathered = grow(args, kwargs, masks, "scatter")
        dense = grow(args, kwargs, masks, "matmul")
        assert routes == ["gather", "dense"]        # one grower each
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
        assert forms._route_form("scatter", 4) == "gather"
        for base in ("matmul", "matmul_chunk"):
            assert forms._route_form(base, forms._ROUTE_DENSE_MAX_D) \
                == "dense"
            assert forms._route_form(base, forms._ROUTE_DENSE_MAX_D + 1) \
                == "gather"
        # the rule is read while tracing: the same call takes the other form
        (packed, feat_of, block_start, thr, onehot), kwargs, _ = \
            route_case("identity")

        def grow():     # a new jit each time, so each call traces
            return jax.jit(lambda stats: grower._grow_tree(
                packed, feat_of, block_start, thr, stats,
                hist_mode="matmul", **kwargs))(onehot)
        dense = grow()
        monkeypatch.setattr(forms, "_ROUTE_DENSE_MAX_D", 4)
        routes = spy_on(monkeypatch, forms, "_route_form")
        gathered = grow()
        assert routes == ["gather"]
        for a, b in zip(dense, gathered):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestSumForms:
    """The per-slot totals of a level and the per-leaf sums of a tree have
    two forms (models/trees/forms.py _sums_form): ``segment_sum`` under the
    ``scatter`` family, a select over the slot axis reduced over the rows
    (``_slot_sums``) under the ``matmul`` family. The same sums: bit-equal
    where the statistics are whole numbers, summation order apart where
    they are real."""

    @pytest.mark.parametrize("case", [
        "identity", "compressed", "denied_levels", "pooled", "wide_bins",
        "vmap_lanes", "real_stats", "real_compressed"])
    def test_dense_sums_equal_scatter_sums(self, case, monkeypatch):
        """(a spy on ``_slot_sums`` counts the dense sums each traces)"""
        args, kwargs, masks, exact = sums_case(case)
        summed = spy_on(monkeypatch, grower, "_slot_sums")
        scattered = grow(args, kwargs, masks, "scatter")
        assert summed == []
        dense = grow(args, kwargs, masks, "matmul")
        assert summed
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
        rng = np.random.default_rng(5)
        stats = jnp.asarray(rng.integers(0, 9, size=(200, 3)), jnp.float64)
        slot = jnp.asarray(rng.integers(-2, 9, size=200), jnp.int32)
        slot = slot.at[:3].set(grower._SLOT_SENTINEL)
        np.testing.assert_array_equal(
            np.asarray(grower._slot_sums(stats, slot, 6)),
            np.asarray(jax.ops.segment_sum(
                jnp.where(((slot >= 0) & (slot < 6))[:, None], stats, 0),
                jnp.clip(slot, 0, 5), num_segments=6)))

    def test_many_slots_keep_the_scatter(self, monkeypatch):
        import jax
        assert forms._sums_form("scatter", 2) == "scatter"
        for base in ("matmul", "matmul_chunk"):
            assert forms._sums_form(base, forms._SUMS_DENSE_MAX_SLOTS) \
                == "dense"
            assert forms._sums_form(base, forms._SUMS_DENSE_MAX_SLOTS + 1) \
                == "scatter"
        # the default cap's widest sum (two leaves under each of 256 slots)
        # is summed densely, by a wide margin
        assert 2 * grower._DEFAULT_NODE_CAP * 4 <= forms._SUMS_DENSE_MAX_SLOTS
        args, kwargs, _, _ = sums_case("compressed")
        packed, feat_of, block_start, thr, onehot = args

        def adds(mode):     # the scatter-adds of a freshly traced grower
            jaxpr = jax.make_jaxpr(lambda st: grower._grow_tree(
                packed, feat_of, block_start, thr, st, hist_mode=mode,
                **kwargs))(onehot)
            return str(jaxpr).count("scatter-add")
        # ``scatter`` keeps segment_sum (a level's histogram, its totals,
        # the leaves); the matmul family holds no scatter-add at all
        assert adds("scatter") >= 2 * kwargs["depth"] + 1
        assert adds("matmul") == 0
        dense = grow(args, kwargs, None, "matmul")
        # the rule is read while tracing: the same call takes the other
        # form (the widest sum of this tree has 2 * 7 columns)
        monkeypatch.setattr(forms, "_SUMS_DENSE_MAX_SLOTS", 13)
        summed = spy_on(monkeypatch, grower, "_slot_sums")
        # (a level's totals and the leaves, and the row counts of the
        # columns of the four carried levels, 2 to 5: ``_carry_slots``)
        assert adds("matmul") == kwargs["depth"] + 1 + 4
        scattered = grow(args, kwargs, None, "matmul")
        assert summed == []             # neither trace summed densely
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
        from transmogrifai_tpu.parallel import make_mesh
        (packed, feat_of, block_start, thr, onehot), kwargs, _, _ = \
            sums_case(case)
        n = packed.shape[0]
        mesh = make_mesh({"data": 8})
        assert n % 8 == 0

        def shard_fn(pk, st):
            return grower._grow_tree(pk, feat_of, block_start, thr, st,
                                     hist_mode="matmul", axis_name="data",
                                     row_total=n, **kwargs)
        sharded = jax.jit(shard_map(
            shard_fn, mesh=mesh, in_specs=(P("data", None), P("data", None)),
            out_specs=(P(), P(), P(), P("data")), check_vma=False))(
            packed, onehot)
        whole = grow((packed, feat_of, block_start, thr, onehot), kwargs,
                     None, "scatter")
        for name, a, b in zip(("feat_heap", "thr_heap", "leaf_stats",
                               "node"), whole, sharded):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=f"{case}: {name}")
