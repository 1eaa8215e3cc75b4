"""The level grower of ``models/trees/grower.py``: the histogram modes
(``scatter`` / ``matmul`` / ``matmul_chunk``, with and without subtraction),
the identity slot fast path, and the slots a compressed level carries over
(``_carry_slots``)."""
import numpy as np
import pytest

from transmogrifai_tpu.models import trees
from transmogrifai_tpu.models.trees import forms, grower
from tree_cases import carry_case, grow, route_case, sums_case, spy_on


class TestHistogramModes:
    """scatter / matmul / matmul_chunk histogram paths must produce
    IDENTICAL trees (models/trees/forms.py _hist_mode chooses among them from
    the platform and the indicator's size; the matmul pair rides the MXU on
    TPU). The resolved mode is threaded as a STATIC jit argument — another
    answer of the resolver between fits in one process must retrace, not
    silently reuse the previous mode's program."""

    def test_modes_agree(self, rng, monkeypatch):
        from transmogrifai_tpu.models.trees import (GBTClassifier,
                                                    RandomForestClassifier)
        X = rng.normal(size=(300, 12))
        X[:, 6:] = (X[:, 6:] > 0).astype(float)   # binary block
        y = (X[:, 0] + X[:, 6] > 0.3).astype(float)
        fits = {}
        for mode in ("scatter", "matmul", "matmul_chunk"):
            monkeypatch.setattr(forms, "_hist_mode", lambda n, tb, m=mode: m)
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
        static ``hist_mode``, which only tests pass: models/trees/grower.py
        _grow_tree): identity levels build LEFT-child histograms only and
        derive right = parent - left. On data without exact gain ties the
        trees are identical to the direct build (ties may legitimately
        resolve to a different equal-gain split — the documented
        caveat)."""
        from transmogrifai_tpu.models.trees import (GBTClassifier,
                                                    RandomForestClassifier)
        X = rng.normal(size=(300, 12))
        y = (X[:, 0] * 2 - X[:, 1] > 0.2).astype(float)
        fits = {}
        for mode in ("scatter", "scatter+sub", "matmul", "matmul+sub"):
            monkeypatch.setattr(forms, "_hist_mode", lambda n, tb, m=mode: m)
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
        from transmogrifai_tpu.models.trees.design import _design_args
        from transmogrifai_tpu.models.trees.grower import _level_histograms
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
        traced ``_grow_tree`` of the other form (a spy on the resolver
        ``forms._route_form`` records the form of every traced grower)."""
        X = rng.normal(size=(83, 4))     # a shape no other test fits
        y = (X[:, 0] > 0).astype(float)
        seen = []
        for mode in ("scatter", "matmul"):
            monkeypatch.setattr(forms, "_hist_mode", lambda n, tb, m=mode: m)
            with pytest.MonkeyPatch.context() as patch:
                routes = spy_on(patch, forms, "_route_form")
                trees.GBTClassifier(num_rounds=2, max_depth=2
                                    ).fit_arrays(X, y)
            seen.append(routes)
        assert seen[0] and set(seen[0]) == {"gather"}
        assert seen[1] and set(seen[1]) == {"dense"}

    def test_fold_grid_kernel_modes_agree(self, rng, monkeypatch):
        """The batched fold x grid kernels pin the mode into their
        static key too."""
        from transmogrifai_tpu.models.trees import GBTClassifier
        X = rng.normal(size=(200, 8))
        y = (X[:, 0] > 0).astype(float)
        masks = np.ones((2, 200))
        masks[0, :100] = 0.0
        masks[1, 100:] = 0.0
        grid = [{"max_depth": 3}, {"max_depth": 3, "step_size": 0.3}]
        outs = {}
        for mode in ("scatter", "matmul", "matmul_chunk"):
            monkeypatch.setattr(forms, "_hist_mode", lambda n, tb, m=mode: m)
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
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    with jax.enable_x64(x64):
        assert forms._hist_mode(n, total_bins) == expected


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
        X = rng.normal(size=(300, 10))
        y = (X[:, 0] + 0.3 * X[:, 1] > 0).astype(float)

        def fit():
            if family == "gbt":
                return trees.GBTClassifier(num_rounds=6, max_depth=4
                                           ).fit_arrays(X, y)
            return trees.DecisionTreeClassifier(max_depth=4).fit_arrays(X, y)
        monkeypatch.setattr(forms, "_hist_mode", lambda n, tb: reference)
        ref = fit()
        monkeypatch.setattr(forms, "_hist_mode",
                            lambda n, tb: "matmul_chunk")
        # force many bin blocks: step = max(8, 1000//300) = 8 bins per
        # block -> dozens of blocks over this design's packed bins
        monkeypatch.setattr(forms, "_HIST_CHUNK_ELEMS", 1000)
        chk = fit()
        np.testing.assert_allclose(ref.thrs, chk.thrs, rtol=1e-6)
        np.testing.assert_array_equal(ref.feats, chk.feats)
        np.testing.assert_allclose(ref.leaves, chk.leaves, rtol=1e-5)


class TestIdentitySlotFastPath:
    """The identity fast path (slots = node ids, no rank-compression
    sort) must produce the same tree as the compressed path whenever
    the budget mask cannot bind."""

    def test_identity_matches_compressed(self, binary_data):
        import jax.numpy as jnp
        import jax
        from transmogrifai_tpu.models.trees.design import _PackedDesign
        from transmogrifai_tpu.models.trees.grower import (_gini_gain,
                                                           _grow_tree)
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
        from transmogrifai_tpu.models.trees.design import _PackedDesign
        from transmogrifai_tpu.models.trees.grower import (_gini_gain,
                                                           _grow_tree)
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
        from transmogrifai_tpu.models.trees.design import _PackedDesign
        from transmogrifai_tpu.models.trees.grower import (_grow_tree,
                                                           _xgb_gain)
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


class TestCarriedSlots:
    """A level wider than the slot cap takes its slots from the level before
    (models/trees/grower.py _carry_slots): the ranks of the occupied node ids
    in ascending order, worked out on the 2 * C (slot, side) columns and not
    on the rows. The same integers a sort of the rows' node ids gives."""

    @staticmethod
    def _grow_levels(monkeypatch, args, kwargs, masks, mode):
        """The tree and what every call of ``_carry_slots`` took and gave,
        returned out of the jitted (and vmapped) grower as outputs."""
        import jax
        packed, feat_of, block_start, thr, stats = args
        real = grower._carry_slots
        seen = []

        def spy(slot, node_of_slot, went_right, *rest):
            out = real(slot, node_of_slot, went_right, *rest)
            seen.append((slot, node_of_slot, went_right) + tuple(out))
            return out
        monkeypatch.setattr(grower, "_carry_slots", spy)

        def one(st):
            del seen[:]
            tree = grower._grow_tree(packed, feat_of, block_start, thr, st,
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
        args, kwargs, masks = carry_case(case)
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
                        == grower._SLOT_SENTINEL).all()
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
        args, kwargs, masks, exact = sums_case(case)
        out = grow(args, kwargs, masks, mode)
        for name, a, want in zip(("feat_heap", "thr_heap", "leaf_stats",
                                  "node"), out, parent):
            if name == "leaf_stats" and not exact:
                continue
            a = np.ascontiguousarray(np.asarray(a))
            got = hashlib.sha256(str((a.dtype.str, a.shape)).encode()
                                 + a.tobytes()).hexdigest()[:16]
            assert got == want, f"{case} {mode}: {name}"

    def test_no_sort_and_no_per_row_scatter_at_depth_12(self, monkeypatch):
        """The traced program of a depth-12 tree at the default cap (levels
        8-11 carried) under the accelerator's mode: no ``sort`` anywhere,
        and no scatter with a row's worth of updates. The CPU's mode keeps
        ``segment_sum``, which is what the walk finds there."""
        import jax
        (packed, feat_of, block_start, thr, onehot), kwargs, _ = \
            route_case("identity")
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
            with pytest.MonkeyPatch.context() as patch:
                carried = spy_on(patch, grower, "_carry_slots")
                jaxpr = jax.make_jaxpr(lambda st: grower._grow_tree(
                    packed, feat_of, block_start, thr, st, hist_mode=mode,
                    **kwargs))(onehot).jaxpr
            assert len(carried) == 4            # levels 8-11
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

    def test_compress_levels_ride_on_the_fetch_span(self, monkeypatch):
        """A traced grower calls ``_carry_slots`` once a compressed level (a
        spy counts the calls: none where every level is an identity level,
        four in the compressed case's levels 2-5). The ``search.fetch`` span
        of a fold-grid program carries no count of traces: its attributes
        are the call's own, the ``hist_mode`` it was compiled with among
        them, from which the forms of its levels follow."""
        from transmogrifai_tpu.observability import trace
        args, kwargs, _ = route_case("compressed")     # levels 2-5
        flat, flat_kwargs, _ = route_case("identity")
        carried = spy_on(monkeypatch, grower, "_carry_slots")
        grow(flat, flat_kwargs, None, "matmul")
        assert carried == []
        grow(args, kwargs, None, "matmul")
        assert len(carried) == 4
        rng = np.random.default_rng(33)
        X = rng.normal(size=(90, 3))
        y = (X[:, 0] > 0).astype(float)
        masks = np.ones((2, 90))
        masks[0, ::2] = masks[1, 1::2] = 0.0
        trace.configure(True)
        try:
            trees.GBTClassifier(num_rounds=2, max_depth=3
                                ).fit_fold_grid_arrays(X, y, masks, [{}])
            (span,) = [s for s in trace.spans()
                       if s["name"] == "search.fetch"]
        finally:
            trace.configure(False)
            trace.reset()
        assert span["attrs"]["hist_mode"] == "scatter"      # a CPU's
        assert not [k for k in span["attrs"] if k.startswith("compress")]
