"""A boosting round adds each row's leaf value to its margin
(models/trees/grower.py ``_TreeGrower.add_leaf_values``) in the form of the
tree's node sums: a per-row gather under the ``scatter`` mode, the CPU
default, and a select over the last level's slots (``_leaf_values_dense``)
under the ``matmul`` family, the accelerator default. The two must give the
same bits: margins, and through them every later round's trees.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from transmogrifai_tpu.models.trees import bodies, grower
from transmogrifai_tpu.models.trees.design import _design_args
from tree_cases import spy_on


def _gathered(vals, state, by_id):
    """The gather, in the place of the dense read."""
    return vals[state.node]


def _table(n=200, d=6, seed=44, classes=2, dtype=np.float64):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d))
    score = X[:, 0] + X[:, 1] * X[:, 2] - 0.5 * X[:, 4]
    if classes == 2:
        y = (score > 0).astype(np.float64)
    else:
        y = np.digitize(score, np.quantile(score, [1 / 3, 2 / 3]))
        y = y.astype(np.float64)
    design, _ = _design_args(X.astype(dtype), 16)
    return design[:4], jnp.asarray(y, design[3].dtype), rng


def _boosted_lanes(objective, depths, lanes, hist_rows=None, dtype=np.float64):
    """``_gbt_body`` over the lanes of depth blocks; with ``hist_rows`` the
    head form, the held-out rows last and at weight zero."""
    design, y, rng = _table(dtype=dtype)
    if objective == "squared":
        y = y + jnp.asarray(rng.normal(size=y.shape[0]), y.dtype)
    L, n = sum(lanes), y.shape[0]
    mask = (rng.random((L, n)) > 0.2).astype(y.dtype)
    if hist_rows is not None:
        mask[:, hist_rows:] = 0.0

    def hp(v):
        return jnp.asarray(np.linspace(v, 1.5 * v, L), y.dtype)
    return jax.jit(lambda: bodies._gbt_body(
        *design, y, jax.random.PRNGKey(3), jnp.asarray(mask), hp(0.3),
        hp(1.0), jnp.zeros(L, y.dtype), hp(0.5), hp(0.8), depth=depths,
        num_rounds=3, objective=objective, hist_mode="matmul",
        lanes=lanes, hist_rows=hist_rows))()


def _fit(depth, gamma=0.0, dtype=np.float64):
    design, y, _ = _table(dtype=dtype)
    bodies._fit_gbt.clear_cache()
    try:
        return bodies._fit_gbt(
            *design, y, jax.random.PRNGKey(5), depth=depth, num_rounds=3,
            step_size=0.3, reg_lambda=1.0, gamma=gamma,
            min_child_weight=0.5, subsample=0.9, objective="logistic",
            hist_mode="matmul")
    finally:
        bodies._fit_gbt.clear_cache()


def _softmax(lanes):
    design, y, rng = _table(classes=3)
    if lanes is None:
        bodies._fit_gbt_softmax.clear_cache()
        try:
            return bodies._fit_gbt_softmax(
                *design, y, jax.random.PRNGKey(7), depth=4, num_rounds=2,
                num_classes=3, step_size=0.3, reg_lambda=1.0, gamma=0.0,
                min_child_weight=0.5, subsample=1.0, hist_mode="matmul")
        finally:
            bodies._fit_gbt_softmax.clear_cache()
    L, n = sum(lanes), y.shape[0]
    mask = (rng.random((L, n)) > 0.2).astype(y.dtype)
    ones = jnp.ones(L, y.dtype)
    return jax.jit(lambda: bodies._gbt_softmax_body(
        *design, y, jax.random.PRNGKey(7), jnp.asarray(mask), 0.3 * ones,
        ones, 0.0 * ones, 0.5 * ones, ones, depth=(2, 5), num_rounds=2,
        num_classes=3, hist_mode="matmul", lanes=lanes))()


CASES = {
    # the fold-grid program's depth blocks in a fold's own order; the
    # depth-12 block's last levels outgrow the 200 rows' slot cap
    "lanes_head_logistic": lambda: _boosted_lanes(
        "logistic", (3, 6, 12), (2, 2, 1), hist_rows=150),
    "lanes_head_squared": lambda: _boosted_lanes(
        "squared", (3, 6, 12), (2, 2, 1), hist_rows=150),
    "lanes_whole_table_float32": lambda: _boosted_lanes(
        "logistic", (3, 6), (2, 2), dtype=np.float32),
    # a slot cap of 8: levels from 3 down are compressed, their slots
    # carried by _carry_slots
    "lanes_compressed": lambda: _boosted_lanes("logistic", (3, 6), (1, 2)),
    "single_fit": lambda: _fit(5),
    "softmax_single": lambda: _softmax(None),
    "softmax_lanes": lambda: _softmax((1, 2)),
    "depth_1": lambda: _fit(1),
    # no split pays for a gamma this large: every row stays left
    "denied_root": lambda: _fit(3, gamma=1e6),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_dense_pick_equals_gather(case, monkeypatch):
    if case == "lanes_compressed":
        monkeypatch.setattr(grower, "_DEFAULT_NODE_CAP", 8)
    picks = spy_on(monkeypatch, grower, "_leaf_values_dense")
    dense = jax.tree_util.tree_leaves(CASES[case]())
    assert picks                        # the case traced the dense read
    monkeypatch.setattr(grower, "_leaf_values_dense", _gathered)
    gathered = jax.tree_util.tree_leaves(CASES[case]())
    assert len(dense) == len(gathered)
    for a, b in zip(dense, gathered):
        a, b = np.atleast_1d(a), np.atleast_1d(b)
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(a.view(np.uint8), b.view(np.uint8),
                                      err_msg=case)
    feats, thrs = np.asarray(dense[0]), np.asarray(dense[1])
    if case == "denied_root":
        assert not feats.any() and np.isinf(thrs).all()
    else:
        assert np.isfinite(thrs).any()          # a tree was grown


@pytest.mark.parametrize("dtype", [np.float32, np.float64])
@pytest.mark.parametrize("level", ["identity", "compressed", "root"])
def test_dense_read_is_the_gather_bit_for_bit(level, dtype):
    """One lane's last level built by hand: node ids of occupied slots (a
    sentinel for an unused one), rows on both sides, and leaf values with
    signed zeros among them."""
    rng = np.random.default_rng(7)
    n, depth = 501, {"identity": 4, "compressed": 7, "root": 0}[level]
    if level == "identity":
        C = 2 ** (depth - 1)
        node_of_slot = np.arange(C, dtype=np.int32)
    elif level == "compressed":
        C = 12
        node_of_slot = np.full(C, grower._SLOT_SENTINEL, np.int32)
        node_of_slot[:9] = np.sort(rng.choice(2 ** (depth - 1), 9,
                                              replace=False))
    else:
        C, node_of_slot = 1, np.zeros(1, np.int32)
    occupied = 9 if level == "compressed" else C
    slot = rng.integers(0, occupied, n).astype(np.int32)
    went_right = (rng.integers(0, 2, n) if depth else np.zeros(n)
                  ).astype(np.int32)
    node = 2 * node_of_slot[slot] + went_right if depth else slot
    vals = rng.normal(size=2 ** depth).astype(dtype)
    vals[::3] = 0.0
    vals[1::5] = -0.0
    if level == "root":
        vals[:] = -0.0
    state = grower._TreeState(
        jnp.asarray(node), jnp.asarray(slot), jnp.asarray(node_of_slot),
        jnp.asarray(occupied), jnp.zeros(0, jnp.int32), jnp.zeros(0, dtype),
        jnp.asarray(went_right), None)
    picked = np.asarray(grower._leaf_values_dense(
        jnp.asarray(vals), state, level == "identity"))
    assert picked.dtype == dtype
    np.testing.assert_array_equal(picked.view(np.uint8),
                                  vals[node].view(np.uint8))
    assert np.signbit(picked).any() and (picked == 0).any()
