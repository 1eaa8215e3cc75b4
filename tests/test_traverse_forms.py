"""The walk of finished heaps has two forms (ISSUE 40, models/trees.py
``_traverse_form``): per-row gathers on a CPU, selects over a level's nodes
and over the columns on an accelerator. They must give every row the same
leaf: the same integers, not close ones.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from transmogrifai_tpu.models import (GBTClassifier, RandomForestClassifier,
                                      trees)


def _walk(form, X, feats, thrs, depth, monkeypatch):
    """(T, n) leaves of ``_traverse`` in ``form``, traced anew."""
    monkeypatch.setattr(trees, "_traverse_form", lambda d, depth: form)
    return np.asarray(jax.jit(jax.vmap(
        lambda f, t: trees._traverse(X, f, t, depth)))(feats, thrs))


def _heaps(rng, T, depth, d, unsplit=0.2):
    """Random heaps: thresholds where the rows are, some of them +-inf,
    and unsplit nodes as ``_grow_tree`` leaves them (feature 0, +inf)."""
    size = 2 ** depth - 1
    feats = rng.integers(0, d, (T, size)).astype(np.int32)
    thrs = rng.normal(size=(T, size))
    thrs[rng.random((T, size)) < 0.05] = -np.inf
    thrs[rng.random((T, size)) < 0.05] = np.inf
    dead = rng.random((T, size)) < unsplit
    feats[dead], thrs[dead] = 0, np.inf
    return jnp.asarray(feats), jnp.asarray(thrs)


def _table(rng, n, d, nan=0.05):
    X = rng.normal(size=(n, d))
    X[rng.random((n, d)) < nan] = np.nan
    return X


@pytest.mark.parametrize("trees_", [1, 20])
@pytest.mark.parametrize("depth", [1, 2, 3, 6, 12])
def test_dense_walk_equals_gather_walk(depth, trees_, monkeypatch):
    rng = np.random.default_rng(depth * 100 + trees_)
    n = 333 if depth == 12 else 1001        # no multiple of a block
    X = jnp.asarray(_table(rng, n, 7))
    feats, thrs = _heaps(rng, trees_, depth, 7)
    gathered = _walk("gather", X, feats, thrs, depth, monkeypatch)
    dense = _walk("dense", X, feats, thrs, depth, monkeypatch)
    np.testing.assert_array_equal(gathered, dense)
    assert gathered.shape == (trees_, n)
    assert gathered.min() >= 0 and gathered.max() < 2 ** depth
    if depth > 1:
        assert len(np.unique(gathered)) > 2       # rows went both ways


@pytest.mark.parametrize("case", ["nan_rows", "infinite_values",
                                  "all_unsplit", "float32_rows",
                                  "signed_zeros", "one_row"])
def test_dense_walk_edge_cases(case, monkeypatch):
    rng = np.random.default_rng(40)
    depth, d, n = 4, 5, 64
    X = _table(rng, n, d, nan=0.0)
    feats, thrs = _heaps(rng, 3, depth, d)
    if case == "nan_rows":               # a NaN walks right at every split
        X[::3] = np.nan
    elif case == "infinite_values":
        X[::2, :] = np.inf
        X[1::4, :] = -np.inf
    elif case == "all_unsplit":          # everybody left, NaN right
        feats, thrs = jnp.zeros_like(feats), jnp.full(thrs.shape, jnp.inf)
        X[5] = np.nan
    elif case == "float32_rows":         # the compare in the promoted dtype
        X = X.astype(np.float32)
    elif case == "signed_zeros":
        X[::2] = -0.0
        thrs = jnp.where(jnp.arange(thrs.shape[1]) % 2 == 0, 0.0, -0.0
                         )[None, :] * jnp.ones_like(thrs)
    elif case == "one_row":
        X = X[:1]
    X = jnp.asarray(X)
    gathered = _walk("gather", X, feats, thrs, depth, monkeypatch)
    np.testing.assert_array_equal(
        gathered, _walk("dense", X, feats, thrs, depth, monkeypatch))
    if case == "nan_rows":
        assert (gathered[:, ::3] == 2 ** depth - 1).all()
    if case == "all_unsplit":
        assert (np.delete(gathered, 5, axis=1) == 0).all()
        assert (gathered[:, 5] == 2 ** depth - 1).all()


@pytest.mark.parametrize("side", ["at_the_cap", "above_the_cap"])
def test_resolver_by_width_and_both_forms_equal_there(side, monkeypatch):
    """``d`` on both sides of the width crossover: the resolver answers by
    it on an accelerator, and either form walks the same rows alike."""
    monkeypatch.setattr(trees, "_TRAVERSE_DENSE_MAX_D", 8)
    d = 8 if side == "at_the_cap" else 9
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert trees._traverse_form(d, 6) == (
        "dense" if side == "at_the_cap" else "gather")
    monkeypatch.undo()
    rng = np.random.default_rng(d)
    X = jnp.asarray(_table(rng, 257, d))
    feats, thrs = _heaps(rng, 5, 6, d)
    np.testing.assert_array_equal(
        _walk("gather", X, feats, thrs, 6, monkeypatch),
        _walk("dense", X, feats, thrs, 6, monkeypatch))


@pytest.mark.parametrize("backend, d, depth, form", [
    ("cpu", 200, 6, "gather"),
    ("tpu", 200, 6, "dense"),
    ("tpu", 200, 12, "dense"),
    ("gpu", 200, 6, "dense"),
    ("tpu", trees._TRAVERSE_DENSE_MAX_D, trees._TRAVERSE_DENSE_MAX_DEPTH,
     "dense"),
    ("tpu", trees._TRAVERSE_DENSE_MAX_D + 1, 6, "gather"),
    ("tpu", 200, trees._TRAVERSE_DENSE_MAX_DEPTH + 1, "gather"),
])
def test_resolver_by_backend_and_size(backend, d, depth, form, monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert trees._traverse_form(d, depth) == form


def test_the_suite_walks_by_gather():
    # this suite runs on a CPU: every caller of _traverse gathers here
    assert trees._traverse_form(200, 6) == "gather"


def test_counter_counts_one_traced_walk(monkeypatch):
    rng = np.random.default_rng(5)
    X = jnp.asarray(_table(rng, 50, 4))
    feats, thrs = _heaps(rng, 3, 3, 4)
    for form in ("dense", "gather"):
        monkeypatch.setattr(trees, "_traverse_form", lambda d, dp, f=form: f)
        walk = jax.jit(jax.vmap(lambda f, t: trees._traverse(X, f, t, 3)))
        before = trees.tree_traverse_forms()
        walk(feats, thrs)
        walk(feats, thrs)                  # the same program: no trace
        after = trees.tree_traverse_forms()
        other = "gather" if form == "dense" else "dense"
        assert after[form] == before[form] + 1
        assert after[other] == before[other]
    assert set(trees.tree_traverse_forms()) == {"dense", "gather"}


def _xy(n=240, d=6, seed=11):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d))
    y = ((X[:, 0] + 0.5 * X[:, 2] > 0) ^ (X[:, 4] > 1.2)).astype(np.float64)
    X[rng.random((n, d)) < 0.03] = np.nan
    return X, y


def _in_form(form, monkeypatch, score):
    monkeypatch.setattr(trees, "_traverse_form", lambda d, depth: form)
    trees._predict_leaves.clear_cache()
    try:
        return np.asarray(score())
    finally:
        trees._predict_leaves.clear_cache()


@pytest.mark.parametrize("scorer", ["gbt_predict_raw", "forest_raw_arrays"])
def test_scorers_equal_under_both_forms(scorer, monkeypatch):
    X, y = _xy()
    fit_X = np.nan_to_num(X)
    if scorer == "gbt_predict_raw":
        model = GBTClassifier(num_rounds=3, max_depth=4,
                              max_bins=8).fit_arrays(fit_X, y)

        def score():
            return model.predict_raw(X)
    else:
        model = RandomForestClassifier(num_trees=4, max_depth=4, max_bins=8,
                                       seed=2).fit_arrays(fit_X, y)

        def score():
            return model.raw_arrays(jnp.asarray(X))
    gathered = _in_form("gather", monkeypatch, score)
    dense = _in_form("dense", monkeypatch, score)
    np.testing.assert_array_equal(gathered, dense)
    assert np.isfinite(gathered).all()


def _components(hlo_text):
    found = set()
    for op_name in re.findall(r'op_name="([^"]+)"', hlo_text):
        for part in op_name.split(";"):
            found.update(re.sub(r"^\w+\((.*)\)$", r"\1", c)
                         for c in part.split("/"))
    return found


@pytest.mark.parametrize("form", ["gather", "dense"])
def test_predict_program_keeps_its_name_and_carries_the_scope(
        form, monkeypatch):
    """``tail_traverse_s`` finds the walk as ``tree.traverse`` inside
    ``jit__predict_leaves``; the dense form holds no gather under it."""
    assert "tree.traverse" in trees.SCOPES
    rng = np.random.default_rng(3)
    X = jnp.asarray(_table(rng, 40, 5))
    feats, thrs = _heaps(rng, 2, 3, 5)
    monkeypatch.setattr(trees, "_traverse_form", lambda d, depth: form)
    trees._predict_leaves.clear_cache()
    hlo = trees._predict_leaves.lower(X, feats, thrs, 3).compile().as_text()
    trees._predict_leaves.clear_cache()
    assert re.search(r"HloModule ([\w.\-]+)", hlo).group(1) \
        == "jit__predict_leaves"
    assert "tree.traverse" in _components(hlo)
    gathers = [line for line in hlo.splitlines()
               if re.search(r"= \S+ gather\(", line)
               and "tree.traverse" in line]
    assert bool(gathers) == (form == "gather")
