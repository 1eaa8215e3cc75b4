"""The default binary pool against plain references (ISSUE 28).

``benchmark/reference/linear_plain.py`` and ``forest_plain.py`` are NumPy
float64 and import nothing of the package; the package's linear families,
its forest and its four fold-grid programs are held to them here at a small
size on the CPU, and the pool's ``Workflow.train()`` is run through the
threaded family dispatch and through the sequential one. The same references
decide ``correct`` in the benchmark cell ``synth100_pool.search``.
"""
import ast
import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:                 # ``benchmark`` is a root package
    sys.path.insert(0, ROOT)

from benchmark.reference.folds_plain import stratified_folds      # noqa: E402
from benchmark.reference.forest_plain import PlainForest          # noqa: E402
from benchmark.reference.linear_plain import (                    # noqa: E402
    PlainLogistic, PlainSVC)
from benchmark.reference.metrics_plain import aupr                # noqa: E402
from transmogrifai_tpu.evaluators import (                        # noqa: E402
    BinaryClassificationEvaluator)
from transmogrifai_tpu.models import (                            # noqa: E402
    GBTClassifier, LinearSVC, LogisticRegression, RandomForestClassifier,
    registry)
from transmogrifai_tpu.observability import trace as package_trace  # noqa: E402,E501
from transmogrifai_tpu.runtime import telemetry                   # noqa: E402
from transmogrifai_tpu.selector import CrossValidation            # noqa: E402


def table(n, numeric=6, binary=14, seed=3):
    """The benchmark's table in small: standard-normal and 15 % binary
    columns, a label that is logistic in the first numeric and the first
    three binary ones."""
    rng = np.random.default_rng(seed)
    x_num = rng.normal(size=(n, numeric))
    x_bin = (rng.uniform(size=(n, binary)) < 0.15).astype(float)
    logit = x_num[:, 0] + x_bin[:, :3].sum(axis=1) - 0.5
    y = (logit + 0.5 * rng.logistic(size=n) > 0).astype(float)
    return np.concatenate([x_num, x_bin], axis=1), y


# ---------------------------------------------------------------------------
# the references import nothing of the package
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["linear_plain", "forest_plain"])
def test_plain_reference_imports_only_numpy_and_references(name):
    path = os.path.join(ROOT, "benchmark", "reference", name + ".py")
    imported = set()
    for node in ast.walk(ast.parse(open(path).read())):
        if isinstance(node, ast.Import):
            imported.update(alias.name for alias in node.names)
        elif isinstance(node, ast.ImportFrom):
            imported.add(node.module)
    assert imported <= {"__future__", "typing", "numpy",
                        "benchmark.reference.gbt_plain"}


# ---------------------------------------------------------------------------
# linear families: coefficients
# ---------------------------------------------------------------------------

#: ``fit_arrays`` runs L-BFGS to a gradient norm of 1e-6 (no L1) or FISTA to
#: a step of 1e-7, at most 250 steps (L1), where the reference converges to
#: 1e-9: on this well-conditioned standardized design both stop within 1e-5
#: of the one minimiser (measured 1e-6), and a wrong ``reg_param`` (x10)
#: moves the largest coefficient by 0.05 and more.
COEF_TOL = 2e-5


@pytest.mark.parametrize("reg,alpha", [(0.01, 0.5), (0.1, 0.0)],
                         ids=["l1", "no-l1"])
def test_logistic_coefficients_match_plain(reg, alpha):
    X, y = table(3000)
    plain = PlainLogistic(reg, alpha).fit(X, y)
    model = LogisticRegression(reg_param=reg, elastic_net_param=alpha,
                               max_iter=50).fit_arrays(X, y)
    assert plain.steps < plain.max_iter
    np.testing.assert_allclose(model.coefficients, plain.coefficients,
                               atol=COEF_TOL)
    assert abs(float(model.intercept) - plain.intercept) < COEF_TOL
    other = PlainLogistic(10 * reg, alpha).fit(X, y)
    assert np.abs(other.coefficients - plain.coefficients).max() > 0.05
    if alpha:                   # the L1 term zeroes what carries no signal
        assert np.sum(plain.coefficients == 0.0) >= 5
        assert np.array_equal(model.coefficients == 0.0,
                              plain.coefficients == 0.0)


@pytest.mark.parametrize("reg", [0.01, 0.2])
def test_svc_coefficients_match_plain(reg):
    X, y = table(3000)
    plain = PlainSVC(reg).fit(X, y)
    model = LinearSVC(reg_param=reg, max_iter=50).fit_arrays(X, y)
    assert plain.steps < plain.max_iter
    np.testing.assert_allclose(model.coefficients, plain.coefficients,
                               atol=COEF_TOL)
    assert abs(model.intercept - plain.intercept) < COEF_TOL
    # the squared hinge is not the logistic loss under another name
    logistic = PlainLogistic(reg, 0.0).fit(X, y)
    assert np.abs(logistic.coefficients - plain.coefficients).max() > 0.05


def test_plain_standardization_leaves_constant_columns_unscaled():
    X, y = table(500)
    X = np.concatenate([X, np.full((500, 1), 3.0)], axis=1)
    mask = (np.arange(500) % 3 != 0).astype(float)
    plain = PlainLogistic(0.01, 0.0).fit(X, y, mask=mask)
    assert plain.coefficients[-1] == 0.0 and np.isfinite(plain.intercept)
    # a masked fit is the fit on the kept rows
    kept = PlainLogistic(0.01, 0.0).fit(X[mask > 0], y[mask > 0])
    np.testing.assert_allclose(plain.coefficients, kept.coefficients,
                               atol=1e-7)


# ---------------------------------------------------------------------------
# forest: one tree split for split, then the default forest in distribution
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("depth,min_instances,min_gain",
                         [(4, 3, 0.001), (11, 1, 0.0)], ids=["4", "11"])
def test_single_tree_matches_plain_split_for_split(depth, min_instances,
                                                   min_gain):
    """Nothing is drawn with ``bootstrap=False`` and every feature at every
    node: the package's tree and the plain one are the same tree. The
    unregularised depth-11 tree fills levels 8 and 9 past the 256-node cap
    of a level, so the cap's rule is part of what agrees."""
    X, y = table(3000)
    params = dict(num_trees=1, max_depth=depth,
                  min_instances_per_node=min_instances,
                  min_info_gain=min_gain)
    est = RandomForestClassifier(**params)
    est.bootstrap = False
    model = est.fit_arrays(X, y)
    plain = PlainForest(bootstrap=False, feature_subset_strategy="all",
                        **params).fit(X, y)
    feats, thrs, shares = plain.trees[0]
    assert np.array_equal(np.concatenate(feats), model.feats[0])
    np.testing.assert_allclose(np.concatenate(thrs), model.thrs[0])
    np.testing.assert_allclose(shares, model.leaves[0], atol=1e-12)
    Xh, _ = table(1000, seed=4)
    np.testing.assert_allclose(plain.votes(Xh), model.predict_raw(Xh),
                               atol=1e-12)
    uncapped = PlainForest(bootstrap=False, feature_subset_strategy="all",
                           node_cap=1 << 20, **params).fit(X, y)
    same = all(np.array_equal(a, b) for a, b in
               zip(uncapped.trees[0][1], thrs))
    assert same == (depth < 9)


def test_default_forest_lanes_inside_plain_seed_spread():
    """The default forest (Poisson bootstrap, pool of 4 sqrt(d) columns,
    sqrt(d) a node) on a 40-column design as the selector sees it, one lane
    a fold through the fold-grid program: each lane's validation AuPR lies
    inside the plain forest's own seed-to-seed range on the same fold,
    widened by half that range (the draws are independent, so equality is
    not to be had; a forest that ignored the mask, the pool or the bootstrap
    would sit outside: the no-bootstrap all-feature forest does)."""
    X, y = table(2400, numeric=8, binary=12, seed=8)
    X = np.concatenate([X, np.zeros_like(X)], axis=1)   # null indicators
    fold_of = stratified_folds(y, 3, 8)
    masks = np.stack([(fold_of >= 0) & (fold_of != f) for f in range(3)]
                     ).astype(float)
    held = [fold_of == f for f in range(3)]
    point = {"max_depth": 6, "min_instances_per_node": 10,
             "min_info_gain": 0.001}
    est = RandomForestClassifier(num_trees=50)
    spec = BinaryClassificationEvaluator().device_metric_spec()
    got = est.eval_fold_grid_arrays(
        X, y, masks, [point], np.stack([X[h] for h in held]),
        np.stack([y[h] for h in held]), spec)
    for fold in range(3):
        plain = [aupr(y[held[fold]], PlainForest(
            num_trees=50, seed=seed, **point).fit(
                X, y, mask=masks[fold]).predict_proba(X[held[fold]]))
            for seed in range(6)]
        room = 0.5 * (max(plain) - min(plain))
        assert min(plain) - room <= got[fold, 0] <= max(plain) + room, \
            (fold, got[fold, 0], plain)
    bare = PlainForest(num_trees=50, bootstrap=False,
                       feature_subset_strategy="all", **point).fit(
                           X, y, mask=masks[0]).predict_proba(X[held[0]])
    assert aupr(y[held[0]], bare) > max(plain) + 0.01 or \
        aupr(y[held[0]], bare) < min(plain) - 0.01


# ---------------------------------------------------------------------------
# the batched path is the sequential path
# ---------------------------------------------------------------------------

#: family -> (estimator, two grid points, AuPR tolerance, why)
FOLD_GRID = {
    "LogisticRegression": (
        LogisticRegression(max_iter=50),
        [{"reg_param": 0.01, "elastic_net_param": 0.5},
         {"reg_param": 0.1, "elastic_net_param": 0.1}], 2e-4,
        "250 fixed FISTA steps against a converged fit: the same minimiser"),
    "LinearSVC": (
        LinearSVC(max_iter=50), [{"reg_param": 0.01}, {"reg_param": 0.2}],
        2e-4, "250 fixed FISTA steps against L-BFGS: the same minimiser"),
    "GBTClassifier": (
        GBTClassifier(num_rounds=5),
        [{"max_depth": 3, "min_child_weight": 1.0, "gamma": 0.001},
         {"max_depth": 4, "min_child_weight": 10.0, "gamma": 0.01}], 1e-3,
        "binary columns bin alike on any rows, so the masked fit on the "
        "whole table is the fit on the fold's rows but for the order of its "
        "sums: rows that tie in one are an ulp apart in the other, and the "
        "AuPR takes a tie as one point (measured 2.7e-4)"),
    "RandomForestClassifier": (
        RandomForestClassifier(num_trees=50),
        [{"max_depth": 3, "min_instances_per_node": 10,
          "min_info_gain": 0.001},
         {"max_depth": 6, "min_instances_per_node": 10,
          "min_info_gain": 0.01}], 0.03,
        "the bootstrap draws over the whole table's rows in the fold-grid "
        "program and over the fold's rows in fit_arrays: the same forest "
        "in distribution only (the plain forest's seeds spread by 0.02)"),
}


@pytest.mark.parametrize("family", sorted(FOLD_GRID))
def test_fold_grid_program_matches_fold_by_fold_fits(family):
    est, grid, tol, _why = FOLD_GRID[family]
    X, y = table(1500, numeric=4, binary=12, seed=5)
    if family == "GBTClassifier":
        X = X[:, 4:]                    # binary columns only: see FOLD_GRID
    evaluator = BinaryClassificationEvaluator()
    cv = CrossValidation(evaluator, num_folds=3, seed=5, stratify=True,
                         mesh=None)
    splits, masks, fold_data, spec, X_val, y_val, _rows = \
        cv._build_fold_arrays(X, y)
    got = est.eval_fold_grid_arrays(X, y, masks, grid, X_val, y_val, spec)
    assert got.shape == (3, len(grid)) and np.isfinite(got).all()
    for f, (X_tr, y_tr, X_va, y_va) in enumerate(fold_data):
        for g, point in enumerate(grid):
            model = est.with_params(**point).fit_arrays(X_tr, y_tr)
            want = evaluator.metric_from(evaluator.evaluate_arrays(
                y_va, model.predict_arrays(X_va)))
            assert abs(got[f, g] - want) <= tol, (family, f, point,
                                                  got[f, g], want)
    # and the program's own fits, taken to the host, score what it scored
    fitted = est.fit_fold_grid_arrays(X, y, masks, grid)
    for f, (_, _, X_va, y_va) in enumerate(fold_data):
        for g in range(len(grid)):
            want = evaluator.metric_from(evaluator.evaluate_arrays(
                y_va, fitted[f][g].predict_arrays(X_va)))
            assert abs(got[f, g] - want) <= 1e-9


# ---------------------------------------------------------------------------
# the pool through Workflow.train(), threaded and in sequence
# ---------------------------------------------------------------------------

def _pool_train(X, y, seed=11):
    """(summary, TRAIN_ZERO counters moved, the ``threaded`` attribute of
    each ``search.dispatch`` span) of one train of the default pool."""
    from benchmark.configs import synth100_pool as cfg
    config = json.load(open(os.path.join(
        ROOT, "benchmark", "configs", "synth100_pool.json")))
    assert cfg.check_pool(config) == []
    workflow, _ = cfg.workflow(config, seed, X.shape[1])
    before = telemetry.counters()
    package_trace.reset()
    package_trace.configure(True)
    try:
        model = workflow.set_input_dataset(cfg.dataset(X, y)).train()
    finally:
        package_trace.configure(False)
    after = telemetry.counters()
    from benchmark.jobs.search import TRAIN_ZERO, _summary
    moved = {k: after.get(k, 0) - before.get(k, 0) for k in TRAIN_ZERO}
    dispatched = [(s["attrs"]["families"], s["attrs"]["threaded"])
                  for s in package_trace.spans()
                  if s["name"] == "search.dispatch"]
    return _summary(model), moved, dispatched


def test_pool_train_threaded_equals_sequential(monkeypatch):
    """The default pool with no ``models`` argument: 48 grid points x 3
    folds through the threaded family dispatch, every family on its device
    path, and the same metric matrix with the families one after another."""
    assert sum(len(grid) for _, grid in registry.default_binary_models()
               ) == 48
    X, y = table(900, numeric=4, binary=6, seed=13)
    # the suite's eight virtual devices would make a search mesh, under which
    # the validator keeps to one dispatch thread; the cell has one chip
    monkeypatch.setenv("TX_SEARCH_MESH", "off")
    monkeypatch.setenv("TX_ASYNC_FAMILIES", "1")
    threaded, moved, dispatched = _pool_train(X, y)
    assert dispatched == [(4, 1)]
    monkeypatch.setenv("TX_ASYNC_FAMILIES", "0")
    sequential, moved_seq, dispatched_seq = _pool_train(X, y)
    assert dispatched_seq == [(4, 0)]
    for summary, counts in ((threaded, moved), (sequential, moved_seq)):
        assert not summary.quarantined
        assert not any(counts.values())
        matrix = [r.metric_values for r in summary.validation_results]
        assert sum(len(row) for row in matrix) == 144
        assert np.isfinite(np.asarray(matrix)).all()
        assert [r.model_name for r in summary.validation_results] == (
            ["LogisticRegression"] * 8 + ["RandomForestClassifier"] * 18
            + ["GBTClassifier"] * 18 + ["LinearSVC"] * 4)
    assert threaded.best_model_name == sequential.best_model_name
    assert threaded.best_model_params == sequential.best_model_params
    for a, b in zip(threaded.validation_results,
                    sequential.validation_results):
        assert (a.model_name, a.grid_index, a.params) == (
            b.model_name, b.grid_index, b.params)
        assert a.metric_values == b.metric_values


# ---------------------------------------------------------------------------
# the cell's limits: what a reference made wrong comes out as
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("control,shown", [
    ({}, False),
    ({"LogisticRegression": {"dtype": "float16", "max_iter": 60}}, True),
    ({"LogisticRegression": {"elastic_net_param": 0.1}}, True)],
    ids=["float64", "float16", "elastic-net"])
def test_winner_coefficients_tell_a_wrong_reference(control, shown):
    """``pool_search.check_readings`` on a refitted logistic winner: correct
    against the float64 reference at the cell's own limit, not correct
    against the same reference in float16 or with another elastic-net."""
    from benchmark.configs import synth100_pool as cfg
    from benchmark.jobs import pool_search
    config = json.load(open(os.path.join(
        ROOT, "benchmark", "configs", "synth100_pool.json")))
    X, y = table(3000)
    params = {"reg_param": 0.01, "elastic_net_param": 0.5}
    model = LogisticRegression(max_iter=50, **params).fit_arrays(
        pool_search.design(X), y)
    got = {"seed": 3, "winner": {
        "family": "LogisticRegression", "params": params,
        "coefficients": np.asarray(model.coefficients).tolist(),
        "intercept": float(model.intercept)}, "lanes": {}}
    problems = pool_search.check_readings(cfg, config, [], got, X, y,
                                          override=control)
    assert bool(problems) == shown, problems


def test_family_overlap_reads_the_programs_union():
    """Two programs side by side on one device for half their time read
    4/3; one after the other 1.0; without the forest's name nothing."""
    from benchmark.layer_metrics import family_overlap as reader
    marker = reader.DeviceTracer.MARKER

    def planes(*modules):
        return [{"name": "/host:CPU", "lines": [
                    {"name": "t", "events": [[marker, 0, 100]]}]},
                {"name": "/device:TPU:0", "lines": [
                    {"name": "XLA Modules", "events": [
                        [name, start, dur] for name, start, dur in modules]}]}]
    assert reader.overlap(planes(("jit_forest_batched(1)", 0, 40),
                                 ("jit_batched(2)", 20, 40))) == 80 / 60
    assert reader.overlap(planes(("jit_forest_batched(1)", 0, 40),
                                 ("jit_linear_batched(3)", 40, 10),
                                 ("jit_other(4)", 0, 90))) == 1.0
    assert reader.overlap(planes(("jit_batched(2)", 0, 40))) is None


# ---------------------------------------------------------------------------
# the benchmark's own check and the new cell's rehearsal
# ---------------------------------------------------------------------------

def test_selfcheck_and_pool_dry_run():
    """``benchmark/selfcheck.py`` on the edited BENCHMARK.json, then the new
    cell's CPU rehearsal to its end (tiny sizes, the four families)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_ENABLE_X64", None)
    check = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "selfcheck.py")],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=600)
    assert check.returncode == 0, check.stdout[-2000:]
    assert "selfcheck: all checks held" in check.stdout
    run = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "synth100_pool.search", "--cpu-dry-run", "tiny",
         "--seed", "2800000003", "--seconds", "1", "--trace", "1"],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=600)
    assert run.returncode == 0, run.stdout[-3000:] + run.stderr[-2000:]
    last = run.stdout.strip().splitlines()[-1]
    line = json.loads(last[last.index("{"):])
    assert line["correct"] is True and line["failed"] == 0
    for name in ("compile_s", "compiles_in_window", "prepare_s_per_train",
                 "selector_s_per_train", "winner_tail_s_per_train",
                 "search_design_s_per_train", "dispatch_threaded"):
        assert name in line["metrics"], name
    assert line["metrics"]["dispatch_threaded"]["value"] == 1.0
