"""The regression selector's default pool as checked device programs
(ISSUE 34): regression trees whose statistics carry the label centred and in
two pieces, the IRLS lanes as the fold-grid program ``jit_glm_batched``, the
plain float64 references of ``msd_reg_pool.search`` and the cell's readers,
all at small sizes on the CPU.
"""
import ast
import copy
import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:                 # ``benchmark`` is a root package
    sys.path.insert(0, ROOT)

import jax                                                  # noqa: E402
import jax.numpy as jnp                                     # noqa: E402

from benchmark.reference.folds_plain import stratified_folds      # noqa: E402
from benchmark.reference.forest_reg_plain import (                # noqa: E402
    PlainForestRegressor)
from benchmark.reference.gbt_reg_plain import PlainGBTRegressor   # noqa: E402
from benchmark.reference.glm_plain import PlainGLM                # noqa: E402
from benchmark.reference.linreg_plain import (                    # noqa: E402
    PlainLinearRegression)
from benchmark.reference.multinomial_plain import to_bfloat16     # noqa: E402
from benchmark.reference.rmse_plain import rmse                   # noqa: E402
from transmogrifai_tpu.models import glm, trees                   # noqa: E402
from transmogrifai_tpu.models.glm import (                        # noqa: E402
    GeneralizedLinearRegression)
from transmogrifai_tpu.models.linear import LinearRegression      # noqa: E402
from transmogrifai_tpu.models.trees import (                      # noqa: E402
    DecisionTreeRegressor, GBTRegressor, RandomForestRegressor)
from transmogrifai_tpu.models.trees import (                      # noqa: E402
    fold_grid, forms, grower)
from transmogrifai_tpu.parallel import cv                         # noqa: E402
from transmogrifai_tpu.runtime import telemetry                   # noqa: E402

SPEC = ("regression", "RootMeanSquaredError")
CONFIG = os.path.join(ROOT, "benchmark", "configs", "msd_reg_pool.json")


def table(n, seed=3, columns=6, year=True):
    """A small table of the cell's shape: real columns on scales from units
    to hundreds, and a label linear in them, a whole year near 1998 +- 11
    or, with ``year=False``, the same signal at 0 +- 1."""
    rng = np.random.default_rng(seed)
    scale = 10.0 ** rng.uniform(0, 2.5, size=columns)
    X = rng.normal(size=(n, columns)) * scale + rng.normal(size=columns)
    signal = (X / scale) @ rng.normal(size=columns)
    z = (signal + 1.5 * rng.normal(size=n)) / np.sqrt(columns / 2 + 2.25)
    return X, (np.round(1998.4 + 11.0 * z) if year else z)


def folds(n, seed=8, k=3):
    fold_of = stratified_folds(np.zeros(n, np.int64), k, seed)
    masks = np.stack([(fold_of >= 0) & (fold_of != f) for f in range(k)]
                     ).astype(float)
    return masks, [fold_of == f for f in range(k)]


def stacked(X, y, held):
    return (np.stack([X[h] for h in held]), np.stack([y[h] for h in held]),
            np.stack([np.nonzero(h)[0] for h in held]).astype(np.int32))


# ---------------------------------------------------------------------------
# the references import nothing of the package
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", [
    "tree_reg_plain", "forest_reg_plain", "gbt_reg_plain", "linreg_plain",
    "glm_plain", "rmse_plain"])
def test_plain_reference_imports_only_numpy(name):
    path = os.path.join(ROOT, "benchmark", "reference", name + ".py")
    imported = set()
    for node in ast.walk(ast.parse(open(path).read())):
        if isinstance(node, ast.Import):
            imported.update(alias.name for alias in node.names)
        elif isinstance(node, ast.ImportFrom):
            imported.add(node.module)
    siblings = {m for m in imported if m.startswith("benchmark.reference.")}
    assert imported - siblings <= {"__future__", "typing", "numpy"}


def test_rmse_on_a_case_worked_by_hand():
    assert rmse([1.0, 2.0, 3.0], [1.0, 4.0, 7.0]) == pytest.approx(
        np.sqrt(20.0 / 3.0))


def test_folds_without_strata_are_the_validators():
    """``folds_plain`` over one class is the validator's unstratified rule:
    equal folds whenever 3 divides the rows, whatever the seed."""
    from transmogrifai_tpu.evaluators import RegressionEvaluator
    from transmogrifai_tpu.selector.validator import CrossValidation
    y = np.arange(300.0)
    assign = CrossValidation(RegressionEvaluator(), num_folds=3, seed=11
                             )._assignments(y, 3)
    np.testing.assert_array_equal(
        assign, stratified_folds(np.zeros(300, np.int64), 3, 11))
    assert np.bincount(assign).tolist() == [100, 100, 100]


# ---------------------------------------------------------------------------
# regression trees: the statistics, one tree split for split, the forest
# ---------------------------------------------------------------------------

def test_variance_statistics_are_two_exact_pieces():
    """``hi + lo`` is ``w * yc`` exactly, ``hi`` holds 8 significant bits
    (what one bf16 pass keeps) and ``lo`` is small beside it; the gain of
    ``_variance_gain`` over them is MLlib's variance gain."""
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.poisson(1.0, 500).astype(np.float32))
    yc = jnp.asarray((11.0 * rng.normal(size=500)).astype(np.float32))
    stats = np.asarray(grower._variance_stats(w, yc))
    v = np.asarray(w * yc)
    np.testing.assert_array_equal(stats[:, 1] + stats[:, 2], v)
    np.testing.assert_array_equal(stats[:, 1], to_bfloat16(stats[:, 1]))
    assert np.all(np.abs(stats[:, 2]) <= np.abs(v) * 2.0 ** -8 + 1e-30)
    left = rng.uniform(size=500) < 0.4
    y = np.asarray(yc, np.float64) + 1998.0
    s = np.stack([np.asarray(w, np.float64), np.asarray(w) * y,
                  np.asarray(w) * y * y], axis=1)

    def sse(t):
        return t[2] - t[1] ** 2 / t[0]
    want = (sse(s.sum(0)) - sse(s[left].sum(0)) - sse(s[~left].sum(0))
            ) / s[:, 0].sum()
    sums = [jnp.asarray(stats[m].sum(0, dtype=np.float64))
            for m in (left, ~left, np.ones(500, bool))]
    got = grower._variance_gain(1.0)(*sums)
    assert float(got) == pytest.approx(want, rel=1e-6)


@pytest.mark.parametrize("year", [True, False], ids=["1998+-11", "0+-1"])
def test_regression_tree_matches_plain_split_for_split(year):
    """One regression tree (``num_trees=1``, no bootstrap) against
    ``tree_reg_plain`` at depth 11, past the 256-node cap, on a label near
    1998 and on the same signal near 0: the package centres the label and the
    plain tree does not, so agreement on both is shift invariance."""
    X, y = table(9000, seed=5, year=year)
    kwargs = dict(max_depth=11, min_instances_per_node=8, min_info_gain=0.0)
    model = DecisionTreeRegressor(**kwargs).fit_arrays(X, y)
    plain = PlainForestRegressor(num_trees=1, bootstrap=False, **kwargs
                                 ).fit(X, y)
    feats, thrs, values = plain.trees[0]
    assert max(np.isfinite(t).sum() for t in thrs) > 128   # the cap binds
    np.testing.assert_array_equal(np.asarray(model.feats)[0],
                                  np.concatenate(feats))
    np.testing.assert_allclose(np.asarray(model.thrs)[0],
                               np.concatenate(thrs), rtol=1e-12)
    np.testing.assert_allclose(model.predict_values(X), plain.predict(X),
                               rtol=1e-10, atol=1e-10)


def test_regression_tree_under_the_accelerators_histogram(monkeypatch):
    """The ``matmul`` histogram mode (the chip's) grows the plain tree too:
    on a CPU its contraction adds in float64, so what is checked is the
    layout of the statistics, not the bf16 pass."""
    monkeypatch.setattr(forms, "_hist_mode", lambda n, tb: "matmul")
    X, y = table(1500, seed=6)
    kwargs = dict(max_depth=6, min_instances_per_node=10, min_info_gain=0.001)
    model = DecisionTreeRegressor(**kwargs).fit_arrays(X, y)
    plain = PlainForestRegressor(num_trees=1, bootstrap=False, **kwargs
                                 ).fit(X, y)
    np.testing.assert_array_equal(np.asarray(model.feats)[0],
                                  np.concatenate(plain.trees[0][0]))
    np.testing.assert_allclose(model.predict_values(X), plain.predict(X),
                               rtol=1e-9)


def test_bfloat16_statistics_break_the_plain_tree():
    """The fault this PR repairs, shown on the reference: the uncentred
    ``[w, wy, wyy]`` rounded to bfloat16 before the histograms (what the
    chip's contraction did to them) splits elsewhere and predicts worse."""
    X, y = table(6000, seed=7)
    kwargs = dict(num_trees=1, bootstrap=False, max_depth=6,
                  min_instances_per_node=10, min_info_gain=0.001)
    plain = PlainForestRegressor(**kwargs).fit(X, y)
    rounded = PlainForestRegressor(round_stats="bfloat16", **kwargs
                                   ).fit(X, y)
    same = np.mean(np.concatenate(plain.trees[0][0])
                   == np.concatenate(rounded.trees[0][0]))
    assert same < 0.9
    assert rmse(y, rounded.predict(X)) > rmse(y, plain.predict(X)) + 0.05


@pytest.fixture(scope="module")
def tree_lanes():
    X, y = table(1800, seed=9)
    masks, held = folds(len(y))
    Xv, yv, rows = stacked(X, y, held)
    return dict(X=X, y=y, masks=masks, held=held, Xv=Xv, yv=yv, rows=rows)


def test_forest_lanes_inside_plain_seed_spread(tree_lanes):
    """The forest lanes' RMSE (in-fit form and traverse form alike) lies
    inside the spread of plain forests drawn from other seeds."""
    t = tree_lanes
    point = {"max_depth": 5, "min_instances_per_node": 10,
             "min_info_gain": 0.001}
    est = RandomForestRegressor(num_trees=30)
    in_fit = est.eval_fold_grid_arrays(
        t["X"], t["y"], t["masks"], [point], None, t["yv"], SPEC,
        val_rows=t["rows"])
    walked = est.eval_fold_grid_arrays(
        t["X"], t["y"], t["masks"], [point], t["Xv"], t["yv"], SPEC)
    np.testing.assert_allclose(in_fit, walked, rtol=1e-9)
    for fold in range(3):
        plain = [rmse(t["y"][t["held"][fold]], PlainForestRegressor(
            num_trees=30, seed=s, **point).fit(
                t["X"], t["y"], mask=t["masks"][fold]).predict(
                    t["X"][t["held"][fold]])) for s in range(4)]
        assert abs(in_fit[fold, 0] - np.mean(plain)) \
            <= 4 * np.std(plain) + 0.02 * np.mean(plain)


def test_boosted_lane_is_the_plain_boosted_fit(tree_lanes):
    t = tree_lanes
    point = {"max_depth": 4, "min_child_weight": 10.0, "gamma": 0.01}
    est = GBTRegressor(num_rounds=6)
    got = est.eval_fold_grid_arrays(
        t["X"], t["y"], t["masks"], [point], None, t["yv"], SPEC,
        val_rows=t["rows"])
    for fold in range(3):
        plain = PlainGBTRegressor(num_rounds=6, max_bins=32, **point).fit(
            t["X"], t["y"], mask=t["masks"][fold])
        assert got[fold, 0] == pytest.approx(rmse(
            t["y"][t["held"][fold]], plain.predict(t["X"][t["held"][fold]])),
            rel=1e-9)


# ---------------------------------------------------------------------------
# the linear lanes: every (fold, grid point) against fit_arrays and the plain
# ---------------------------------------------------------------------------

GLM_GRID = [{"family": f, "reg_param": r}
            for f in ("gaussian", "poisson") for r in (0.001, 0.1)]
LIN_GRID = [{"reg_param": r, "elastic_net_param": e}
            for r in (0.001, 0.1) for e in (0.1, 0.5)]


@pytest.fixture(scope="module")
def linear_lanes():
    X, y = table(1500, seed=4)
    masks, held = folds(len(y))
    Xv, yv, rows = stacked(X, y, held)
    est = GeneralizedLinearRegression()
    lin = LinearRegression(max_iter=50)
    return dict(
        X=X, y=y, masks=masks, held=held, Xv=Xv, yv=yv, rows=rows,
        glm=est, lin=lin,
        glm_matrix=est.eval_fold_grid_arrays(X, y, masks, GLM_GRID, Xv, yv,
                                             SPEC, val_rows=rows),
        glm_fitted=est.fit_fold_grid_arrays(X, y, masks, GLM_GRID),
        lin_matrix=lin.eval_fold_grid_arrays(X, y, masks, LIN_GRID, Xv, yv,
                                             SPEC),
        lin_fitted=lin.fit_fold_grid_arrays(X, y, masks, LIN_GRID))


@pytest.mark.parametrize("fold", range(3))
@pytest.mark.parametrize("point", range(len(GLM_GRID)))
def test_glm_lane_is_the_fold_by_fold_fit_and_the_plain_irls(
        linear_lanes, fold, point):
    t = linear_lanes
    train, held = t["masks"][fold] > 0, t["held"][fold]
    lane = t["glm_fitted"][fold][point]
    alone = t["glm"].with_params(**GLM_GRID[point]).fit_arrays(
        t["X"][train], t["y"][train])
    np.testing.assert_allclose(lane.coefficients, alone.coefficients,
                               rtol=1e-7, atol=1e-10)
    plain = PlainGLM(**GLM_GRID[point]).fit(t["X"], t["y"],
                                            mask=t["masks"][fold])
    np.testing.assert_allclose(lane.coefficients, plain.coefficients,
                               rtol=2e-5, atol=1e-8)
    assert lane.intercept == pytest.approx(plain.intercept, rel=1e-6)
    assert t["glm_matrix"][fold, point] == pytest.approx(rmse(
        t["y"][held], plain.predict(t["X"][held])), rel=1e-6)


def test_glm_penalty_sits_on_the_standardized_coefficients(linear_lanes):
    """The departure of PR 34, against both readings of the reference: with
    ``standardize=False`` (the package as found, MLlib's IRLS families) the
    same ``reg_param`` gives other coefficients on columns of unequal
    scale."""
    t = linear_lanes
    got = GeneralizedLinearRegression(reg_param=0.1).fit_arrays(t["X"],
                                                                t["y"])
    now = PlainGLM("gaussian", 0.1).fit(t["X"], t["y"])
    found = PlainGLM("gaussian", 0.1, standardize=False).fit(t["X"], t["y"])
    np.testing.assert_allclose(got.coefficients, now.coefficients, rtol=1e-7)
    assert np.max(np.abs(found.coefficients - now.coefficients)
                  * now.sigma) > 1e-3


def test_glm_traverse_form_and_cand_idx(linear_lanes):
    t = linear_lanes
    walked = t["glm"].eval_fold_grid_arrays(
        t["X"], t["y"], t["masks"], GLM_GRID, t["Xv"], t["yv"], SPEC)
    np.testing.assert_allclose(walked, t["glm_matrix"], rtol=1e-10)
    subset = t["glm"].eval_fold_grid_arrays(
        t["X"], t["y"], t["masks"], GLM_GRID, t["Xv"], t["yv"], SPEC,
        cand_idx=np.asarray([3, 0]), val_rows=t["rows"])
    np.testing.assert_allclose(subset, t["glm_matrix"][:, [3, 0]],
                               rtol=1e-10)


@pytest.mark.parametrize("fold", range(3))
@pytest.mark.parametrize("point", range(len(LIN_GRID)))
def test_squared_lane_is_the_fold_by_fold_fit_and_the_plain_minimiser(
        linear_lanes, fold, point):
    t = linear_lanes
    train, held = t["masks"][fold] > 0, t["held"][fold]
    lane = t["lin_fitted"][fold][point]
    alone = t["lin"].with_params(**LIN_GRID[point]).fit_arrays(
        t["X"][train], t["y"][train])
    np.testing.assert_allclose(lane.coefficients, alone.coefficients,
                               rtol=1e-4, atol=1e-7)
    plain = PlainLinearRegression(**LIN_GRID[point]).fit(
        t["X"], t["y"], mask=t["masks"][fold])
    np.testing.assert_allclose(lane.coefficients * plain.sigma,
                               plain.coefficients * plain.sigma, atol=1e-5)
    assert t["lin_matrix"][fold, point] == pytest.approx(rmse(
        t["y"][held], plain.predict(t["X"][held])), rel=1e-6)


def test_squared_refit_takes_the_plain_schedule(linear_lanes):
    t = linear_lanes
    params = {"reg_param": 0.01, "elastic_net_param": 0.5}
    got = t["lin"].with_params(**params).fit_arrays(t["X"], t["y"])
    plain = PlainLinearRegression(
        schedule={"steps": 250, "stop": 1e-7}, **params).fit(t["X"], t["y"])
    assert plain.steps < 250
    np.testing.assert_allclose(got.coefficients, plain.coefficients,
                               rtol=1e-8, atol=1e-11)
    assert got.intercept == pytest.approx(plain.intercept, rel=1e-10)


# ---------------------------------------------------------------------------
# the IRLS program's name, scopes and span, locally and on a mesh
# ---------------------------------------------------------------------------

def _glm_args(t, lanes):
    reps = lanes // 3
    return (jnp.asarray(np.repeat(t["masks"], reps, axis=0)),
            jnp.full(lanes, 0.01), jnp.full(lanes, 1.5),
            jnp.asarray(np.repeat(np.arange(3), reps)), jnp.asarray(t["X"]),
            jnp.asarray(t["y"]), jnp.asarray(t["rows"]),
            jnp.asarray(t["yv"]), jnp.asarray(1e-6))


@pytest.mark.parametrize("devices", [None, 4], ids=["local", "mesh4"])
def test_glm_program_has_a_name_and_scopes(linear_lanes, devices):
    mesh = None if devices is None else cv.models_mesh(
        jax.devices()[:devices])
    statics = ("poisson", "log", 25, True)
    kernel = glm._glm_eval_kernel(statics, SPEC, mesh, True)
    text = kernel.lower(*_glm_args(linear_lanes, 12)).as_text(
        debug_info=True)
    assert "jit_glm_batched" in text
    for scope in ("fg.glm", "glm.gram", "glm.solve", "fg.metric",
                  "lin.standardize"):
        assert scope in text, scope
    assert "jit_batched" not in text.replace("jit_glm_batched", "")
    assert glm._glm_fit_kernel(statics, mesh).__name__ == "glm_batched"
    assert {"fg.glm", "glm.gram", "glm.solve"} <= set(trees.SCOPES)


def test_glm_mesh_kernel_is_the_local_kernel(linear_lanes):
    t = linear_lanes
    mesh = cv.models_mesh(jax.devices()[:4])
    sharded = t["glm"].eval_fold_grid_arrays(
        t["X"], t["y"], t["masks"], GLM_GRID, None, t["yv"], SPEC,
        mesh=mesh, val_rows=t["rows"])
    np.testing.assert_allclose(sharded, t["glm_matrix"], rtol=1e-12)


def test_only_the_boosted_program_is_named_jit_batched():
    """Of the pool's four families' fold-grid kernels, the boosted one alone
    bears the name every ``search_*_s`` / ``pool_gbt_s`` reader sums."""
    names = {
        "gbt": fold_grid._gbt_eval_kernel(
            ((3,), 2, "squared", "scatter"), SPEC, None, True).__name__,
        "forest": fold_grid._forest_eval_kernel(
            ("reg", (3,), 0, 2, None, None, "", True, "scatter"), SPEC, None,
            True).__name__,
        "linear": cv._local_eval_kernel(cv._kernel_cfg(
            "squared", True, True, True, 50, None), SPEC).__name__,
        "glm": glm._glm_eval_kernel(("gaussian", "identity", 25, True),
                                    SPEC, None, True).__name__}
    assert names == {"gbt": "batched", "forest": "forest_batched",
                     "linear": "linear_batched", "glm": "glm_batched"}


def test_fetch_span_carries_the_irls_iterations(linear_lanes):
    from transmogrifai_tpu.observability import trace as package_trace
    t = linear_lanes
    package_trace.configure(True)
    package_trace.reset()
    try:
        t["glm"].eval_fold_grid_arrays(
            t["X"], t["y"], t["masks"], GLM_GRID, None, t["yv"], SPEC,
            val_rows=t["rows"])
        spans = [s for s in package_trace.spans()
                 if s["name"] == "search.fetch"]
    finally:
        package_trace.configure(False)
        package_trace.reset()
    by_family = {s["attrs"]["family"]: s["attrs"] for s in spans}
    assert set(by_family) == {"gaussian", "poisson"}
    assert by_family["gaussian"]["lanes"] == 6
    assert by_family["gaussian"]["irls_iterations"] == 1
    assert 2 <= by_family["poisson"]["irls_iterations"] <= 25


# ---------------------------------------------------------------------------
# the default pool through Workflow.train()
# ---------------------------------------------------------------------------

def small_config(columns=5):
    """The cell's configuration cut to its first ``columns`` columns (the
    label's terms on the others dropped): the same generator and workflow at
    a size the CPU trains the whole default pool on."""
    config = copy.deepcopy(json.load(open(CONFIG)))
    for key in ("at", "scale", "draw"):
        config["columns"][key] = config["columns"][key][:columns]
    config["label"]["terms"] = [term for term in config["label"]["terms"]
                                if term["column"] < columns]
    return config


@pytest.fixture(scope="module")
def pool_train():
    """One ``Workflow.train()`` of the whole default pool (no ``models``
    argument) on a small table of the cell's generator, spans on."""
    from benchmark.configs import msd_reg_pool as cfg
    from transmogrifai_tpu.observability import trace as package_trace
    from transmogrifai_tpu.selector import SelectedModel
    from transmogrifai_tpu.utils.uid import reset as reset_uids
    reset_uids(deterministic=True)
    config = small_config()
    X, y, _ = (np.asarray(a) for a in cfg.make_table(config, 5, 540))
    telemetry.reset()
    package_trace.configure(True)
    try:
        workflow, prediction = cfg.workflow(config, 5, X.shape[1])
        model = workflow.set_input_dataset(cfg.dataset(X, y)).train()
        spans = package_trace.spans()
    finally:
        package_trace.configure(False)
        package_trace.reset()       # leave the ring as later tests expect it
    summary = next(s.summary for s in model.stages()
                   if isinstance(s, SelectedModel) and s.summary is not None)
    return dict(summary=summary, counters=telemetry.counters(), spans=spans,
                model=model, X=X, y=y, config=config)


@pytest.mark.parametrize("family,points", [
    ("LinearRegression", 8), ("RandomForestRegressor", 18),
    ("GBTRegressor", 18), ("GeneralizedLinearRegression", 6)])
def test_default_pool_family_runs_as_a_device_program(pool_train, family,
                                                      points):
    results = [r for r in pool_train["summary"].validation_results
               if r.model_name == family]
    assert len(results) == points
    assert all(len(r.metric_values) == 3
               and np.isfinite(r.metric_values).all() for r in results)
    journal = [s for s in pool_train["spans"] if s["name"] == "search.family"
               and s["attrs"].get("family") == family]
    assert journal and all(s["attrs"].get("path") != "host" for s in journal)


def test_default_pool_picks_the_smallest_rmse(pool_train):
    summary, counters = pool_train["summary"], pool_train["counters"]
    assert sum(len(r.metric_values) for r in summary.validation_results) \
        == 150
    assert counters["host_path_families"] == 0
    assert not summary.quarantined
    assert summary.evaluation_metric == "RootMeanSquaredError"
    means = [float(np.mean(r.metric_values))
             for r in summary.validation_results]
    assert summary.best_validation_metric == pytest.approx(min(means))
    best = summary.validation_results[int(np.argmin(means))]
    assert (summary.best_model_name, dict(summary.best_model_params)) \
        == (best.model_name, dict(best.params))
    assert summary.best_model_name in ("LinearRegression",
                                       "GeneralizedLinearRegression")
    irls = [s["attrs"]["irls_iterations"] for s in pool_train["spans"]
            if s["name"] == "search.fetch"
            and "irls_iterations" in s["attrs"]]
    assert len(irls) == 2 and all(1 <= k <= 25 for k in irls)


def test_pool_check_and_package_check_agree_with_the_package():
    from benchmark.configs import msd_reg_pool as cfg
    from benchmark.jobs import reg_pool_search as job
    config = json.load(open(CONFIG))
    assert cfg.check_pool(config) == []
    assert job.package_lacks() == []
    shapes = cfg.lane_shapes(config, 98304)
    assert {k: len(v) for k, v in shapes.items()} == {
        "LinearRegression": 24, "RandomForestRegressor": 54,
        "GBTRegressor": 54, "GeneralizedLinearRegression": 18}
    assert shapes["RandomForestRegressor"][0]["pooled_bins"] == 3060
    drifted = copy.deepcopy(config)
    drifted["selector"]["families"][3]["grid"]["reg_param"] = [0.5]
    assert cfg.check_pool(drifted)


def test_job_refuses_a_package_without_the_scopes(monkeypatch):
    from benchmark.jobs import reg_pool_search as job
    monkeypatch.setattr(trees, "SCOPES", tuple(
        s for s in trees.SCOPES if not s.startswith("glm.")))
    assert len(job.package_lacks()) == 1
    monkeypatch.delattr(trees, "_variance_stats")
    assert len(job.package_lacks()) == 2


# ---------------------------------------------------------------------------
# the cell's limits: what a reference made wrong comes out as
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("control,shown", [
    ({}, False),
    ({"LinearRegression": {"dtype": "bfloat16"},
      "GeneralizedLinearRegression": {"dtype": "bfloat16"}}, True),
    ({"LinearRegression": {"elastic_net_param": 0.1},
      "GeneralizedLinearRegression": {"reg_param": 0.5}}, True),
    ({"fold_seed": 4}, True)],
    ids=["float64", "bfloat16", "other-penalty", "other-folds"])
def test_check_readings_tells_a_wrong_reference(pool_train, control, shown):
    """``reg_pool_search.check_readings`` on the trained pool's readings:
    correct against the float64 references at the cell's own coefficient
    limit, not correct against the same references in bfloat16 or with
    another penalty, nor on folds drawn from another seed."""
    from benchmark.configs import msd_reg_pool as cfg
    from benchmark.jobs import reg_pool_search as job
    config = pool_train["config"]
    got = job.readings(pool_train["model"], 5, 540)
    assert got["winner"]["family"] in job.LINEAR
    check = [["LinearRegression", 3, 0, 0.001],
             ["GeneralizedLinearRegression", 1, 1, 0.001],
             ["GeneralizedLinearRegression", 4, 2, 0.001],
             ["GBTRegressor", 6, 2, 0.001],
             ["RandomForestRegressor", 6, 1, [0.25, 0.25]]]
    problems = job.check_readings(cfg, config, check, got, pool_train["X"],
                                  pool_train["y"], override=control)
    assert bool(problems) == shown, problems


def test_forest_control_rounds_the_statistics():
    """The control ``round_stats: bfloat16`` reaches the plain forest through
    the job's ``_plain_lane``: the lane as a forest with bfloat16-rounded
    histogram statistics scores it is worse than the float64 forests of two
    seeds (by little at this size: the chip's table shows it in full,
    ``benchmark/controls_reg.py``)."""
    from benchmark.configs import msd_reg_pool as cfg
    from benchmark.jobs import reg_pool_search as job
    config = small_config()
    config["reference"]["forest_reference_seeds"] = 2
    family = next(f for f in cfg.families(config)
                  if f["class"] == "RandomForestRegressor")
    family = dict(family, params=dict(family["params"], num_trees=12))
    X, y, _ = (np.asarray(a) for a in cfg.make_table(config, 5, 4500))
    masks, held = folds(len(y), seed=5)
    point = cfg.grid(family)[6]
    sound, other, rounded = (job._plain_lane(
        config, family, point, X, y, masks[1], held[1], seed, override)
        for seed, override in ((5, None), (50, None), (5, {
            "RandomForestRegressor": {"round_stats": "bfloat16"}})))
    assert rounded > max(sound, other) and rounded - sound > 0.03


# ---------------------------------------------------------------------------
# the new readers and costs, on observations made by hand
# ---------------------------------------------------------------------------

def observations(**more):
    lanes = {"GeneralizedLinearRegression": [
        {"rows": 65536, "columns": 180, "family": f, "max_iter": 25}
        for f in ("gaussian", "poisson") for _ in range(9)],
        "RandomForestRegressor": [
            {"rows": 65536, "depth": 6, "pooled_bins": 3060, "trees": 50,
             "classes": 3}] * 3,
        "LinearRegression": [{"rows": 65536, "columns": 180,
                              "steps": 250}] * 24}
    obs = {"reps": [{"ok": True, "traced": False, "host_path_families": 0},
                    {"ok": True, "traced": True, "host_path_families": 0}],
           "device_kind": "TPU v5 lite", "matrix_rows": 98304,
           "pool_lane_shapes": lanes,
           "glm_calls": [{"family": "gaussian", "irls_iterations": 1},
                         {"family": "poisson", "irls_iterations": 25},
                         {"family": "poisson", "irls_iterations": 24}],
           "trace": {"devices": [{"busy_s": 1.0}], "programs": [
               ["jit_forest_batched", 40.0, 1], ["jit_batched", 12.0, 1],
               ["jit_linear_batched", 0.5, 1], ["jit_glm_batched", 0.8, 2]]}}
    obs.update(more)
    return obs


def test_glm_cost_counts_the_iterations_that_ran():
    from benchmark import costs_reg
    obs = observations()
    lanes = obs["pool_lane_shapes"]["GeneralizedLinearRegression"]
    assert costs_reg.family_iterations(obs["glm_calls"]) == {
        "gaussian": 1, "poisson": 24}
    cost = costs_reg.glm_grid_cost(lanes, obs["glm_calls"], 98304)
    assert cost["flops"] == 9 * (1 + 24) * 2 * 65536 * 181 ** 2
    assert cost["bytes"] == (1 + 24) * 98304 * 181 * 4
    at_most = costs_reg.glm_grid_cost(
        lanes, [{"family": f, "irls_iterations": 25}
                for f in ("gaussian", "poisson")], 98304)
    assert cost["flops"] < at_most["flops"]
    assert costs_reg.glm_grid_cost(lanes, obs["glm_calls"][:1], 98304) is None
    with pytest.raises(ValueError):     # more than max_iter: nobody ran them
        costs_reg.glm_grid_cost(lanes, [
            {"family": "gaussian", "irls_iterations": 1},
            {"family": "poisson", "irls_iterations": 26}], 98304)


def test_new_readers_on_hand_made_observations(capsys):
    from benchmark import costs, costs_pool, costs_reg, harness
    from benchmark.layer_metrics import (
        glm_grid_roofline, reg_forest_grid_roofline, reg_glm_s,
        reg_linear_grid_roofline)
    obs = observations()
    peaks = harness.load_peaks("TPU v5 lite")
    assert reg_glm_s.read(obs) == 0.8
    lanes = obs["pool_lane_shapes"]
    want = costs.least_seconds(costs_reg.glm_grid_cost(
        lanes["GeneralizedLinearRegression"], obs["glm_calls"], 98304),
        peaks)["seconds"]
    assert glm_grid_roofline.read(obs) == pytest.approx(100 * want / 0.8)
    want = costs.least_seconds(costs_pool.summed(
        [costs_pool.forest_fit_cost(**lane)
         for lane in lanes["RandomForestRegressor"]]), peaks)["seconds"]
    assert reg_forest_grid_roofline.read(obs) == pytest.approx(
        100 * want / 40.0)
    want = costs.least_seconds(costs_pool.linear_grid_cost(
        lanes["LinearRegression"], 98304), peaks)["seconds"]
    assert reg_linear_grid_roofline.read(obs) == pytest.approx(
        100 * want / 0.5)
    assert "IRLS lanes" in capsys.readouterr().out
    # nothing to read: a package without the program, a run without spans
    parent = observations(glm_calls=[])
    parent["trace"]["programs"] = parent["trace"]["programs"][:3]
    for reader in (reg_glm_s, glm_grid_roofline):
        assert reader.read(parent) is None
    assert glm_grid_roofline.read(observations(glm_calls=[])) is None
    for reader in (reg_glm_s, glm_grid_roofline, reg_forest_grid_roofline,
                   reg_linear_grid_roofline):
        assert reader.read({}) is None
        assert reader.read(observations(pool_lane_shapes={},
                                        trace=None)) is None


def test_glm_roofline_cannot_pass_its_peak_by_counting_max_iter():
    """A program that stopped after 3 iterations and took the least time 3
    iterations can take reads 100 %; credited with ``max_iter`` it would
    read 833 %."""
    from benchmark import costs, costs_reg, harness
    from benchmark.layer_metrics import glm_grid_roofline
    calls = [{"family": "gaussian", "irls_iterations": 3},
             {"family": "poisson", "irls_iterations": 3}]
    obs = observations(glm_calls=calls)
    least = costs.least_seconds(costs_reg.glm_grid_cost(
        obs["pool_lane_shapes"]["GeneralizedLinearRegression"], calls,
        98304), harness.load_peaks("TPU v5 lite"))["seconds"]
    obs["trace"]["programs"][3] = ["jit_glm_batched", least, 2]
    assert glm_grid_roofline.read(obs) == pytest.approx(100.0)


# ---------------------------------------------------------------------------
# the benchmark's own check and the new cell's rehearsal
# ---------------------------------------------------------------------------

def test_selfcheck_and_regression_pool_dry_run():
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
         "--workload", "msd_reg_pool.search", "--cpu-dry-run", "tiny",
         "--seed", "3400000003", "--seconds", "1", "--trace", "1"],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=600)
    assert run.returncode == 0, run.stdout[-3000:] + run.stderr[-2000:]
    last = run.stdout.strip().splitlines()[-1]
    line = json.loads(last[last.index("{"):])
    assert line["correct"] is True and line["failed"] == 0
    for name in ("compile_s", "compiles_in_window", "prepare_s_per_train",
                 "selector_s_per_train", "winner_tail_s_per_train",
                 "search_design_s_per_train", "dispatch_threaded",
                 "families_on_host_path"):
        assert name in line["metrics"], name
    assert line["metrics"]["families_on_host_path"]["value"] == 0.0
    assert "models_x_folds: 24" in run.stdout
    assert "search.fetch spans of jit_glm_batched" in run.stdout
