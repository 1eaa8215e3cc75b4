"""The default multiclass pool as fold-grid device programs, against plain
references (ISSUE 32).

``benchmark/reference/multinomial_plain.py``, ``bayes_plain.py``,
``f1_plain.py`` and (as it stands, at K classes) ``forest_plain.py`` are NumPy
float64 and import nothing of the package; the package's multinomial logistic
core, its fold-grid lanes (local and on a four-device mesh), naive Bayes, the
K-class tree and forest and the whole default pool through
``Workflow.train()`` are held to them here at a small size on the CPU. The
same references decide ``correct`` in the cell ``covtype_mc_pool.search``.
"""
import ast
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:                 # ``benchmark`` is a root package
    sys.path.insert(0, ROOT)

from benchmark.reference.bayes_plain import PlainNaiveBayes       # noqa: E402
from benchmark.reference.f1_plain import (                        # noqa: E402
    predicted_class, weighted_f1)
from benchmark.reference.folds_plain import stratified_folds      # noqa: E402
from benchmark.reference.forest_plain import PlainForest          # noqa: E402
from benchmark.reference.multinomial_plain import (               # noqa: E402
    PlainMultinomial, to_bfloat16)
from transmogrifai_tpu.evaluators import (                        # noqa: E402
    MultiClassificationEvaluator)
from transmogrifai_tpu.models import (                            # noqa: E402
    DecisionTreeClassifier, LogisticRegression, NaiveBayes,
    RandomForestClassifier, linear, registry)
from transmogrifai_tpu.parallel import cv                         # noqa: E402
from transmogrifai_tpu.runtime import telemetry                   # noqa: E402
from transmogrifai_tpu.selector import CrossValidation            # noqa: E402

K = 5
SPEC = ("multiclass", "F1")


def table(n, seed=3, numeric=6, levels=8):
    """The benchmark's table in small: non-negative numeric columns, a
    one-of-``levels`` indicator group, and a label that is multinomial-
    logistic in two numeric columns and three indicator levels, one class
    rare."""
    rng = np.random.default_rng(seed)
    x_num = np.abs(rng.normal(size=(n, numeric))) * [40, 3, 1, 1, 10, 1]
    x_cat = np.eye(levels)[rng.choice(levels, n, p=np.arange(
        levels, 0, -1) / (levels * (levels + 1) / 2))]
    W = np.random.default_rng(99).normal(size=(K, 5)) * 1.5
    z = np.column_stack([x_num[:, 0] / 40, x_num[:, 1] / 3, x_cat[:, :3]])
    logits = z @ W.T + [1.0, 1.0, 0.0, -2.5, 0.0]
    y = np.argmax(logits + rng.gumbel(size=logits.shape), axis=1)
    return np.concatenate([x_num, x_cat], axis=1), y.astype(float)


def folds(y, seed=8, k=3):
    fold_of = stratified_folds(y, k, seed)
    masks = np.stack([(fold_of >= 0) & (fold_of != f) for f in range(k)]
                     ).astype(float)
    return masks, [fold_of == f for f in range(k)]


# ---------------------------------------------------------------------------
# the references import nothing of the package
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["multinomial_plain", "bayes_plain",
                                  "f1_plain"])
def test_plain_reference_imports_only_numpy(name):
    path = os.path.join(ROOT, "benchmark", "reference", name + ".py")
    imported = set()
    for node in ast.walk(ast.parse(open(path).read())):
        if isinstance(node, ast.Import):
            imported.update(alias.name for alias in node.names)
        elif isinstance(node, ast.ImportFrom):
            imported.add(node.module)
    assert imported <= {"__future__", "typing", "numpy"}


def test_weighted_f1_on_cases_worked_by_hand():
    """Two of class 0 (one found), one of class 1 (found, and one false
    alarm), class 2 predicted but absent from the labels: F1 2/3 and 2/3 at
    weights 2/3 and 1/3; the package's evaluator and its device twin agree."""
    from transmogrifai_tpu.evaluators.device_metrics import multiclass_metric
    y, predicted = np.array([0, 0, 1]), np.array([0, 1, 1])
    assert weighted_f1(y, predicted) == pytest.approx(2 / 3)
    y, predicted = np.array([0, 0, 1, 1]), np.array([0, 2, 1, 1])
    assert weighted_f1(y, predicted) == pytest.approx(
        0.5 * (2 / 3) + 0.5 * 1.0)
    scores = np.array([[.5, .5, 0], [.2, .3, .5], [0, 1, 0], [.1, .8, .1]])
    assert predicted_class(scores).tolist() == [0, 2, 1, 1]   # first on ties
    assert float(multiclass_metric(jnp.asarray(y, jnp.float64), jnp.asarray(
        scores), "F1")) == pytest.approx(weighted_f1(y, predicted))


def test_bfloat16_rounding_keeps_eight_bits():
    assert to_bfloat16(np.float32([1.0, 2960.0, 1.00390625, 1.01171875])
                       ).tolist() == [1.0, 2960.0, 1.0, 1.015625]


# ---------------------------------------------------------------------------
# the multinomial core: the mask, the plain reference, one objective
# ---------------------------------------------------------------------------

def core_fit(X, y, w, reg, alpha, **more):
    return [np.asarray(a) for a in jax.jit(
        lambda X_, y_, w_: linear.multinomial_logistic_core(
            X_, y_, w_, reg, alpha, k=K, fit_intercept=True,
            standardize=True, max_iter=50, use_l1=reg * alpha > 0, **more)
    )(jnp.asarray(X), jnp.asarray(y), jnp.asarray(w))]


@pytest.mark.parametrize("reg,alpha", [(0.01, 0.5), (0.05, 0.0)],
                         ids=["l1", "no-l1"])
def test_multinomial_core_mask_and_plain_reference(reg, alpha):
    """A 0/1 mask is the unweighted fit on the kept rows, and both are the
    plain reference's coefficients: the minimiser's where L-BFGS converges
    (no L1), the same 250 steps' where FISTA runs (L1), and there the
    minimiser's too on this well-conditioned design."""
    X, y = table(2400)
    masks, _ = folds(y)
    kept = masks[0] > 0
    masked = core_fit(X, y, masks[0], reg, alpha)
    subset = core_fit(X[kept], y[kept], np.ones(int(kept.sum())), reg, alpha)
    np.testing.assert_allclose(masked[0], subset[0], atol=2e-6)
    np.testing.assert_allclose(masked[1], subset[1], atol=2e-6)
    schedule = {"steps": 250, "stop": 1e-7} if reg * alpha > 0 else None
    plain = PlainMultinomial(reg, alpha, schedule=schedule).fit(
        X, y, mask=masks[0])
    np.testing.assert_allclose(masked[0], plain.coefficients, atol=2e-5)
    np.testing.assert_allclose(masked[1], plain.intercept, atol=2e-5)
    minimiser = PlainMultinomial(reg, alpha).fit(X[kept], y[kept])
    np.testing.assert_allclose(masked[0] * minimiser.sigma,
                               minimiser.coefficients * minimiser.sigma,
                               atol=5e-3)
    wrong = PlainMultinomial(10 * reg, alpha).fit(X[kept], y[kept])
    assert np.max(np.abs((masked[0] - wrong.coefficients) * wrong.sigma)
                  ) > 0.05


def test_fit_arrays_shares_the_core():
    """``_fit_multinomial_logistic`` is the core with unit weights: one
    multinomial objective in the package, not two."""
    X, y = table(1200)
    model = LogisticRegression(reg_param=0.01, elastic_net_param=0.5,
                               max_iter=50).fit_arrays(X, y)
    coef, intercept = core_fit(X, y, np.ones(len(y)), 0.01, 0.5)
    np.testing.assert_allclose(model.coefficients, coef, atol=1e-12)
    np.testing.assert_allclose(model.intercept, intercept, atol=1e-12)
    source = open(linear.__file__).read()
    assert source.count("log_softmax") == 1
    assert "binary-only" not in source.split("class LinearRegression")[0]


# ---------------------------------------------------------------------------
# the softmax lanes as one fold-grid program
# ---------------------------------------------------------------------------

GRID = [{"reg_param": 0.01, "elastic_net_param": 0.5},
        {"reg_param": 0.05, "elastic_net_param": 0.1},
        {"reg_param": 0.1, "elastic_net_param": 0.0},
        {"reg_param": 0.2, "elastic_net_param": 0.5}]


@pytest.fixture(scope="module")
def lanes():
    """The (3 folds, 4 grid points) metric matrix of the fold-grid program
    and everything it was computed from."""
    X, y = table(2400)
    masks, held = folds(y)
    Xv, yv = np.stack([X[h] for h in held]), np.stack([y[h] for h in held])
    est = LogisticRegression(max_iter=50)
    matrix = est.eval_fold_grid_arrays(X, y, masks, GRID, Xv, yv, SPEC)
    return dict(X=X, y=y, masks=masks, held=held, Xv=Xv, yv=yv, est=est,
                matrix=matrix)


@pytest.mark.parametrize("fold", range(3))
@pytest.mark.parametrize("point", range(len(GRID)))
def test_softmax_lane_is_the_fold_by_fold_fit(lanes, fold, point):
    """Every (fold, grid point) lane of ``eval_linear_fold_grid("softmax")``
    scores what ``fit_arrays`` on the fold's rows + the host evaluator score
    (250 fixed steps against a converged fit of the same objective: a row or
    two of 800 may change sides), and what the plain minimiser scores."""
    X, y, held = lanes["X"], lanes["y"], lanes["held"][fold]
    train = lanes["masks"][fold] > 0
    model = lanes["est"].with_params(**GRID[point]).fit_arrays(
        X[train], y[train])
    evaluator = MultiClassificationEvaluator()
    host = evaluator.metric_from(evaluator.evaluate_arrays(
        y[held], model.predict_arrays(X[held])))
    assert lanes["matrix"][fold, point] == pytest.approx(host, abs=4e-3)
    plain = PlainMultinomial(**GRID[point]).fit(X, y, mask=train)
    assert lanes["matrix"][fold, point] == pytest.approx(weighted_f1(
        y[held], predicted_class(plain.scores(X[held]))), abs=4e-3)


def test_softmax_lanes_fitted_parameters_and_program_name(lanes):
    """``fit_fold_grid_arrays`` hands back (K, d) models whose coefficients
    are the core's under the fold's mask; the jitted function is named so
    that the program reads ``jit_softmax_batched`` and traces under
    ``fg.softmax``, the binary kinds' keep ``jit_linear_batched``."""
    X, y, masks = lanes["X"], lanes["y"], lanes["masks"]
    fitted = lanes["est"].fit_fold_grid_arrays(X, y, masks, GRID[:2])
    assert np.shape(fitted[0][0].coefficients) == (K, X.shape[1])
    coef, intercept = core_fit(X, y, masks[1], 0.05, 0.1, solver="fista")
    np.testing.assert_allclose(fitted[1][1].coefficients, coef, atol=1e-9)
    np.testing.assert_allclose(fitted[1][1].intercept, intercept, atol=1e-9)
    soft = cv._local_eval_kernel(cv._kernel_cfg(
        "softmax", True, True, True, 50, K), SPEC)
    binary = cv._local_eval_kernel(cv._kernel_cfg(
        "logistic", True, True, True, 50, None), ("binary", "AuPR"))
    assert soft.__name__ == "softmax_batched"
    assert binary.__name__ == "linear_batched"
    args = (jnp.asarray(np.repeat(masks, 2, axis=0)), jnp.full(6, 0.01),
            jnp.full(6, 0.5), jnp.asarray(np.repeat(np.arange(3), 2)),
            jnp.asarray(X), jnp.asarray(y), jnp.asarray(lanes["Xv"]),
            jnp.asarray(lanes["yv"]))
    text = soft.lower(*args).as_text(debug_info=True)
    assert "jit_softmax_batched" in text and "fg.softmax" in text
    assert "fg.linear" not in text
    with pytest.raises(ValueError):
        cv._kernel_cfg("logistic", True, True, True, 50, K)


def test_softmax_mesh_kernel_is_the_local_kernel(lanes):
    """Four virtual CPU devices, the candidate axis sharded ``models: 4``
    (12 lanes, 3 a device): the metric matrix and the fitted parameters are
    the local program's."""
    mesh = cv.models_mesh(jax.devices()[:4])
    assert dict(mesh.shape) == {"models": 4, "data": 1}
    args = (lanes["X"], lanes["y"], lanes["masks"], GRID)
    sharded = lanes["est"].eval_fold_grid_arrays(
        *args, lanes["Xv"], lanes["yv"], SPEC, mesh=mesh)
    np.testing.assert_allclose(sharded, lanes["matrix"], atol=1e-12)
    local = lanes["est"].fit_fold_grid_arrays(*args)
    fitted = lanes["est"].fit_fold_grid_arrays(*args, mesh=mesh)
    for f in range(3):
        for g in range(len(GRID)):
            np.testing.assert_allclose(fitted[f][g].coefficients,
                                       local[f][g].coefficients, atol=1e-9)


@pytest.mark.parametrize("cand_idx", [[2], [3, 0], [1, 1, 2]],
                         ids=["one", "reordered", "padded"])
def test_softmax_cand_idx_subsets_the_matrix(lanes, cand_idx):
    got = lanes["est"].eval_fold_grid_arrays(
        lanes["X"], lanes["y"], lanes["masks"], GRID, lanes["Xv"],
        lanes["yv"], SPEC, cand_idx=np.asarray(cand_idx))
    np.testing.assert_allclose(got, lanes["matrix"][:, cand_idx],
                               atol=1e-12)


def test_two_classes_under_a_multiclass_metric_stay_on_the_device():
    """Binary labels under the multiclass evaluator run the binomial lanes
    and score the softmax of their [-m, m] pair; a binary metric on K-class
    labels is refused."""
    X, y = table(1200)
    y2 = (y > 1).astype(float)
    masks, held = folds(y2)
    Xv, yv = np.stack([X[h] for h in held]), np.stack([y2[h] for h in held])
    est = LogisticRegression(max_iter=50)
    got = est.eval_fold_grid_arrays(X, y2, masks, GRID[:1], Xv, yv, SPEC)
    model = est.with_params(**GRID[0]).fit_arrays(X[masks[0] > 0],
                                                  y2[masks[0] > 0])
    want = weighted_f1(y2[held[0]], predicted_class(
        model.predict_raw(X[held[0]])))
    assert got[0, 0] == pytest.approx(want, abs=4e-3)
    with pytest.raises(NotImplementedError):
        est.eval_fold_grid_arrays(X, y, masks, GRID[:1], Xv, yv,
                                  ("binary", "AuPR"))


# ---------------------------------------------------------------------------
# naive Bayes: the closed form, and a program with a name
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("smoothing", [1.0, 0.25])
def test_naive_bayes_lane_is_the_plain_closed_form(smoothing):
    X, y = table(2400)
    masks, held = folds(y)
    Xv, yv = np.stack([X[h] for h in held]), np.stack([y[h] for h in held])
    est = NaiveBayes()
    got = est.eval_fold_grid_arrays(X, y, masks, [{"smoothing": smoothing}],
                                    Xv, yv, SPEC)
    fitted = est.fit_fold_grid_arrays(X, y, masks,
                                      [{"smoothing": smoothing}])
    for fold in range(3):
        plain = PlainNaiveBayes(smoothing).fit(X, y, mask=masks[fold])
        np.testing.assert_allclose(fitted[fold][0].theta, plain.theta,
                                   atol=1e-10)
        np.testing.assert_allclose(fitted[fold][0].pi, plain.pi, atol=1e-10)
        assert got[fold, 0] == pytest.approx(weighted_f1(
            y[held[fold]], predicted_class(plain.scores(X[held[fold]]))),
            abs=1e-12)


def test_naive_bayes_program_has_a_name_and_a_scope():
    from transmogrifai_tpu.models import bayes, trees
    X, y = table(300)
    masks, held = folds(y)
    kernel = bayes._nb_eval_kernel(K, "multinomial", SPEC)
    text = kernel.lower(
        jnp.asarray(masks), jnp.ones(3), jnp.arange(3), jnp.asarray(X),
        jnp.asarray(y), jnp.asarray(np.stack([X[h] for h in held])),
        jnp.asarray(np.stack([y[h] for h in held]))).as_text(debug_info=True)
    assert "jit_bayes_batched" in text and "fg.bayes" in text
    assert bayes._nb_fit_kernel(K, "multinomial").__name__ == "bayes_batched"
    assert {"fg.softmax", "fg.bayes"} <= set(trees.SCOPES)


# ---------------------------------------------------------------------------
# K-class trees: one tree split for split, the forest in distribution
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("depth,min_instances,min_gain",
                         [(4, 3, 0.001), (11, 1, 0.0)], ids=["4", "11"])
def test_k_class_tree_matches_plain_split_for_split(depth, min_instances,
                                                    min_gain):
    """``DecisionTreeClassifier`` draws nothing: its tree over five classes
    and the plain one (``forest_plain`` with one tree, no bagging, every
    feature; statistics five wide) are the same tree. The unregularised
    depth-11 tree fills its last levels past the 256-node cap."""
    X, y = table(3000)
    params = dict(max_depth=depth, min_instances_per_node=min_instances,
                  min_info_gain=min_gain)
    model = DecisionTreeClassifier(**params).fit_arrays(X, y)
    plain = PlainForest(num_trees=1, bootstrap=False, **params).fit(X, y)
    feats, thrs, shares = plain.trees[0]
    assert shares.shape[1] == K
    assert np.array_equal(np.concatenate(feats), model.feats[0])
    np.testing.assert_allclose(np.concatenate(thrs), model.thrs[0])
    np.testing.assert_allclose(shares, model.leaves[0], atol=1e-12)
    Xh, yh = table(1000, seed=4)
    np.testing.assert_allclose(plain.votes(Xh), model.predict_raw(Xh),
                               atol=1e-12)
    uncapped = PlainForest(num_trees=1, bootstrap=False, node_cap=1 << 20,
                           **params).fit(X, y)
    same = all(np.array_equal(a, b) for a, b in
               zip(uncapped.trees[0][1], thrs))
    assert same == (depth < 9)


def test_k_class_tree_lane_scores_the_plain_tree():
    """The single tree through the fold-grid program, one lane a fold, held
    to the plain tree under the fold's mask by the selector's score."""
    X, y = table(2400)
    masks, held = folds(y)
    point = {"max_depth": 6, "min_instances_per_node": 10,
             "min_info_gain": 0.001}
    got = DecisionTreeClassifier().eval_fold_grid_arrays(
        X, y, masks, [point], np.stack([X[h] for h in held]),
        np.stack([y[h] for h in held]), SPEC)
    for fold in range(3):
        plain = PlainForest(num_trees=1, bootstrap=False, **point).fit(
            X, y, mask=masks[fold])
        assert got[fold, 0] == pytest.approx(weighted_f1(
            y[held[fold]], predicted_class(plain.votes(X[held[fold]]))),
            abs=1e-12)


def test_k_class_forest_lanes_inside_plain_seed_spread():
    """The default forest over five classes on a design as the selector sees
    it (null indicators appended), one lane a fold: each lane's weighted F1
    lies inside the plain forest's own seed-to-seed range on the same fold,
    widened by half that range."""
    X, y = table(2400, seed=8)
    X = np.concatenate([X, np.zeros_like(X)], axis=1)   # null indicators
    masks, held = folds(y)
    point = {"max_depth": 6, "min_instances_per_node": 10,
             "min_info_gain": 0.001}
    got = RandomForestClassifier(num_trees=50).eval_fold_grid_arrays(
        X, y, masks, [point], np.stack([X[h] for h in held]),
        np.stack([y[h] for h in held]), SPEC)
    for fold in range(3):
        plain = [weighted_f1(y[held[fold]], predicted_class(PlainForest(
            num_trees=50, seed=seed, **point).fit(
                X, y, mask=masks[fold]).votes(X[held[fold]])))
            for seed in range(5)]
        room = 0.5 * (max(plain) - min(plain))
        assert min(plain) - room <= got[fold, 0] <= max(plain) + room, \
            (fold, got[fold, 0], plain)


# ---------------------------------------------------------------------------
# the default pool: no family on the host path, the host path's winner
# ---------------------------------------------------------------------------

def test_logistic_lanes_pick_the_host_paths_winner(monkeypatch):
    """The default multiclass grid of ``LogisticRegression`` through the
    validator: the device program's winner is the one the host path picked
    before there was a program (the parent's path, forced here), the
    counter reads 0 and 1."""
    X, y = table(1800)
    est, grid = registry.default_multiclass_models()[0]
    assert type(est).__name__ == "LogisticRegression" and len(grid) == 8

    def search():
        telemetry.reset()
        best = CrossValidation(MultiClassificationEvaluator(), num_folds=3,
                               seed=5, stratify=True).validate(
                                   [(est, grid)], X, y)
        return best, telemetry.counters()["host_path_families"]

    device, counted = search()
    assert counted == 0

    def refuse(*_args, **_kwargs):
        raise NotImplementedError("binary-only, as the parent was")
    monkeypatch.setattr(LogisticRegression, "eval_fold_grid_arrays", refuse)
    monkeypatch.setattr(LogisticRegression, "fit_fold_grid_arrays", refuse)
    host, counted = search()
    assert counted == 1
    assert device.params == host.params
    for a, b in zip(device.results, host.results):
        np.testing.assert_allclose(a.metric_values, b.metric_values,
                                   atol=6e-3)


@pytest.fixture(scope="module")
def pool_train():
    """One ``Workflow.train()`` of the whole default pool (no ``models``
    argument) on a small table, with the package's spans on."""
    from benchmark.configs import covtype_mc_pool as cfg
    from transmogrifai_tpu.observability import trace as package_trace
    from transmogrifai_tpu.selector import SelectedModel
    from transmogrifai_tpu.utils.uid import reset as reset_uids
    reset_uids(deterministic=True)
    config = json.load(open(os.path.join(
        ROOT, "benchmark", "configs", "covtype_mc_pool.json")))
    X, y = table(1500)
    telemetry.reset()
    package_trace.configure(True)
    try:
        workflow, prediction = cfg.workflow(config, 5, X.shape[1])
        model = workflow.set_input_dataset(cfg.dataset(X, y)).train()
        spans = package_trace.spans()
    finally:
        package_trace.configure(False)
    summary = next(s.summary for s in model.stages()
                   if isinstance(s, SelectedModel) and s.summary is not None)
    return dict(summary=summary, counters=telemetry.counters(), spans=spans)


@pytest.mark.parametrize("family,points", [
    ("LogisticRegression", 8), ("RandomForestClassifier", 18),
    ("NaiveBayes", 1), ("DecisionTreeClassifier", 18)])
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


def test_default_pool_counts_no_family_on_the_host_path(pool_train):
    summary, counters = pool_train["summary"], pool_train["counters"]
    assert sum(len(r.metric_values) for r in summary.validation_results) \
        == 135
    assert counters["host_path_families"] == 0
    assert not summary.quarantined
    dispatch = [s for s in pool_train["spans"]
                if s["name"] == "search.dispatch"]
    assert dispatch and all(s["attrs"]["host_path"] == "" for s in dispatch)
    assert summary.best_model_name == "LogisticRegression"


# ---------------------------------------------------------------------------
# the cell's limits: what a reference made wrong comes out as
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("control,shown", [
    ({}, False),
    ({"LogisticRegression": {"dtype": "bfloat16"}}, True),
    ({"LogisticRegression": {"elastic_net_param": 0.1}}, True),
    ({"fold_seed": 4}, True)],
    ids=["float64", "bfloat16", "elastic-net", "other-folds"])
def test_check_readings_tells_a_wrong_reference(control, shown):
    """``mc_pool_search.check_readings`` on a refitted logistic winner and
    one lane of every deterministic family: correct against the float64
    references at the cell's own coefficient limit, not correct against the
    same reference in bfloat16 or with another elastic-net, nor on folds
    drawn from another seed."""
    from benchmark.configs import covtype_mc_pool as cfg
    from benchmark.jobs import mc_pool_search as job
    config = json.load(open(os.path.join(
        ROOT, "benchmark", "configs", "covtype_mc_pool.json")))
    X, y = table(3000)
    masks, held = folds(y, seed=3)
    params = {"reg_param": 0.01, "elastic_net_param": 0.5}
    D = job.design(X)
    model = LogisticRegression(max_iter=50, **params).fit_arrays(D, y)
    Xv, yv = np.stack([D[h] for h in held]), np.stack([y[h] for h in held])
    lanes = {}
    for est, name, index in ((NaiveBayes(), "NaiveBayes", 0),
                             (DecisionTreeClassifier(),
                              "DecisionTreeClassifier", 6)):
        family = next(f for f in cfg.families(config) if f["class"] == name)
        point = cfg.grid(family)[index]
        lanes[name] = {str(index): {"params": point, "folds": est.with_params(
            **family["params"]).eval_fold_grid_arrays(
                D, y, masks, [point], Xv, yv, SPEC)[:, 0].tolist()}}
    got = {"seed": 3, "metric": "F1", "lanes": lanes, "winner": {
        "family": "LogisticRegression", "params": params,
        "coefficients": np.asarray(model.coefficients).tolist(),
        "intercept": np.asarray(model.intercept).tolist()}}
    check = [["NaiveBayes", 0, 1, 0.0005],
             ["DecisionTreeClassifier", 6, 2, 0.002]]
    problems = job.check_readings(cfg, config, check, got, X, y,
                                  override=control)
    assert bool(problems) == shown, problems


# ---------------------------------------------------------------------------
# the new readers, on observations made by hand
# ---------------------------------------------------------------------------

def observations(**more):
    obs = {"reps": [{"ok": True, "traced": False, "host_path_families": 0},
                    {"ok": True, "traced": True, "host_path_families": 0}],
           "trace": {"devices": [{"device": 0, "busy_s": 9.0}],
                     "programs": [["jit_forest_batched", 8.0, 2],
                                  ["jit_softmax_batched", 0.5, 1],
                                  ["jit_bayes_batched", 0.01, 1]]},
           "device_kind": "TPU v5 lite", "matrix_rows": 98304}
    obs.update(more)
    return obs


def test_program_readers_on_hand_made_observations():
    from benchmark.layer_metrics import (families_on_host_path, mc_forest_s,
                                         mc_softmax_s)
    obs = observations()
    assert mc_softmax_s.read(obs) == 0.5
    assert mc_forest_s.read(obs) == 8.0
    assert families_on_host_path.read(obs) == 0.0
    parent = observations(reps=[
        {"ok": True, "traced": True, "host_path_families": None}])
    parent["trace"]["programs"] = parent["trace"]["programs"][:1]
    assert mc_softmax_s.read(parent) is None
    assert families_on_host_path.read(parent) is None
    assert mc_forest_s.read(parent) == 8.0
    one = observations(reps=[
        {"ok": True, "traced": True, "host_path_families": 1},
        {"ok": False, "traced": False, "host_path_families": 3}])
    assert families_on_host_path.read(one) == 1.0
    for reader in (mc_softmax_s, mc_forest_s, families_on_host_path):
        assert reader.read({}) is None


def test_softmax_roofline_counts_the_classes():
    """24 lanes x 250 steps x 2 x 2 x 65,536 rows x 108 columns x 7 classes
    = 1.189 T operations: 6.0 ms at the chip's 197 T a second; 250 sweeps of
    the 98,304 x 108 float32 matrix = 10.6 GB: 13.0 ms at 819 GB/s, which
    bounds. Seven times the binary lanes' operations, the same bytes."""
    from benchmark import costs, costs_mc, costs_pool, harness
    from benchmark.configs import covtype_mc_pool as cfg
    from benchmark.layer_metrics import (forest_grid_roofline,
                                         softmax_grid_roofline)
    config = json.load(open(os.path.join(
        ROOT, "benchmark", "configs", "covtype_mc_pool.json")))
    shapes = cfg.lane_shapes(config, 98304)
    assert {k: len(v) for k, v in shapes.items()} == {
        "LogisticRegression": 24, "RandomForestClassifier": 54,
        "NaiveBayes": 3, "DecisionTreeClassifier": 54}
    cost = costs_mc.softmax_grid_cost(shapes["LogisticRegression"], 98304)
    assert cost["flops"] == 24 * 250 * 4 * 65536 * 108 * 7
    assert cost["bytes"] == 250 * 98304 * 108 * 4
    binary = costs_pool.linear_grid_cost(shapes["LogisticRegression"], 98304)
    assert cost["flops"] == 7 * binary["flops"]
    assert cost["bytes"] == binary["bytes"]
    least = costs.least_seconds(cost, harness.load_peaks("TPU v5 lite"))
    assert least["bound"] == "bandwidth"
    obs = observations(pool_lane_shapes=shapes)
    assert softmax_grid_roofline.read(obs) == pytest.approx(
        100 * least["seconds"] / 0.5)
    assert softmax_grid_roofline.read(observations()) is None
    # the accepted forest reader takes the class count from the lane shapes
    lane = shapes["RandomForestClassifier"][-1]
    assert lane == {"rows": 65536, "depth": 12, "pooled_bins": 200,
                    "trees": 50, "classes": 7}
    assert costs_pool.forest_fit_cost(**lane)["flops"] == 3.5 * \
        costs_pool.forest_fit_cost(**dict(lane, classes=2))["flops"]
    assert 0 < forest_grid_roofline.read(obs) < 100
    assert cfg.class_counts(config, 98304) == [
        35841, 47934, 6048, 465, 1605, 2940, 3471]
    assert all(c % 3 == 0 for c in cfg.class_counts(config, 3072))
    assert sum(cfg.class_counts(config, 32768)) == 32768


def test_scope_readers_on_a_hand_made_table(monkeypatch):
    from benchmark.layer_metrics import mc_forest_hist_s, mc_forest_votes_s
    from benchmark.trace import scopes
    monkeypatch.setattr(scopes, "table", lambda: {"jit_forest_batched": {
        "runs": 2, "by_scope": {"tree.hist": 3.0, "fg.forest": 1.0}}})
    assert mc_forest_hist_s.read(observations()) == 3.0     # a train, 2 runs
    assert mc_forest_votes_s.read(observations()) == 1.0
    monkeypatch.setattr(scopes, "table", lambda: {"jit_forest_batched": {
        "runs": 2, "by_scope": {}}})
    assert mc_forest_hist_s.read(observations()) is None
    monkeypatch.setattr(scopes, "table", lambda: None)
    assert mc_forest_votes_s.read(observations()) is None
    assert mc_forest_hist_s.read({}) is None


# ---------------------------------------------------------------------------
# the benchmark's own check and the new cell's rehearsal
# ---------------------------------------------------------------------------

def test_selfcheck_and_multiclass_pool_dry_run():
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
         "--workload", "covtype_mc_pool.search", "--cpu-dry-run", "tiny",
         "--seed", "3200000003", "--seconds", "1", "--trace", "1"],
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
    assert "models_x_folds: 21" in run.stdout
