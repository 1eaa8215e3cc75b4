"""Workflow-level CV tests (reference OpWorkflowCVTest.scala:59,
FitStagesUtil.cutDAG:305): the in-CV DAG segment — every label-consuming
ancestor of the ModelSelector, e.g. SanityChecker — must be refit inside
each fold so validation metrics carry no fold leakage."""
import numpy as np
import pytest

from transmogrifai_tpu.checkers import SanityChecker
from transmogrifai_tpu.models.base import ClassifierModel, Predictor
from transmogrifai_tpu.evaluators import BinaryClassificationEvaluator
from transmogrifai_tpu.features.builder import FeatureBuilder
from transmogrifai_tpu.models import LogisticRegression
from transmogrifai_tpu.ops import transmogrify
from transmogrifai_tpu.selector import (BinaryClassificationModelSelector,
                                        SelectedModel)
from transmogrifai_tpu.workflow import Workflow
from transmogrifai_tpu.workflow.workflow import cut_dag


class _CountingSanityChecker(SanityChecker):
    fit_calls = 0

    def fit_columns(self, cols):
        type(self).fit_calls += 1
        return super().fit_columns(cols)


def _records(rng, n=160):
    recs = []
    for i in range(n):
        xs = rng.normal(size=5)
        y = float(xs[0] + 0.8 * rng.normal() > 0)
        rec = {f"x{j}": float(xs[j]) for j in range(5)}
        rec["label"] = y
        recs.append(rec)
    return recs


def _pipeline(checker_cls=SanityChecker):
    label = FeatureBuilder.real_nn("label").extract(
        lambda r: r["label"]).as_response()
    xs = [FeatureBuilder.real(f"x{j}").extract(
        lambda r, j=j: r[f"x{j}"]).as_predictor() for j in range(5)]
    fv = transmogrify(xs)
    checked = checker_cls(check_sample=1.0).set_input(label, fv).get_output()
    selector = BinaryClassificationModelSelector.with_cross_validation(
        num_folds=3, stratify=True, splitter=None,
        models=[(LogisticRegression(max_iter=25),
                 [{"reg_param": r} for r in (0.01, 0.1)])])
    pred = selector.set_input(label, checked).get_output()
    return label, pred, selector


def test_cut_dag_identifies_in_cv_segment():
    label, pred, selector = _pipeline()
    ms, during = cut_dag([label, pred])
    assert ms is selector
    names = {type(s).__name__ for layer in during for s in layer}
    # the SanityChecker consumes (response, predictor vector) -> in-CV
    assert "SanityChecker" in names


def test_cut_dag_no_selector():
    label = FeatureBuilder.real_nn("label").extract(
        lambda r: r["label"]).as_response()
    x = FeatureBuilder.real("x0").extract(
        lambda r: r["x0"]).as_predictor()
    fv = transmogrify([x])
    pred = LogisticRegression().set_input(label, fv).get_output()
    ms, during = cut_dag([label, pred])
    assert ms is None and during == []


def test_workflow_cv_refits_checker_per_fold(rng):
    recs = _records(rng)
    _CountingSanityChecker.fit_calls = 0
    label, pred, selector = _pipeline(_CountingSanityChecker)
    model = (Workflow().set_result_features(label, pred)
             .set_input_records(recs).with_workflow_cv().train())
    # 3 in-fold refits + 1 final full-data fit
    assert _CountingSanityChecker.fit_calls == 4
    sel = [s for s in model.stages() if isinstance(s, SelectedModel)][0]
    assert np.isfinite(sel.summary.best_validation_metric)
    # the preset winner skipped in-selector validation but kept results
    assert len(sel.summary.validation_results) == 2


def test_workflow_cv_changes_validation_metric(rng):
    """Per-fold SanityChecker refits change the validation metric vs the
    naive full-data-checker path (VERDICT r2 item 5 'Done'): with many
    noise features hovering around the min-correlation prune threshold,
    full-data pruning (which sees validation folds' labels) keeps a
    different set than leakage-free per-fold pruning."""
    n, d_noise = 160, 24
    Xn = rng.normal(size=(n, d_noise))
    recs = []
    for i in range(n):
        y = float(Xn[i, 0] * 0.4 + rng.normal() > 0)
        rec = {f"x{j}": float(Xn[i, j]) for j in range(d_noise)}
        rec["label"] = y
        recs.append(rec)

    def pipeline():
        label = FeatureBuilder.real_nn("label").extract(
            lambda r: r["label"]).as_response()
        xs = [FeatureBuilder.real(f"x{j}").extract(
            lambda r, j=j: r[f"x{j}"]).as_predictor()
            for j in range(d_noise)]
        fv = transmogrify(xs)
        checked = SanityChecker(min_correlation=0.08).set_input(
            label, fv).get_output()
        selector = BinaryClassificationModelSelector.with_cross_validation(
            num_folds=3, stratify=True, splitter=None,
            models=[(LogisticRegression(max_iter=25),
                     [{"reg_param": r} for r in (0.01, 0.1)])])
        pred = selector.set_input(label, checked).get_output()
        return label, pred

    def run(workflow_cv):
        label, pred = pipeline()
        wf = (Workflow().set_result_features(label, pred)
              .set_input_records(recs))
        if workflow_cv:
            wf = wf.with_workflow_cv()
        model = wf.train()
        sel = [s for s in model.stages()
               if isinstance(s, SelectedModel)][0]
        return sel.summary

    naive = run(False)
    wcv = run(True)
    assert naive.best_validation_metric != wcv.best_validation_metric
    # both searched the same grid and scoring still works end-to-end
    assert len(naive.validation_results) == len(wcv.validation_results)


def test_workflow_cv_imbalanced_with_balancer():
    """In-search balancing (reference OpValidator.applyDAG:250-252):
    the selector's DataBalancer now resamples every fold's train and
    validation rows inside the workflow-CV search. On 10:1 imbalanced
    data the search must complete, keep every fold's metric finite,
    and the final balanced refit must detect the minority class."""
    from transmogrifai_tpu.selector.splitters import DataBalancer
    rng = np.random.default_rng(7)
    recs = []
    for i in range(440):
        xs = rng.normal(size=5)
        # ~9% positives, signal on x0
        y = float(xs[0] > 1.3)
        rec = {f"x{j}": float(xs[j]) for j in range(5)}
        rec["label"] = y
        recs.append(rec)
    assert 0.05 < np.mean([r["label"] for r in recs]) < 0.18
    label = FeatureBuilder.real_nn("label").extract(
        lambda r: r["label"]).as_response()
    xs = [FeatureBuilder.real(f"x{j}").extract(
        lambda r, j=j: r[f"x{j}"]).as_predictor() for j in range(5)]
    fv = transmogrify(xs)
    checked = SanityChecker(check_sample=1.0).set_input(
        label, fv).get_output()
    selector = BinaryClassificationModelSelector.with_cross_validation(
        num_folds=3, stratify=True,
        splitter=DataBalancer(sample_fraction=0.4, seed=3),
        models=[(LogisticRegression(max_iter=25),
                 [{"reg_param": r} for r in (0.01, 0.1)])])
    pred = selector.set_input(label, checked).get_output()
    model = (Workflow().set_result_features(pred)
             .set_input_records(recs).with_workflow_cv().train())
    sel_model = [s for s in model.stages()
                 if isinstance(s, SelectedModel)][0]
    for r in sel_model.summary.validation_results:
        assert all(np.isfinite(v) for v in r.metric_values), r
    scored = model.score(recs)
    pred_labels = scored[pred.name].data
    y = np.array([r["label"] for r in recs])
    # balanced refit must not collapse to the majority class
    assert pred_labels[y == 1].mean() > 0.5
    assert (pred_labels == y).mean() > 0.85


class _PickyModel(ClassifierModel):
    """Scores the strong feature only if its train labels were balanced
    (otherwise a constant score) — a probe for whether the search saw
    balanced or raw folds."""

    def __init__(self, balanced=True, uid=None):
        super().__init__(uid=uid)
        self.balanced = balanced

    def predict_raw(self, X):
        s = X[:, 0] if self.balanced else np.zeros(len(X))
        return np.stack([-s, s], axis=1)


class _WeakModel(ClassifierModel):
    def predict_raw(self, X):
        s = X[:, 1]
        return np.stack([-s, s], axis=1)


class _BalancePicky(Predictor):
    def fit_arrays(self, X, y):
        return _PickyModel(balanced=bool(0.3 <= np.mean(y) <= 0.7))


class _Weak(Predictor):
    def fit_arrays(self, X, y):
        return _WeakModel()


def test_insearch_balancing_flips_winner():
    """In-search DataBalancer changes candidate RANKING, not just the
    final refit (reference ModelSelector.scala:140-152 +
    OpValidator.applyDAG:250-252): a model that exploits the strong
    feature only on balanced train data loses the stratify-only search
    (5% positives -> constant scores -> AuPR ~= prevalence) but wins
    the balanced search (~40% positives -> near-perfect AuPR)."""
    from transmogrifai_tpu.selector.splitters import DataBalancer
    rng = np.random.default_rng(11)
    recs = []
    for i in range(600):
        y = float(rng.random() < 0.05)
        recs.append({"x0": y + 0.2 * rng.normal(),     # strong signal
                     "x1": y + 2.0 * rng.normal(),     # weak signal
                     "label": y})

    def run(splitter):
        label = FeatureBuilder.real_nn("label").extract(
            lambda r: r["label"]).as_response()
        xs = [FeatureBuilder.real(n).extract(
            lambda r, n=n: r[n]).as_predictor() for n in ("x0", "x1")]
        fv = transmogrify(xs)
        checked = SanityChecker(check_sample=1.0).set_input(
            label, fv).get_output()
        selector = BinaryClassificationModelSelector.with_cross_validation(
            num_folds=3, stratify=True, splitter=splitter,
            models=[(_BalancePicky(), [{}]), (_Weak(), [{}])])
        pred = selector.set_input(label, checked).get_output()
        model = (Workflow().set_result_features(pred)
                 .set_input_records(recs).with_workflow_cv().train())
        sel = [s for s in model.stages()
               if isinstance(s, SelectedModel)][0]
        return sel.summary.best_model_name

    assert run(None) == "_Weak"
    assert run(DataBalancer(sample_fraction=0.4, seed=3)) == "_BalancePicky"


def test_r5_tree_flags_compose_end_to_end(rng, monkeypatch):
    """The r5 tree paths — depth ``blocks``, TX_TREE_EDGES=fold, histogram
    subtraction (``+sub``) — must compose: one end-to-end search with
    ALL of them on, plus an in-search balancer, still trains, scores and
    reaches sane quality. Combinations are where path interactions
    regress (each path's own parity is covered by its unit tests)."""
    from transmogrifai_tpu.models import GBTClassifier, trees
    from transmogrifai_tpu.selector.splitters import DataBalancer
    monkeypatch.setattr(trees, "_depth_mode", lambda: "blocks")
    monkeypatch.setenv("TX_TREE_EDGES", "fold")
    monkeypatch.setattr(trees, "_hist_mode", lambda n, tb: "scatter+sub")
    recs = []
    for i in range(400):
        y = float(rng.random() < 0.25)
        recs.append({"x0": y * 1.5 + rng.normal(),
                     "x1": y - 1.2 * rng.normal(),
                     "x2": float(rng.normal()),
                     "label": y})
    label = FeatureBuilder.real_nn("label").extract(
        lambda r: r["label"]).as_response()
    xs = [FeatureBuilder.real(n).extract(
        lambda r, n=n: r[n]).as_predictor() for n in ("x0", "x1", "x2")]
    fv = transmogrify(xs)
    selector = BinaryClassificationModelSelector.with_cross_validation(
        num_folds=2, stratify=True,
        splitter=DataBalancer(sample_fraction=0.4, seed=7),
        models=[(GBTClassifier(num_rounds=4),
                 [{"max_depth": 2}, {"max_depth": 3}])])
    pred = selector.set_input(label, fv).get_output()
    model = (Workflow().set_result_features(label, pred)
             .set_input_records(recs).train())
    sel = [s for s in model.stages() if isinstance(s, SelectedModel)][0]
    assert np.isfinite(sel.summary.best_validation_metric)
    assert sel.summary.best_validation_metric > 0.5   # AuPR >> 0.25 base
    scored = model.score(recs[:20])
    assert scored[pred.name].data.shape == (20,)
