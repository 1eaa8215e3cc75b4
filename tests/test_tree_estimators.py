"""Tree model family tests (reference OpRandomForestClassifierTest,
OpGBTClassifierTest, OpDecisionTreeClassifierTest et al. in
core/src/test/.../classification/ and .../regression/): the estimators of
``models/trees/estimators.py`` and the feature-pool plans of
``models/trees/design.py``."""
import numpy as np
import pytest

from transmogrifai_tpu.models import (
    DecisionTreeClassifier, DecisionTreeRegressor, GBTClassifier,
    GBTRegressor, RandomForestClassifier, RandomForestRegressor,
    XGBoostClassifier, XGBoostRegressor)


def _accuracy(model, X, y):
    pred = model.predict_arrays(X).data
    return float(np.mean(pred == y))


class TestDecisionTree:
    def test_classifier_learns_axis_aligned(self, binary_data):
        X, y = binary_data
        model = DecisionTreeClassifier(max_depth=3).fit_arrays(X, y)
        assert _accuracy(model, X, y) > 0.97

    def test_classifier_probabilities_valid(self, binary_data):
        X, y = binary_data
        model = DecisionTreeClassifier(max_depth=3).fit_arrays(X, y)
        prob = model.predict_arrays(X).probability
        assert prob.shape == (len(y), 2)
        np.testing.assert_allclose(prob.sum(axis=1), 1.0, atol=1e-9)
        assert (prob >= 0).all()

    def test_min_info_gain_prunes(self, binary_data):
        X, y = binary_data
        model = DecisionTreeClassifier(
            max_depth=3, min_info_gain=1e9).fit_arrays(X, y)
        # no split survives an impossible gain bar -> all thresholds +inf
        assert not np.isfinite(model.thrs).any()

    def test_regressor_learns_step(self, regression_data):
        X, y = regression_data
        model = DecisionTreeRegressor(max_depth=3).fit_arrays(X, y)
        pred = model.predict_values(X)
        assert np.mean((pred - y) ** 2) < 0.1

    def test_multiclass(self):
        rng = np.random.default_rng(3)
        X = rng.normal(size=(300, 4))
        y = (X[:, 0] > 0).astype(float) + (X[:, 1] > 0) * 1.0
        model = DecisionTreeClassifier(max_depth=4).fit_arrays(X, y)
        prob = model.predict_arrays(X).probability
        assert prob.shape[1] == 3
        assert _accuracy(model, X, y) > 0.9


class TestRandomForest:
    def test_classifier(self, binary_data):
        X, y = binary_data
        model = RandomForestClassifier(
            num_trees=20, max_depth=4, seed=7).fit_arrays(X, y)
        assert _accuracy(model, X, y) > 0.93

    def test_seed_determinism(self, binary_data):
        X, y = binary_data
        m1 = RandomForestClassifier(num_trees=5, seed=9).fit_arrays(X, y)
        m2 = RandomForestClassifier(num_trees=5, seed=9).fit_arrays(X, y)
        np.testing.assert_array_equal(m1.thrs, m2.thrs)

    def test_regressor(self, regression_data):
        X, y = regression_data
        model = RandomForestRegressor(
            num_trees=20, max_depth=4, seed=7).fit_arrays(X, y)
        pred = model.predict_values(X)
        assert np.mean((pred - y) ** 2) < 1.0

    def test_feature_importances(self, binary_data):
        X, y = binary_data
        model = RandomForestClassifier(
            num_trees=10, max_depth=3, seed=7,
            feature_subset_strategy="all").fit_arrays(X, y)
        imp = model.feature_importances
        assert imp.sum() == pytest.approx(1.0)
        # the two signal features should dominate
        assert imp[0] + imp[2] > 0.5


class TestGBT:
    def test_classifier_beats_depth_one(self, binary_data):
        X, y = binary_data
        model = GBTClassifier(num_rounds=30, max_depth=3).fit_arrays(X, y)
        assert _accuracy(model, X, y) > 0.97

    def test_classifier_probability_monotone_in_margin(self, binary_data):
        X, y = binary_data
        model = GBTClassifier(num_rounds=10, max_depth=3).fit_arrays(X, y)
        out = model.predict_arrays(X)
        m = model.margins(X)
        p = out.probability[:, 1]
        order = np.argsort(m)
        assert (np.diff(p[order]) >= -1e-12).all()

    def test_regressor(self, regression_data):
        X, y = regression_data
        model = GBTRegressor(num_rounds=100, max_depth=3).fit_arrays(X, y)
        pred = model.predict_values(X)
        assert np.mean((pred - y) ** 2) < 0.05

    def test_subsample(self, binary_data):
        X, y = binary_data
        model = GBTClassifier(num_rounds=20, max_depth=3,
                              subsample=0.7, seed=5).fit_arrays(X, y)
        assert _accuracy(model, X, y) > 0.9

    def test_xgboost_facade_param_names(self, binary_data):
        X, y = binary_data
        est = XGBoostClassifier(eta=0.3, num_round=20, max_depth=3)
        assert est.step_size == 0.3 and est.num_rounds == 20
        model = est.fit_arrays(X, y)
        assert _accuracy(model, X, y) > 0.95

    def test_xgboost_regressor(self, regression_data):
        X, y = regression_data
        model = XGBoostRegressor(num_round=40, max_depth=3).fit_arrays(X, y)
        assert np.mean((model.predict_values(X) - y) ** 2) < 0.05


class TestGridSupport:
    def test_with_params_copies(self):
        est = RandomForestClassifier()
        est2 = est.with_params(max_depth=9, num_trees=3)
        assert est2.max_depth == 9 and est2.num_trees == 3
        assert est.max_depth == 5  # original untouched
        assert type(est2) is RandomForestClassifier


class TestPoolPlan:
    """Stratified feature-pool planning edge cases (review findings)."""

    def test_minority_class_never_starved(self):
        import numpy as np
        from transmogrifai_tpu.models.trees.design import _pool_classes
        widths = np.array([2] * 3 + [32] * 997)
        (_, _), (p_n, p_w, b_n, b_w), _ = _pool_classes(widths, 124, 31)
        assert p_n >= 1 and p_w >= 1
        widths = np.array([32] + [2] * 999)
        (_, _), (p_n, p_w, _, _), _ = _pool_classes(widths, 124, 31)
        assert p_n >= 1 and p_w >= 1

    def test_full_coverage_pool_uses_exact_design(self):
        import numpy as np
        from transmogrifai_tpu.models.trees.design import _pool_plan
        (_, _), cfg, mf = _pool_plan(np.array([2] * 8), 2)
        assert cfg is None and mf == 2
