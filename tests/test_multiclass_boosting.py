"""K-class softmax boosting (models/trees._gbt_softmax_body).

The reference reaches multiclass boosting through xgboost4j's
multi:softprob (OpXGBoostClassifier.scala:47); MLlib GBT itself is
binary-only — so GBTClassifier here stays binary (parity) and
XGBoostClassifier carries the softmax path.
"""
import numpy as np
import pytest

from transmogrifai_tpu.models import (GBTClassifier,
                                      GBTMulticlassClassifierModel,
                                      RandomForestClassifier,
                                      XGBoostClassifier)


def _three_class(n=450, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, 6))
    y = np.zeros(n)
    y[X[:, 0] > 0.5] = 1.0
    y[X[:, 1] > 0.8] = 2.0
    return X, y


class TestSoftmaxBoosting:
    def test_multiclass_fit_quality(self):
        X, y = _three_class()
        model = XGBoostClassifier(num_round=15, max_depth=3).fit_arrays(
            X, y)
        assert isinstance(model, GBTMulticlassClassifierModel)
        pred = model.predict_arrays(X)
        acc = float(np.mean(pred.data == y))
        assert acc > 0.93, acc
        # probabilities are a proper softmax simplex
        prob = pred.probability
        assert prob.shape == (len(y), 3)
        np.testing.assert_allclose(prob.sum(axis=1), 1.0, atol=1e-9)

    def test_binary_still_uses_binary_booster(self):
        X, y = _three_class()
        yb = (y > 0).astype(float)
        model = XGBoostClassifier(num_round=10).fit_arrays(X, yb)
        from transmogrifai_tpu.models import GBTClassifierModel
        assert isinstance(model, GBTClassifierModel)

    def test_gbt_classifier_remains_binary_only(self):
        X, y = _three_class()
        with pytest.raises(ValueError, match="binary"):
            GBTClassifier().fit_arrays(X, y)

    def test_quality_competitive_with_rf(self):
        # VERDICT r3 item 5 done-criterion: boosted multiclass quality
        # in the same class as the RF winner
        X, y = _three_class()
        holdout = slice(0, 150)
        train = slice(150, None)
        xgb = XGBoostClassifier(num_round=20, max_depth=3).fit_arrays(
            X[train], y[train])
        rf = RandomForestClassifier(num_trees=30, max_depth=6).fit_arrays(
            X[train], y[train])
        acc_x = float(np.mean(xgb.predict_arrays(X[holdout]).data
                              == y[holdout]))
        acc_r = float(np.mean(rf.predict_arrays(X[holdout]).data
                              == y[holdout]))
        assert acc_x >= acc_r - 0.05, (acc_x, acc_r)

    def test_save_load_round_trip(self, tmp_path):
        from transmogrifai_tpu.workflow.persistence import (stage_from_json,
                                                            stage_to_json)
        X, y = _three_class(n=240)
        model = XGBoostClassifier(num_round=5, max_depth=3).fit_arrays(
            X, y)
        arrays = {}
        doc = stage_to_json(model, arrays)
        loaded = stage_from_json(doc, arrays)
        np.testing.assert_allclose(loaded.predict_raw(X[:20]),
                                   model.predict_raw(X[:20]))

    def test_multiclass_search_includes_xgb(self):
        # the multiclass opt-in pool exposes XGBoostClassifier
        # (reference modelTypesToUse selection)
        from transmogrifai_tpu.selector import (
            MultiClassificationModelSelector, SelectedModel)
        from transmogrifai_tpu.models import NaiveBayes
        X, y = _three_class(n=330)
        sel = MultiClassificationModelSelector.with_cross_validation(
            num_folds=2, stratify=True, splitter=None,
            model_types_to_use=["XGBoostClassifier",
                                "RandomForestClassifier"],
            models=None)
        names = {type(est).__name__ for est, _ in sel.models}
        assert names == {"XGBoostClassifier", "RandomForestClassifier"}
        # shrink grids for test speed
        sel.models = [(est.with_params(**(
            {"num_round": 5} if type(est).__name__ == "XGBoostClassifier"
            else {"num_trees": 10})),
            grid[:2]) for est, grid in sel.models]
        best = sel.fit_arrays(X, y)
        assert best.summary is not None
        fams = {r.model_name for r in best.summary.validation_results}
        assert "XGBoostClassifier" in fams
        finite = [v for r in best.summary.validation_results
                  for v in r.metric_values
                  if r.model_name == "XGBoostClassifier"]
        assert all(np.isfinite(v) for v in finite)


class TestSoftmaxFoldGrid:
    """Fused multiclass fold×grid kernels (r5): the softmax booster now
    has the same device-resident search path as every other family."""

    def _data(self, n=240, d=5, F=3):
        rng = np.random.default_rng(3)
        X = rng.normal(size=(n, d))
        y = np.clip((X[:, 0] > -0.5).astype(int) + (X[:, 1] > 0.5),
                    0, 2).astype(float)
        masks = np.ones((F, n))
        for f in range(F):
            masks[f, f::F] = 0.0
        nv = n // F
        Xv = np.stack([X[masks[f] == 0][:nv] for f in range(F)])
        yv = np.stack([y[masks[f] == 0][:nv] for f in range(F)])
        return X, y, masks, Xv, yv

    def test_eval_matches_host_exactly_under_fold_edges(self, monkeypatch):
        from transmogrifai_tpu.evaluators import \
            MultiClassificationEvaluator
        from transmogrifai_tpu.models.trees import XGBoostClassifier
        monkeypatch.setenv("TX_TREE_EDGES", "fold")
        X, y, masks, Xv, yv = self._data()
        ev = MultiClassificationEvaluator()
        est = XGBoostClassifier(num_round=4)
        grid = [{"max_depth": dd, "min_child_weight": m}
                for dd in (3, 4) for m in (1.0, 5.0)]
        mm = est.eval_fold_grid_arrays(X, y, masks, grid, Xv, yv,
                                       ev.device_metric_spec())
        assert mm.shape == (3, 4) and np.isfinite(mm).all()
        for f in range(3):
            tr = masks[f] > 0
            for gi, p in enumerate(grid):
                model = est.with_params(**p).fit_arrays(X[tr], y[tr])
                host = ev.metric_from(
                    ev.evaluate_arrays(yv[f],
                                       model.predict_arrays(Xv[f])))
                assert abs(host - mm[f, gi]) < 1e-9

    def test_fold_grid_models_match_sequential(self, monkeypatch):
        from transmogrifai_tpu.models.trees import XGBoostClassifier
        monkeypatch.setenv("TX_TREE_EDGES", "fold")
        X, y, masks, _, _ = self._data()
        est = XGBoostClassifier(num_round=4)
        grid = [{"max_depth": 3}, {"max_depth": 4}]
        ms = est.fit_fold_grid_arrays(X, y, masks, grid)
        tr = masks[1] > 0
        seq = est.with_params(**grid[0]).fit_arrays(X[tr], y[tr])
        np.testing.assert_array_equal(ms[1][0].feats, seq.feats)
        np.testing.assert_array_equal(ms[1][0].leaves, seq.leaves)

    def test_depth_block_models_match_static(self, monkeypatch):
        """Softmax lanes under the ``blocks`` depth mode come back at their
        own depth, bit-equal to the per-depth ``static`` programs' (the
        (R, K, H) heaps and (R, K, L) leaves of each block)."""
        from transmogrifai_tpu.models import trees
        from transmogrifai_tpu.models.trees import XGBoostClassifier
        X, y, masks, _, _ = self._data()
        est = XGBoostClassifier(num_round=3)
        grid = [{"max_depth": 2}, {"max_depth": 4}]
        monkeypatch.setattr(trees, "_depth_mode", lambda: "static")
        ms = est.fit_fold_grid_arrays(X, y, masks[:1], grid)
        monkeypatch.setattr(trees, "_depth_mode", lambda: "blocks")
        mk = est.fit_fold_grid_arrays(X, y, masks[:1], grid)
        for gi in range(2):
            np.testing.assert_array_equal(ms[0][gi].feats, mk[0][gi].feats)
            np.testing.assert_array_equal(ms[0][gi].leaves,
                                          mk[0][gi].leaves)
            assert ms[0][gi].depth == mk[0][gi].depth == grid[gi]["max_depth"]
            assert mk[0][gi].feats.shape[-1] == 2 ** mk[0][gi].depth - 1
