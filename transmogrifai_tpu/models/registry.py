"""Default model pools for the selector factories.

Centralizes the per-problem-type candidate pools + hyperparameter grids,
mirroring the reference's defaults
(BinaryClassificationModelSelector.scala:57-128 `defaultModelsToUse` =
LR / RandomForest / GBT / LinearSVC — NaiveBayes, DecisionTree and
XGBoost are declared but opt-in via `modelTypesToUse`;
MultiClassificationModelSelector.scala:138-183;
RegressionModelSelector.scala:150-193; grid values from
DefaultSelectorParams.scala:36-59).

minInfoGain is swept over (0.001, 0.01, 0.1) exactly as
DefaultSelectorParams.MinInfoGain prescribes: it is a *traced* scalar
in the batched fold x grid kernels (`_FOREST_TRACED` of
`trees/fold_grid.py`), so the sweep adds vmapped candidate lanes, not
compiles. For the XGB-style
GBT booster the analog is ``gamma`` (min split-loss reduction), swept
over the same values; ``min_child_weight`` (1, 10) plays the
minInstancesPerNode (10, 100) role on the hessian scale.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

from .base import Predictor

__all__ = ["default_binary_models", "default_multiclass_models",
           "default_regression_models", "default_binary_extra_models",
           "default_multiclass_extra_models",
           "default_regression_extra_models"]

#: DefaultSelectorParams.Regularization
_REG = (0.001, 0.01, 0.1, 0.2)
#: DefaultSelectorParams.ElasticNet
_ELASTIC = (0.1, 0.5)
#: DefaultSelectorParams.MaxDepth
_DEPTH = (3, 6, 12)
#: DefaultSelectorParams.MinInstancesPerNode
_MIN_INST = (10, 100)
#: DefaultSelectorParams.MinInfoGain
_MIN_GAIN = (0.001, 0.01, 0.1)
#: DefaultSelectorParams.MinChildWeight (xgboost)
_MIN_CHILD = (1.0, 5.0, 10.0)
#: DefaultSelectorParams.{MaxTrees, MaxIterTree, MaxIterLin}
_NUM_TREES, _GBT_ROUNDS, _MAX_ITER_LIN = 50, 20, 50


def default_binary_models() -> List[Tuple[Predictor, List[Dict]]]:
    """Reference defaultModelsToUse: LR, RF, GBT, SVC
    (BinaryClassificationModelSelector.scala:57-60)."""
    from .linear import LinearSVC, LogisticRegression
    from .trees import GBTClassifier, RandomForestClassifier
    return [
        (LogisticRegression(max_iter=_MAX_ITER_LIN),
         [{"reg_param": r, "elastic_net_param": e}
          for r in _REG for e in _ELASTIC]),
        (RandomForestClassifier(num_trees=_NUM_TREES),
         [{"max_depth": d, "min_instances_per_node": m, "min_info_gain": g}
          for d in _DEPTH for m in _MIN_INST for g in _MIN_GAIN]),
        (GBTClassifier(num_rounds=_GBT_ROUNDS),
         [{"max_depth": d, "min_child_weight": float(m), "gamma": g}
          for d in _DEPTH for m in (1, 10) for g in _MIN_GAIN]),
        (LinearSVC(max_iter=_MAX_ITER_LIN),
         [{"reg_param": r} for r in _REG]),
    ]


def default_binary_extra_models() -> List[Tuple[Predictor, List[Dict]]]:
    """Opt-in families (reference modelsAndParams minus
    defaultModelsToUse): NaiveBayes, DecisionTree, XGBoost."""
    from .bayes import NaiveBayes
    from .trees import DecisionTreeClassifier, XGBoostClassifier
    return [
        (NaiveBayes(), [{"smoothing": 1.0}]),
        (DecisionTreeClassifier(),
         [{"max_depth": d, "min_instances_per_node": m, "min_info_gain": g}
          for d in _DEPTH for m in _MIN_INST for g in _MIN_GAIN]),
        (XGBoostClassifier(),
         [{"max_depth": d, "eta": e, "min_child_weight": m}
          for d in _DEPTH for e in (0.1, 0.3) for m in _MIN_CHILD]),
    ]


def default_multiclass_models() -> List[Tuple[Predictor, List[Dict]]]:
    """Reference MultiClassificationModelSelector defaults: LR, RF, NB,
    DT (MultiClassificationModelSelector.scala:138-183)."""
    from .bayes import NaiveBayes
    from .linear import LogisticRegression
    from .trees import DecisionTreeClassifier, RandomForestClassifier
    return [
        (LogisticRegression(max_iter=_MAX_ITER_LIN),
         [{"reg_param": r, "elastic_net_param": e}
          for r in _REG for e in _ELASTIC]),
        (RandomForestClassifier(num_trees=_NUM_TREES),
         [{"max_depth": d, "min_instances_per_node": m, "min_info_gain": g}
          for d in _DEPTH for m in _MIN_INST for g in _MIN_GAIN]),
        (NaiveBayes(), [{"smoothing": 1.0}]),
        (DecisionTreeClassifier(),
         [{"max_depth": d, "min_instances_per_node": m, "min_info_gain": g}
          for d in _DEPTH for m in _MIN_INST for g in _MIN_GAIN]),
    ]


def default_multiclass_extra_models() -> List[Tuple[Predictor, List[Dict]]]:
    """Opt-in multiclass families: softmax XGBoost (the reference's
    xgboost4j handles K classes via multi:softprob,
    OpXGBoostClassifier.scala:47) and the MLP."""
    from .mlp import MultilayerPerceptronClassifier
    from .trees import XGBoostClassifier
    return [
        (XGBoostClassifier(num_round=_GBT_ROUNDS),
         [{"max_depth": d, "min_child_weight": m}
          for d in _DEPTH for m in _MIN_CHILD]),
        (MultilayerPerceptronClassifier(),
         [{"hidden_layers": h} for h in ((10,), (32, 16))]),
    ]


def default_regression_models() -> List[Tuple[Predictor, List[Dict]]]:
    """Reference RegressionModelSelector defaults: LinReg, RF, GBT, GLM
    + DT in modelsAndParams (RegressionModelSelector.scala:150-193,
    DistFamily gaussian/poisson)."""
    from .glm import GeneralizedLinearRegression
    from .linear import LinearRegression
    from .trees import (DecisionTreeRegressor, GBTRegressor,
                        RandomForestRegressor)
    return [
        (LinearRegression(max_iter=_MAX_ITER_LIN),
         [{"reg_param": r, "elastic_net_param": e}
          for r in _REG for e in _ELASTIC]),
        (RandomForestRegressor(num_trees=_NUM_TREES),
         [{"max_depth": d, "min_instances_per_node": m, "min_info_gain": g}
          for d in _DEPTH for m in _MIN_INST for g in _MIN_GAIN]),
        (GBTRegressor(num_rounds=_GBT_ROUNDS),
         [{"max_depth": d, "min_child_weight": float(m), "gamma": g}
          for d in _DEPTH for m in (1, 10) for g in _MIN_GAIN]),
        (GeneralizedLinearRegression(),
         [{"family": f, "reg_param": r}
          for f in ("gaussian", "poisson") for r in (0.001, 0.01, 0.1)]),
    ]


def default_regression_extra_models() -> List[Tuple[Predictor, List[Dict]]]:
    from .trees import DecisionTreeRegressor, XGBoostRegressor
    return [
        (DecisionTreeRegressor(),
         [{"max_depth": d, "min_instances_per_node": m, "min_info_gain": g}
          for d in _DEPTH for m in _MIN_INST for g in _MIN_GAIN]),
        (XGBoostRegressor(),
         [{"max_depth": d, "eta": e, "min_child_weight": m}
          for d in _DEPTH for e in (0.1, 0.3) for m in _MIN_CHILD]),
    ]
