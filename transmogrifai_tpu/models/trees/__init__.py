"""Tree model family: decision tree, random forest, gradient-boosted trees.

TPU-native replacements for the reference's Spark MLlib / XGBoost wrappers:
- OpDecisionTreeClassifier / OpDecisionTreeRegressor
  (core/.../classification/OpDecisionTreeClassifier.scala,
   core/.../regression/OpDecisionTreeRegressor.scala)
- OpRandomForestClassifier / OpRandomForestRegressor
  (core/.../classification/OpRandomForestClassifier.scala)
- OpGBTClassifier / OpGBTRegressor
  (core/.../classification/OpGBTClassifier.scala)
- OpXGBoostClassifier / OpXGBoostRegressor
  (core/.../classification/OpXGBoostClassifier.scala:47 — xgboost4j JNI,
   the reference's only native-C++ compute; see SURVEY.md §2.9)

Design (histogram GBDT, XLA-first — no CUDA/Rabit translation):

- Features are quantile-binned once into <= ``max_bins`` integer bins
  (MLlib ``maxBins``/XGBoost ``tree_method=hist`` equivalent), every
  feature's bins PACKED into one flat axis.
- Trees grow **level-wise** with ACTIVE-NODE SLOT COMPRESSION (deep levels
  of a complete tree are mostly empty; histograms cover only occupied
  nodes): every level computes per-(slot, packed-bin) statistic histograms,
  on the chip as one-hot contractions on the MXU (``matmul``: the slot
  one-hot, the statistics and the bin indicator; ``segment_sum`` is the
  CPU's reference path), turns them into split gains with one segmented
  cumulative sum over the packed axis, and advances every row one level.
  No data-dependent shapes anywhere, so the whole builder jits into one XLA
  program; a forest is a ``lax.scan`` (or ``vmap``) of that program over
  bootstrap keys (with per-tree feature pools bounding histogram width) and
  boosting is a ``lax.scan`` of it over rounds with margin updates.
- Nodes that fail the gain/min-weight checks emit a +inf threshold
  ("everything goes left"), which makes dead branches self-propagating
  without ragged control flow.
- Split histograms sum 2nd-order grad/hess stats (XGBoost objective) or
  class-count/variance stats (MLlib gini/variance impurity).

Distributed fit: histograms are linear in rows, so data-parallel multi-chip
training is a ``psum`` of per-shard histograms over ICI — the TPU
equivalent of XGBoost's Rabit allreduce (see parallel/cv.py for the mesh
machinery).

The modules, each importing only those above it:

- ``forms``: every choice of path by backend and shape;
- ``design``: binning, the packed design, feature pools, the design memo;
- ``grower``: the level grower and the split criteria;
- ``bodies``: the jitted fit programs and the row-sharded kernels;
- ``walk``: walks of finished heaps (the scoring programs);
- ``fitted``: the fitted models and ``batch_predict_raw``;
- ``fold_grid``: the fold x grid programs and their drivers, the one module
  that opens spans;
- ``estimators``: the estimators.
"""
from __future__ import annotations

from .design import clear_design_cache
from .estimators import (DecisionTreeClassifier, DecisionTreeRegressor,
                         GBTClassifier, GBTRegressor, RandomForestClassifier,
                         RandomForestRegressor, XGBoostClassifier,
                         XGBoostRegressor)
from .fitted import (GBTClassifierModel, GBTMulticlassClassifierModel,
                     GBTRegressorModel, TreeEnsembleClassifierModel,
                     TreeEnsembleRegressorModel, batch_predict_raw)
from .fold_grid import tree_kernel_compiles
# the regression cell's harness asks the package for it by name
# (``hasattr(trees, "_variance_stats")``): nothing here calls it
from .grower import _variance_stats  # noqa: F401

__all__ = [
    "DecisionTreeClassifier", "DecisionTreeRegressor",
    "RandomForestClassifier", "RandomForestRegressor",
    "GBTClassifier", "GBTRegressor",
    "XGBoostClassifier", "XGBoostRegressor",
    "TreeEnsembleClassifierModel", "TreeEnsembleRegressorModel",
    "GBTClassifierModel", "GBTRegressorModel",
    "GBTMulticlassClassifierModel",
]

#: every ``jax.named_scope`` of the package's fold-grid and fit programs
#: (docs/observability.md "Device time by scope"): the ``tree.*`` phases of
#: one level of ``grower._grow_tree``, a forest tree's bootstrap weights and
#: feature pool, a boosting round, the validation metric of the
#: ``*_eval_kernel``s (and of the linear fold-grid programs), one
#: ``fg.<family>`` around each fold-grid program's body (``fg.softmax``: the
#: multinomial logistic lanes, ``fg.bayes``: ``models/bayes.py``; ``fg.glm``:
#: the IRLS lanes of ``models/glm.py``, with ``glm.gram`` and ``glm.solve``
#: inside an iteration), the linear cores' ``lin.*`` (``models/linear.py``,
#: ``parallel/cv.py``), and ``fg.order``, the row gathers that put the table
#: in every fold's own order (``fold_grid._by_fold``). The one list of the
#: package: the benchmark's scope readers take it from this attribute. A
#: scope is a path component of the ``op_name`` of the ops traced under it
#: and exists only while JAX traces: it adds no operation and changes no
#: program's name. ``tree.traverse`` is the walk of finished heaps
#: (``walk._traverse``, either form): the scoring programs'
#: (``jit__predict_leaves``), not a fit's. ``gbt.pick``, inside
#: ``gbt.round``, adds each row's leaf value of a round's finished tree to
#: its margin (``grower._TreeGrower.add_leaf_values``).
SCOPES = ("tree.indicator", "tree.compress", "tree.hist", "tree.node_sums",
          "tree.split", "tree.route", "tree.bootstrap", "tree.pool",
          "gbt.round", "fg.metric", "fg.gbt", "fg.forest",
          "fg.gbt_softmax", "fg.linear", "lin.standardize", "lin.solve",
          "fg.softmax", "fg.bayes", "fg.glm", "glm.gram", "glm.solve",
          "fg.order", "tree.traverse", "gbt.pick")
