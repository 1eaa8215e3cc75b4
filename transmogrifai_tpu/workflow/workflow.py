"""The workflow engine: fit a feature DAG, score with the fitted model.

TPU-native re-design of the reference workflow core
(core/src/main/scala/com/salesforce/op/{OpWorkflow.scala:332,
OpWorkflowModel.scala:253, OpWorkflowCore.scala:52} and the DAG executor
core/.../utils/stages/FitStagesUtil.scala:173-305). Differences from the
Spark design:

- Data is a columnar :class:`Dataset` (host numpy feeding XLA device
  arrays), not a Spark DataFrame; a "layer" of the DAG is executed as
  direct columnar kernels instead of one RDD map over row closures
  (FitStagesUtil.applyOpTransformations:96).
- Estimator -> fitted-model DAG rewiring uses
  ``Feature.copy_with_new_stages`` exactly like the reference
  (OpWorkflow.scala:347).
"""
from __future__ import annotations

import logging
import os
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

_log = logging.getLogger(__name__)

from ..evaluators.base import EvaluationMetrics, Evaluator
from ..features.columns import Dataset, FeatureColumn
from ..features.feature import Feature, topo_layers
from ..features.generator import FeatureGeneratorStage
from ..stages.base import Estimator, PipelineStage, Transformer

__all__ = ["Workflow", "WorkflowModel"]


def _unique_raw_features(result_features: Sequence[Feature]) -> List[Feature]:
    uniq: Dict[str, Feature] = {}
    for rf in result_features:
        for f in rf.raw_features():
            uniq.setdefault(f.uid, f)
    return sorted(uniq.values(), key=lambda f: f.name)


def _generate_raw_data(raw_features: Sequence[Feature], data: Any,
                       require_responses: bool) -> Dataset:
    """Materialize raw feature columns from a Dataset or record iterable
    (reference generateRawData, OpWorkflow.scala:222 + readers'
    DataReader.generateDataFrame, readers/.../DataReader.scala:173).

    At score time (``require_responses=False``) absent response features
    become all-NaN columns so non-nullable label types don't block
    label-free scoring.
    """
    from ..readers.data_readers import DataReader
    if isinstance(data, DataReader):
        # (reference reader.generateDataFrame, Reader.scala:168)
        if require_responses:
            data = data.generate_dataset(raw_features)
        else:
            # label-free scoring: a response column the data can't
            # produce becomes all-NaN instead of failing extraction
            predictors = [f for f in raw_features if not f.is_response]
            ds0 = data.generate_dataset(predictors)
            cols0 = {f.name: ds0[f.name] for f in predictors}
            n0 = ds0.n_rows
            for f in raw_features:
                if not f.is_response:
                    continue
                try:
                    cols0[f.name] = data.generate_dataset([f])[f.name]
                except Exception as e:
                    _log.warning(
                        "response %r not extractable from score data "
                        "(%s); substituting an all-NaN column", f.name, e)
                    cols0[f.name] = FeatureColumn(
                        ftype=f.ftype,
                        data=np.full(n0, np.nan, dtype=np.float64))
            data = Dataset(cols0)
    if isinstance(data, Dataset):
        n = data.n_rows
        cols: Dict[str, FeatureColumn] = {}
        for f in raw_features:
            if f.name in data:
                cols[f.name] = data[f.name]
            elif f.is_response and not require_responses:
                cols[f.name] = FeatureColumn(
                    ftype=f.ftype, data=np.full(n, np.nan, dtype=np.float64))
            else:
                raise KeyError(
                    f"Raw feature {f.name!r} not present in input dataset")
        return Dataset(cols)

    records = list(data)
    cols = {}
    for f in raw_features:
        gen = f.origin_stage
        if not isinstance(gen, FeatureGeneratorStage):
            raise TypeError(
                f"Raw feature {f.name!r} has no generator stage")
        if f.is_response and not require_responses:
            # user extract fns may KeyError/None on label-free score data
            def safe(r, fn=gen.extract_fn):
                try:
                    return fn(r)
                except Exception:
                    return None
            vals = [safe(r) for r in records]
            if all(v is None for v in vals):
                cols[f.name] = FeatureColumn(
                    ftype=f.ftype,
                    data=np.full(len(records), np.nan, dtype=np.float64))
                continue
        cols[f.name] = gen.extract_column(records)
    return Dataset(cols)


def _fit_and_transform_layers(
        layers: List[List[PipelineStage]], ds: Dataset, fit: bool,
        listener=None, prefitted: Optional[Dict[str, PipelineStage]] = None
        ) -> Tuple[Dataset, Dict[str, PipelineStage]]:
    """Layer-by-layer DAG execution (reference
    FitStagesUtil.fitAndTransformDAG:213 / fitAndTransformLayer:254):
    estimators in a layer are fitted then their models applied; plain
    transformers are applied directly. ``prefitted`` supplies models
    already fitted on THIS dataset (the workflow-CV pre-pass) so they
    are not fitted twice."""
    import time as _time
    fitted: Dict[str, PipelineStage] = {}
    if listener is not None:
        # per-stage compile/execute split (utils/compile_time.py)
        from ..utils import compile_time
        compile_time.install()

    def timed(stage, phase, fn):
        t0 = _time.perf_counter()
        c0 = compile_time.compile_seconds() if listener is not None else 0.0
        result = fn()
        if listener is not None:
            listener.on_stage_completed(
                stage, phase, _time.perf_counter() - t0, ds.n_rows,
                compile_seconds=compile_time.compile_seconds() - c0)
        return result

    for layer in layers:
        for stage in layer:
            if isinstance(stage, FeatureGeneratorStage):
                continue  # raw features are already materialized
            if isinstance(stage, Estimator):
                if not fit:
                    raise RuntimeError(
                        f"Unfitted estimator {stage!r} in scoring DAG — "
                        "train the workflow first")
                model = (prefitted or {}).get(stage.uid)
                if model is None:
                    model = timed(stage, "fit", lambda: stage.fit(ds))
                fitted[stage.uid] = model
                out = stage.get_output()
                ds = ds.with_column(
                    out.name, timed(
                        stage, "transform",
                        lambda: model.transform_columns(  # tx-lint: disable=TX-J09 (TX_PREPARE=host escape hatch)
                            [ds[f.name] for f in model.input_features])))
            elif isinstance(stage, Transformer):
                ds = timed(stage, "transform",
                           lambda: stage.transform_dataset(ds))  # tx-lint: disable=TX-J09 (TX_PREPARE=host escape hatch)
            else:
                raise TypeError(f"Cannot execute stage {stage!r}")
    return ds, fitted


def check_serializable(result_features: Sequence[Feature]) -> List[str]:
    """Pre-train serializability audit (reference
    OpWorkflow.checkSerializable:265 + ClosureUtils): every feature
    extract fn and stage ctor arg must be importable (module:qualname)
    for the saved model to round-trip; lambdas/closures survive
    in-process scoring but are DROPPED by persistence. Returns the list
    of problems (empty = fully serializable)."""
    problems: List[str] = []

    def fn_importable(fn) -> bool:
        # shared with the persistence encoder so the audit warns about
        # EXACTLY what save would drop (incl. __main__-script functions
        # whose module another process cannot re-import)
        from .persistence import resolve_importable_fn
        return resolve_importable_fn(fn) is not None

    for layer in topo_layers(result_features):
        for stage in layer:
            if isinstance(stage, FeatureGeneratorStage):
                if not fn_importable(stage.extract_fn):
                    problems.append(
                        f"raw feature {stage.get_output().name!r}: "
                        f"extract fn is a lambda/closure (not importable)")
                continue
            for k, v in getattr(stage, "_ctor_args", {}).items():
                if callable(v) and not isinstance(v, type) \
                        and not fn_importable(v):
                    problems.append(
                        f"stage {type(stage).__name__}({stage.uid}): "
                        f"ctor arg {k!r} is a lambda/closure "
                        f"(not importable)")
    return problems


def _validate_distinct_uids(result_features: Sequence[Feature]) -> None:
    """Every stage in the DAG must have a unique uid — duplicate uids
    silently alias fitted models during DAG rewiring (reference
    OpWorkflow.scala:305 validation)."""
    seen: Dict[str, PipelineStage] = {}
    for layer in topo_layers(result_features):
        for stage in layer:
            other = seen.get(stage.uid)
            if other is not None and other is not stage:
                raise ValueError(
                    f"Duplicate stage uid {stage.uid!r}: "
                    f"{type(other).__name__} and {type(stage).__name__}. "
                    f"Each stage instance needs its own uid — don't reuse "
                    f"one stage object with different inputs")
            seen[stage.uid] = stage


def _transform_with_fitted(layers: List[List[PipelineStage]],
                           fitted: Dict[str, PipelineStage],
                           ds: Dataset) -> Dataset:
    """Apply already-fitted stages to new rows (the validation side of a
    workflow-CV fold; reference FittedDAG.transformers application,
    FitStagesUtil.scala:254-292)."""
    for layer in layers:
        for stage in layer:
            if isinstance(stage, FeatureGeneratorStage):
                continue
            if isinstance(stage, Estimator):
                model = fitted[stage.uid]
                out = stage.get_output()
                ds = ds.with_column(out.name, model.transform_columns(  # tx-lint: disable=TX-J09 (per-fold refit segments stay host-side)
                    [ds[f.name] for f in model.input_features]))
            else:
                ds = stage.transform_dataset(ds)  # tx-lint: disable=TX-J09 (per-fold refit segments stay host-side)
    return ds


def cut_dag(result_features: Sequence[Feature]):
    """Split the DAG around the ModelSelector for leakage-free
    workflow-level CV (reference FitStagesUtil.cutDAG:305).

    Returns (selector, during_layers) where ``during_layers`` are the
    selector-ancestor layers from the FIRST stage whose inputs mix a
    response with predictors (e.g. SanityChecker) onward — exactly the
    stages whose full-data fit would leak validation-fold label
    information into model selection. Empty when there is no selector or
    no label-consuming ancestor. Raises on >1 selector (reference
    "at most 1 Model Selector").
    """
    from ..selector.selector import ModelSelector
    layers = topo_layers(result_features)
    selectors = [s for layer in layers for s in layer
                 if isinstance(s, ModelSelector)]
    if len(selectors) > 1:
        raise ValueError(
            f"Workflow can contain at most 1 ModelSelector for "
            f"workflow-level CV; found {len(selectors)}")
    if not selectors:
        return None, []
    ms = selectors[0]
    anc_layers = topo_layers(list(ms.input_features))
    first = None
    for i, layer in enumerate(anc_layers):
        for s in layer:
            if isinstance(s, FeatureGeneratorStage):
                continue
            ins = getattr(s, "input_features", ())
            if (any(f.is_response for f in ins)
                    and any(not f.is_response for f in ins)):
                first = i
                break
        if first is not None:
            break
    if first is None:
        return ms, []
    during = [[s for s in layer if not isinstance(s, FeatureGeneratorStage)]
              for layer in anc_layers[first:]]
    return ms, [l for l in during if l]


class Workflow:
    """Declare result features + input data, then ``train()``
    (reference OpWorkflow.scala:59)."""

    def __init__(self):
        self.result_features: Tuple[Feature, ...] = ()
        self._input_data: Any = None
        self._raw_feature_filter = None
        self._rff_score_data: Any = None
        self._workflow_cv = False
        #: raw features removed by the RawFeatureFilter (reference
        #: blacklistedFeatures on OpWorkflow)
        self.blacklisted_features: Tuple[Feature, ...] = ()
        #: RawFeatureFilterResults after train() (reference
        #: getRawFeatureFilterResults)
        self.raw_feature_filter_results = None

    # -- configuration -----------------------------------------------------
    def set_result_features(self, *features: Feature) -> "Workflow":
        """(reference setResultFeatures:85; stages are derived from the
        feature DAG via topological sort, setStagesDAG:195)"""
        if not features:
            raise ValueError("At least one result feature required")
        self.result_features = tuple(features)
        return self

    def set_input_dataset(self, ds: Dataset) -> "Workflow":
        """(reference setInputDataset:136)"""
        self._input_data = ds
        return self

    def set_input_records(self, records: Iterable[Any]) -> "Workflow":
        """Row records (dicts/objects); raw features are extracted with
        their generator stages (reference setInputRDD)."""
        self._input_data = list(records)
        return self

    def set_reader(self, reader) -> "Workflow":
        """A DataReader supplies (and possibly aggregates) the raw data
        (reference setReader, OpWorkflowCore.scala:121)."""
        self._input_data = reader
        return self

    def with_listener(self, listener) -> "Workflow":
        """Attach a WorkflowListener collecting per-stage metrics
        (reference OpSparkListener wiring, OpWorkflowRunner.scala:326)."""
        self._listener = listener
        return self

    def with_raw_feature_filter(self, rff,
                                score_data: Any = None) -> "Workflow":
        """Enable pre-DAG raw-feature exclusion during ``train()``
        (reference withRawFeatureFilter on OpWorkflow). ``score_data``
        optionally supplies scoring-time data for distribution-shift
        checks."""
        self._raw_feature_filter = rff
        self._rff_score_data = score_data
        return self

    def with_workflow_cv(self) -> "Workflow":
        """Leakage-free workflow-level CV (reference withWorkflowCV,
        OpWorkflowCore.scala:109 + OpWorkflow.scala:388-440): during
        model selection, every label-consuming ancestor stage of the
        ModelSelector (e.g. SanityChecker) is REFIT inside each CV fold
        on that fold's training rows only, so validation metrics carry no
        fold leakage. The winner is then refit on the full data."""
        self._workflow_cv = True
        return self

    # -- introspection -----------------------------------------------------
    def raw_features(self) -> List[Feature]:
        return _unique_raw_features(self.result_features)

    def stages(self) -> List[PipelineStage]:
        return [s for layer in topo_layers(self.result_features)
                for s in layer if not isinstance(s, FeatureGeneratorStage)]

    # -- training ----------------------------------------------------------
    def train(self, validate: str = "warn",
              resume_from: Optional[str] = None) -> "WorkflowModel":
        """Fit all estimators layer-by-layer and return the fitted model
        (reference OpWorkflow.train:332 / fitStages:368).

        ``validate`` runs the pre-flight static analyzer (lint/) over
        the feature DAG BEFORE any data is read, any stage traced or any
        device buffer allocated — the compile-time safety pillar of the
        reference, restored as a millisecond graph walk:

        - ``"strict"``: raise :class:`~..lint.LintError` on any
          error-severity finding (leakage path, cycle, type-contract
          violation, duplicate uid, ...)
        - ``"warn"`` (default): log findings and continue
        - ``"off"``: skip the pre-flight entirely

        ``resume_from`` points the workflow's ModelSelector at a search
        checkpoint directory (docs/resilience.md): completed (family,
        candidates, rung) evaluations journaled by a previous —
        possibly killed — ``train()`` with the same search fingerprint
        replay from disk, and only the missing work is dispatched. The
        resumed search picks the bitwise-identical winner. The same
        directory is also written to, so repeatedly retrying
        ``train(resume_from=d)`` after crashes converges. Equivalent to
        constructing ``ModelSelector(checkpoint_dir=...)``.
        """
        if validate not in ("strict", "warn", "off"):
            raise ValueError(
                f"validate must be 'strict', 'warn' or 'off', "
                f"got {validate!r}")
        if not self.result_features:
            raise ValueError("No result features set")
        if self._input_data is None:
            raise ValueError("No input data set")
        # train is where a trainer process starts using JAX: the search
        # programs it compiles are there for the next process from this
        # checkout (utils/jax_setup placement rule)
        from ..utils.jax_setup import enable_compilation_cache
        enable_compilation_cache()
        if resume_from is not None:
            from ..selector.selector import ModelSelector
            selectors = [s for s in self.stages()
                         if isinstance(s, ModelSelector)]
            if not selectors:
                raise ValueError(
                    "resume_from requires a ModelSelector in the "
                    "workflow DAG — there is no search to resume")
            for s in selectors:
                s.checkpoint_dir = resume_from
        if validate != "off":
            from ..lint import ERROR, LintError, lint_workflow
            findings = lint_workflow(self)
            errors = [f for f in findings if f.severity == ERROR]
            if validate == "strict" and errors:
                raise LintError(errors)
            for f in findings:
                _log.warning("pre-flight lint: %s", f)
        result_features = self.result_features
        self.blacklisted_features = ()
        self.raw_feature_filter_results = None
        raw = self.raw_features()
        ds = _generate_raw_data(raw, self._input_data,
                                require_responses=True)
        if self._raw_feature_filter is not None:
            # (reference generateRawData -> RawFeatureFilter
            #  .generateFilteredRaw, OpWorkflow.scala:222)
            from ..checkers import rewire_without
            score_ds = None
            if self._rff_score_data is not None:
                score_ds = _generate_raw_data(
                    raw, self._rff_score_data, require_responses=False)
            responses = [f for f in raw if f.is_response]
            label = None
            if len(responses) == 1 and responses[0].name in ds \
                    and ds[responses[0].name].kind == "numeric":
                # non-numeric labels (e.g. string classes indexed
                # in-DAG) skip the null-label correlation check
                label = np.asarray(ds[responses[0].name].data,
                                   dtype=np.float64)
            results = self._raw_feature_filter.compute_exclusions(
                raw, ds, score_ds, label=label)
            self.raw_feature_filter_results = results
            if results.excluded_names:
                result_features, removed = rewire_without(
                    result_features, results.excluded_names)
                self.blacklisted_features = tuple(removed)
        _validate_distinct_uids(result_features)
        for problem in check_serializable(result_features):
            _log.warning("serializability: %s — model save/load will "
                         "drop it (reference checkSerializable, "
                         "OpWorkflow.scala:265)", problem)
        prefitted = None
        if self._workflow_cv:
            prefitted = self._find_best_with_workflow_cv(result_features, ds)
        listener = getattr(self, "_listener", None)
        # the train root span: prepare segments, family dispatches,
        # racing rungs and journal replays all nest under it
        # (docs/observability.md; off-by-default, TX_TRACE enables)
        from ..observability import trace as _trace
        with _trace.span("train", rows=ds.n_rows,
                         prepare=os.environ.get("TX_PREPARE", "plan")):
            train_ds, fitted = self._prepare(result_features, ds,
                                             listener, prefitted)
        result = tuple(f.copy_with_new_stages(fitted)
                       for f in result_features)
        if listener is not None:
            listener.on_application_end()
        return WorkflowModel(
            result_features=result, train_dataset=train_ds,
            raw_feature_filter_results=self.raw_feature_filter_results,
            blacklisted_feature_names=[f.name for f
                                       in self.blacklisted_features])

    def _prepare(self, result_features, ds, listener, prefitted):
        """Fit + transform the feature DAG over the training data.

        Default (``TX_PREPARE=plan``): the compiled prepare path
        (plans/prepare.py) — the fitted DAG executes through the SAME
        ``transform_arrays`` kernel library serving uses, fused into
        jitted segment programs, and the training matrices are born on
        device for the selector search (docs/prepare.md).
        ``TX_PREPARE=host`` is the escape hatch: the per-stage host
        ``transform_columns`` walk, exactly the pre-plan behavior. A
        plan that cannot be built degrades to the host path with the
        reason recorded (never silently)."""
        import os
        mode = os.environ.get("TX_PREPARE", "plan")
        if mode not in ("plan", "host"):
            raise ValueError(
                f"TX_PREPARE must be 'plan' or 'host', got {mode!r}")
        layers = topo_layers(result_features)
        if mode == "plan":
            from ..plans import PlanCompileError, PreparePlan
            plan = PreparePlan(result_features, listener=listener)
            try:
                train_ds, fitted = plan.execute(ds, prefitted=prefitted)
                #: introspection: coverage / fit placements / segment
                #: seconds of the most recent train (bench reads this)
                self.last_prepare_plan = plan
                return train_ds, fitted
            except PlanCompileError as e:
                from ..runtime import telemetry as _telemetry
                _telemetry.count("prepare_plan_fallbacks")
                _telemetry.event("prepare_plan_fallback",
                                 error=f"{type(e).__name__}: {e}")
                _log.warning(
                    "compiled prepare unavailable (%s); falling back to "
                    "the host transform_columns path", e)
        self.last_prepare_plan = None
        return _fit_and_transform_layers(layers, ds, fit=True,
                                         listener=listener,
                                         prefitted=prefitted)

    def _find_best_with_workflow_cv(self, result_features, ds
                                    ) -> Optional[Dict[str, PipelineStage]]:
        """Leakage-free model selection (reference OpWorkflow.scala:
        388-440 + OpValidator.applyDAG:228): refit the in-CV DAG segment
        per fold, validate candidates on per-fold matrices, preset the
        winner on the selector. Returns the models fitted by the
        pre-pass (selector ancestors OUTSIDE the in-CV segment, fitted
        on full data) so the final pass reuses instead of refitting
        them; the in-CV segment itself IS refit on full data there.

        The selector's splitter participates in the search exactly as in
        the reference: the holdout is reserved BEFORE folding
        (OpWorkflow.scala:372-376), the balancer/cutter plan is
        estimated once from the search labels
        (OpValidator.prepareStratification:203-226), and each fold's
        train AND validation rows are resampled with that plan after
        the in-CV DAG refit (OpValidator.applyDAG:250-252) — candidate
        ranking happens on balanced data, not just stratified folds."""
        selector, during = cut_dag(result_features)
        if selector is None or not during:
            return None  # nothing label-consuming feeds the selector
        during_uids = {s.uid for layer in during for s in layer}
        label_f, features_f = selector.input_features
        # 1. fit the selector's ancestors OUTSIDE the in-CV segment once
        #    on full data (reference nonCVTS DAG); non-ancestor stages
        #    and in-CV/selector consumers wait for the final pass
        anc_layers = [[s for s in layer
                       if not isinstance(s, FeatureGeneratorStage)
                       and s.uid not in during_uids]
                      for layer in topo_layers(list(selector.input_features))]
        pre, prefitted = _fit_and_transform_layers(
            [l for l in anc_layers if l], ds, fit=True)
        if label_f.name not in pre:
            _log.warning(
                "workflow-level CV skipped: label %r is produced inside "
                "the in-CV DAG segment", label_f.name)
            return prefitted
        # 2. reserve the holdout BEFORE folding so the search never sees
        #    it; the exact indices are preset on the selector so its
        #    final fit reuses THIS split rather than re-deriving one
        #    (structural agreement — no determinism convention to break)
        y_pre = np.asarray(pre[label_f.name].data, dtype=np.float64)
        splitter = selector.splitter
        reserved = None
        if splitter is not None:
            splitter.reset_plan()
            tr_idx, te_idx = splitter.split(y_pre)
            reserved = (tr_idx, te_idx)
            if len(te_idx):
                pre, y_pre = pre.take(tr_idx), y_pre[tr_idx]
            est = getattr(splitter, "estimate", None)
            if est is not None:   # one global resampling plan
                est(y_pre)
        # 3. per fold: refit the in-CV segment on the fold's train rows,
        #    transform its validation rows with those fitted stages,
        #    then apply the splitter's resampling plan to both
        validator = selector.validator
        folds = []
        for train_idx, val_idx in validator._splits(y_pre):
            tr_ds, fitted_cv = _fit_and_transform_layers(
                during, pre.take(train_idx), fit=True)
            val_ds = _transform_with_fitted(during, fitted_cv,
                                            pre.take(val_idx))
            fold = [
                np.asarray(tr_ds[features_f.name].data, dtype=np.float64),
                np.asarray(tr_ds[label_f.name].data, dtype=np.float64),
                np.asarray(val_ds[features_f.name].data, dtype=np.float64),
                np.asarray(val_ds[label_f.name].data, dtype=np.float64)]
            if splitter is not None:
                ridx = splitter.prepare(fold[1])
                vidx = splitter.prepare(fold[3])
                fold = [fold[0][ridx], fold[1][ridx],
                        fold[2][vidx], fold[3][vidx]]
            folds.append(tuple(fold))
        selector.best_estimator = validator.validate_prepared(
            selector.models, folds)
        # preset only once the search SUCCEEDED — a failed search must
        # not leave stale reserved indices for some future fit
        if reserved is not None:
            selector.preset_split = reserved
        return prefitted


class WorkflowModel:
    """A fitted workflow: every origin stage in the result-feature DAG is a
    transformer (reference OpWorkflowModel.scala:58)."""

    def __init__(self, result_features: Tuple[Feature, ...],
                 train_dataset: Optional[Dataset] = None,
                 raw_feature_filter_results=None,
                 blacklisted_feature_names=()):
        self.result_features = tuple(result_features)
        #: transformed training data (all intermediate columns)
        self.train_dataset = train_dataset
        #: RawFeatureFilterResults carried into the fitted model and the
        #: saved op-model.json (reference OpWorkflowModelWriter:75-120 /
        #: ModelInsights.scala:72 — r3 kept them on the Workflow only)
        self.raw_feature_filter_results = raw_feature_filter_results
        self.blacklisted_feature_names = list(blacklisted_feature_names)
        #: directory this model was saved to / loaded from (None for a
        #: purely in-memory model); the serve-time drift sentinel
        #: resolves drift-fingerprints.json through it
        self.model_dir: Optional[str] = None

    def raw_features(self) -> List[Feature]:
        return _unique_raw_features(self.result_features)

    def stages(self) -> List[PipelineStage]:
        return [s for layer in topo_layers(self.result_features)
                for s in layer if not isinstance(s, FeatureGeneratorStage)]

    # -- scoring -----------------------------------------------------------
    def score(self, data: Any = None, keep_intermediate: bool = False,
              engine: str = "columnar") -> Dataset:
        """Transform new data through the fitted DAG
        (reference OpWorkflowModel.score:253). ``data`` is a Dataset or
        record iterable; response features may be absent.

        ``engine`` selects the execution path:

        - ``"columnar"`` (default): per-stage host numpy columnar
          kernels, layer by layer.
        - ``"compiled"``: the serving :class:`ScoringPlan` — the DAG
          fused into shape-bucketed jitted XLA programs with per-stage
          numpy fallback (docs/serving.md). Compiled once per model and
          cached; ~identical results (floating-point associativity
          aside), much faster on large batches.
        """
        if engine not in ("columnar", "compiled"):
            raise ValueError(
                f"engine must be 'columnar' or 'compiled', got {engine!r}")
        if engine == "compiled":
            if keep_intermediate:
                raise ValueError(
                    "keep_intermediate is not supported with "
                    "engine='compiled' (intermediates are fused away "
                    "inside the XLA program)")
            return self.scoring_plan().score(data)
        raw = self.raw_features()
        ds = _generate_raw_data(raw, data, require_responses=False)
        layers = topo_layers(self.result_features)
        scored, _ = _fit_and_transform_layers(layers, ds, fit=False)
        if keep_intermediate:
            return scored
        keep = [f.name for f in raw if f.name in scored] + \
               [f.name for f in self.result_features]
        seen, names = set(), []
        for n in keep:
            if n not in seen:
                seen.add(n)
                names.append(n)
        return scored.select(names)

    def scoring_plan(self, **plan_kwargs):
        """The compiled serving plan for this model (built and compiled
        lazily, cached on the model; see serving/plan.py). Pass
        ``min_bucket``/``max_bucket``/``donate`` to rebuild with a
        different bucket policy."""
        from ..serving import ScoringPlan
        cached = getattr(self, "_scoring_plan", None)
        if cached is None or plan_kwargs:
            cached = ScoringPlan(self, **plan_kwargs).compile()
            self._scoring_plan = cached
        return cached

    def score_and_evaluate(self, data: Any, evaluator: Evaluator,
                           label_feature: Optional[Feature] = None,
                           prediction_feature: Optional[Feature] = None
                           ) -> Tuple[Dataset, EvaluationMetrics]:
        """(reference scoreAndEvaluate:290)"""
        scored = self.score(data)
        self._wire_evaluator(evaluator, label_feature, prediction_feature)
        return scored, evaluator.evaluate_all(scored)

    def evaluate(self, data: Any, evaluator: Evaluator,
                 label_feature: Optional[Feature] = None,
                 prediction_feature: Optional[Feature] = None
                 ) -> EvaluationMetrics:
        """(reference evaluate:318)"""
        return self.score_and_evaluate(
            data, evaluator, label_feature, prediction_feature)[1]

    def _wire_evaluator(self, evaluator: Evaluator,
                        label_feature: Optional[Feature],
                        prediction_feature: Optional[Feature]) -> None:
        if evaluator.label_col is None:
            if label_feature is None:
                responses = [f for f in self.raw_features() if f.is_response]
                if len(responses) != 1:
                    raise ValueError(
                        "Cannot infer label column; pass label_feature")
                label_feature = responses[0]
            evaluator.label_col = label_feature.name
        if evaluator.prediction_col is None:
            pred = (prediction_feature if prediction_feature is not None
                    else self.result_features[-1])
            evaluator.prediction_col = pred.name

    def compute_data_up_to(self, feature: Feature, data: Any) -> Dataset:
        """Materialize all columns needed to produce ``feature``
        (reference computeDataUpTo:105). ``feature`` may be the
        pre-training handle; it is resolved into the fitted DAG by uid."""
        feature = self._resolve(feature)
        raw = _unique_raw_features([feature])
        ds = _generate_raw_data(raw, data, require_responses=False)
        layers = topo_layers([feature])
        out, _ = _fit_and_transform_layers(layers, ds, fit=False)
        return out

    # -- explainability ----------------------------------------------------
    def model_insights(self):
        """Post-hoc explainability report
        (reference OpWorkflowModel.modelInsights:162)."""
        from ..insights import extract_model_insights
        return extract_model_insights(self)

    def summary(self) -> str:
        """JSON summary of all stage metadata (reference summary:182)."""
        import json
        return json.dumps(self.model_insights().to_json(), indent=1,
                          default=str)

    def summary_pretty(self) -> str:
        """(reference summaryPretty:204)"""
        insights = self.model_insights()
        parts = [insights.pretty()]
        sel = insights.selected_model
        if sel:
            from ..selector.selector import SelectedModel
            for s in self.stages():
                if isinstance(s, SelectedModel) and s.summary:
                    parts.append(s.summary.pretty())
                    break
        return "\n\n".join(parts)

    # -- persistence -------------------------------------------------------
    def save(self, path: str) -> None:
        """Persist the fitted DAG to a directory
        (reference OpWorkflowModel.save:218)."""
        from .persistence import save_model
        save_model(self, path)

    @staticmethod
    def load(path: str) -> "WorkflowModel":
        """(reference OpWorkflow.loadModel)"""
        from .persistence import load_model
        return load_model(path)

    def _resolve(self, feature: Feature) -> Feature:
        """Find the fitted-DAG feature with the same uid (features keep
        their uid through copy_with_new_stages)."""
        found: List[Feature] = []

        def visit(f: Feature):
            if f.uid == feature.uid:
                found.append(f)

        for rf in self.result_features:
            rf.traverse(visit)
            if found:
                return found[0]
        raise KeyError(
            f"Feature {feature.name!r} is not part of this workflow model")
