"""ModelSelector: automated model selection — pillar #3.

TPU-native port of core/src/main/scala/com/salesforce/op/stages/impl/
selector/{ModelSelector.scala:74,136, ModelSelectorSummary.scala:59}. The
selector is an estimator over (label, features): it prepares the data
with an optional splitter (balance / cut), validates every candidate
(family x grid point) under CV or TVS, refits the winner on the full
prepared training set, and emits a ``SelectedModel`` carrying the full
``ModelSelectorSummary`` (every model x grid x metric).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..evaluators.base import EvaluationMetrics, Evaluator
from ..features.columns import PredictionColumn
from ..models.base import PredictionModel, Predictor
from ..observability import trace as _trace
from .splitters import Splitter, SplitterSummary
from .validator import BestEstimator, CrossValidation, ValidationResult, \
    _ValidatorBase

__all__ = ["ModelSelector", "SelectedModel", "ModelSelectorSummary"]


def _is_device_array(x) -> bool:
    try:
        import jax
        return isinstance(x, jax.Array)
    except (ImportError, AttributeError):  # pragma: no cover - old jax
        return False


@dataclass
class ModelSelectorSummary:
    """Full validation record (reference ModelSelectorSummary.scala:59)."""
    validation_type: str = ""
    validation_parameters: Dict = field(default_factory=dict)
    data_prep_parameters: Dict = field(default_factory=dict)
    data_prep_results: Dict = field(default_factory=dict)
    evaluation_metric: str = ""
    problem_type: str = ""
    best_model_name: str = ""
    best_model_uid: str = ""
    best_model_params: Dict = field(default_factory=dict)
    best_validation_metric: float = 0.0
    validation_results: List[ValidationResult] = field(default_factory=list)
    train_evaluation: Optional[EvaluationMetrics] = None
    holdout_evaluation: Optional[EvaluationMetrics] = None
    metric_larger_better: bool = True
    #: multi-fidelity racing telemetry (selector/racing.py
    #: RacingCrossValidation.last_report): rung schedule, budgets,
    #: pruned counts. Empty — and absent from the JSON — under exact
    #: validation, keeping default summaries byte-identical.
    racing: Dict = field(default_factory=dict)
    #: quarantine ledger (runtime/errors.QuarantineRecord.to_json rows):
    #: families removed from this search and why (OOM, XlaRuntimeError,
    #: poisoned metrics, deadline). Empty — and absent from the JSON —
    #: on a fault-free search, keeping default summaries byte-identical
    #: to pre-runtime output.
    quarantined: List[Dict] = field(default_factory=list)

    def to_json(self) -> dict:
        out = {
            "validationType": self.validation_type,
            "validationParameters": self.validation_parameters,
            "dataPrepParameters": self.data_prep_parameters,
            "dataPrepResults": self.data_prep_results,
            "evaluationMetric": self.evaluation_metric,
            "problemType": self.problem_type,
            "bestModelName": self.best_model_name,
            "bestModelUID": self.best_model_uid,
            "bestModelParams": self.best_model_params,
            "bestValidationMetric": self.best_validation_metric,
            "validationResults": [r.to_json()
                                  for r in self.validation_results],
            "metricLargerBetter": self.metric_larger_better,
            "trainEvaluation": (self.train_evaluation.to_json()
                                if self.train_evaluation else None),
            # RawMetrics fallbacks re-record the ORIGINAL class name so
            # a later load with the class importable rebuilds the type
            "trainEvaluationClass": (
                getattr(self.train_evaluation, "class_name", "")
                or type(self.train_evaluation).__name__
                if self.train_evaluation else None),
            "holdoutEvaluation": (self.holdout_evaluation.to_json()
                                  if self.holdout_evaluation else None),
            "holdoutEvaluationClass": (
                getattr(self.holdout_evaluation, "class_name", "")
                or type(self.holdout_evaluation).__name__
                if self.holdout_evaluation else None),
        }
        if self.racing:
            out["racing"] = self.racing
        if self.quarantined:
            out["quarantined"] = self.quarantined
        return out

    @classmethod
    def from_json(cls, d: dict) -> "ModelSelectorSummary":
        """Inverse of :meth:`to_json` (model save/load)."""
        from ..evaluators.base import metrics_from_json

        def metrics(which: str):
            payload = d.get(which)
            name = d.get(which + "Class")
            return (metrics_from_json(name, payload)
                    if payload is not None and name else None)

        return cls(
            validation_type=d.get("validationType", ""),
            validation_parameters=d.get("validationParameters") or {},
            data_prep_parameters=d.get("dataPrepParameters") or {},
            data_prep_results=d.get("dataPrepResults") or {},
            evaluation_metric=d.get("evaluationMetric", ""),
            problem_type=d.get("problemType", ""),
            best_model_name=d.get("bestModelName", ""),
            best_model_uid=d.get("bestModelUID", ""),
            best_model_params=d.get("bestModelParams") or {},
            best_validation_metric=d.get("bestValidationMetric", 0.0),
            validation_results=[ValidationResult.from_json(r)
                                for r in d.get("validationResults", [])],
            train_evaluation=metrics("trainEvaluation"),
            holdout_evaluation=metrics("holdoutEvaluation"),
            metric_larger_better=d.get("metricLargerBetter", True),
            racing=d.get("racing") or {},
            quarantined=d.get("quarantined") or [],
        )

    def pretty(self) -> str:
        """Human summary (reference summaryPretty,
        OpWorkflowModel.scala:204)."""
        lines = [
            f"Selected model: {self.best_model_name} "
            f"({self.evaluation_metric}={self.best_validation_metric:.4f} "
            f"under {self.validation_type})",
            f"Best params: {self.best_model_params}",
            "Validation results (mean metric per grid point):",
        ]
        sign = -1.0 if self.metric_larger_better else 1.0

        def rank(r):  # non-finite metrics sort last
            m = r.mean_metric
            return sign * m if np.isfinite(m) else np.inf

        for r in sorted(self.validation_results, key=rank):
            # racing records annotate their trajectory (a pruned
            # candidate's low-fidelity mean is not comparable to a
            # full-CV one); exact records render exactly as before
            racing = ""
            if r.rung is not None:
                racing = (f"  [pruned@rung{r.pruned_at}]"
                          if r.pruned_at is not None
                          else "  [finalist]")
            lines.append(f"  {r.model_name}[{r.grid_index}] "
                         f"{r.params} -> {r.mean_metric:.4f}{racing}")
        if self.quarantined:
            lines.append("Quarantined families (search degraded to "
                         "survivors; docs/resilience.md):")
            for q in self.quarantined:
                retries = (f" after {q.get('retries')} retries"
                           if q.get("retries") else "")
                lines.append(f"  {q.get('family')}: [{q.get('kind')}] "
                             f"{q.get('reason')}{retries}")
        return "\n".join(lines)


class SelectedModel(PredictionModel):
    """The winning fitted model + selection summary (reference
    SelectedModel, ModelSelector.scala:214). Delegates prediction to the
    wrapped inner model."""

    def __init__(self, inner: PredictionModel = None,
                 summary: Optional[ModelSelectorSummary] = None,
                 uid: Optional[str] = None):
        super().__init__(operation_name="modelSelector", uid=uid)
        self.inner = inner
        self.summary = summary

    def predict_arrays(self, X: np.ndarray) -> PredictionColumn:
        return self.inner.predict_arrays(X)

    # compiled-serving lowering delegates to the winning model so the
    # fused program embeds ITS kernel (serving/plan.py)
    def raw_arrays(self, X):
        return self.inner.raw_arrays(X)

    def supports_arrays(self) -> bool:
        return self.inner is not None and self.inner.supports_arrays()

    def prediction_from_raw(self, raw: np.ndarray) -> PredictionColumn:
        return self.inner.prediction_from_raw(raw)


def models_x_folds(model) -> int:
    """Total (candidate, fold) evaluations recorded by the selector(s)
    in a fitted workflow model — the unit of the north-star throughput
    metric (BASELINE.json). Shared by chip_smoke.py, the benchmark's
    search jobs and examples/multicore_bench.py so their rows stay
    comparable."""
    return sum(
        len(r.metric_values)
        for s in model.stages()
        if isinstance(s, SelectedModel) and s.summary is not None
        for r in s.summary.validation_results)


class ModelSelector(Predictor):
    """Run candidates x grids under a validator, pick the winner
    (reference ModelSelector.scala:74)."""

    def __init__(self,
                 models: Sequence[Tuple[Predictor, Sequence[Dict]]] = (),
                 validator: Optional[_ValidatorBase] = None,
                 splitter: Optional[Splitter] = None,
                 problem_type: str = "",
                 validation: str = "exact",
                 eta: Optional[int] = None,
                 min_fidelity: Optional[float] = None,
                 checkpoint_dir: Optional[str] = None,
                 retry_policy=None,
                 family_deadline: Optional[float] = None,
                 uid: Optional[str] = None):
        super().__init__(uid=uid)
        if validation not in ("exact", "racing"):
            raise ValueError(
                f"validation must be 'exact' or 'racing', got "
                f"{validation!r}")
        if validation == "racing" and validator is not None:
            # multi-fidelity successive halving (selector/racing.py):
            # same folds/seed/evaluator as the exact validator, but
            # losing candidates stop training early. Opt-in — the
            # default stays exact full CV with a bit-identical winner.
            from .racing import RacingCrossValidation
            if isinstance(validator, RacingCrossValidation):
                pass
            elif isinstance(validator, CrossValidation):
                validator = RacingCrossValidation.from_cross_validation(
                    validator, eta=eta, min_fidelity=min_fidelity)
            else:
                raise ValueError(
                    "validation='racing' requires a CrossValidation "
                    "validator (train/validation split has a single "
                    "fold — nothing to race)")
        self.models = list(models)
        self.validator = validator
        self.splitter = splitter
        self.problem_type = problem_type
        #: fault-tolerant runtime knobs (runtime/, docs/resilience.md):
        #: journal completed (family, cands, rung) evaluations under
        #: this directory so an interrupted search resumes via
        #: ``Workflow.train(resume_from=...)`` with zero re-dispatch
        self.checkpoint_dir = checkpoint_dir
        #: RetryPolicy for transient (preemption/RESOURCE_EXHAUSTED-
        #: shaped) dispatch failures; None = TX_RETRY_* env defaults
        self.retry_policy = retry_policy
        #: per-family dispatch deadline in wall-clock seconds (None =
        #: off; also TX_FAMILY_DEADLINE_S)
        self.family_deadline = family_deadline
        #: pre-computed winner from workflow-level CV (reference
        #: findBestEstimator, ModelSelector.scala:113): when set, fit
        #: skips validation and refits this estimator on the full data
        self.best_estimator: Optional[BestEstimator] = None
        #: (train_idx, test_idx) reserved by workflow-level CV BEFORE
        #: the fold search — consumed by fit so search and final fit
        #: share ONE split structurally (not by re-derivation)
        self.preset_split = None

    def fit_columns(self, cols) -> SelectedModel:
        """Overrides the Predictor boundary: a feature matrix the
        compiled prepare plan left on device (plans/prepare.py) feeds
        the search AS-IS — the fold gathers, stacked validation arrays
        and family kernels all consume it without a host round-trip
        (the label is tiny and host-side by construction)."""
        y = np.asarray(cols[0].data, dtype=np.float64)
        data = cols[1].data
        X = data if _is_device_array(data) \
            else np.asarray(data, dtype=np.float64)
        model = self.fit_arrays(X, y)
        model.vector_metadata = cols[1].metadata
        return model

    def fit_arrays(self, X: np.ndarray, y: np.ndarray) -> SelectedModel:
        if not self.models:
            raise ValueError("ModelSelector has no candidate models")
        if self.validator is None:
            raise ValueError("ModelSelector requires a validator")

        # 1. data prep (reference splitter.split + splitter.prepare,
        # ModelSelector.scala:140-152, tuning/Splitter.scala:56,64):
        # reserve a holdout first, then resample the training portion.
        prep_params: Dict = {}
        prep_results: Dict = {}
        X_hold = y_hold = None
        if self.splitter is not None:
            if self.preset_split is not None:
                # workflow-level CV already reserved the holdout; reuse
                # its exact indices (and its estimated resampling plan)
                train_idx, test_idx = self.preset_split
                self.preset_split = None
            else:
                # a fresh fit must not recycle a plan estimated on some
                # earlier dataset (reused selector instances re-validate)
                self.splitter.reset_plan()
                train_idx, test_idx = self.splitter.split(y)
            if len(test_idx):
                X_hold, y_hold = X[test_idx], y[test_idx]
            X_tr, y_tr = X[train_idx], y[train_idx]
            idx = self.splitter.prepare(y_tr)
            Xp, yp = X_tr[idx], y_tr[idx]
            kept = getattr(self.splitter, "labels_kept", None)
            if kept is not None and X_hold is not None:
                # score the holdout only on labels the cutter kept —
                # the refit model cannot predict dropped classes
                hold_mask = np.isin(y_hold, kept)
                X_hold, y_hold = X_hold[hold_mask], y_hold[hold_mask]
                if not len(y_hold):
                    X_hold = y_hold = None
            summ = self.splitter.summary or SplitterSummary()
            prep_params = summ.parameters
            prep_results = summ.results
        else:
            Xp, yp = X, y

        # 2. validation (reference validator.validate) — unless workflow-
        # level CV already found the winner (ModelSelector.scala:136
        # bestEstimator.getOrElse{...}). The preset is CONSUMED so a
        # reused selector instance re-validates on its new data instead
        # of silently recycling a stale winner.
        best: BestEstimator
        if self.best_estimator is not None:
            best, self.best_estimator = self.best_estimator, None
        else:
            # thread the fault-tolerance knobs into the validator for
            # THIS search (runtime/): journal + retry + deadline
            v = self.validator
            if self.checkpoint_dir is not None:
                v.checkpoint_dir = self.checkpoint_dir
            if self.retry_policy is not None:
                v.retry_policy = self.retry_policy
            if self.family_deadline is not None:
                v.family_deadline = self.family_deadline
            best = self.validator.validate(self.models, Xp, yp)
        rt = getattr(self.validator, "last_runtime", None)
        quarantined = ([r.to_json() for r in rt.quarantined]
                       if rt is not None else [])

        # 3. refit winner on the full prepared train set
        # (reference ModelSelector.scala:163) — behind the retry
        # policy: a preemption during the refit must not discard the
        # whole (journaled) search
        from ..runtime.retry import RetryPolicy
        retry = (self.retry_policy
                 or getattr(self.validator, "retry_policy", None)
                 or RetryPolicy.from_env())
        with _trace.span("search.refit", family=best.name):
            inner = retry.call(
                lambda: best.estimator.fit_arrays(Xp, yp),
                description=f"winner-refit:{best.name}")

        # 4. training-set evaluation (reference :172)
        evaluator = self.validator.evaluator
        with _trace.span("search.train_eval"):
            train_eval = evaluator.evaluate_arrays(
                yp, inner.predict_arrays(Xp))
            holdout_eval = None
            if X_hold is not None:
                holdout_eval = evaluator.evaluate_arrays(
                    y_hold, inner.predict_arrays(X_hold))

        summary = ModelSelectorSummary(
            validation_type=type(self.validator).__name__,
            validation_parameters=self.validator.get_params(),
            racing=dict(getattr(self.validator, "last_report", {}) or {}),
            quarantined=quarantined,
            data_prep_parameters=prep_params,
            data_prep_results=prep_results,
            evaluation_metric=evaluator.default_metric,
            problem_type=self.problem_type,
            best_model_name=best.name,
            best_model_uid=best.estimator.uid,
            best_model_params=best.params,
            best_validation_metric=best.metric,
            validation_results=best.results,
            train_evaluation=train_eval,
            holdout_evaluation=holdout_eval,
            metric_larger_better=evaluator.is_larger_better,
        )
        return SelectedModel(inner=inner, summary=summary)
