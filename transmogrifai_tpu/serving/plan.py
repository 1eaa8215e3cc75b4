"""ScoringPlan: freeze a fitted DAG into batched, shape-bucketed XLA
programs.

The fit side already batches whole hyperparameter grids into single
vmapped XLA programs (parallel/cv.py); this module gives the SERVING
side the same treatment. Instead of walking the DAG stage-by-stage in
host numpy per batch (workflow.py) — or record-by-record in a Python
loop (local/scoring.py) — a plan:

1. **Compiles the DAG once.** ``topo_layers`` is walked and every
   fitted stage is asked for an array-level kernel
   (``Transformer.transform_arrays``, stages/base.py). Stages that
   lower are composed into ONE traced function; XLA then fuses the
   whole feature pipeline + model predict into a single program
   (operator-fusion rationale: arxiv 2301.13062 — hand the compiler
   the program, not one stage at a time). Stages that cannot lower run
   through their numpy ``transform_columns`` fallback, host-side,
   before (``pre``) or after (``post``) the device program; coverage
   is reported, parity is guaranteed either way.
2. **Buckets batch shapes.** Incoming batches are padded up to
   power-of-two row buckets with a validity mask, so arbitrary request
   sizes hit a handful of cached compilations instead of recompiling
   per batch size. Batches beyond the largest bucket are chunked.
   ``utils/jax_setup.enable_compilation_cache`` is enabled at plan
   compile, so a warm-started server skips XLA entirely.
3. **Scores in one round-trip.** One host->device transfer of the
   encoded raw arrays, one fused program, one device->host transfer of
   the requested outputs — with input-buffer donation on accelerator
   backends.

``plan_compiles()`` counts distinct (plan, bucket) programs — the
compile diagnostic (same idiom as
models/trees.tree_kernel_compiles): a repeated same-bucket batch adds
zero.
"""
from __future__ import annotations

import logging
from dataclasses import dataclass, field
from typing import (Any, Callable, Dict, List, Optional, Sequence,
                    Tuple)

import numpy as np

from ..features.columns import Dataset, FeatureColumn, PredictionColumn
from ..features.feature import topo_layers
from ..features.generator import FeatureGeneratorStage
from ..plans.common import (DEFAULT_MAX_BUCKET, DEFAULT_MIN_BUCKET,
                            PlanCompileError, PlanCoverage,
                            PlanStep as _Step, bucket_for,
                            bucket_profile as _shared_bucket_profile,
                            bucket_section as _bucket_section, compiles,
                            empty_raw_dataset as _empty_raw_dataset,
                            fallback_reason as _shared_fallback_reason,
                            default_lattice, normalize_lattice,
                            pad_rows as _pad_rows, plan_seq,
                            record_compile, record_rows)
from ..observability import trace as _trace
from ..runtime import telemetry as _telemetry
from ..runtime.faults import maybe_inject
from ..runtime.retry import RetryPolicy
from ..stages.base import Transformer
from ..types import Prediction
from .guard import (AdmissionPolicy, BreakerOpenError, CircuitBreaker,
                    GuardedScoreResult, GuardReason, OutputGuard,
                    ServingGuard, _invalidate_rows)

_log = logging.getLogger(__name__)

__all__ = ["ScoringPlan", "EncodedScoreBatch", "PlanCoverage",
           "PlanCompileError", "plan_compiles", "bucket_for",
           "DEFAULT_MIN_BUCKET", "DEFAULT_MAX_BUCKET"]


def plan_compiles() -> int:
    """Distinct compiled scoring programs so far in this process (the
    compile-count diagnostic of the scoring path)."""
    return compiles("score")


@dataclass
class EncodedScoreBatch:
    """A raw Dataset host-encoded, chunked, padded and masked — ready
    for device dispatch. Splitting :meth:`ScoringPlan.score_raw_dataset`
    into :meth:`~ScoringPlan.encode_raw_dataset` +
    :meth:`~ScoringPlan.dispatch_encoded` lets the serving loop
    double-buffer: batch k+1's host-side boxing/encoding overlaps batch
    k's in-flight device program (serving/server.py)."""
    #: raw Dataset AFTER the plan's "pre"-phase host fallbacks ran
    ds: Dataset
    n_rows: int
    #: (bucket, padded input arrays, validity mask, live rows) per chunk
    chunks: List[Tuple[int, tuple, np.ndarray, int]] = \
        field(default_factory=list)


class ScoringPlan:
    """A fitted ``WorkflowModel`` frozen into jitted, shape-bucketed
    scoring programs. Build once per model, reuse per batch:

    >>> plan = ScoringPlan(model).compile()
    >>> scored = plan.score(records)        # Dataset of result columns
    """

    def __init__(self, model, min_bucket: int = DEFAULT_MIN_BUCKET,
                 max_bucket: int = DEFAULT_MAX_BUCKET,
                 donate: Optional[bool] = None,
                 lattice: Optional[Sequence[int]] = None):
        self.model = model
        #: explicit bucket lattice (tuning/lattice.py choose_lattice)
        #: — None keeps the default power-of-two ladder over
        #: [min_bucket, max_bucket] bitwise; a lattice overrides the
        #: range args (its first/last rungs become min/max)
        self.lattice: Optional[Tuple[int, ...]] = \
            normalize_lattice(lattice) if lattice else None
        if self.lattice:
            self.min_bucket = self.lattice[0]
            self.max_bucket = self.lattice[-1]
        else:
            self.min_bucket = int(min_bucket)
            self.max_bucket = int(max_bucket)
        if self.min_bucket < 1 or self.max_bucket < self.min_bucket:
            raise ValueError(
                f"bad bucket range [{min_bucket}, {max_bucket}]")
        #: donate input buffers to the program (skips one device copy);
        #: None = auto (on for accelerators, off for CPU which does not
        #: implement donation and would warn per call)
        self.donate = donate
        self._plan_id = plan_seq()
        self._compiled = False
        self.coverage = PlanCoverage()
        #: serving guardrails (guard.py) — None means DISABLED: the
        #: default score path is the exact pre-guard code, byte-
        #: identical output (asserted in tests/test_serving_guard.py)
        self.guard: Optional[ServingGuard] = None
        #: online drift sentinel (sentinel.py) — None means disabled
        self.sentinel = None
        #: GuardedScoreResult of the most recent guarded batch
        self.last_guard_result: Optional[GuardedScoreResult] = None
        self._deadline_pool = None
        #: live rows dispatched per bucket (bucket_profile denominator)
        self._bucket_rows: Dict[int, int] = {}
        #: bucket -> deserialized AOT executable (artifacts/loader.py)
        #: — when present for a bucket, dispatch calls it INSTEAD of
        #: the jitted fn: same program, zero serve-process compiles
        self._aot_executables: Dict[int, Any] = {}
        #: the artifact manifest the executables came from (None =
        #: live-compiled plan)
        self.aot_manifest: Optional[dict] = None

    # -- compilation -------------------------------------------------------
    def compile(self) -> "ScoringPlan":
        """Walk the fitted DAG, classify every stage (device kernel vs
        numpy fallback), probe zero rows through the numpy path for
        output metadata, and build the jitted device program. Idempotent.
        """
        if self._compiled:
            return self
        from ..utils.jax_setup import enable_compilation_cache
        # warm-start serving: persisted XLA artifacts skip compiles
        enable_compilation_cache()
        import jax

        self._raw_features = self.model.raw_features()
        self._result_names = [f.name for f in self.model.result_features]
        self._retry = RetryPolicy.from_env()
        stages = []
        for layer in topo_layers(self.model.result_features):
            for s in layer:
                if isinstance(s, FeatureGeneratorStage):
                    continue
                if not isinstance(s, Transformer):
                    raise PlanCompileError(
                        f"unfitted estimator {s!r} in scoring DAG")
                stages.append(s)

        self._proto_cols = self._probe_zero_rows(stages)
        # graceful degradation loop: a stage kernel that fails to trace
        # is DEMOTED to its host transform_columns fallback (with the
        # reason in coverage + a loud warning) and the plan rebuilds —
        # a bad kernel costs that stage's speedup, never the plan
        self._demoted: Dict[str, str] = {}
        for _ in range(len(stages) + 1):
            self.coverage = PlanCoverage()
            self._classify(stages)
            self._build_device_fn(jax)
            culprit = self._verify_device_fn(jax)
            if culprit is None:
                break
            uid, stage_name, reason = culprit
            self._demoted[uid] = reason
            _telemetry.count("plan_fallbacks")
            _telemetry.event("plan_fallback", stage=stage_name,
                             reason=reason)
            _log.warning(
                "scoring plan: stage %s failed to compile (%s); "
                "falling back to its host transform_columns path",
                stage_name, reason)
        self._compiled = True
        return self

    # -- AOT artifacts (artifacts/, docs/aot_artifacts.md) -----------------
    def attach_artifacts(self, execs: Dict[int, Any],
                         manifest: Optional[dict] = None
                         ) -> "ScoringPlan":
        """Route per-bucket dispatch through deserialized AOT
        executables (artifacts/loader.load_or_compile is the sanctioned
        caller). The executables ARE the programs the live path would
        compile — bitwise-identical outputs, asserted in
        tests/test_aot_artifacts.py."""
        self._aot_executables = dict(execs)
        self.aot_manifest = manifest
        return self

    def aot_active(self) -> bool:
        return bool(self._aot_executables)

    def aot_summary(self) -> Optional[dict]:
        """The snapshot/metrics slice: which artifact store this plan
        serves from (serving/state.py records it per model)."""
        if not self._aot_executables:
            return None
        from ..artifacts.store import manifest_summary
        out = manifest_summary(self.aot_manifest) or {}
        out["loadedBuckets"] = sorted(self._aot_executables)
        return out

    def fallbacks(self) -> int:
        """How many stages of this plan run through the host
        ``transform_columns`` fallback instead of the fused device
        program — including kernels demoted because they failed to
        compile (``coverage`` carries the reasons)."""
        return len(self.coverage.fallback)

    def _probe_zero_rows(self, stages: List[Transformer]
                         ) -> Dict[str, FeatureColumn]:
        """Run the whole DAG over ZERO rows through the numpy path —
        milliseconds, no device code — capturing every intermediate
        column's type/width/metadata so device outputs can be wrapped
        back into columns exactly as the numpy path would build them.
        Prediction outputs are skipped (they carry no metadata)."""
        ds = _empty_raw_dataset(self._raw_features)
        for stage in stages:
            out = stage.get_output()
            if issubclass(stage.static_output_type(), Prediction):
                ds = ds.with_column(
                    out.name, PredictionColumn.from_arrays(np.zeros(0)))
                continue
            try:
                ds = stage.transform_dataset(ds)
            except Exception as e:
                raise PlanCompileError(
                    f"stage {type(stage).__name__}({stage.uid}) failed "
                    f"the zero-row probe: {e!r}") from e
        return {name: ds[name] for name in ds.column_names}

    def _classify(self, stages: List[Transformer]) -> None:
        """Assign each stage to the device graph or a host fallback
        phase. A stage lowers when it has an array kernel AND every
        input is array-feedable; a fallback stage downstream of any
        lowered stage must wait for the device outputs (phase "post"),
        and nothing downstream of a "post" stage can lower (the device
        program runs once)."""
        producer: Dict[str, str] = {f.name: "host"
                                    for f in self._raw_features}
        steps: List[_Step] = []
        for stage in stages:
            out_name = stage.get_output().name
            in_names = tuple(f.name for f in stage.input_features)
            reason = ""
            if stage.uid in getattr(self, "_demoted", {}):
                reason = self._demoted[stage.uid]
            elif not stage.supports_arrays():
                reason = "no array kernel (transform_arrays)"
            else:
                for i, name in enumerate(in_names):
                    src = producer.get(name, "host")
                    if src == "post":
                        reason = (f"input {name!r} is produced by a "
                                  f"host fallback downstream of the "
                                  f"device graph")
                        break
                    if src == "device":
                        if stage.encodes_input(i):
                            reason = (f"input {name!r} needs host "
                                      f"encoding but is produced on "
                                      f"device")
                            break
                        continue
                    # host-materialized input: probe the encoder on the
                    # zero-row proto column
                    try:
                        stage.encode_input_column(
                            i, self._proto_cols[name])
                    except Exception as e:
                        reason = self._fallback_reason(
                            f"input {name!r} not encodable", e)
                        break
            if not reason:
                phase = "device"
                producer[out_name] = "device"
            else:
                upstream = {producer.get(n, "host") for n in in_names}
                phase = "pre" if upstream <= {"host"} else "post"
                producer[out_name] = "host" if phase == "pre" else "post"
                self.coverage.fallback.append(
                    (f"{type(stage).__name__}({out_name})", reason))
            if phase == "device":
                self.coverage.lowered.append(
                    f"{type(stage).__name__}({out_name})")
            steps.append(_Step(stage, out_name, in_names, phase, reason))
        self._steps = steps
        self._producer = producer

        # device inputs: (key, feature name, encoder) — encoders with
        # stage-specific lookups get their own key, identity encodings
        # share the feature name
        self._host_inputs: List[Tuple[str, str, Callable]] = []
        seen_keys = set()
        for step in steps:
            if step.phase != "device":
                continue
            for i, name in enumerate(step.input_names):
                if self._producer.get(name) == "device":
                    continue
                if step.stage.encodes_input(i):
                    key = f"enc:{step.stage.uid}:{i}"
                    enc = (lambda col, s=step.stage, slot=i:
                           s.encode_input_column(slot, col))
                else:
                    key = name
                    enc = (lambda col, s=step.stage, slot=i:
                           s.encode_input_column(slot, col))
                if key not in seen_keys:
                    seen_keys.add(key)
                    self._host_inputs.append((key, name, enc))

        # which device outputs must be materialized back into columns:
        # result features + inputs of host "post" fallbacks
        needed = set(self._result_names)
        for step in steps:
            if step.phase == "post":
                needed.update(step.input_names)
        self._device_outputs = [
            s.out_name for s in steps
            if s.phase == "device" and s.out_name in needed]

    @staticmethod
    def _fallback_reason(what: str, e: Exception) -> str:
        """One-line fallback reason for coverage records (the TX-R01
        contract: a swallowed hot-path exception must surface as a
        recorded degradation, never vanish)."""
        return _shared_fallback_reason(what, e)

    def _verify_device_fn(self, jax):
        """Abstractly trace the composed device program (zero device
        code — ``jax.eval_shape``) and return the first stage whose
        kernel fails as ``(uid, stage_name, reason)``, or None when the
        program traces clean. The compile() loop demotes the culprit to
        the host path and rebuilds."""
        # deterministic test hook: an injected per-stage compile fault
        # demotes exactly like a real trace failure
        for stage, out_name, _ in self._device_steps:
            try:
                maybe_inject("plan", type(stage).__name__, "compile")
            except Exception as e:
                return (stage.uid, f"{type(stage).__name__}({out_name})",
                        self._fallback_reason("injected compile fault",
                                              e))
        if not self._device_steps:
            return None
        sds = {}
        for key, name, enc in self._host_inputs:
            arr = np.asarray(enc(self._proto_cols[name]))
            sds[key] = jax.ShapeDtypeStruct(
                (self.min_bucket,) + arr.shape[1:], arr.dtype)
        env = dict(sds)
        for stage, out_name, keys in self._device_steps:
            try:
                env[out_name] = jax.eval_shape(
                    lambda *a, s=stage: s.transform_arrays(list(a)),
                    *[env[k] for k in keys])
            except Exception as e:
                return (stage.uid, f"{type(stage).__name__}({out_name})",
                        self._fallback_reason("kernel failed abstract "
                                              "trace", e))
        return None

    def _build_device_fn(self, jax) -> None:
        """Compose the lowered kernels into ONE traced function; jit it
        once — per-bucket shapes then hit jit's own compile cache."""
        device_steps = [
            (s.stage,
             s.out_name,
             tuple((f"enc:{s.stage.uid}:{i}"
                    if self._producer.get(n) != "device"
                    and s.stage.encodes_input(i) else n)
                   for i, n in enumerate(s.input_names)))
            for s in self._steps if s.phase == "device"]
        self._device_steps = device_steps
        in_keys = tuple(k for k, _, _ in self._host_inputs)
        out_names = tuple(self._device_outputs)

        def run(inputs, mask):
            env = dict(zip(in_keys, inputs))
            outs = []
            for stage, out_name, keys in device_steps:
                env[out_name] = stage.transform_arrays(
                    [env[k] for k in keys])
            for name in out_names:
                o = env[name]
                outs.append(o * (mask[:, None] if o.ndim == 2 else mask))
            return tuple(outs)

        if self.donate is None:
            self.donate = jax.default_backend() != "cpu"
        donate = (0,) if self.donate else ()
        self._device_fn = jax.jit(run, donate_argnums=donate)  # tx-lint: disable=TX-J02,TX-J06 (one jit per PLAN: compile() runs once per model, each bucket shape cached)

    # -- guardrails --------------------------------------------------------
    def with_guardrails(self, admission: Optional[AdmissionPolicy] = None,
                        output_guard: Optional[OutputGuard] = None,
                        breaker: Optional[CircuitBreaker] = None,
                        deadline_seconds: Optional[float] = None,
                        sentinel: Any = True,
                        thresholds=None) -> "ScoringPlan":
        """Enable the serving guardrails (docs/serving_guardrails.md):
        schema admission + output guards + circuit breaker + per-batch
        deadline, and (``sentinel=True``, the default here) the online
        drift sentinel. Guardrails are OFF unless this is called — the
        default ``score()`` path is byte-identical to the unguarded
        plan. ``sentinel`` may also be a prebuilt
        :class:`~.sentinel.DriftSentinel`."""
        self.guard = ServingGuard(self.model, admission=admission,
                                  output_guard=output_guard,
                                  breaker=breaker,
                                  deadline_seconds=deadline_seconds)
        from .sentinel import DriftSentinel
        if isinstance(sentinel, DriftSentinel):
            self.sentinel = sentinel
        elif sentinel:
            self.sentinel = DriftSentinel.for_model(
                self.model, thresholds=thresholds)
            if self.sentinel is None:
                _log.warning(
                    "drift sentinel unavailable: the model carries no "
                    "training fingerprints (re-save it with this build "
                    "or train in-process); serving without drift "
                    "monitoring")
        return self

    def drift_report(self) -> dict:
        """Per-feature JS divergence of scored traffic vs training
        (sentinel.py). ``{"enabled": False}`` when no sentinel is
        attached."""
        if self.sentinel is None:
            return {"enabled": False}
        report = self.sentinel.drift_report()
        report["enabled"] = True
        return report

    # -- execution ---------------------------------------------------------
    def score(self, data: Any) -> Dataset:
        """Score a Dataset / record iterable / DataReader through the
        plan; returns the raw + result feature columns (the
        ``Workflow.score`` contract). Compiles lazily on first use.

        With guardrails enabled (:meth:`with_guardrails`) this routes
        through :meth:`score_guarded`, stashing the quarantine/
        invalidation ledger on ``last_guard_result``."""
        if self.guard is not None or self.sentinel is not None:
            return self.score_guarded(data).scored
        self.compile()
        from ..workflow.workflow import _generate_raw_data
        ds = _generate_raw_data(self._raw_features, data,
                                require_responses=False)
        return self.score_raw_dataset(ds)

    def score_guarded(self, data: Any) -> GuardedScoreResult:
        """Guarded batch scoring: admission -> masked device scoring
        (or host fallback behind the breaker) -> output guards ->
        sentinel observation. The returned Dataset keeps the FULL row
        count; quarantined/invalidated rows carry NaN outputs and one
        machine-readable reason each."""
        self.compile()
        from ..readers.data_readers import DataReader
        from ..workflow.workflow import _generate_raw_data
        if self.guard is not None \
                and not isinstance(data, (Dataset, DataReader)):
            # record admission materializes the raw Dataset itself:
            # malformed fields become boxable placeholders instead of
            # crashing strict extraction, and the row is masked out
            ds, reasons = self.guard.schema.admit_records(list(data))
            return self._score_guarded_raw(ds, pre_reasons=reasons,
                                           columnar_admission=False)
        ds = _generate_raw_data(self._raw_features, data,
                                require_responses=False)
        return self._score_guarded_raw(ds)

    def _score_guarded_raw(self, ds: Dataset,
                           pre_reasons: Optional[List[GuardReason]] = None,
                           columnar_admission: bool = True
                           ) -> GuardedScoreResult:
        """Core guarded path over a materialized raw Dataset."""
        with _trace.span("score.guarded", rows=ds.n_rows):
            return self._score_guarded_raw_inner(
                ds, pre_reasons=pre_reasons,
                columnar_admission=columnar_admission)

    def _score_guarded_raw_inner(self, ds: Dataset,
                                 pre_reasons: Optional[
                                     List[GuardReason]] = None,
                                 columnar_admission: bool = True
                                 ) -> GuardedScoreResult:
        n = ds.n_rows
        quarantined: List[GuardReason] = list(pre_reasons or [])
        if self.guard is not None and columnar_admission:
            ds, more = self.guard.schema.admit_dataset(ds)
            quarantined.extend(more)
        qmask = np.zeros(n, dtype=bool)
        for r in quarantined:
            if 0 <= r.row < n:
                qmask[r.row] = True
        valid = (~qmask).astype(np.float64)

        breaker = self.guard.breaker if self.guard is not None else None
        used_fallback = False
        try:
            if breaker is not None:
                breaker.before_dispatch()
            scored = self.score_raw_dataset(ds, valid_mask=valid)
            if breaker is not None:
                breaker.record_success()
        except BreakerOpenError as e:
            used_fallback = True
            _telemetry.count("serving_breaker_short_circuits")
            _log.warning("scoring breaker open; host fallback: %s", e)
            scored = self._score_host_fallback(ds)
        except Exception as e:
            # device dispatch failed after retries: trip the breaker
            # and serve this batch through the host columnar fallback
            # (classified + recorded — the TX-R01/TX-R02 contract)
            from ..runtime.errors import BUG, classify_error
            if breaker is None or classify_error(e) == BUG:
                raise
            breaker.record_failure()
            used_fallback = True
            _telemetry.count("serving_device_failures")
            _telemetry.event("serving_fallback",
                             error=f"{type(e).__name__}: {e}",
                             breaker=breaker.state)
            _log.warning(
                "device scoring failed (%s: %s); host fallback "
                "(breaker %s)", type(e).__name__, e, breaker.state)
            scored = self._score_host_fallback(ds)

        # deterministic test hook: poison one output row so the output
        # guard's invalidate path is provable under TX_FAULT_PLAN
        if maybe_inject("serving", "output", "guard") == "nan":
            scored = _poison_first_valid_row(scored, self._result_names,
                                             qmask)

        invalidated: List[GuardReason] = []
        if self.guard is not None:
            scored, invalidated = self.guard.output.check(
                scored, self._result_names, skip_rows=qmask)
        if qmask.any():
            # quarantined rows were masked out of the device batch;
            # their zeroed outputs are garbage by construction — NaN
            # them so nothing downstream mistakes them for scores
            scored = _invalidate_rows(scored, self._result_names, qmask)

        if self.sentinel is not None:
            obs = ds.take(np.flatnonzero(~qmask)) if qmask.any() else ds
            self.sentinel.observe_dataset(obs)

        n_bad = int(qmask.sum())
        _telemetry.count("serving_rows_scored", n - n_bad)
        if n_bad:
            _telemetry.count("serving_rows_quarantined", n_bad)
        if invalidated:
            _telemetry.count("serving_rows_invalidated",
                             len({r.row for r in invalidated}))
        result = GuardedScoreResult(
            scored=scored, quarantined=quarantined,
            invalidated=invalidated, used_host_fallback=used_fallback,
            breaker_state=(breaker.state if breaker is not None
                           else CircuitBreaker.CLOSED))
        self.last_guard_result = result
        return result

    def score_host_columnar(self, ds: Dataset) -> Dataset:
        """The existing host columnar path (per-stage numpy kernels,
        layer by layer) as a whole-batch fallback when the device is
        unavailable — same outputs as ``engine="columnar"``. Public:
        the serving loop routes breaker-open / failed-dispatch batches
        here per tenant (serving/server.py)."""
        from ..workflow.workflow import _fit_and_transform_layers
        _telemetry.count("serving_host_fallback_batches")
        layers = topo_layers(self.model.result_features)
        scored, _ = _fit_and_transform_layers(layers, ds, fit=False)
        return self._select_outputs(scored)

    #: pre-PR-8 internal name, kept for call-site compatibility
    _score_host_fallback = score_host_columnar

    def score_raw_dataset(self, ds: Dataset,
                          valid_mask: Optional[np.ndarray] = None
                          ) -> Dataset:
        """Score an already-materialized raw Dataset (all raw feature
        columns present; absent responses NaN-filled by the caller).
        ``valid_mask`` (guarded path) zeroes quarantined rows inside
        the padded device batch — same shapes, zero recompiles."""
        self.compile()
        return self.dispatch_encoded(
            self.encode_raw_dataset(ds, valid_mask=valid_mask))

    def encode_raw_dataset(self, ds: Dataset,
                           valid_mask: Optional[np.ndarray] = None
                           ) -> EncodedScoreBatch:
        """The HOST half of scoring: run the "pre"-phase numpy
        fallbacks, encode every device input column once, and chunk/
        pad/mask the arrays onto the power-of-two bucket lattice. Pure
        host work — the serving loop runs it for batch k+1 while batch
        k's device program is still in flight (double-buffering)."""
        self.compile()
        n = ds.n_rows
        with _trace.span("score.encode", rows=n):
            return self._encode_raw_dataset_inner(ds, valid_mask)

    def _encode_raw_dataset_inner(self, ds: Dataset,
                                  valid_mask: Optional[np.ndarray]
                                  ) -> EncodedScoreBatch:
        n = ds.n_rows
        # phase "pre": numpy fallbacks feeding the device graph
        for step in self._steps:
            if step.phase == "pre":
                ds = step.stage.transform_dataset(ds)

        # encode once per host input, then chunk onto the bucket lattice
        encoded = [(key, enc(ds[name]))
                   for key, name, enc in self._host_inputs]
        chunks: List[Tuple[int, tuple, np.ndarray, int]] = []
        for start in range(0, max(n, 1), self.max_bucket):
            stop = min(start + self.max_bucket, n)
            rows = stop - start
            bucket = bucket_for(rows, self.min_bucket, self.max_bucket,
                                lattice=self.lattice)
            inputs = tuple(_pad_rows(arr[start:stop], bucket)
                           for _, arr in encoded)
            mask = np.zeros(bucket, dtype=np.float64)
            if valid_mask is None:
                mask[:rows] = 1.0
            else:
                mask[:rows] = valid_mask[start:stop]
            chunks.append((bucket, inputs, mask, rows))
            if n == 0:
                break
        return EncodedScoreBatch(ds=ds, n_rows=n, chunks=chunks)

    def dispatch_encoded(self, enc: EncodedScoreBatch) -> Dataset:
        """The DEVICE half of scoring: dispatch every encoded chunk
        through the fused program (per-bucket cost recorded for
        :meth:`bucket_profile`), then materialize columns and run the
        "post"-phase host fallbacks."""
        out_chunks: List[List[np.ndarray]] = [[] for _ in
                                              self._device_outputs]
        with _trace.span("score.dispatch", rows=enc.n_rows,
                         chunks=len(enc.chunks)):
            for bucket, inputs, mask, rows in enc.chunks:
                if bucket in self._aot_executables:
                    # AOT path: the program was deserialized, not
                    # compiled — the compile diagnostic stays flat
                    _telemetry.count("serve_aot_dispatches")
                else:
                    record_compile("score", (self._plan_id, bucket))
                self._bucket_rows[bucket] = \
                    self._bucket_rows.get(bucket, 0) + rows
                # real (pre-padding) rows: the occupancy histogram the
                # lattice chooser trains on (plans/common.record_rows)
                record_rows("score", rows)
                # the bucket section reports into the span as a child
                # carrying the per-bucket compile/execute split
                # (utils/compile_time section observer)
                with _bucket_section("score", self._plan_id, bucket):
                    outs = self._dispatch_device(inputs, mask,
                                                 bucket=bucket)
                for i, o in enumerate(outs):
                    out_chunks[i].append(np.asarray(o)[:rows])
        return self._finish_score(enc.ds, out_chunks)

    def bucket_profile(self) -> Dict[int, dict]:
        """Observed per-bucket dispatch cost of THIS plan:
        ``{bucket: {calls, wall_seconds, compile_seconds,
        execute_seconds, rows}}`` (plans/common.bucket_profile over
        utils/compile_time sections). Lattice-aware by construction:
        keys are the buckets ACTUALLY dispatched (whatever rungs this
        plan's lattice has) and ``rows`` is the real pre-padding row
        count per bucket — nothing assumes a power-of-two ladder. The
        serving coalescer (serving/server.py) reads this to pick its
        dispatch target from recorded data; bench emits it."""
        return _shared_bucket_profile("score", self._plan_id,
                                      self._bucket_rows)

    def _aot_dispatch_fallback(self, bucket, e: Exception):
        """A loaded executable that fails at CALL time (arg layout
        drift, backend refusal) is dropped for its bucket — the live
        jit path takes over seamlessly — and the degradation is
        recorded loudly (the artifacts loud-fallback contract)."""
        self._aot_executables.pop(bucket, None)
        _telemetry.count("serve_aot_dispatch_errors")
        _telemetry.event("serve_aot_dispatch_error", bucket=bucket,
                         error=f"{type(e).__name__}: {e}")
        _log.warning(
            "AOT executable for bucket %s failed at dispatch "
            "(%s: %s); live-compiling this bucket from now on",
            bucket, type(e).__name__, e)
        record_compile("score", (self._plan_id, bucket))

    def _dispatch_device(self, inputs, mask, bucket=None):
        """One fused-program dispatch behind the runtime retry policy:
        a preemption/RESOURCE_EXHAUSTED-shaped backend error retries
        with backoff (runtime/retry.py) instead of failing the serving
        request; persistent errors propagate to the caller. With a
        guardrail deadline configured, the whole dispatch (retries
        included) runs under a per-batch wall-clock budget — a hung
        backend is abandoned (the thread is orphaned, exactly like the
        selector's family deadline) and surfaces as DEADLINE_EXCEEDED
        for the breaker/fallback layer.

        With an AOT executable attached for ``bucket`` the dispatch
        calls it instead of the jitted fn — the identical program,
        deserialized rather than compiled."""
        def attempt():
            maybe_inject("plan", "device", "dispatch")
            aot = (self._aot_executables.get(bucket)
                   if bucket is not None else None)
            if aot is not None:
                try:
                    return aot(inputs, mask)
                except Exception as e:
                    self._aot_dispatch_fallback(bucket, e)
            return self._device_fn(inputs, mask)

        deadline = (self.guard.deadline_seconds
                    if self.guard is not None else None)
        if deadline is None:
            return self._retry.call(attempt, description="plan-dispatch")
        import concurrent.futures as _cf
        if self._deadline_pool is None:
            self._deadline_pool = _cf.ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="tx-serve-dispatch")
        future = self._deadline_pool.submit(
            self._retry.call, attempt, description="plan-dispatch")
        try:
            return future.result(timeout=deadline)
        except _cf.TimeoutError:
            future.cancel()
            # the pool thread may be wedged inside the backend; a new
            # pool is created for the next batch rather than queueing
            # behind it
            self._deadline_pool = None
            _telemetry.count("serving_deadline_exceeded")
            raise TimeoutError(
                f"DEADLINE_EXCEEDED: device scoring batch exceeded "
                f"the {deadline}s per-batch deadline") from None

    def _finish_score(self, ds: Dataset, out_chunks) -> Dataset:
        for name, chunks in zip(self._device_outputs, out_chunks):
            arr = (np.concatenate(chunks, axis=0) if chunks
                   else np.zeros(0))
            ds = ds.with_column(name, self._wrap_output(name, arr))

        # phase "post": numpy fallbacks consuming device outputs
        for step in self._steps:
            if step.phase == "post":
                ds = step.stage.transform_dataset(ds)
        return self._select_outputs(ds)

    def _select_outputs(self, ds: Dataset) -> Dataset:
        keep = [f.name for f in self._raw_features if f.name in ds] \
            + [nm for nm in self._result_names]
        seen, names = set(), []
        for nm in keep:
            if nm not in seen:
                seen.add(nm)
                names.append(nm)
        return ds.select(names)

    def _wrap_output(self, name: str, arr: np.ndarray) -> FeatureColumn:
        """Materialize a device output array as the column the numpy
        path would have produced (metadata from the zero-row probe;
        Prediction raws through the model's own prediction_from_raw)."""
        step = next(s for s in self._steps if s.out_name == name)
        stage = step.stage
        if issubclass(stage.static_output_type(), Prediction):
            return stage.prediction_from_raw(arr)
        proto = self._proto_cols[name]
        if proto.kind == "vector":
            arr = arr.reshape(len(arr), -1)
            return FeatureColumn(ftype=proto.ftype, data=arr,
                                 metadata=proto.metadata)
        return FeatureColumn(ftype=proto.ftype, data=arr.reshape(-1))

    # -- introspection -----------------------------------------------------
    def describe(self) -> dict:
        """Plan summary for logs/benchmarks."""
        self.compile()
        return {
            "stages": len(self._steps),
            "device_stages": len(self.coverage.lowered),
            "fallback_stages": len(self.coverage.fallback),
            "coverage": self.coverage.to_json(),
            "host_inputs": [k for k, _, _ in self._host_inputs],
            "device_outputs": list(self._device_outputs),
            "buckets": self.buckets(),
            "lattice": list(self.lattice) if self.lattice else None,
        }

    def buckets(self) -> List[int]:
        """The plan's bucket ladder: the explicit lattice when one was
        chosen, else the default power-of-two ladder (identical values
        to the historical doubling loop)."""
        if self.lattice:
            return list(self.lattice)
        return list(default_lattice(self.min_bucket, self.max_bucket))

    def device_input_avals(self, bucket: int):
        """The abstract inputs of one bucket's device program:
        ``(tuple of ShapeDtypeStruct, mask aval)`` — exactly the shapes
        ``dispatch_encoded`` feeds it (encoders probed on the zero-row
        proto columns, mask is the f64 validity vector)."""
        self.compile()
        import jax
        sds = []
        for key, name, enc in self._host_inputs:
            arr = np.asarray(enc(self._proto_cols[name]))
            sds.append(jax.ShapeDtypeStruct(
                (int(bucket),) + arr.shape[1:], arr.dtype))
        mask = jax.ShapeDtypeStruct((int(bucket),), np.float64)
        return tuple(sds), mask

    def lower_bucket(self, bucket: int):
        """AOT-lower ONE bucket's fused scoring program — no execution,
        no device buffers, works under ``JAX_PLATFORMS=cpu``. This is
        the plan auditor's entry point (analysis/audit.py): the
        returned ``jax.stages.Lowered`` exposes the StableHLO text the
        TX-P rules and the canonical IR fingerprint are computed from."""
        self.compile()
        if not self._device_steps:
            raise PlanCompileError(
                "plan has no device program (every stage fell back to "
                "host numpy); nothing to lower")
        inputs, mask = self.device_input_avals(bucket)
        return self._device_fn.lower(inputs, mask)


def _poison_first_valid_row(scored: Dataset, result_names, qmask
                            ) -> Dataset:
    """TX_FAULT_PLAN ``serving:output:guard:N=nan`` hook: corrupt the
    first non-quarantined row's outputs with NaN, so the output guard's
    invalidate-with-reason path is provable end to end."""
    valid = np.flatnonzero(~qmask)
    if valid.size == 0:
        return scored
    row = int(valid[0])
    for name in result_names:
        if name not in scored:
            continue
        col = scored[name]
        if isinstance(col, PredictionColumn):
            data = col.data.copy()
            data[row] = np.nan
            scored = scored.with_column(name, PredictionColumn(
                ftype=col.ftype, data=data, metadata=col.metadata,
                probability=col.probability,
                raw_prediction=col.raw_prediction))
        elif col.kind == "numeric":
            data = np.asarray(col.data, dtype=np.float64).copy()
            data[row] = np.inf
            scored = scored.with_column(name, FeatureColumn(
                ftype=col.ftype, data=data, metadata=col.metadata))
    return scored


