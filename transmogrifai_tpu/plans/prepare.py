"""PreparePlan: compiled train-time feature engineering.

``Workflow.train()`` historically materialized every feature through
host-side ``transform_columns`` loops before the device ever saw a
matrix — two parallel kernel code paths for the same math, because the
serving ScoringPlan (PR 2) already lowers every transmogrify family
through ``Transformer.transform_arrays``. This module deletes the fork:
at train time the SAME array kernels execute the feature DAG on device,
with the per-family chain vectorization → ``VectorsCombiner`` →
fold-matrix staging fused into jitted segment programs ("Operator
Fusion in XLA": hand the compiler the program, not one stage at a
time). The training matrices are born on the device the sharded search
already occupies — ``ModelSelector`` receives a device-resident feature
matrix and the validator stages its fold arrays with device gathers, no
host round-trip in between (docs/prepare.md).

Execution model — a :class:`ScoringPlan` that interleaves fits:

1. Stages are walked in topo order. Transformers (and fitted models)
   whose kernels lower join the CURRENT SEGMENT — a maximal run of
   device steps that will trace into one jitted program.
2. An estimator forces the segment to FLUSH first when its fit needs
   device-produced values (vectorizers fitting on raw host columns
   don't): the fused program executes over power-of-two row buckets
   (padding + validity mask, chunking past the max bucket), outputs
   stay on device AND are wrapped back into jax-backed columns.
3. The fit itself is placed by :class:`~.placement.PlacementPolicy`
   (host vs a ``fit_device`` kernel, driven by the recorded
   compile/execute split) — a host fit of a device-resident input is a
   RECORDED fallback, never a silent one.
4. Stage kernels that fail the abstract trace are demoted to their
   host ``transform_columns`` path with the reason in ``coverage``
   (the ScoringPlan graceful-degradation contract).

Repeat trains reuse compiled segments: a segment's jitted callable is
cached process-wide under a fingerprint of every step's fitted state,
so retraining on identical data re-executes the cached XLA programs
with ZERO new traces or compiles (``prepare_compiles()`` stays flat —
asserted in tests/test_prepare_plan.py).

Per-stage telemetry inside a fused program cannot come from wall-clock
alone; each stage's kernel is traced under a ``prepare:stage:<uid>``
compile-time section (utils/compile_time.py) and segment dispatch under
``prepare:seg<k>``, and the listener receives per-stage compile/execute
seconds apportioned by trace share — ``stage_profile_top`` keeps its
per-stage rows (the telemetry-autotuning data source).
"""
from __future__ import annotations

import hashlib
import logging
import pickle
import time
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..features.columns import Dataset, FeatureColumn
from ..features.feature import Feature, topo_layers
from ..features.generator import FeatureGeneratorStage
from ..observability import trace as _trace
from ..runtime import telemetry as _telemetry
from ..runtime.faults import maybe_inject
from ..runtime.retry import RetryPolicy
from ..stages.base import Estimator, PipelineStage, Transformer
from ..types import Prediction
from ..utils import compile_time
from .common import (DEFAULT_MIN_BUCKET, PlanCompileError, PlanCoverage,
                     PlanStep, bucket_for, compiles, empty_raw_dataset,
                     fallback_reason, lowering_reason, normalize_lattice,
                     pad_rows, plan_seq, probe_stage, record_compile,
                     record_rows)
from .placement import PlacementPolicy

_log = logging.getLogger(__name__)

__all__ = ["PreparePlan", "prepare_compiles",
           "DEFAULT_PREPARE_MAX_BUCKET"]

#: train datasets are one batch, not a request stream — a larger max
#: bucket keeps typical training sizes in ONE fused dispatch while the
#: power-of-two ladder still bounds distinct programs
DEFAULT_PREPARE_MAX_BUCKET = 65536


def prepare_compiles() -> int:
    """Distinct compiled prepare segment programs so far in this
    process (the flat-across-repeat-trains diagnostic the bench and
    tests/test_prepare_plan.py assert on)."""
    return compiles("prepare")


#: the most recent PreparePlan executed in this process — the plan
#: auditor's handle to the fused segment programs a train() just built
#: (workflow.train constructs the plan internally; audit_prepare_plan
#: re-lowers its segments from the recorded audit handles)
_LAST_PLAN: Optional["PreparePlan"] = None


def last_prepare_plan() -> Optional["PreparePlan"]:
    """The most recently executed PreparePlan of this process (None
    before any plan-mode train). Audit-only introspection — the plan's
    ``audit_handles`` carry each fused segment's jitted fn, input
    avals, dispatched buckets and stage roster (analysis/audit.py)."""
    return _LAST_PLAN


# ---------------------------------------------------------------------------
# cross-train segment cache
# ---------------------------------------------------------------------------

#: (segment signature) -> (jitted fn, trace_seconds by uid). Bounded
#: LRU: a long-lived retraining process keeps its hot segments warm.
_SEGMENT_CACHE: "OrderedDict[tuple, tuple]" = OrderedDict()
_SEGMENT_CACHE_MAX = 64


def _state_fingerprint(stage: PipelineStage) -> Optional[str]:
    """Deterministic digest of a stage's fitted state (every public
    attribute except DAG wiring and identity). Retraining a workflow on
    identical data produces models with equal state -> equal
    fingerprints -> the cached jitted segment is reused with zero
    retrace. Over-inclusion is safe by construction (a spurious
    difference only costs a recompile, never stale reuse); unpicklable
    state (lambdas) returns None: that stage's segments never
    cross-train cache — correct, just cold."""
    try:
        payload = {k: v for k, v in sorted(stage.__dict__.items())
                   if k not in ("input_features", "_output_feature",
                                "fitted_model", "uid", "operation_name")}
        blob = pickle.dumps(payload, protocol=4)
    except Exception:
        return None
    return hashlib.sha1(blob).hexdigest()


def _sig_digest(sig) -> Optional[str]:
    """Stable digest of a segment signature — the cross-PROCESS reuse
    key the AOT artifact store files prepare executables under
    (artifacts/export.py). The signature is already deterministic
    (state fingerprints + positions + bucket range), so its repr is."""
    if sig is None:
        return None
    return hashlib.sha1(repr(sig).encode()).hexdigest()


def _prepare_aot_executable(sig_digest: Optional[str], bucket: int):
    """The deserialized AOT executable for one (segment, bucket), or
    None — a thin guard over artifacts/loader.prepare_executable so a
    broken artifacts layer can never take training down."""
    if sig_digest is None:
        return None
    try:
        from ..artifacts.loader import prepare_executable
        return prepare_executable(sig_digest, bucket)
    except Exception:           # registry is an optimization, not truth
        return None


def _segment_cache_get(sig):
    hit = _SEGMENT_CACHE.get(sig)
    if hit is not None:
        _SEGMENT_CACHE.move_to_end(sig)
    return hit


def _segment_cache_put(sig, value) -> None:
    _SEGMENT_CACHE[sig] = value
    _SEGMENT_CACHE.move_to_end(sig)
    while len(_SEGMENT_CACHE) > _SEGMENT_CACHE_MAX:
        _SEGMENT_CACHE.popitem(last=False)


def _is_jax_array(x) -> bool:
    try:
        import jax
        return isinstance(x, jax.Array)
    except (ImportError, AttributeError):  # pragma: no cover - old jax
        return False


def _fit_encode(col: FeatureColumn):
    """Array view of a host column for a device fit: numeric/vector
    columns encode identically to the transform boundary; device-
    resident arrays pass through. Object columns have no array form —
    the caller falls back to the host fit with a recorded reason."""
    if _is_jax_array(col.data):
        return col.data
    if col.kind in ("numeric", "vector"):
        return np.asarray(col.data, dtype=np.float64)
    raise NotImplementedError(
        f"{col.ftype.__name__} column has no array encoding for a "
        f"device fit")


class PreparePlan:
    """Execute (fit + transform) a feature DAG with the serving kernel
    library at train time. One instance per ``train()`` call; compiled
    segments are shared process-wide (see module docstring).

    >>> plan = PreparePlan(result_features, listener=listener)
    >>> train_ds, fitted = plan.execute(raw_ds)
    """

    def __init__(self, result_features: Sequence[Feature],
                 min_bucket: int = DEFAULT_MIN_BUCKET,
                 max_bucket: int = DEFAULT_PREPARE_MAX_BUCKET,
                 listener=None, placement: Optional[PlacementPolicy] = None,
                 lattice: Optional[Sequence[int]] = None):
        self.result_features = tuple(result_features)
        #: explicit bucket lattice — None keeps the default
        #: power-of-two ladder bitwise; a lattice overrides the range
        #: args (its first/last rungs become min/max), and joins the
        #: cross-train segment signature so cached programs never mix
        #: lattices
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
        self.listener = listener
        self.placement = placement or PlacementPolicy()
        self.coverage = PlanCoverage()
        #: [(stage label, "host"|"device", reason)] fit placements
        self.fit_placements: List[Tuple[str, str, str]] = []
        #: seconds spent executing fused device segments (+ encoders)
        self.device_transform_seconds = 0.0
        #: seconds spent in host transform_columns fallbacks
        self.host_transform_seconds = 0.0
        self.segments_run = 0
        self._plan_id = plan_seq()
        self._retry = RetryPolicy.from_env()
        #: one record per executed segment — the auditor's re-lowering
        #: handles: {label, fn (jitted), in_avals [(trailing shape,
        #: dtype)], buckets dispatched, stages}. Holding the jitted fn
        #: keeps re-lowering exact (same traced program) and costs
        #: nothing: the fn is alive in _SEGMENT_CACHE anyway.
        self.audit_handles: List[Dict[str, Any]] = []

    # -- public ------------------------------------------------------------
    def execute(self, ds: Dataset,
                prefitted: Optional[Dict[str, PipelineStage]] = None
                ) -> Tuple[Dataset, Dict[str, PipelineStage]]:
        """Fit every estimator and materialize every stage output over
        ``ds`` (the ``_fit_and_transform_layers(fit=True)`` contract:
        returns the fully transformed Dataset — device-lowered columns
        are jax-backed, host fallbacks numpy — and the fitted models by
        estimator uid). ``prefitted`` supplies models already fitted on
        THIS dataset (the workflow-CV pre-pass)."""
        global _LAST_PLAN
        _LAST_PLAN = self
        compile_time.install()
        _telemetry.count(_telemetry.PREPARE_PULL_BYTES, 0)
        import jax  # noqa: F401  (device path; deferred like the plans)
        stages = [s for layer in topo_layers(list(self.result_features))
                  for s in layer
                  if not isinstance(s, FeatureGeneratorStage)]
        raw_names = [f.name for f in _raw_features(self.result_features)]
        self._proto = empty_raw_dataset(
            _raw_features(self.result_features))
        self._producer: Dict[str, str] = {n: "host" for n in raw_names}
        self._device_env: Dict[str, Any] = {}
        self._aval_env: Dict[str, Any] = {}
        self._pending: List[PlanStep] = []
        fitted: Dict[str, PipelineStage] = {}

        for stage in stages:
            if isinstance(stage, Estimator):
                model = (prefitted or {}).get(stage.uid)
                if model is None:
                    ds, model = self._fit_stage(stage, ds)
                fitted[stage.uid] = model
                out_name = stage.get_output().name
                ds = self._add_transform(model, out_name, ds,
                                         n_rows=ds.n_rows)
            elif isinstance(stage, Transformer):
                ds = self._add_transform(stage, stage.get_output().name,
                                         ds, n_rows=ds.n_rows)
            else:
                raise TypeError(f"Cannot execute stage {stage!r}")
        ds = self._flush(ds)
        return ds, fitted

    def describe(self) -> dict:
        """Plan summary for logs/benchmarks."""
        return {
            "coverage": self.coverage.to_json(),
            "fit_placements": [list(p) for p in self.fit_placements],
            "segments_run": self.segments_run,
            "device_transform_seconds":
                round(self.device_transform_seconds, 4),
            "host_transform_seconds":
                round(self.host_transform_seconds, 4),
            "lattice": list(self.lattice) if self.lattice else None,
        }

    # -- transform classification ------------------------------------------
    def _add_transform(self, stage: Transformer, out_name: str,
                       ds: Dataset, n_rows: int) -> Dataset:
        """Classify one (fitted) stage's transform and either append it
        to the pending device segment or run its host fallback now."""
        in_names = tuple(f.name for f in stage.input_features)
        is_prediction = issubclass(stage.static_output_type(), Prediction)
        if is_prediction:
            # the train-time prediction column feeds boxed evaluation /
            # insights host-side anyway; raw-margin lowering buys
            # nothing here (serving lowers it — serving/plan.py)
            reason = "prediction output assembles host-side at train time"
        else:
            reason = lowering_reason(
                stage, in_names, self._producer,
                lambda n: self._proto[n])
        if not reason:
            reason = self._verify_kernel(stage, in_names, out_name)
        # proto update AFTER classification: lowering_reason probes
        # encoders on the zero-row proto of the stage's INPUTS. A stage
        # that crashes the probe cannot be wrapped from device output
        # metadata, so it is demoted to the host path (its real output,
        # sliced to zero rows, becomes the proto instead).
        probed = True
        try:
            self._proto = probe_stage(stage, self._proto, out_name)
        except Exception as e:
            probed = False
            if not reason:
                self._note_demotion(stage, "zero-row probe failed", e)
                reason = fallback_reason("zero-row probe failed", e)
        label = f"{type(stage).__name__}({out_name})"
        if not reason:
            self._pending.append(
                PlanStep(stage, out_name, in_names, "device"))
            self._producer[out_name] = "device"
            self.coverage.lowered.append(label)
            return ds
        # host fallback: needs the VALUES of its inputs materialized
        ds = self._flush(ds)
        self.coverage.fallback.append((label, reason))
        self._producer[out_name] = "host"
        t0 = time.perf_counter()
        c0 = compile_time.compile_seconds()
        col = stage.transform_columns([ds[n] for n in in_names])
        ds = ds.with_column(out_name, col)
        if not probed:
            self._proto = self._proto.with_column(
                out_name, col.take(np.zeros(0, dtype=np.int64)))
        wall = time.perf_counter() - t0
        self.host_transform_seconds += wall
        if self.listener is not None:
            self.listener.on_stage_completed(
                stage, "transform", wall, n_rows,
                compile_seconds=compile_time.compile_seconds() - c0)
        return ds

    def _input_key(self, step: PlanStep, i: int, name: str) -> str:
        if self._producer.get(name) == "device":
            return name
        if step.stage.encodes_input(i):
            return f"enc:{step.stage.uid}:{i}"
        return name

    def _verify_kernel(self, stage: Transformer,
                       in_names: Tuple[str, ...], out_name: str) -> str:
        """Abstractly trace ONE stage's kernel (``jax.eval_shape`` — no
        device code) against its input avals; a failing kernel is
        demoted to the host path with the recorded reason instead of
        failing the plan. Deterministic test hook: an injected
        ``prepare:<Stage>:compile`` fault demotes exactly like a real
        trace failure."""
        import jax
        try:
            maybe_inject("prepare", type(stage).__name__, "compile")
        except Exception as e:
            self._note_demotion(stage, "injected compile fault", e)
            return fallback_reason("injected compile fault", e)
        avals = []
        try:
            for i, name in enumerate(in_names):
                if self._producer.get(name) == "device":
                    avals.append(self._aval_env[name])
                else:
                    arr = np.asarray(stage.encode_input_column(
                        i, self._proto[name]))
                    avals.append(jax.ShapeDtypeStruct(
                        (self.min_bucket,) + arr.shape[1:], arr.dtype))
            out = jax.eval_shape(
                lambda *a, s=stage: s.transform_arrays(list(a)), *avals)
        except Exception as e:
            self._note_demotion(stage, "kernel failed abstract trace", e)
            return fallback_reason("kernel failed abstract trace", e)
        self._aval_env[out_name] = out
        return ""

    def _note_demotion(self, stage, what: str, e: Exception) -> None:
        _telemetry.count("prepare_fallbacks")
        _telemetry.event("prepare_fallback", stage=type(stage).__name__,
                         reason=f"{what}: {type(e).__name__}: {e}")
        _log.warning(
            "prepare plan: stage %s failed to lower (%s: %s); falling "
            "back to its host transform_columns path",
            type(stage).__name__, what, e)

    # -- estimator fits ----------------------------------------------------
    def _fit_stage(self, stage: Estimator, ds: Dataset
                   ) -> Tuple[Dataset, PipelineStage]:
        in_names = [f.name for f in stage.input_features]
        srcs = [self._producer.get(n, "host") for n in in_names]
        n_rows = ds.n_rows
        if all(s == "host" for s in srcs):
            # vocab builders fit on raw/host-materialized columns — the
            # data is host-resident either way, nothing to place
            with _trace.span("prepare.encode", phase="fit",
                             columns=len(in_names), rows=n_rows):
                return self._host_fit(stage, ds, n_rows,
                                      reason="inputs host-resident")
        ds = self._flush(ds)    # fit needs VALUES of device outputs
        where, why = self.placement.decide_fit(stage, n_rows)
        if where == "device":
            try:
                arrays = [
                    self._device_env[n]
                    if self._producer.get(n) == "device"
                    else _fit_encode(ds[n])
                    for n in in_names]
                protos = [self._proto[n] for n in in_names]
                return ds, self._device_fit(stage, arrays, protos,
                                            n_rows, why)
            except NotImplementedError as e:
                why = fallback_reason("fit_device rejected the inputs", e)
                _telemetry.count("prepare_fit_fallbacks")
        else:
            _telemetry.count("prepare_fit_fallbacks")
        return self._host_fit(stage, ds, n_rows, reason=why,
                              pulled_device=True)

    def _host_fit(self, stage: Estimator, ds: Dataset, n_rows: int,
                  reason: str, pulled_device: bool = False
                  ) -> Tuple[Dataset, PipelineStage]:
        label = f"{type(stage).__name__}({stage.uid})"
        if pulled_device:
            # a host fit of device-resident inputs is a recorded
            # degradation (TX-R01 spirit), not a silent np.asarray
            reason = f"host fit over device columns: {reason}"
        self.fit_placements.append((label, "host", reason))
        t0 = time.perf_counter()
        c0 = compile_time.compile_seconds()
        with compile_time.section(f"prepare:fit:{type(stage).__name__}"):
            model = stage.fit(ds)
        wall = time.perf_counter() - t0
        cdelta = compile_time.compile_seconds() - c0
        PlacementPolicy.record_fit(stage, "host", wall, cdelta, n_rows)
        if self.listener is not None:
            self.listener.on_stage_completed(stage, "fit", wall, n_rows,
                                             compile_seconds=cdelta)
        return ds, model

    def _device_fit(self, stage: Estimator, arrays, protos, n_rows: int,
                    why: str) -> PipelineStage:
        label = f"{type(stage).__name__}({stage.uid})"
        self.fit_placements.append((label, "device", why))
        t0 = time.perf_counter()
        c0 = compile_time.compile_seconds()
        with compile_time.section(f"prepare:fit:{type(stage).__name__}"):
            model = stage.fit_from_arrays(arrays, protos)
        wall = time.perf_counter() - t0
        cdelta = compile_time.compile_seconds() - c0
        PlacementPolicy.record_fit(stage, "device", wall, cdelta, n_rows)
        if self.listener is not None:
            self.listener.on_stage_completed(stage, "fit", wall, n_rows,
                                             compile_seconds=cdelta)
        return model

    # -- segment execution -------------------------------------------------
    def _flush(self, ds: Dataset) -> Dataset:
        """Execute the pending device segment as ONE jitted program
        over padded row buckets; outputs land in the device env AND as
        jax-backed columns of the returned Dataset."""
        if not self._pending:
            return ds
        steps, self._pending = self._pending, []
        seg_idx = self.segments_run
        self.segments_run += 1
        n = ds.n_rows

        # device inputs: device-env arrays pass through by name; host
        # columns encode once per distinct (encoder, column) key
        in_keys: List[str] = []
        inputs_of: List[Tuple[PlanStep, int, str]] = []
        seen = set()
        produced = {s.out_name for s in steps}
        for step in steps:
            for i, name in enumerate(step.input_names):
                key = self._input_key(step, i, name)
                if key in seen or key in produced:
                    continue
                seen.add(key)
                in_keys.append(key)
                inputs_of.append((step, i, name))
        host = sum(self._producer.get(name) != "device"
                   for _, _, name in inputs_of)
        with _trace.span("prepare.encode", phase="encode", columns=host,
                         rows=n):
            sources: List[Tuple[str, Any]] = [   # (key, array)
                (key, self._device_env[name]
                 if self._producer.get(name) == "device"
                 else stage_encode(step.stage, i, ds[name]))
                for key, (step, i, name) in zip(in_keys, inputs_of)]

        # canonical POSITIONAL form: inputs 0..K-1 in discovery order,
        # then one slot per step output. Stage uids / feature names
        # stay out of the traced function and the cache signature —
        # retraining a workflow on identical data reuses the compiled
        # programs (fitted state that embeds output names, e.g. vector
        # metadata, still fingerprints per workflow instance).
        pos_of = {key: i for i, key in enumerate(in_keys)}
        k_in = len(in_keys)
        step_pos = []
        for j, s in enumerate(steps):
            in_pos = tuple(
                pos_of[self._input_key(s, i, nm)]
                for i, nm in enumerate(s.input_names))
            step_pos.append((s.stage, in_pos))
            pos_of[s.out_name] = k_in + j
        step_pos = tuple(step_pos)
        sig = self._segment_signature(step_pos, k_in)
        sig_digest = _sig_digest(sig)
        seg_label = f"prepare:seg{seg_idx}"
        t0 = time.perf_counter()
        c0 = compile_time.compile_seconds()
        with compile_time.section(seg_label):
            cached = None if sig is None else _segment_cache_get(sig)
            if cached is None:
                fn, trace_seconds = _build_segment_fn(step_pos, k_in)
                if sig is not None:
                    _segment_cache_put(sig, (fn, trace_seconds))
            else:
                fn, trace_seconds = cached

            chunks: List[List[Any]] = [[] for _ in steps]
            seg_buckets: List[int] = []
            for start in range(0, max(n, 1), self.max_bucket):
                stop = min(start + self.max_bucket, n)
                rows = stop - start
                bucket = bucket_for(rows, self.min_bucket,
                                    self.max_bucket,
                                    lattice=self.lattice)
                record_rows("prepare", rows)
                if bucket not in seg_buckets:
                    seg_buckets.append(bucket)
                inputs = tuple(pad_rows(arr[start:stop], bucket)
                               for _, arr in sources)
                mask = np.zeros(bucket, dtype=np.float64)
                mask[:rows] = 1.0
                # a seeded AOT executable (artifacts/loader.py — the
                # lifecycle retrain path seeds from the live model's
                # artifact store) dispatches without compiling; the
                # prepare-compile diagnostic stays flat
                aot_fn = _prepare_aot_executable(sig_digest, bucket)
                if aot_fn is not None:
                    _telemetry.count("prepare_aot_dispatches")
                else:
                    record_compile(
                        "prepare",
                        (sig if sig is not None else self._plan_id,
                         bucket))
                outs = self._dispatch(aot_fn or fn, inputs, mask)
                for i, o in enumerate(outs):
                    chunks[i].append(o[:rows])
                if n == 0:
                    break
        wall = time.perf_counter() - t0
        cdelta = compile_time.compile_seconds() - c0
        self.device_transform_seconds += wall
        # audit handle: enough to RE-LOWER this exact segment program
        # per dispatched bucket without re-executing anything
        # (analysis/audit.audit_prepare_plan). Shapes/dtypes read off
        # the source arrays' metadata — no materialization.
        self.audit_handles.append({
            "label": f"seg{seg_idx}",
            "fn": fn,
            "sig_digest": sig_digest,
            "in_avals": [(tuple(arr.shape[1:]), arr.dtype)
                         for _, arr in sources],
            "buckets": sorted(seg_buckets),
            "stages": [type(s.stage).__name__ for s in steps],
            "stage_modules": sorted({type(s.stage).__module__
                                     for s in steps}),
        })

        import jax.numpy as jnp
        for step, outs in zip(steps, chunks):
            arr = outs[0] if len(outs) == 1 else jnp.concatenate(outs)
            self._device_env[step.out_name] = arr
            ds = ds.with_column(step.out_name,
                                self._wrap_output(step.out_name, arr))
        self._report_segment(steps, trace_seconds, wall, cdelta, n)
        return ds

    def _dispatch(self, fn, inputs, mask):
        """One fused-program dispatch behind the runtime retry policy
        (transient backend errors back off and retry; persistent ones
        propagate — train has the selector-level quarantine above)."""
        def attempt():
            maybe_inject("prepare", "device", "dispatch")
            return fn(inputs, mask)

        return self._retry.call(attempt, description="prepare-dispatch")

    def _segment_signature(self, step_pos, k_in: int):
        parts = []
        for stage, in_pos in step_pos:
            fp = _state_fingerprint(stage)
            if fp is None:
                return None     # unfingerprintable: no cross-train reuse
            parts.append((type(stage).__name__, fp, in_pos))
        return (tuple(parts), k_in, self.min_bucket, self.max_bucket,
                self.lattice)

    def _wrap_output(self, name: str, arr) -> FeatureColumn:
        """Wrap a device output as the column the numpy path would have
        produced — metadata from the zero-row probe, the ARRAY left on
        device (numpy consumers convert lazily on first touch)."""
        proto = self._proto[name]
        if proto.kind == "vector":
            return FeatureColumn(ftype=proto.ftype,
                                 data=arr.reshape(len(arr), -1),
                                 metadata=proto.metadata)
        return FeatureColumn(ftype=proto.ftype, data=arr.reshape(-1))

    def _report_segment(self, steps, trace_seconds, wall, cdelta,
                        n_rows) -> None:
        """Per-stage listener rows for a fused segment: wall/compile
        apportioned by each stage's recorded TRACE share (the only
        per-stage signal a fused program leaves; documented
        approximation, docs/prepare.md)."""
        if self.listener is None:
            return
        shares = [max(trace_seconds.get(j, 0.0), 0.0)
                  for j in range(len(steps))]
        total = sum(shares)
        if total <= 0:
            shares = [1.0] * len(steps)
            total = float(len(steps))
        for step, share in zip(steps, shares):
            frac = share / total
            self.listener.on_stage_completed(
                step.stage, "transform", wall * frac, n_rows,
                compile_seconds=cdelta * frac)


def stage_encode(stage: Transformer, i: int, col: FeatureColumn):
    """Host boundary encoder for input slot ``i`` — identity for
    numeric/vector columns (device-resident arrays pass through
    untouched instead of round-tripping via numpy)."""
    if not stage.encodes_input(i) and col.kind in ("numeric", "vector") \
            and _is_jax_array(col.data):
        return col.data
    return stage.encode_input_column(i, col)


def _build_segment_fn(step_pos, k_in: int):
    """Compose the segment's kernels into ONE traced function and jit
    it. The body runs exactly once per trace: per-stage wall time
    measured here IS that stage's trace cost, and the compile-time
    section attributes its trace/lower events (utils/compile_time.py).
    Everything is positional (slot 0..k_in-1 = inputs, then one slot
    per step) so the program is identical across retrains regardless
    of stage uids or feature names."""
    import jax

    trace_seconds: Dict[int, float] = {}

    def run(inputs, mask):
        env = list(inputs)
        for j, (stage, in_pos) in enumerate(step_pos):
            t0 = time.perf_counter()
            with compile_time.section(
                    f"prepare:stage:{type(stage).__name__}"):
                env.append(stage.transform_arrays(
                    [env[p] for p in in_pos]))
            trace_seconds[j] = trace_seconds.get(
                j, 0.0) + time.perf_counter() - t0
        outs = []
        for o in env[k_in:]:
            outs.append(o * (mask[:, None] if o.ndim == 2 else mask))
        return tuple(outs)

    return jax.jit(run), trace_seconds  # tx-lint: disable=TX-J02 (one jit per SEGMENT, cached across trains via the state fingerprint)


def _raw_features(result_features: Sequence[Feature]) -> List[Feature]:
    uniq: Dict[str, Feature] = {}
    for rf in result_features:
        for f in rf.raw_features():
            uniq.setdefault(f.uid, f)
    return sorted(uniq.values(), key=lambda f: f.name)
