"""Hyperparameter validation: cross-validation and train/validation split.

TPU-native port of the reference validators
(core/src/main/scala/com/salesforce/op/tuning/{OpValidator.scala:94,
OpCrossValidation.scala:40, OpTrainValidationSplit.scala}). The
reference's per-fold / per-family ``Future`` task parallelism maps to:

- one jitted XLA fit per (family, grid point, fold); hyperparameters are
  traced scalars so a whole grid reuses one compiled program per family,
- mesh execution BY DEFAULT: the validator resolves a
  ``("models", "data")`` mesh over the visible devices at search time
  (``parallel/cv.resolve_search_mesh``; ``TX_SEARCH_MESH`` policies it,
  a single visible device keeps the local path) and families exposing a
  mesh kernel (see parallel/cv.py) train all fold x grid candidates in
  one SPMD program, candidate axis sharded over chips. Candidate-axis
  sharding keeps every candidate's arithmetic identical to the local
  program, so the winner is BITWISE invariant across device counts
  (docs/distributed.md; tests/test_sharded_search.py).
"""
from __future__ import annotations

import inspect
import logging
import os
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

_log = logging.getLogger(__name__)

#: per-family dispatch accounting (wall + compile seconds, call count),
#: accumulated across every search in this process — the benchmark's
#: ``slowest_family_s`` reads it (``family_profile()``) to tell a
#: compile-bound search from a compute-bound one family by family (the
#: thread is named ``tx-family-<Name>`` while the family's
#: kernels run, so profiler lanes carry the same attribution)
_FAMILY_PROFILE: Dict[str, Dict[str, float]] = {}


def family_profile() -> List[dict]:
    """Per-family device-dispatch profile rows, slowest first:
    ``{"family", "seconds", "compileSeconds", "executeSeconds",
    "calls"}``. compileSeconds is the XLA trace+lower+compile time
    observed on the family's dispatch thread (utils/compile_time.py) —
    a warm process pays only executeSeconds."""
    return [
        {"family": k, "seconds": round(v["seconds"], 4),
         "compileSeconds": round(min(v["compile"], v["seconds"]), 4),
         "executeSeconds": round(
             max(0.0, v["seconds"] - v["compile"]), 4),
         "calls": int(v["calls"])}
        for k, v in sorted(_FAMILY_PROFILE.items(),
                           key=lambda kv: -kv[1]["seconds"])]


def reset_family_profile() -> None:
    _FAMILY_PROFILE.clear()

from ..evaluators.base import Evaluator
from ..models.base import (FamilyPreconditionError,
                           PredictionModel, Predictor)
from ..observability import trace as _trace
from ..runtime import telemetry as _telemetry
from ..runtime.context import RuntimeContext
from ..runtime.errors import (AllFamiliesFailedError, BUG,
                              classify_error)
from ..runtime.faults import maybe_inject

__all__ = ["ValidationResult", "BestEstimator", "CrossValidation",
           "TrainValidationSplit"]

#: sentinel a dispatch returns for a quarantined family — distinct from
#: None (= no device path; fall through to the host evaluation)
_QUARANTINED = object()


def _async_dispatch_bytes(X, masks, X_val_st, y_val_st) -> int:
    """Bytes concurrent family dispatch keeps resident on device AT
    ONCE: the train matrix, the fold masks and (when the device fast
    path is active) the stacked per-fold validation arrays. The async
    HBM guard must sum all of them — counting X alone under-estimates
    peak HBM for many-fold searches near the threshold."""
    total = int(getattr(X, "nbytes", 0)) + int(masks.nbytes)
    if X_val_st is not None:
        total += int(X_val_st.nbytes) + int(y_val_st.nbytes)
    return total


@dataclass
class ValidationResult:
    """Metric record for one (model family, grid point)
    (reference ValidatedModel, OpValidator.scala:72).

    The racing scheduler (selector/racing.py) annotates each record with
    its multi-fidelity trajectory: ``rung`` is the highest rung the
    candidate was evaluated at, ``budget_spent`` the fold-fit
    equivalents consumed (full CV = num_folds per candidate), and
    ``pruned_at`` the rung where the racer dropped it (None = survived
    to the final full-fidelity rung). All three stay None/0 — and OUT of
    the JSON — under exact validation, so default summaries are
    byte-identical to pre-racing ones."""
    model_name: str
    model_uid: str
    grid_index: int
    params: Dict
    metric_values: List[float] = field(default_factory=list)
    rung: Optional[int] = None
    budget_spent: float = 0.0
    pruned_at: Optional[int] = None

    @property
    def mean_metric(self) -> float:
        return float(np.mean(self.metric_values))

    def to_json(self) -> dict:
        out = {"modelName": self.model_name, "modelUID": self.model_uid,
               "gridIndex": self.grid_index, "params": self.params,
               "metricValues": [float(v) for v in self.metric_values],
               "meanMetric": self.mean_metric}
        if self.rung is not None:
            out["rung"] = self.rung
            out["budgetSpent"] = float(self.budget_spent)
            out["prunedAt"] = self.pruned_at
        return out

    @classmethod
    def from_json(cls, d: dict) -> "ValidationResult":
        return cls(model_name=d["modelName"], model_uid=d["modelUID"],
                   grid_index=d["gridIndex"], params=dict(d["params"]),
                   metric_values=list(d["metricValues"]),
                   rung=d.get("rung"),
                   budget_spent=d.get("budgetSpent", 0.0),
                   pruned_at=d.get("prunedAt"))


@dataclass
class BestEstimator:
    """Winner of validation (reference BestEstimator,
    OpValidator.scala:62)."""
    estimator: Predictor
    name: str
    params: Dict
    metric: float
    results: List[ValidationResult] = field(default_factory=list)


def _batched_fold_raw(fitted_fold_models, X_val):
    """Raw predictions for every tree-family candidate of one fold in
    one device program (models/trees.batch_predict_raw); {} on a
    backend-shaped failure so the per-candidate path takes over. A
    genuine kernel bug PROPAGATES (r4 narrowed the former blanket
    ``except Exception`` to the runtime's transient/family classifier
    — silently degrading every search to the slow path used to hide
    real defects; lint rule TX-R01 now flags that pattern)."""
    try:
        from ..models.trees import batch_predict_raw
        return batch_predict_raw(fitted_fold_models, X_val)
    except NotImplementedError:
        return {}
    except Exception as e:
        if classify_error(e) == BUG:
            raise
        _log.warning("batched fold evaluation failed (%s: %s); falling "
                     "back to per-candidate predicts",
                     type(e).__name__, e)
        return {}


class _ValidatorBase:
    def __init__(self, evaluator: Evaluator, seed: int = 42,
                 stratify: bool = False, mesh="auto"):
        self.evaluator = evaluator
        self.seed = seed
        self.stratify = stratify
        #: ("models", "data") jax.sharding.Mesh, a policy string, or
        #: None. The default ``"auto"`` resolves LAZILY at search time
        #: (parallel/cv.resolve_search_mesh — constructing a selector
        #: must never initialize a backend): with >1 visible device the
        #: fold x grid candidate axis of every kernel-capable family
        #: shards over chips as ONE SPMD program (parallel/cv.py);
        #: ``None`` forces the local single-device path; results are
        #: bitwise identical either way (docs/distributed.md).
        self.mesh = mesh
        #: fault-tolerance knobs (runtime/; docs/resilience.md) — set
        #: directly or via ModelSelector(checkpoint_dir=..., ...):
        #: journal completed family evaluations here and replay them on
        #: a resumed search
        self.checkpoint_dir: Optional[str] = None
        #: RetryPolicy for transient dispatch failures (None = env
        #: defaults, runtime/retry.py)
        self.retry_policy = None
        #: wall-clock seconds one family's threaded dispatch may take
        #: before it is abandoned + quarantined (None = no deadline)
        self.family_deadline: Optional[float] = None
        #: RuntimeContext of the most recent validate() call — the
        #: selector reads the quarantine ledger from here
        self.last_runtime: Optional[RuntimeContext] = None

    # -- mesh resolution ---------------------------------------------------
    def _resolve_mesh(self):
        """Resolve a mesh policy ("auto"/int/None/Mesh) into a concrete
        mesh ONCE, at search time. Idempotent; the resolved mesh is
        stored back so every dispatch of this search (and the next)
        shares one mesh object — the lru_cache'd family kernels key on
        it."""
        from ..parallel.cv import resolve_search_mesh
        if isinstance(self.mesh, (str, int)):
            self.mesh = resolve_search_mesh(self.mesh)
        return self.mesh

    def mesh_topology(self) -> dict:
        """Topology descriptor of the resolved search mesh — journal
        header metadata (a resume on a different device count replays
        the same metrics; runtime/journal.py)."""
        mesh = self._resolve_mesh()
        if mesh is None:
            return {"devices": 1, "mesh": None}
        return {"devices": int(mesh.size),
                "mesh": {str(k): int(v) for k, v in mesh.shape.items()},
                "platform": mesh.devices.flat[0].platform}

    def _dispatch_workers(self, n_tasks: int) -> int:
        """Concurrent family-dispatch thread budget. Without a mesh:
        one per family up to the core count (threads overlap host
        orchestration + transfers with on-chip compute). With the
        search mesh active every family's kernel is itself an SPMD
        program over the WHOLE mesh — extra host threads would queue
        full-mesh programs against the same chips the sharded rungs
        already occupy (oversubscription buys queueing, not overlap) —
        so the budget is 1 + the device slots the mesh leaves free. A
        family deadline still forces >= 2 workers: deadline abandonment
        only works from the threaded path."""
        workers = min(n_tasks, os.cpu_count() or 1)
        mesh = self._resolve_mesh()
        if mesh is not None:
            import jax
            free = max(0, len(jax.devices()) - int(mesh.size))
            workers = min(workers, 1 + free)
        return workers

    # -- fault-tolerant runtime --------------------------------------------
    @staticmethod
    def _family_key(fi: int, estimator) -> str:
        """Journal/dispatch identity of one family in THIS pool: the
        pool index disambiguates two instances of the same class."""
        return f"{fi}:{type(estimator).__name__}"

    def _begin_runtime(self, models, X, y) -> RuntimeContext:
        """Open this search's RuntimeContext (quarantine ledger + retry
        + optional journal). The journal is keyed by the search
        fingerprint — grid x splits x seed x data — so a stale
        checkpoint from a different search is rotated aside instead of
        mis-replayed."""
        ctx = RuntimeContext(retry=self.retry_policy,
                             family_deadline=self.family_deadline)
        # a search that sends no family down the host path still shows the
        # counter, at 0 (what reads it tells "none" from "not counted")
        _telemetry.count("host_path_families", 0)
        if self.checkpoint_dir and X is not None:
            from ..runtime.journal import search_fingerprint
            params = dict(self.get_params(),
                          validationType=type(self).__name__)
            # mesh topology rides along as header METADATA — it is NOT
            # part of the fingerprint, so a search preempted on one
            # device count resumes on another to the bitwise-identical
            # winner (docs/distributed.md)
            ctx.open_journal(self.checkpoint_dir,
                             search_fingerprint(models, params, X, y),
                             topology=self.mesh_topology())
        self.last_runtime = ctx
        return ctx

    def _results_from_journal(self, estimator, grid, metric_rows
                              ) -> List["ValidationResult"]:
        """ValidationResults rebuilt from journaled per-candidate fold
        vectors — bit-exact (JSON doubles round-trip via repr)."""
        return [
            ValidationResult(
                model_name=type(estimator).__name__,
                model_uid=estimator.uid, grid_index=gi,
                params=dict(params),
                metric_values=[float(v) for v in metric_rows[gi]])
            for gi, params in enumerate(grid)]

    # -- split construction ------------------------------------------------
    def _splits(self, y: np.ndarray
                ) -> List[Tuple[np.ndarray, np.ndarray]]:
        raise NotImplementedError

    def _assignments(self, y: np.ndarray, k: int) -> np.ndarray:
        """Fold id per row; -1 = dropped. Folds are exactly equal-sized
        (up to k-1 remainder rows are dropped): every fold's train set
        then has the same static shape, so one XLA program per family
        covers all folds instead of recompiling per fold — the
        TPU-native replacement for MLUtils.kFold's uneven splits
        (documented deviation; at most k-1 of n rows are unused)."""
        rng = np.random.default_rng(self.seed)
        assign = np.full(len(y), -1, dtype=np.int64)

        def round_robin(idx: np.ndarray):
            m = (len(idx) // k) * k
            perm = rng.permutation(idx)
            assign[perm[:m]] = np.arange(m) % k

        if self.stratify:
            for cls in np.unique(y):
                round_robin(np.nonzero(y == cls)[0])
        else:
            round_robin(np.arange(len(y)))
        return assign

    def _use_batched_kernel(self, estimator) -> bool:
        """Whether to hand this family's grid to its batched fold
        kernel: it must expose one. (r3's ``fold_grid_needs_mesh``
        escape hatch is gone — the MLP's fixed-trip mini-batch solver
        removed the last family whose batched kernel lost to the
        sequential path on one device.)"""
        return hasattr(estimator, "fit_fold_grid_arrays")

    def _try_device_eval(self, estimator, grid, X, y, masks,
                         X_val_st, y_val_st, spec, cand_idx=None,
                         val_rows=None):
        """(F, G) metric matrix from the family's fused fit+metric
        device kernel, or None to fall through to the host paths.
        This is the device-resident search: candidates' fitted
        parameters never reach the host — only these floats do (the
        winner is refit from scratch by the selector afterwards).
        ``cand_idx`` (racing rungs) evaluates only that candidate
        subset: the returned matrix is then (F, len(cand_idx)).
        ``val_rows`` ((F, nv) positions with ``X_val_st[f] ==
        X[val_rows[f]]``, from _build_fold_arrays; None where the
        validation rows are not rows of ``X``) goes to the families
        whose kernel takes it (the tree families score the held-out
        rows where their fit already put them, models/trees._eval_form);
        the others are called as ever."""
        if (X_val_st is None or spec is None
                or not hasattr(estimator, "eval_fold_grid_arrays")
                or not self._use_batched_kernel(estimator)):
            return None
        kwargs = {} if cand_idx is None else {"cand_idx": cand_idx}
        if val_rows is not None and "val_rows" in inspect.signature(
                estimator.eval_fold_grid_arrays).parameters:
            kwargs["val_rows"] = val_rows
        try:
            return estimator.eval_fold_grid_arrays(
                X, y, masks, grid, X_val_st, y_val_st, spec,
                mesh=self._resolve_mesh(), **kwargs)
        except NotImplementedError:
            return None         # grid/labels not traceable -> host path
        except FamilyPreconditionError as e:
            # family precondition violated (e.g. NaiveBayes on negative
            # features): the sequential path below raises it per fold,
            # dropping the family with NaN metrics instead of failing.
            # Deliberately NOT a blanket ValueError catch — a genuine
            # kernel bug must propagate, not silently degrade every
            # search to the host path.
            _log.warning("device eval kernel for %s rejected the "
                         "data: %s", type(estimator).__name__, e)
            return None

    def _results_from_matrix(self, estimator, grid, mm
                             ) -> List[ValidationResult]:
        return [
            ValidationResult(
                model_name=type(estimator).__name__,
                model_uid=estimator.uid, grid_index=gi,
                params=dict(params),
                metric_values=[float(v) for v in mm[:, gi]])
            for gi, params in enumerate(grid)]

    # -- shared fold/array preparation -------------------------------------
    def _build_fold_arrays(self, X: np.ndarray, y: np.ndarray):
        """(splits, masks, fold_data, spec, X_val_st, y_val_st,
        val_rows) — the arrays every validation strategy (exact and
        racing) shares. ``val_rows`` (F, nv) int32 stacks the folds'
        validation indices beside the ``X_val_st`` they select
        (``X_val_st[f]`` is ``X[val_rows[f]]``), None where that is.
        fold_data is materialized ONCE per search; stable array identity
        also lets the tree family's host-side binning memoize per
        fold. This is also where the search mesh resolves: from here on
        every family kernel places the flattened fold x grid candidate
        axis on the mesh's ``models`` axis (parallel/cv.py et al.)."""
        self._resolve_mesh()
        splits = self._splits(y)
        masks = np.zeros((len(splits), len(y)))
        for f, (train_idx, _) in enumerate(splits):
            masks[f, train_idx] = 1.0
        # a feature matrix the compiled prepare plan left on device
        # (plans/prepare.py) stages its folds with device gathers and a
        # device stack — the matrices the search consumes never
        # round-trip through the host (y is host-side by construction)
        xp = np
        if not isinstance(X, (np.ndarray, type(None))) \
                and type(X).__module__.partition(".")[0] != "numpy":
            import jax.numpy as jnp
            xp = jnp
        fold_data = [(X[tr], y[tr], X[va], y[va]) for tr, va in splits]
        # stacked validation folds for the device-resident fast path
        # (fold sizes are equal by _assignments construction)
        spec = self.evaluator.device_metric_spec()
        X_val_st = y_val_st = val_rows = None
        if spec is not None and len({len(va) for _, va in splits}) == 1:
            X_val_st = xp.stack([fd[2] for fd in fold_data])
            y_val_st = np.stack([fd[3] for fd in fold_data])
            val_rows = np.stack([va for _, va in splits]).astype(np.int32)
        return splits, masks, fold_data, spec, X_val_st, y_val_st, val_rows

    def _dispatch_device_evals(self, tasks, X, masks, X_val_st, y_val_st,
                               spec, ctx: Optional[RuntimeContext] = None,
                               rung: Optional[int] = None,
                               rung_label: str = "exact"):
        """Run per-family device-eval thunks, threaded when profitable.

        ``tasks`` is [(family_name, family_key, cand_indices, thunk),
        ...]; returns per-task results in order: an (F, G) metric
        matrix, None (no device path — host evaluation takes over), or
        the ``_QUARANTINED`` sentinel.

        Dispatch every family's device kernel BEFORE fetching any
        result: each kernel ends in a blocking device->host fetch, so a
        sequential loop would stall family B's dispatch on family A's
        transfer. Threads overlap host orchestration + transfers with
        on-chip compute (the chip still serializes the programs); JAX
        tracing/dispatch is thread-safe and the shared binning memo in
        models/trees serializes under its own lock.
        size guard: concurrent dispatch keeps EVERY family's input
        buffers + intermediates resident at once — at search sizes
        that's noise, but a huge matrix could push peak HBM past the
        chip where the sequential loop (family A freed before B
        uploads) would have fit. Beyond the cap, dispatch sequentially.
        Workers are capped at os.cpu_count() (more threads than cores
        only adds GIL churn) and each task renames its worker thread to
        ``tx-family-<Name>`` so profiler lanes and the compile-time
        accumulator (utils/compile_time.py) attribute work to a
        family.

        Fault tolerance (runtime/, docs/resilience.md), active when a
        RuntimeContext is supplied:

        - journaled (family, cands, rung) evaluations replay from the
          checkpoint without dispatching anything;
        - transient backend errors (preemption / RESOURCE_EXHAUSTED
          shapes) retry under ``ctx.retry`` with backoff; persistent or
          family-fatal errors quarantine the family (the sentinel) and
          the search continues with survivors — only a classified BUG
          propagates;
        - with ``ctx.family_deadline`` set, a family whose dispatch
          outlives the deadline is abandoned on its thread and
          quarantined, so one hung backend cannot stall the rung
          barrier forever."""
        import threading

        from ..utils import compile_time
        compile_time.install()
        folds = int(masks.shape[0])

        def named(name, fn):
            th = threading.current_thread()
            label = f"tx-family-{name}"
            prev, th.name = th.name, label
            t0 = time.perf_counter()
            c0 = compile_time.compile_seconds_by_thread().get(label, 0.0)
            try:
                return fn()
            finally:
                rec = _FAMILY_PROFILE.setdefault(
                    name, {"seconds": 0.0, "compile": 0.0, "calls": 0})
                rec["seconds"] += time.perf_counter() - t0
                rec["compile"] += (compile_time.compile_seconds_by_thread()
                                   .get(label, 0.0) - c0)
                rec["calls"] += 1
                th.name = prev

        span_parent = None      # the dispatch span's ref, set below

        def run_task(name, key, cands, thunk):
            with _trace.span("search.family", parent=span_parent,
                             family=name, rung=rung_label,
                             cands=len(cands), folds=folds):
                return run_task_traced(name, key, cands, thunk)

        def run_task_traced(name, key, cands, thunk):
            if ctx is not None:
                cached = ctx.journal_lookup(key, rung_label, cands)
                if cached is not None:
                    # journal stores per-candidate fold vectors; the
                    # dispatch contract is (folds, candidates)
                    _trace.add_event("journal.replay", family=name,
                                     rung=rung_label, cands=len(cands))
                    return np.asarray(cached, dtype=np.float64).T

            def attempt():
                maybe_inject("family", name, "dispatch")
                return thunk()

            retries = [0]
            try:
                if ctx is not None:
                    mm = named(name, lambda: ctx.retry.call(
                        attempt, description=f"dispatch:{name}",
                        on_retry=lambda a, e: retries.__setitem__(
                            0, a + 1)))
                else:
                    mm = named(name, attempt)
            except Exception as e:
                kind = classify_error(e)
                if ctx is None or kind == BUG:
                    raise
                ctx.quarantine(
                    name, f"{type(e).__name__}: {e}", kind=kind,
                    error_type=type(e).__name__, rung=rung,
                    retries=retries[0])
                return _QUARANTINED
            if mm is None:
                return None
            if maybe_inject("family", name, "metric") == "nan":
                mm = np.full_like(np.asarray(mm, dtype=np.float64),
                                  np.nan)
            arr = np.asarray(mm, dtype=np.float64)
            if ctx is not None and arr.size:
                bad = 1.0 - float(np.mean(np.isfinite(arr)))
                if bad >= ctx.nan_quarantine_fraction:
                    ctx.quarantine(
                        name,
                        f"{bad:.0%} of device metrics non-finite",
                        kind="metrics", rung=rung)
                    return _QUARANTINED
            _telemetry.note_dispatch(key, rung_label, tuple(cands),
                                     folds)
            if ctx is not None:
                ctx.journal_record(key, rung_label, cands,
                                   arr.T.tolist(), folds)
            return arr

        async_cap = int(os.environ.get("TX_ASYNC_FAMILIES_MAX_BYTES",
                                       256 * 1024 * 1024))
        dispatch_bytes = _async_dispatch_bytes(X, masks, X_val_st,
                                               y_val_st)
        deadline = ctx.family_deadline if ctx is not None else None
        # mesh-slot cap: with the sharded search active, each family's
        # kernel already spans the whole mesh — see _dispatch_workers.
        # A deadline forces the threaded path regardless: abandonment
        # of a hung family only works from a worker thread.
        workers = self._dispatch_workers(len(tasks))
        if deadline is not None:
            workers = min(len(tasks), max(2, workers))
        threaded = (len(tasks) > 1 and workers > 1 and spec is not None
                    and dispatch_bytes <= async_cap
                    and os.environ.get("TX_ASYNC_FAMILIES", "1") != "0")

        def pooled():
            from concurrent.futures import ThreadPoolExecutor
            from concurrent.futures import TimeoutError as _FutTimeout
            from concurrent.futures import wait as _fut_wait
            ex = ThreadPoolExecutor(max_workers=workers,
                                    thread_name_prefix="tx-family")
            futures = [ex.submit(run_task, *t) for t in tasks]
            t_submit = time.monotonic()
            results, kill = [], None
            for (name, _, _, _), f in zip(tasks, futures):
                try:
                    timeout = (None if deadline is None else max(
                        0.05, deadline - (time.monotonic() - t_submit)))
                    results.append(f.result(timeout=timeout))
                except _FutTimeout:
                    ctx.quarantine(
                        name,
                        f"family dispatch exceeded the {deadline:g}s "
                        f"deadline (backend hung or wedged); thread "
                        f"abandoned", kind="deadline", rung=rung)
                    results.append(_QUARANTINED)
                except BaseException as e:
                    # only classified bugs and KillPoints reach here —
                    # run_task absorbs everything quarantinable. Drain
                    # the remaining in-flight families first so their
                    # journal records land (a resumed search must not
                    # lose work that actually completed), then re-raise.
                    kill = e
                    results.append(_QUARANTINED)
            if kill is not None:
                _fut_wait(futures, timeout=deadline or 30.0)
                ex.shutdown(wait=False)
                raise kill
            # with a deadline, an abandoned thread may still be running:
            # do not join it — the whole point is not to wait forever
            ex.shutdown(wait=deadline is None)
            return results

        with _trace.span("search.dispatch", families=len(tasks),
                         workers=workers if threaded else 1,
                         threaded=int(threaded),
                         dispatch_bytes=dispatch_bytes) as rec:
            # family spans run on pool worker threads where the
            # context-var stack is empty: parent them explicitly to the
            # dispatch span
            span_parent = _trace.current_ref()
            results = (pooled() if threaded
                       else [run_task(*t) for t in tasks])
            if rec is not None:
                # the families with no device program for this search:
                # the caller evaluates them on the host, one fit after
                # another (counter ``host_path_families``)
                rec["attrs"]["host_path"] = ",".join(
                    t[0] for t, r in zip(tasks, results) if r is None)
            return results

    def _device_matrices(self, models, X, y, masks, X_val_st, y_val_st,
                         spec, ctx: Optional[RuntimeContext] = None,
                         val_rows=None):
        """Per-family (F, G) device metric matrices (None entries fall
        through to the host paths; ``_QUARANTINED`` entries are out of
        the search)."""
        tasks = [
            (type(est).__name__, self._family_key(fi, est),
             tuple(range(len(grid))),
             (lambda e=est, g=grid: self._try_device_eval(
                 e, g, X, y, masks, X_val_st, y_val_st, spec,
                 val_rows=val_rows)))
            for fi, (est, grid) in enumerate(models)]
        return self._dispatch_device_evals(tasks, X, masks, X_val_st,
                                           y_val_st, spec, ctx=ctx)

    def _family_host_results(self, estimator, grid, X, y, masks,
                             fold_data) -> List[ValidationResult]:
        """Host evaluation of one family: batched fold x grid kernel when
        available, per-candidate sequential fits otherwise. Counted once
        a family a search (``host_path_families``): a default pool that
        counts any has a family without a fold-grid device program."""
        _telemetry.count("host_path_families")
        results: List[ValidationResult] = []
        # fast path: families exposing a fold x grid kernel train all
        # candidates in ONE batched XLA program (mesh-sharded when
        # self.mesh is set) instead of len(grid) x folds fits
        fitted = None
        if self._use_batched_kernel(estimator):
            try:
                fitted = estimator.fit_fold_grid_arrays(
                    X, y, masks, grid, mesh=self._resolve_mesh())
            except NotImplementedError:
                fitted = None   # grid not traceable -> sequential
            except FamilyPreconditionError as e:
                # family precondition violated (e.g. NaiveBayes on
                # negative features): the sequential path raises it
                # per fold below, dropping the family out of the
                # race with NaN metrics instead of failing the search
                _log.warning("batched kernel for %s rejected the "
                             "data: %s", type(estimator).__name__, e)
                fitted = None
        # batched evaluation: all tree-family candidates of a fold
        # predict in ONE device program (others fall through to the
        # per-candidate path)
        fold_raw = ([_batched_fold_raw(fitted[f], fold_data[f][2])
                     for f in range(len(fold_data))]
                    if fitted is not None else None)
        for gi, params in enumerate(grid):
            candidate = (None if fitted is not None
                         else estimator.with_params(**params))
            res = ValidationResult(
                model_name=type(estimator).__name__,
                model_uid=estimator.uid, grid_index=gi,
                params=dict(params))
            for f, (X_tr, y_tr, X_val, y_val) in enumerate(fold_data):
                try:
                    if fitted is not None:
                        model: PredictionModel = fitted[f][gi]
                        raw = fold_raw[f].get(gi)
                        pred = (model.prediction_from_raw(raw)
                                if raw is not None
                                else model.predict_arrays(X_val))
                    else:
                        model = candidate.fit_arrays_guarded(X_tr, y_tr)
                        pred = model.predict_arrays(X_val)
                    metrics = self.evaluator.evaluate_arrays(
                        y_val, pred)
                    res.metric_values.append(
                        self.evaluator.metric_from(metrics))
                except (ValueError, FloatingPointError) as e:
                    # a family whose preconditions the data violates
                    # (e.g. NaiveBayes on negative features) drops out
                    # of the race instead of failing the whole search
                    _log.warning("candidate %s%s failed on a fold: %s",
                                 res.model_name, params, e)
                    res.metric_values.append(float("nan"))
            results.append(res)
        return results

    def _host_results_journaled(self, fi, estimator, grid, X, y, masks,
                                fold_data, ctx: RuntimeContext
                                ) -> List[ValidationResult]:
        """Host evaluation of one family behind the runtime: journal
        replay first, quarantine-on-classified-failure, journal append
        on success. Label ``"exact-host"`` keeps host metric vectors
        from ever replaying into the device-matrix path (they are
        float-identical in theory, but the journal's contract is
        bit-exactness, not theory)."""
        key = self._family_key(fi, estimator)
        cands = tuple(range(len(grid)))
        cached = ctx.journal_lookup(key, "exact-host", cands)
        if cached is not None:
            _trace.add_event("journal.replay",
                             family=type(estimator).__name__,
                             rung="exact-host", cands=len(cands))
            return self._results_from_journal(estimator, grid, cached)
        try:
            with _trace.span("search.family",
                             family=type(estimator).__name__,
                             rung="exact-host", path="host",
                             cands=len(cands), folds=len(fold_data)):
                host = self._family_host_results(estimator, grid, X, y,
                                                 masks, fold_data)
        except Exception as e:
            kind = classify_error(e)
            if kind == BUG:
                raise
            ctx.quarantine(type(estimator).__name__,
                           f"{type(e).__name__}: {e}", kind=kind,
                           error_type=type(e).__name__)
            return []
        _telemetry.note_dispatch(key, "exact-host", cands,
                                 len(fold_data))
        ctx.journal_record(key, "exact-host", cands,
                           [r.metric_values for r in host],
                           len(fold_data))
        return host

    # -- main loop (reference getSummary, OpValidator.scala:270-310) -------
    def validate(self,
                 models: Sequence[Tuple[Predictor, Sequence[Dict]]],
                 X: np.ndarray, y: np.ndarray) -> BestEstimator:
        models = [(est, list(grid) or [{}]) for est, grid in models]
        ctx = self._begin_runtime(models, X, y)
        try:
            _, masks, fold_data, spec, X_val_st, y_val_st, val_rows = \
                self._build_fold_arrays(X, y)
            results: List[ValidationResult] = []
            device_mm = self._device_matrices(models, X, y, masks,
                                              X_val_st, y_val_st, spec,
                                              ctx=ctx, val_rows=val_rows)
            for fi, ((estimator, grid), mm) in enumerate(
                    zip(models, device_mm)):
                if mm is _QUARANTINED:
                    continue
                if mm is not None:
                    results.extend(self._results_from_matrix(
                        estimator, grid, mm))
                    continue
                results.extend(self._host_results_journaled(
                    fi, estimator, grid, X, y, masks, fold_data, ctx))
        finally:
            ctx.close_journal()
        return self._pick_best(models, results, ctx=ctx)

    def validate_prepared(self,
                          models: Sequence[Tuple[Predictor, Sequence[Dict]]],
                          folds: Sequence[Tuple[np.ndarray, np.ndarray,
                                                np.ndarray, np.ndarray]]
                          ) -> BestEstimator:
        """Validate over pre-materialized per-fold data — the
        workflow-level-CV entry point (reference OpValidator.applyDAG:228
        + getSummary): each fold's in-CV DAG segment was refit on that
        fold's train rows, so feature matrices may differ across folds
        (even in width). ``folds`` is [(X_tr, y_tr, X_val, y_val), ...].
        Grid batching still applies per fold via the family kernels.

        Fault tolerance: a family whose evaluation raises a classified
        transient/family error is quarantined (the workflow-CV search
        degrades to survivors exactly like the array-level path); the
        per-fold journal is NOT written here — fold matrices differ per
        refit DAG segment, so there is no stable fingerprint to key a
        resume on (docs/resilience.md)."""
        spec = self.evaluator.device_metric_spec()
        self._resolve_mesh()
        models = [(est, list(grid) or [{}]) for est, grid in models]
        ctx = self._begin_runtime(models, None, None)
        results: List[ValidationResult] = []
        for estimator, grid in models:
            try:
                fam = self._prepared_family_results(
                    estimator, grid, folds, spec)
            except Exception as e:
                kind = classify_error(e)
                if kind == BUG:
                    raise
                ctx.quarantine(type(estimator).__name__,
                               f"{type(e).__name__}: {e}", kind=kind,
                               error_type=type(e).__name__)
                continue
            results.extend(fam)
        return self._pick_best(models, results, ctx=ctx)

    def _prepared_family_results(self, estimator, grid, folds, spec
                                 ) -> List[ValidationResult]:
        """One family's results over pre-materialized folds (the body
        validate_prepared quarantines as a unit)."""
        results: List[ValidationResult] = []
        # device-resident fast path, one fold at a time (fold
        # matrices may differ in shape after per-fold DAG refits,
        # so they cannot stack into one kernel call)
        mm = None
        if spec is not None:
            rows = []
            for X_tr, y_tr, X_val, y_val in folds:
                row = self._try_device_eval(
                    estimator, grid, X_tr, y_tr,
                    np.ones((1, len(y_tr))), X_val[None],
                    np.asarray(y_val)[None], spec)
                if row is None:
                    break
                rows.append(row[0])
            else:
                mm = np.stack(rows) if rows else None
        if mm is not None:
            return self._results_from_matrix(estimator, grid, mm)
        fitted = None
        if self._use_batched_kernel(estimator):
            try:
                fitted = [
                    estimator.fit_fold_grid_arrays(
                        X_tr, y_tr, np.ones((1, len(y_tr))), grid,
                        mesh=self._resolve_mesh())[0]
                    for X_tr, y_tr, _, _ in folds]
            except NotImplementedError:
                fitted = None
            except FamilyPreconditionError as e:
                _log.warning("batched kernel for %s rejected the "
                             "data: %s", type(estimator).__name__, e)
                fitted = None
        fold_raw = ([_batched_fold_raw(fitted[f], folds[f][2])
                     for f in range(len(folds))]
                    if fitted is not None else None)
        for gi, params in enumerate(grid):
            candidate = (None if fitted is not None
                         else estimator.with_params(**params))
            res = ValidationResult(
                model_name=type(estimator).__name__,
                model_uid=estimator.uid, grid_index=gi,
                params=dict(params))
            for f, (X_tr, y_tr, X_val, y_val) in enumerate(folds):
                try:
                    model = (fitted[f][gi] if fitted is not None
                             else candidate.fit_arrays_guarded(X_tr, y_tr))
                    raw = (fold_raw[f].get(gi)
                           if fitted is not None else None)
                    pred = (model.prediction_from_raw(raw)
                            if raw is not None
                            else model.predict_arrays(X_val))
                    metrics = self.evaluator.evaluate_arrays(y_val, pred)
                    res.metric_values.append(
                        self.evaluator.metric_from(metrics))
                except (ValueError, FloatingPointError) as e:
                    _log.warning("candidate %s%s failed on a fold: %s",
                                 res.model_name, params, e)
                    res.metric_values.append(float("nan"))
            results.append(res)
        return results

    def _pick_best(self, models, results: List[ValidationResult],
                   rank_pool: Optional[List[ValidationResult]] = None,
                   ctx: Optional[RuntimeContext] = None
                   ) -> BestEstimator:
        """Winner among ``rank_pool`` (default: all results). Racing
        passes only full-fidelity finalists — a pruned candidate's
        low-fidelity metric is not comparable to a full-CV one — while
        every record still lands in ``BestEstimator.results``."""
        sign = 1.0 if self.evaluator.is_larger_better else -1.0
        pool = results if rank_pool is None else rank_pool
        finite = [r for r in pool if np.isfinite(r.mean_metric)]
        if not finite:
            if ctx is not None and ctx.quarantined:
                # nothing survived the quarantine ledger: ONE aggregated
                # error naming every family and reason, instead of
                # whichever family died first
                raise AllFamiliesFailedError(
                    ctx.quarantined,
                    detail="no family produced a finite validation "
                           "metric")
            raise ValueError(
                "all validation metrics are non-finite; cannot select a "
                "model (check for degenerate folds — e.g. a fold with a "
                "single class; stratify=True may help)")
        best = max(finite, key=lambda r: sign * r.mean_metric)
        by_uid = {est.uid: est for est, _ in models}
        winner = by_uid[best.model_uid].with_params(**best.params)
        return BestEstimator(estimator=winner, name=best.model_name,
                             params=best.params, metric=best.mean_metric,
                             results=results)


class CrossValidation(_ValidatorBase):
    """k-fold CV (reference OpCrossValidation.scala:40,71)."""

    validation_type = "CrossValidation"

    def __init__(self, evaluator: Evaluator, num_folds: int = 3,
                 seed: int = 42, stratify: bool = False, mesh="auto"):
        super().__init__(evaluator, seed, stratify, mesh=mesh)
        if num_folds < 2:
            raise ValueError("num_folds must be >= 2")
        self.num_folds = num_folds

    def _splits(self, y):
        assign = self._assignments(y, self.num_folds)
        return [(np.nonzero((assign != f) & (assign >= 0))[0],
                 np.nonzero(assign == f)[0])
                for f in range(self.num_folds)]

    def get_params(self):
        return {"numFolds": self.num_folds, "seed": self.seed,
                "stratify": self.stratify}


class TrainValidationSplit(_ValidatorBase):
    """Single random split (reference OpTrainValidationSplit.scala:48)."""

    validation_type = "TrainValidationSplit"

    def __init__(self, evaluator: Evaluator, train_ratio: float = 0.75,
                 seed: int = 42, stratify: bool = False, mesh="auto"):
        super().__init__(evaluator, seed, stratify, mesh=mesh)
        if not 0.0 < train_ratio < 1.0:
            raise ValueError("train_ratio must be in (0, 1)")
        self.train_ratio = train_ratio

    def _splits(self, y):
        # exact single split honoring train_ratio (stratified on request)
        from .splitters import stratified_split
        rng = np.random.default_rng(self.seed)
        if self.stratify:
            train_idx, val_idx = stratified_split(
                y, 1.0 - self.train_ratio, rng)
        else:
            perm = rng.permutation(len(y))
            n_val = int(round(len(y) * (1.0 - self.train_ratio)))
            train_idx, val_idx = np.sort(perm[n_val:]), np.sort(perm[:n_val])
        if len(val_idx) == 0 or len(train_idx) == 0:
            raise ValueError(
                f"train_ratio={self.train_ratio} leaves an empty train or "
                f"validation set for n={len(y)} rows")
        return [(train_idx, val_idx)]

    def get_params(self):
        return {"trainRatio": self.train_ratio, "seed": self.seed,
                "stratify": self.stratify}
