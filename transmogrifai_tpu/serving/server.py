"""Async micro-batching serving loop: live requests -> bucketed
compiled dispatches under latency SLOs.

Every prior serving entry point scores a MATERIALIZED batch: the caller
already holds all the rows. "Millions of users" (ROADMAP north star)
means concurrent single-record requests arriving on their own clock —
and per-request dispatch wastes the compiled bucket programs the
:class:`~.plan.ScoringPlan` exists to amortize (a batch-of-1 pays the
same fixed dispatch cost as a batch-of-64), while unbounded coalescing
blows the tail latency. This module is the middle path, the
batching-vs-latency tradeoff the Gemma-on-TPU serving comparison in
PAPERS.md frames:

- **Deadline-or-full coalescing.** Requests queue per (model, tenant)
  lane; a lane dispatches when its queue reaches the coalescer's
  target bucket OR the oldest request has waited ``max_wait_ms`` —
  whichever comes first. The target bucket is picked from the plan's
  RECORDED per-bucket dispatch costs (:meth:`~.plan.ScoringPlan
  .bucket_profile`, the "A Learned Performance Model for TPUs"
  direction in PAPERS.md) rather than a static default.
- **Double buffering.** Host-side boxing/encoding of batch k+1
  (:meth:`~.plan.ScoringPlan.encode_raw_dataset`, the encode pool)
  overlaps batch k's in-flight device program
  (:meth:`~.plan.ScoringPlan.dispatch_encoded`, the device lane); a
  semaphore bounds the pipeline at one in-flight dispatch so the
  collector never runs unboundedly ahead.
- **Per-tenant guardrails.** Each tenant carries its own PR-5 stack:
  schema admission with machine-readable quarantine reasons, an output
  guard, a circuit breaker + per-batch deadline around device dispatch
  with the host columnar fallback, and a drift sentinel fed from the
  live stream. One tenant's breaker trip routes ITS batches to the
  fallback pool — another tenant's queue keeps dispatching to the
  device lane (isolation asserted in tests/test_serving_loop.py). A
  hung backend is ORPHANED at the deadline: the device executor is
  abandoned and replaced, so the event loop never wedges behind it.
- **Multi-model plan cache.** N fitted models stay resident under an
  LRU budget keyed by (model dir, bucket range); evictions are counted
  (``serve_plan_cache_evictions``) and an evicted model transparently
  recompiles on next use — one process serves a model zoo.

The whole hot path runs through the already-fused ScoringPlan bucket
programs, so steady state pays ZERO compiles (asserted); per-request
results are bitwise identical to offline ``score_guarded()`` on the
same rows (asserted). Entry points: ``python -m transmogrifai_tpu.cli
serve`` (JSON-lines over TCP, cli/serve.py) and the in-process
:class:`ServingClient` for tests and in-process load generators.
Blocking calls are banned from the async handlers by lint rule TX-J10
(docs/lint.md); everything blocking runs in a named executor.
"""
from __future__ import annotations

import asyncio
import collections
import concurrent.futures as _cf
import logging
import os
import threading
import time
from dataclasses import dataclass, field
from typing import (Any, Callable, Dict, List, Optional, Sequence,
                    Tuple)

import itertools

import numpy as np

from ..observability import trace as _trace
from ..observability.metrics import ServeMetrics
from ..runtime import telemetry as _telemetry
from .admission import AdmissionConfig, AdmissionController, ServeShed
from .guard import (AdmissionPolicy, BreakerOpenError, CircuitBreaker,
                    GuardReason, OutputGuard, SchemaGuard,
                    _invalidate_rows)
from .plan import EncodedScoreBatch, ScoringPlan

_log = logging.getLogger(__name__)

__all__ = ["ServeConfig", "ServingServer", "ServingClient", "PlanCache",
           "ServeRejected", "ServeDraining", "ServeShed",
           "AdmissionConfig", "AdmissionController", "serve_in_process"]

from ..tuning.registry import STATIC_DEFAULTS as _TUNABLES

#: coalescer target when no bucket profile has been recorded yet (the
#: number lives in tuning/registry.py — lint rule TX-T01)
_DEFAULT_TARGET = int(_TUNABLES["serving.target_batch"])

#: raw admitted records retained per model for the warm-restart
#: snapshot's prewarm manifest (serving/state.py) — enough to cycle
#: into any recorded bucket, small enough to serialize
_SAMPLE_RING = 8


class ServeRejected(RuntimeError):
    """A request was refused before scoring (queue over its
    backpressure limit, unknown model, or server shutdown)."""


class ServeDraining(ServeRejected):
    """The loop is draining toward a graceful shutdown: queued and
    in-flight requests will still be answered, but NEW requests are
    refused with a machine-readable ``"draining"`` answer so a
    reconnecting client (serving/client.py) retries against the next
    incarnation instead of counting a failure."""


@dataclass
class ServeConfig:
    """Knobs of the serving loop (docs/serving_loop.md)."""
    #: SLO half of deadline-or-full: a request waits at most this long
    #: in the coalescing queue before its lane dispatches
    max_wait_ms: float = 5.0
    #: coalescer target batch; None derives it per lane from the
    #: plan's recorded ``bucket_profile()`` (largest bucket whose warm
    #: per-dispatch cost fits inside max_wait_ms)
    target_batch: Optional[int] = None
    #: hard cap on rows per dispatch (<= the plan's max bucket)
    max_batch: int = 256
    #: per-lane backpressure: requests beyond this are rejected with
    #: ServeRejected instead of growing the queue without bound
    queue_limit: int = 4096
    #: LRU budget of the multi-model plan cache (resident plans)
    plan_budget: int = 4
    #: per-tenant PR-5 guardrails (admission/output/breaker/sentinel);
    #: False = raw dispatch (no quarantine, no breaker, no sentinel)
    guardrails: bool = True
    admission: Optional[AdmissionPolicy] = None
    #: drift sentinel per tenant (requires guardrails)
    sentinel: bool = True
    drift_thresholds: Any = None
    #: per-batch device dispatch deadline; a dispatch still running at
    #: the deadline is ORPHANED (executor abandoned + replaced) and the
    #: batch falls back to the host columnar path
    deadline_seconds: Optional[float] = None
    #: per-tenant breaker parameters (breaker_factory overrides, e.g.
    #: to inject a test clock)
    breaker_failures: int = 3
    breaker_cooldown_seconds: float = 30.0
    breaker_factory: Optional[Callable[[], CircuitBreaker]] = None
    #: self-healing lifecycle (serving/lifecycle.LifecycleConfig);
    #: None (the default) disables drift-triggered retraining entirely
    #: — the loop behaves byte-identically to a build without it
    lifecycle: Any = None
    #: overload admission control (serving/admission.AdmissionConfig);
    #: None (the default, and `tx serve --admission=off`) constructs
    #: no controller — the enqueue edge, dispatch semaphore and every
    #: answer are byte-identical to a build without docs/admission.md
    admission_control: Optional[AdmissionConfig] = None
    #: coalescer split policy (docs/ragged_batching.md):
    #: "deadline_or_full" (the classic rule) or "predicted_cost"
    #: (split a popped batch at a lattice rung when the cost model
    #: predicts the smaller dispatch is cheaper per row); None defers
    #: to the tuning policy, which only upgrades off the default when
    #: a tuned lattice AND recorded score costs exist
    coalesce_policy: Optional[str] = None


@dataclass
class _Request:
    record: dict
    future: asyncio.Future
    arrived: float
    #: request id, generated at admission (or supplied by the TCP
    #: client) and propagated enqueue -> coalesce -> encode -> dispatch
    #: -> reply; the trace id of this request's span tree
    rid: str = ""


@dataclass
class _CacheEntry:
    model: Any
    plan: ScoringPlan
    result_names: List[str]
    guards: Dict[str, "_TenantGuards"] = field(default_factory=dict)


class _TenantGuards:
    """One tenant's PR-5 stack over a shared compiled plan. The plan
    itself stays UNGUARDED (``plan.guard is None``) — guard state that
    used to live on the plan (breaker, sentinel sketches) lives here,
    per tenant, so tenants fail and recover independently."""

    def __init__(self, model, config: ServeConfig):
        self.schema: Optional[SchemaGuard] = None
        self.output: Optional[OutputGuard] = None
        self.breaker: Optional[CircuitBreaker] = None
        self.sentinel = None
        if not config.guardrails:
            return
        self.schema = SchemaGuard(model, policy=config.admission)
        self.output = OutputGuard()
        self.breaker = (config.breaker_factory()
                        if config.breaker_factory is not None else
                        CircuitBreaker(
                            failure_threshold=config.breaker_failures,
                            cooldown_seconds=(
                                config.breaker_cooldown_seconds)))
        if config.sentinel:
            from .sentinel import DriftSentinel
            self.sentinel = DriftSentinel.for_model(
                model, thresholds=config.drift_thresholds)


#: marker pinned when a tenant swap had no previous override (rollback
#: must REMOVE the override, not restore a None entry)
_NO_OVERRIDE = object()


class PlanCache:
    """LRU of compiled ScoringPlans keyed by (model dir, bucket range)
    — the compile-cache budget that turns one process into a model-zoo
    server. Eviction drops the plan (and its jitted programs) but
    keeps the loader, so an evicted model transparently reloads +
    recompiles on next use; hits/misses/evictions are counted.

    Hot-swaps go through :meth:`swap_entry`/:meth:`rollback` ONLY (lint
    rule TX-R03 bans in-place mutation of a live entry): the replace is
    one dict assignment, atomic between batches — a prepare that
    already captured the old entry finishes on it, the next prepare
    resolves the new one, and the previous entry stays PINNED for one
    generation so a post-swap fault rolls back instantly."""

    def __init__(self, budget: int = 4):
        if budget < 1:
            raise ValueError("plan cache budget must be >= 1")
        self.budget = int(budget)
        #: name -> loader (model dir string, or an in-memory model)
        self._loaders: Dict[str, Any] = {}
        self._entries: "collections.OrderedDict[Tuple, _CacheEntry]" = \
            collections.OrderedDict()
        #: (name, tenant) -> swapped-in entry (tenant-scoped hot-swaps;
        #: resolution order: override, then the shared LRU entry)
        self._overrides: Dict[Tuple[str, str], _CacheEntry] = {}
        #: previous entry pinned per swap scope until commit/rollback
        self._pinned: Dict[Tuple[str, Optional[str]], Any] = {}
        self.evictions = 0
        self.hits = 0
        self.misses = 0

    def register(self, name: str, model_or_dir: Any) -> None:
        self._loaders[name] = model_or_dir

    def names(self) -> List[str]:
        return sorted(self._loaders)

    @staticmethod
    def _key(name: str, buckets: Tuple[int, int],
             lattice: Optional[Tuple[int, ...]]) -> Tuple:
        """Cache key. With ``lattice=None`` the key is EXACTLY the
        pre-lattice ``(name, buckets)`` shape, so cold starts, warm
        restarts (serving/state.py) and every existing snapshot keep
        resolving the same entries bitwise."""
        if lattice is None:
            return (name, buckets)
        return (name, buckets, tuple(int(b) for b in lattice))

    def get(self, name: str,
            buckets: Tuple[int, int] = (None, None),
            lattice: Optional[Tuple[int, ...]] = None) -> _CacheEntry:
        """Resident entry for ``name`` (LRU-bumped), loading the model
        and compiling its plan on a miss. Blocking — call from an
        executor, never from the event loop."""
        if name not in self._loaders:
            raise ServeRejected(f"unknown model {name!r}; registered: "
                                f"{self.names()}")
        key = self._key(name, buckets, lattice)
        entry = self._entries.get(key)
        if entry is not None:
            self._entries.move_to_end(key)
            self.hits += 1
            _telemetry.count("serve_plan_cache_hits")
            return entry
        self.misses += 1
        _telemetry.count("serve_plan_cache_misses")
        loader = self._loaders[name]
        if isinstance(loader, str):
            from ..workflow.workflow import WorkflowModel
            model = WorkflowModel.load(loader)
        else:
            model = loader
        kwargs = {}
        if buckets[0] is not None:
            kwargs["min_bucket"] = buckets[0]
        if buckets[1] is not None:
            kwargs["max_bucket"] = buckets[1]
        if lattice is not None:
            kwargs["lattice"] = lattice
        # artifact-first compile (artifacts/loader.py, TX-R06): a
        # saved model's AOT executables deserialize instead of
        # compiling — a cache MISS (boot or eviction reload) costs a
        # file read, not an XLA compile; loud counted fallback
        # otherwise
        from ..artifacts.loader import load_or_compile
        plan = load_or_compile(
            model, model_dir=loader if isinstance(loader, str) else None,
            **kwargs)
        entry = _CacheEntry(
            model=model, plan=plan,
            result_names=[f.name for f in model.result_features])
        self._entries[key] = entry
        while len(self._entries) > self.budget:
            old_key, _old = self._entries.popitem(last=False)
            self.evictions += 1
            _telemetry.count("serve_plan_cache_evictions")
            _telemetry.event("serve_plan_evicted", model=old_key[0])
        return entry

    # -- hot-swap (the ONLY sanctioned live replacement, TX-R03) -----------
    def entry_for(self, name: str, tenant: str,
                  buckets: Tuple[int, int] = (None, None),
                  lattice: Optional[Tuple[int, ...]] = None
                  ) -> _CacheEntry:
        """Tenant-aware resolution: a tenant-scoped swapped-in entry
        wins; every other tenant resolves the shared LRU entry —
        untouched by a 'tenant'-policy swap, hence bitwise
        unaffected."""
        override = self._overrides.get((name, tenant))
        if override is not None:
            self.hits += 1
            _telemetry.count("serve_plan_cache_hits")
            return override
        return self.get(name, buckets, lattice)

    def swap_entry(self, name: str, new_entry: _CacheEntry,
                   tenant: Optional[str] = None,
                   buckets: Tuple[int, int] = (None, None),
                   lattice: Optional[Tuple[int, ...]] = None) -> None:
        """Atomically replace the live entry for ``name`` (one dict
        assignment — batches already holding the old entry finish on
        it; the next ``entry_for`` resolves ``new_entry``). The
        previous entry is pinned until :meth:`commit` or
        :meth:`rollback`. ``tenant=None`` swaps the shared entry for
        every tenant; a tenant name swaps only that tenant's
        resolution."""
        if name not in self._loaders:
            raise ServeRejected(f"unknown model {name!r}; registered: "
                                f"{self.names()}")
        if tenant is not None:
            self._pinned[(name, tenant)] = self._overrides.get(
                (name, tenant), _NO_OVERRIDE)
            self._overrides[(name, tenant)] = new_entry
        else:
            key = self._key(name, buckets, lattice)
            self._pinned[(name, None)] = self._entries.get(key)
            self._entries[key] = new_entry
        _telemetry.count("serve_plan_swaps")
        _telemetry.event("serve_plan_swapped", model=name,
                         tenant=tenant or "*")

    def rollback(self, name: str, tenant: Optional[str] = None,
                 buckets: Tuple[int, int] = (None, None),
                 lattice: Optional[Tuple[int, ...]] = None) -> bool:
        """Instantly restore the entry pinned by the last
        :meth:`swap_entry` for this scope. Returns False when nothing
        is pinned (already committed or never swapped)."""
        pin = (name, tenant)
        if pin not in self._pinned:
            return False
        prev = self._pinned.pop(pin)
        key = self._key(name, buckets, lattice)
        if tenant is not None:
            if prev is _NO_OVERRIDE:
                self._overrides.pop((name, tenant), None)
            else:
                self._overrides[(name, tenant)] = prev
        elif prev is not None:
            self._entries[key] = prev
        else:
            self._entries.pop(key, None)
        return True

    def commit(self, name: str, tenant: Optional[str] = None) -> None:
        """Unpin the previous entry after a healthy post-swap watch
        window — the swap becomes permanent and the old plan (and its
        compiled programs) may be released."""
        self._pinned.pop((name, tenant), None)

    def swapped_entries(self) -> Dict[Tuple[str, str], _CacheEntry]:
        """Live tenant-scoped overrides (metrics/introspection)."""
        return dict(self._overrides)

    def resident_entries(self) -> List[Tuple[Tuple, _CacheEntry]]:
        """Resident (key, entry) pairs, LRU first (introspection +
        the warm-restart snapshot, serving/state.py)."""
        return list(self._entries.items())

    def touch(self, name: str,
              buckets: Tuple[int, int] = (None, None),
              lattice: Optional[Tuple[int, ...]] = None) -> bool:
        """LRU-bump a resident entry without resolving it (no
        hit/miss accounting) — how a warm restart replays the
        snapshot's recorded LRU order (serving/state.py)."""
        key = self._key(name, buckets, lattice)
        if key not in self._entries:
            return False
        self._entries.move_to_end(key)
        return True

    def lru_order(self) -> List[Tuple[str, Tuple[int, int]]]:
        """Resident entry keys, least-recently-used first."""
        return list(self._entries.keys())


class _Lane:
    """One (model, tenant) coalescing queue + its collector task."""

    def __init__(self, model_name: str, tenant: str,
                 queue_limit: int = 4096):
        self.model_name = model_name
        self.tenant = tenant
        #: bounded at the backpressure limit (TX-R05): the enqueue edge
        #: rejects BEFORE append, so the maxlen never silently drops —
        #: it is the structural backstop, not the admission policy
        self.queue: "collections.deque[_Request]" = collections.deque(
            maxlen=max(int(queue_limit), 1))
        self.wakeup: Optional[asyncio.Event] = None   # built on the loop
        self.full: Optional[asyncio.Event] = None
        #: the collector's current deadline-or-full threshold; the
        #: enqueue edge signals ``full`` when the queue reaches it so
        #: the collector wakes ONCE per batch, not once per request
        self.target: int = _DEFAULT_TARGET
        self.task: Optional[asyncio.Task] = None


@dataclass
class _PreparedBatch:
    """Everything the dispatch stage needs, produced host-side in the
    encode pool (the double-buffered half)."""
    entry: _CacheEntry
    guards: _TenantGuards
    requests: List[_Request]
    enc: EncodedScoreBatch
    ds: Any
    quarantined: List[GuardReason]
    qmask: np.ndarray
    #: (model, tenant) lane + batch sequence number — span attributes
    model: str = ""
    tenant: str = ""
    seq: int = 0
    #: monotonic marks of the batch's pipeline stages
    #: (encode_t0/encode_t1/guard_t0/guard_t1, fallback flag); the
    #: request spans are reconstructed from these at resolve time
    marks: Dict[str, float] = field(default_factory=dict)
    #: set when the per-batch deadline orphaned this batch's dispatch:
    #: the batch was already answered through the host fallback, so a
    #: hung device thread that eventually wakes must NOT run the
    #: finish stage (it would double-count telemetry and re-observe
    #: rows on the sentinel, long after the batch resolved)
    abandoned: bool = False


class ServingServer:
    """The asyncio micro-batching scorer. Typical in-process use::

        server = ServingServer(ServeConfig(max_wait_ms=2.0))
        server.add_model("titanic", model)       # or a saved model dir
        client = server.start_background()
        row = client.score({"age": 31.0, ...}, model="titanic")
        server.stop()

    ``python -m transmogrifai_tpu.cli serve`` wraps the same object in
    a JSON-lines TCP front end (cli/serve.py)."""

    def __init__(self, config: Optional[ServeConfig] = None):
        self.config = config or ServeConfig()
        self.plans = PlanCache(budget=self.config.plan_budget)
        self._lanes: Dict[Tuple[str, str], _Lane] = {}
        self._default_model: Optional[str] = None
        self._running = False
        self.loop: Optional[asyncio.AbstractEventLoop] = None
        self._thread: Optional[threading.Thread] = None
        self._encode_pool = _cf.ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="tx-serve-encode")
        self._device_pool = _cf.ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="tx-serve-device")
        self._fallback_pool = _cf.ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="tx-serve-fallback")
        self._dispatch_sem: Optional[asyncio.Semaphore] = None
        #: live metrics (per-tenant latency histograms, answered/failed
        #: counts) — served by the {"metrics": true} control request
        #: and `tx serve --metrics-port` (docs/observability.md)
        self.metrics = ServeMetrics()
        self._batch_seq = itertools.count(1)
        #: float accumulators (occupancy/saturation; bench reads these)
        self.stats: Dict[str, float] = {
            "requests": 0, "batches": 0, "rows": 0,
            "full_dispatches": 0, "deadline_dispatches": 0,
            "dispatch_seconds": 0.0, "orphaned_dispatches": 0,
        }
        self._first_dispatch_at: Optional[float] = None
        self._last_dispatch_at: Optional[float] = None
        #: graceful-drain + warm-restart process state
        #: (docs/serving_restart.md)
        self._draining = False
        self._inflight = 0
        self._drain_event: Optional[asyncio.Event] = None
        #: readiness gate: False while a --resume-state boot is still
        #: restoring/prewarming; the TCP front end answers the
        #: {"ready": true} control request from this flag
        self.ready = True
        #: which restart of this serving identity we are (the
        #: --supervise parent bumps TX_SERVE_GENERATION per incarnation)
        self.restart_generation = int(
            os.environ.get("TX_SERVE_GENERATION", "0") or 0)
        #: wall-clock time of the last successful state snapshot, and
        #: the manager that writes them (attached by cli/serve.py when
        #: --state-dir/--resume-state is on; None = feature off)
        self.last_snapshot_at: Optional[float] = None
        self.state_manager = None
        #: per-model ring of recently admitted raw records — the
        #: snapshot's prewarm rows (serving/state.py)
        self._sample_records: Dict[str, "collections.deque"] = {}
        #: self-healing lifecycle manager — None unless
        #: ``config.lifecycle`` is an enabled LifecycleConfig
        self.lifecycle = None
        lc = self.config.lifecycle
        if lc is not None and getattr(lc, "enabled", False):
            from .lifecycle import ModelLifecycle
            self.lifecycle = ModelLifecycle(self, lc)
        #: telemetry-driven autotuning (docs/autotuning.md): one store
        #: snapshot's decisions for this server's lifetime. With an
        #: empty store or TX_TUNE=off every decision IS the static
        #: default, so behavior below is bitwise the untuned loop.
        from ..tuning.policy import TuningPolicy
        self.tuning = TuningPolicy()
        self._target_decision = self.tuning.target_batch(
            self.config.max_wait_ms, self.config.max_batch)
        lo_d, hi_d = self.tuning.bucket_range(self.config.max_batch)
        #: ScoringPlan bucket range for every plan this server
        #: compiles; (None, None) = plan defaults (and the SAME cache
        #: key as before, keeping cold-start bitwise)
        self.plan_buckets: Tuple[Optional[int], Optional[int]] = (
            (lo_d.chosen, hi_d.chosen)
            if (lo_d.tuned() or hi_d.tuned()) else (None, None))
        self._bucket_decisions = (lo_d, hi_d)
        #: padding-aware ragged batching (docs/ragged_batching.md):
        #: the tuning policy's per-plan bucket LATTICE, chosen from the
        #: recorded occupancy histogram × predicted per-bucket cost.
        #: Untuned (cold store / TX_TUNE=off / no improvement found)
        #: => None, and every plan + cache key stays bitwise the
        #: power-of-two build.
        self._lattice_decision = self.tuning.bucket_lattice(
            min_bucket=self.plan_buckets[0],
            max_bucket=self.plan_buckets[1])
        self.plan_lattice: Optional[Tuple[int, ...]] = (
            tuple(int(b) for b in self._lattice_decision.chosen)
            if self._lattice_decision.tuned() else None)
        #: coalescer split policy: caller (ServeConfig) wins, then an
        #: override pin, then the model (which only proposes
        #: "predicted_cost" when the lattice itself tuned)
        self._coalesce_decision = self.tuning.coalesce_policy(
            caller=self.config.coalesce_policy,
            lattice_tuned=self._lattice_decision.tuned())
        self.coalesce_policy = str(self._coalesce_decision.chosen)
        #: split dispatches taken by the predicted-cost coalescer
        self.stats.setdefault("split_dispatches", 0)
        #: overload admission (docs/admission.md) — None when
        #: ``config.admission_control`` is None: every path below
        #: byte-identical to a build without the controller
        self._admission: Optional[AdmissionController] = None
        if self.config.admission_control is not None:
            self._admission = AdmissionController(
                self.config.admission_control, tuning=self.tuning,
                max_batch=self.config.max_batch,
                max_wait_ms=self.config.max_wait_ms)

    # -- registry ----------------------------------------------------------
    def add_model(self, name: str, model_or_dir: Any,
                  default: bool = False) -> "ServingServer":
        """Register a fitted model (in-memory ``WorkflowModel`` or a
        saved model directory). The first registered model is the
        default for requests that name none."""
        self.plans.register(name, model_or_dir)
        if default or self._default_model is None:
            self._default_model = name
        return self

    def register_refit(self, name: str, workflow_factory=None,
                       base_records: Optional[List[dict]] = None,
                       checkpoint_dir: Optional[str] = None,
                       save_dir: Optional[str] = None) -> "ServingServer":
        """In-process half of ``tx serve --auto-retrain``: how to
        retrain ``name`` when its sentinel degrades.
        ``workflow_factory`` returns a fresh unfitted workflow (exact
        estimators/hyperparameters); without one the workflow is
        reconstructed generically from the fitted model
        (runtime/refit.py). Requires ``ServeConfig.lifecycle``."""
        if self.lifecycle is None:
            raise ValueError(
                "register_refit requires an enabled "
                "ServeConfig.lifecycle (serving/lifecycle."
                "LifecycleConfig)")
        from ..runtime.refit import RefitSpec
        self.lifecycle.register(name, RefitSpec(
            workflow_factory=workflow_factory,
            base_records=base_records, checkpoint_dir=checkpoint_dir,
            save_dir=save_dir))
        return self

    def prewarm(self, names: Optional[List[str]] = None,
                samples: Optional[Dict[str, List[dict]]] = None
                ) -> Dict[str, List[int]]:
        """Pre-compile the tuning policy's pre-warm bucket set for
        each registered model BEFORE traffic (the serving/state.py
        warm-restart idiom: score a cycled placeholder batch per
        bucket), so an unprofiled plan's first requests never pay the
        per-bucket compile bill in-band. With a cold store or
        TX_TUNE=off the decision is the empty set and this is a no-op.
        ``samples`` supplies representative raw records per model;
        without it the admitted-traffic ring (populated by a state
        restore) is used, then an empty placeholder record — models
        whose raw extractors index keys strictly need real samples.
        Blocking — call before the port binds (cli/serve.py does)."""
        decision = self.tuning.prewarm_buckets(self.config.max_batch)
        buckets = sorted(int(b) for b in (decision.chosen or ()))
        warmed: Dict[str, List[int]] = {}
        if not buckets:
            return warmed
        for name in (names if names is not None
                     else self.plans.names()):
            try:
                entry = self.plans.get(name, self.plan_buckets,
                                       self.plan_lattice)
            except Exception as e:  # pragma: no cover - bad loader
                from ..runtime.errors import classify_error
                _telemetry.event("serve_prewarm_failed", model=name,
                                 kind=classify_error(e),
                                 error=f"{type(e).__name__}: {e}")
                continue
            given = (samples or {}).get(name)
            ring = self._sample_records.get(name)
            samples_for = given or (list(ring) if ring else [{}])
            done: List[int] = []
            for bucket in buckets:
                if bucket < entry.plan.min_bucket \
                        or bucket > entry.plan.max_bucket:
                    continue
                try:
                    entry.plan.score(list(itertools.islice(
                        itertools.cycle(samples_for), bucket)))
                    done.append(bucket)
                except Exception as e:
                    from ..runtime.errors import classify_error
                    _telemetry.event("serve_prewarm_failed",
                                     model=name, bucket=bucket,
                                     kind=classify_error(e),
                                     error=f"{type(e).__name__}: {e}")
            warmed[name] = done
            _telemetry.event("serve_prewarmed", model=name,
                             buckets=done)
        return warmed

    # -- async request edge ------------------------------------------------
    async def score_async(self, record: dict, model: Optional[str] = None,
                          tenant: str = "default") -> dict:
        """Enqueue one record; resolves with the scored row dict (the
        ``ScoreFunction`` row contract — result features by name, plus
        a ``"_guard"`` reason list for quarantined/invalidated rows)."""
        _rid, row = await self.score_with_id(record, model=model,
                                             tenant=tenant)
        return row

    async def score_with_id(self, record: dict,
                            model: Optional[str] = None,
                            tenant: str = "default",
                            rid: Optional[str] = None
                            ) -> Tuple[str, dict]:
        """:meth:`score_async` plus the request id: generated here at
        ADMISSION (or supplied by the caller, e.g. the TCP protocol's
        ``"id"`` field) and carried through coalesce -> encode ->
        dispatch -> reply, so one request's wait/batch/device time is
        attributable end to end. The TCP front end echoes it in every
        response line (cli/serve.py)."""
        if self._draining:
            _telemetry.count("serve_draining_rejections")
            raise ServeDraining(
                "serving loop is draining for shutdown; retry against "
                "the next incarnation")
        if not self._running:
            raise ServeRejected("serving loop is not running")
        name = model or self._default_model
        if name is None:
            raise ServeRejected("no model registered")
        lane = self._lane(name, tenant)
        if len(lane.queue) >= self.config.queue_limit:
            _telemetry.count("serve_queue_rejections")
            raise ServeRejected(
                f"lane {name}/{tenant} queue is at its backpressure "
                f"limit ({self.config.queue_limit})")
        if self._admission is not None:
            # the overload gatekeeper (docs/admission.md): raises
            # ServeShed with a retry_after_ms hint, or admits
            backlog: Dict[str, int] = {}
            for (_m, t), ln in self._lanes.items():
                backlog[t] = backlog.get(t, 0) + len(ln.queue)
            self._admission.admit(name, tenant, len(lane.queue),
                                  backlog)
        loop = asyncio.get_running_loop()
        req = _Request(record=record, future=loop.create_future(),
                       arrived=time.monotonic(),
                       rid=rid or _trace.new_request_id())
        lane.queue.append(req)
        self.stats["requests"] += 1
        _telemetry.count("serve_requests")
        if len(lane.queue) == 1:
            lane.wakeup.set()               # lane was idle: start timer
        if len(lane.queue) >= lane.target:
            lane.full.set()                 # bucket filled: fire early
        self._inflight += 1
        try:
            return req.rid, await req.future
        finally:
            self._inflight -= 1
            if self._inflight == 0 and self._drain_event is not None:
                self._drain_event.set()

    def _lane(self, model_name: str, tenant: str) -> _Lane:
        key = (model_name, tenant)
        lane = self._lanes.get(key)
        if lane is None:
            lane = self._lanes[key] = _Lane(
                model_name, tenant,
                queue_limit=self.config.queue_limit)
            lane.wakeup = asyncio.Event()
            lane.full = asyncio.Event()
            lane.task = asyncio.get_running_loop().create_task(
                self._lane_loop(lane),
                name=f"tx-serve-lane-{model_name}-{tenant}")
        return lane

    # -- the coalescing collector ------------------------------------------
    def _target_batch(self, plan: ScoringPlan) -> int:
        """Deadline-or-full's "full": the coalescer's target batch.
        Explicit config wins; otherwise the largest bucket whose
        RECORDED warm per-dispatch cost still fits inside the wait
        budget (``bucket_profile()``), so the threshold comes from
        this process's measured dispatch costs, not a constant."""
        cfg = self.config
        if cfg.target_batch:
            return max(1, min(cfg.target_batch, cfg.max_batch))
        budget_s = cfg.max_wait_ms / 1000.0
        best = 0
        for bucket, rec in plan.bucket_profile().items():
            if rec["calls"] < 1 or bucket > cfg.max_batch:
                continue
            per_dispatch = rec["execute_seconds"] / rec["calls"]
            if per_dispatch <= budget_s and bucket > best:
                best = bucket
        if best:
            return best
        # no local profile yet: the tuning policy's cross-run
        # prediction (tuning/policy.py) replaces the static constant;
        # cold store / TX_TUNE=off resolves to exactly _DEFAULT_TARGET
        return max(1, min(int(self._target_decision.chosen),
                          cfg.max_batch))

    async def _collect(self, lane: _Lane, target: int
                       ) -> List[_Request]:
        """Deadline-or-full: wait for the first request, then ONE
        timer until the lane holds ``target`` requests (the enqueue
        edge fires ``lane.full``) or the OLDEST request has waited
        ``max_wait_ms`` — whichever comes first."""
        lane.target = max(1, target)
        while not lane.queue:
            lane.wakeup.clear()
            await lane.wakeup.wait()
            if not self._running:
                return []
        wait_ms = self.config.max_wait_ms
        if self._admission is not None:
            # browned out, the coalescer dispatches smaller batches
            # sooner — occupancy traded for latency headroom
            wait_ms = self._admission.effective_max_wait_ms(wait_ms)
        deadline = lane.queue[0].arrived + wait_ms / 1000.0
        while len(lane.queue) < lane.target:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                break
            lane.full.clear()
            try:
                await asyncio.wait_for(lane.full.wait(), remaining)
            except asyncio.TimeoutError:
                break
        n = min(len(lane.queue), self.config.max_batch)
        if self.coalesce_policy == "predicted_cost":
            k = self._coalesce_pop_count(n)
            if k < n:
                # split: the leftover stays queued (its deadline is
                # its own arrival time, so no request waits longer
                # than max_wait_ms) and this dispatch pads less
                self.stats["split_dispatches"] += 1
                _telemetry.count("serve_split_dispatches")
                n = k
        batch = [lane.queue.popleft() for _ in range(n)]
        key = ("full_dispatches" if n >= lane.target
               else "deadline_dispatches")
        self.stats[key] += 1
        _telemetry.count(f"serve_{key}")
        return batch

    def _coalesce_pop_count(self, n: int) -> int:
        """Predicted-cost split rule (docs/ragged_batching.md): pop
        ``k <= n`` where ``k`` is the largest lattice rung <= n IF the
        cost model predicts the rung's per-row execute cost beats
        dispatching all ``n`` rows at their (larger, padded) rung.
        Unknown costs or no lattice => ``n`` (the classic rule)."""
        if n < 2 or not self.plan_lattice:
            return n
        rungs = [b for b in self.plan_lattice
                 if b <= min(n, self.config.max_batch)]
        if not rungs:
            return n
        k = rungs[-1]
        if k >= n:
            return n
        model = getattr(self.tuning, "model", None)
        if model is None:
            return n
        up = next((b for b in self.plan_lattice if b >= n), None)
        if up is None:
            return n
        full = model.predict("score", bucket=int(up))
        part = model.predict("score", bucket=int(k))
        if full.execute is None or part.execute is None:
            return n
        # per-real-row cost of dispatching n rows padded to `up` vs
        # k rows exactly at rung `k` (leftover pays its own dispatch
        # later — charge it the same rate as the k-row dispatch)
        if part.execute / k < full.execute / n:
            return k
        return n

    async def _lane_loop(self, lane: _Lane) -> None:
        """One lane's collector: coalesce -> host-encode (encode pool)
        -> bounded-spawn the dispatch stage. The semaphore is acquired
        HERE and released when the dispatch completes, so exactly one
        batch is on the device while this loop coalesces + encodes the
        next one — the double buffer."""
        from ..runtime.errors import classify_error
        loop = asyncio.get_running_loop()
        # first-collect target before any plan profile exists: the
        # tuning decision (== _DEFAULT_TARGET on a cold store)
        target = max(1, int(self._target_decision.chosen))
        while self._running:
            batch: List[_Request] = []
            try:
                batch = await self._collect(lane, target)
                if not batch:
                    continue
                prep = await loop.run_in_executor(
                    self._encode_pool, self._prepare_batch, lane, batch)
                target = self._target_batch(prep.entry.plan)
                if self._admission is not None:
                    # the DRR fair-queuing twin of the semaphore:
                    # contended grants are served by weighted deficit
                    # round-robin across tenants (docs/admission.md)
                    await self._admission.acquire_grant(
                        lane.tenant, len(prep.requests))
                else:
                    await self._dispatch_sem.acquire()
                loop.create_task(self._dispatch_resolve(prep))
            except asyncio.CancelledError:
                raise
            except Exception as e:
                # a failed prepare fails THIS batch's requests with the
                # recorded, classified reason and the lane keeps
                # serving (the TX-R01/TX-R02 contract: never silent)
                _telemetry.count("serve_batch_failures")
                _telemetry.event("serve_batch_failed", lane=lane.tenant,
                                 model=lane.model_name,
                                 kind=classify_error(e),
                                 error=f"{type(e).__name__}: {e}")
                for req in batch:
                    if not req.future.done():
                        req.future.set_exception(e)

    # -- host-side batch prep (encode pool thread) -------------------------
    def _prepare_batch(self, lane: _Lane, batch: List[_Request]
                       ) -> _PreparedBatch:
        """Blocking host work: plan-cache lookup (may reload/recompile
        an evicted model), schema admission with per-row quarantine
        reasons, raw-Dataset boxing, and bucket encode/padding."""
        marks = {"encode_t0": time.monotonic()}
        entry = self.plans.entry_for(lane.model_name, lane.tenant,
                                     buckets=self.plan_buckets,
                                     lattice=self.plan_lattice)
        guards = entry.guards.get(lane.tenant)
        if guards is None:
            guards = entry.guards[lane.tenant] = _TenantGuards(
                entry.model, self.config)
        records = [r.record for r in batch]
        n = len(records)
        if guards.schema is not None:
            ds, quarantined = guards.schema.admit_records(records)
        else:
            from ..workflow.workflow import _generate_raw_data
            ds = _generate_raw_data(entry.model.raw_features(), records,
                                    require_responses=False)
            quarantined = []
        qmask = np.zeros(n, dtype=bool)
        for r in quarantined:
            if 0 <= r.row < n:
                qmask[r.row] = True
        enc = entry.plan.encode_raw_dataset(
            ds, valid_mask=(~qmask).astype(np.float64))
        ring = self._sample_records.get(lane.model_name)
        if ring is None:
            ring = self._sample_records[lane.model_name] = \
                collections.deque(maxlen=_SAMPLE_RING)
        ring.extend(r for i, r in enumerate(records) if not qmask[i])
        marks["encode_t1"] = time.monotonic()
        return _PreparedBatch(entry=entry, guards=guards, requests=batch,
                              enc=enc, ds=ds, quarantined=quarantined,
                              qmask=qmask, model=lane.model_name,
                              tenant=lane.tenant,
                              seq=next(self._batch_seq), marks=marks)

    # -- device dispatch + guarded resolution ------------------------------
    async def _dispatch_resolve(self, prep: _PreparedBatch) -> None:
        try:
            rows = await self._dispatch_guarded(prep)
            now = time.monotonic()
            for req, row in zip(prep.requests, rows):
                if not req.future.done():
                    req.future.set_result(row)
            self.metrics.observe_batch(
                prep.tenant,
                [now - req.arrived for req in prep.requests])
            if _trace.enabled():
                self._emit_request_spans(prep, now)
        except asyncio.CancelledError:
            raise
        except Exception as e:
            # classified-bug dispatches (and finish-stage crashes)
            # fail the batch's requests with the recorded reason
            from ..runtime.errors import classify_error
            _telemetry.count("serve_batch_failures")
            self.metrics.note_failure()
            _telemetry.event("serve_batch_failed",
                             kind=classify_error(e),
                             error=f"{type(e).__name__}: {e}")
            if _trace.enabled():
                self._emit_request_spans(prep, time.monotonic(),
                                         error=f"{type(e).__name__}: "
                                               f"{e}")
            for req in prep.requests:
                if not req.future.done():
                    req.future.set_exception(e)
        finally:
            if self._admission is not None:
                self._admission.release_grant()
            else:
                self._dispatch_sem.release()

    def _emit_request_spans(self, prep: _PreparedBatch, resolved: float,
                            error: Optional[str] = None) -> None:
        """Reconstruct each request's span tree from the batch's
        monotonic marks at resolve time: root ``serve.request`` (trace
        id = request id) with CONTIGUOUS children wait / encode /
        dispatch / guard, so >= 95% of the request wall-clock is
        covered by child spans (the acceptance gate tests assert).
        Retrospective emission keeps the hot path free of context
        managers across async hops — the cost is a handful of dict
        appends per request, paid only when tracing is on."""
        m = prep.marks
        enc0 = m.get("encode_t0")
        enc1 = m.get("encode_t1", enc0)
        guard0 = m.get("guard_t0", resolved)
        attrs = {"model": prep.model, "tenant": prep.tenant,
                 "batch": prep.seq, "batch_rows": len(prep.requests)}
        if m.get("fallback"):
            attrs["host_fallback"] = True
        if error is not None:
            attrs["status"], attrs["error"] = "error", error
        for req in prep.requests:
            root = _trace.add_span("serve.request", req.arrived,
                                   resolved, trace_id=req.rid,
                                   attrs=attrs)
            parent = (req.rid, root)
            if enc0 is None:
                continue
            _trace.add_span("serve.wait", req.arrived, enc0,
                            parent=parent)
            _trace.add_span("serve.encode", enc0, enc1, parent=parent)
            _trace.add_span("serve.dispatch", enc1, guard0,
                            parent=parent,
                            attrs={"fallback": bool(m.get("fallback"))})
            # guard runs from finish-stage start to RESOLUTION: the
            # guard/boxing work plus the executor->loop handoff that
            # delivers the reply — the four children partition the
            # request's latency completely
            _trace.add_span("serve.guard", guard0, resolved,
                            parent=parent,
                            attrs={"boxing_seconds": round(
                                max(m.get("guard_t1", guard0) - guard0,
                                    0.0), 6)})

    async def _dispatch_guarded(self, prep: _PreparedBatch
                                ) -> List[dict]:
        """Breaker-gated device dispatch with the per-batch deadline
        and host columnar fallback — the per-tenant serving half of
        ``ScoringPlan.score_guarded`` over a shared unguarded plan.
        Dispatch + post-dispatch bookkeeping run in ONE executor hop
        (``_device_batch``): every loop round-trip costs real tail
        latency on a contended host."""
        loop = asyncio.get_running_loop()
        breaker = prep.guards.breaker
        t0 = time.monotonic()
        try:
            if breaker is not None:
                breaker.before_dispatch()
            fut = self._device_pool.submit(self._device_batch, prep)
            aw = asyncio.wrap_future(fut)
            deadline = self.config.deadline_seconds
            if deadline is not None:
                try:
                    rows = await asyncio.wait_for(aw, deadline)
                except asyncio.TimeoutError:
                    # the device thread may be wedged inside the
                    # backend: ORPHAN the executor (new lane for the
                    # next batch) rather than queueing behind it
                    prep.abandoned = True
                    self._orphan_device_pool()
                    _telemetry.count("serving_deadline_exceeded")
                    raise TimeoutError(
                        f"DEADLINE_EXCEEDED: serve batch exceeded the "
                        f"{deadline}s device dispatch deadline"
                    ) from None
            else:
                rows = await aw
            if breaker is not None:
                breaker.record_success()
            self._note_dispatch(prep, t0)
            return rows
        except BreakerOpenError as e:
            _telemetry.count("serving_breaker_short_circuits")
            _log.warning("serve lane breaker open; host fallback: %s", e)
        except Exception as e:
            from ..runtime.errors import BUG, classify_error
            if breaker is None or classify_error(e) == BUG:
                raise
            breaker.record_failure()
            _telemetry.count("serving_device_failures")
            _telemetry.event("serving_fallback",
                             error=f"{type(e).__name__}: {e}",
                             breaker=breaker.state)
            _log.warning(
                "serve device dispatch failed (%s: %s); host fallback "
                "(breaker %s)", type(e).__name__, e, breaker.state)
        # breaker open / classified device failure: the tenant's batch
        # scores through the host columnar path in the FALLBACK pool —
        # the device lane stays free for healthy tenants
        rows = await loop.run_in_executor(
            self._fallback_pool, self._fallback_batch, prep)
        self._note_dispatch(prep, t0)
        return rows

    def _device_batch(self, prep: _PreparedBatch) -> List[dict]:
        """Device-pool thread: fused-program dispatch + guarded finish
        in one hop. An abandoned batch (deadline fired; answered via
        fallback) skips both — this thread may be waking from a hang
        long after anyone cared."""
        if prep.abandoned:
            return []
        scored = prep.entry.plan.dispatch_encoded(prep.enc)
        if prep.abandoned:
            return []
        return self._finish_batch(prep, scored, used_fallback=False)

    def _fallback_batch(self, prep: _PreparedBatch) -> List[dict]:
        """Fallback-pool thread: host columnar scoring + guarded
        finish for a tenant whose device path is unavailable."""
        scored = prep.entry.plan.score_host_columnar(prep.ds)
        return self._finish_batch(prep, scored, used_fallback=True)

    def _note_dispatch(self, prep: _PreparedBatch, t0: float) -> None:
        now = time.monotonic()
        self.stats["batches"] += 1
        self.stats["rows"] += len(prep.requests)
        self.stats["dispatch_seconds"] += now - t0
        if self._admission is not None:
            # measured drain rate + brownout recovery as backlogs clear
            self._admission.note_dispatch(
                len(prep.requests), now - t0,
                max(len(ln.queue) for ln in self._lanes.values())
                if self._lanes else 0)
        if self._first_dispatch_at is None:
            self._first_dispatch_at = t0
        self._last_dispatch_at = now
        _telemetry.count("serve_batches")
        _telemetry.count("serve_rows", len(prep.requests))

    def _finish_batch(self, prep: _PreparedBatch, scored,
                      used_fallback: bool) -> List[dict]:
        """Blocking post-dispatch host work: output guard, quarantined-
        row invalidation, sentinel observation, per-request row boxing
        (identical bookkeeping to ``ScoringPlan._score_guarded_raw``)."""
        from ..local.scoring import _unbox
        prep.marks["guard_t0"] = time.monotonic()
        prep.marks["fallback"] = used_fallback
        guards, names = prep.guards, prep.entry.result_names
        n, qmask = len(prep.requests), prep.qmask
        invalidated: List[GuardReason] = []
        if guards.output is not None:
            scored, invalidated = guards.output.check(
                scored, names, skip_rows=qmask)
        if qmask.any():
            scored = _invalidate_rows(scored, names, qmask)
        if guards.sentinel is not None:
            obs = (prep.ds.take(np.flatnonzero(~qmask)) if qmask.any()
                   else prep.ds)
            guards.sentinel.observe_dataset(obs)
        if self.lifecycle is not None:
            # ring feed + drift poll + post-swap watch
            # (serving/lifecycle.py); a dict lookup when idle
            self.lifecycle.note_batch(prep)
        n_bad = int(qmask.sum())
        _telemetry.count("serving_rows_scored", n - n_bad)
        if n_bad:
            _telemetry.count("serving_rows_quarantined", n_bad)
        if invalidated:
            _telemetry.count("serving_rows_invalidated",
                             len({r.row for r in invalidated}))
        by_row: Dict[int, List[dict]] = {}
        for r in prep.quarantined:
            by_row.setdefault(r.row, []).append(
                {"kind": "quarantined", **r.to_json()})
        for r in invalidated:
            by_row.setdefault(r.row, []).append(
                {"kind": "invalidated", **r.to_json()})
        cols = [scored[nm] for nm in names]
        rows: List[dict] = []
        for i in range(n):
            if i in by_row:
                row: dict = {nm: None for nm in names}
                row["_guard"] = by_row[i]
            else:
                row = {nm: _unbox(col.boxed(i))
                       for nm, col in zip(names, cols)}
            if used_fallback:
                row["_host_fallback"] = True
            rows.append(row)
        prep.marks["guard_t1"] = time.monotonic()
        return rows

    def _orphan_device_pool(self) -> None:
        """Abandon a wedged device executor (its thread may be stuck
        inside the backend forever) and stand up a fresh lane so the
        loop keeps dispatching — the serving twin of the selector's
        family-deadline abandonment."""
        self.stats["orphaned_dispatches"] += 1
        old = self._device_pool
        self._device_pool = _cf.ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="tx-serve-device")
        old.shutdown(wait=False)

    # -- lifecycle ---------------------------------------------------------
    async def start(self) -> None:
        """Arm the loop-bound primitives (call from the event loop the
        server will live on)."""
        self.loop = asyncio.get_running_loop()
        self._dispatch_sem = asyncio.Semaphore(1)
        self._running = True

    async def drain(self, timeout: float = 30.0) -> dict:
        """Graceful-shutdown half of preemption tolerance
        (docs/serving_restart.md): flip the loop to DRAINING — new
        requests refuse with :class:`ServeDraining` (the TCP front end
        turns that into the machine-readable ``"draining"`` answer) —
        then wait up to ``timeout`` seconds for every queued and
        in-flight request to resolve. Returns ``{"drained", "inflight",
        "seconds"}``; ``drained`` False means the deadline fired with
        requests still outstanding (they fail at :meth:`shutdown`)."""
        t0 = time.monotonic()
        self._draining = True
        self._drain_event = asyncio.Event()
        _telemetry.count("serve_drains")
        _telemetry.event("serve_draining", inflight=self._inflight)
        if self._inflight == 0:
            self._drain_event.set()
        try:
            await asyncio.wait_for(self._drain_event.wait(), timeout)
            drained = True
        except asyncio.TimeoutError:
            drained = False
            _telemetry.count("serve_drain_timeouts")
        out = {"drained": drained, "inflight": self._inflight,
               "seconds": round(time.monotonic() - t0, 4)}
        _telemetry.event("serve_drained", **out)
        return out

    @property
    def draining(self) -> bool:
        return self._draining

    @property
    def inflight(self) -> int:
        return self._inflight

    async def shutdown(self) -> None:
        self._running = False
        if self._admission is not None:
            self._admission.drain_waiters()
        for lane in self._lanes.values():
            if lane.wakeup is not None:
                lane.wakeup.set()
            if lane.task is not None:
                lane.task.cancel()
            for req in lane.queue:
                if not req.future.done():
                    req.future.set_exception(
                        ServeRejected("serving loop stopped"))
            lane.queue.clear()

    def start_background(self) -> "ServingClient":
        """Run the server on a daemon-thread event loop and return a
        sync :class:`ServingClient` — the in-process entry point for
        tests and the bench."""
        if self._thread is not None:
            return ServingClient(self)
        ready = threading.Event()

        def runner():
            loop = asyncio.new_event_loop()
            asyncio.set_event_loop(loop)
            loop.run_until_complete(self.start())
            ready.set()
            loop.run_forever()
            loop.run_until_complete(self.shutdown())
            loop.close()

        self._thread = threading.Thread(target=runner, daemon=True,
                                        name="tx-serve-loop")
        self._thread.start()
        ready.wait(timeout=30)
        return ServingClient(self)

    def stop(self) -> None:
        if self._thread is None:
            return
        self._running = False
        if self.loop is not None:
            self.loop.call_soon_threadsafe(lambda: None)  # wake
            self.loop.call_soon_threadsafe(self.loop.stop)
        self._thread.join(timeout=30)
        self._thread = None
        self._encode_pool.shutdown(wait=False)
        self._device_pool.shutdown(wait=False)
        self._fallback_pool.shutdown(wait=False)
        if self.lifecycle is not None:
            self.lifecycle.shutdown()

    # -- introspection -----------------------------------------------------
    def describe(self) -> dict:
        """Loop stats for bench/ops: occupancy (mean rows per
        dispatch) and device-lane saturation (fraction of wall time a
        dispatch was in flight)."""
        batches = self.stats["batches"] or 1
        wall = None
        if self._first_dispatch_at is not None:
            wall = max((self._last_dispatch_at or 0)
                       - self._first_dispatch_at, 1e-9)
        return {
            "requests": int(self.stats["requests"]),
            "batches": int(self.stats["batches"]),
            "rows": int(self.stats["rows"]),
            "mean_batch_occupancy": self.stats["rows"] / batches,
            "full_dispatches": int(self.stats["full_dispatches"]),
            "deadline_dispatches": int(self.stats["deadline_dispatches"]),
            "orphaned_dispatches": int(self.stats["orphaned_dispatches"]),
            "dispatch_saturation": (
                self.stats["dispatch_seconds"] / wall
                if wall is not None else 0.0),
            "plan_cache": {"budget": self.plans.budget,
                           "resident": len(self.plans._entries),
                           "evictions": self.plans.evictions},
            "models": self.plans.names(),
            "lanes": sorted("/".join(k) for k in self._lanes),
        }

    def process_block(self) -> dict:
        """The ``process`` slice of :meth:`metrics_snapshot`: this
        incarnation's identity and restart-readiness state — what a
        supervisor, load balancer, or the bench restart drill polls.
        Field set is pinned by tests (schema version 3)."""
        return {
            "uptime_seconds": round(self.metrics.uptime_seconds(), 3),
            "restart_generation": self.restart_generation,
            "draining": self._draining,
            "ready": bool(self.ready),
            "inflight": self._inflight,
            "last_snapshot_age_seconds": (
                round(max(time.time() - self.last_snapshot_at, 0.0), 3)
                if self.last_snapshot_at is not None else None),
        }

    def metrics_snapshot(self) -> dict:
        """The LIVE metrics document (schema versioned,
        docs/observability.md): loop counters, per-tenant latency
        quantiles from the streaming histograms, per-lane queue depth,
        plan-cache hits/evictions, per-tenant breaker state, and the
        serving slice of the process telemetry counters. Answered by
        the ``{"metrics": true}`` TCP control request and the
        ``tx serve --metrics-port`` HTTP endpoint while the loop is
        SERVING — no stop() required. Cheap enough for the event loop:
        dict reads + fixed-bin quantile interpolation, no device work,
        no I/O."""
        from ..observability.metrics import METRICS_SCHEMA_VERSION
        from ..utils.jax_setup import backend_block
        from .plan import plan_compiles
        breakers = {}
        sentinels = {}
        live = [(name, entry) for (name, _buckets), entry
                in list(self.plans._entries.items())]
        live += [(name, entry) for (name, _tenant), entry
                 in self.plans.swapped_entries().items()]
        for name, entry in live:
            for tenant, guards in list(entry.guards.items()):
                lane = f"{name}/{tenant}"
                if guards.breaker is not None:
                    breakers[lane] = guards.breaker.state
                if guards.sentinel is not None:
                    # per-tenant drift state: per-feature JS vs the
                    # warn/degrade thresholds + rows observed — the
                    # condition that triggers the self-healing loop,
                    # visible BEFORE it fires (docs/self_healing.md)
                    report = guards.sentinel.drift_report()
                    sentinels[lane] = {
                        "status": report["status"],
                        "rowsSeen": report["rowsSeen"],
                        "warnThreshold": report["warnThreshold"],
                        "degradeThreshold": report["degradeThreshold"],
                        "generation": getattr(guards.sentinel,
                                              "generation", 0),
                        "features": {
                            f["feature"]: {
                                "jsDivergence": f["jsDivergence"],
                                "status": f["status"],
                                "rowsObserved": f["rowsObserved"],
                            } for f in report["features"]},
                    }
        serving_counters = {
            k: v for k, v in _telemetry.counters().items()
            if k.startswith(("serve_", "serving_", "breaker_",
                             "drift_", "lifecycle_", "plan_"))}
        return {
            "schema": METRICS_SCHEMA_VERSION,
            "uptime_seconds": round(self.metrics.uptime_seconds(), 3),
            "running": self._running,
            "process": self.process_block(),
            "backend": backend_block(),
            "requests": int(self.stats["requests"]),
            "answered": self.metrics.answered,
            "failed_batches": self.metrics.failed,
            "batches": int(self.stats["batches"]),
            "rows": int(self.stats["rows"]),
            "mean_batch_occupancy": round(
                self.stats["rows"] / (self.stats["batches"] or 1), 3),
            "full_dispatches": int(self.stats["full_dispatches"]),
            "deadline_dispatches": int(
                self.stats["deadline_dispatches"]),
            "orphaned_dispatches": int(
                self.stats["orphaned_dispatches"]),
            "queue_depth": {"/".join(k): len(lane.queue)
                            for k, lane in sorted(self._lanes.items())},
            "admission": (self._admission.snapshot(
                {"/".join(k): len(lane.queue)
                 for k, lane in sorted(self._lanes.items())})
                if self._admission is not None
                else {"enabled": False}),
            "latency_ms": self.metrics.latency_json(),
            "plan_cache": {"budget": self.plans.budget,
                           "resident": len(self.plans._entries),
                           "hits": self.plans.hits,
                           "misses": self.plans.misses,
                           "evictions": self.plans.evictions},
            "plan_compiles": plan_compiles(),
            # AOT artifact state per resident model (docs/
            # aot_artifacts.md): which plans serve from deserialized
            # executables vs live compiles — the zero-compile-cold-
            # start acceptance signal next to plan_compiles above
            "aot": {
                name: (entry.plan.aot_summary()
                       if hasattr(entry.plan, "aot_summary") else None)
                for name, entry in live},
            "breakers": breakers,
            "sentinels": sentinels,
            "lifecycle": (self.lifecycle.snapshot()
                          if self.lifecycle is not None else None),
            "counters": serving_counters,
            "trace": {"enabled": _trace.enabled(),
                      "path": _trace.trace_path()},
        }


class ServingClient:
    """Synchronous in-process facade over a background-thread
    :class:`ServingServer` — what tests and in-process load generators
    drive. ``submit`` returns a concurrent future for open-loop load
    generation; ``score`` blocks for one row."""

    def __init__(self, server: ServingServer):
        self.server = server

    def submit(self, record: dict, model: Optional[str] = None,
               tenant: str = "default") -> "_cf.Future":
        if self.server.loop is None:
            raise ServeRejected("server not started")
        return asyncio.run_coroutine_threadsafe(
            self.server.score_async(record, model=model, tenant=tenant),
            self.server.loop)

    def score(self, record: dict, model: Optional[str] = None,
              tenant: str = "default", timeout: float = 60.0) -> dict:
        return self.submit(record, model=model, tenant=tenant).result(
            timeout)

    def score_many(self, records: Sequence[dict],
                   model: Optional[str] = None, tenant: str = "default",
                   timeout: float = 120.0) -> List[dict]:
        """Submit every record CONCURRENTLY (they coalesce into shared
        bucket dispatches) and return rows in request order."""
        futs = [self.submit(r, model=model, tenant=tenant)
                for r in records]
        return [f.result(timeout) for f in futs]


def serve_in_process(models: Dict[str, Any],
                     config: Optional[ServeConfig] = None
                     ) -> Tuple[ServingServer, ServingClient]:
    """One-call setup for tests/bench: register ``models`` (name ->
    fitted model or saved dir), start the loop on a background thread,
    return (server, client). Caller owns ``server.stop()``."""
    server = ServingServer(config)
    for name, m in models.items():
        server.add_model(name, m)
    client = server.start_background()
    return server, client
