"""Fleet router: one asyncio front-end over N serving replicas
(docs/fleet.md).

A single serving process (docs/serving_loop.md) is one event loop on
one host; the fleet layer puts a router in front of N of them. The
router speaks the SAME newline-delimited JSON protocol as ``tx serve``
(cli/serve.py) — existing clients, including the reconnecting
:class:`~.client.TcpServingClient`, point at the router port and
notice nothing — and owns three fleet-only concerns:

- **Placement.** Each (model, tenant) lane is pinned to one replica,
  chosen by predicted dispatch cost from the tuning cost model
  (tuning/model.py) plus plan-cache pressure — NOT round-robin: a
  replica already hosting the lane's compiled plan is cheaper than one
  that would have to evict + recompile (docs/autotuning.md,
  docs/aot_artifacts.md). Lanes stick until their replica dies or
  drains, so per-tenant state (sentinels, breakers, fair-queue
  deficits) stays on one incarnation.
- **Failover.** Forwards carry the reconnect/resend semantics of
  :class:`~.client.TcpServingClient`, made async: a transport failure
  mid-request closes the backend link, re-places the lane on a
  survivor and RESENDS — the caller sees one answer, late replies for
  abandoned requests are deduped on the echoed ``request_id``. A
  ``{"ok": false, "draining": true}`` answer from a gracefully
  stopping replica (docs/serving_restart.md) is the rolling-deploy
  re-place signal: the lane moves, the request resends, zero
  client-observed failures. A replica the router marked dead on a
  transient blip is re-probed by the admission poll and restored to
  ``ok`` on a successful round trip (``fleet_replica_recoveries``) —
  router-side death is never permanent while the replica stays
  registered.
- **Fleet-coherent admission.** The router polls every replica's
  ``metrics_snapshot()["admission"]`` block (docs/admission.md) and
  merges them: fleet state is the WORST replica state, the drain rate
  is the fleet-wide sum, and when the merged state is ``shed`` the
  router sheds at ITS door for every lane at once — no replica sits in
  ``ok`` serving full rate while its neighbor browns out. Shed answers
  carry ``retry_after_ms`` derived from the merged drain rate.

Deterministic fault drills (runtime/faults.py, ``TX_FAULT_PLAN``):
``fleet:<replica>:partition`` is probed on every forward to that
replica (a raising fault — e.g. ``preempt`` — is treated as a
transport failure: reconnect, then fail over), and
``fleet:<replica>:hang`` stalls the forward in an executor thread so
the per-request timeout and the failover path are drillable without a
real hung replica. ``fleet:<replica>:kill`` lives in the replica
manager (serving/fleet.py).
"""
from __future__ import annotations

import asyncio
import itertools
import json
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional, Set, Tuple

from ..observability import trace as _trace
from ..runtime import telemetry as _telemetry
from ..runtime.errors import classify_error
from ..runtime.faults import InjectedFault, injector_active, maybe_inject
from ..runtime.retry import RetryPolicy

__all__ = ["FleetRouter", "RouterConfig", "ReplicaHandle",
           "BackendUnavailable", "merge_admission",
           "FLEET_METRICS_SCHEMA"]

#: schema identity of the router's merged metrics document
FLEET_METRICS_SCHEMA = "tx-fleet-metrics/1"

#: admission states ordered by severity (serving/admission.py)
_STATE_ORDER = {"ok": 0, "brownout": 1, "shed": 2}

#: bounds on the merged retry hint — same clamp the per-replica
#: controller applies (serving/admission.py retry_after_ms)
_MIN_RETRY_MS = 1
_MAX_RETRY_MS = 5000

#: ring of request ids whose replies were abandoned mid-failover —
#: a late reply for one of these is a duplicate, not an answer
_STALE_RING = 64


class BackendUnavailable(ConnectionError):
    """Every live replica (or every allowed failover attempt) failed
    to answer the forwarded request."""


@dataclass
class RouterConfig:
    """Router knobs. ``plan_budget`` mirrors the replicas'
    ``--plan-cache`` so the placement cost can model eviction
    pressure; the cost priors only matter until the profile store has
    real measurements."""
    max_failovers: int = 3          # distinct replicas tried per request
    forward_timeout: float = 30.0   # per-forward round-trip deadline
    admission_poll_s: float = 0.25  # merged-admission refresh period
    plan_budget: int = 4            # replica plan-cache budget (LRU slots)
    default_wall_ms: float = 1.0    # dispatch-cost prior (cold store)
    default_compile_ms: float = 250.0  # compile-cost prior (cold store)
    placement_bucket: int = 8       # bucket the dispatch prediction reads


@dataclass
class ReplicaHandle:
    """One registered backend replica as the router sees it."""
    name: str
    host: str
    port: int
    generation: int = 1
    #: "ok" | "draining" | "dead"
    state: str = "ok"
    #: last polled admission block (metrics_snapshot()["admission"])
    admission: Optional[dict] = None
    #: last polled process/plan slice, for the fleet metrics document
    last_metrics: Dict[str, Any] = field(default_factory=dict)

    def usable(self) -> bool:
        return self.state == "ok"


def merge_admission(snaps: Dict[str, Optional[dict]]) -> dict:
    """Fold per-replica admission snapshots into ONE fleet-wide block
    (the DrJAX map-reduce framing: replicas map, the router reduces).

    - ``state`` — the WORST replica state: one replica in ``shed``
      puts the whole fleet in ``shed``, which is what makes the
      brownout coherent (the router sheds every lane, so no replica
      keeps absorbing full rate while another drowns).
    - ``drain_rows_per_s`` — the SUM across replicas: the fleet drains
      its merged backlog with all its capacity.
    - ``retry_after_ms`` — merged backlog over merged drain rate,
      clamped exactly like the per-replica hint.
    """
    live = {n: s for n, s in snaps.items()
            if isinstance(s, dict) and s.get("enabled")}
    replicas = {n: {"state": s.get("state", "ok"),
                    "pressure": float(s.get("pressure", 0.0))}
                for n, s in snaps.items() if isinstance(s, dict)}
    if not live:
        return {"enabled": False, "state": "ok", "pressure": 0.0,
                "drain_rows_per_s": 0.0, "queue_rows": 0,
                "retry_after_ms": _MIN_RETRY_MS, "replicas": replicas}
    drain = sum(float(s.get("drain_rows_per_s", 0.0))
                for s in live.values())
    depth = sum(sum(int(v) for v in (s.get("queue_depth") or {})
                    .values()) for s in live.values())
    state = max((s.get("state", "ok") for s in live.values()),
                key=lambda st: _STATE_ORDER.get(st, 0))
    pressure = max(float(s.get("pressure", 0.0)) for s in live.values())
    retry = int(min(max(depth / max(drain, 1e-6) * 1000.0,
                        _MIN_RETRY_MS), _MAX_RETRY_MS))
    return {"enabled": True, "state": state,
            "pressure": round(pressure, 4),
            "drain_rows_per_s": round(drain, 1), "queue_rows": depth,
            "retry_after_ms": retry, "replicas": replicas}


class _BackendLink:
    """Async reconnecting JSON-lines client for ONE replica — the
    asyncio twin of :class:`~.client.TcpServingClient`: transport
    failures close, back off (``await asyncio.sleep``) and RESEND;
    answered verdicts return as-is. Requests are serialized per link
    (one lane talks to one replica at a time), and replies whose
    echoed ``request_id`` belongs to an abandoned earlier request are
    discarded, not surfaced."""

    def __init__(self, handle: ReplicaHandle, retry: RetryPolicy,
                 timeout: float):
        self.handle = handle
        self.retry = retry
        self.timeout = timeout
        self._reader: Optional[asyncio.StreamReader] = None
        self._writer: Optional[asyncio.StreamWriter] = None
        self._lock = asyncio.Lock()
        self._stale_rids: deque = deque(maxlen=_STALE_RING)

    async def _connect(self) -> None:
        if self._writer is not None:
            return
        self._reader, self._writer = await asyncio.wait_for(
            asyncio.open_connection(self.handle.host,
                                    self.handle.port),
            self.timeout)

    async def close(self) -> None:
        writer, self._reader, self._writer = self._writer, None, None
        if writer is not None:
            try:
                writer.close()
                await writer.wait_closed()
            except (OSError, ConnectionError):
                pass

    async def _roundtrip(self, line: bytes, expect_rid: Optional[str]
                         ) -> dict:
        if injector_active():
            # fleet:<replica>:hang — the stall runs in an executor
            # thread so only THIS forward waits; the surrounding
            # wait_for turns a long hang into a transport timeout and
            # the caller fails over (docs/fleet.md fault matrix)
            await asyncio.get_running_loop().run_in_executor(
                None, maybe_inject, "fleet", self.handle.name, "hang")
        await self._connect()
        self._writer.write(line)
        await self._writer.drain()
        while True:
            raw = await self._reader.readline()
            if not raw:
                raise ConnectionError(
                    f"replica {self.handle.name} closed the "
                    f"connection mid-request")
            doc = json.loads(raw)
            rid = (doc.get("request_id")
                   if isinstance(doc, dict) else None)
            wanted = (expect_rid is not None and rid is not None
                      and str(rid) == str(expect_rid))
            if rid is not None and not wanted \
                    and rid in self._stale_rids:
                # late reply for a request we already abandoned and
                # resent elsewhere — surfacing it would answer the
                # CURRENT request with a stale payload. A reply whose
                # rid matches expect_rid is NEVER stale: an in-link
                # reconnect resends the SAME rid, and its answer is
                # exactly the one we are waiting for.
                _telemetry.count("fleet_backend_duplicate_replies")
                continue
            if expect_rid is not None and rid is not None \
                    and str(rid) != str(expect_rid):
                _telemetry.count("fleet_backend_duplicate_replies")
                continue
            return doc

    async def request(self, payload: Dict[str, Any]) -> Dict[str, Any]:
        """One round trip with reconnect + resend under the bounded
        retry policy. Raises :class:`BackendUnavailable` when every
        attempt fails — the caller's failover signal."""
        line = (json.dumps(payload, default=float) + "\n").encode()
        expect_rid = payload.get("id")
        last: Optional[Exception] = None
        async with self._lock:
            for attempt in range(1, self.retry.max_attempts + 1):
                try:
                    # fleet:<replica>:partition — a raising fault
                    # (preempt/oom) IS the simulated partition: the
                    # send never reaches the replica
                    maybe_inject("fleet", self.handle.name,
                                 "partition")
                    return await asyncio.wait_for(
                        self._roundtrip(line, expect_rid),
                        self.timeout)
                except (OSError, ConnectionError, asyncio.TimeoutError,
                        json.JSONDecodeError, InjectedFault) as e:
                    last = e
                    await self.close()
                    _telemetry.count("fleet_backend_reconnects")
                    if attempt < self.retry.max_attempts:
                        await asyncio.sleep(self.retry.delay_for(
                            attempt,
                            f"fleet:{self.handle.name}:"
                            f"{self.handle.port}"))
            if expect_rid is not None:
                # only NOW is the request abandoned on this link (the
                # caller fails the lane over and resends elsewhere) —
                # a reply that straggles in later must not answer a
                # future request. Recording the rid per-attempt would
                # make the in-link reconnect discard its own resend's
                # genuine reply as a duplicate.
                self._stale_rids.append(expect_rid)
        raise BackendUnavailable(
            f"replica {self.handle.name} "
            f"({self.handle.host}:{self.handle.port}) unreachable "
            f"after {self.retry.max_attempts} attempts "
            f"[{classify_error(last)}]: {last}") from last

    async def probe(self) -> dict:
        """One SINGLE-attempt metrics round trip with a short
        deadline and no backoff — the router's dead-replica recovery
        probe (:meth:`FleetRouter.poll_admission_once`). Kept separate
        from :meth:`request` so a still-dead replica costs the poll
        loop one fast failure, not a full retry ladder."""
        line = b'{"metrics": true}\n'
        async with self._lock:
            try:
                maybe_inject("fleet", self.handle.name, "partition")
                return await asyncio.wait_for(
                    self._roundtrip(line, None),
                    min(self.timeout, 2.0))
            except (OSError, ConnectionError, asyncio.TimeoutError,
                    json.JSONDecodeError, InjectedFault) as e:
                await self.close()
                raise BackendUnavailable(
                    f"replica {self.handle.name} "
                    f"({self.handle.host}:{self.handle.port}) probe "
                    f"failed [{classify_error(e)}]: {e}") from e


class FleetRouter:
    """The fleet front door: lane placement, forwarding with failover,
    merged admission, and the fleet metrics document. Runs entirely on
    ONE asyncio loop — replica managers on other threads talk to it
    only through the ``*_threadsafe`` entry points, which marshal onto
    the loop via ``call_soon_threadsafe`` (the TX-X03 contract)."""

    def __init__(self, config: Optional[RouterConfig] = None,
                 cost_model=None, retry: Optional[RetryPolicy] = None):
        self.config = config or RouterConfig()
        self.retry = retry or RetryPolicy.from_env()
        if cost_model is None:
            # load NOW, from sync construction context — the store
            # read is file I/O, which must never run on the event
            # loop inside the async forward path (lint TX-X01)
            from ..tuning.model import CostModel
            cost_model = CostModel.from_store()
        self._cost = cost_model
        self.replicas: Dict[str, ReplicaHandle] = {}
        self._links: Dict[str, _BackendLink] = {}
        #: (model, tenant) -> replica name; the sticky lane table
        self._lanes: Dict[Tuple[str, str], str] = {}
        #: live client connections (popped on disconnect — TX-R07)
        self._client_writers: Dict[int, asyncio.StreamWriter] = {}
        self._fleet_admission: dict = {
            "enabled": False, "state": "ok", "pressure": 0.0,
            "drain_rows_per_s": 0.0, "queue_rows": 0,
            "retry_after_ms": _MIN_RETRY_MS, "replicas": {}}
        self.default_model: Optional[str] = None
        self.on_replica_down: Optional[Callable[[str, str], None]] = None
        self.stats = {"requests": 0, "answered": 0, "failovers": 0,
                      "sheds": 0, "placements": 0,
                      "lane_replacements": 0, "unavailable": 0,
                      "recoveries": 0}
        self._rid_counter = itertools.count(1)
        self._conn_counter = itertools.count(1)
        self._started_at = time.time()
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        self._poll_task: Optional[asyncio.Task] = None
        self._stop_event: Optional[asyncio.Event] = None

    # -- replica registry --------------------------------------------------
    def register_replica(self, name: str, host: str, port: int,
                         generation: int = 1) -> ReplicaHandle:
        """Add (or refresh, after a takeover respawn) one replica.
        Loop context only — threads use the ``_threadsafe`` variant."""
        old = self._links.pop(name, None)
        if old is not None and self._loop is not None:
            self._loop.create_task(old.close())
        handle = ReplicaHandle(name=name, host=host, port=port,
                               generation=generation)
        self.replicas[name] = handle
        self._links[name] = _BackendLink(handle, self.retry,
                                         self.config.forward_timeout)
        _telemetry.event("fleet_replica_registered", replica=name,
                         port=port, generation=generation)
        return handle

    def unregister_replica(self, name: str,
                           reason: str = "unregistered") -> None:
        handle = self.replicas.get(name)
        if handle is not None:
            handle.state = "dead"
        self._replace_lanes(name, reason)
        link = self._links.pop(name, None)
        if link is not None and self._loop is not None:
            self._loop.create_task(link.close())

    def mark_draining(self, name: str) -> None:
        """Stop placing lanes on ``name`` and move its existing lanes
        to survivors — the rolling-deploy pre-drain signal."""
        handle = self.replicas.get(name)
        if handle is not None and handle.state == "ok":
            handle.state = "draining"
        self._replace_lanes(name, "draining")

    # thread-safe marshals for the replica manager's watch thread ---------
    def _call_threadsafe(self, fn, *args) -> None:
        loop = self._loop
        if loop is None or loop.is_closed():
            fn(*args)
        else:
            loop.call_soon_threadsafe(fn, *args)

    def register_replica_threadsafe(self, name: str, host: str,
                                    port: int,
                                    generation: int = 1) -> None:
        self._call_threadsafe(self.register_replica, name, host, port,
                              generation)

    def unregister_replica_threadsafe(self, name: str,
                                      reason: str = "down") -> None:
        self._call_threadsafe(self.unregister_replica, name, reason)

    def mark_draining_threadsafe(self, name: str) -> None:
        self._call_threadsafe(self.mark_draining, name)

    def stop_threadsafe(self) -> None:
        """Ask a running :meth:`serve` loop to shut down from another
        thread — the in-process drills and bench phases own the router
        without owning a signal to send it."""
        loop, ev = self._loop, self._stop_event
        if loop is not None and ev is not None and not loop.is_closed():
            loop.call_soon_threadsafe(ev.set)

    # -- placement ---------------------------------------------------------
    def _placement_cost(self, name: str, model: str) -> float:
        """Predicted cost (ms) of routing one more lane of ``model``
        to ``name``: the model's predicted per-dispatch wall cost
        scaled by the replica's current lane load, plus a plan-cache
        pressure term — landing a model the replica does not already
        host costs its predicted compile, scaled up as the cache fills
        toward (and past) its LRU budget, where placement would force
        an eviction (docs/fleet.md)."""
        cfg = self.config
        est = self._cost.predict("score", bucket=cfg.placement_bucket)
        wall_ms = (est.wall * 1000.0 if est.wall
                   else cfg.default_wall_ms)
        compile_ms = (est.compile * 1000.0 if est.compile
                      else cfg.default_compile_ms)
        lanes_here = sum(1 for r in self._lanes.values() if r == name)
        models_here = {m for (m, _t), r in self._lanes.items()
                       if r == name}
        cost = wall_ms * (1.0 + lanes_here)
        if model not in models_here:
            pressure = len(models_here) / max(cfg.plan_budget, 1)
            cost += compile_ms * (1.0 + pressure)
        return cost

    def place(self, model: str, tenant: str,
              exclude: Optional[Set[str]] = None) -> str:
        """The replica for lane (model, tenant): sticky while its
        replica stays usable, otherwise re-placed on the cheapest
        survivor by :meth:`_placement_cost` (deterministic tie-break
        on replica name). Raises :class:`BackendUnavailable` when no
        usable replica remains."""
        exclude = exclude or set()
        lane = (model, tenant)
        current = self._lanes.get(lane)
        if current is not None and current not in exclude:
            handle = self.replicas.get(current)
            if handle is not None and handle.usable():
                return current
        best: Optional[Tuple[float, str]] = None
        for name in sorted(self.replicas):
            if name in exclude or not self.replicas[name].usable():
                continue
            score = self._placement_cost(name, model)
            if best is None or score < best[0]:
                best = (score, name)
        if best is None:
            raise BackendUnavailable(
                f"no usable replica for lane {model}/{tenant} "
                f"(replicas: "
                f"{ {n: h.state for n, h in self.replicas.items()} })")
        self._lanes[lane] = best[1]
        self.stats["placements"] += 1
        _telemetry.count("fleet_lane_placements")
        _telemetry.event("fleet_lane_placed", model=model,
                         tenant=tenant, replica=best[1],
                         cost_ms=round(best[0], 3))
        return best[1]

    def _replace_lanes(self, name: str, reason: str) -> None:
        moved = [lane for lane, r in self._lanes.items() if r == name]
        for lane in moved:
            del self._lanes[lane]
        if moved:
            self.stats["lane_replacements"] += len(moved)
            _telemetry.count("fleet_lane_replacements", len(moved))
            _telemetry.event("fleet_lanes_replaced", replica=name,
                             lanes=len(moved), reason=reason)

    def _mark_down(self, name: str, reason: str) -> None:
        handle = self.replicas.get(name)
        if handle is None or handle.state == "dead":
            return
        handle.state = "dead"
        _telemetry.count("fleet_replicas_down")
        _telemetry.event("fleet_replica_down", replica=name,
                         reason=reason[:200])
        self._replace_lanes(name, "replica down")
        if self.on_replica_down is not None:
            self.on_replica_down(name, reason)

    # -- merged admission --------------------------------------------------
    async def poll_admission_once(self) -> dict:
        """One poll + merge pass over every usable replica — the
        background poller's body, callable directly from tests."""
        for name in list(self.replicas):
            handle = self.replicas.get(name)
            link = self._links.get(name)
            if handle is None or link is None \
                    or handle.state == "draining":
                continue
            if handle.state == "dead":
                # recovery probe: a replica the ROUTER marked dead on
                # a transient blip (failed forward or metrics poll)
                # is still registered — one successful round trip
                # restores it. Without this, a brief network error
                # would shrink the fleet permanently: the manager
                # only re-announces a replica after a respawn, and a
                # healthy child never respawns.
                try:
                    answer = await link.probe()
                except BackendUnavailable:
                    _telemetry.count("fleet_recovery_probe_failures")
                    continue
                handle.state = "ok"
                self.stats["recoveries"] += 1
                _telemetry.count("fleet_replica_recoveries")
                _telemetry.event("fleet_replica_recovered",
                                 replica=name)
            else:
                try:
                    answer = await link.request({"metrics": True})
                except BackendUnavailable as e:
                    _telemetry.count("fleet_admission_poll_failures")
                    self._mark_down(name, f"metrics poll failed: {e}")
                    continue
            snap = answer.get("metrics", answer) \
                if isinstance(answer, dict) else {}
            handle.admission = snap.get("admission")
            handle.last_metrics = {
                "plan_compiles": snap.get("plan_compiles"),
                "answered": snap.get("answered"),
                "process": snap.get("process"),
                "plan_cache": snap.get("plan_cache"),
            }
        merged = merge_admission(
            {n: h.admission for n, h in self.replicas.items()
             if h.state != "dead"})
        if merged["state"] != self._fleet_admission.get("state"):
            _telemetry.event("fleet_admission_transition",
                             frm=self._fleet_admission.get("state"),
                             to=merged["state"],
                             pressure=merged["pressure"])
        self._fleet_admission = merged
        return merged

    async def _poll_admission_forever(self) -> None:
        while True:
            await asyncio.sleep(self.config.admission_poll_s)
            await self.poll_admission_once()

    @property
    def fleet_admission(self) -> dict:
        return self._fleet_admission

    # -- forwarding --------------------------------------------------------
    async def score(self, msg: Dict[str, Any]) -> Dict[str, Any]:
        """Forward one score request: fleet-admission check at the
        door, then place -> forward -> (on failure or a draining
        answer) re-place and resend, up to ``max_failovers`` distinct
        replicas. The caller observes exactly one answer."""
        self.stats["requests"] += 1
        model = msg.get("model") or self.default_model
        tenant = str(msg.get("tenant", "default"))
        rid = str(msg.get("id") or f"flt-{next(self._rid_counter)}")
        merged = self._fleet_admission
        if merged.get("state") == "shed":
            # the COHERENT brownout: one merged decision sheds every
            # lane at the fleet door, hint from the merged drain rate
            self.stats["sheds"] += 1
            _telemetry.count("fleet_router_sheds")
            return {"ok": False, "request_id": rid, "shed": True,
                    "fleet": True,
                    "retry_after_ms": merged["retry_after_ms"],
                    "error": "ServeShed: fleet admission state is "
                             "shed (merged across replicas)",
                    "kind": "transient"}
        payload = dict(msg)
        payload["id"] = rid   # pin the id so resends dedupe downstream
        tried: Set[str] = set()
        t0 = time.time()
        for _hop in range(self.config.max_failovers + 1):
            try:
                name = self.place(model or "", tenant, exclude=tried)
            except BackendUnavailable:
                break
            link = self._links.get(name)
            if link is None:
                tried.add(name)
                continue
            try:
                answer = await link.request(payload)
            except BackendUnavailable as e:
                tried.add(name)
                self.stats["failovers"] += 1
                _telemetry.count("fleet_router_failovers")
                self._mark_down(name, str(e))
                continue
            if isinstance(answer, dict) and answer.get("draining"):
                # graceful drain answer = the rolling-deploy re-place
                # signal: move the lane, resend, caller never sees it
                tried.add(name)
                _telemetry.count("fleet_drain_replacements")
                self.mark_draining(name)
                continue
            if isinstance(answer, dict) and answer.get("shed") \
                    and merged.get("enabled"):
                # per-replica shed under a merged view: rewrite the
                # hint so every caller backs off by FLEET drain time
                answer["retry_after_ms"] = merged["retry_after_ms"]
            self.stats["answered"] += 1
            if _trace.enabled():
                _trace.add_span("fleet.forward", t0, time.time(),
                                attrs={"replica": name, "rid": rid,
                                       "model": model or "",
                                       "tenant": tenant,
                                       "hops": len(tried) + 1})
            return answer
        self.stats["unavailable"] += 1
        _telemetry.count("fleet_router_unavailable")
        return {"ok": False, "request_id": rid,
                "error": "BackendUnavailable: no usable replica "
                         "answered within the failover budget",
                "kind": "transient", "unavailable": True}

    # -- metrics -----------------------------------------------------------
    def ready(self) -> bool:
        return any(h.usable() for h in self.replicas.values())

    def metrics_snapshot(self) -> dict:
        """The fleet-level metrics document: router counters, the lane
        table, per-replica last-polled slices, and the merged
        admission block (docs/fleet.md)."""
        return {
            "schema": FLEET_METRICS_SCHEMA,
            "uptime_seconds": round(time.time() - self._started_at, 3),
            "router": dict(self.stats),
            "replicas": {
                name: {"state": h.state, "host": h.host,
                       "port": h.port, "generation": h.generation,
                       **h.last_metrics}
                for name, h in sorted(self.replicas.items())},
            "lanes": {f"{m}/{t}": r
                      for (m, t), r in sorted(self._lanes.items())},
            "admission": self._fleet_admission,
            "client_connections": len(self._client_writers),
        }

    # -- the JSON-lines front end ------------------------------------------
    async def handle(self, reader: asyncio.StreamReader,
                     writer: asyncio.StreamWriter) -> None:
        """One client connection: same protocol as cli/serve.py's
        handler — score requests, ``{"metrics": true}`` and
        ``{"ready": true}`` control lines — answered from the fleet."""
        key = next(self._conn_counter)
        self._client_writers[key] = writer
        try:
            while True:
                line = await reader.readline()
                if not line:
                    break
                try:
                    msg = json.loads(line)
                except json.JSONDecodeError as e:
                    out = {"ok": False, "request_id": None,
                           "error": f"{type(e).__name__}: {e}",
                           "kind": classify_error(e)}
                    writer.write((json.dumps(out) + "\n").encode())
                    await writer.drain()
                    continue
                if isinstance(msg, dict) and msg.get("metrics"):
                    out = {"ok": True,
                           "metrics": self.metrics_snapshot()}
                elif isinstance(msg, dict) and msg.get("ready"):
                    out = {"ok": True, "ready": self.ready(),
                           "draining": False, "generation": 0,
                           "fleet": {n: h.state for n, h in
                                     sorted(self.replicas.items())}}
                elif isinstance(msg, dict):
                    out = await self.score(msg)
                else:
                    out = {"ok": False, "request_id": None,
                           "error": "TypeError: request must be a "
                                    "JSON object", "kind": "permanent"}
                writer.write((json.dumps(out, default=float) + "\n")
                             .encode())
                await writer.drain()
        except (OSError, ConnectionError):
            # client went away mid-answer: nothing to answer TO — the
            # finally below releases the writer entry either way
            _telemetry.count("fleet_client_disconnects")
        finally:
            # the disconnect-cleanup path (lint TX-R07): the writer
            # entry MUST leave the table when the connection does
            self._client_writers.pop(key, None)
            writer.close()

    async def serve(self, host: str, port: int,
                    ready_cb=None, max_requests: Optional[int] = None,
                    banner_extra: Optional[dict] = None) -> int:
        """Bind the router front end and run until SIGTERM/SIGINT (or
        ``max_requests`` answers). Prints the same one-line JSON
        banner shape as ``tx serve`` with ``"fleet": true``."""
        self._loop = asyncio.get_running_loop()
        self._stop_event = stop = asyncio.Event()
        server = await asyncio.start_server(self.handle, host, port)
        bound = server.sockets[0].getsockname()[1]
        banner = {"serving": True, "fleet": True, "host": host,
                  "port": bound,
                  "replicas": sorted(self.replicas)}
        if banner_extra:
            banner.update(banner_extra)
        print(json.dumps(banner), flush=True)
        if ready_cb is not None:
            ready_cb(bound)
        sig_installed = []
        try:
            import signal as _signal
            for sig in (_signal.SIGTERM, _signal.SIGINT):
                self._loop.add_signal_handler(sig, stop.set)
                sig_installed.append(sig)
        except (ValueError, OSError, RuntimeError,
                NotImplementedError):
            pass
        self._poll_task = asyncio.create_task(
            self._poll_admission_forever())

        async def _watch_budget():
            while max_requests and \
                    self.stats["answered"] < max_requests:
                await asyncio.sleep(0.05)
            stop.set()

        budget_task = (asyncio.create_task(_watch_budget())
                       if max_requests else None)
        try:
            await stop.wait()
        finally:
            for sig in sig_installed:
                try:
                    self._loop.remove_signal_handler(sig)
                except (ValueError, RuntimeError):  # pragma: no cover
                    _telemetry.count("fleet_signal_cleanup_races")
            if budget_task is not None:
                budget_task.cancel()
            self._poll_task.cancel()
            self._poll_task = None
            self._stop_event = None
            server.close()
            # Server.wait_closed() waits for every accepted connection:
            # hang up on attached clients or a stopped router never exits
            for writer in list(self._client_writers.values()):
                writer.close()
            await server.wait_closed()
            for link in list(self._links.values()):
                await link.close()
        print(json.dumps({"fleet": True, **self.metrics_snapshot()},
                         default=float), flush=True)
        return 0
