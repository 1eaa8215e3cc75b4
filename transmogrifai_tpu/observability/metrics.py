"""Live serving metrics: streaming latency histograms + the snapshot
schema the metrics endpoint serves.

The serving loop (serving/server.py) was observable only POST-MORTEM —
``describe()`` after ``stop()``. This module gives it a live view:
per-tenant request-latency histograms built on the same fixed-memory
:class:`~..utils.histogram.StreamingHistogram` the drift sentinel uses
(bounded bins, so a month-long serve process holds constant memory),
plus one :func:`snapshot` shape answered by the ``{"metrics": true}``
TCP control request and the ``tx serve --metrics-port`` HTTP endpoint
(docs/observability.md documents the schema).
"""
from __future__ import annotations

import threading
import time
from typing import Dict, Optional

from ..utils.histogram import StreamingHistogram

__all__ = ["METRICS_SCHEMA_VERSION", "LatencyHistogram", "ServeMetrics"]

#: bump when the snapshot shape changes (the endpoint's contract)
#: v2: per-tenant "sentinels" drift state + the "lifecycle" slice
#: v3: top-level "process" block (uptime, restart generation,
#:     draining/ready flags, in-flight count, last snapshot age) +
#:     "plan_compiles" — the restart-drill contract
#:     (docs/serving_restart.md)
#: v4: top-level "admission" block (overload admission state: brownout
#:     state + transitions, pressure, lane bound / DRR quantum,
#:     measured drain rate, per-tenant weight/admitted/shed counts,
#:     knob decisions) — {"enabled": false} when the controller is off
#:     (docs/admission.md)
#: v5: top-level "backend" block (platform, device_kind, device_count,
#:     jax version, x64, compile-cache dir + hits/misses) — what the
#:     process actually runs on (utils/jax_setup.backend_block)
METRICS_SCHEMA_VERSION = 5


class LatencyHistogram:
    """Streaming latency sketch: fixed-size bins, exact count/min/max,
    interpolated quantiles — observe() is O(log bins) amortized and
    the memory never grows with traffic."""

    def __init__(self, max_bins: int = 64):
        self._hist = StreamingHistogram(max_bins=max_bins)
        self.count = 0
        self.min = float("inf")
        self.max = 0.0

    def observe(self, seconds: float) -> None:
        self.observe_many([seconds])

    def observe_many(self, seconds_batch) -> None:
        """One histogram merge for a whole batch of latencies — the
        serving loop observes per DISPATCH, not per request, so the
        numpy merge cost amortizes over the batch."""
        ms = [s * 1000.0 for s in seconds_batch]
        if not ms:
            return
        self._hist.update(ms)
        self.count += len(ms)
        self.min = min(self.min, min(ms))
        self.max = max(self.max, max(ms))

    def to_json(self) -> dict:
        if self.count == 0:
            return {"count": 0}
        return {
            "count": self.count,
            "p50_ms": round(self._hist.quantile(0.50), 3),
            "p95_ms": round(self._hist.quantile(0.95), 3),
            "p99_ms": round(self._hist.quantile(0.99), 3),
            "min_ms": round(self.min, 3),
            "max_ms": round(self.max, 3),
        }


class ServeMetrics:
    """The serving loop's live accumulators: per-tenant latency
    histograms + answered/failed counts. One instance per
    :class:`~..serving.server.ServingServer`; updated at request
    resolution (the executor side, never the event loop)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._latency: Dict[str, LatencyHistogram] = {}
        self.started_at = time.time()
        self.answered = 0
        self.failed = 0

    def observe(self, tenant: str, seconds: float) -> None:
        self.observe_batch(tenant, [seconds])

    def observe_batch(self, tenant: str, seconds_batch) -> None:
        with self._lock:
            hist = self._latency.get(tenant)
            if hist is None:
                hist = self._latency[tenant] = LatencyHistogram()
            hist.observe_many(seconds_batch)
            self.answered += len(seconds_batch)

    def note_failure(self) -> None:
        with self._lock:
            self.failed += 1

    def latency_json(self) -> Dict[str, dict]:
        with self._lock:
            return {t: h.to_json() for t, h in
                    sorted(self._latency.items())}

    def uptime_seconds(self) -> float:
        return time.time() - self.started_at
