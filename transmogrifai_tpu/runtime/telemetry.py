"""Process-wide fault-tolerance telemetry: counters, dispatch log,
event stream.

The same idiom as ``serving.plan_compiles()`` / ``racing
.search_compiles()``: module-level accumulators that chip_smoke.py, the
benchmark (``benchmark/``) and the resilience tests read to prove runtime behavior (zero re-dispatch of
journaled work, retry counts, quarantine counts) rather than infer it
from timing. ``WorkflowListener`` snapshots the event stream into
``AppMetrics.fault_events`` so one training run's retries and
quarantines land next to its stage profile.
"""
from __future__ import annotations

import logging
import os
import threading
from collections import deque
from typing import Dict, List, Tuple

from ..observability import trace as _trace

_log = logging.getLogger(__name__)

__all__ = ["count", "counters", "reset", "note_dispatch", "dispatch_log",
           "event", "events_mark", "events_since", "events_dropped",
           "host_pull", "OVERFLOW_EVENT", "PREPARE_PULL_BYTES"]

_LOCK = threading.Lock()
_COUNTERS: Dict[str, int] = {}
#: every ACTUAL family dispatch of this process:
#: (family, rung_label, cand_indices, folds) — the unit the resume
#: acceptance gate asserts over ("zero re-dispatch of journaled
#: (family, cand, fold) entries")
_DISPATCH_LOG: List[Tuple[str, str, Tuple[int, ...], int]] = []
#: the event stream is a RING: a long-running `tx serve` process emits
#: events forever, so the in-process list is bounded
#: (``TX_TELEMETRY_EVENTS_CAP``, default 4096) — overflow drops the
#: OLDEST events, counts them (``telemetry_events_dropped``), and
#: ``events_since`` marks the gap with an explicit overflow record
_EVENTS: "deque[dict]" = deque()
#: absolute stream index of _EVENTS[0] (how many events were dropped
#: off the front so far) — events_mark()/events_since() marks are
#: absolute stream positions, so they stay valid across overflow
_EVENTS_BASE = 0

#: the synthetic record events_since() prepends when its mark fell off
#: the ring
OVERFLOW_EVENT = "telemetry_events_overflow"


def _events_cap() -> int:
    """Env-tunable ring capacity (re-read per event so tests and a
    live process can retune without reimport)."""
    try:
        return max(16, int(os.environ.get("TX_TELEMETRY_EVENTS_CAP",
                                          "4096")))
    except ValueError:
        return 4096


def count(name: str, n: int = 1) -> None:
    with _LOCK:
        _COUNTERS[name] = _COUNTERS.get(name, 0) + n


#: bytes the prepare plan and the checkers read back from the device in a
#: train (:func:`host_pull`); ``PreparePlan.execute`` makes it present at 0
PREPARE_PULL_BYTES = "prepare_host_pull_bytes"


def host_pull(x, dtype=None):
    """``np.asarray(x, dtype)``, counting the bytes of ``x`` under
    :data:`PREPARE_PULL_BYTES` when it is a device array (a host array
    moves nothing and is not counted)."""
    import numpy as np
    if not isinstance(x, np.ndarray) and hasattr(x, "addressable_shards"):
        count(PREPARE_PULL_BYTES, int(x.nbytes))
    return np.asarray(x, dtype=dtype)


def counters() -> Dict[str, int]:
    """Snapshot of all counters (``retries``, ``quarantines``,
    ``journal_hits``, ``journal_replayed_entries``,
    ``candidate_fold_dispatches``, ``family_dispatches``, ...)."""
    with _LOCK:
        return dict(_COUNTERS)


def note_dispatch(family: str, rung_label: str,
                  cands: Tuple[int, ...], folds: int) -> None:
    """Record one REAL family dispatch (journal replays never land
    here) of ``len(cands) x folds`` candidate-fold evaluations."""
    with _LOCK:
        _DISPATCH_LOG.append((family, rung_label, tuple(cands),
                              int(folds)))
        _COUNTERS["family_dispatches"] = \
            _COUNTERS.get("family_dispatches", 0) + 1
        _COUNTERS["candidate_fold_dispatches"] = \
            _COUNTERS.get("candidate_fold_dispatches", 0) \
            + len(cands) * int(folds)


def dispatch_log() -> List[Tuple[str, str, Tuple[int, ...], int]]:
    with _LOCK:
        return list(_DISPATCH_LOG)


def event(event_name: str, **fields) -> None:
    """Append one fault event (``retry`` / ``quarantine`` /
    ``journal_resume`` / ``plan_fallback`` / ...) and log it — the
    runtime degrades LOUDLY, never silently. With tracing enabled the
    event ALSO attaches to the current span (observability/trace.py),
    so a retry/quarantine lands inside the dispatch that suffered it."""
    global _EVENTS_BASE
    rec = {"event": event_name, **fields}
    with _LOCK:
        _EVENTS.append(rec)
        cap = _events_cap()
        while len(_EVENTS) > cap:
            _EVENTS.popleft()
            _EVENTS_BASE += 1
            _COUNTERS["telemetry_events_dropped"] = \
                _COUNTERS.get("telemetry_events_dropped", 0) + 1
    if _trace.enabled():
        _trace.add_event(event_name, **fields)
    _log.warning("runtime: %s %s", event_name,
                 " ".join(f"{k}={v}" for k, v in fields.items()))


def events_mark() -> int:
    """Absolute position in the event stream (events emitted so far) —
    stable across ring overflow."""
    with _LOCK:
        return _EVENTS_BASE + len(_EVENTS)


def events_since(mark: int) -> List[dict]:
    """Events from ``mark`` on. If the ring dropped events past the
    mark, the FIRST returned record is an explicit
    ``{"event": OVERFLOW_EVENT, "dropped": n}`` marker — consumers see
    the gap instead of a silently shortened history."""
    with _LOCK:
        if mark >= _EVENTS_BASE:
            start = mark - _EVENTS_BASE
            return [dict(e) for e in list(_EVENTS)[start:]]
        out: List[dict] = [{"event": OVERFLOW_EVENT,
                            "dropped": _EVENTS_BASE - mark}]
        out.extend(dict(e) for e in _EVENTS)
        return out


def events_dropped() -> int:
    """Events lost to ring overflow so far in this process."""
    with _LOCK:
        return _COUNTERS.get("telemetry_events_dropped", 0)


def reset() -> None:
    """Zero every accumulator (tests / bench isolation)."""
    global _EVENTS_BASE
    with _LOCK:
        _COUNTERS.clear()
        _DISPATCH_LOG.clear()
        _EVENTS.clear()
        _EVENTS_BASE = 0
