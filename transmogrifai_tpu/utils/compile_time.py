"""Process-wide XLA compile-time accounting via ``jax.monitoring``.

CPU benchmark runs are frequently COMPILE-bound (tracing + XLA
compilation dominates the wall clock) while accelerator runs are
compute-bound — a single per-stage wall-time number cannot tell the two
apart. JAX publishes internal event durations (``.../backend_compile``
and friends) through ``jax.monitoring``; this module installs one
listener that accumulates them

- globally (``compile_seconds()``), snapshotted around each workflow
  stage so ``StageMetric.compile_seconds`` splits first-call compile
  time from steady-state execute time,
- per thread NAME (``compile_seconds_by_thread()``): the validator
  renames its dispatch workers ``tx-family-<Name>``
  (selector/validator.py), so a model-selection search attributes its
  compile bill family by family, and
- per SECTION label (``section()`` / ``seconds_by_section()``): the
  compiled prepare plan (plans/prepare.py) runs many stages inside ONE
  fused program, so thread- and stage-wall attribution alone would
  lose the per-stage compile/execute split that the telemetry-
  autotuning roadmap item consumes. A section is a labelled span
  (``with section("prepare:seg0"): ...``) on a per-thread stack;
  monitoring events observed inside attribute to EVERY open label, so
  a segment's total includes its per-stage sub-sections. Each label
  also records wall seconds and call count, giving callers the
  ``execute = wall - compile`` split per label.

The same listener registration counts the persistent compilation
cache's hits and misses (``cache_counts()``), so a process can say
whether its compiles were served from ``utils/jax_setup``'s cache
directory.

Installation is lazy and idempotent.
"""
from __future__ import annotations

import threading
import time
from collections import defaultdict
from contextlib import contextmanager
from typing import Dict

__all__ = ["install", "compile_seconds", "compile_seconds_by_thread",
           "cache_counts", "section", "seconds_by_section",
           "reset_sections", "set_section_observer"]

_LOCK = threading.Lock()
_TOTAL = {"seconds": 0.0}
_BY_THREAD: Dict[str, float] = defaultdict(float)
#: label -> {"seconds": wall, "compile": event seconds, "calls": n}
_SECTIONS: Dict[str, Dict[str, float]] = {}
#: persistent compilation cache hits / misses seen by this process
_CACHE = {"hits": 0, "misses": 0}
_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "hits",
                 "/jax/compilation_cache/cache_misses": "misses"}
_STATE = {"installed": False}
_SECTION_STACK = threading.local()
#: optional callback ``(label, wall_seconds, compile_seconds)`` fired
#: as each section CLOSES — how the span tracer
#: (observability/trace.py) attaches a section's compile/execute split
#: to the enclosing span. None (the default) costs nothing.
_SECTION_OBSERVER = {"fn": None}


def set_section_observer(fn) -> None:
    """Register (or clear, with None) the section-close observer."""
    _SECTION_OBSERVER["fn"] = fn


def _stack():
    st = getattr(_SECTION_STACK, "stack", None)
    if st is None:
        st = _SECTION_STACK.stack = []
    return st


def _on_event_duration(event: str, duration: float, **_kw) -> None:
    # '/jax/core/compile/backend_compile_duration' and the pjit
    # trace/lower events all carry 'compile' or 'trace' in the key;
    # anything else (transfer, execution) is not compile cost
    if "compile" not in event and "trace" not in event and \
            "lower" not in event:
        return
    open_labels = list(_stack())
    with _LOCK:
        _TOTAL["seconds"] += duration
        _BY_THREAD[threading.current_thread().name] += duration
        for label in open_labels:
            rec = _SECTIONS.setdefault(
                label, {"seconds": 0.0, "compile": 0.0, "calls": 0})
            rec["compile"] += duration


def _on_event(event: str, **_kw) -> None:
    key = _CACHE_EVENTS.get(event)
    if key is not None:
        with _LOCK:
            _CACHE[key] += 1


def install() -> None:
    """Register the listeners once."""
    with _LOCK:
        if _STATE["installed"]:
            return
        _STATE["installed"] = True
    import jax.monitoring as monitoring
    monitoring.register_event_duration_secs_listener(_on_event_duration)
    monitoring.register_event_listener(_on_event)


def cache_counts() -> Dict[str, int]:
    """``{"hits", "misses"}`` of the persistent compilation cache as
    seen by this process since :func:`install`. JAX records a miss
    when it WRITES an entry, i.e. only for compiles that took at least
    ``jax_persistent_cache_min_compile_time_secs``; quicker programs
    are recompiled by every process and counted nowhere."""
    with _LOCK:
        return dict(_CACHE)


def compile_seconds() -> float:
    """Total compile/trace seconds observed so far in this process."""
    with _LOCK:
        return _TOTAL["seconds"]


def compile_seconds_by_thread(prefix: str = "") -> Dict[str, float]:
    """Snapshot of compile seconds keyed by the OBSERVING thread's name
    at event time (filtered to names starting with ``prefix``)."""
    with _LOCK:
        return {k: v for k, v in _BY_THREAD.items()
                if k.startswith(prefix)}


@contextmanager
def section(label: str):
    """Attribute wall + compile seconds inside this span to ``label``
    (nested sections attribute compile events to every open label).
    Works inside a jit trace too: the body of a traced function runs
    exactly once per trace, so a per-stage section there measures that
    stage's TRACE cost — the per-stage half of the plan-section
    telemetry (docs/prepare.md)."""
    install()
    st = _stack()
    st.append(label)
    observer = _SECTION_OBSERVER["fn"]
    if observer is not None:
        with _LOCK:
            prev = _SECTIONS.get(label)
            compile_before = prev["compile"] if prev else 0.0
    t0 = time.perf_counter()
    try:
        yield
    finally:
        st.pop()
        wall = time.perf_counter() - t0
        with _LOCK:
            rec = _SECTIONS.setdefault(
                label, {"seconds": 0.0, "compile": 0.0, "calls": 0})
            rec["seconds"] += wall
            rec["calls"] += 1
            compile_after = rec["compile"]
        if observer is not None:
            # per-invocation compile share: this label's event seconds
            # accumulated while the span was open (approximate under
            # concurrent same-label sections; exact single-threaded)
            observer(label, wall, max(compile_after - compile_before,
                                      0.0))


def seconds_by_section(prefix: str = "") -> Dict[str, Dict[str, float]]:
    """Snapshot of ``{label: {"seconds", "compile", "calls"}}`` for
    labels starting with ``prefix``. ``seconds`` is wall time inside
    the span, ``compile`` the monitoring-event (trace/lower/compile)
    seconds observed while it was open; ``seconds - compile`` is the
    steady-state execute estimate for the label."""
    with _LOCK:
        return {k: dict(v) for k, v in _SECTIONS.items()
                if k.startswith(prefix)}


def reset_sections(prefix: str = "") -> None:
    """Drop section records (filtered by prefix; "" drops all) — test
    and bench isolation."""
    with _LOCK:
        for k in [k for k in _SECTIONS if k.startswith(prefix)]:
            del _SECTIONS[k]
