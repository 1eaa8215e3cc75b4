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
directory, and keeps the COMPILE LOG (``compile_log()``): one record
for every program that entered the process, closed when its
``backend_compile_duration`` arrives (docs/observability.md "Set-up by
program"): ``program`` (``jit(batched)`` as the profiler's ``XLA
Modules`` lane spells it, ``jit_batched``), ``thread`` (its name at the
event), ``t0`` / ``t1`` (``time.monotonic()``: start of the program's
own trace, or of its lowering where no trace was seen; end of the
backend event), ``trace_s`` (the OUTERMOST trace only: nested jitted
functions raise their own trace events first and are inside it),
``lower_s``, ``backend_s``, ``cache`` (``"hit"`` where the persistent
cache served it, else ``"compiled"``), ``retrieval_s`` and ``saved_s``
(cache detail: the load is inside ``backend_s`` already, and the
seconds a hit SAVED were never spent, so neither is in any total).

Installation is lazy and idempotent.
"""
from __future__ import annotations

import re
import threading
import time
from collections import defaultdict, deque
from contextlib import contextmanager
from typing import Dict, List

__all__ = ["install", "compile_seconds", "compile_seconds_by_thread",
           "cache_counts", "section", "seconds_by_section",
           "reset_sections", "set_section_observer", "compile_log",
           "compile_log_dropped", "set_program_observer"]

_LOCK = threading.Lock()
_TOTAL = {"seconds": 0.0}
_BY_THREAD: Dict[str, float] = defaultdict(float)
#: label -> {"seconds": wall, "compile": event seconds, "calls": n}
_SECTIONS: Dict[str, Dict[str, float]] = {}
#: persistent compilation cache hits / misses seen by this process
_CACHE = {"hits": 0, "misses": 0}
_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "hits",
                 "/jax/compilation_cache/cache_misses": "misses"}
#: the duration events this module means, by the record field each
#: fills; the first three are time spent and add up to every total
_DURATION_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "backend_s",
    "/jax/compilation_cache/cache_retrieval_time_sec": "retrieval_s",
    "/jax/compilation_cache/compile_time_saved_sec": "saved_s"}
_STATE = {"installed": False, "dropped": 0}
#: the compile log, in arrival order; the oldest record is dropped
#: (and counted) when a 4,097th arrives
_LOG: "deque[dict]" = deque(maxlen=4096)
#: per thread: the section stack, the record in the making, the newest
#: trace by function name since the last lowering, and the timed events
#: no later one holds yet (start, seconds)
_SECTION_STACK = threading.local()
#: optional callback ``(label, wall_seconds, compile_seconds)`` fired
#: as each section CLOSES — how the span tracer
#: (observability/trace.py) attaches a section's compile/execute split
#: to the enclosing span. None (the default) costs nothing.
_SECTION_OBSERVER = {"fn": None}
#: optional callback ``(record)`` fired as each compile-log record
#: CLOSES, on the thread that paid — the tracer's ``compile.program``
_PROGRAM_OBSERVER = {"fn": None}


def set_section_observer(fn) -> None:
    """Register (or clear, with None) the section-close observer."""
    _SECTION_OBSERVER["fn"] = fn


def set_program_observer(fn) -> None:
    """Register (or clear, with None) the compile-log observer."""
    _PROGRAM_OBSERVER["fn"] = fn


def _local():
    tl = _SECTION_STACK
    if not hasattr(tl, "stack"):
        tl.stack, tl.open, tl.traces = [], {}, {}
        tl.events = deque(maxlen=_LOG.maxlen)
    return tl


def _stack():
    return _local().stack


def _on_event_duration(event: str, duration: float, fun_name=None,
                       **_kw) -> None:
    field = _DURATION_EVENTS.get(event)
    if field is None:       # transfer, execution: not compile cost
        return
    tl, now = _local(), time.monotonic()
    if field in ("retrieval_s", "saved_s"):
        tl.open[field] = duration
        return
    # events nest (a jitted function traced inside another's trace, a
    # program compiled inside one): what an event holds of earlier ones
    # leaves ``spent``, so a total counts every second once
    start, spent, rec = now - duration, duration, None
    while tl.events and tl.events[-1][0] >= start:
        spent -= tl.events.pop()[1]
    tl.events.append((start, duration))
    thread = threading.current_thread().name
    if field == "trace_s":
        tl.traces[fun_name] = (start, duration)
    elif field == "lower_s":
        # ``jit(X)``'s own trace is the newest one named X before its
        # lowering: the nested functions' came first and are inside it
        t0, trace_s = tl.traces.get(
            fun_name[fun_name.find("(") + 1:-1] if fun_name else None,
            (start, 0.0))
        tl.traces.clear()
        tl.open = {"fun": fun_name, "t0": t0, "trace_s": trace_s,
                   "lower_s": duration}
    else:
        cur, tl.open = tl.open, {}
        if cur.get("fun", fun_name) != fun_name:    # lowered elsewhere
            cur = {k: v for k, v in cur.items() if k not in (
                "t0", "trace_s", "lower_s")}
        rec = {"program": re.sub(r"[^\w.-]", "_", re.sub(
                   r"^(\w+)\((.*)\)$", r"\1_\2", fun_name or "?")),
               "thread": thread, "t0": cur.get("t0", start), "t1": now,
               "trace_s": cur.get("trace_s", 0.0),
               "lower_s": cur.get("lower_s", 0.0), "backend_s": duration,
               "cache": cur.get("cache", "compiled"),
               "retrieval_s": cur.get("retrieval_s", 0.0),
               "saved_s": cur.get("saved_s", 0.0)}
    spent = max(spent, 0.0)
    with _LOCK:
        _TOTAL["seconds"] += spent
        _BY_THREAD[thread] += spent
        for label in tl.stack:
            sec = _SECTIONS.setdefault(
                label, {"seconds": 0.0, "compile": 0.0, "calls": 0})
            sec["compile"] += spent
        if rec is not None:
            if len(_LOG) == _LOG.maxlen:    # the append drops the oldest
                _STATE["dropped"] += 1
            _LOG.append(rec)
    observer = _PROGRAM_OBSERVER["fn"]
    if rec is not None and observer is not None:
        observer(dict(rec))


def _on_event(event: str, **_kw) -> None:
    key = _CACHE_EVENTS.get(event)
    if key is not None:
        if key == "hits":       # on the thread of the record it closes
            _local().open["cache"] = "hit"
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


def compile_log() -> List[dict]:
    """A copy of the compile log: a record a program, in the order the
    backend events arrived (fields: the module docstring)."""
    with _LOCK:
        return [dict(r) for r in _LOG]


def compile_log_dropped() -> int:
    """Records the bounded log has dropped (its oldest) so far."""
    with _LOCK:
        return _STATE["dropped"]


def compile_seconds() -> float:
    """Total trace + lower + backend (compile or cache load) seconds
    observed so far in this process."""
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
