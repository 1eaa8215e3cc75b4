"""Structured span tracing: the Dapper-style correlation layer over the
whole train/search/serve pipeline (docs/observability.md).

The system's telemetry was write-only and fragmented: runtime counters
(runtime/telemetry.py), compile-time sections (utils/compile_time.py)
and bench-only profiles never correlated into "what did THIS request /
THIS train spend its time on". This module is the correlation
substrate:

- **Spans.** A span is one timed operation (``train``, ``search.rung``,
  ``serve.request``, ``score.dispatch``) with a parent, a trace id, and
  attributes. Parentage is a context-var stack per thread, so nested
  ``with span(...)`` blocks build the tree for free; cross-thread work
  (the validator's family pool, the serving executors) passes an
  explicit ``parent=current_ref()`` instead — context vars do not cross
  executor threads, and implicit inheritance there would lie. An open
  ``span()`` is also a ``jax.profiler.TraceAnnotation`` (name + scalar
  attributes), so under a running ``jax.profiler`` trace the package's
  spans sit in the profile on the profiler's clock, beside the device
  lanes; ``add_span`` is retrospective and cannot annotate.
- **Off by default, near-zero when off.** ``enabled()`` is one bool
  read; ``span()`` returns a shared no-op context manager and
  allocates NOTHING when tracing is disabled — the serving hot path
  pays one predicate per call site. Enable with ``TX_TRACE=1``
  (in-memory ring) or ``TX_TRACE=/path/trace.jsonl`` (also streamed to
  a schema-versioned JSONL file).
- **Monotonic clocks.** All span times are ``time.monotonic()``; the
  file header records an (epoch, monotonic) anchor pair so exporters
  (Perfetto) can place spans on the wall clock without any span paying
  a ``time.time()`` call.
- **Integration points.** ``utils/compile_time`` sections report into
  the CURRENT span as child spans carrying their compile/execute split,
  and its compile log's records as ``compile.program`` child spans
  (both registered via :func:`configure`); ``runtime/telemetry.event``
  fault/retry/quarantine events attach to the current span as span
  events. Neither module imports this one at module level in reverse —
  the dependency is one-way (observability imports nothing from the
  pipeline).

In-memory spans live in a bounded ring (``TX_TRACE_BUFFER``, default
20000) so a long-lived traced server cannot grow without bound; the
JSONL stream is the durable record. ``python -m transmogrifai_tpu.cli
trace`` summarizes and converts a trace file (cli/trace.py).
"""
from __future__ import annotations

import contextvars
import itertools
import json
import os
import threading
import time
from collections import deque
from typing import Any, Dict, Iterable, List, Optional, Tuple

__all__ = ["SCHEMA_VERSION", "configure", "configure_from_env",
           "enabled", "trace_path", "span", "add_span", "add_event",
           "current_ref", "new_request_id", "spans", "reset", "flush",
           "read_trace", "to_perfetto", "span_tree", "coverage"]

#: bump when the JSONL span record shape changes; the header line and
#: every span record carry it so readers can refuse foreign files
SCHEMA_VERSION = 1

_LOCK = threading.Lock()
_ENABLED = False
_PATH: Optional[str] = None
_FILE = None
_SPAN_IDS = itertools.count(1)
_REQ_IDS = itertools.count(1)
#: (epoch seconds, monotonic seconds) captured together: exporters map
#: monotonic span times onto the wall clock via this anchor
_ANCHOR = (time.time(), time.monotonic())

def _buffer_cap() -> int:
    try:
        return max(16, int(os.environ.get("TX_TRACE_BUFFER", "20000")))
    except ValueError:
        return 20000


_SPANS: "deque[dict]" = deque(maxlen=_buffer_cap())

#: per-thread/task stack of OPEN span records (contextvars: coroutines
#: on one loop each see their own stack; worker threads start empty)
_STACK: contextvars.ContextVar = contextvars.ContextVar(
    "tx_trace_stack", default=())


# ---------------------------------------------------------------------------
# configuration
# ---------------------------------------------------------------------------

def configure(enabled: bool, path: Optional[str] = None) -> None:
    """Turn tracing on/off at runtime. ``path`` additionally streams
    every finished span to a JSONL file (header line first). Also
    (un)registers the compile-time section and compile-log observers so
    section wall/compile splits, and every program that is traced,
    lowered and loaded or compiled, land as child spans of whatever
    span is open."""
    global _ENABLED, _PATH, _FILE, _SPANS
    if _PATH is not None and (not enabled or path != _PATH):
        _drain_pending()            # pending spans land before close
    with _LOCK:
        if _FILE is not None and (not enabled or path != _PATH):
            try:
                _FILE.close()
            except OSError:  # pragma: no cover - best effort
                pass
            _FILE = None
        _ENABLED = bool(enabled)
        _PATH = path if enabled else None
        if enabled and _SPANS.maxlen != _buffer_cap():
            _SPANS = deque(_SPANS, maxlen=_buffer_cap())
    from ..utils import compile_time
    compile_time.set_section_observer(_note_section if enabled else None)
    compile_time.set_program_observer(_note_program if enabled else None)


def configure_from_env() -> bool:
    """Read ``TX_TRACE``: unset/``0``/empty disables, ``1`` enables the
    in-memory ring, anything else is a JSONL output path. Returns the
    resulting enabled state."""
    raw = os.environ.get("TX_TRACE", "").strip()
    if raw in ("", "0", "off", "false"):
        configure(False)
    elif raw in ("1", "on", "true"):
        configure(True)
    else:
        configure(True, path=raw)
    return _ENABLED


def enabled() -> bool:
    """One bool read — the hot-path predicate."""
    return _ENABLED


def trace_path() -> Optional[str]:
    return _PATH


def new_request_id() -> str:
    """Process-unique request id, generated at serving admission and
    propagated enqueue -> coalesce -> encode -> dispatch -> reply
    (serving/server.py); echoed in the JSON-lines response."""
    return f"req-{os.getpid():x}-{next(_REQ_IDS):x}"


# ---------------------------------------------------------------------------
# span emission
# ---------------------------------------------------------------------------

class _NoopSpan:
    """The shared disabled-path context manager: no allocation, no
    record, identity across calls (asserted in tests)."""
    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NOOP = _NoopSpan()


class _Span:
    __slots__ = ("rec", "_token", "_annotation")

    def __init__(self, rec: dict):
        self.rec = rec
        self._token = None
        self._annotation = None

    def __enter__(self):
        stack = _STACK.get()
        rec = self.rec
        if rec["parent"] is None and stack:
            top = stack[-1]
            rec["parent"] = top["sid"]
            rec["trace"] = rec["trace"] or top["trace"]
        if rec["trace"] is None:
            rec["trace"] = f"t{rec['sid']}"
        self._token = _STACK.set(stack + (rec,))
        # the same span, written into a running ``jax.profiler`` trace on
        # the profiler's own clock, beside the device's lanes (a TraceMe:
        # nothing is recorded while no profiler session is open)
        from jax.profiler import TraceAnnotation
        self._annotation = TraceAnnotation(rec["name"], **{
            k: v for k, v in rec["attrs"].items()
            if isinstance(v, (str, int, float, bool))})
        self._annotation.__enter__()
        rec["t0"] = time.monotonic()
        return rec

    def __exit__(self, exc_type, exc, tb):
        rec = self.rec
        rec["dur"] = time.monotonic() - rec["t0"]
        self._annotation.__exit__(exc_type, exc, tb)
        if exc_type is not None:
            rec["attrs"]["status"] = "error"
            rec["attrs"]["error"] = f"{exc_type.__name__}: {exc}"
        if self._token is not None:
            _STACK.reset(self._token)
        _emit(rec)
        return False


def _new_rec(name: str, parent: Optional[int], trace_id: Optional[str],
             attrs: Dict[str, Any]) -> dict:
    return {"v": SCHEMA_VERSION, "sid": next(_SPAN_IDS), "parent": parent,
            "trace": trace_id, "name": name, "t0": 0.0, "dur": None,
            "attrs": attrs, "events": []}


def span(name: str, parent: Optional[Tuple[str, int]] = None,
         trace_id: Optional[str] = None, **attrs):
    """Context manager for one timed operation. With no explicit
    ``parent``, the innermost open span on this thread/task is the
    parent; pass ``parent=current_ref()`` captured BEFORE handing work
    to an executor to keep cross-thread spans in the tree."""
    if not _ENABLED:
        return _NOOP
    pid = None
    if parent is not None:
        trace_id = trace_id or parent[0]
        pid = parent[1]
    return _Span(_new_rec(name, pid, trace_id, attrs))


def add_span(name: str, start: float, end: float,
             parent: Optional[Tuple[str, int]] = None,
             trace_id: Optional[str] = None,
             attrs: Optional[dict] = None,
             events: Optional[List[dict]] = None) -> Optional[int]:
    """Retrospective span emission over an already-measured monotonic
    window — the serving loop reconstructs each request's
    wait/encode/dispatch/guard segments this way at resolve time
    instead of holding context managers open across async hops.
    Returns the span id (None when tracing is off)."""
    if not _ENABLED:
        return None
    pid = parent[1] if parent is not None else None
    if parent is not None and trace_id is None:
        trace_id = parent[0]
    rec = _new_rec(name, pid, trace_id, dict(attrs or {}))
    if rec["trace"] is None:
        rec["trace"] = f"t{rec['sid']}"
    rec["t0"] = float(start)
    rec["dur"] = max(float(end) - float(start), 0.0)
    if events:
        rec["events"] = list(events)
    _emit(rec)
    return rec["sid"]


def add_event(name: str, **fields) -> None:
    """Attach one timestamped event to the CURRENT open span (no-op
    when tracing is off or no span is open) — how runtime/telemetry
    fault/retry/quarantine events land inside the span that was doing
    the work when they fired."""
    if not _ENABLED:
        return
    stack = _STACK.get()
    if not stack:
        return
    stack[-1]["events"].append(
        {"name": name, "t": time.monotonic(), **fields})


def current_ref() -> Optional[Tuple[str, int]]:
    """(trace_id, span_id) of the innermost open span on this
    thread/task, or None — capture it before submitting work to an
    executor and pass it as ``span(parent=...)``."""
    if not _ENABLED:
        return None
    stack = _STACK.get()
    if not stack:
        return None
    top = stack[-1]
    return (top["trace"], top["sid"])


def _note_section(label: str, wall: float, compile_s: float) -> None:
    """utils/compile_time section observer: a closed section becomes a
    child span of the current span, carrying the compile/execute split
    (``execute = wall - compile``). Sections outside any span are
    dropped — a section is attribution detail, not a root operation."""
    if not _ENABLED:
        return
    stack = _STACK.get()
    if not stack:
        return
    top = stack[-1]
    now = time.monotonic()
    add_span(f"section:{label}", now - wall, now,
             parent=(top["trace"], top["sid"]),
             attrs={"compile_seconds": round(compile_s, 6),
                    "execute_seconds": round(max(wall - compile_s, 0.0),
                                             6)})


def _note_program(rec: dict) -> None:
    """utils/compile_time compile-log observer: a closed record becomes
    a ``compile.program`` child of the span open on the thread that
    paid for it (a program's first call traces inside ``search.fetch``),
    over the record's own ``t0`` .. ``t1`` and carrying its other
    fields. Outside any span it is dropped, as a section is: the log
    (``compile_time.compile_log()``) has it anyway."""
    stack = _STACK.get() if _ENABLED else ()
    if stack:
        add_span("compile.program", rec.pop("t0"), rec.pop("t1"),
                 parent=(stack[-1]["trace"], stack[-1]["sid"]), attrs=rec)


#: spans awaiting JSONL serialization — the hot path pays two atomic
#: deque appends; json.dumps + file I/O happen on the writer thread
#: (serialization on the serving EVENT LOOP cost 20% throughput and
#: 4x p99 in the serve_loop bench before this split)
_PENDING: "deque[dict]" = deque()
_WRITER = {"thread": None}


def _emit(rec: dict) -> None:
    _SPANS.append(rec)          # deque appends are atomic under the GIL
    if _PATH is not None:
        _PENDING.append(rec)
        th = _WRITER["thread"]
        if th is None or not th.is_alive():
            _start_writer()


def _start_writer() -> None:
    with _LOCK:
        th = _WRITER["thread"]
        if th is not None and th.is_alive():
            return
        th = threading.Thread(target=_writer_loop, daemon=True,
                              name="tx-trace-writer")
        _WRITER["thread"] = th
        th.start()


def _writer_loop() -> None:
    while _PATH is not None:
        time.sleep(0.05)
        _drain_pending()


def _open_file():
    """Call with _LOCK held."""
    global _FILE
    if _FILE is None and _PATH is not None:
        fresh = (not os.path.exists(_PATH)
                 or os.path.getsize(_PATH) == 0)
        _FILE = open(_PATH, "a", encoding="utf-8")
        if fresh:
            _FILE.write(json.dumps(
                {"kind": "header", "schema": SCHEMA_VERSION,
                 "anchor_epoch": _ANCHOR[0],
                 "anchor_monotonic": _ANCHOR[1],
                 "pid": os.getpid()}) + "\n")
    return _FILE


def _drain_pending() -> None:
    batch: List[dict] = []
    while True:
        try:
            batch.append(_PENDING.popleft())
        except IndexError:
            break
    if not batch:
        return
    with _LOCK:
        fh = _open_file()
        if fh is None:
            return
        fh.write("".join(
            json.dumps({"kind": "span", **r}, default=str) + "\n"
            for r in batch))


def flush() -> None:
    """Serialize every pending span to the JSONL file and fsync-level
    flush it — call before reading the file back."""
    _drain_pending()
    with _LOCK:
        if _FILE is not None:
            _FILE.flush()


def spans() -> List[dict]:
    """Snapshot of the in-memory span ring (finished spans only)."""
    with _LOCK:
        return [dict(s) for s in _SPANS]


def reset() -> None:
    """Drop buffered spans (test/bench isolation); the JSONL file, the
    id counters and the enabled state are untouched."""
    with _LOCK:
        _SPANS.clear()


# ---------------------------------------------------------------------------
# reading + analysis (tx trace, tests, bench)
# ---------------------------------------------------------------------------

def read_trace(path: str) -> Tuple[dict, List[dict]]:
    """(header meta, span records) from a JSONL trace file. Torn final
    lines (a killed writer) are dropped, same as the journal reader.

    A file may hold APPENDED segments from several traced processes
    (each starts with its own header); span ids are process-local, so
    sids/parents are rescoped per segment (seg * 1e9 + sid) and
    anonymous ``t<sid>`` trace ids get a segment prefix — spans from
    different runs never alias."""
    meta: dict = {"schema": SCHEMA_VERSION,
                  "anchor_epoch": _ANCHOR[0],
                  "anchor_monotonic": _ANCHOR[1]}
    out: List[dict] = []
    seg = 0
    with open(path, encoding="utf-8") as fh:
        for line in fh:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                continue                    # torn tail
            kind = rec.pop("kind", "span")
            if kind == "header":
                if rec.get("schema", SCHEMA_VERSION) > SCHEMA_VERSION:
                    raise ValueError(
                        f"{path}: trace schema {rec.get('schema')} is "
                        f"newer than this reader ({SCHEMA_VERSION})")
                seg += 1
                meta.update(rec)
                meta["segments"] = seg
            elif kind == "span":
                base = max(seg - 1, 0) * 1_000_000_000
                if base:
                    rec["sid"] = rec.get("sid", 0) + base
                    if rec.get("parent") is not None:
                        rec["parent"] += base
                    tr = rec.get("trace")
                    if isinstance(tr, str) and tr.startswith("t") \
                            and tr[1:].isdigit():
                        rec["trace"] = f"s{seg}:{tr}"
                out.append(rec)
    return meta, out


def span_tree(records: Iterable[dict], trace_id: str) -> List[dict]:
    """The spans of one trace (request/train) as a nested tree:
    ``[{span, children: [...]}, ...]`` roots in start order."""
    recs = [r for r in records if r.get("trace") == trace_id]
    by_sid = {r["sid"]: {"span": r, "children": []} for r in recs}
    roots = []
    for r in sorted(recs, key=lambda r: r.get("t0", 0.0)):
        node = by_sid[r["sid"]]
        parent = by_sid.get(r.get("parent"))
        (parent["children"] if parent else roots).append(node)
    return roots


def coverage(records: Iterable[dict], trace_id: str) -> float:
    """Fraction of the trace's root span wall-clock covered by its
    direct child spans (overlaps merged) — the acceptance metric for
    request attribution (>= 0.95 for a traced serve request)."""
    roots = span_tree(records, trace_id)
    if not roots:
        return 0.0
    root = roots[0]["span"]
    total = root.get("dur") or 0.0
    if total <= 0:
        return 0.0
    windows = sorted(
        (c["span"]["t0"], c["span"]["t0"] + (c["span"]["dur"] or 0.0))
        for c in roots[0]["children"])
    covered, cur0, cur1 = 0.0, None, None
    for w0, w1 in windows:
        if cur0 is None:
            cur0, cur1 = w0, w1
        elif w0 <= cur1:
            cur1 = max(cur1, w1)
        else:
            covered += cur1 - cur0
            cur0, cur1 = w0, w1
    if cur0 is not None:
        covered += cur1 - cur0
    return min(covered / total, 1.0)


def to_perfetto(meta: dict, records: Iterable[dict]) -> dict:
    """Chrome/Perfetto ``trace_event`` JSON: complete ("X") events per
    span (one tid lane per trace id) + instant ("i") events for span
    events — load the result straight into ui.perfetto.dev."""
    base = meta.get("anchor_monotonic", _ANCHOR[1])
    lanes: Dict[str, int] = {}
    events: List[dict] = []
    for r in records:
        tid = lanes.setdefault(r.get("trace") or "?", len(lanes) + 1)
        ts_us = (r.get("t0", 0.0) - base) * 1e6
        events.append({
            "name": r.get("name", "?"), "cat": "span", "ph": "X",
            "ts": round(ts_us, 3),
            "dur": round((r.get("dur") or 0.0) * 1e6, 3),
            "pid": meta.get("pid", os.getpid()), "tid": tid,
            "args": {**(r.get("attrs") or {}),
                     "trace": r.get("trace"), "sid": r.get("sid")},
        })
        for ev in r.get("events", ()):
            events.append({
                "name": ev.get("name", "event"), "cat": "event",
                "ph": "i", "s": "t",
                "ts": round((ev.get("t", r.get("t0", 0.0)) - base) * 1e6,
                            3),
                "pid": meta.get("pid", os.getpid()), "tid": tid,
                "args": {k: v for k, v in ev.items()
                         if k not in ("name", "t")},
            })
    return {"traceEvents": events,
            "displayTimeUnit": "ms",
            "otherData": {"schema": meta.get("schema", SCHEMA_VERSION)}}


# import-time default: a process started with TX_TRACE set traces from
# its first span without any explicit configure call (tx serve, bench)
if os.environ.get("TX_TRACE", "").strip() not in ("", "0", "off",
                                                  "false"):
    configure_from_env()
