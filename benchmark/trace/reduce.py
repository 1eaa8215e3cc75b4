"""From a profiler trace to busy seconds, idle gaps and the operations that
took the time. The only reduction the benchmark uses; checked on the recorded
trace beside it by ``python3 benchmark/selfcheck.py``.

A trace is handled as plain data, ``[{"name": plane, "lines": [{"name": line,
"events": [[name, start_ns, duration_ns], ...]}]}]``: :func:`load_xplane`
makes it from the profiler's ``.xplane.pb`` with nothing but JAX, and the
recorded trace is the same thing as JSON.

Rules (they are what ``utils/profiling.summarize_device_trace`` gets wrong, it
reads 172.7 % busy on one device):

- a device is a plane named ``/device:TPU:<n>``; time is read from its
  ``XLA Ops`` line only. ``Async XLA Ops`` (copies and collectives in flight
  beside the compute), ``Steps`` and the overlay lines describe the same time
  again and are left out; ``XLA Modules`` (one event per program run) only
  lends each op the name of the program it ran in;
- busy time is the UNION of the op intervals clipped to the traced window, so
  a ``while`` and the body ops nested inside it count once;
- an operation's time in the top list is its SELF time: its duration less the
  ops nested directly inside it, so the list adds up to the busy time and a
  ``while`` shows only what its body does not explain. The profiler names an
  op by its whole HLO line; it is cut to ``<program>/<instruction>
  <result shape>``, and summed over the devices;
- the traced window is the ``TraceAnnotation`` the harness wraps around the
  traced repetitions (found on any host line), and an idle gap is a maximal
  stretch of the window with no op on that device. A gap is named after the
  innermost host span that covers its midpoint, once host spans
  (``time.monotonic()`` seconds) are shifted onto the profiler's clock by the
  marker's two start times; "none" when no span covers it.
"""
from __future__ import annotations

import bisect
import re
from typing import Any, Dict, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OP_LINE, MODULE_LINE = "XLA Ops", "XLA Modules"
HLO_LINE = re.compile(r"^%(?P<op>[\w.\-]+) = \(?(?P<shape>\w+\[[\d,]*\])")

Plane = Dict[str, Any]


def load_xplane(path: str) -> List[Plane]:
    """The device planes' op lines, and every host line that holds the
    harness's marker, as plain data."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    planes: List[Plane] = []
    for plane in data.planes:
        device = DEVICE_PLANE.match(plane.name) is not None
        lines = []
        for line in plane.lines:
            if device and line.name not in (OP_LINE, MODULE_LINE):
                continue
            events = [[e.name, int(e.start_ns), int(e.duration_ns)]
                      for e in line.events
                      if device or e.name.startswith("bench.")]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return planes


def find_marker(planes: Sequence[Plane], marker: str
                ) -> Optional[Tuple[int, int]]:
    """(start_ns, end_ns) of the marker annotation on the profiler's clock."""
    for plane in planes:
        if DEVICE_PLANE.match(plane["name"]):
            continue
        for line in plane["lines"]:
            for name, start, dur in line["events"]:
                if name == marker:
                    return start, start + dur
    return None


def short_name(raw: str) -> str:
    """``%fusion.5 = f32[8,128]{...} fusion(...)`` -> ``fusion.5 f32[8,128]``;
    a program's ``jit_f(123)`` -> ``jit_f``."""
    match = HLO_LINE.match(raw)
    if match:
        return f"{match['op']} {match['shape']}"
    return re.sub(r"\(\d+\)$", "", raw)[:80]


def union(intervals: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Disjoint, sorted intervals covering the same points."""
    out: List[Tuple[int, int]] = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1] = (out[-1][0], end)
        else:
            out.append((start, end))
    return out


def self_times(events: Sequence[Sequence[Any]]) -> Dict[str, int]:
    """Nanoseconds by op name, each event's duration less the events nested
    directly inside it on the same line."""
    totals: Dict[str, int] = {}
    stack: List[List[Any]] = []          # [name, end_ns, self_ns]

    def close(item: List[Any]) -> None:
        totals[item[0]] = totals.get(item[0], 0) + max(item[2], 0)

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= start:
            close(stack.pop())
        if stack:
            stack[-1][2] -= dur
        stack.append([name, start + dur, dur])
    while stack:
        close(stack.pop())
    return totals


def reduce(planes: Sequence[Plane], marker: str,
           host_spans: Sequence[tuple] = (),
           mono_start: Optional[float] = None) -> Dict[str, Any]:
    """``{"window_s", "devices": [{"device", "busy_s", "ops"}], "top_ops":
    [[name, seconds]], "programs": [[name, seconds, runs]], "idle_gaps":
    [[name, seconds]]}``: the ten ops with most self time, the ten programs
    with most time (and how many times each ran) and the five longest gaps,
    each summed or pooled over the devices. ``host_spans`` are
    ``(name, start, end)`` in ``time.monotonic()`` seconds and ``mono_start``
    is that clock at the marker's start. Without a marker the window is the
    extent of the device ops."""
    window = find_marker(planes, marker)
    device_lines = []
    for plane in planes:
        match = DEVICE_PLANE.match(plane["name"])
        if not match:
            continue
        modules = sorted((start, start + dur, short_name(name))
                         for line in plane["lines"]
                         if line["name"] == MODULE_LINE
                         for name, start, dur in line["events"])
        starts = [m[0] for m in modules]

        def named(name: str, start: int) -> str:
            i = bisect.bisect_right(starts, start) - 1
            owner = modules[i][2] if i >= 0 and start < modules[i][1] else "?"
            return f"{owner}/{short_name(name)}"

        events = [(named(name, start), start, dur)
                  for line in plane["lines"] if line["name"] == OP_LINE
                  for name, start, dur in line["events"]]
        device_lines.append((int(match.group(1)), events, modules))
    device_lines.sort()
    if window is None:
        starts = [e[1] for _, evs, _ in device_lines for e in evs]
        ends = [e[1] + e[2] for _, evs, _ in device_lines for e in evs]
        window = (min(starts), max(ends)) if starts else (0, 0)
    w0, w1 = window

    shifted = []
    if mono_start is not None:
        for name, start, end in host_spans:
            shifted.append((name, w0 + int((start - mono_start) * 1e9),
                            w0 + int((end - mono_start) * 1e9)))

    def span_at(t_ns: int) -> str:
        covering = [(end - start, name) for name, start, end in shifted
                    if start <= t_ns <= end]
        return min(covering)[1] if covering else "none"

    devices, op_ns, module_ns, module_runs, gaps = [], {}, {}, {}, []
    for index, events, modules in device_lines:
        for start, end, name in modules:
            inside_ns = min(end, w1) - max(start, w0)
            if inside_ns > 0:
                module_ns[name] = module_ns.get(name, 0) + inside_ns
                module_runs[name] = module_runs.get(name, 0) + 1
        inside = [(name, max(start, w0), min(start + dur, w1) - max(start, w0))
                  for name, start, dur in events
                  if start < w1 and start + dur > w0]
        busy = union([(s, s + d) for _, s, d in inside])
        devices.append({"device": index, "ops": len(inside),
                        "busy_s": sum(e - s for s, e in busy) / 1e9})
        for name, ns in self_times(inside).items():
            op_ns[name] = op_ns.get(name, 0) + ns
        edges = [w0] + [t for interval in busy for t in interval] + [w1]
        gaps.extend((end - start, start, index)
                    for start, end in zip(edges[0::2], edges[1::2])
                    if end > start)

    def gap_name(length: int, start: int, index: int) -> str:
        name = span_at(start + length // 2)
        return f"{name} @dev{index}" if len(device_lines) > 1 else name

    def top(table: Dict[str, int]) -> List[List[Any]]:
        ranked = sorted(table.items(), key=lambda kv: -kv[1])[:10]
        return [[name, ns / 1e9] for name, ns in ranked]

    return {"window_s": (w1 - w0) / 1e9, "devices": devices,
            "top_ops": top(op_ns),
            "programs": [[name, seconds, module_runs[name]]
                         for name, seconds in top(module_ns)],
            "idle_gaps": [[gap_name(*gap), gap[0] / 1e9]
                          for gap in sorted(gaps, reverse=True)[:5]]}


def idle_share(summary: Dict[str, Any]) -> Optional[float]:
    """1 - busy over window, averaged over the devices; None without ops."""
    if not summary["devices"] or summary["window_s"] <= 0:
        return None
    busy = sum(d["busy_s"] for d in summary["devices"]) / len(
        summary["devices"])
    return 1.0 - busy / summary["window_s"]
