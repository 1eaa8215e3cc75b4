"""Device-trace post-processing: per-op timing from a jax.profiler run.

`jax.profiler.start_trace` writes a Chrome-trace JSON
(``plugins/profile/<ts>/<host>.trace.json.gz``) whose DEVICE lanes carry
one complete event per XLA op execution — the accelerator-level
profile the reference delegates to the Spark UI (SURVEY §5.5 aux).
:func:`summarize_device_trace` reduces it to the top time-sink ops and a
device-busy figure so benchmarks can report utilization, not just
wall-clock.

NOTE (jax 0.9.0 on a TPU v5e, PR 23's chip run): the profiler writes
both ``<host>.xplane.pb`` and ``<host>.trace.json.gz`` and the device
lanes are found, but the busy figure OVER-COUNTS — 172.7% of the span
on one device for a warm flagship search. A ``while`` op is an event
that contains its body's ops on the same "XLA Ops" lane, and the lane
filter also keeps "Async XLA Ops". Do not quote ``device_busy_pct``
until ROADMAP S1 rebuilds the reduction (union of intervals, one
lane) and checks it on a small recorded trace.

On the CPU backend the trace contains only host python frames (no
device lanes) — callers fall back to the workflow listener's per-stage
profile there.
"""
from __future__ import annotations

import collections
import glob
import gzip
import json
import os
from typing import Dict, List, Optional, Tuple

__all__ = ["summarize_device_trace", "trace_and_summarize"]


def _newest_trace(log_dir: str) -> Optional[str]:
    paths = glob.glob(os.path.join(log_dir, "**", "*.trace.json.gz"),
                      recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


def summarize_device_trace(log_dir: str, top: int = 5) -> Optional[Dict]:
    """Aggregate the newest trace under ``log_dir``.

    Returns ``{"top_ops": [(name, total_ms), ...], "device_busy_ms",
    "device_span_ms", "device_busy_pct", "device_lanes"}`` or None when
    the trace has no device lanes (CPU backend) or no trace exists."""
    path = _newest_trace(log_dir)
    if path is None:
        return None
    data = json.loads(gzip.open(path).read())
    events = data.get("traceEvents", [])
    # pid -> process name metadata; device lanes are "/device:..." (TPU)
    proc_names = {e.get("pid"): (e.get("args") or {}).get("name", "")
                  for e in events
                  if e.get("ph") == "M" and e.get("name") == "process_name"}
    device_pids = {pid for pid, name in proc_names.items()
                   if "/device:" in name and "CPU" not in name}
    if not device_pids:
        return None
    # a device pid carries OVERLAPPING thread lanes (module-level spans,
    # per-op events, step markers); summing them all double-counts — so
    # per pid keep the per-op lanes: every thread named "XLA Ops" or
    # "Stream ..." (genuinely concurrent lanes all count), falling back
    # to the single busiest lane when nothing is named
    thread_names: Dict[Tuple, str] = {
        (e.get("pid"), e.get("tid")): (e.get("args") or {}).get("name", "")
        for e in events
        if e.get("ph") == "M" and e.get("name") == "thread_name"}
    lane_busy: collections.Counter = collections.Counter()
    for e in events:
        if e.get("ph") == "X" and e.get("pid") in device_pids:
            lane_busy[(e.get("pid"), e.get("tid"))] += float(
                e.get("dur", 0.0))
    keep_lanes = set()
    for pid in device_pids:
        lanes = [k for k in lane_busy if k[0] == pid]
        if not lanes:
            continue
        named = [k for k in lanes
                 if any(t in thread_names.get(k, "").lower()
                        for t in ("xla ops", "stream"))]
        keep_lanes.update(named if named
                          else [max(lanes, key=lane_busy.__getitem__)])
    agg: collections.Counter = collections.Counter()
    t_min, t_max = float("inf"), 0.0
    busy = 0.0
    for e in events:
        if e.get("ph") != "X" or \
                (e.get("pid"), e.get("tid")) not in keep_lanes:
            continue
        dur = float(e.get("dur", 0.0))          # microseconds
        agg[e.get("name", "?")] += dur
        busy += dur
        ts = float(e.get("ts", 0.0))
        t_min = min(t_min, ts)
        t_max = max(t_max, ts + dur)
    span = max(t_max - t_min, 1e-9)
    return {
        "top_ops": [(name, round(dur / 1000.0, 3))
                    for name, dur in agg.most_common(top)],
        "device_busy_ms": round(busy / 1000.0, 3),
        "device_span_ms": round(span / 1000.0, 3),
        # busy sums the kept per-op lanes of every DEVICE; dividing by
        # span x device count makes an 8-chip mesh at full tilt read
        # ~100 (it can exceed 100 only through real intra-device lane
        # concurrency, e.g. overlapped GPU streams)
        "device_busy_pct": round(
            100.0 * busy / (span * len(device_pids)), 2),
        "device_lanes": sorted(proc_names[p] for p in device_pids),
    }


def trace_and_summarize(fn, log_dir: str, top: int = 5
                        ) -> Tuple[object, Optional[Dict]]:
    """Run ``fn()`` under a device trace rooted at a FRESH subdirectory
    of ``log_dir`` and summarize it. Returns (fn result,
    summary-or-None). The per-run subdirectory guarantees a run that
    writes no trace reports None instead of silently summarizing a
    previous run's files."""
    import tempfile

    import jax
    os.makedirs(log_dir, exist_ok=True)
    run_dir = tempfile.mkdtemp(prefix="run_", dir=log_dir)
    jax.profiler.start_trace(run_dir)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    return out, summarize_device_trace(run_dir, top=top)
