"""What every job shares: the device check, the compile counter, the closed
loop, the profiler window and the result line.

A job (``benchmark/jobs/<kind>.py``) does its set-up, hands a repetition to
:func:`closed_loop`, checks its outputs after the window and returns an
:class:`Outcome`. ``run.py`` turns that into the last line of stdout.
Nothing here knows a cell, a configuration or a metric by name.
"""
from __future__ import annotations

import dataclasses
import glob
import importlib
import json
import os
import statistics
import time
import traceback
from typing import Any, Callable, Dict, List, Optional

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def say(msg: str) -> None:
    print(f"bench: {say.prefix}{msg}", flush=True)


say.prefix = ""


class BenchFailure(Exception):
    """The run cannot produce a result line (no chip, a broken set-up)."""


@dataclasses.dataclass
class Context:
    """One run of one cell, as ``run.py`` resolved it."""
    cell: Dict[str, Any]            # the entry of BENCHMARK.json "workloads"
    config: Dict[str, Any]          # the configuration's JSON file
    config_module: Any              # benchmark.configs.<name>
    traffic: Dict[str, Any]         # the traffic mix's JSON file
    seed: int
    seconds: float
    trace: bool
    dry_run: bool                   # --cpu-dry-run tiny: CPU, tiny sizes
    work_dir: str                   # everything the run writes
    t_start: float                  # perf_counter() at process start

    def size(self, key: str) -> Any:
        """A traffic parameter, at its ``tiny`` value in a dry run."""
        if self.dry_run and key in self.traffic.get("tiny", {}):
            return self.traffic["tiny"][key]
        return self.traffic[key]


@dataclasses.dataclass
class Outcome:
    """What a job hands back."""
    metrics: Dict[str, float]               # end-to-end, by metric name
    attempted: int
    failed: int
    problems: List[str]                     # empty means correct
    observations: Dict[str, Any]            # what the per-layer readers read


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------

def require_devices(ctx: Context) -> Dict[str, Any]:
    """Open the backend and refuse anything but the chips the cell asks for.
    There is no fallback: a run off the chip ends here, unless it is the
    labelled dry run."""
    import jax
    devices = jax.devices()
    block = {"platform": devices[0].platform, "kind": devices[0].device_kind,
             "count": len(devices)}
    say(f"platform: {block['platform']}  device_kind: {block['kind']}  "
        f"device_count: {block['count']}  jax: {jax.__version__}")
    want = "cpu" if ctx.dry_run else "tpu"
    if block["platform"] != want:
        raise BenchFailure(f"this run needs platform {want!r}, JAX reports "
                           f"{block['platform']!r}")
    if block["count"] != ctx.cell["chips"]:
        raise BenchFailure(f"the cell asks for {ctx.cell['chips']} chip(s), "
                           f"JAX sees {block['count']}")
    if jax.config.jax_enable_x64:
        raise BenchFailure("the chip path is float32: x64 must be off")
    return block


def memory_peak_bytes() -> Optional[int]:
    """Peak HBM held on the fullest device, as the backend reports it: the
    allocator's ``peak_bytes_in_use`` (live arrays) plus its
    ``peak_bytes_reserved`` (the region the TPU runtime reserves for the
    temporaries of the programs it runs, which ``bytes_in_use`` leaves out:
    a 1 GiB sort moved ``reserved`` by 3.2 GB and ``in_use`` not at all,
    PERF.md PR 24). None where the backend reports nothing, as on CPU."""
    import jax
    peaks = []
    for device in jax.devices():
        stats = device.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(stats["peak_bytes_in_use"]
                         + stats.get("peak_bytes_reserved", 0))
    return max(peaks) if peaks else None


def load_peaks(device_kind: str) -> Dict[str, float]:
    """The chip's published peaks. An unknown device is an error."""
    path = os.path.join(os.path.dirname(__file__), "peaks.json")
    with open(path) as fh:
        table = json.load(fh)["devices"]
    if device_kind not in table:
        raise BenchFailure(f"no peaks for device_kind {device_kind!r} in "
                           f"{path}: add them with their source")
    return table[device_kind]


# ---------------------------------------------------------------------------
# compiles
# ---------------------------------------------------------------------------

class CompileWatch:
    """Counts XLA backend compiles (a load from the persistent cache is one
    too: either way a new executable entered the process) and their seconds,
    from ``jax.monitoring``. ``mark()`` returns the totals so far."""

    def __init__(self):
        import jax.monitoring
        self.count, self.seconds = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if event == BACKEND_COMPILE_EVENT:
            self.count += 1
            self.seconds += duration

    def mark(self) -> tuple:
        return self.count, self.seconds


# ---------------------------------------------------------------------------
# the profiler window
# ---------------------------------------------------------------------------

class DeviceTracer:
    """``jax.profiler`` around a few repetitions of the window. One
    ``TraceAnnotation`` spans them: its start and length on the profiler's
    clock are the traced window, and its start on ``time.monotonic()`` is what
    host spans are aligned by."""

    MARKER = "bench.traced_window"

    def __init__(self, out_dir: str):
        self.out_dir = out_dir
        self.mono_start: Optional[float] = None
        self._annotation = None

    def start(self) -> None:
        import jax
        options = jax.profiler.ProfileOptions()
        # the Python tracer records every call of the host code and slows it
        # severalfold; the device lanes and TraceAnnotations are enough
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        jax.profiler.start_trace(self.out_dir, profiler_options=options)
        self._annotation = jax.profiler.TraceAnnotation(self.MARKER)
        self._annotation.__enter__()
        self.mono_start = time.monotonic()

    def stop(self) -> None:
        import jax
        self._annotation.__exit__(None, None, None)
        jax.profiler.stop_trace()

    def summary(self, host_spans: List[tuple]) -> Optional[Dict[str, Any]]:
        """The reduced trace (``benchmark/trace/reduce.py``), or None when
        the profiler wrote no file."""
        from benchmark.trace import reduce as trace_reduce
        paths = glob.glob(os.path.join(self.out_dir, "**", "*.xplane.pb"),
                          recursive=True)
        if not paths:
            return None
        planes = trace_reduce.load_xplane(max(paths, key=os.path.getmtime))
        summary = trace_reduce.reduce(planes, self.MARKER, host_spans,
                                      self.mono_start)
        say(f"traced window {summary['window_s']:.4f} s; busy seconds by "
            f"device: {[round(d['busy_s'], 4) for d in summary['devices']]}; "
            f"idle share: {trace_reduce.idle_share(summary)}; [program, "
            f"seconds, runs]: {summary['programs']}")
        return summary


# ---------------------------------------------------------------------------
# the closed loop
# ---------------------------------------------------------------------------

def closed_loop(ctx: Context, fresh: Callable[[], Any],
                work: Callable[[Any], Dict[str, Any]],
                tracer: Optional[DeviceTracer], trace_reps: int,
                spans: List[tuple]) -> List[Dict[str, Any]]:
    """One client, back to back, for ``ctx.seconds``: ``fresh()`` makes a
    repetition's inputs (untimed), ``work(inputs)`` is the timed call and
    returns what it observed. A repetition that has started runs to its end,
    so the loop may outlast the window by one repetition. With a tracer, the
    second repetition onwards runs under the profiler for ``trace_reps``
    repetitions, whatever the window says. A repetition that raises is
    recorded as failed; two in a row end the loop."""
    reps: List[Dict[str, Any]] = []
    traced_left = trace_reps if tracer is not None else 0
    tracing = False
    t_open = time.monotonic()
    while True:
        elapsed = time.monotonic() - t_open
        if elapsed >= ctx.seconds and not traced_left and not tracing:
            break
        if traced_left and not tracing and reps:
            tracer.start()
            tracing = True
        t_f = time.monotonic()
        inputs = fresh()
        t0 = time.monotonic()
        rep: Dict[str, Any] = {"traced": tracing}
        try:
            rep.update(work(inputs))
            rep["ok"] = True
        except Exception:      # the boundary that must keep the loop running
            rep["ok"] = False
            say("repetition failed:\n" + traceback.format_exc())
        t1 = time.monotonic()
        rep["seconds"] = t1 - t0
        spans.append(("bench.fresh_inputs", t_f, t0))
        spans.append(("bench.repetition", t0, t1))
        reps.append(rep)
        del inputs
        if tracing:
            traced_left -= 1
            if not traced_left:
                tracer.stop()
                tracing = False
        if len(reps) >= 2 and not reps[-1]["ok"] and not reps[-2]["ok"]:
            break
    if tracing:
        tracer.stop()
    return reps


@dataclasses.dataclass
class Window:
    """The measured window of one run: its repetitions and what was counted
    around them."""
    reps: List[Dict[str, Any]]
    setup_s: float                  # process start to the window's start
    setup_compiles: tuple           # (count, seconds) before the window
    window_compiles: tuple          # (count, seconds) inside it
    tracer: Optional[DeviceTracer]

    @property
    def seconds(self) -> Optional[float]:
        """Median wall seconds of the repetitions that succeeded. In a traced
        run the profiled repetitions are slower and are left out where others
        exist."""
        ok = [r for r in self.reps if r["ok"]]
        plain = [r["seconds"] for r in ok if not r["traced"]]
        seconds = plain or [r["seconds"] for r in ok]
        return statistics.median(seconds) if seconds else None

    @property
    def failed(self) -> int:
        return sum(not r["ok"] for r in self.reps)

    def rate(self, work: float) -> float:
        """``work`` units per median repetition; 0 when none completed."""
        return work / self.seconds if self.seconds else 0.0

    def outcome(self, metrics: Dict[str, float], problems: List[str],
                spans: List[tuple], **observed: Any) -> "Outcome":
        """The job's result: its own metrics, problems and observations, with
        what every window has (``setup_s``, the counts, the compiles, the
        reduced trace) filled in."""
        mine = []
        if self.seconds is None:
            mine.append("no repetition completed in the window")
        if self.window_compiles[0]:
            mine.append(f"{self.window_compiles[0]} compiles inside the "
                        f"measured window")
        return Outcome(
            metrics=dict(metrics, setup_s=self.setup_s),
            attempted=len(self.reps), failed=self.failed,
            problems=mine + problems,
            observations=dict(
                observed, reps=self.reps, median_seconds=self.seconds,
                setup_compiles=self.setup_compiles,
                window_compiles=self.window_compiles,
                trace=self.tracer.summary(spans) if self.tracer else None))


def run_window(ctx: Context, watch: CompileWatch, fresh: Callable[[], Any],
               work: Callable[[Any], Dict[str, Any]], spans: List[tuple]
               ) -> Window:
    """Everything before this call was set-up. Opens the window, runs the
    closed loop and prints the sample behind the median."""
    setup = watch.mark()
    tracer = DeviceTracer(os.path.join(ctx.work_dir, "trace")) if ctx.trace \
        else None
    t_open = time.perf_counter()
    reps = closed_loop(ctx, fresh, work, tracer, ctx.size("trace_reps"),
                       spans)
    after = watch.mark()
    say("repetition seconds: " + " ".join(
        f"{r['seconds']:.4f}{'T' if r['traced'] else ''}"
        f"{'' if r['ok'] else '!'}" for r in reps)
        + f"  (n={len(reps)}, T = under the profiler, ! = failed)")
    return Window(reps=reps, setup_s=t_open - ctx.t_start,
                  setup_compiles=setup,
                  window_compiles=(after[0] - setup[0], after[1] - setup[1]),
                  tracer=tracer)


# ---------------------------------------------------------------------------
# the result line
# ---------------------------------------------------------------------------

def layer_metrics(spec: Dict[str, Any], cell_name: str,
                  observations: Dict[str, Any]) -> Dict[str, Dict[str, Any]]:
    """Every per-layer metric of BENCHMARK.json that belongs to this cell,
    read by ``benchmark/layer_metrics/<name>.py``. A reader that finds
    nothing to read returns None and its metric is left out."""
    end_to_end = {m["name"] for m in spec["end_to_end"]
                  if cell_name in m.get("workloads", [cell_name])}
    out: Dict[str, Dict[str, Any]] = {}
    for metric in spec["per_layer"]:
        if cell_name not in metric.get("workloads", [cell_name]):
            continue
        if metric["moves"] not in end_to_end:
            continue
        reader = importlib.import_module(
            f"benchmark.layer_metrics.{metric['name']}")
        value = reader.read(observations)
        if value is not None:
            out[metric["name"]] = {"value": float(value),
                                   "unit": metric["unit"]}
    return out


def result_line(spec: Dict[str, Any], ctx: Context, device: Dict[str, Any],
                outcome: Outcome) -> Dict[str, Any]:
    cell_name = ctx.cell["name"]
    device = dict(device, memory_peak_bytes=memory_peak_bytes())
    line: Dict[str, Any] = {"correct": not outcome.problems,
                            "attempted": outcome.attempted,
                            "failed": outcome.failed}
    trace = outcome.observations.get("trace")
    if ctx.trace:
        line["metrics"] = layer_metrics(spec, cell_name, outcome.observations)
        if trace is not None and trace["devices"]:
            device["busy_s"] = statistics.fmean(
                d["busy_s"] for d in trace["devices"])
            device["window_s"] = trace["window_s"]
            line["breakdown"] = {"device_ops": trace["top_ops"][:10],
                                 "idle_gaps": trace["idle_gaps"][:10]}
    else:
        units = {m["name"]: m["unit"] for m in spec["end_to_end"]}
        line["metrics"] = {name: {"value": float(value), "unit": units[name]}
                           for name, value in outcome.metrics.items()}
    line["device"] = device
    return line
