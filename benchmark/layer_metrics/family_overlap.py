"""Layer: search. How far the families' fold-grid programs overlap ON THE
CHIPS: the sum of the seconds of ``jit_forest_batched``, ``jit_batched`` and
``jit_linear_batched`` in the traced window over the seconds at least one of
them was running, from the trace's ``XLA Modules`` lanes, the devices pooled.
1.0 = one family's program at a time (what one chip does with the four
dispatch threads: it serializes them); 2.0 = two running all the time. The
host threads' own seconds say nothing here: a thread that waits for the chip
counts as busy. None without a traced window or without the forest's name
(the parent of PR 28)."""
from benchmark.harness import DeviceTracer
from benchmark.layer_metrics.pool_forest_s import FOREST, GBT, LINEAR
from benchmark.trace import reduce, scopes


def overlap(planes, marker=DeviceTracer.MARKER, programs=(FOREST, GBT, LINEAR)):
    """(sum of the programs' intervals) / (their union), or None when the
    forest's program never ran; intervals are clipped to the marker."""
    window = reduce.find_marker(planes, marker)
    total, together, seen = 0, 0, set()
    for plane in planes:
        if not reduce.DEVICE_PLANE.match(plane["name"]):
            continue
        intervals = []
        for line in plane["lines"]:
            if line["name"] != reduce.MODULE_LINE:
                continue
            for name, start, dur in line["events"]:
                name, end = reduce.short_name(name), start + dur
                if window is not None:
                    start, end = max(start, window[0]), min(end, window[1])
                if name in programs and end > start:
                    intervals.append((start, end))
                    seen.add(name)
        total += sum(end - start for start, end in intervals)
        together += sum(end - start for start, end in reduce.union(intervals))
    return total / together if FOREST in seen and together else None


def read(obs):
    if not (obs.get("trace") or {}).get("devices"):
        return None
    path = scopes.newest_trace()
    return overlap(reduce.load_xplane(path)) if path else None
