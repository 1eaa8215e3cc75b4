"""From a profiler trace to device seconds by the package's named scopes
(``transmogrifai_tpu.models.trees.SCOPES``): which phase of the tree kernels
the time of ``jit__fit_gbt`` and ``jit_batched`` went to, under names that
outlive a renumbering of the fusions. Beside ``reduce.py`` and by its rules
(``XLA Ops`` line only, self times, the traced window); it edits nothing.

Two layers, as there: :func:`load` turns the ``.xplane.pb`` into plain data,

    {"source": "c" | None, "marker": [start_ns, end_ns] | None,
     "host": [[name, start_ns, dur_ns], ...],      # train, search.* spans
     "devices": [{"device": n, "modules": [[name, start_ns, dur_ns], ...],
                  "ops": [[name, start_ns, dur_ns, scope_path], ...]}]}

and :func:`by_scope` reduces that. An op's ``scope_path`` is the ``op_name``
of its HLO instruction, ``jit(batched)/fg.gbt/vmap()/while/body/closed_call/
gbt.round/tree.route/gather``: scopes are path COMPONENTS (``vmap(fg.metric)``
where a transform wraps the first scope under it). A fused
instruction may carry several paths joined by ``;``: it is charged to the
first that holds a scope, and where the paths name different scopes its
seconds are also counted under ``disagree``. The paths after the first are
those of the instructions inside a fusion, nested fusions included.

The scope readers (``layer_metrics/fit_route_s.py`` and the like) share one
:func:`table` a process. A program found without one scoped op reads as
absent, never 0: its executable then came from a compile cache filled before
the scopes were added (the cache key is taken after debug info is stripped,
and a scope is only debug info; PERF.md section 3).
"""
from __future__ import annotations

import bisect
import functools
import glob
import os
import re
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

from benchmark.harness import DeviceTracer, say
from benchmark.trace.reduce import (DEVICE_PLANE, MODULE_LINE, OP_LINE,
                                    self_times, short_name)

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
MARKER = DeviceTracer.MARKER
METADATA_PLANE = "/host:metadata"
#: the profiler names an op by its whole HLO line, ``%fusion.5 = f32[...``
INSTRUCTION = re.compile(r"^%([\w.\-]+) = ")
WRAPPED = re.compile(r"^(?:\w+\()+(.*?)\)+$")     # jvp(vmap(tree.route))
NAME_CUT = 120                      # an op's name in the plain data
FAMILY_PREFIX = "fg."

Plain = Dict[str, Any]


def package_scopes() -> Tuple[str, ...]:
    """``trees.SCOPES``; empty where the package has none (a parent commit
    under this benchmark), so every scope reader then finds nothing."""
    from transmogrifai_tpu.models import trees
    return tuple(getattr(trees, "SCOPES", ()))


def newest_trace() -> Optional[str]:
    """The newest trace under ``.bench_work``: this run's, because a cell's
    directory is wiped at its start and the readers run after its window."""
    paths = glob.glob(os.path.join(ROOT, ".bench_work", "*", "trace", "**",
                                   "*.xplane.pb"), recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


def _varint(buf, at: int) -> Tuple[int, int]:
    value, shift = 0, 0
    while True:
        byte = buf[at]
        at += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, at
        shift += 7


def _fields(buf) -> Iterator[Tuple[int, Any]]:
    """(field number, value) of one serialized protobuf message: an int for
    a varint, a slice of ``buf`` for anything with a length. All that is
    needed to walk to the strings below without the ``.proto`` files."""
    at, end = 0, len(buf)
    while at < end:
        key, at = _varint(buf, at)
        wire = key & 7
        if wire == 0:
            value, at = _varint(buf, at)
        else:
            if wire == 2:
                size, at = _varint(buf, at)
            elif wire in (1, 5):
                size = 8 if wire == 1 else 4
            else:
                raise ValueError(f"protobuf wire type {wire}")
            value, at = buf[at:at + size], at + size
        yield key >> 3, value


def _text(value) -> str:
    return bytes(value).decode("utf-8", "replace")


def _module_op_names(hlo_proto) -> Dict[str, str]:
    """instruction name -> scope path, from one ``xla.HloProto``
    (``hlo_module`` = 1; in it ``computations`` = 3, each with ``id`` = 5 and
    ``instructions`` = 2; an instruction's ``name`` = 1, ``opcode`` = 2,
    ``metadata`` = 7 with ``op_name`` = 2, ``called_computation_ids`` = 38).
    A fusion's path is its own ``op_name``, then after ``;`` every other
    ``op_name`` among the instructions it fused."""
    instructions, fused = [], {}
    for number, module in _fields(hlo_proto):
        if number != 1:
            continue
        for number, computation in _fields(module):
            if number != 3:
                continue
            comp_id, inner = None, []
            for number, value in _fields(computation):
                if number == 5:
                    comp_id = value
                elif number == 2:
                    name = opcode = op_name = ""
                    called: List[int] = []
                    for number, v in _fields(value):
                        if number == 1:
                            name = _text(v)
                        elif number == 2:
                            opcode = _text(v)
                        elif number == 7:
                            op_name = next((_text(x) for n, x in _fields(v)
                                            if n == 2), "")
                        elif number == 38 and isinstance(v, int):
                            called.append(v)
                        elif number == 38:              # packed
                            at = 0
                            while at < len(v):
                                one, at = _varint(v, at)
                                called.append(one)
                    inner.append((name, op_name))
                    instructions.append((name, opcode, op_name, called))
            fused[comp_id] = inner
    calls = {name: called for name, opcode, _, called in instructions
             if opcode == "fusion"}

    def inside(called: List[int], seen: set) -> set:
        """Every ``op_name`` under these computations, nested fusions too."""
        found = set()
        for comp_id in called:
            if comp_id in seen:
                continue
            seen.add(comp_id)
            for inner_name, op_name in fused.get(comp_id, ()):
                found.add(op_name)
                found |= inside(calls.get(inner_name, []), seen)
        return found

    out = {}
    for name, opcode, op_name, called in instructions:
        paths = [op_name]
        if opcode == "fusion":
            paths += sorted(inside(called, set()) - {op_name, ""})
        out[name] = ";".join(paths)
    return out


def hlo_op_names(path: str) -> Dict[str, Dict[str, str]]:
    """``{"jit__fit_gbt(<id>)": {instruction: scope path}}`` for every program
    whose HLO the profiler kept: the ``/host:metadata`` plane of the XSpace
    (``planes`` = 1; a plane's ``name`` = 2 and ``event_metadata`` = 4, a map
    whose values (2) are ``XEventMetadata`` with ``name`` = 2 and ``stats``
    = 5, the one stat holding the ``HloProto`` as ``bytes_value`` = 6).
    ``jax.profiler.ProfileData`` shows that plane with no lines, so it is
    read off the wire."""
    with open(path, "rb") as fh:
        space = memoryview(fh.read())
    out: Dict[str, Dict[str, str]] = {}
    for number, plane in _fields(space):
        if number != 1:
            continue
        name, entries = "", []
        for number, value in _fields(plane):
            if number == 2:
                name = _text(value)
            elif number == 4:
                entries.append(value)
        if name != METADATA_PLANE:
            continue
        for entry in entries:
            for number, metadata in _fields(entry):
                if number != 2:
                    continue
                program, protos = "", []
                for number, value in _fields(metadata):
                    if number == 2:
                        program = _text(value)
                    elif number == 5:
                        protos += [v for n, v in _fields(value) if n == 6]
                for proto in protos:
                    out[program] = _module_op_names(proto)
    return out


def _covering(spans: Sequence[Tuple[int, int, str]], starts: Sequence[int],
              t_ns: int) -> Optional[str]:
    """Name of the (start, end, name) span, sorted by start, that holds
    ``t_ns``: the program an op ran in."""
    at = bisect.bisect_right(starts, t_ns) - 1
    return spans[at][2] if at >= 0 and t_ns < spans[at][1] else None


def load(path: str) -> Plain:
    """The device planes' programs and ops, each op with the scope path of
    its HLO instruction, and the harness's marker, as plain data."""
    import jax
    op_names = hlo_op_names(path)
    data = jax.profiler.ProfileData.from_file(path)
    devices: List[Dict[str, Any]] = []
    marker, host = None, []
    for plane in data.planes:
        match = DEVICE_PLANE.match(plane.name)
        if not match:
            for line in plane.lines:
                for event in line.events:
                    name = event.name
                    if marker is None and name == MARKER:
                        marker = [int(event.start_ns),
                                  int(event.start_ns + event.duration_ns)]
                    elif name == "train" or name.startswith("search."):
                        host.append([name, int(event.start_ns),
                                     int(event.duration_ns)])
            continue
        modules: List[List[Any]] = []
        raw_ops: List[Tuple[str, int, int]] = []
        for line in plane.lines:
            if line.name == MODULE_LINE:
                modules = [[e.name, int(e.start_ns), int(e.duration_ns)]
                           for e in line.events]
            elif line.name == OP_LINE:
                raw_ops = [(e.name, int(e.start_ns), int(e.duration_ns))
                           for e in line.events]
        spans = sorted((start, start + dur, name)
                       for name, start, dur in modules)
        starts = [s[0] for s in spans]
        ops = []
        for name, start, dur in raw_ops:
            names = op_names.get(_covering(spans, starts, start), {})
            instruction = INSTRUCTION.match(name)
            ops.append([name[:NAME_CUT], start, dur,
                        names.get(instruction.group(1), "")
                        if instruction else ""])
        devices.append({"device": int(match.group(1)), "modules": modules,
                        "ops": ops})
    devices.sort(key=lambda d: d["device"])
    return {"source": "c" if op_names else None, "marker": marker,
            "host": sorted(host, key=lambda e: e[1]), "devices": devices}


def components(path: str) -> List[str]:
    """The components of one ``op_name`` path, outermost first, each without
    the transforms JAX wraps around the first scope opened under them
    (``vmap(fg.metric)`` is the scope ``fg.metric``)."""
    out = []
    for component in path.strip().split("/"):
        wrapped = WRAPPED.match(component)
        out.append(wrapped.group(1) if wrapped else component)
    return out


def scopes_of(scope_path: str, scopes: Sequence[str]
              ) -> Tuple[Optional[str], Optional[str], bool, bool]:
    """(innermost component in ``scopes``, outermost ``fg.*`` component,
    whether the ``;``-joined paths disagree about the innermost, whether the
    scope is inherited: not on the op's own path, the first, but on that of
    an instruction fused into it) of one op."""
    found = []
    for at, part in enumerate(scope_path.split(";")):
        components_ = [c for c in components(part) if c in scopes]
        if components_:
            family = next((c for c in components_
                           if c.startswith(FAMILY_PREFIX)), None)
            found.append((components_[-1], family, at))
    if not found:
        return None, None, False, False
    return (found[0][0], found[0][1], len({f[0] for f in found}) > 1,
            found[0][2] > 0)


def by_scope(plain: Plain, scopes: Optional[Sequence[str]] = None
             ) -> Dict[str, Dict[str, Any]]:
    """Per program (``jit__fit_gbt``), over all devices and inside the
    marker: ``{"runs", "devices", "seconds", "by_scope": {scope: s},
    "by_family": {"fg.gbt": s}, "unscoped": s, "disagree": s, "inherited":
    s}``. Seconds are chip seconds of SELF time, so a ``while`` counts only
    what its body does not explain and a program's entries add up to
    ``seconds``. ``disagree`` and ``inherited`` say how much of the scoped
    time is a choice: fusions spanning scopes, and ops the compiler left
    without a path of their own (its batched scatter-adds), charged to the
    scope of what was fused into them."""
    scopes = package_scopes() if scopes is None else tuple(scopes)
    window = plain.get("marker")
    out: Dict[str, Dict[str, Any]] = {}

    def entry(program: str) -> Dict[str, Any]:
        return out.setdefault(program, {
            "runs": 0, "devices": 0, "seconds": 0.0, "by_scope": {},
            "by_family": {}, "unscoped": 0.0, "disagree": 0.0,
            "inherited": 0.0})

    for device in plain["devices"]:
        ops = device["ops"]
        if window is None:
            w0 = min((op[1] for op in ops), default=0)
            w1 = max((op[1] + op[2] for op in ops), default=0)
        else:
            w0, w1 = window
        modules = sorted((start, start + dur, short_name(name))
                         for name, start, dur in device["modules"])
        starts = [m[0] for m in modules]
        seen = set()
        for start, end, program in modules:
            if min(end, w1) - max(start, w0) > 0:
                entry(program)["runs"] += 1
                seen.add(program)
        for program in seen:
            out[program]["devices"] += 1
        inside = [(i, max(op[1], w0), min(op[1] + op[2], w1) - max(op[1], w0))
                  for i, op in enumerate(ops)
                  if op[1] < w1 and op[1] + op[2] > w0]
        for i, ns in self_times(inside).items():
            _, start, _, scope_path = ops[i]
            program = _covering(modules, starts, start)
            if program is None:
                continue                    # outside every program
            row, seconds = entry(program), ns / 1e9
            innermost, family, disagree, inherited = scopes_of(scope_path,
                                                               scopes)
            row["seconds"] += seconds
            if innermost is None:
                row["unscoped"] += seconds
            else:
                row["by_scope"][innermost] = (
                    row["by_scope"].get(innermost, 0.0) + seconds)
            if family is not None:
                row["by_family"][family] = (
                    row["by_family"].get(family, 0.0) + seconds)
            if disagree:
                row["disagree"] += seconds
            if inherited:
                row["inherited"] += seconds
    return out


def clock_offsets(host: Sequence[Sequence[Any]], records: Sequence[Dict]
                  ) -> Dict[str, List[float]]:
    """Per span name, profiler clock minus ``time.monotonic()`` in ns for
    every ``TraceAnnotation`` of the profile found again among the package's
    spans: the span of that name whose length is nearest, within 1 ms (the
    ring also holds the repetitions that ran outside the profiler). The
    harness places host spans on the device's clock by ONE such offset, the
    marker's; how far these scatter is how far the two placements of a span
    can disagree."""
    out: Dict[str, List[float]] = {}
    for name, start, dur in host:
        mine = [r for r in records
                if r["name"] == name and r.get("dur") is not None]
        if mine:
            nearest = min(mine, key=lambda r: abs(r["dur"] * 1e9 - dur))
            if abs(nearest["dur"] * 1e9 - dur) < 1e6:
                out.setdefault(name, []).append(start - nearest["t0"] * 1e9)
    return out


@functools.lru_cache(maxsize=1)
def table() -> Optional[Dict[str, Dict[str, Any]]]:
    """:func:`by_scope` of this run's trace, read once a process and said
    once; None where there is no trace."""
    path = newest_trace()
    if path is None:
        return None
    plain = load(path)
    result = by_scope(plain)
    from transmogrifai_tpu.observability import trace as package_trace
    offsets = clock_offsets(plain.get("host", ()), package_trace.spans())
    if offsets:
        every = [o for values in offsets.values() for o in values]
        trains = offsets.get("train", [])
        say(f"{len(every)} package spans are TraceAnnotations in the profile "
            f"({ {k: len(v) for k, v in offsets.items()} }); profiler clock "
            f"minus time.monotonic() scatters over "
            f"{(max(every) - min(every)) / 1e3:.1f} us, over "
            f"{(max(trains) - min(trains)) / 1e3 if trains else 0.0:.1f} us "
            f"for `train`: the marker shift places a span that far from "
            f"where its annotation sits")
    say(f"device seconds by scope (source {plain['source']}, "
        f"{os.path.relpath(path, ROOT)})"
        + (":" if result else ": no device plane in this trace"))
    for program, row in sorted(result.items(),
                               key=lambda kv: -kv[1]["seconds"])[:6]:
        scoped = row["seconds"] - row["unscoped"]
        share = scoped / row["seconds"] if row["seconds"] else 0.0
        say(f"  {program}: {row['seconds']:.4f} chip s in {row['runs']} runs "
            f"on {row['devices']} device(s), scoped share {share:.4f}, "
            f"disagree {row['disagree']:.4f} s, inherited "
            f"{row['inherited']:.4f} s; by scope "
            f"{ {k: round(v, 4) for k, v in sorted(row['by_scope'].items())} }"
            f"; by family "
            f"{ {k: round(v, 4) for k, v in sorted(row['by_family'].items())} }"
            f"; unscoped {row["unscoped"]:.4f}")
    return result


@functools.lru_cache(maxsize=None)
def _say_once(msg: str) -> None:
    say(msg)


def seconds_per_run(obs: Dict[str, Any], program: str, scope: str
                    ) -> Optional[float]:
    """What a scope reader returns: chip seconds of ``scope`` inside
    ``program`` per run of it on every device (a fit; a train), summed over
    the chips: over runs / devices of the trace, as ``fold_grid_roofline.py``
    counts trains. On four chips that makes up for the mesh's first device,
    where the profiler records no ``XLA Modules`` event for the sharded
    program and names its ops ``region.N``: three runs on four devices are
    three quarters of a train. None without a traced window, without the
    program, without the scope, and where the program shows no package scope
    at all."""
    devices = len((obs.get("trace") or {}).get("devices", ()))
    row = (table() or {}).get(program) if devices else None
    if not row or not row["runs"]:
        return None
    if not row["by_scope"]:
        _say_once(f"{program}: no package scope in this trace: executable "
                  f"from a cache filled before the scopes?")
        return None
    if scope not in row["by_scope"]:
        return None
    return row["by_scope"][scope] / (row["runs"] / devices)
