"""Layer: process_setup. What the ``setup_*`` readers share: the set-up's
records of the package's compile log (``transmogrifai_tpu.utils.compile_time
.compile_log()``: one record a program that entered the process, with its
trace, lowering and backend seconds, the thread that paid, and whether the
persistent cache served it; docs/observability.md "Set-up by program").

The set-up's records are the first N of the log, N the harness's own count of
backend events before the window (``obs["setup_compiles"][0]``): the package
installs its listener in ``enable_compilation_cache()``, before ``run.py``
makes the harness's ``CompileWatch``, and nothing compiles between the two.
They are the same events, so the records' ``backend_s`` must add up to the
harness's seconds (``compile_s``) within 0.5 %, with nothing dropped from the
bounded log; where either fails, and where the package keeps no log (a parent
commit under this benchmark), every reader returns None and one line says why.
"""
import functools

from benchmark.harness import say
from benchmark.trace.reduce import union

SPENT = ("trace_s", "lower_s", "backend_s")
TOLERANCE = 0.005


@functools.lru_cache(maxsize=1)
def cut(setup_compiles):
    """The set-up's records, checked against the harness's ``(count,
    seconds)`` and said once a process; None where they cannot be told."""
    from transmogrifai_tpu.utils import compile_time
    if not hasattr(compile_time, "compile_log"):
        say("set-up by program: the package keeps no compile log")
        return None
    count, seconds = setup_compiles
    records = compile_time.compile_log()[:count]
    backend = sum(r["backend_s"] for r in records)
    dropped = compile_time.compile_log_dropped()
    if dropped or len(records) != count \
            or abs(backend - seconds) > TOLERANCE * seconds:
        say(f"set-up by program: NOT READ: the log dropped {dropped} records "
            f"and holds {len(records)} of the harness's {count} set-up "
            f"programs, backend seconds {backend:.4f} against its "
            f"{seconds:.4f}")
        return None
    say("set-up by program: [program, thread, trace_s, lower_s, backend_s, "
        "cache] " + str([
            [r["program"], r["thread"]] + [round(r[k], 3) for k in SPENT]
            + [r["cache"]] for r in sorted(
                records, key=lambda r: -sum(r[k] for k in SPENT))[:8]]))
    return records


def records(obs):
    return cut(tuple(obs["setup_compiles"]))


def total(obs, fields, cache=None):
    """The sum of ``fields`` over the set-up's records (those the cache
    served as ``cache`` says, where it says), or None."""
    cut_records = records(obs)
    if cut_records is None:
        return None
    return sum(r[k] for r in cut_records for k in fields
               if cache in (None, r["cache"]))


def overlap(obs):
    """The sum of the records' ``t1 - t0`` over the length of the union of
    their ``[t0, t1]``, or None (also where no program entered)."""
    spans = [(r["t0"], r["t1"]) for r in records(obs) or ()]
    together = sum(end - start for start, end in union(spans))
    return sum(end - start for start, end in spans) / together \
        if together else None
