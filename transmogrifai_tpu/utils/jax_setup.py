"""Process-level JAX configuration helpers.

The selector's hyperparameter grids span several static shapes (tree
depth, forest size, fold sizes), each costing an XLA compile. The
persistent compilation cache amortizes those compiles across processes
— the same mechanism production JAX training jobs use.
:func:`enable_compilation_cache` runs once where a process starts
using JAX: ``Workflow.train``, ``ScoringPlan.compile``, the CLI entry
point, the benchmark (``benchmark/run.py``) and the examples all call it, and it is idempotent.
"""
from __future__ import annotations

import os

__all__ = ["enable_compilation_cache", "backend_block", "device_trace",
           "with_frame_room"]


def device_trace(log_dir: str):
    """Context manager around ``jax.profiler`` tracing: per-op device
    timelines viewable in TensorBoard/Perfetto — the accelerator-level
    profile the reference leaves to the Spark UI (aux SURVEY §5.5).

    >>> with device_trace("/tmp/trace"):
    ...     model = workflow.train()
    """
    import contextlib

    import jax

    @contextlib.contextmanager
    def _trace():
        jax.profiler.start_trace(log_dir)
        try:
            yield log_dir
        finally:
            jax.profiler.stop_trace()
    return _trace()

#: the fixed in-checkout cache directory (listed in .gitignore). Fixed
#: on purpose: the directory is part of the cache key, so a path that
#: moves with the host or the process never hits.
_DEFAULT_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def _machine_fingerprint() -> str:
    """Hash of the CPU capability set, for ``artifacts/store.env_stamp``:
    XLA:CPU AOT executables are machine-feature-specific, and loading
    one compiled for a different microarchitecture can SIGILL
    (cpu_aot_loader warns exactly this)."""
    import hashlib
    import platform
    ident = platform.machine()
    try:
        with open("/proc/cpuinfo") as fh:
            for line in fh:
                if line.startswith("flags"):
                    ident += ",".join(sorted(line.split(":")[1].split()))
                    break
    except OSError:
        pass
    return hashlib.sha1(ident.encode()).hexdigest()[:12]


def _cache_dir() -> str:
    """The placement rule: ``JAX_COMPILATION_CACHE_DIR`` when set,
    else the fixed in-checkout directory."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or _DEFAULT_CACHE


def enable_compilation_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its
    directory. When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
    itself and NO directory is set in code; otherwise the fixed
    ``<checkout>/.jax_cache`` is used, so every process started from
    one checkout (trainer, then server) shares one cache. Idempotent."""
    import jax
    from . import compile_time
    compile_time.install()      # counts the cache's hits and misses
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        os.makedirs(_DEFAULT_CACHE, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", _DEFAULT_CACHE)
    return _cache_dir()


def backend_block() -> dict:
    """What this process actually runs on, as JAX reports it — the
    ``backend`` slice of the serving metrics and the first thing
    ``chip_smoke.py`` prints. Initializes the backend if nothing has."""
    import jax
    from . import compile_time
    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
        "jax": jax.__version__,
        "x64": bool(jax.config.jax_enable_x64),
        "compile_cache": {"dir": _cache_dir(),
                          **compile_time.cache_counts()},
    }


#: slots of the frame that :func:`with_frame_room` pushes: just over half a
#: MiB, so that CPython rounds its chunk up to one MiB and leaves the callees
#: the other half
_ROOMY_SLOTS = 66_000
_ROOMY: dict = {}


def with_frame_room(fn):
    """``fn()``, called from a frame so large that the interpreter gives it a
    chunk of the frame stack of its own, with half a MiB of room below it.

    CPython 3.11 and 3.12 keep a thread's frames in chunks of 16 KiB, take a
    new chunk where a call does not fit the current one and give it back
    when that call returns. A loop of small calls that sits right at a
    chunk's end maps and unmaps memory on every call: 180 times slower here
    (a function of one return, called 100,000 times at every depth: once in
    270 depths), and dearer still under a sandboxed kernel, where those are
    two system calls. Tracing a fold-grid program is a quarter of a million
    such calls at depths of 150 to 300 frames, so whether a program's trace
    takes 3 s or 14 s on the chip machine is decided by how deep the stack
    happens to be where it starts: what made the same boosted program trace
    in 10 s on the main thread and in 3 s on a family thread (PERF.md
    section 6, PR 36), and the other way round one ``lax.map`` deeper
    (PR 37). Below this frame no call meets a chunk's end for some 1,500
    frames. The frame costs its 66,000 empty slots, 0.3 ms a call, and
    0.2 s once a process to make."""
    roomy = _ROOMY.get("fn")
    if roomy is None:
        names = ", ".join(f"v{i}" for i in range(_ROOMY_SLOTS))
        scope: dict = {}
        exec(compile(f"def roomy(fn):\n    if fn is None:\n        {names} = fn"
                     "\n    return fn()\n", "<with_frame_room>", "exec"), scope)
        roomy = _ROOMY["fn"] = scope["roomy"]
    return roomy(fn)
