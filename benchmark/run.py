#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Resolves the cell to its configuration (``benchmark/configs/``), traffic mix
(``benchmark/traffic/``) and job kind (``benchmark/jobs/``) by name, runs the
job in this one process (one process per chip) and prints the result as the
last line of stdout. Without a TPU, or with another number of chips than the
cell asks for, it exits non-zero and prints no result. ``--cpu-dry-run tiny``
rehearses the control flow on the CPU at a tiny size: every line is labelled
``platform: cpu`` and no result line is printed. See ``benchmark/README.md``.
"""
from __future__ import annotations

import time

_T_START = time.perf_counter()      # process start, as near as Python sees it

import argparse      # noqa: E402
import importlib     # noqa: E402
import json          # noqa: E402
import os            # noqa: E402
import shutil        # noqa: E402
import sys           # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: everything a run writes besides the compile cache: the (empty) profile
#: store and audit cache the package is pointed at, and the profiler's
#: trace. Listed in .gitignore; a cell's directory is wiped at its start.
WORK = os.path.join(ROOT, ".bench_work")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None,
                    help="length of the measured window (default: "
                         "run_seconds of BENCHMARK.json)")
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    ap.add_argument("--cpu-dry-run", choices=["tiny"], default=None)
    args = ap.parse_args()
    dry_run = args.cpu_dry_run is not None

    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        spec = json.load(fh)
    cell = next((w for w in spec["workloads"]
                 if w["name"] == args.workload), None)
    if cell is None:
        print(f"bench: no workload {args.workload!r} in BENCHMARK.json",
              file=sys.stderr)
        return 2
    config_entry = next(c for c in spec["configs"]
                        if c["name"] == cell["config"])
    with open(os.path.join(ROOT, config_entry["file"])) as fh:
        config = json.load(fh)
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           cell["traffic"] + ".json")) as fh:
        traffic = json.load(fh)

    work_dir = os.path.join(WORK, cell["name"])
    shutil.rmtree(work_dir, ignore_errors=True)
    os.makedirs(work_dir)
    # before the package is imported: it reads these at import or first use.
    # The profile store steers the package's tuning, so every run starts from
    # an empty one at a stated path; the compile cache is the package's own
    # fixed <checkout>/.jax_cache unless JAX_COMPILATION_CACHE_DIR is set.
    os.environ["TX_PROFILE_STORE"] = os.path.join(work_dir,
                                                  "profile_store.json")
    os.environ["TX_AUDIT_CACHE"] = os.path.join(work_dir, "audit_cache.json")
    os.environ.pop("JAX_ENABLE_X64", None)
    os.environ.pop("TX_TRACE", None)
    if dry_run:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={cell['chips']}")
    sys.path.insert(0, ROOT)

    from benchmark import harness
    from transmogrifai_tpu.utils.jax_setup import enable_compilation_cache
    if dry_run:
        harness.say.prefix = "platform: cpu | "
    ctx = harness.Context(
        cell=cell, config=config, traffic=traffic, seed=args.seed,
        config_module=importlib.import_module(
            f"benchmark.configs.{cell['config']}"),
        seconds=args.seconds if args.seconds is not None
        else float(spec["run_seconds"]),
        trace=bool(args.trace), dry_run=dry_run, work_dir=work_dir,
        t_start=_T_START)
    job = importlib.import_module(f"benchmark.jobs.{traffic['job']}")
    try:
        enable_compilation_cache()
        device = harness.require_devices(ctx)
        outcome = job.run(ctx, harness.CompileWatch())
    except harness.BenchFailure as e:
        harness.say(f"FAIL: {e}")
        return 4
    outcome.observations.update(device_kind=device["kind"],
                                platform=device["platform"])
    for problem in outcome.problems:
        harness.say(f"NOT CORRECT: {problem}")
    line = harness.result_line(spec, ctx, device, outcome)
    harness.say("end-to-end: " + json.dumps(outcome.metrics)
                + f"  peak HBM bytes: {line['device']['memory_peak_bytes']}")
    if dry_run:
        harness.say("dry run, no result line; it would have been: "
                    + json.dumps(line))
        return 0 if not outcome.problems else 1
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
