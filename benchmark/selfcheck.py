#!/usr/bin/env python3
"""The benchmark's own check, on the CPU, in about a minute:

    python3 benchmark/selfcheck.py

1. BENCHMARK.json keeps the contract's limits and names nothing that is
   missing: every cell has its configuration, traffic file and job, every
   per-layer metric its reader and a layer that is a row of PERF.md.
2. ``trace/reduce.py`` on the recorded trace beside it: busy seconds, idle
   share, idle gaps and top operations are pinned.
3. ``reference/gbt_plain.py`` against the package's ``GBTClassifier`` at a
   small size (a whole fit, and a masked fold deep enough for the node cap),
   the plain fold rule, and the plain metrics on cases worked by hand.

It measures nothing: no number it prints is a speed. Exit code 0 means every
check held.
"""
from __future__ import annotations

import importlib
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "benchmark")
FAILED = []


def check(ok: bool, what: str) -> None:
    print(("ok    " if ok else "FAIL  ") + what, flush=True)
    if not ok:
        FAILED.append(what)


NAME = re.compile(r"[A-Za-z0-9][A-Za-z0-9_.-]{0,63}\Z")
# the driver's rule for a layer, as its refusal of PR 24's first attempt
# stated it ("process set-up" was refused)
LAYER = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}\Z")
PLAIN_PATH = re.compile(r"[A-Za-z0-9_./-]{1,200}\Z")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def check_limits(spec: dict) -> None:
    """The limits the contract puts on BENCHMARK.json itself, which the
    driver checks before any run."""
    check(set(spec) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"},
          "BENCHMARK.json has exactly the contract's keys")
    check(os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 << 10
          and 1 <= len(spec["paths"]) <= 16 and len(spec["command"]) <= 32
          and all(PLAIN_PATH.match(p) and ".." not in p and p[0] != "/"
                  for p in spec["paths"]), "size, paths and command")
    cells = spec["workloads"]
    check(1 <= len(spec["configs"]) <= 24 and 2 <= len(cells) <= 24
          and 1 <= len(spec["end_to_end"]) <= 16
          and 1 <= len(spec["per_layer"]) <= 128, "counts of entries")
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in spec[kind]]
        check(all(NAME.match(n) for n in names)
              and len(set(names)) == len(names), f"{kind}: plain names, once")
    for entry in spec["configs"] + cells:
        check(len(entry["why"]) <= 200, f"{entry['name']}: why <= 200 chars")
    for config in spec["configs"]:
        check(PLAIN_PATH.match(config["file"]) and any(
            config["file"].startswith(p + "/") for p in spec["paths"])
            and any(c["config"] == config["name"] for c in cells),
            f"{config['name']}: file under paths, used by a cell")
    chips4 = sum(c["chips"] == 4 for c in cells)
    check(all(c["chips"] in (1, 4) for c in cells)
          and chips4 <= max(1, len(cells) // 4)
          and len({(c["config"], c["traffic"]) for c in cells}) == len(cells),
          "chips 1 or 4, four-chip share, (config, traffic) pairs once")
    for metric in spec["end_to_end"]:
        check(0 < metric["bound"] <= 0.1
              and metric["source"] in ("host_clock", "device_trace")
              and metric["better"] in ("higher", "lower"),
              f"{metric['name']}: bound and source")
    check(any(m["name"] == "setup_s" and "workloads" not in m
              for m in spec["end_to_end"]), "setup_s in every cell")
    for metric in spec["per_layer"]:
        check(bool(LAYER.match(metric["layer"]))
              and metric["source"] in SOURCES and "bound" not in metric
              and metric["better"] in ("higher", "lower"),
              f"{metric['name']}: layer {metric['layer']!r} is a plain name")
    seconds = spec["run_seconds"]
    check(isinstance(seconds, int) and 1 <= seconds <= 51
          and (2 + 14 * 24) * (seconds + 60) + 24 * 180 + 1200 <= 43200,
          "run_seconds fits a full check of 24 cells")
    # every cell: setup_s, another end-to-end metric, a per-layer metric; a
    # per-layer metric only where the metric it moves is
    names = {c["name"] for c in cells}
    where = {m["name"]: set(m.get("workloads", names))
             for m in spec["end_to_end"] + spec["per_layer"]}
    for cell in names:
        check(any(cell in where[m["name"]] for m in spec["end_to_end"]
                  if m["name"] != "setup_s")
              and any(cell in where[m["name"]] for m in spec["per_layer"]),
              f"{cell}: an end-to-end metric besides setup_s, and a layer's")
    for metric in spec["per_layer"]:
        check(where[metric["name"]] <= where.get(metric["moves"], set()),
              f"{metric['name']}: only where {metric['moves']} is")
    perf_md = os.path.join(ROOT, "PERF.md")      # absent in a bare checkout
    perf = open(perf_md).read() if os.path.isfile(perf_md) else None
    for layer in sorted({m["layer"] for m in spec["per_layer"]}):
        check(perf is None or f"| `{layer}` " in perf,
              f"layer {layer} is a row of PERF.md")


def check_spec() -> None:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        spec = json.load(fh)
    check_limits(spec)
    configs = {c["name"]: c for c in spec["configs"]}
    end_to_end = {m["name"] for m in spec["end_to_end"]}
    cells = {w["name"] for w in spec["workloads"]}
    for cell in spec["workloads"]:
        entry = configs.get(cell["config"])
        check(entry is not None and os.path.isfile(
            os.path.join(ROOT, entry["file"])),
            f"{cell['name']}: configuration file")
        importlib.import_module(f"benchmark.configs.{cell['config']}")
        traffic_path = os.path.join(HERE, "traffic",
                                    cell["traffic"] + ".json")
        check(os.path.isfile(traffic_path), f"{cell['name']}: traffic file")
        with open(traffic_path) as fh:
            job = json.load(fh)["job"]
        check(hasattr(importlib.import_module(f"benchmark.jobs.{job}"),
                      "run"), f"{cell['name']}: job kind {job!r}")
    for metric in spec["end_to_end"] + spec["per_layer"]:
        check(set(metric.get("workloads", cells)) <= cells,
              f"{metric['name']}: its cells exist")
    for metric in spec["per_layer"]:
        reader = importlib.import_module(
            f"benchmark.layer_metrics.{metric['name']}")
        check(callable(getattr(reader, "read", None))
              and metric["moves"] in end_to_end,
              f"{metric['name']}: reader, and moves an end-to-end metric")


def check_trace() -> None:
    from benchmark.trace import reduce as trace_reduce
    with open(os.path.join(HERE, "trace", "recorded_v5e.json")) as fh:
        recorded = json.load(fh)
    planes, pins = recorded["planes"], recorded["pinned"]
    # host spans as the harness passes them: monotonic seconds, the marker's
    # start being mono_start
    summary = trace_reduce.reduce(planes, "bench.traced_window",
                                  [tuple(s) for s in recorded["host_spans"]],
                                  recorded["mono_start"])
    check(abs(summary["window_s"] - pins["window_s"]) < 1e-12, "window")
    for got, want in zip(summary["devices"], pins["devices"]):
        check(got["device"] == want["device"]
              and abs(got["busy_s"] - want["busy_s"]) < 1e-12,
              f"busy seconds of device {want['device']}")
        check(0.0 <= got["busy_s"] <= summary["window_s"],
              f"device {want['device']} is busy for at most the window")
    share = trace_reduce.idle_share(summary)
    check(abs(share - pins["idle_share"]) < 1e-9 and 0.0 <= share <= 1.0,
          f"idle share {share:.6f} pinned and within [0, 1]")
    check([op[0] for op in summary["top_ops"]] == pins["top_ops"],
          "top operations by self time")
    check([[name, runs] for name, _, runs in summary["programs"]]
          == pins["programs"], "programs by time, and their runs")
    for plane in planes:
        for line in plane["lines"]:
            if line["name"] != trace_reduce.OP_LINE:
                continue
            busy = trace_reduce.union([(s, s + d)
                                       for _, s, d in line["events"]])
            check(sum(trace_reduce.self_times(line["events"]).values())
                  == sum(e - s for s, e in busy),
                  f"{plane['name']}: self times add up to the union")
    check([g[0] for g in summary["idle_gaps"]] == pins["idle_gaps"],
          "idle gaps named after the covering host span")
    # the arithmetic the package's reduction uses, for contrast: every event
    # of every lane summed (here only the part of each inside the window)
    _, w0, length = planes[0]["lines"][0]["events"][0]
    naive = sum(max(0, min(s + d, w0 + length) - max(s, w0))
                for p in planes if p["name"].startswith("/device:")
                for line in p["lines"] for _, s, d in line["events"]) / 1e9
    devices = sum(p["name"].startswith("/device:") for p in planes)
    print(f"      (summing every lane, as utils/profiling does, would read "
          f"{100 * naive / devices / summary['window_s']:.1f} % busy)")


def check_references() -> None:
    os.environ["JAX_PLATFORMS"] = "cpu"
    import numpy as np

    from benchmark.reference.folds_plain import stratified_folds
    from benchmark.reference.gbt_plain import PlainGBT
    from benchmark.reference.metrics_plain import aupr, log_loss
    from transmogrifai_tpu.models import GBTClassifier
    # points (recall, precision): (0, 1) in front, then (.5, 1), (.5, .5),
    # (1, 2/3), (1, .5); trapezoids .5 * 1 + .5 * (.5 + 2/3) / 2
    check(abs(aupr([1, 0, 1, 0], [0.9, 0.8, 0.7, 0.1])
              - (0.5 + 0.5 * (0.5 + 2 / 3) / 2)) < 1e-12,
          "aupr on a case worked by hand")
    check(abs(aupr([1, 0], [0.5, 0.5]) - 0.5) < 1e-12, "aupr with a tie")
    check(abs(log_loss([1, 0], [0.8, 0.4])
              + 0.5 * (np.log(0.8) + np.log(0.6))) < 1e-12, "log_loss")
    rng = np.random.default_rng(7)

    def table(n):
        x_num = rng.normal(size=(n, 6))
        x_bin = (rng.uniform(size=(n, 10)) < 0.15).astype(float)
        logit = x_num[:, 0] + x_bin[:, :3].sum(axis=1) - 0.5
        y = (logit + 0.5 * rng.logistic(size=n) > 0).astype(np.float32)
        return np.concatenate([x_num, x_bin], axis=1).astype(np.float32), y

    (X, y), (Xh, yh) = table(4096), table(4096)
    params = dict(num_rounds=8, max_depth=4, max_bins=32)
    model = GBTClassifier(**params).fit_arrays(X, y)
    system = log_loss(yh, model.raw_to_probability(
        model.predict_raw(Xh))[:, 1])
    plain = log_loss(yh, PlainGBT(**params).fit(X, y).predict_proba(Xh))
    fewer = log_loss(yh, PlainGBT(**dict(params, num_rounds=7)).fit(
        X, y).predict_proba(Xh))
    check(abs(system - plain) < 1e-4,
          f"GBTClassifier {system:.6f} vs plain reference {plain:.6f}")
    check(abs(fewer - plain) > 10 * abs(system - plain),
          f"a dropped round shows ({fewer:.6f})")

    # a fold as the selector trains it: the whole table under a mask, deep
    # enough for the cap on a level's nodes to bind
    (X, y) = table(8192)
    fold = stratified_folds(y, 3, 11)
    sizes = [[int(np.sum((fold == f) & (y == c))) for f in range(3)]
             for c in (0, 1)]
    check(all(len(set(row)) == 1 for row in sizes)
          and int(np.sum(fold < 0)) < 2 * 3,
          f"folds are equal and stratified ({sizes})")
    mask = (fold >= 0) & (fold != 1)
    deep = dict(num_rounds=8, max_depth=11, max_bins=32, min_child_weight=0.25)
    model = GBTClassifier(**deep).fit_fold_grid_arrays(
        X, y, mask[None, :].astype(float), [{}])[0][0]
    system = log_loss(yh, model.raw_to_probability(
        model.predict_raw(Xh))[:, 1])

    def plain_loss(rows=slice(None), **more) -> float:
        return log_loss(yh, PlainGBT(**deep, **more).fit(
            X[rows], y[rows], mask=mask[rows]).predict_proba(Xh))

    plain = plain_loss()
    check(abs(system - plain) < 2e-4,
          f"masked depth-11 fold: fit_fold_grid_arrays {system:.6f} vs "
          f"plain reference {plain:.6f}")
    check(abs(plain_loss(node_cap=1 << 20) - plain) > 10 * abs(system - plain),
          "a reference without the cap on a level's nodes shows")
    check(abs(plain_loss(rows=mask) - plain) > 10 * abs(system - plain),
          "a reference that drops the masked rows instead shows")


def main() -> int:
    sys.path.insert(0, ROOT)
    check_spec()
    check_trace()
    check_references()
    print("selfcheck: " + ("all checks held" if not FAILED
                           else f"{len(FAILED)} FAILED"))
    return 1 if FAILED else 0


if __name__ == "__main__":
    sys.exit(main())
